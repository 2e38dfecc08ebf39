"""vperm: static E-element permutations from lane-local primitives.

The sparse-GLM hot loop needs exactly one data-DEPENDENT movement per
direction — the static row-order ↔ feature-order exchange of the entry
stream — and XLA lowers it as a random gather or scatter.  This module
routes that exchange through lane-local Pallas gathers and strided XLA
transposes instead.

STATUS ON THE v5e (jax 0.9.0 / libtpu 0.0.34, PR 21 chip run): the chunk
kernel does NOT lower.  Its middle stage is a ``take_along_axis`` along a
CH-wide lane axis of the transposed ``[128, CH]`` chunk, and Mosaic
refuses any CH over one vreg: "Not implemented: Multiple source vregs
along gather dimension" (CH = 256, 2048, 4096 all refused; CH = 8 and the
lane-only middle pass compile and match).  Any exchange over 2^14
entries has CH > 128 (production geometries use 2048 or 4096), so the
``xchg`` kernel runs in interpret mode only; it is an
explicit opt-in (``PHOTON_SPARSE_GRAD=xchg``), never an auto candidate,
and on a TPU that opt-in fails at compile.  Whether it is rebuilt around
one-vreg gathers or deleted is ROADMAP D3.

Decomposition (two-level Clos, all stages static, routed on host):

    y = x[perm]  over a padded domain  N = NC × CS,  CS = CH×128

      chunk stage R1   — arbitrary perm within each CS-element chunk,
                         itself a fused 5-stage in-VMEM micro-Clos
                         (lane-gather / VMEM transpose / wide row-gather
                         / VMEM transpose / lane-gather), one pallas
                         pass over HBM
      transpose        — [NC, CS] → [CS, NC] (XLA, strided, fast)
      lane stage  C    — per-column NC-perms of the transposed view,
                         lane-packed into [total/128, 128] tiles
                         (NC is a power of two ≤ 128, so 128/NC logical
                         rows pack per vreg row), one pallas pass
      transpose back   — [CS, NC] → [NC, CS]
      chunk stage R2   — as R1

CH adapts (2048 or 4096 sublane-rows) so domains up to 2^26 elements
route with NC ≤ 128.  Rectangular use (source and destination streams
of different lengths, e.g. row-major entries → padded layout slots) is
supported by a full-domain bijection: ``n_in`` real sources pad with
zeros, ``n_out`` real destinations slice off the front.

Host routing is three levels of bipartite edge-coloring (Slepian–Duguid
route construction, native/src/clos_route.cpp): one macro coloring on
the [NC, CS] grid and two micro colorings per chunk on [CH, 128].
Routing is one-time per dataset layout (the permutation is static data
layout, not step data) and is carried as int8/int16 index planes so the
per-step routing read is ~5 bytes/element.

The reference has no analog: its Spark shuffle IS a dynamic random
exchange (SURVEY.md §2.6).  This module is the TPU-native re-design
that makes the same data movement run at sequential-stream speeds.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import tree_util

from photon_tpu.ops.clos import route_permutation
from photon_tpu.utils.device import pallas_interpret

Array = jax.Array

LANES = 128
SUBLANES_PAD = 8             # f32 sublane tile: chunk heights pad to it
CH_SMALL = 2048              # chunk sublane-rows (1 MB f32 chunks)
CH_LARGE = 4096              # for domains past 128 small chunks
MAX_N = 128 * CH_LARGE * LANES   # 2^26: lane stage holds NC <= 128


@dataclasses.dataclass(frozen=True)
class VpermRoute:
    """Device-ready routing for one static bijection over ``total``
    padded elements, applied as ``y[:n_out] = x_padded[perm][:n_out]``
    with ``x`` of length ``n_in``.  Index planes are stored narrow
    (int8/int16) and upcast in-kernel; shapes are static per layout.

    ``i1/i3`` and ``i4/i6``: [NC*CH, 128] int8 lane indices for the two
    chunk stages' outer lane-gathers.  ``i2``/``i5``: [NC*128, CH] int16
    wide row-gather indices on the transposed [128, CH] chunk view.
    ``c``: [total/128, 128] int8 lane-packed middle-stage indices
    (``None`` when NC == 1 and the middle stage is the identity, in
    which case R2 is skipped too).
    """

    n_in: int
    n_out: int
    nc: int
    ch: int
    i1: jnp.ndarray
    i2: jnp.ndarray
    i3: jnp.ndarray
    c: object
    i4: object
    i5: object
    i6: object

    @property
    def cs(self) -> int:
        return self.ch * LANES

    @property
    def total(self) -> int:
        return self.nc * self.cs


tree_util.register_dataclass(
    VpermRoute,
    data_fields=("i1", "i2", "i3", "c", "i4", "i5", "i6"),
    meta_fields=("n_in", "n_out", "nc", "ch"),
)


def route_threads() -> int:
    """Worker count for per-chunk route colorings (PHOTON_ROUTE_THREADS,
    default: host cores capped at 8 — the walk is memory-bound past
    that).  The native edge coloring releases the GIL (ctypes) and is
    reentrant (stack-local scratch), so chunks color concurrently."""
    import os

    from photon_tpu.utils.env import env_int

    return env_int(
        "PHOTON_ROUTE_THREADS", min(os.cpu_count() or 1, 8), minimum=1
    )


def _chunk_stage_arrays(rows: np.ndarray, ch: int):
    """Factor per-chunk CS-perms into the 5-stage micro-Clos planes.

    ``rows`` is [NC, CS] int64: row i is the permutation applied within
    chunk i (y_chunk = x_chunk[rows[i]]).  Returns (i1 [NC*CH, 128] int8,
    i2 [NC*128, CH] int16, i3 [NC*CH, 128] int8).

    The per-chunk colorings are independent and GIL-releasing, so they
    run on a thread pool (:func:`route_threads`) — the measured
    profile at E=2^23 is ~60% native edge-coloring walk, so on an
    8-core host the build drops accordingly (tools/probe_route_scaling
    carries the numbers).
    """
    nc = rows.shape[0]
    i1 = np.empty((nc * ch, LANES), np.int8)
    i2 = np.empty((nc * LANES, ch), np.int16)
    i3 = np.empty((nc * ch, LANES), np.int8)

    def one(i: int) -> None:
        r = route_permutation(rows[i], a=ch, b=LANES, device=False)
        # clos stage semantics (apply_clos_grid): lane-gather by p1 on
        # [CH,128], transpose, row-gather by p2 on [128,CH], transpose,
        # lane-gather by p3.
        i1[i * ch:(i + 1) * ch] = r.p1.astype(np.int8)
        i2[i * LANES:(i + 1) * LANES] = r.p2.astype(np.int16)
        i3[i * ch:(i + 1) * ch] = r.p3.astype(np.int8)

    from photon_tpu.utils.io_pool import in_pool_worker, map_ordered

    workers = min(route_threads(), nc)
    if in_pool_worker():
        # Already on an io_pool worker (e.g. a streamed chunk attach):
        # nesting a second pool would oversubscribe cores on a walk
        # that is cache-pressure-bound — thread at one level.
        workers = 1
    # list(): drain, surfacing the first worker exception in order.
    list(map_ordered(one, range(nc), workers=workers))
    return i1, i2, i3


def _pack_middle(cidx: np.ndarray, nc: int) -> np.ndarray:
    """Lane-pack the [CS, NC] per-row middle perms into [total/128, 128].

    NC divides 128, so each vreg row holds 128/NC whole logical rows;
    the packed lane index for flat position p*128+l is
    ``(l//NC)*NC + cidx[s, l%NC]`` with ``s = (p*128+l)//NC`` — still a
    within-128-lane gather.
    """
    cs = cidx.shape[0]
    total = cs * nc
    flat = np.arange(total, dtype=np.int64)
    s = flat // nc
    c = flat % nc
    packed = ((flat % 128) // nc * nc + cidx[s, c]).astype(np.int8)
    return packed.reshape(total // LANES, LANES)


def pick_geometry(need: int) -> tuple[int, int]:
    """(ch, nc) covering ``need`` elements: the smaller chunk height when
    it fits in 128 chunks, NC a power of two so it divides 128."""
    if need > MAX_N:
        raise ValueError(
            f"vperm supports up to {MAX_N:,} elements single-device "
            f"(got {need:,}); shard the layout across devices first"
        )
    ch = CH_SMALL if need <= 128 * CH_SMALL * LANES else CH_LARGE
    nc = max(1, -(-need // (ch * LANES)))
    if nc & (nc - 1):
        nc = 1 << nc.bit_length()
    return ch, nc


def route_vperm_full(perm: np.ndarray, n_in: int, n_out: int,
                     ch: int) -> VpermRoute:
    """Route a FULL-domain bijection (``len(perm)`` = NC×CS exactly).

    ``perm[d]`` is the padded-source index feeding padded-destination
    ``d``; callers guarantee destinations below ``n_out`` read real
    sources and pad destinations read pad (zero) sources.
    """
    perm = np.ascontiguousarray(perm, dtype=np.int64)
    total = perm.size
    cs = ch * LANES
    nc = total // cs
    if nc * cs != total or (nc & (nc - 1)) or nc > 128:
        raise ValueError(f"total {total} is not a valid NC*CS geometry")
    if perm.size and (
        perm.min() < 0 or perm.max() >= total
        or np.bincount(perm, minlength=total).max() != 1
    ):
        raise ValueError("perm is not a permutation of [0, total)")

    if nc == 1:
        i1, i2, i3 = _chunk_stage_arrays(perm[None, :], ch)
        c = i4 = i5 = i6 = None
    else:
        r = route_permutation(perm, a=nc, b=cs, device=False)
        i1, i2, i3 = _chunk_stage_arrays(r.p1.astype(np.int64), ch)
        c = jnp.asarray(_pack_middle(r.p2.astype(np.int64), nc))
        i4, i5, i6 = (
            jnp.asarray(p)
            for p in _chunk_stage_arrays(r.p3.astype(np.int64), ch)
        )

    return VpermRoute(
        n_in=n_in, n_out=n_out, nc=nc, ch=ch,
        i1=jnp.asarray(i1), i2=jnp.asarray(i2), i3=jnp.asarray(i3),
        c=c, i4=i4, i5=i5, i6=i6,
    )


def route_vperm(perm: np.ndarray) -> VpermRoute:
    """Route ``y = x[perm]`` (square n-element permutation, n ≤ MAX_N).

    The domain pads to whole chunks; pad slots map identically so padded
    inputs carry zeros through untouched.
    """
    perm = np.ascontiguousarray(perm, dtype=np.int64)
    n = perm.size
    if n and (perm.min() < 0 or perm.max() >= n
              or np.bincount(perm, minlength=n).max() != 1):
        raise ValueError("perm is not a permutation of [0, n)")
    ch, nc = pick_geometry(n)
    total = nc * ch * LANES
    full = np.arange(total, dtype=np.int64)
    full[:n] = perm
    return route_vperm_full(full, n, n, ch)


def full_bijection(dest_src: np.ndarray, n_sources: int,
                   total: int) -> np.ndarray:
    """Extend an injective dest→source map to a full-domain bijection.

    ``dest_src[d]`` is the real source for destination ``d`` (< 0 for
    pad destinations).  Real sources live in [0, n_sources); the unused
    sources (real pads plus the [n_sources, total) tail) fill the pad
    destinations and the tail in ascending order — they only ever carry
    zeros.  Shared by ops/benes (grid domains) and the xchg route.
    """
    n_dest = dest_src.size
    if n_dest > total or n_sources > total:
        raise ValueError("total smaller than the streams it must cover")
    perm = np.empty(total, dtype=np.int64)
    real = dest_src >= 0
    perm[:n_dest][real] = dest_src[real]
    used = np.zeros(total, dtype=bool)
    used[dest_src[real]] = True
    unused = np.flatnonzero(~used)
    n_pad_dest = int((~real).sum()) + (total - n_dest)
    if unused.size != n_pad_dest:
        raise ValueError("dest_src is not injective into the source stream")
    perm[:n_dest][~real] = unused[: int((~real).sum())]
    perm[n_dest:] = unused[int((~real).sum()):]
    return perm


def _micro_clos_body(y, i1_ref, i2_ref, i3_ref):
    """The 5-stage micro-Clos array math, shared by every chunk kernel
    variant (plain, dz-expanding) so the stage sequence can never
    desynchronize between them."""
    y = jnp.take_along_axis(y, i1_ref[...].astype(jnp.int32), axis=1)
    y = y.T  # [128, CH] in VMEM
    y = jnp.take_along_axis(y, i2_ref[...].astype(jnp.int32), axis=1)
    y = y.T
    return jnp.take_along_axis(y, i3_ref[...].astype(jnp.int32), axis=1)


def _chunk_kernel(x_ref, i1_ref, i2_ref, i3_ref, o_ref):
    """Fused 5-stage micro-Clos over one [CH, 128] chunk in VMEM."""
    o_ref[...] = _micro_clos_body(x_ref[...], i1_ref, i2_ref, i3_ref)


def _lane_kernel(x_ref, c_ref, o_ref):
    o_ref[...] = jnp.take_along_axis(
        x_ref[...], c_ref[...].astype(jnp.int32), axis=1
    )


def _chunk_pass(x2d: Array, i1: Array, i2: Array, i3: Array, nc: int,
                ch: int, interpret: bool) -> Array:
    from jax.experimental import pallas as pl

    return pl.pallas_call(
        _chunk_kernel,
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        grid=(nc,),
        in_specs=[
            pl.BlockSpec((ch, LANES), lambda i: (i, 0)),
            pl.BlockSpec((ch, LANES), lambda i: (i, 0)),
            pl.BlockSpec((LANES, ch), lambda i: (i, 0)),
            pl.BlockSpec((ch, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ch, LANES), lambda i: (i, 0)),
        interpret=interpret,
    )(x2d, i1, i2, i3)


def _lane_pass(x2d: Array, c: Array, ch: int, interpret: bool) -> Array:
    from jax.experimental import pallas as pl

    n_tiles = x2d.shape[0] // ch
    return pl.pallas_call(
        _lane_kernel,
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((ch, LANES), lambda i: (i, 0)),
            pl.BlockSpec((ch, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ch, LANES), lambda i: (i, 0)),
        interpret=interpret,
    )(x2d, c)


@functools.partial(jax.jit, static_argnames=("interpret",))
def apply_vperm(x: Array, route: VpermRoute,
                interpret: bool = False) -> Array:
    """Apply the routed bijection to a flat [n_in] array → flat [n_out].

    Pipeline: chunk pass R1 → transpose [NC,CS]→[CS,NC] → lane-packed
    middle pass → transpose back → chunk pass R2.  Three pallas passes
    plus two XLA transposes, no data-dependent XLA ops.  NC == 1 runs
    the single chunk pass only.
    """
    nc, ch, cs, total = route.nc, route.ch, route.cs, route.total
    if x.shape[0] != route.n_in:
        raise ValueError(f"length {x.shape[0]} != routed n_in {route.n_in}")
    dtype = x.dtype
    if total > route.n_in:
        x = jnp.concatenate([x, jnp.zeros(total - route.n_in, dtype)])
    g = x.reshape(nc * ch, LANES)
    g = _chunk_pass(g, route.i1, route.i2, route.i3, nc, ch, interpret)
    if nc > 1:
        # [NC, CS] -> [CS, NC]: per-column NC-perms become lane-local
        # once packed; flat row-major order of the [CS, NC] view is the
        # packed [total/128, 128] layout _pack_middle indexed.
        t = g.reshape(nc, cs).T.reshape(nc * ch, LANES)
        t = _lane_pass(t, route.c, ch, interpret)
        g = t.reshape(cs, nc).T.reshape(nc * ch, LANES)
        g = _chunk_pass(g, route.i4, route.i5, route.i6, nc, ch, interpret)
    return g.reshape(total)[:route.n_out]


def invert_vperm(route: VpermRoute) -> VpermRoute:
    """The inverse bijection's route from the same routing (no second
    edge-coloring): run the pipeline backwards with each stage's rows
    inverted row-wise.  A chunk stage applies (i1, T, i2, T, i3); its
    inverse applies (inv i3, T, inv i2, T, inv i1) — the same kernel
    shape — and the middle lane stage inverts row-wise (each packed row
    is a 128-perm, so argsort per row is its inverse).  ``n_in`` and
    ``n_out`` swap."""

    def inv_rows(p):
        return jnp.argsort(p.astype(jnp.int32), axis=1).astype(p.dtype)

    if route.nc == 1:
        return VpermRoute(
            n_in=route.n_out, n_out=route.n_in, nc=1, ch=route.ch,
            i1=inv_rows(route.i3), i2=inv_rows(route.i2),
            i3=inv_rows(route.i1),
            c=None, i4=None, i5=None, i6=None,
        )
    return VpermRoute(
        n_in=route.n_out, n_out=route.n_in, nc=route.nc, ch=route.ch,
        i1=inv_rows(route.i6), i2=inv_rows(route.i5),
        i3=inv_rows(route.i4),
        c=inv_rows(route.c),
        i4=inv_rows(route.i3), i5=inv_rows(route.i2),
        i6=inv_rows(route.i1),
    )


def apply_vperm_reference(x: np.ndarray, perm: np.ndarray) -> np.ndarray:
    """NumPy oracle for tests."""
    return np.asarray(x)[np.asarray(perm)]


# -- the xchg production routes ---------------------------------------------

@dataclasses.dataclass(frozen=True)
class XchgAux:
    """Batch-attached exchange routing for the `xchg` kernel.

    ``route`` permutes the row-major per-entry product stream into the
    reduce-side order.  Two reduce strategies (PHOTON_XCHG_REDUCE):

    - ``aligned`` — destination is the slab-aligned slot stream; the
      reduce is ops/pallas_gather.aligned_reduce (``bounds`` is None).
    - ``cumsum`` — destination is the COMPACT feature-sorted stream
      (exactly n*k entries: zero NC padding when n*k is a chunk
      multiple); the reduce is an f32 cumsum + one [d+1] boundary
      gather (``g[f] = ps[bounds[f+1]] - ps[bounds[f]]``).  Cheaper
      data movement, at f32 prefix-sum precision — the auto probe's
      correctness gate arbitrates.

    ``vals_dest`` (cumsum mode, when the attach provides vals): the
    STATIC value stream pre-permuted to the destination order, so each
    step moves only the dz expansion and the value multiply happens at
    the destination, fused into the prefix scan — one fewer E-stream
    read per evaluation.
    """

    route: VpermRoute
    bounds: object = None  # [dim+1] int32 device array for cumsum mode
    vals_dest: object = None  # [total] f32, pre-permuted static values
    # Fingerprint of the row-major value stream the bake saw — the
    # strided f32 SAMPLE itself (elementwise-comparable: a collapsed
    # scalar like an L1 norm is permutation-invariant and would miss
    # value swaps), carried as a DATA leaf so re-attaching with new
    # values never changes the treedef (a meta fp would force a full
    # jit recompile per re-attach).  Lets eager callers that pass
    # DIFFERENT values be rejected instead of silently reading the
    # stale baked stream (ADVICE r4).  None until vals are baked.
    vals_fp: object = None


# Sample cap for the fingerprint: bounds the guard's host cost to O(1)
# per eager call.  The sample is STRIDED across the whole stream (not a
# prefix) so a re-weighting confined to a later region of a large
# stream still moves the fingerprint.
_VALS_FP_SAMPLES = 65536


tree_util.register_dataclass(
    XchgAux, data_fields=("route", "bounds", "vals_dest", "vals_fp"),
    meta_fields=(),
)


def build_xchg_route(layout, n: int, k: int) -> VpermRoute:
    """Route the row-major entry stream into aligned-layout slot order.

    ``layout`` is the host ops/pallas_gather.AlignedLayout (must carry
    ``src``).  The returned route feeds ops/pallas_gather.aligned_reduce:
    ``apply_vperm(products_rowmajor, route)`` is the slot stream, with
    pad slots carrying zeros.  This replaces the per-step E-element XLA
    ``per_row[rows]`` gather with the 3-pass vperm pipeline.
    """
    n_rm = n * k
    slots_src = layout.src.reshape(-1)
    n_slots = int(slots_src.size)
    ch, nc = pick_geometry(max(n_rm, n_slots))
    total = nc * ch * LANES
    perm = full_bijection(slots_src, n_rm, total)
    return route_vperm_full(perm, n_rm, n_slots, ch)


def build_xchg_sorted_route(ids: np.ndarray, dim: int,
                            order: np.ndarray | None = None) -> XchgAux:
    """Route row-major entries into the COMPACT feature-sorted stream.

    ``ids`` is the batch's [n, k] padded id array (pads carry id 0 and
    val 0 — they land inside feature 0's segment and contribute zero,
    exactly as in the fm segment-sum).  The destination has the same
    length as the source, so the permutation is square and the only
    padding is the chunk-multiple tail.  ``order`` is the stable argsort
    of the flat id stream when the caller already computed it (the fm
    aux build does — no second O(E log E) host sort).
    """
    flat = ids.reshape(-1).astype(np.int64)
    if order is None:
        order = np.argsort(flat, kind="stable")  # dest i <- rm order[i]
    else:
        order = np.ascontiguousarray(order, dtype=np.int64)
    n_rm = flat.size
    ch, nc = pick_geometry(n_rm)
    total = nc * ch * LANES
    perm = np.arange(total, dtype=np.int64)
    perm[:n_rm] = order
    if total > n_rm:
        # Tail destinations must read tail (zero-pad) sources: order is
        # already a bijection on [0, n_rm), identity on the tail.
        perm[n_rm:] = np.arange(n_rm, total, dtype=np.int64)
    route = route_vperm_full(perm, n_rm, n_rm, ch)
    bounds = np.searchsorted(
        flat[order], np.arange(dim + 1, dtype=np.int64)
    ).astype(np.int32)
    return XchgAux(route=route, bounds=jnp.asarray(bounds))


@dataclasses.dataclass(frozen=True)
class BalancedRoute:
    """Coloring-free exchange into the feature-sorted stream.

    The sorted destination gives total placement freedom for pad slots
    (zeros are harmless anywhere under a prefix-sum reduce), so the
    macro stage needs no edge-coloring: dest window j draws its
    entries from source window i in a fixed-size [NC, NC, B] block grid
    and the exchange is one XLA block transpose between two chunk-local
    passes.  B is the max per-(i, j) count plus padding — near E/NC²
    for any data whose sorted stream mixes source positions (uniform
    AND zipf do; a pre-sorted pathological dataset would not, and the
    builder falls back to the colored route).

    ``a1/a2/a3``: stage-A micro-Clos planes ([NC*CH,128] int8,
    [NC*128,CH] int16, [NC*CH,128] int8); ``b1/b2/b3``: stage B.
    ``n_in`` real sources; ``cs_win`` raw rm entries per source window
    (each physical chunk = one window front-packed plus pad tail); the
    flat output length is NC*CS.
    """

    n_in: int
    n_out: int      # real destination-stream length (repack slice)
    nc: int
    ch: int
    blk: int
    cs_win: int
    ds_win: int     # real dest entries per chunk front
    k_expand: int   # k when the in-kernel dz expansion applies, else 0
    a1: jnp.ndarray
    a2: jnp.ndarray
    a3: jnp.ndarray
    b1: jnp.ndarray
    b2: jnp.ndarray
    b3: jnp.ndarray

    @property
    def cs(self) -> int:
        return self.ch * LANES

    @property
    def total(self) -> int:
        return self.nc * self.cs


tree_util.register_dataclass(
    BalancedRoute,
    data_fields=("a1", "a2", "a3", "b1", "b2", "b3"),
    meta_fields=(
        "n_in", "n_out", "nc", "ch", "blk", "cs_win", "ds_win", "k_expand",
    ),
)


def _complete_chunk_local(dest_src: np.ndarray, nc: int,
                          cs: int) -> np.ndarray:
    """Fill pad destinations (< 0) with each CHUNK's own unused sources
    (ascending), so every row of the resulting [nc, cs] perm is a
    within-chunk permutation.  Feasible because real slots and real
    sources tally per chunk by construction."""
    grid = dest_src.reshape(nc, cs)
    out = grid % cs  # real slots: chunk-local source offset
    for i in range(nc):
        row = grid[i]
        real = row >= 0
        used = np.zeros(cs, bool)
        used[row[real] % cs] = True
        out[i, ~real] = np.flatnonzero(~used)
    return out


def _balanced_windows(dest_src: np.ndarray, n_src_stream: int, k: int):
    """Window partition + per-(src, dest)-window block census of the
    balanced exchange: ``(nc, cs_win, ds_win, k_expand, d_real, src_of,
    src_win, dest_win, blk)`` or None when the streams exceed geometry
    limits.  Split out of :func:`_build_balanced_core` so a SHARDED
    attach can census every shard's natural ``blk`` first and rebuild
    all shards with the shared maximum (uniform route geometry is what
    lets per-shard routes stack into one shard_map pytree).  Everything
    here except ``blk`` (and the data-dependent index arrays) is a
    function of (n_src_stream, n_dest, k) alone — identical across
    equal-shaped shards by construction."""
    n_dest = dest_src.size
    d_real = np.flatnonzero(dest_src >= 0)
    src_of = dest_src[d_real]
    e = d_real.size
    if max(n_src_stream, n_dest) > MAX_N:
        return None
    if e and (src_of.min() < 0 or src_of.max() >= n_src_stream):
        return None
    nc = min(
        128,
        max(1, -(-max(n_src_stream, n_dest) // (CH_SMALL * LANES))),
    )
    ds_win = -(-n_dest // nc)  # dest window j = dests [j*ds_win, ...)
    dest_win = np.minimum(d_real // ds_win, nc - 1)

    # Source windows are cs_win RAW rm entries; each physical chunk is
    # one window front-packed plus a pad tail (apply_balanced inserts
    # the tails with one fused XLA pad), so the window partition does
    # not depend on the block-derived chunk size.  When k divides 128,
    # round the window to whole rows so chunk boundaries never split a
    # row — then the in-kernel dz expansion (apply_balanced_dz) can
    # rebuild the row-major stream from a [ch, 128/k] dz tile and the
    # per-step E-stream materialization disappears.
    k_expand = k if (k and LANES % k == 0) else 0
    cs_base = -(-n_src_stream // nc)
    if k_expand:
        cs_win = k * (-(-cs_base // k))
    else:
        cs_win = cs_base
    src_win = np.minimum(src_of // cs_win, nc - 1)
    counts = np.bincount(
        src_win * nc + dest_win, minlength=nc * nc
    ).reshape(nc, nc)
    blk = int(counts.max())
    return nc, cs_win, ds_win, k_expand, d_real, src_of, src_win, dest_win, blk


def _build_balanced_core(dest_src: np.ndarray, n_src_stream: int, k: int,
                         blk_override: int | None = None):
    """Factor an exchange into the balanced form, for ANY destination
    stream that tolerates zero pads between real entries.

    ``dest_src[d]`` = source rm index feeding destination ``d`` (< 0
    for pad destinations; each source index appears at most once).
    ``n_src_stream`` is the FULL row-major stream length (n*k) — source
    windows partition the whole stream, since rm indices of real
    entries range over all of it.  ``blk_override`` forces a (>= natural)
    block capacity so equal-shaped shards share one geometry.  Returns a
    :class:`BalancedRoute` or None when the data defeats the balance
    assumption / geometry limits (caller falls back to the colored
    route).
    """
    n_dest = dest_src.size
    win = _balanced_windows(dest_src, n_src_stream, k)
    if win is None:
        return None
    nc, cs_win, ds_win, k_expand, d_real, src_of, src_win, dest_win, blk = win
    e = d_real.size
    cs_base = -(-n_src_stream // nc)
    if blk_override is not None:
        if blk_override < blk:
            raise ValueError(
                f"blk_override {blk_override} < this shard's natural "
                f"block census {blk}"
            )
        blk = blk_override
    # Quantum LANES * lcm(nc, 8): cs_pad/nc (the block stride) must be
    # whole, and ch = cs_pad/128 must be a multiple of 8 (the f32 sublane
    # tile) or Mosaic can reject the chunk kernel's block height when nc
    # is not a power of two (ADVICE r4).  Pads carry zeros.
    quantum = LANES * math.lcm(nc, SUBLANES_PAD)
    cs_pad = -(-max(nc * blk, cs_win, ds_win) // quantum) * quantum
    if nc > 1 and cs_pad > 2 * max(cs_base, ds_win):
        return None  # pathological source/dest correlation
    ch = cs_pad // LANES
    if ch > 8192:
        # VMEM ceiling for the fused chunk kernel (and headroom under
        # the int16 i2/b2 index planes' 32767 bound).
        return None
    blk_slots = cs_pad // nc
    total = nc * cs_pad

    # Stage-A slot of each entry: source chunk src_win, block dest_win,
    # position by destination order within the (src, dest) pair.  With
    # one chunk the transpose and stage B are skipped (apply's nc > 1
    # guard), so stage A must place entries at their FINAL positions —
    # mid == final, not the compacted block order (real destinations
    # can be sparse in the aligned slot stream).
    seq = np.arange(e, dtype=np.int64)
    if nc == 1:
        mid_slot = d_real.astype(np.int64)
    else:
        pair = src_win * nc + dest_win
        pair_order = np.argsort(pair, kind="stable")
        sizes = np.bincount(pair, minlength=nc * nc)
        starts = np.concatenate(([0], np.cumsum(sizes)))[:-1]
        rank_in_block = np.zeros(e, dtype=np.int64)
        rank_in_block[pair_order] = seq - np.repeat(starts, sizes)
        mid_slot = (
            src_win * cs_pad + dest_win * blk_slots + rank_in_block
        )

    # Stage A within-chunk perms (pads complete chunk-locally against
    # each chunk's own unused — zero-valued — sources).  Source
    # coordinates are in the PADDED stream: window-local offset is the
    # raw offset (windows front-pack their chunks).
    dest_src_a = np.full(total, -1, np.int64)
    dest_src_a[mid_slot] = src_win * cs_pad + (src_of % cs_win)
    rows_a = _complete_chunk_local(dest_src_a, nc, cs_pad)
    a1, a2, a3 = _chunk_stage_arrays(rows_a, ch)

    if nc == 1:
        # Stage B is skipped at apply time; identity planes keep the
        # dataclass/serialization shape uniform.
        ident = np.arange(cs_pad, dtype=np.int64)[None, :]
        b1, b2p, b3 = _chunk_stage_arrays(ident, ch)
    else:
        # Block transpose [nc, nc, blk_slots]:
        # (src, dest, b) -> (dest, src, b).
        post_t = (
            dest_win * cs_pad + src_win * blk_slots + rank_in_block
        )
        # Stage B: destination d front-packs into dest chunk dest_win.
        final = dest_win * cs_pad + (d_real - dest_win * ds_win)
        dest_src_b = np.full(total, -1, np.int64)
        dest_src_b[final] = post_t
        rows_b = _complete_chunk_local(dest_src_b, nc, cs_pad)
        b1, b2p, b3 = _chunk_stage_arrays(rows_b, ch)

    return BalancedRoute(
        n_in=n_src_stream, n_out=n_dest, nc=nc, ch=ch, blk=blk_slots,
        cs_win=cs_win, ds_win=ds_win, k_expand=k_expand,
        a1=jnp.asarray(a1), a2=jnp.asarray(a2), a3=jnp.asarray(a3),
        b1=jnp.asarray(b1), b2=jnp.asarray(b2p), b3=jnp.asarray(b3),
    )


def build_balanced_sorted_route(
    ids: np.ndarray, dim: int, order: np.ndarray | None = None,
    blk_override: int | None = None,
):
    """(BalancedRoute, bounds) for the rm → feature-sorted exchange, or
    None when the data defeats the balance assumption."""
    flat = ids.reshape(-1).astype(np.int64)
    k = int(ids.shape[-1]) if ids.ndim == 2 else 0
    e = flat.size
    if order is None:
        order = np.argsort(flat, kind="stable")
    else:
        order = np.ascontiguousarray(order, dtype=np.int64)
    route = _build_balanced_core(order, e, k, blk_override=blk_override)
    if route is None:
        return None
    bounds_rank = np.searchsorted(
        flat[order], np.arange(dim + 1, dtype=np.int64)
    )
    bw = np.minimum(bounds_rank // route.ds_win, route.nc - 1)
    bounds = (bw * route.cs + (bounds_rank - bw * route.ds_win))
    return route, jnp.asarray(bounds.astype(np.int32))


def build_balanced_aligned_route(layout, ids: np.ndarray,
                                 blk_override: int | None = None):
    """BalancedRoute for the rm → aligned-slot exchange (same balanced
    construction; the destination is the slab slot stream, whose pads
    carry zeros automatically because chunk-local completion pairs them
    with the zero-valued unused sources).  The applied stream repacks
    chunk fronts back into the contiguous slot array
    (see xchg_segment_grad).  None → colored fallback."""
    k = int(ids.shape[-1]) if ids.ndim == 2 else 0
    slots_src = np.ascontiguousarray(
        layout.src.reshape(-1), dtype=np.int64
    )
    return _build_balanced_core(slots_src, int(ids.size), k,
                                blk_override=blk_override)


def _chunk_expand_kernel(dz_ref, i1_ref, i2_ref, i3_ref, o_ref):
    """Stage A with the dz expansion fused: the [ch, 128/k] dz tile
    broadcasts to the row-major [ch, 128] stream in VMEM (static lane
    repeat), then the shared 5-stage micro-Clos body runs.  Pad-tail
    positions carry whatever dz value the repeat lands there — they
    flow into pad destinations whose vals_dest is zero."""
    k = LANES // dz_ref.shape[1]
    y = jnp.repeat(dz_ref[...], k, axis=1)
    o_ref[...] = _micro_clos_body(y, i1_ref, i2_ref, i3_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def apply_balanced_dz(dz: Array, route: BalancedRoute,
                      interpret: bool = False) -> Array:
    """The per-step exchange with the dz expansion fused into stage A:
    moves a [n] dz vector (4 MB at the bench shape) instead of a
    materialized E-stream.  Requires ``route.k_expand`` (k | 128 and
    row-aligned windows)."""
    from jax.experimental import pallas as pl

    nc, ch = route.nc, route.ch
    cs, cs_win, k = route.cs, route.cs_win, route.k_expand
    if not k:
        raise ValueError("route was built without k_expand")
    rows_win = cs_win // k
    if dz.shape[0] * k != route.n_in:
        raise ValueError(f"dz length {dz.shape[0]} != n_in/{k}")
    if nc * rows_win > dz.shape[0]:
        dz = jnp.concatenate(
            [dz, jnp.zeros(nc * rows_win - dz.shape[0], dz.dtype)]
        )
    dz2d = jnp.pad(
        dz.reshape(nc, rows_win), ((0, 0), (0, cs // k - rows_win))
    ).reshape(nc * ch, LANES // k)
    g = pl.pallas_call(
        _chunk_expand_kernel,
        out_shape=jax.ShapeDtypeStruct((nc * ch, LANES), dz.dtype),
        grid=(nc,),
        in_specs=[
            pl.BlockSpec((ch, LANES // k), lambda i: (i, 0)),
            pl.BlockSpec((ch, LANES), lambda i: (i, 0)),
            pl.BlockSpec((LANES, ch), lambda i: (i, 0)),
            pl.BlockSpec((ch, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ch, LANES), lambda i: (i, 0)),
        interpret=interpret,
    )(dz2d, route.a1, route.a2, route.a3)
    return _balanced_tail(g, route, interpret)


def _balanced_tail(g: Array, route: BalancedRoute,
                   interpret: bool) -> Array:
    """Block transpose + stage B, shared by both stage-A variants."""
    nc, ch, blk, total = route.nc, route.ch, route.blk, route.total
    if nc > 1:
        g = (
            g.reshape(nc, nc, blk)
            .transpose(1, 0, 2)
            .reshape(nc * ch, LANES)
        )
        g = _chunk_pass(g, route.b1, route.b2, route.b3, nc, ch, interpret)
    return g.reshape(total)


@functools.partial(jax.jit, static_argnames=("interpret",))
def apply_balanced(x: Array, route: BalancedRoute,
                   interpret: bool = False) -> Array:
    """rm stream [n_in] → padded sorted stream [total] (pads carry 0)."""
    nc, ch, blk, total = route.nc, route.ch, route.blk, route.total
    cs, cs_win = route.cs, route.cs_win
    if x.shape[0] != route.n_in:
        raise ValueError(f"length {x.shape[0]} != routed n_in {route.n_in}")
    # Each physical chunk = one cs_win-entry rm window front-packed plus
    # a zero tail (one fused XLA pad, no data-dependent movement).
    if nc * cs_win > route.n_in:
        x = jnp.concatenate(
            [x, jnp.zeros(nc * cs_win - route.n_in, x.dtype)]
        )
    g = jnp.pad(
        x.reshape(nc, cs_win), ((0, 0), (0, cs - cs_win))
    ).reshape(nc * ch, LANES)
    g = _chunk_pass(g, route.a1, route.a2, route.a3, nc, ch, interpret)
    # The balanced exchange is one strided XLA transpose, then stage B
    # packs each dest chunk into sorted front order.
    return _balanced_tail(g, route, interpret)


# Versioned PER MODE so bumping one builder doesn't invalidate the other
# mode's (expensive) cached routes.
_ROUTE_CACHE_VERSION = {"aligned": 2, "cumsum": 3}


def _route_cache_path(ids: np.ndarray, dim: int, mode: str, layout,
                      has_vals: bool, blk_override: int | None = None,
                      force_colored: bool = False):
    """Disk-cache path for a routed exchange, or None when disabled.

    Routes are pure functions of their inputs and cost tens of host-
    seconds at production scale (edge colorings); caching turns every
    re-run — warm bench passes, lambda sweeps, checkpoint restarts —
    into a file load.  The key hashes the [n, k] shape and ids bytes;
    aligned mode additionally hashes ``layout.src`` (the slot→source
    map), because the aligned layout drops val==0 entries — identical
    ids with different zero patterns yield different routes.
    ``has_vals`` enters the key because aligned-mode route KIND depends
    on it (balanced needs the destination value stream) — a vals-less
    caller must not pin the colored route for later vals-carrying ones.
    """
    import hashlib
    import os

    from photon_tpu.utils.caches import resolve_cache_dir

    root = resolve_cache_dir("PHOTON_ROUTE_CACHE", "")
    if root is None:
        return None
    h = hashlib.sha256()
    h.update(repr(ids.shape).encode())
    h.update(np.ascontiguousarray(ids).tobytes())
    if mode != "cumsum" and layout is not None:
        h.update(np.ascontiguousarray(layout.src).tobytes())
    ver = _ROUTE_CACHE_VERSION.get(mode, _ROUTE_CACHE_VERSION["aligned"])
    # vals-carrying keys stay in the canonical (unsuffixed) namespace so
    # the expensive production entries survive this key extension.
    # "novals2": round 5 made vals-less aligned builds produce BALANCED
    # routes (previously colored); the namespace change orphans the old
    # colored entries instead of silently serving the wrong variant,
    # while leaving the canonical namespace untouched.
    suffix = "" if has_vals else "|novals2"
    # Sharded-attach geometry levers change the route CONTENT for the
    # same ids, so they must enter the key; single-shard builds stay in
    # the canonical namespace.
    if blk_override is not None:
        suffix += f"|blk{blk_override}"
    if force_colored:
        suffix += "|colored"
    h.update(f"|{dim}|{mode}|v{ver}{suffix}".encode())
    return os.path.join(root, h.hexdigest()[:32] + ".npz")


def _aux_to_npz(aux: XchgAux) -> dict:
    out = {}
    r = aux.route
    if isinstance(r, BalancedRoute):
        out["kind"] = np.int64(2)
        out["meta"] = np.asarray(
            [r.n_in, r.n_out, r.nc, r.ch, r.blk, r.cs_win, r.ds_win,
             r.k_expand],
            np.int64,
        )
        for name in ("a1", "a2", "a3", "b1", "b2", "b3"):
            out[name] = np.asarray(getattr(r, name))
    else:
        out["kind"] = np.int64(1)
        out["meta"] = np.asarray(
            [r.n_in, r.n_out, r.nc, r.ch], np.int64
        )
        for name in ("i1", "i2", "i3", "c", "i4", "i5", "i6"):
            v = getattr(r, name)
            if v is not None:
                out[name] = np.asarray(v)
    if aux.bounds is not None:
        out["bounds"] = np.asarray(aux.bounds)
    return out


def _aux_from_npz(z) -> XchgAux:
    bounds = jnp.asarray(z["bounds"]) if "bounds" in z else None
    if int(z["kind"]) == 2:
        (n_in, n_out, nc, ch, blk, cs_win, ds_win, k_expand) = (
            int(v) for v in z["meta"]
        )
        route = BalancedRoute(
            n_in=n_in, n_out=n_out, nc=nc, ch=ch, blk=blk, cs_win=cs_win,
            ds_win=ds_win, k_expand=k_expand,
            a1=jnp.asarray(z["a1"]), a2=jnp.asarray(z["a2"]),
            a3=jnp.asarray(z["a3"]), b1=jnp.asarray(z["b1"]),
            b2=jnp.asarray(z["b2"]), b3=jnp.asarray(z["b3"]),
        )
    else:
        n_in, n_out, nc, ch = (int(v) for v in z["meta"])
        opt = {
            name: (jnp.asarray(z[name]) if name in z else None)
            for name in ("c", "i4", "i5", "i6")
        }
        route = VpermRoute(
            n_in=n_in, n_out=n_out, nc=nc, ch=ch,
            i1=jnp.asarray(z["i1"]), i2=jnp.asarray(z["i2"]),
            i3=jnp.asarray(z["i3"]), **opt,
        )
    return XchgAux(route=route, bounds=bounds)


def balanced_blk_census(dest_src: np.ndarray, n_src_stream: int,
                        k: int) -> int | None:
    """This shard's natural per-(src, dest)-window block census, or None
    when its streams exceed the balanced geometry limits.  A sharded
    attach runs this over every shard and rebuilds all of them with the
    shared maximum (``build_xchg_aux(blk_override=...)``) so the routes
    stack into one uniform-geometry pytree."""
    win = _balanced_windows(
        np.ascontiguousarray(dest_src, dtype=np.int64), n_src_stream, k
    )
    return None if win is None else win[-1]


def build_xchg_aux(layout, ids: np.ndarray, dim: int,
                   order: np.ndarray | None = None,
                   vals: np.ndarray | None = None,
                   blk_override: int | None = None,
                   force_colored: bool = False) -> XchgAux:
    """The attach/probe entry point: build the exchange aux for the
    reduce strategy selected by PHOTON_XCHG_REDUCE (aligned | cumsum).
    One builder so the auto-selection probe measures exactly the
    variant production batches carry; routes disk-cache by content
    hash (PHOTON_ROUTE_CACHE dir, "0" disables).  With ``vals``, the
    cumsum aux also carries the statically pre-permuted value stream
    (``vals_dest`` — one device pass at attach, never cached: the
    route itself is vals-independent).

    ``blk_override`` / ``force_colored`` are the sharded-attach levers
    (see :func:`balanced_blk_census`): every shard of one batch must
    come out with the same route KIND and geometry meta, or the stacked
    aux pytree would have mismatched treedefs."""
    import logging
    import os

    n, k = ids.shape
    mode = os.environ.get("PHOTON_XCHG_REDUCE", "aligned")
    path = _route_cache_path(
        np.asarray(ids), dim, mode, layout, vals is not None,
        blk_override=blk_override, force_colored=force_colored,
    )
    aux = None
    if path is not None and os.path.exists(path):
        try:
            with np.load(path) as z:
                aux = _aux_from_npz(z)
        except Exception as exc:  # noqa: BLE001 — corrupt cache = rebuild
            logging.getLogger("photon_tpu.vperm").warning(
                "route cache read failed (%s); rebuilding", exc
            )
        if (
            aux is not None
            and isinstance(aux.route, BalancedRoute)
            and aux.route.ch % math.lcm(aux.route.nc, SUBLANES_PAD)
        ):
            # Pre-round-5 caches could hold a chunk height indivisible
            # by the f32 sublane tile (the ADVICE-r4 Mosaic-rejection
            # geometry); rebuild rather than version-bump so valid
            # cached routes (nc a multiple of 8 — all production
            # shapes) survive.
            logging.getLogger("photon_tpu.vperm").warning(
                "cached route has a stale chunk geometry (ch=%d, nc=%d);"
                " rebuilding", aux.route.ch, aux.route.nc,
            )
            aux = None
    if aux is None:
        # Announce BEFORE the build, from the one place every caller
        # (auto-probe, production attach, tests) funnels through and
        # only on a real cache miss: the edge-coloring/factoring below
        # is tens of host-seconds at production size, and an
        # unexplained first-step stall was the ADVICE-r4 complaint.
        # WARNING level — on an unconfigured root logger INFO is
        # dropped by logging's lastResort handler.
        logging.getLogger("photon_tpu.vperm").warning(
            "building the xchg exchange route for %d entries (mode=%s) "
            "— one-time host work, disk-cached for reuse%s",
            ids.size, mode,
            "" if path is not None else
            " (caching DISABLED via PHOTON_ROUTE_CACHE=0)",
        )
        if mode == "cumsum":
            # The coloring-free balanced exchange when the data permits
            # it (any stream whose sorted order mixes source positions);
            # otherwise the general colored route.
            built = None if force_colored else build_balanced_sorted_route(
                np.asarray(ids), dim, order, blk_override=blk_override
            )
            if built is not None:
                route, bounds = built
                aux = XchgAux(route=route, bounds=bounds)
            else:
                aux = build_xchg_sorted_route(
                    np.asarray(ids), dim, order=order
                )
        else:
            # Aligned destination: the balanced exchange also applies
            # (slab slot pads pair with zero-valued unused sources —
            # zero-valued in the PRODUCT stream whether or not values
            # are baked, so the unbaked variant is equally valid);
            # otherwise the general colored route.
            built = (
                build_balanced_aligned_route(
                    layout, np.asarray(ids), blk_override=blk_override
                )
                if not force_colored else None
            )
            if built is not None:
                aux = XchgAux(route=built)
            else:
                aux = XchgAux(route=build_xchg_route(layout, n, k))
        if path is not None:
            try:
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                tmp = path + f".tmp{os.getpid()}"
                with open(tmp, "wb") as f:
                    np.savez(f, **_aux_to_npz(aux))
                os.replace(tmp, path)
            except Exception as exc:  # noqa: BLE001 — best-effort
                logging.getLogger("photon_tpu.vperm").warning(
                    "route cache write failed (%s)", exc
                )
    if vals is not None:
        aux = bake_vals_dest(aux, vals)
    return aux


def bake_vals_dest(aux: XchgAux, vals: np.ndarray) -> XchgAux:
    """Pre-permute the STATIC value stream to the destination order and
    attach it (plus its fingerprint) to the aux — one device pass, so
    each training step moves only the dz expansion and the value multiply
    happens at the destination.  Split out of :func:`build_xchg_aux` so
    callers that load a cached route (e.g. the streaming layout cache)
    can re-bake against freshly parsed values without rebuilding the
    route.  No-op for route kinds whose reduce reads row-major values
    directly (colored aligned)."""
    import os

    if not (aux.bounds is not None or isinstance(aux.route, BalancedRoute)):
        return aux
    interp = pallas_interpret()
    flat_np = np.asarray(vals, np.float32).reshape(-1)
    flat = jnp.asarray(flat_np)
    if isinstance(aux.route, BalancedRoute):
        vd = apply_balanced(flat, aux.route, interpret=interp)
    else:
        vd = apply_vperm(flat, aux.route, interpret=interp)
    if os.environ.get("PHOTON_XCHG_DTYPE", "float32") == "bfloat16":
        vd = vd.astype(jnp.bfloat16)
    fp = np.ascontiguousarray(
        flat_np[::_vals_fp_stride(flat_np.size)], np.float32
    )
    return dataclasses.replace(aux, vals_dest=vd, vals_fp=fp)


def _vals_fp_stride(size: int) -> int:
    """Stride that spreads ``_VALS_FP_SAMPLES`` samples over ``size``.
    Ceil division: with floor, sizes in (cap, ~3*cap) would stride 1-2
    and a cap-truncated sample would cover only a prefix, leaving a
    tail re-weighting invisible to the guard."""
    return max(1, -(-size // _VALS_FP_SAMPLES))


def _trace_state_clean() -> bool:
    """True when no trace is active (fully eager).  Private-API probe,
    permissive on failure in the SKIP direction (guard disabled, never
    a spurious error)."""
    try:
        from jax._src import core as _core

        return bool(_core.trace_state_clean())
    except Exception:  # noqa: BLE001
        return False


def xchg_segment_grad(per_row: Array, vals_rowmajor: Array, al,
                      aux: "XchgAux | VpermRoute", dim: int,
                      interpret: bool | None = None) -> Array:
    """``g[f] = sum_e per_row[row_e] * val_e`` — the xchg backward.

    Row-major products (a free broadcast-multiply) ride the vperm into
    the reduce-side order; the reduce is either the aligned
    position-reduce or the cumsum + boundary gather (see XchgAux).

    Contract: when ``aux.vals_dest`` is set, the values were baked into
    the aux at attach time and ``vals_rowmajor`` contributes only its
    shape — it must be the SAME value array the attach saw (true for
    every production caller: both read the batch's static vals).
    """
    from photon_tpu.ops.pallas_gather import aligned_reduce

    if interpret is None:
        interpret = pallas_interpret()
    import os

    if isinstance(aux, VpermRoute):  # back-compat: bare aligned route
        aux = XchgAux(route=aux)
    if (
        aux.vals_dest is not None
        and aux.vals_fp is not None
        and vals_rowmajor is not None
        and not isinstance(vals_rowmajor, jax.core.Tracer)
        and not isinstance(aux.vals_fp, jax.core.Tracer)
        and _trace_state_clean()
    ):
        # Fully-eager calls can be checked against the baked stream's
        # fingerprint; traced production calls read the batch's static
        # vals by construction.  The trace-state check matters beyond
        # the isinstance ones: under omnistaging, ops on CONCRETE
        # closed-over arrays still stage inside an enclosing trace
        # (e.g. the optimizer's while_loop body), so the slicing below
        # is only safe in a clean eval state.  The strided sample
        # bounds the host transfer to O(1) while covering the whole
        # stream, and comparing it ELEMENTWISE catches swaps and
        # off-grid-adjacent edits a collapsed norm would miss; loose
        # rtol because batch_astype may have re-stored vals in bf16
        # after the attach (bf16 requantization is ~2^-9 relative per
        # element).
        flat = jnp.ravel(vals_rowmajor)
        sample_np = np.asarray(
            flat[::_vals_fp_stride(int(flat.shape[0]))], np.float32
        )
        ref = np.asarray(aux.vals_fp, np.float32)
        if sample_np.shape != ref.shape or not np.allclose(
            sample_np, ref, rtol=1e-2, atol=1e-6
        ):
            raise ValueError(
                "xchg aux has values BAKED at attach time (vals_dest), but "
                "the vals_rowmajor passed here differs from what the attach "
                "saw; re-attach the aux (build_xchg_aux(..., vals=...)) "
                "after re-weighting values"
            )
    bf16 = os.environ.get("PHOTON_XCHG_DTYPE", "float32") == "bfloat16"
    balanced = isinstance(aux.route, BalancedRoute)
    if balanced and aux.route.k_expand and aux.vals_dest is not None:
        # Fully fused fast path: the [n] dz vector expands INSIDE stage
        # A (no E-stream materialization at all) and the static values
        # multiply at the destination.
        dz = per_row.astype(jnp.bfloat16 if bf16 else jnp.float32)
        moved = apply_balanced_dz(dz, aux.route, interpret=bool(interpret))
    else:
        if aux.vals_dest is not None:
            # The static value stream is pre-permuted (attach time), so
            # each step moves only the dz expansion; the value multiply
            # happens at the destination, fused into the reduce read.
            k = vals_rowmajor.shape[1]
            stream = jnp.repeat(per_row.astype(jnp.float32), k)
        else:
            stream = (per_row[:, None] * vals_rowmajor).astype(
                jnp.float32
            ).reshape(-1)
        # Optional half-width payload through the exchange: the
        # permutation passes are pure data movement, so bf16 halves
        # their HBM traffic; products quantize at ~2^-9 relative and
        # the reduce runs f32 (the compensated scan below, or the
        # aligned position-reduce's f32 accumulate), so per-feature
        # sums keep ~0.1% worst-case error.  Measured-choice knob like
        # every kernel decision here.
        if bf16:
            stream = stream.astype(jnp.bfloat16)
        if balanced:
            moved = apply_balanced(stream, aux.route,
                                   interpret=bool(interpret))
        else:
            moved = apply_vperm(stream, aux.route,
                                interpret=bool(interpret))
    if aux.vals_dest is not None:
        # Upcast BOTH operands before multiplying: the exchange is done,
        # so there is no traffic reason to multiply in bf16, and a bf16
        # product of two already-quantized operands would round a third
        # time.
        moved = moved.astype(jnp.float32) * aux.vals_dest.astype(
            jnp.float32
        )
    else:
        moved = moved.astype(jnp.float32)
    if aux.bounds is None:
        if balanced:
            # Repack chunk fronts into the contiguous slot stream (one
            # XLA copy), then the existing position-reduce finishes.
            r = aux.route
            moved = (
                moved.reshape(r.nc, r.cs)[:, :r.ds_win]
                .reshape(-1)[: r.n_out]
            )
        return aligned_reduce(
            moved.reshape(al.lo.shape), al, dim, interpret=interpret
        )
    hi, lo = _compensated_cumsum(moved)
    zero = jnp.zeros(1, jnp.float32)
    hi = jnp.concatenate([zero, hi])
    lo = jnp.concatenate([zero, lo])
    bh = jnp.take(hi, aux.bounds, axis=0)
    bl = jnp.take(lo, aux.bounds, axis=0)
    # Difference the compensated pair BEFORE collapsing: at production
    # scale (E ~ 2^25) a plain f32 prefix sum reaches magnitudes where
    # its ulp exceeds small per-feature gradients, so g[f] would be
    # rounding noise.  The (hi, lo) double-f32 carries ~48 effective
    # mantissa bits through the scan at stream cost.
    return (bh[1:] - bh[:-1]) + (bl[1:] - bl[:-1])


def _compensated_cumsum(x: Array) -> tuple[Array, Array]:
    """Inclusive prefix sum of f32 ``x`` as a (hi, lo) double-f32 pair
    via an associative two-sum combine (Dekker/Knuth), so the error of
    the running sum stays bounded by the ~48-bit pair precision instead
    of growing with the prefix magnitude."""

    def combine(a, b):
        a_hi, a_lo = a
        b_hi, b_lo = b
        s = a_hi + b_hi
        z = s - a_hi
        err = (a_hi - (s - z)) + (b_hi - z)
        return s, err + a_lo + b_lo

    hi, lo = jax.lax.associative_scan(
        combine, (x, jnp.zeros_like(x))
    )
    return hi, lo
