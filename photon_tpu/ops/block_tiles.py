"""Row-block x feature-block entry tiles — both random accesses of a sparse
value+gradient evaluation made inside VMEM.

Every other kernel in this package pays at least one E-element XLA gather or
scatter a direction (KERNEL_NOTES.md "Eliminating random access"): an access
XLA makes against an array in HBM.  Here the entries are stored in tiles that
are local on BOTH sides: the rows are cut into blocks of ``kr`` and the
features into blocks of ``kf``; a tile holds up to 2,048 entries of ONE cell
of that grid, so the slice of the vector it reads (``kf`` coefficients for
``Xw``, ``kr`` per-row factors for ``Xᵀdz``) and the slice it adds into are
both a few vregs.  One kernel serves both directions with the two index
halves swapped: its grid is the dense cell grid ``(output block, input
block)``, walked row-block-major for ``Xw`` and feature-block-major for
``Xᵀdz``; both windows come by ``BlockSpec`` off the grid indices, and the
cell's tiles (consecutive in storage, found through one scalar-prefetched
table of first tiles) stream from HBM through two buffers.

Inside a tile (entries on the lane axis, 128 a chunk):

- *read*: ``x[hi, lo]`` of the ``[h, 128]`` float32 window: one lane gather
  a window row (a single source vreg, which Mosaic lowers on the v5e), kept
  where ``hi`` names that row.  No arithmetic touches the value.
- multiply by the tile's values;
- *write*: ``acc[hi, lo] += p`` as ``(p * onehot_hi)[3h, t] @
  onehot_lo[128, t]ᵀ`` on the MXU (there is no vector scatter), accumulated
  in the output window, which stays in VMEM across the cells of one output
  block.

**Same arithmetic.**  The product's right operand is 0/1, exact in bfloat16;
``p`` is split EXACTLY into three bfloat16 terms (:func:`split_bf16x3`, 8 +
8 + 8 significand bits) kept in separate rows, each accumulated in float32
and added last.  The MXU therefore selects and adds float32 numbers; nothing
is rounded to bfloat16.  The result is a float32 sum in another order.

The layout is a function of ``(ids, vals)`` alone and exact for any ids
(duplicates in a row, skew, pad slots): a hot cell is more tiles, a cold one
is one mostly-empty tile or none.  ``kr``, ``kf`` come from ``(n, d, E)``
(:func:`block_tile_geometry`); there is no option.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax import tree_util
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_tpu.utils.device import count_h2d, pallas_interpret

Array = jax.Array

LANES = 128
TILE_SLOTS = 2048  # entries a tile: (16, 128) index words, (16, 128) values
CELL_TILES = 2  # mean tiles a grid cell aimed at: ~1 / (2 * CELL_TILES) padding
MIN_BLOCK = 2048  # 16 window rows: 8 rows of two-bfloat16 words a term
MAX_BLOCK = 16384  # local indices stay under 2^15; one-hot rows under 3 * 128
_IDX_BITS = 16  # packed slot: row-in-block << 16 | feature-in-block
MAX_CELLS = 3 << 16  # the tile-start table is scalar-prefetched: 1 MB of SMEM
GROUP = 4  # tiles a copy from HBM (64 KB): a cell is most often one copy


def split_bf16x3(x: Array) -> tuple[Array, Array, Array]:
    """``x`` (float32) as three float32 terms, each exactly a bfloat16, with
    ``(x1 + x2) + x3 == x`` bit for bit: each remainder is exact in float32
    and 8 significand bits shorter than the last (finite inputs whose third
    term is not denormal).  A 0/1 matrix times the three terms, accumulated
    in float32, therefore selects and adds float32 numbers on the MXU."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    x1 = x.astype(bf16).astype(f32)
    r1 = x - x1
    x2 = r1.astype(bf16).astype(f32)
    return x1, x2, r1 - x2


def block_tile_geometry(n: int, d: int, e: int) -> tuple[int, int] | None:
    """``(kr, kf)``: powers of two whose grid cell holds about ``CELL_TILES``
    tiles of entries at this batch's density, as square as ``n`` and ``d``
    allow (the write side's work grows with the window it adds into).
    ``None`` when that grid has ``MAX_CELLS`` cells or more: the tile-start
    table would not fit the scalar memory, and the batch cannot be tiled."""

    def ceil_log2(v: int) -> int:
        return max(int(v) - 1, 1).bit_length()

    lo, hi = MIN_BLOCK.bit_length() - 1, MAX_BLOCK.bit_length() - 1
    area = CELL_TILES * TILE_SLOTS * max(n, 1) * max(d, 1) / max(e, 1)
    log_area = max(int(round(np.log2(area))), 2 * lo)
    f_hi = min(hi, max(ceil_log2(d), lo))
    r_hi = min(hi, max(ceil_log2(n), lo))
    kf = min(max(log_area // 2, lo), f_hi)
    kr = min(max(log_area - kf, lo), r_hi)
    kf = min(max(log_area - kr, lo), f_hi)  # what the row side could not take
    if (-(-max(n, 1) >> kr)) * (-(-max(d, 1) >> kf)) >= MAX_CELLS:
        return None
    return 1 << kr, 1 << kf


@dataclasses.dataclass(frozen=True)
class BlockTiles:
    """The tile storage and the one table both walk orders read (host arrays
    from :func:`build_block_tiles`, device arrays after
    :func:`device_block_tiles`).

    - ``slots`` ``[n_tiles + GROUP - 1, 32, 128]`` int32: a tile is 2,048
      slots; its first 16 rows pack ``row_in_block << 16 |
      feature_in_block``, the other 16 hold the bits of the float32 values
      (pads: index 0, value 0.0).  The tiles of a grid cell are consecutive;
      cells are stored row-block-major; ``GROUP - 1`` empty tiles close the
      storage (a copy of ``GROUP`` tiles from any tile stays inside it).
    - ``start`` ``[n_rb * n_fb + 1]`` int32: the first tile of each cell
      ``rb * n_fb + fb`` (an empty cell has ``start[c] == start[c + 1]``).

    Every leaf has a leading axis (a mesh of one device places the batch's
    leaves by it).

    The kernel's grid is the dense cell grid, walked row-block-major for
    ``Xw`` and feature-block-major for ``Xᵀdz``: every output window is
    visited (and zeroed on its first cell) whatever the ids are, and both
    orders read the same tiles through the same table.
    """

    slots: Array
    start: Array
    kr: int
    kf: int
    n_rows: int
    dim: int

    @property
    def n_tiles(self) -> int:
        return int(self.slots.shape[0]) - (GROUP - 1)

    @property
    def n_rb(self) -> int:
        return max(-(-self.n_rows // self.kr), 1)

    @property
    def n_fb(self) -> int:
        return max(-(-self.dim // self.kf), 1)

    def padded_fraction(self, n_entries: int) -> float:
        """Slots over the ``n_entries`` real entries stored, less one."""
        return self.n_tiles * TILE_SLOTS / max(int(n_entries), 1) - 1.0


tree_util.register_dataclass(
    BlockTiles,
    data_fields=("slots", "start"),
    meta_fields=("kr", "kf", "n_rows", "dim"),
)


def untileable(n: int, d: int) -> str:
    """Why :func:`block_tile_geometry` gave ``None`` for this shape."""
    return (
        f"{n} rows x {d} features make {MAX_CELLS} grid cells or more "
        f"(blocks of at most {MAX_BLOCK}): the tile-start table would not "
        f"fit the scalar memory"
    )


def _build_threads() -> int:
    return min(os.cpu_count() or 1, 8)


def build_block_tiles(ids: np.ndarray, vals: np.ndarray, dim: int) -> BlockTiles:
    """Tile a padded-COO batch (host side, once a batch).

    Rows are already in row-block order, so the only sort is one stable sort
    of each row block's entries by feature block (a 16-bit key: numpy's
    radix sort), run on a small thread pool; no argsort over all E ids.
    Entries of value 0.0 (the pad slots) are dropped.  Raises ``ValueError``
    for a batch :func:`block_tile_geometry` cannot tile (callers that have
    another kernel to run ask it first).
    """
    ids = np.asarray(ids)
    vals = np.asarray(vals, np.float32)
    n, k = ids.shape
    geometry = block_tile_geometry(n, dim, n * k)
    if geometry is None:
        raise ValueError(untileable(n, dim))
    kr, kf = geometry
    if ids.size and (ids.min() < 0 or ids.max() >= dim):
        raise ValueError(f"feature id out of range for dim {dim}")
    n_rb, n_fb = max(-(-n // kr), 1), max(-(-dim // kf), 1)
    key_dtype = np.uint16 if n_fb <= (1 << 16) else np.int32
    t = TILE_SLOTS

    def sort_block(b: int):
        """One row block: its live entries sorted by feature block, as
        (counts per feature block, packed indices, values)."""
        f = ids[b * kr:(b + 1) * kr].reshape(-1)
        v = vals[b * kr:(b + 1) * kr].reshape(-1)
        live = np.flatnonzero(v)
        dropped = live.size < v.size
        if dropped:
            f, v = f[live], v[live]
        block = f // kf
        order = np.argsort(block.astype(key_dtype), kind="stable")
        src = live[order] if dropped else order  # flat position in the block
        packed = ((src // k).astype(np.int32) << _IDX_BITS) | (f[order] % kf)
        return np.bincount(block, minlength=n_fb), packed, v[order]

    with ThreadPoolExecutor(_build_threads()) as pool:
        blocks = list(pool.map(sort_block, range(n_rb)))
        counts = np.stack([c for c, _, _ in blocks]).astype(np.int64)
        start = np.zeros(n_rb * n_fb + 1, np.int64)
        np.cumsum(-(-counts.reshape(-1) // t), out=start[1:])
        n_tiles = max(int(start[-1]), 1)  # storage is never empty
        # Closing pads: a copy of GROUP tiles from any tile stays inside.
        slots = np.zeros((n_tiles + GROUP - 1, 2, t), np.int32)
        flat = slots.reshape(-1)

        def place(b: int):
            c, packed, v = blocks[b]
            # Sorted position j of cell (b, f) is slot j - (entries before
            # the cell) of the cell's first tile; a tile's slots are 2 * t
            # words apart (indices, then value bits).
            shift = start[b * n_fb:(b + 1) * n_fb] * t - (np.cumsum(c) - c)
            s = np.arange(packed.size, dtype=np.int64) + np.repeat(shift, c)
            s += s & ~np.int64(t - 1)
            flat[s] = packed
            flat[s + t] = v.view(np.int32)

        list(pool.map(place, range(n_rb)))
    return BlockTiles(
        slots=slots.reshape(-1, 2 * t // LANES, LANES),
        start=start.astype(np.int32),
        kr=kr, kf=kf, n_rows=n, dim=dim,
    )


def device_block_tiles(layout: BlockTiles) -> BlockTiles:
    """The layout's arrays on the device (``layout.h2d_bytes{what=block_tiles}``)."""
    dev = jax.tree.map(jnp.asarray, layout)
    count_h2d("block_tiles", dev)
    return dev


def round_values(bt: BlockTiles, dtype) -> BlockTiles:
    """The tiles with their values rounded through ``dtype`` (they stay
    float32 words: the kernel reads their bits), so that a batch re-stored by
    ``batch_astype`` keeps ONE value stream."""
    rows = bt.slots.shape[1] // 2
    vals = jax.lax.bitcast_convert_type(bt.slots[:, rows:], jnp.float32)
    bits = jax.lax.bitcast_convert_type(
        vals.astype(dtype).astype(jnp.float32), jnp.int32
    )
    return dataclasses.replace(bt, slots=bt.slots.at[:, rows:].set(bits))


def attach_block_tiles(ids: np.ndarray, vals: np.ndarray, dim: int) -> BlockTiles:
    """Build and upload under the ``layout.block_tiles`` span, and publish
    the layout's padding (``valuegrad.tile_padded_fraction``)."""
    from photon_tpu import telemetry

    with telemetry.span("layout.block_tiles", entries=int(np.size(ids))) as sp:
        layout = build_block_tiles(ids, vals, dim)
        sp.set_attribute("tiles", layout.n_tiles)
        dev = device_block_tiles(layout)
    telemetry.process_registry().gauge("valuegrad.tile_padded_fraction").set(
        layout.padded_fraction(np.count_nonzero(vals))
    )
    return dev


def _tile_into(acc, window, slots, *, read_shift: int, write_shift: int,
               h_in: int, h_out: int):
    """One tile: gather its entries' elements of the float32 ``window``
    (``[h_in, 128]``), multiply by the values, and add into the output
    window's three partial sums ``acc`` (``[3 * h_out, 128]``)."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    rows = slots.shape[0] // 2
    mask = (1 << _IDX_BITS) - 1
    idx = slots[:rows]
    val = jax.lax.bitcast_convert_type(slots[rows:], f32)
    rd = (idx >> read_shift) & mask
    wr = (idx >> write_shift) & mask
    rd_hi, rd_lo = rd >> 7, rd & (LANES - 1)
    wr_hi, wr_lo = wr >> 7, wr & (LANES - 1)
    # Read: one lane gather a window row (a single source vreg each), kept
    # where that row is the entry's.  Exact: no arithmetic touches the value.
    got = jnp.zeros((rows, LANES), f32)
    for h in range(h_in):
        line = jnp.broadcast_to(window[h:h + 1], (rows, LANES))
        got = jnp.where(
            rd_hi == h, jnp.take_along_axis(line, rd_lo, axis=1), got
        )
    terms = split_bf16x3(got * val)
    # Write: acc[hi, lo] += p as (p * onehot_hi) @ onehot_loᵀ, the entries
    # (on the lane axis, 128 a chunk) the contracted dimension.  Both
    # operands are built as int32 words holding two bfloat16 rows each (row
    # 2k the low half, row 2k + 1 the high half: ``pltpu.bitcast``), half
    # the selects and no float32 -> bfloat16 packing on a chip whose vector
    # unit has no bfloat16.  A term is exactly a bfloat16, so its bits are
    # the high half of its float32 word.
    one = 0x3F80  # bfloat16 1.0
    even, high = (wr_hi & 1) == 0, wr_hi >> 1
    bits = [jax.lax.bitcast_convert_type(term, jnp.int32) for term in terms]
    words = [
        jnp.where(even, jax.lax.shift_right_logical(b, 16), b) for b in bits
    ]
    put_word = jnp.where((wr_lo & 1) == 0, one, one << 16)
    pair_out = jax.lax.broadcasted_iota(jnp.int32, (h_out // 2, LANES), 0)
    pair_lane = jax.lax.broadcasted_iota(jnp.int32, (LANES // 2, LANES), 0)
    spread, put = [], []
    for c in range(rows):
        row = slice(c, c + 1)
        here = pair_out == high[row]
        spread.append(jnp.concatenate(
            [jnp.where(here, word[row], 0) for word in words], axis=0
        ))
        put.append(
            jnp.where(pair_lane == (wr_lo[row] >> 1), put_word[row], 0)
        )
    # [3 * h_out, t] and [128, t] bfloat16, rows in (term, hi) / lo order.
    spread = pltpu.bitcast(jnp.concatenate(spread, axis=1), bf16)
    put = pltpu.bitcast(jnp.concatenate(put, axis=1), bf16)
    return acc + jax.lax.dot_general(
        spread, put, (((1,), (1,)), ((), ())), preferred_element_type=f32
    )


def _cell_kernel(start_ref, u_ref, slots_hbm, o_ref, buf, sem, state, *,
                 n_fb: int, transpose: bool, h_in: int, h_out: int):
    """One grid cell ``(output block, input block)``: copy its tiles from HBM
    ``GROUP`` at a time through two buffers and add them into the output
    window, which stays in VMEM across the cells of one output block.  A
    cell's last group starts the copy of the next cell's first, so a cell
    does not open with a wait on HBM; ``state`` carries the groups copied so
    far (the buffer in turn) and whether that copy is in flight."""
    o, j = pl.program_id(0), pl.program_id(1)
    n_o, n_j = pl.num_programs(0), pl.num_programs(1)

    def tiles_of(o, j):
        cell = j * n_fb + o if transpose else o * n_fb + j
        return start_ref[cell], start_ref[cell + 1] - start_ref[cell]

    first, count = tiles_of(o, j)
    wraps = j == n_j - 1
    o_next = jnp.where(wraps, o + 1, o)
    next_first, next_count = tiles_of(
        jnp.minimum(o_next, n_o - 1), jnp.where(wraps, 0, j + 1)
    )
    next_count = jnp.where(o_next < n_o, next_count, 0)

    @pl.when(jnp.logical_and(o == 0, j == 0))
    def _start():
        state[0] = 0
        state[1] = 0

    @pl.when(j == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    def fetch(slot, tile):
        # GROUP tiles from ``tile`` on: past the cell's end they are the
        # next cells' (or the storage's closing pads) and are not read.
        return pltpu.make_async_copy(
            slots_hbm.at[pl.ds(tile, GROUP)], buf.at[slot], sem.at[slot]
        )

    @pl.when(count > 0)
    def _cell():
        done = state[0]
        groups = (count + GROUP - 1) // GROUP

        @pl.when(state[1] == 0)
        def _first():
            fetch(done % 2, first).start()

        window = u_ref[...]

        def one_group(g, acc):
            slot = (done + g) % 2
            fetch(slot, first + g * GROUP).wait()

            @pl.when(g + 1 < groups)
            def _next_group():
                fetch(1 - slot, first + (g + 1) * GROUP).start()

            @pl.when(jnp.logical_and(g + 1 == groups, next_count > 0))
            def _next_cell():
                fetch(1 - slot, next_first).start()

            def one_tile(i, acc):
                return _tile_into(
                    acc, window, buf[slot, i],
                    read_shift=_IDX_BITS if transpose else 0,
                    write_shift=0 if transpose else _IDX_BITS,
                    h_in=h_in, h_out=h_out,
                )

            return jax.lax.fori_loop(
                0, jnp.minimum(GROUP, count - g * GROUP), one_tile, acc
            )

        o_ref[...] += jax.lax.fori_loop(
            0, groups, one_group, jnp.zeros(o_ref.shape, jnp.float32)
        )
        state[0] = done + groups
        state[1] = (next_count > 0).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("k_in", "k_out", "n_in_blocks", "n_out_blocks",
                              "transpose", "interpret"),
)
def _cell_products(start, u, slots, k_in: int, k_out: int, n_in_blocks: int,
                   n_out_blocks: int, transpose: bool, interpret: bool):
    h_in, h_out = k_in // LANES, k_out // LANES
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_out_blocks, n_in_blocks),
        in_specs=[
            pl.BlockSpec((h_in, LANES), lambda o, j, start: (j, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((3 * h_out, LANES), lambda o, j, start: (o, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, GROUP) + slots.shape[1:], jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _cell_kernel, n_fb=n_out_blocks if transpose else n_in_blocks,
        transpose=transpose, h_in=h_in, h_out=h_out,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (n_out_blocks * 3 * h_out, LANES), jnp.float32
        ),
        grid_spec=grid_spec,
        interpret=interpret,
    )(start, u, slots)


def block_tiles_product(
    u: Array, bt: BlockTiles, out_len: int, transpose: bool = False
) -> Array:
    """``X u`` per row (``u`` over the ``dim`` features, ``out_len`` = rows)
    or, with ``transpose``, ``Xᵀ u`` per feature (``u`` over the rows,
    ``out_len`` = ``dim``) over the tiled entries of ``X``."""
    if transpose:
        k_in, k_out, n_in_blocks, n_out_blocks = bt.kr, bt.kf, bt.n_rb, bt.n_fb
    else:
        k_in, k_out, n_in_blocks, n_out_blocks = bt.kf, bt.kr, bt.n_fb, bt.n_rb
    u = jnp.pad(u.astype(jnp.float32), (0, n_in_blocks * k_in - u.shape[0]))
    parts = _cell_products(
        bt.start, u.reshape(-1, LANES), bt.slots, k_in=k_in, k_out=k_out,
        n_in_blocks=n_in_blocks, n_out_blocks=n_out_blocks,
        transpose=bool(transpose), interpret=pallas_interpret(),
    ).reshape(n_out_blocks, 3, k_out)
    out = (parts[:, 0] + parts[:, 1]) + parts[:, 2]
    return out.reshape(-1)[:out_len]
