"""Static-shape training batches.

The reference represents a training point as ``data.LabeledPoint(label,
features: Breeze vector, offset, weight)`` (photon-lib .../data/LabeledPoint —
SURVEY.md §2.1) and streams RDD partitions of them through per-partition
aggregators.  XLA wants static shapes and batched math instead, so the rebuild
uses two batch layouts:

- :class:`DenseBatch` — ``x: [n, d]`` feature matrix.  Right layout for
  low/moderate-dimensional problems; margins are a single MXU matmul.
- :class:`SparseBatch` — padded COO-per-row layout ``ids/vals: [n, k]`` with a
  fixed per-row capacity ``k`` (pad with ``id=0, val=0``).  Margins are a
  gather + row-sum; gradients come out of ``jax.grad`` as scatter-adds.  This
  replaces Breeze ``SparseVector`` + BLAS ``dot``/``axpy`` with one fused XLA
  program, and keeps shapes static for the compiler (SURVEY.md §7 "sparse
  features on TPU").

Both carry ``label``, ``offset`` (GAME residual-passing depends on it), and
``weight`` exactly like ``LabeledPoint``.

The padding convention ``id=0, val=0.0`` makes padded entries contribute
``w[0] * 0.0 = 0`` to margins and zero to scatter-add gradients, so no masks
are needed in the hot loop.
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


class FeatureMajorAux(NamedTuple):
    """Static feature-major (sorted-by-feature-id) view of a batch's entries.

    The production gradient of a sparse GLM is a scatter-add of per-entry
    contributions into the coefficient vector; XLA lowers an unsorted
    scatter-add on TPU as sort + segmented reduce, paying an O(E log E)
    device sort on EVERY objective evaluation.  The sparsity pattern is
    static across a whole optimizer run (the reference exploits the same
    invariant by pre-building per-partition aggregator layouts — SURVEY.md
    §3.4), so the sort is done ONCE host-side at batch build and the runtime
    reduction becomes ``segment_sum(..., indices_are_sorted=True)``.

    All arrays are ``[S, E_s]`` where ``S`` is the number of contiguous
    row blocks (1 for single-device batches; the mesh axis size for sharded
    batches, so that sharding on the leading axis gives every device its own
    block-local sorted view) and ``E_s = rows_per_block * k``:

    - ``ids``: int32 feature ids, non-decreasing within each block.
    - ``rows``: int32 BLOCK-LOCAL source row of each entry.
    - ``vals``: float entry values — float32, or the storage dtype set by
      :func:`batch_astype` (0.0 for the row-padding entries, which therefore
      contribute nothing, same convention as SparseBatch).
    """

    ids: Array
    rows: Array
    vals: Array


class DenseBatch(NamedTuple):
    """A batch of examples with dense features."""

    x: Array  # [n, d] float
    label: Array  # [n] float
    offset: Array  # [n] float
    weight: Array  # [n] float

    @property
    def num_examples(self) -> int:
        return self.x.shape[0]

    @property
    def dim(self) -> int:
        return self.x.shape[1]


class SparseBatch(NamedTuple):
    """A batch of examples with padded sparse features.

    ``ids[i, j]`` / ``vals[i, j]`` give the j-th nonzero of example i; rows
    with fewer than ``k`` nonzeros are padded with ``(0, 0.0)``.

    ``fm`` optionally carries the static feature-major entry layout
    (:class:`FeatureMajorAux`, built by :func:`attach_feature_major`); when
    present, objectives can compute gradients via a pre-sorted segment sum
    instead of an unsorted scatter — see
    :meth:`photon_tpu.core.objective.GlmObjective.value_and_grad`.  Which
    of the four optional layouts an attach builds is
    :func:`attach_feature_major`'s decision (where a probe picks the kernel:
    the winner's alone).
    """

    ids: Array  # [n, k] int32
    vals: Array  # [n, k] float
    label: Array  # [n] float
    offset: Array  # [n] float
    weight: Array  # [n] float
    fm: Optional[FeatureMajorAux] = None
    # Optional slab-aligned layout (ops/pallas_gather.AlignedLayoutDev) for
    # the Pallas gradient kernel; attach with
    # ``attach_feature_major(..., aligned_dim=d)``.  Single-block batches
    # only (each shard of a distributed batch builds its own).
    al: Optional["object"] = None
    # Optional TRANSPOSED aligned layout (rows as the slab dictionary) for
    # the Pallas FORWARD (margins) direction; attach with
    # ``attach_feature_major(..., aligned_dim=d, aligned_forward=True)``.
    al_t: Optional["object"] = None
    # Optional row-block x feature-block entry tiles
    # (ops/block_tiles.BlockTiles) for the `blocked` kernel: margins,
    # gradient and Hv with both random accesses inside VMEM.  Built by
    # ``attach_feature_major(..., aligned_dim=d)`` on single-block batches
    # when ``PHOTON_SPARSE_GRAD`` is ``blocked``, or ``auto`` and the probe
    # picks that kernel.
    bt: Optional["object"] = None

    @property
    def num_examples(self) -> int:
        return self.ids.shape[0]


# The optional static layouts a SparseBatch can carry, named once: what
# pad_batch strips, a sharded placement squeezes and a multi-process
# assembly rebuilds.  ops/sparse_grad_select says which kernel reads which.
LAYOUT_FIELDS = ("fm", "al", "al_t", "bt")

Batch = Union[DenseBatch, SparseBatch]


def margins(w: Array, batch: Batch) -> Array:
    """Per-example margins ``w . x_i + offset_i``.

    The rebuild's equivalent of the reference aggregators' per-example
    ``margin = dot(coefficients, features) + offset`` inner loop
    (ValueAndGradientAggregator — SURVEY.md §3.4), batched.
    Supports a leading batch dimension on ``w`` being absent only; use vmap
    for batched models.
    """
    if isinstance(batch, DenseBatch):
        return batch.x @ w + batch.offset
    # Gather-based sparse dot: padded entries hit w[0] with val 0.
    return jnp.sum(jnp.take(w, batch.ids, axis=0) * batch.vals, axis=-1) + batch.offset


def dense_batch(
    x: np.ndarray,
    label: np.ndarray,
    offset: np.ndarray | None = None,
    weight: np.ndarray | None = None,
    dtype=jnp.float32,
) -> DenseBatch:
    n = x.shape[0]
    return DenseBatch(
        x=jnp.asarray(x, dtype),
        label=jnp.asarray(label, dtype),
        offset=jnp.zeros(n, dtype) if offset is None else jnp.asarray(offset, dtype),
        weight=jnp.ones(n, dtype) if weight is None else jnp.asarray(weight, dtype),
    )


def pad_row_capacity(nnz_per_row: np.ndarray, bucket_sizes: tuple[int, ...] | None = None) -> int:
    """Pick the padded per-row capacity k: smallest power-of-two-ish bucket
    >= max nnz, so recompiles are bounded across batches."""
    max_nnz = int(nnz_per_row.max()) if len(nnz_per_row) else 1
    if bucket_sizes is None:
        k = 1
        while k < max_nnz:
            k *= 2
        return k
    for b in bucket_sizes:
        if b >= max_nnz:
            return b
    raise ValueError(
        f"max nnz per row ({max_nnz}) exceeds the largest capacity bucket "
        f"({bucket_sizes[-1]}); truncating would silently drop features"
    )


def sparse_batch_from_rows(
    rows: list[tuple[np.ndarray, np.ndarray]],
    label: np.ndarray,
    offset: np.ndarray | None = None,
    weight: np.ndarray | None = None,
    capacity: int | None = None,
    dtype=jnp.float32,
) -> SparseBatch:
    """Build a SparseBatch from per-row (ids, vals) arrays, padding to a fixed
    capacity (power-of-two bucket by default).

    Raises if any row has more nonzeros than the capacity — silently dropping
    features would corrupt margins/gradients with no diagnostic.
    """
    n = len(rows)
    nnz = np.array([len(ids) for ids, _ in rows], dtype=np.int64)
    k = capacity if capacity is not None else pad_row_capacity(nnz)
    if len(nnz) and int(nnz.max()) > k:
        raise ValueError(
            f"row with {int(nnz.max())} nonzeros exceeds capacity {k}; "
            f"raise `capacity` instead of truncating features"
        )
    ids = np.zeros((n, k), dtype=np.int32)
    vals = np.zeros((n, k), dtype=np.float32)
    for i, (r_ids, r_vals) in enumerate(rows):
        m = len(r_ids)
        ids[i, :m] = r_ids
        vals[i, :m] = r_vals
    return SparseBatch(
        ids=jnp.asarray(ids),
        vals=jnp.asarray(vals, dtype),
        label=jnp.asarray(label, dtype),
        offset=jnp.zeros(n, dtype) if offset is None else jnp.asarray(offset, dtype),
        weight=jnp.ones(n, dtype) if weight is None else jnp.asarray(weight, dtype),
    )


def with_offset(batch: Batch, offset: Array) -> Batch:
    """Return the batch with its offset column replaced (GAME residual passing)."""
    return batch._replace(offset=offset)


def attach_feature_major(
    batch: SparseBatch,
    shards: int = 1,
    aligned_dim: int | None = None,
    aligned_forward: bool | None = None,
    geometry_gather=None,
) -> SparseBatch:
    """Attach the static layouts the sparse-gradient kernels read.

    ``attach_feature_major(batch)`` attaches the feature-major layout
    (:class:`FeatureMajorAux`, ``batch.fm``).  Host-side: one stable argsort
    of the flat entries per row block — run once per dataset, amortized over
    every optimizer iteration (the runtime win is deleting the
    per-evaluation device sort inside XLA's scatter lowering; see
    FeatureMajorAux).  ``shards`` must match the mesh data-axis size the
    batch will be sharded over (1 for single-device use); rows are split
    into ``shards`` contiguous blocks, mirroring
    :func:`photon_tpu.parallel.mesh.shard_batch` placement.

    With ``aligned_dim`` (the coefficient dimension; callers pass it
    unconditionally) every kernel of ops/sparse_grad_select is on the
    table, and which layouts are built is decided HERE:

    - A single-block batch (``shards == 1``, no ``geometry_gather``) in auto
      mode at or above the probe floor takes the selector's VERDICT FIRST
      (``sparse_grad_select.kernel_for_shape``: the probe measures every
      kernel that could be built for this shape, on a problem of its own)
      and builds the layout the winner reads, and nothing else:
      ``blocked`` -> ``bt`` (the row-block x feature-block tiles of
      ops/block_tiles.py), ``pallas`` -> ``al`` (the slab-aligned layout,
      through its disk cache; and ``al_t`` under ``aligned_forward``),
      ``fm`` -> ``fm``, ``autodiff`` -> no layout at all.  Each build so
      spared counts ``layout.skipped{layout}``.  On a TPU that is the tiles
      alone: no sort, no bin-packing.  The trace-time selection finds the
      same verdict cached and does not measure again.
    - Under a pin, or under the probe floor, no measurement decides: ``fm``
      is built, and beside it what ``sparse_grad_select.layouts_wanted``
      names on the batch's entry count (the pinned kernel's layout; nothing
      under the floor), so CPU runs never pay for layouts the selector
      cannot route to.  A batch whose grid of blocks outgrows the
      ``blocked`` kernel's tile table goes without the tiles in either case,
      loudly (``kernels.refused{kernel=blocked}``), and selection goes on
      among the other kernels.
    - With ``shards > 1`` ``fm`` is built, and every row block gets its OWN
      aligned layout (block-local rows) whenever any layout is wanted; the
      per-block layouts are padded to a common geometry and stacked on a
      leading shard axis, so sharding the batch on that axis hands each
      device exactly its block's layout (VERDICT r5 item 2 — the fast
      kernels must run under the sharded objective; squeeze + dispatch
      happen in parallel/distributed.py).  Tiles are single-block only.
    - A ``geometry_gather`` caller (a multi-process assembly) gets the same
      stacked form: every process must take the same branch around the
      gather's collectives, so that caller decides on globally agreed inputs
      and passes ``aligned_dim`` or None.

    ``aligned_forward`` additionally builds the transposed (row-dictionary)
    layout so the Pallas path computes MARGINS through the same kernel
    (``batch.al_t``) — costs a second layout's host build and device
    memory, so it defaults to the ``PHOTON_SPARSE_MARGIN=pallas`` env
    opt-in.
    """
    if not isinstance(batch, SparseBatch) or batch.ids.ndim != 2:
        raise ValueError("feature-major layout requires a 2-D SparseBatch")
    from photon_tpu import telemetry
    from photon_tpu.utils.device import count_h2d

    n, k = batch.ids.shape
    if n % shards:
        raise ValueError(f"rows ({n}) not divisible by shards ({shards}); pad first")
    if aligned_forward and aligned_dim is None:
        raise ValueError(
            "aligned_forward requires aligned_dim (the transposed layout "
            "only serves the pallas kernel, which needs the aligned "
            "gradient layout too)"
        )
    if aligned_forward is None:
        aligned_forward = (
            os.environ.get("PHOTON_SPARSE_MARGIN", "xla") == "pallas"
        )
    single_block = shards == 1 and geometry_gather is None
    build = {"fm"}
    if aligned_dim is not None and single_block:
        build = _single_block_layouts(n, k, aligned_dim, aligned_forward)
    ns = n // shards
    if "fm" in build:
        # The host argsort and the reorder gathers of the flat entries
        # (plus the fetch of ids/vals when the batch is already on the
        # device).
        with telemetry.span(
            "layout.feature_major", entries=n * k, shards=shards
        ):
            ids = np.asarray(batch.ids).reshape(shards, ns * k)
            vals = np.asarray(batch.vals).reshape(shards, ns * k)
            rows = np.broadcast_to(
                np.repeat(np.arange(ns, dtype=np.int32), k), (shards, ns * k)
            )
            order = np.argsort(ids, axis=1, kind="stable")
            take = np.take_along_axis
            fm = FeatureMajorAux(
                ids=jnp.asarray(take(ids, order, axis=1)),
                rows=jnp.asarray(take(rows, order, axis=1)),
                vals=jnp.asarray(take(vals, order, axis=1)),
            )
        count_h2d("feature_major", fm)
        batch = batch._replace(fm=fm)
    if aligned_dim is None or (single_block and build <= {"fm"}):
        return batch
    ids_np = np.asarray(batch.ids)
    vals_np = np.asarray(batch.vals, np.float32)
    if not single_block:
        # A geometry gather forces the STACKED form even for one local
        # shard: a multi-process assembly needs every process's aux to
        # carry the leading shard axis (and to agree on the
        # globally-gathered geometry) so the per-process arrays
        # concatenate into one global sharded pytree.  Tiles are
        # single-block only: a sharded batch gets the aligned layouts
        # whenever any layout is wanted (a ``blocked`` pin then runs the
        # nearest kernel the batch carries).
        from photon_tpu.ops.sparse_grad_select import aligned_layout_wanted

        if geometry_gather is None and not aligned_layout_wanted(n * k):
            return batch
        return _attach_aligned_sharded(
            batch, ids_np, vals_np, aligned_dim, shards,
            bool(aligned_forward), geometry_gather,
        )
    if "bt" in build:
        from photon_tpu.ops.block_tiles import attach_block_tiles

        batch = batch._replace(
            bt=attach_block_tiles(ids_np, vals_np, aligned_dim)
        )
    if "al" not in build:
        return batch
    from photon_tpu.ops.pallas_gather import (
        device_layout,
        layout_content_hash,
        load_or_build_aligned_layout,
    )

    with telemetry.span("layout.cache_key"):
        base_hash = layout_content_hash(ids_np, vals_np)
    batch = batch._replace(al=device_layout(load_or_build_aligned_layout(
        ids_np, vals_np, aligned_dim, base_hash=base_hash
    )))
    if "al_t" in build:
        batch = batch._replace(al_t=device_layout(
            load_or_build_aligned_layout(
                ids_np, vals_np, aligned_dim, transposed=True,
                base_hash=base_hash,
            )
        ))
    return batch


def _single_block_layouts(
    n: int, k: int, dim: int, aligned_forward: bool
) -> set:
    """The fields of :data:`LAYOUT_FIELDS` a single-block ``[n, k]`` attach
    builds.  Without a measurement (a pin, the probe floor): ``fm`` and what
    ``layouts_wanted`` names.  When the probe decides, its verdict is taken
    now and only the layout the winner reads is kept; every other build is
    counted as spared (``layout.skipped{layout}``)."""
    from photon_tpu.ops import block_tiles
    from photon_tpu.ops.sparse_grad_select import (
        kernel_for_shape,
        layouts_wanted,
    )
    from photon_tpu.utils.device import (
        count_layout_skipped,
        record_kernel_refusal,
    )

    want_aligned, want_tiles = layouts_wanted(n * k)
    if want_tiles and block_tiles.block_tile_geometry(n, dim, n * k) is None:
        record_kernel_refusal(
            "blocked", ValueError(block_tiles.untileable(n, dim))
        )
        want_tiles = False
    build = {"fm"}
    if want_aligned:
        build |= {"al", "al_t"} if aligned_forward else {"al"}
    if want_tiles:
        build.add("bt")
    verdict = kernel_for_shape(n, k, dim)
    if verdict.probed:
        # (``{None}`` for autodiff: no layout is kept.)
        keep = {"al", "al_t"} if verdict.layout == "al" else {verdict.layout}
        for field in sorted(build - keep):
            count_layout_skipped(field)
        build &= keep
    return build


def _attach_aligned_sharded(
    batch: SparseBatch,
    ids_np: np.ndarray,
    vals_np: np.ndarray,
    aligned_dim: int,
    shards: int,
    aligned_forward: bool,
    geometry_gather=None,
) -> SparseBatch:
    """Per-shard aligned layouts (+ optional transposed layouts), padded to
    the max (slabs, tiles) across shards
    (ops/pallas_gather.stack_device_layouts) and stacked on a leading shard
    axis (VERDICT r5 item 2), so every shard's arrays are ONE pytree with
    ONE treedef.

    ``geometry_gather(local [S, 4] int64) -> global [S_total, 4]``
    widens the geometry agreement beyond this call's shards — the
    multi-process assembly (data/streaming.make_global_batch) passes a
    process-allgather so every process pads to ONE global geometry and
    the per-process stacked leaves concatenate into one sharded global
    array.  Columns: (n_slabs, n_tiles, al_t n_slabs, al_t n_tiles).
    Default: identity (single-process attach).
    """
    from photon_tpu import telemetry
    from photon_tpu.ops.pallas_gather import (
        common_layout_geometry_arr,
        layout_content_hash,
        load_or_build_aligned_layout,
        pad_aligned_layout,
        stack_device_layouts,
    )

    if geometry_gather is None:
        geometry_gather = lambda arr: arr  # noqa: E731 — identity
    n, k = ids_np.shape
    ns = n // shards
    ids_blocks = ids_np.reshape(shards, ns, k)
    vals_blocks = vals_np.reshape(shards, ns, k)
    with telemetry.span("layout.cache_key"):
        base_hashes = [
            layout_content_hash(ids_blocks[s], vals_blocks[s])
            for s in range(shards)
        ]
    layouts = [
        load_or_build_aligned_layout(
            ids_blocks[s], vals_blocks[s], aligned_dim,
            base_hash=base_hashes[s],
        )
        for s in range(shards)
    ]
    layouts_t = (
        [
            load_or_build_aligned_layout(
                ids_blocks[s], vals_blocks[s], aligned_dim,
                transposed=True, base_hash=base_hashes[s],
            )
            for s in range(shards)
        ]
        if aligned_forward else None
    )
    geo_local = np.asarray([
        [
            layouts[s].n_slabs, layouts[s].n_tiles,
            layouts_t[s].n_slabs if layouts_t else 0,
            layouts_t[s].n_tiles if layouts_t else 0,
        ]
        for s in range(shards)
    ], np.int64)
    geo = np.asarray(geometry_gather(geo_local), np.int64)
    s_tgt, t_tgt = common_layout_geometry_arr(geo[:, :2])
    batch = batch._replace(al=stack_device_layouts(
        [pad_aligned_layout(l, s_tgt, t_tgt) for l in layouts]
    ))
    if aligned_forward:
        st, tt = common_layout_geometry_arr(geo[:, 2:])
        batch = batch._replace(al_t=stack_device_layouts(
            [pad_aligned_layout(l, st, tt) for l in layouts_t]
        ))
    return batch


def batch_astype(batch: Batch, dtype) -> Batch:
    """Re-store the batch's FEATURE VALUES in ``dtype`` (e.g. bfloat16).

    TPU-first storage option: feature values are the second-largest stream
    the sparse hot loop reads (after int32 ids), and GLM margins/gradients
    are insensitive to feature-value precision at bf16 scale — all
    arithmetic still happens in float32 via JAX type promotion (coefficients,
    labels, offsets, weights, and every reduction stay f32; only the stored
    values shrink).  The reference has no analog: Breeze vectors are f64.
    """
    import dataclasses

    dtype = jnp.dtype(dtype)
    if isinstance(batch, DenseBatch):
        return batch._replace(x=batch.x.astype(dtype))
    out = batch._replace(vals=batch.vals.astype(dtype))
    if out.fm is not None:
        out = out._replace(fm=out.fm._replace(vals=out.fm.vals.astype(dtype)))
    for aux in ("al", "al_t"):
        lay = getattr(out, aux)
        if lay is not None:
            out = out._replace(**{
                aux: dataclasses.replace(lay, vals=lay.vals.astype(dtype))
            })
    if out.bt is not None:
        from photon_tpu.ops.block_tiles import round_values

        out = out._replace(bt=round_values(out.bt, dtype))
    return out


def pad_batch(batch: Batch, target_n: int) -> Batch:
    """Pad a batch to ``target_n`` examples with zero-weight rows (so padded
    rows contribute nothing to any weighted objective or evaluator)."""
    n = batch.num_examples
    if n == target_n:
        return batch
    if n > target_n:
        raise ValueError(f"batch has {n} rows > target {target_n}")
    pad = target_n - n

    def _pad(a: Array) -> Array:
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        if isinstance(a, jax.Array):
            return jnp.pad(a, widths)
        # Host leaves pad on host: a row-capacity rebuild at a new true
        # row count then uploads at the (unchanged) padded shape and
        # compiles nothing — the point of the capacity headroom.
        return np.pad(np.asarray(a), widths)

    # The static layouts are row-count- and block-structure-dependent;
    # padding per-leaf would corrupt them.  Strip them (padded rows carry
    # only zero-value entries, so a layout rebuilt after padding is
    # equivalent) and let the caller re-attach at the final row count.
    if isinstance(batch, SparseBatch):
        batch = batch._replace(**dict.fromkeys(LAYOUT_FIELDS))
    return jax.tree.map(_pad, batch)
