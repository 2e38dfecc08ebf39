"""Device mesh construction and batch sharding.

Replaces the reference's Spark partitioning layer (RDD partitions spread over
executors — SURVEY.md §2.6).  Axes used by the framework:

- ``"data"``  — batch/data parallelism for the fixed effect (≙ RDD partitions
  + treeAggregate).
- ``"entity"`` — per-entity sharding of random-effect solves (≙
  RandomEffectDatasetPartitioner's hash partitioning).  In practice both map
  onto the same physical chips; a 1-D mesh reused under two names keeps the
  code paths explicit.

Multi-host: mesh creation uses all addressable JAX devices; under
``jax.distributed`` the same code spans slices, with `pjit` emitting DCN
collectives across slice boundaries automatically.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_tpu.data.batch import (
    LAYOUT_FIELDS,
    Batch,
    SparseBatch,
    attach_feature_major,
    pad_batch,
)

DATA_AXIS = "data"
ENTITY_AXIS = "entity"


def create_mesh(
    n_devices: Optional[int] = None, axis_name: str = DATA_AXIS, devices=None
) -> Mesh:
    """1-D mesh over the first ``n_devices`` devices (all by default)."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis_name,))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, batch: Batch, axis_name: str = DATA_AXIS):
    """Shardings for a batch pytree: every leaf sharded on its leading
    (example) axis."""
    return jax.tree.map(
        lambda leaf: NamedSharding(mesh, P(axis_name, *([None] * (leaf.ndim - 1)))),
        batch,
    )


def shard_batch(
    batch: Batch,
    mesh: Mesh,
    axis_name: str = DATA_AXIS,
    build_fm: bool = True,
    aligned_dim: Optional[int] = None,
) -> Batch:
    """Pad the batch to a multiple of the mesh axis size (zero-weight rows)
    and place it sharded across the axis.

    The padding convention means padded rows are invisible to objectives and
    evaluators — the analog of the reference's uneven final RDD partition.

    For 2-D sparse batches this also attaches the per-shard feature-major
    layout (``build_fm``), so sharded objectives take the pre-sorted
    segment-sum gradient path; the aux's leading block axis is sharded like
    the rows, giving each device its block-local sorted view.  With
    ``aligned_dim`` (the coefficient dimension) the per-shard slab-aligned
    layouts are built and stacked too, so the fast kernels run inside the
    sharded objective (VERDICT r5 item 2).  Callers pass the dimension
    unconditionally: ``attach_feature_major`` builds only the layouts the
    kernel selector could route to, so CPU-only runs never pay for them
    (and on a mesh of one device, a single-block attach, only the layout
    of the kernel the probe picked).
    """
    n_shards = mesh.shape[axis_name]
    n = batch.num_examples
    target = ((n + n_shards - 1) // n_shards) * n_shards
    padded = pad_batch(batch, target)
    if isinstance(padded, SparseBatch):
        rebuild = build_fm and padded.ids.ndim == 2
        # Pre-attached single-block layouts cannot be row-sharded: strip
        # them.  Only fm has a per-shard form a caller could have attached
        # itself; it stays unless it is rebuilt here.
        padded = padded._replace(**{
            field: None for field in LAYOUT_FIELDS
            if rebuild or field != "fm"
        })
        if rebuild:
            padded = attach_feature_major(
                padded, shards=n_shards, aligned_dim=aligned_dim
            )
    return jax.device_put(padded, batch_sharding(mesh, padded, axis_name))


def put_replicated(x, mesh: Optional[Mesh]):
    """Place a pytree of arrays fully replicated over ``mesh``.

    ``mesh=None`` (single device) just materializes the leaves as device
    arrays.  Used for state every shard reads whole (model coefficient
    vectors, small index buffers); bulk per-row state (score rows, scoring
    feature caches) is sharded with :func:`put_sharded` instead.
    """
    if mesh is None:
        return jax.tree.map(jnp.asarray, x)
    sharding = NamedSharding(mesh, P())
    return jax.tree.map(lambda leaf: jax.device_put(leaf, sharding), x)


def mesh_shards(mesh: Optional[Mesh]) -> int:
    """Number of shards along a mesh's axes (1 without a mesh)."""
    if mesh is None:
        return 1
    return int(np.prod(list(mesh.shape.values())))


def first_axis_name(mesh: Mesh) -> str:
    """The mesh's leading (in practice: only) axis name — the one physical
    axis the data-sharded score tables AND the entity-sharded random-effect
    solve bins both split over.  One accessor so the two layouts cannot
    silently pick different axes on a future multi-axis mesh."""
    return next(iter(mesh.shape))


def axis_sharding(
    mesh: Mesh, ndim: int, axis: int = 0, axis_name: str = DATA_AXIS
) -> NamedSharding:
    """Sharding that splits dimension ``axis`` of an ``ndim``-array over
    ``axis_name`` and replicates every other dimension."""
    spec = [None] * ndim
    spec[axis] = axis_name
    return NamedSharding(mesh, P(*spec))


def put_sharded(x, mesh: Optional[Mesh], axis: int = 0,
                axis_name: str = DATA_AXIS):
    """Place a pytree of arrays with dimension ``axis`` sharded over the
    mesh (``mesh=None`` just materializes device arrays).

    The residual/validation engines and the coordinate scoring caches use
    this for per-row state (score rows, feature shards, entity indices):
    each device holds only its row slice — one copy of the data across the
    mesh instead of one copy per device — and the per-coordinate offset /
    compensated-total kernels stay element-wise per shard, with GSPMD
    inserting the collectives (psum for metric reductions, gathers for
    cross-shard row selection) where an op genuinely crosses shards.
    The sharded dimension must already be padded to a multiple of the mesh
    size (:func:`pad_to_multiple`; padded rows carry weight 0).
    """
    if mesh is None:
        return jax.tree.map(jnp.asarray, x)
    return jax.tree.map(
        lambda leaf: jax.device_put(
            leaf, axis_sharding(mesh, leaf.ndim, axis, axis_name)
        ),
        x,
    )


_RESHARD_CACHE: dict = {}


def reshard(x: jax.Array, sharding: NamedSharding) -> jax.Array:
    """Re-place a DEVICE array onto ``sharding`` through a jitted identity.

    ``jax.device_put`` on committed multi-process arrays cannot always move
    data across processes; a jitted identity with ``out_shardings`` lets
    XLA insert the collective instead, and is a no-op when the sharding
    already matches.  Jitted identities are cached per sharding so repeated
    calls (one per descent iteration) never retrace.
    """
    fn = _RESHARD_CACHE.get(sharding)
    if fn is None:
        fn = jax.jit(lambda y: y, out_shardings=sharding)
        _RESHARD_CACHE[sharding] = fn
    return fn(x)


def pad_to_multiple(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k


def reshard_to_mesh(x, mesh: Optional[Mesh], axis: int = 0,
                    axis_name: str = DATA_AXIS, pad_value=0):
    """Re-pad and re-shard one array onto the CURRENT mesh — the elastic-
    resume placement path.

    ``x`` holds a LOGICAL (unpadded) dimension along ``axis`` — score rows,
    labels, per-row entity indices — possibly written by a run on a
    different device/process count.  The dimension is padded with
    ``pad_value`` up to a multiple of THIS mesh's size (the padding
    convention everywhere: padded rows carry weight 0 / entity index -1,
    invisible to kernels and metrics) and the result is placed sharded over
    ``axis_name``.  Host numpy uploads directly; device arrays re-place
    through the jitted-identity :func:`reshard` (safe for committed
    multi-process arrays).  ``mesh=None`` just materializes a device array
    — so one code path serves every mesh shape, including none.

    This is deliberately the ONLY coupling between a checkpoint and the
    mesh that restores it: checkpoints record logical layouts, and every
    padded/sharded buffer is rebuilt HERE against whatever mesh the
    resuming run constructed (see photon_tpu.fault.checkpoint).
    """
    if mesh is None:
        return jnp.asarray(x)
    # Pad to the multiple of the WHOLE mesh (product of axes), not just the
    # sharded axis: the engines' preallocated tables and caches size n_pad
    # with mesh_shards(mesh), and the two must never disagree on a
    # multi-axis mesh (a product-multiple is always divisible by the
    # sharded axis's extent, so the placement below stays valid).
    n_shards = mesh_shards(mesh)
    length = x.shape[axis]
    short = pad_to_multiple(length, n_shards) - length
    sharding = axis_sharding(mesh, x.ndim, axis, axis_name)
    if isinstance(x, jax.Array):
        if short:
            widths = [(0, 0)] * x.ndim
            widths[axis] = (0, short)
            x = jnp.pad(x, widths, constant_values=pad_value)
        return reshard(x, sharding)
    host = np.asarray(x)
    if short:
        widths = [(0, 0)] * host.ndim
        widths[axis] = (0, short)
        host = np.pad(host, widths, constant_values=pad_value)
    return jax.device_put(host, sharding)


def put_request(x, mesh: Optional[Mesh]):
    """Place one serving request-batch buffer (a pytree of small host
    arrays) for the online scoring hot path.

    Request micro-batches are tiny next to the model gather tables, so they
    are REPLICATED over the mesh: every shard reads the whole batch and the
    per-row gather against the row-sharded tables resolves with one
    collective on the table side instead of re-sharding a few-hundred-row
    buffer every request.  Today that makes this exactly
    :func:`put_replicated`; the alias exists so the serving request layout
    is decided in ONE place — the pre-compiled bucket programs
    (photon_tpu.serving.scorer) are lowered against buffers placed here,
    and every later request must hit the exact compiled layout or it would
    force a recompile.
    """
    return put_replicated(x, mesh)


def abstract_like(x):
    """``jax.ShapeDtypeStruct`` pytree mirroring ``x``'s shapes, dtypes,
    and shardings — AOT-lowering inputs (``jax.jit(f).lower(...)``) without
    keeping sample buffers alive.  The serving scorer lowers each bucket
    program against abstract request buffers shaped by this, then compiles
    once; committed-array leaves carry their sharding into the lowering so
    the compiled program pins the exact runtime placement."""
    return jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape,
            leaf.dtype,
            sharding=leaf.sharding if isinstance(leaf, jax.Array) else None,
        ),
        x,
    )


def to_host(x) -> np.ndarray:
    """``np.asarray`` that also works for multi-process sharded arrays.

    In a multi-process job a globally-sharded ``jax.Array`` spans devices
    this process cannot address; fetching it raises.  Gather the shards
    across processes first (every host gets the full array — host fetches
    in this framework are small: solver stats, model tables, score
    vectors).  Single-process arrays pass straight through.
    """
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.experimental import multihost_utils

        x = multihost_utils.process_allgather(x, tiled=True)
    return np.asarray(x)
