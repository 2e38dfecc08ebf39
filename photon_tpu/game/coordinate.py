"""GAME coordinates: fixed-effect and random-effect training units.

Rebuild of the reference's ``algorithm.Coordinate`` hierarchy
(``FixedEffectCoordinate`` / ``RandomEffectCoordinate`` — SURVEY.md §2.2,
§3.1): a coordinate owns one slice of the model, can ``train`` it against the
residuals (offsets) of the other coordinates, and can ``score`` data with it.

TPU-native shapes (SURVEY.md §2.5 parallelism table):

- **FixedEffectCoordinate** — whole-dataset GLM fit: the batch is sharded
  over the mesh's data axis and gradients ``psum`` over ICI
  (DistributedGlmObjective); the reference's broadcast + treeAggregate loop
  collapses into one XLA program per optimizer run.
- **RandomEffectCoordinate** — per-entity independent solves: each row-count
  bucket is a ``[E, R, ...]`` block, and the whole per-entity solver
  (L-BFGS/OWL-QN/TRON with masked line search) runs under ``jax.vmap`` over
  the entity axis — thousands of entity solves advance in lockstep, with
  converged lanes frozen (SURVEY.md §7 'hard parts').  Under a mesh the
  entity axis is sharded across chips, the analog of the reference's
  ``RandomEffectDatasetPartitioner`` hash partitioning.

Device-resident data is cached in dataset objects (``FixedEffectDeviceData``
/ ``RandomEffectDeviceData``) that coordinates share across sweep
configurations and descent iterations — only the per-iteration offsets move
host→device (the reference, by contrast, re-broadcasts coefficients every
iteration).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Protocol, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_tpu.core.normalization import NormalizationContext
from photon_tpu.core.objective import GlmObjective
from photon_tpu.core.optimizers import OptimizationStatesTracker
from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig
from photon_tpu.data.batch import DenseBatch, SparseBatch, with_offset
from photon_tpu.game.data import (
    DenseShard,
    EntityBucket,
    Float,
    GameDataset,
    RandomEffectDataset,
    _gather_shard_rows,
    build_random_effect_dataset,
    SparseShard,
    entity_index_for,
    keys_match,
    pad_bucket_entities,
    pad_bucket_rows,
)
from photon_tpu.game.model import (
    FixedEffectModel,
    RandomEffectModel,
    _shard_feats,
    count_sparse_entries,
    shard_to_batch,
)
from photon_tpu.models.glm import Coefficients, model_for_task
from photon_tpu.telemetry import NULL_SESSION
from photon_tpu.utils.device import count_h2d, named_jit
from photon_tpu.parallel.mesh import (
    DATA_AXIS,
    first_axis_name,
    mesh_shards,
    pad_to_multiple,
    put_sharded,
    reshard,
    shard_batch,
    to_host,
)

Array = jax.Array


@functools.partial(named_jit, "gather_rows")
def _gather_rows(offsets: Array, row_index: Array) -> Array:
    """Device row gather: the fixed effect's downsample selection applied to
    a device-resident offsets vector."""
    return offsets[row_index]


@functools.partial(named_jit, "gather_bucket_offsets")
def _gather_bucket_offsets(offsets: Array, row_index: Array, mask: Array) -> Array:
    """Per-bucket offset gather on device: ``offsets[row_index] * mask``
    against the pre-uploaded ``[E, R]`` row-index/mask buffers — replaces the
    host fancy-index + fresh upload the seed paid per bucket per iteration."""
    return offsets[row_index] * mask


@functools.partial(named_jit, "accumulate_solve_stats")
def _accumulate_solve_stats(
    acc: Array, entity_index: Array, num_entities, converged: Array,
    iterations: Array, good: Array, cg_iterations: Array | None = None,
    bin_slot: Array | None = None,
) -> Array:
    """Fold one bucket's solve results into the per-coordinate int32 stats
    accumulator ``[entities, converged, iterations_max, quarantined,
    cg_iters, cg_entities, *bin_iterations]`` — entirely on device, so a coordinate's
    train() emits NO host sync of its own: the descent loop drains every
    coordinate's accumulator (plus the score-table guard flags) in ONE
    ``device_get`` per outer iteration.  Padded entities (``entity_index
    >= num_entities``) — bin-padding and mesh-padding slots alike — are
    masked out of every component, so they can never inflate ``entities``
    or ``converged``; a quarantined (non-finite) entity is not counted
    converged either — its "solution" was discarded.  ``cg_iterations``
    (per-entity inner-CG totals, from the bins whose solver has a CG
    inner loop, Newton-CG or TRON — see ``OptimizerResult.cg_iterations``) sums into the ``cg_iters`` slot,
    and the SAME bins' real entities into ``cg_entities`` — the correct
    per-entity-mean denominator when a coordinate mixes CG and non-CG
    bins (projected buckets can differ in solve_dim); other routes
    contribute 0 to both.  Past the six totals the accumulator may carry
    one slot per bin: with ``bin_slot`` (a scalar index) this bucket's own
    lockstep iteration count — the most iterations any of its real
    entities ran, which is what its whole ``[E, R]`` program ran — lands in
    slot ``6 + bin_slot``, so every bin's count of every descent iteration
    rides the same drain (``solves.newton_iterations``)."""
    real = entity_index < num_entities
    real_i = real.astype(jnp.int32)
    if cg_iterations is None:
        cg = cg_ents = jnp.asarray(0, jnp.int32)
    else:
        cg = (cg_iterations.astype(jnp.int32) * real_i).sum()
        cg_ents = real_i.sum()
    lockstep = jnp.max(jnp.where(real, iterations.astype(jnp.int32), 0))
    totals = jnp.stack([
        acc[0] + real_i.sum(),
        acc[1] + ((converged & good).astype(jnp.int32) * real_i).sum(),
        jnp.maximum(acc[2], lockstep),
        acc[3] + ((~good).astype(jnp.int32) * real_i).sum(),
        acc[4] + cg,
        acc[5] + cg_ents,
    ])
    per_bin = acc[6:]
    if bin_slot is not None:
        per_bin = per_bin.at[bin_slot].set(lockstep)
    return jnp.concatenate([totals, per_bin])


@jax.jit
def _count_quarantined(acc: Array, good: Array) -> Array:
    """Add a non-finite-row count to the accumulator's quarantined slot
    (the factored coordinate's materialized-table guard)."""
    return acc.at[3].add((~good).astype(jnp.int32).sum())


class DeferredSolveStats:
    """A coordinate train()'s convergence stats as ONE device int32 vector.

    The descent loop collects these per coordinate and drains them all in
    a single host sync at the outer-iteration boundary
    (``descent.host_syncs``); :meth:`resolve` turns the fetched vector into
    the stats dict the telemetry/logging paths consume.  Direct callers
    (tests, benches) can index it like the old dict — the first access
    lazily fetches.  ``extra`` carries static host-side entries (e.g. the
    factored coordinate's ``latent_iterations``)."""

    KEYS = ("entities", "converged", "iterations_max", "quarantined",
            "cg_iters", "cg_entities")

    def __init__(self, device: Array, extra: Optional[dict] = None):
        self.device = device
        self.extra = dict(extra or {})
        self._resolved: Optional[dict] = None

    def resolve(self, host_vec=None) -> dict:
        """The stats dict; ``host_vec`` is the pre-fetched ``[6]`` vector
        from the descent boundary drain (without it, direct callers pay
        their own fetch here — off the descent hot loop)."""
        if self._resolved is None:
            if host_vec is None:
                # host-sync: direct-caller fetch (tests/benches) — the
                # descent loop always passes the batched host_vec instead.
                host_vec = np.asarray(self.device)
            stats = {k: int(host_vec[i]) for i, k in enumerate(self.KEYS)}
            if len(host_vec) > len(self.KEYS):
                # One lockstep iteration count per bin (see
                # _accumulate_solve_stats); ``extra`` says what each bin is.
                stats["bin_iterations"] = [
                    int(v) for v in host_vec[len(self.KEYS):]
                ]
            stats.update(self.extra)
            self._resolved = stats
        return self._resolved

    def __getitem__(self, key):
        return self.resolve()[key]

    def get(self, key, default=None):
        return self.resolve().get(key, default)

    def __contains__(self, key):
        return key in self.resolve()

    def __str__(self):
        return str(self.resolve()) if self._resolved is not None else (
            f"DeferredSolveStats(pending, extra={self.extra})"
        )


def _foreign_src_idx(device_data, model_keys) -> np.ndarray:
    """Cached foreign-vocabulary join: ``src_idx[e]`` is the row of
    ``model_keys`` holding this dataset's entity ``e`` (-1 = absent).

    The O(E) host key join used to run once per warm start — once per
    (configuration × iteration) for a sweep warm-started from disk.  It is
    keyed by the keys OBJECT's identity and cached on the shared device
    data (the cached entry pins the keys array, so the id cannot be
    recycled), closing part of the ROADMAP "host-resident paths" edge.
    A cache entry may hold an io-pool Future (the join PREFETCHED while the
    fixed-effect coordinate trains — :func:`prefetch_warm_joins`); the
    first consumer resolves it, so the first-hit join overlaps compute
    instead of blocking the coordinate sweep."""
    from concurrent.futures import Future

    cache = device_data._warm_join_cache
    hit = cache.get(id(model_keys))
    if hit is not None and hit[0] is model_keys:
        src_idx = hit[1]
        if isinstance(src_idx, Future):
            # host-sync: resolving a prefetched join Future — host numpy
            # computed on the io pool, no device data involved.
            src_idx = src_idx.result()
            cache[id(model_keys)] = (model_keys, src_idx)
        return src_idx
    # host-sync: foreign-vocabulary key join (host keys) — once per
    # distinct warm-start vocabulary, cached after.
    src_idx = entity_index_for(
        device_data.dataset.keys, np.asarray(model_keys)
    )
    if len(cache) >= 8:
        cache.pop(next(iter(cache)))
    cache[id(model_keys)] = (model_keys, src_idx)
    return src_idx


def prefetch_warm_joins(coordinates, initial_model) -> int:
    """Schedule the FIRST-HIT foreign-vocabulary warm-start key joins on
    the io pool so they overlap the fixed-effect coordinate's training
    instead of blocking the first random coordinate's train() (ROADMAP
    "remaining known edges"; ISSUE 10 satellite).

    For every random-effect coordinate whose warm-start model carries a
    vocabulary that is NOT this run's own keys object, the O(E) host
    ``entity_index_for`` join is submitted as a background job and parked
    in the coordinate's warm-join cache as a Future;
    :func:`_foreign_src_idx` resolves it on first use.  The
    ``descent.host_transfer_bytes{path=warm_start}`` accounting is
    untouched — it meters the table transfers in ``_align_foreign_table``,
    which still run at consume time.  Returns the number of joins
    scheduled."""
    from photon_tpu.game.model import RandomEffectModel
    from photon_tpu.utils import io_pool

    scheduled = 0
    for name, coord in coordinates.items():
        device_data = getattr(coord, "device_data", None)
        dataset = getattr(device_data, "dataset", None)
        if dataset is None:
            continue
        model = initial_model.coordinates.get(name)
        if not isinstance(model, RandomEffectModel):
            continue
        # host-sync: key identity/value compare (host vocabularies) — the
        # same gate _initial_table applies; same-run models skip the join.
        if keys_match(model.keys, dataset.keys):
            continue
        cache = device_data._warm_join_cache
        hit = cache.get(id(model.keys))
        if hit is not None and hit[0] is model.keys:
            continue  # already joined (or already scheduled)
        model_keys = model.keys
        fut = io_pool.submit(
            # host-sync: the prefetched join is pure host numpy, computed
            # on an io-pool thread while the fixed effect trains.
            lambda keys=dataset.keys, mk=model_keys: entity_index_for(
                keys, np.asarray(mk)
            )
        )
        if len(cache) >= 8:
            cache.pop(next(iter(cache)))
        cache[id(model_keys)] = (model_keys, fut)
        scheduled += 1
    return scheduled


def _align_foreign_table(coord, initial_model) -> np.ndarray:
    """Key-aligned host ``[E+1, dim]`` table of a FOREIGN warm-start model
    (unseen entities zero; the dummy slot absorbs padded entities), with the
    join's host traffic recorded as ``descent.host_transfer_bytes``
    ``path=warm_start`` — the once-per-warm-start transfers the ROADMAP
    flags, now visible next to the engines' steady-state counters."""
    telemetry = getattr(coord, "telemetry", NULL_SESSION)
    aligned = np.zeros(
        (coord.dataset.num_entities + 1, coord.dim), np.float32
    )
    src_idx = _foreign_src_idx(coord.device_data, initial_model.keys)
    found = src_idx >= 0
    # host-sync: foreign warm start — the table fetch of the join.
    table = to_host(initial_model.table)
    telemetry.counter(
        "descent.host_transfer_bytes", direction="d2h", path="warm_start"
    ).inc(table.nbytes)
    aligned[:-1][found] = table[src_idx[found]]
    telemetry.counter(
        "descent.host_transfer_bytes", direction="h2d", path="warm_start"
    ).inc(aligned.nbytes)
    return aligned


def _bucket_offsets(device_data, i: int, bucket, offsets) -> Array:
    """Training offsets for bucket ``i``: a jitted device gather when the
    residual engine hands a device vector, the seed's host fancy-index +
    upload when given a numpy vector (``PHOTON_RESIDUALS=host``)."""
    if isinstance(offsets, jax.Array):
        row_index, row_mask = device_data.gather_buffers(i)
        return _gather_bucket_offsets(offsets, row_index, row_mask)
    return jnp.asarray(
        offsets[bucket.row_index] * (bucket.row_weight > 0), jnp.float32
    )


@jax.jit
def _restrict_index_map(table: Array, proj_ids: Array, mask: Array) -> Array:
    """Device warm-start restriction for index-map projections: gather each
    entity's active global columns into its local slots (the device analog
    of ``IndexMapBucketProjection.restrict_table``)."""
    return jnp.take_along_axis(table, proj_ids, axis=1) * mask


@jax.jit
def _restrict_random(table: Array, matrix: Array, inv_col_norms: Array) -> Array:
    """Device warm-start restriction for random projections: the
    column-normalized least-squares pullback of
    ``RandomProjectionMatrix.restrict_table``."""
    return (table @ matrix) * inv_col_norms


def _score_pad(coord) -> int:
    """Padded row count of the coordinate's scoring caches and score rows:
    the training row count rounded up to a multiple of the mesh size (the
    residual engine pads identically, so score rows line up shard for
    shard)."""
    return pad_to_multiple(coord.data.num_examples, mesh_shards(coord.mesh))


def _scoring_feats(coord) -> tuple:
    """The coordinate's training-shard features as device arrays, uploaded
    once and cached on the coordinate's shared ``device_data`` (which the
    estimator reuses across sweep configurations, unlike the coordinate
    objects themselves), SHARDED over the mesh data axis: the residual
    engine re-scores every coordinate every outer iteration, and the seed's
    ``model.score(data)`` re-uploaded the shard each time.

    This cache is a SECOND device copy of the shard's features (the training
    copies live row-selected/bucketed in the batch structures and cannot
    serve full-row-order scoring) — a deliberate memory-for-transfers
    trade.  One coordinate never asks for it: a sparse fixed effect whose
    training batch is the whole shard in row order on one device and
    carries block tiles scores from those tiles
    (:meth:`FixedEffectCoordinate.score_device`), so its entries are
    resident once.  Sharding it over the data axis (rows zero-padded to the
    mesh multiple) keeps that trade to ONE extra copy across the whole mesh
    rather than the one-per-device the replicated cache used to cost.
    ``_score_cache_bytes`` makes the residency visible (the descent loop
    exports it as the ``residuals.scoring_cache_bytes`` gauge — global
    bytes; per-device residency divides by the mesh size);
    ``PHOTON_RESIDUALS=host`` never pays it."""
    holder = coord.device_data
    if holder._score_feats is None:
        from photon_tpu.game.model import _shard_feats_padded

        leaves, dense = _shard_feats_padded(
            coord.data.shard(coord.config.shard_name), _score_pad(coord)
        )
        dev_feats = put_sharded(leaves, coord.mesh)
        count_h2d("scoring_cache", dev_feats)
        holder._score_feats = (dev_feats, dense)
        holder._score_cache_bytes += sum(
            leaf.nbytes for leaf in jax.tree.leaves(dev_feats)
        )
    return holder._score_feats


def _random_score_device(coord, model) -> Array:
    """Device-resident training-data margins for a random-effect model:
    gather-join against the cached per-row entity index (the common case —
    the model was trained on this coordinate's vocabulary); a warm-start
    model with a different vocabulary joins by key on host once.  A model
    whose feature-shard/entity-column layout differs from the coordinate's
    config scores through its own host path — the device caches hold the
    coordinate's shard, not the model's."""
    if (model.shard_name != coord.config.shard_name
            or model.entity_column != coord.config.entity_column):
        return model.score(coord.data)
    feats, dense = _scoring_feats(coord)
    holder = coord.device_data
    n_pad = _score_pad(coord)

    def pad_idx(idx: np.ndarray) -> np.ndarray:
        # Padding rows carry entity index -1 -> zero margins.
        return np.pad(
            idx.astype(np.int32), (0, n_pad - len(idx)), constant_values=-1
        )

    # host-sync: foreign-vocabulary key compare (warm starts from disk);
    # same-run models hit the identity check inside keys_match.
    if keys_match(model.keys, coord.dataset.keys):
        if holder._score_entity_idx is None:
            holder._score_entity_idx = put_sharded(
                pad_idx(coord.dataset.entity_idx_per_row), coord.mesh
            )
            holder._score_cache_bytes += holder._score_entity_idx.nbytes
        entity_idx = holder._score_entity_idx
    else:
        entity_idx = put_sharded(
            pad_idx(entity_index_for(
                coord.data.id_columns[coord.config.entity_column],
                # host-sync: foreign-vocabulary key join (host keys; the
                # warm-start path — not the descent steady state).
                np.asarray(model.keys),
            )),
            coord.mesh,
        )
    return model.margins_device(entity_idx, feats, dense)


@dataclasses.dataclass(frozen=True)
class FixedEffectCoordinateConfig:
    """Reference: FixedEffectDataConfiguration + per-coordinate optimization
    config inside GameOptimizationConfiguration."""

    # Coordinate kind, shared by the config and its coordinate class: the
    # checkpoint fingerprint's logical-layout component (fault.checkpoint
    # .logical_layout) — what a coordinate IS, independent of mesh shape.
    kind = "fixed"

    shard_name: str
    problem: ProblemConfig = ProblemConfig()
    downsampling_rate: float = 1.0  # <1: train on a subsample
    downsampler: str = "default"  # default (uniform) | binary (negatives only)
    seed: int = 0  # subsample seed

    @property
    def data_key(self):
        return (
            "fixed", self.shard_name, self.downsampling_rate,
            self.downsampler, self.seed,
        )


@dataclasses.dataclass(frozen=True)
class RandomEffectCoordinateConfig:
    """Reference: RandomEffectDataConfiguration (entity id column a.k.a.
    randomEffectType, feature shard, active-data upper bound)."""

    kind = "random"

    shard_name: str
    entity_column: str
    problem: ProblemConfig = ProblemConfig()
    active_row_cap: Optional[int] = None
    # Feature projection for the per-entity solves (reference: data/projectors
    # — SURVEY.md §2.2): none | index_map (per-entity active features) |
    # random (sparse-sign matrix to projected_dim).
    projection: str = "none"
    projected_dim: Optional[int] = None
    seed: int = 0
    # Row-split placement (README §scale-out): instead of sharding the ENTITY
    # axis over the mesh, every shard holds a ROW slice of every entity and
    # per-entity data terms psum — for entities whose rows exceed one
    # shard/host (the reference co-locates them with a shuffle; here no row
    # moves).  Ignored without a mesh.
    row_split: bool = False

    def __post_init__(self):
        if self.projection not in ("none", "index_map", "random"):
            raise ValueError(f"unknown projection {self.projection!r}")
        if self.projection == "random" and not self.projected_dim:
            raise ValueError("random projection needs projected_dim")
        if self.row_split and self.projection == "index_map":
            # Per-entity index-map projection picks each entity's active
            # features from its OWN rows; under row-split a shard sees only
            # a row slice, so the projection would differ per shard.
            raise ValueError("row_split does not support index_map projection")

    @property
    def data_key(self):
        return (
            "random",
            self.shard_name,
            self.entity_column,
            self.active_row_cap,
            self.projection,
            self.projected_dim,
            self.seed,
            self.row_split,
        )


@dataclasses.dataclass(frozen=True)
class FactoredRandomEffectCoordinateConfig:
    """Latent-factor random effect (reference: FactoredRandomEffectCoordinate,
    SURVEY.md §2.2 [K?]): per-entity coefficients are constrained to a shared
    ``latent_dim``-rank subspace, ``w_e = L z_e`` with ``L: [d, r]`` learned
    on pooled data and ``z_e`` per entity — regularizing entities with few
    rows far harder than a free per-entity fit."""

    kind = "factored_random"

    shard_name: str
    entity_column: str
    latent_dim: int = 4
    problem: ProblemConfig = ProblemConfig()
    # Alternations between the per-entity z solves and the pooled L solve
    # (the reference's latent-space iteration count).
    latent_iterations: int = 2
    active_row_cap: Optional[int] = None
    seed: int = 0

    def __post_init__(self):
        if self.latent_dim < 1:
            raise ValueError("latent_dim must be >= 1")
        if self.latent_iterations < 2:
            # li=1 would fit z against the random-init projection and never
            # solve L; li=0 would return an all-zero model.
            raise ValueError("latent_iterations must be >= 2 (z,L,...,z)")
        if self.problem.variance_computation != "none":
            raise ValueError(
                "variance computation is not supported for factored random "
                "effects (z-space variances do not transport to w = L z)"
            )
        if self.problem.regularization.l1_weight > 0 or (
            self.problem.optimizer.lower() not in ("lbfgs", "l-bfgs")
        ):
            raise ValueError(
                "factored random effects support lbfgs with none/l2 "
                "regularization only (the pooled projection solve is a "
                "smooth L-BFGS problem)"
            )

    @property
    def data_key(self):
        # Same device data as an unprojected random coordinate (the latent
        # projection is learned, so buckets hold raw features) — delegate so
        # the estimator's device-data cache shares entries by construction.
        return self.as_random_config().data_key

    def as_random_config(self) -> "RandomEffectCoordinateConfig":
        return RandomEffectCoordinateConfig(
            shard_name=self.shard_name,
            entity_column=self.entity_column,
            problem=self.problem,
            active_row_cap=self.active_row_cap,
            seed=self.seed,
        )


CoordinateConfig = Union[
    FixedEffectCoordinateConfig,
    RandomEffectCoordinateConfig,
    FactoredRandomEffectCoordinateConfig,
]


class Coordinate(Protocol):
    def train(self, offsets: np.ndarray, initial_model=None): ...

    def score(self, model) -> np.ndarray: ...


# ---------------------------------------------------------------------------
# Device-resident datasets (shared across sweep configurations)
# ---------------------------------------------------------------------------


def _pad_fixed_rows(shard, label, offset, weight, target_n):
    """Host-side row padding for the fixed-effect batch's row-capacity
    headroom: pad rows carry weight 0 (inert in every weighted objective),
    zero features (ids=0/vals=0 for sparse — a no-op gather), and zero
    label/offset.  Padding on HOST, before :func:`shard_to_batch` uploads,
    is what makes a capacity rebuild compile-free — the device only ever
    sees the capacity shape."""
    n = len(label)
    pad = target_n - n
    # host-sync: every input here is caller-owned host numpy (this runs
    # BEFORE the one device upload) — the asarray calls are dtype casts.
    label = np.pad(np.asarray(label, np.float32), (0, pad))
    # host-sync: host numpy offset (pre-upload).
    offset = None if offset is None else np.pad(
        np.asarray(offset, np.float32), (0, pad)
    )
    # A None weight means "all ones" — materialize it so the pad rows can
    # carry the zeros that keep them out of the loss.
    # host-sync: host numpy weight (pre-upload).
    weight = np.pad(
        np.ones(n, np.float32) if weight is None
        else np.asarray(weight, np.float32),
        (0, pad),
    )
    if isinstance(shard, DenseShard):
        # host-sync: host numpy shard rows (pre-upload).
        shard = DenseShard(
            np.pad(np.asarray(shard.x), ((0, pad), (0, 0)))
        )
    else:
        shard = SparseShard(
            # host-sync: host numpy shard rows (pre-upload).
            np.pad(np.asarray(shard.ids), ((0, pad), (0, 0))),
            np.pad(np.asarray(shard.vals), ((0, pad), (0, 0))),
            shard.dim_,
        )
    return shard, label, offset, weight


class FixedEffectDeviceData:
    """The fixed-effect training batch, resident on device (sharded over the
    mesh's data axis when a mesh is given).  Built once per (shard,
    downsampling) data config; reused across the regularization sweep."""

    def __init__(
        self,
        data: GameDataset,
        config: FixedEffectCoordinateConfig,
        mesh=None,
        build_fm: bool = True,
        row_capacity: Optional[int] = None,
    ):
        self.mesh = mesh
        shard = data.shard(config.shard_name)
        self.dim = shard.dim
        self.train_rows: Optional[np.ndarray] = None
        label, offset, weight = data.label, data.offset, data.weight
        if config.downsampling_rate < 1.0:
            # Weight-corrected subsample (the reference's DownSampler on the
            # fixed-effect dataset; `binary` keeps positives and thins
            # negatives — data.sampling).
            from photon_tpu.data.sampling import get_down_sampler

            sampler = get_down_sampler(config.downsampler, config.downsampling_rate)
            keep, corrected = sampler.down_sample(label, weight, seed=config.seed)
            self.train_rows = keep
            shard = _gather_shard_rows(shard, keep)
            label = label[keep]
            offset = offset[keep]
            weight = corrected
        self.unpadded_n = len(label)
        if row_capacity is not None and row_capacity > self.unpadded_n:
            # Row-capacity headroom (ISSUE 18 satellite): weight-0 pad rows
            # on HOST, ahead of the device upload and aux construction, so
            # a refresh that rebuilds this layout at the SAME capacity
            # reproduces the batch shape exactly — the upload lands at the
            # (unchanged) padded shape, every program compiled against it
            # stays hot, and nothing recompiles.  Pad rows are inert in the
            # solve (the loss is weight-summed) and invisible to scoring
            # (score paths read the shard, or the batch's tiles cut to the
            # true row count: a pad row holds no entry).
            shard, label, offset, weight = _pad_fixed_rows(
                shard, label, offset, weight, row_capacity
            )
        self.batch = shard_to_batch(shard, label, offset, weight)
        count_h2d("fixed_shard", self.batch)
        self._train_rows_dev: Optional[Array] = None
        # Device scoring cache (residual engine): full-row-order shard
        # features + residency accounting, filled by _scoring_feats.
        self._score_feats: Optional[tuple] = None
        self._score_cache_bytes: int = 0
        if mesh is not None:
            # Same fast-kernel eligibility as single-device: the per-shard
            # aligned layouts are built when the selector could route to
            # them (decided inside attach_feature_major — VERDICT r5 item 2).
            self.batch = shard_batch(
                self.batch, mesh, build_fm=build_fm, aligned_dim=self.dim
            )
        elif build_fm and isinstance(self.batch, SparseBatch):
            from photon_tpu.data.batch import attach_feature_major

            # Single-device: the GAME fixed effect is the framework's big
            # sparse solve, so it gets the same fast-kernel eligibility as
            # the legacy driver (the selector's verdict first, then the
            # winner's layout alone).
            self.batch = attach_feature_major(self.batch, aligned_dim=self.dim)

    def offsets_to_device(self, offsets) -> Array:
        """Training offsets ready for the batch: accepts the residual
        engine's device vector — already padded to the mesh multiple, so the
        row gather / pad below is sized off the ACTUAL length — or a host
        numpy vector (the seed's upload path)."""
        if isinstance(offsets, jax.Array):
            dev = offsets
            if self.train_rows is not None:
                if self._train_rows_dev is None:
                    self._train_rows_dev = jnp.asarray(self.train_rows)
                dev = _gather_rows(dev, self._train_rows_dev)
        else:
            if self.train_rows is not None:
                offsets = offsets[self.train_rows]
            # host-sync: caller-owned host numpy on the seed path (this
            # branch never sees device data — jax.Array took the one above).
            offsets = np.asarray(offsets, np.float32)
            pad = self.batch.num_examples - offsets.shape[0]
            if pad:
                # Pad on HOST: the upload then always lands at the batch's
                # (capacity) shape, so a refresh at a new true row count
                # compiles nothing on the seed path.
                offsets = np.pad(offsets, (0, pad))
            dev = jnp.asarray(offsets)
        short = self.batch.num_examples - dev.shape[0]
        if short:
            # Device vectors (the residual engine's total) pad on device:
            # covers both the mesh pad-to-shard-multiple and single-device
            # row-capacity headroom (pad rows carry weight 0, so their
            # offset value never reaches the loss).
            dev = jnp.pad(dev, (0, short))
        if self.mesh is None:
            return dev
        return reshard(dev, NamedSharding(self.mesh, P(DATA_AXIS)))


class RandomEffectDeviceData:
    """Bucketed per-entity data resident on device, entity axis sharded over
    the mesh.  Holds everything except offsets, which change per descent
    iteration.

    The raw power-of-two row-capacity buckets are consolidated into SIZE
    BINS (``game.batched_solve.bin_layout``) before upload: each bin is one
    padded ``[E, R, ...]`` block solved by a single jitted program —
    ``self.buckets`` / ``self.device_buckets`` hold the binned blocks, and
    ``self.bin_stats`` records each bin's padding economics for the
    ``solves.*`` telemetry gauges.  New entities arriving between fits
    extend the layout in place via :meth:`onboard` (appended bins, remapped
    indices) instead of a full rebuild."""

    def __init__(
        self,
        data: GameDataset,
        config: RandomEffectCoordinateConfig,
        mesh=None,
    ):
        self.mesh = mesh
        self.config = config
        self.dataset: RandomEffectDataset = build_random_effect_dataset(
            data,
            entity_column=config.entity_column,
            shard_name=config.shard_name,
            active_row_cap=config.active_row_cap,
            seed=config.seed,
        )
        self.dim = self.dataset.dim
        n_shards = mesh_shards(mesh)
        self.row_split = bool(getattr(config, "row_split", False)) and n_shards > 1
        # Optional feature projection shrinks each bucket's solve dimension
        # (reference: data/projectors — see game.projection).
        self.random_matrix = None
        if config.projection == "random":
            from photon_tpu.game.projection import build_random_projection

            self.random_matrix = build_random_projection(
                self.dim, config.projected_dim, seed=config.seed
            )
        # Device scoring cache (residual engine): full-row-order shard
        # features + per-row entity index + residency accounting, filled by
        # _scoring_feats / _random_score_device.
        self._score_feats: Optional[tuple] = None
        self._score_entity_idx: Optional[Array] = None
        self._score_cache_bytes: int = 0
        # Foreign-vocabulary warm-start join cache: keys-object identity ->
        # src_idx (see _align_foreign_table) — the O(E) host key join is
        # paid once per distinct warm-start vocabulary, not once per warm
        # start.
        self._warm_join_cache: dict = {}
        # Size-binned device blocks: features / label / weight / entity idx
        # per bin.
        self.buckets: list = []
        self.device_buckets: list = []
        self.bin_stats: list = []
        # Per-entity placement index (bin / slot / used rows), built lazily
        # by _entity_locator for the in-place growth path and invalidated
        # whenever the layout changes.
        self._locator = None
        self._append_bins(self.dataset.buckets)

    def _append_bins(self, raw_buckets) -> None:
        """Bin ``raw_buckets`` (host ``EntityBucket``s over THIS dataset's
        entity indices), pad for the mesh placement, upload, and append to
        the device layout — the shared path of __init__ and onboard()."""
        from photon_tpu.game.batched_solve import bin_layout
        from photon_tpu.game.data import merge_buckets

        from photon_tpu import telemetry

        n_shards = mesh_shards(self.mesh)
        for group in bin_layout(raw_buckets):
            with telemetry.span(
                "layout.entity_bins", column=self.config.entity_column,
                buckets=len(group),
            ):
                merged = merge_buckets([raw_buckets[i] for i in group])
                live_entities = merged.num_entities
                live_rows = int((merged.row_weight > 0).sum())
                if self.row_split:
                    # Entities replicated, each entity's ROWS sharded over
                    # the mesh (solve_entities_row_split); pad row
                    # capacity, not entities.
                    merged = pad_bucket_rows(merged, n_shards)
                else:
                    merged = pad_bucket_entities(
                        merged, n_shards, self.dataset.num_entities
                    )
            self.buckets.append(merged)
            self.bin_stats.append({
                "capacity": merged.row_capacity,
                "live_entities": live_entities,
                "total_entities": merged.num_entities,
                "live_rows": live_rows,
            })
            self.device_buckets.append(self._build_device_bucket(merged))

    def _build_device_bucket(self, bucket) -> dict:
        config = self.config
        feats = bucket.features
        proj = None
        if config.projection == "index_map":
            from photon_tpu.game.projection import build_index_map_projection

            proj = build_index_map_projection(bucket)
        elif config.projection == "random":
            proj = self.random_matrix
        if proj is not None:
            feats = proj.project(feats)
        solve_dim = self.dim if proj is None else proj.projected_dim
        if isinstance(feats, DenseShard):
            dev_feats = (self._place(jnp.asarray(feats.x)),)
        else:
            dev_feats = (
                self._place(jnp.asarray(feats.ids)),
                self._place(jnp.asarray(feats.vals)),
            )
        dev = {
            "feats": dev_feats,
            "dense": isinstance(feats, DenseShard),
            "label": self._place(jnp.asarray(bucket.label)),
            "weight": self._place(jnp.asarray(bucket.row_weight)),
            "entity_index": jnp.asarray(bucket.entity_index),
            # This bin's slot among the stats accumulator's per-bin
            # iteration counts (_accumulate_solve_stats), on the device once.
            "bin_slot": jnp.asarray(len(self.device_buckets), jnp.int32),
            "proj": proj,
            "solve_dim": solve_dim,
            "w0": self._place_w0(
                jnp.zeros((bucket.num_entities, solve_dim), jnp.float32)
            ),
        }
        count_h2d("entity_bins", (
            dev["feats"], dev["label"], dev["weight"], dev["entity_index"],
        ))
        return dev

    def _sharding(self, ndim: int):
        # The mesh's one physical axis — the same axis the score tables
        # shard their row dimension over (parallel.mesh.first_axis_name):
        # entity blocks and score rows split across the same chips.
        axis = first_axis_name(self.mesh)
        if self.row_split:
            # [E, R, ...]: entities replicated, the row axis sharded.
            if ndim < 2:
                return NamedSharding(self.mesh, P())
            return NamedSharding(self.mesh, P(None, axis, *([None] * (ndim - 2))))
        return NamedSharding(self.mesh, P(axis, *([None] * (ndim - 1))))

    def _place(self, leaf: Array) -> Array:
        if self.mesh is None:
            return leaf
        return jax.device_put(leaf, self._sharding(leaf.ndim))

    def _place_w0(self, leaf: Array) -> Array:
        """Per-entity coefficient tables: sharded like entities normally,
        REPLICATED under row-split (every shard runs the same optimizer on
        psum-ed gradients)."""
        if self.mesh is None:
            return leaf
        if self.row_split:
            return jax.device_put(leaf, NamedSharding(self.mesh, P()))
        return jax.device_put(leaf, self._sharding(leaf.ndim))

    def restrict_device(self, i: int, table: Array) -> Array:
        """Bucket ``i``'s warm-start restriction applied on DEVICE: local
        per-entity coefficients from the globally-gathered ``[E_b, dim]``
        table.  The projection's static buffers (index-map slots + mask, or
        the random matrix + its column norms) upload on first warm start
        and stay cached — the seed fetched the whole aligned table to host
        and restricted in numpy once per bucket per warm start."""
        dev = self.device_buckets[i]
        proj = dev["proj"]
        if proj is None:
            return table
        from photon_tpu.game.projection import IndexMapBucketProjection

        if "restrict_buffers" not in dev:
            if isinstance(proj, IndexMapBucketProjection):
                ids, mask = proj.scatter_args()
                dev["restrict_buffers"] = (
                    self._place(jnp.asarray(ids)),
                    self._place(jnp.asarray(mask)),
                )
            else:
                col_norms = (proj.matrix**2).sum(axis=0)
                dev["restrict_buffers"] = (
                    jnp.asarray(proj.matrix),
                    jnp.asarray(
                        (1.0 / np.maximum(col_norms, 1e-12)).astype(np.float32)
                    ),
                )
        a, b = dev["restrict_buffers"]
        if isinstance(proj, IndexMapBucketProjection):
            return _restrict_index_map(table, a, b)
        return _restrict_random(table, a, b)

    def gather_buffers(self, i: int) -> tuple[Array, Array]:
        """Bucket ``i``'s device-resident ``row_index``/mask gather buffers
        for the residual engine, uploaded on first use (host-mode runs —
        including the automatic multi-process fallback — never pay for
        them) and cached for every later iteration."""
        dev = self.device_buckets[i]
        if "row_index" not in dev:
            bucket = self.buckets[i]
            dev["row_index"] = self._place(jnp.asarray(bucket.row_index))
            dev["row_mask"] = self._place(
                jnp.asarray(bucket.row_weight > 0, jnp.float32)
            )
        return dev["row_index"], dev["row_mask"]

    def batch_for(self, i: int, offsets_b: Array):
        dev = self.device_buckets[i]
        offsets_b = self._place(offsets_b)
        if dev["dense"]:
            return DenseBatch(dev["feats"][0], dev["label"], offsets_b, dev["weight"])
        return SparseBatch(
            dev["feats"][0], dev["feats"][1], dev["label"], offsets_b, dev["weight"]
        )

    def check_onboard(self, data: GameDataset, absent_tail=None) -> None:
        """Validate :meth:`onboard`'s preconditions WITHOUT mutating — so a
        caller onboarding several layouts (the estimator's device-data
        cache) can reject the whole batch up front instead of leaving some
        layouts grown and others not (a half-onboarded cache would mix
        grown bucket row indices with old-length offset vectors).

        Appended rows may reference BOTH new and existing entities (ISSUE
        15 blocker fix — existing-entity rows grow the layout in place).
        ``absent_tail`` is an optional bool mask over the appended rows
        marking rows that carry NO id for this coordinate (the online
        ingest's missing-column fill): they are skipped, not bucketed."""
        old = self.dataset
        n_old = len(old.entity_idx_per_row)
        if data.num_examples < n_old:
            raise ValueError(
                f"onboard() needs the GROWN dataset: got {data.num_examples} "
                f"rows, the layout was built from {n_old}"
            )
        if self.config.entity_column not in data.id_columns:
            raise KeyError(
                f"grown dataset lacks id column {self.config.entity_column!r}"
            )
        shard = data.shard(self.config.shard_name)  # raises on a missing shard
        if shard.dim != self.dim:
            raise ValueError(
                f"appended shard {self.config.shard_name!r} has dim "
                f"{shard.dim}; the layout was built at dim {self.dim}"
            )
        if self.buckets:
            built_dense = isinstance(self.buckets[0].features, DenseShard)
            if isinstance(shard, DenseShard) != built_dense:
                raise ValueError(
                    f"grown shard {self.config.shard_name!r} is "
                    f"{'dense' if not built_dense else 'sparse'} but the "
                    f"layout was built "
                    f"{'dense' if built_dense else 'sparse'}; coerce the "
                    "appended rows to the layout's storage (the online "
                    "merge does) or rebuild"
                )
        n_tail = data.num_examples - n_old
        if absent_tail is not None and len(absent_tail) != n_tail:
            raise ValueError(
                f"absent_tail mask covers {len(absent_tail)} rows, the "
                f"appended tail has {n_tail}"
            )

    def _entity_locator(self):
        """``[bin_of, slot_of, used]`` per entity over the CURRENT layout —
        which bin block holds the entity, at which slot, with how many live
        (weight > 0) rows.  The in-place growth path's placement index;
        built lazily, invalidated by :meth:`onboard`."""
        if self._locator is None:
            n_entities = self.dataset.num_entities
            bin_of = np.full(n_entities, -1, np.int32)
            slot_of = np.zeros(n_entities, np.int32)
            used = np.zeros(n_entities, np.int32)
            for i, bucket in enumerate(self.buckets):
                idx = bucket.entity_index
                live = idx < n_entities  # skip dummy/padded/migrated-away
                if not live.any():
                    continue
                slots = np.nonzero(live)[0].astype(np.int32)
                bin_of[idx[live]] = i
                slot_of[idx[live]] = slots
                used[idx[live]] = (
                    bucket.row_weight[slots] > 0
                ).sum(axis=1).astype(np.int32)
            self._locator = [bin_of, slot_of, used]
        return self._locator

    def _plan_append_buckets(self, data, entities, rows_by_entity,
                             corrections):
        """Host ``EntityBucket``s for appended entities (new arrivals and
        migrations alike): ``entities`` are MERGED-vocabulary indices,
        ``rows_by_entity[i]`` the kept global row ids, ``corrections[i]``
        the active-cap weight correction.  Row capacities are the next
        power of two past each entity's kept count — the same amortized-
        doubling headroom the original bucketing gives, so a steadily
        growing entity migrates O(log rows) times."""
        from photon_tpu.utils import pow2_at_least

        if not entities:
            return []
        shard = data.shard(self.config.shard_name)
        # host-sync: append-bucket planning — pure host numpy over the
        # delta's row lists, no device data involved.
        counts = np.asarray([len(r) for r in rows_by_entity], np.int64)
        caps = np.asarray([pow2_at_least(int(c)) for c in counts], np.int64)
        buckets = []
        for capacity in np.unique(caps):
            members = np.nonzero(caps == capacity)[0]
            n_e = len(members)
            row_index = np.zeros((n_e, capacity), np.int64)
            mask = np.zeros((n_e, capacity), np.float32)
            corr = np.ones(n_e, np.float32)
            for k, m in enumerate(members):
                rr = rows_by_entity[m]
                row_index[k, : len(rr)] = rr
                mask[k, : len(rr)] = 1.0
                corr[k] = corrections[m]
            row_weight = (
                data.weight[row_index] * mask * corr[:, None]
            ).astype(Float)
            buckets.append(
                EntityBucket(
                    row_capacity=int(capacity),
                    # host-sync: host bucket assembly (merged entity ids).
                    entity_index=np.asarray(
                        [entities[m] for m in members], np.int32
                    ),
                    row_index=row_index,
                    row_weight=row_weight,
                    label=(data.label[row_index] * mask).astype(Float),
                    features=_gather_shard_rows(shard, row_index),
                )
            )
        return buckets

    def _grow_bin_in_place(self, i: int, slots, pos, rows, data) -> None:
        """Scatter appended rows into bin ``i``'s row-capacity headroom —
        host arrays and the resident device blocks both.  No shape changes,
        so every compiled solve program over this bin stays valid (the
        serving-table capacity trick applied to training bins)."""
        bucket = self.buckets[i]
        shard = data.shard(self.config.shard_name)
        w = data.weight[rows].astype(Float)
        lab = data.label[rows].astype(Float)
        bucket.row_index[slots, pos] = rows
        bucket.row_weight[slots, pos] = w
        bucket.label[slots, pos] = lab
        feats = bucket.features
        if isinstance(feats, DenseShard):
            new_ids = new_vals = None
            feats.x[slots, pos] = shard.x[rows]
        else:
            # The plan phase routed wider-than-block rows to migration;
            # narrower rows pad up to the block's nonzero width (zero
            # ids/vals are inert, the padded-COO convention).
            k_block = feats.ids.shape[-1]
            k_shard = shard.ids.shape[1]
            new_ids, new_vals = shard.ids[rows], shard.vals[rows]
            if k_shard < k_block:
                widths = [(0, 0), (0, k_block - k_shard)]
                new_ids = np.pad(new_ids, widths)
                new_vals = np.pad(new_vals, widths)
            feats.ids[slots, pos] = new_ids
            feats.vals[slots, pos] = new_vals
        dev = self.device_buckets[i]
        sl, po = jnp.asarray(slots), jnp.asarray(pos)
        dev["label"] = self._place(
            dev["label"].at[sl, po].set(jnp.asarray(lab))
        )
        dev["weight"] = self._place(
            dev["weight"].at[sl, po].set(jnp.asarray(w))
        )
        if dev["dense"]:
            dev["feats"] = (
                self._place(
                    dev["feats"][0].at[sl, po].set(jnp.asarray(shard.x[rows]))
                ),
            )
        else:
            dev["feats"] = (
                self._place(
                    dev["feats"][0].at[sl, po].set(jnp.asarray(new_ids))
                ),
                self._place(
                    dev["feats"][1].at[sl, po].set(jnp.asarray(new_vals))
                ),
            )
        if "row_index" in dev:
            # The residual engine's cached gather buffers follow the bin.
            dev["row_index"] = self._place(
                dev["row_index"].at[sl, po].set(jnp.asarray(rows))
            )
            dev["row_mask"] = self._place(dev["row_mask"].at[sl, po].set(1.0))
        self.bin_stats[i]["live_rows"] += int(len(rows))

    def _neutralize_slot(self, i: int, slot: int, dummy: int,
                         used: int) -> None:
        """Retire a migrated-away entity's old slot: dummy entity index (its
        scatter lands on the coefficient table's absorbing row, masked out
        of the solve stats) and zero row weights (invisible to the
        objective).  The slot's feature block stays resident — dead padding,
        exactly like a bucket's built-in pad rows."""
        bucket = self.buckets[i]
        bucket.entity_index[slot] = dummy
        bucket.row_weight[slot, :] = 0.0
        dev = self.device_buckets[i]
        dev["entity_index"] = dev["entity_index"].at[slot].set(dummy)
        dev["weight"] = self._place(dev["weight"].at[slot].set(0.0))
        if "row_mask" in dev:
            dev["row_mask"] = self._place(dev["row_mask"].at[slot].set(0.0))
        self.bin_stats[i]["live_rows"] -= int(used)
        self.bin_stats[i]["live_entities"] -= 1

    def _record_headroom(self, telemetry) -> None:
        """Capacity-headroom accounting (ISSUE 15 satellite): per-bin padded
        row cells vs live rows — the room the next append lands in without
        a migration."""
        col = self.config.entity_column
        for i, st in enumerate(self.bin_stats):
            cells = st["capacity"] * st["total_entities"]
            telemetry.gauge(
                "onboard.bin_row_capacity", column=col, bin=i
            ).set(cells)
            telemetry.gauge(
                "onboard.bin_rows_live", column=col, bin=i
            ).set(st["live_rows"])
            telemetry.gauge(
                "onboard.bin_row_headroom", column=col, bin=i
            ).set(cells - st["live_rows"])

    def onboard(self, data: GameDataset, telemetry=None,
                absent_tail=None) -> None:
        """Incremental onboarding: extend this device layout with rows
        APPENDED to the training data — for BOTH new and existing entities
        — without a full rebuild (ISSUE 15: the continual-training blocker
        fix).

        ``data`` is the grown dataset — its first ``n_old`` rows must be
        the rows this layout was built from (append-only).  Work done here
        is proportional to the APPENDED rows:

        - Rows for NEW entities are bucketed, binned, and uploaded as
          appended bins; existing bins' tiny ``entity_index`` vectors are
          remapped (one device gather each) onto the merged vocabulary.
        - Rows for EXISTING entities land IN PLACE: each power-of-two bin
          block carries row-capacity headroom, and the new rows scatter
          into the owning entity's free padded slots on host AND device —
          no shapes change, no recompiles, resident feature blocks
          untouched.
        - An entity whose headroom is exhausted — or that crosses the
          active-row cap, or lives under a per-bin projection (whose
          feature transform its new rows would invalidate) — MIGRATES: its
          old slot is neutralized (dummy index, zero weights) and its full
          row set re-buckets into an appended bin at the next power-of-two
          capacity (amortized doubling).  An entity pushed past
          ``active_row_cap`` re-subsamples with a per-entity seeded draw
          (unbiased weight correction; the draw is per-entity stable, not
          byte-identical to a cold rebuild's shared-stream draws).

        ``absent_tail`` (bool mask over the appended rows) marks rows that
        carry no id for this coordinate (the online ingest's missing-
        column fill): they keep per-row entity index -1 — zero margin from
        this coordinate, no bin membership.

        A batch failing validation mutates NOTHING: every rejection happens
        in the plan phase, before the first host/device write.  Scoring-
        side caches are dropped and lazily rebuilt at the grown row count.
        """
        from photon_tpu.telemetry import NULL_SESSION

        telemetry = telemetry or NULL_SESSION
        self.check_onboard(data, absent_tail=absent_tail)
        old = self.dataset
        n_old = len(old.entity_idx_per_row)
        n_tail = data.num_examples - n_old
        if n_tail == 0:
            return
        col = self.config.entity_column
        raw_tail = data.id_columns[col][n_old:]
        present = np.ones(n_tail, bool)
        if absent_tail is not None:
            present &= ~absent_tail.astype(bool)
        sel = np.nonzero(present)[0]
        raw_present = raw_tail[sel]

        # ---- plan phase: NO mutation until every input is validated ----
        old_idx = (
            entity_index_for(raw_present, old.keys)
            if len(raw_present) else np.zeros(0, np.int32)
        )
        new_mask = old_idx < 0
        new_raw = raw_present[new_mask]
        if len(new_raw):
            merged_keys = np.unique(
                np.concatenate([old.keys, np.unique(new_raw)])
            )
        else:
            merged_keys = old.keys
        grew = len(merged_keys) != len(old.keys)
        dummy = len(merged_keys)
        if grew:
            remap = entity_index_for(old.keys, merged_keys)
            # Old index -> merged index, with the dummy padding slot
            # (old num_entities) mapped to the NEW dummy slot.
            remap_full = np.concatenate(
                [remap, [dummy]]
            ).astype(np.int32)
        else:
            remap_full = None
        # Per-row map of the appended tail in MERGED space (-1 = absent).
        tail_idx = np.full(n_tail, -1, np.int32)
        if len(raw_present):
            tail_idx[sel] = entity_index_for(raw_present, merged_keys)
        tail_global = n_old + sel

        bin_of, slot_of, used_of = self._entity_locator()  # OLD index space
        cap = self.config.active_row_cap
        shard = data.shard(self.config.shard_name)
        # Sparse shards: an in-place write must fit the bin block's
        # padded-COO nonzero width (a merged append can WIDEN the shard —
        # wider rows migrate instead, into blocks built at the new width;
        # narrower rows pad up in _grow_bin_in_place).
        shard_k = (
            None if isinstance(shard, DenseShard) else shard.ids.shape[1]
        )

        def width_fits(i: int) -> bool:
            if shard_k is None:
                return True
            feats = self.buckets[i].features
            return shard_k <= feats.ids.shape[-1]
        append_entities: list = []  # merged entity index per appended entity
        append_rows: list = []      # kept global row ids per appended entity
        append_corr: list = []      # active-cap weight correction
        in_place: dict = {}         # bin -> [(slot, used, rows)]
        neutralize: list = []       # (bin, slot, used) of migrated entities
        in_place_rows = 0
        migrated_rows = 0
        n_migrated = 0

        exist_pos = np.nonzero(~new_mask)[0]
        if len(exist_pos):
            ents_old = old_idx[exist_pos]
            order = np.argsort(ents_old, kind="stable")
            ents_sorted = ents_old[order]
            rows_sorted = tail_global[exist_pos[order]]
            uniq, starts = np.unique(ents_sorted, return_index=True)
            bounds = np.append(starts, len(ents_sorted))
            # True per-entity base row counts (the active-cap accounting):
            # the per-row map covers every base row, including rows a
            # previous subsample dropped from the bin.
            full_counts = np.bincount(
                old.entity_idx_per_row[old.entity_idx_per_row >= 0],
                minlength=len(old.keys),
            )
            migrating: list = []
            for j, e_old in enumerate(uniq):
                rr = rows_sorted[bounds[j]: bounds[j + 1]]
                i = int(bin_of[e_old])
                u = int(used_of[e_old])
                total = int(full_counts[e_old]) + len(rr)
                subsampled = int(full_counts[e_old]) > u
                fits = (
                    i >= 0
                    and not subsampled
                    and (cap is None or total <= cap)
                    and u + len(rr) <= self.buckets[i].row_capacity
                    and self.config.projection == "none"
                    and width_fits(i)
                )
                if fits:
                    in_place.setdefault(i, []).append(
                        (int(slot_of[e_old]), u, rr)
                    )
                    in_place_rows += len(rr)
                else:
                    migrating.append((int(e_old), rr, i, int(slot_of[e_old]),
                                      u))
            n_migrated = len(migrating)
            for e_old, rr, i, s, u in migrating:
                # The entity's true base row universe, from the per-row
                # map (the bin may hold only a subsample of it).
                base_rows = np.nonzero(old.entity_idx_per_row == e_old)[0]
                all_rows = np.concatenate([base_rows, rr])
                corr = 1.0
                if cap is not None and len(all_rows) > cap:
                    rng = np.random.default_rng(
                        (self.config.seed, 0x6F6E6C, int(e_old))
                    )
                    keep = rng.choice(len(all_rows), size=cap, replace=False)
                    keep.sort()
                    corr = len(all_rows) / cap
                    all_rows = all_rows[keep]
                append_entities.append(
                    int(remap_full[e_old]) if grew else int(e_old)
                )
                append_rows.append(all_rows)
                append_corr.append(corr)
                migrated_rows += len(rr)
                if i >= 0:
                    neutralize.append((i, s, u))

        n_new_entities = 0
        if new_mask.any():
            ents_new = tail_idx[sel[new_mask]]  # merged index
            rows_new = tail_global[new_mask]
            order = np.argsort(ents_new, kind="stable")
            es, rs = ents_new[order], rows_new[order]
            uniq, starts = np.unique(es, return_index=True)
            bounds = np.append(starts, len(es))
            n_new_entities = len(uniq)
            for j, e in enumerate(uniq):
                rr = rs[bounds[j]: bounds[j + 1]]
                corr = 1.0
                if cap is not None and len(rr) > cap:
                    rng = np.random.default_rng(
                        (self.config.seed, 0x6F6E6C, int(e))
                    )
                    keep = rng.choice(len(rr), size=cap, replace=False)
                    keep.sort()
                    corr = len(rr) / cap
                    rr = rr[keep]
                append_entities.append(int(e))
                append_rows.append(rr)
                append_corr.append(corr)
        append_buckets = self._plan_append_buckets(
            data, append_entities, append_rows, append_corr
        )

        # ---- apply phase: mutations only, nothing below rejects input ----
        if grew:
            remap_dev = jnp.asarray(remap_full)
            for i, bucket in enumerate(self.buckets):
                self.buckets[i] = dataclasses.replace(
                    bucket, entity_index=remap_full[bucket.entity_index]
                )
                dev = self.device_buckets[i]
                dev["entity_index"] = remap_dev[dev["entity_index"]]
            old_per_row = np.where(
                old.entity_idx_per_row >= 0,
                remap_full[np.maximum(old.entity_idx_per_row, 0)],
                -1,
            ).astype(np.int32)
        else:
            old_per_row = old.entity_idx_per_row
        for i, writes in sorted(in_place.items()):
            slots = np.concatenate(
                [np.full(len(rr), s, np.int32) for s, _, rr in writes]
            )
            pos = np.concatenate(
                [u + np.arange(len(rr), dtype=np.int32)
                 for _, u, rr in writes]
            )
            rows = np.concatenate([rr for _, _, rr in writes])
            self._grow_bin_in_place(i, slots, pos, rows, data)
        for i, s, u in neutralize:
            self._neutralize_slot(i, s, dummy, u)
        self.dataset = dataclasses.replace(
            old,
            keys=merged_keys,
            buckets=tuple(self.buckets),
            entity_idx_per_row=np.concatenate([old_per_row, tail_idx]),
        )
        if append_buckets:
            self._append_bins(append_buckets)
            self.dataset = dataclasses.replace(
                self.dataset, buckets=tuple(self.buckets)
            )
        # Row count and vocabulary changed: the scoring caches, the
        # warm-start join cache, and the placement index are stale — drop
        # them (rebuilt lazily).
        self._score_feats = None
        self._score_entity_idx = None
        self._score_cache_bytes = 0
        self._warm_join_cache.clear()
        self._locator = None
        if in_place_rows:
            telemetry.counter("onboard.rows_in_place", column=col).inc(
                in_place_rows
            )
        if migrated_rows:
            telemetry.counter("onboard.rows_migrated", column=col).inc(
                migrated_rows
            )
        if n_migrated:
            telemetry.counter("onboard.entities_migrated", column=col).inc(
                n_migrated
            )
        if n_new_entities:
            telemetry.counter("onboard.entities_new", column=col).inc(
                n_new_entities
            )
        skipped = n_tail - len(sel)
        if skipped:
            telemetry.counter("onboard.rows_absent", column=col).inc(skipped)
        self._record_headroom(telemetry)


# ---------------------------------------------------------------------------
# Coordinates
# ---------------------------------------------------------------------------


class FixedEffectCoordinate:
    """Data-parallel global GLM fit (reference: FixedEffectCoordinate)."""

    kind = "fixed"

    def __init__(
        self,
        data: GameDataset,
        config: FixedEffectCoordinateConfig,
        task_type: str,
        mesh=None,
        normalization: Optional[NormalizationContext] = None,
        device_data: Optional[FixedEffectDeviceData] = None,
    ):
        self.data = data
        self.config = config
        self.task_type = task_type
        self.mesh = mesh
        self.device_data = device_data or FixedEffectDeviceData(data, config, mesh)
        self.dim = self.device_data.dim
        # host-sync: one-time construction check of host-side factors.
        if normalization is not None and len(
            np.asarray(normalization.factors_or_ones(self.dim))
        ) != self.dim:
            raise ValueError(
                f"normalization context dim mismatch for shard "
                f"{config.shard_name!r} (expected {self.dim})"
            )
        # get_loss accepts task-type names directly (core/losses.TASK_TO_LOSS).
        obj = GlmObjective.create(
            task_type, config.problem.regularization, normalization
        )
        if mesh is None:
            self.objective = obj
        else:
            from photon_tpu.parallel.distributed import DistributedGlmObjective

            self.objective = DistributedGlmObjective(obj, mesh)
        self.problem = GlmOptimizationProblem(self.objective, config.problem)
        self.normalization = normalization

    def train(
        self, offsets: np.ndarray, initial_model: Optional[FixedEffectModel] = None
    ) -> tuple[FixedEffectModel, OptimizationStatesTracker]:
        """One GLM fit against the other coordinates' scores as offsets
        (SURVEY.md §3.1: offsets = sum of scores of other coordinates)."""
        import time

        batch = with_offset(
            self.device_data.batch, self.device_data.offsets_to_device(offsets)
        )
        w0 = None
        if initial_model is not None:
            w0 = jnp.asarray(initial_model.coefficients.means)
            if self.normalization is not None:
                w0 = self.normalization.model_to_normalized_space(w0)
        t0 = time.monotonic()
        coefficients, result = self.problem.run(batch, w0, dim=self.dim)
        jax.block_until_ready(coefficients.means)
        tracker = OptimizationStatesTracker(result, time.monotonic() - t0)
        means, variances = coefficients.means, coefficients.variances
        if self.normalization is not None:
            means = self.normalization.model_to_original_space(means)
            variances = self.normalization.variances_to_original_space(variances)
        from photon_tpu.fault.injection import consume_nan_injection

        if consume_nan_injection(getattr(self, "fault_name", None)):
            means = means.at[0].set(jnp.nan)
        # Non-finite guard (graceful degradation): a diverged/poisoned solve
        # keeps the previous iterate (the warm-start model, or zeros on the
        # first pass) instead of feeding NaN margins into the residual
        # engine.  The solve already synced above, so this check is a
        # dim-sized host reduce, not a new hot-loop transfer.
        tracker.quarantined = 0
        if not bool(jnp.all(jnp.isfinite(means))):
            tracker.quarantined = 1
            if initial_model is not None:
                prev = initial_model.coefficients
                means = jnp.asarray(prev.means)
                variances = (
                    None if prev.variances is None else jnp.asarray(prev.variances)
                )
            else:
                means, variances = jnp.zeros_like(means), None
        model = FixedEffectModel(
            model=model_for_task(self.task_type, Coefficients(means, variances)),
            shard_name=self.config.shard_name,
        )
        return model, tracker

    def score(self, model: FixedEffectModel) -> np.ndarray:
        return model.score(self.data)

    def score_device(self, model: FixedEffectModel) -> Array:
        """Training-data margins as a device array (the residual engine's
        scoring path), from the training batch's block tiles where it
        carries them and is the shard itself, else from shard features
        uploaded once and cached (:func:`_scoring_feats`).  A model
        trained on a different feature shard (foreign warm start) scores
        through its own host path — the device holds this coordinate's
        shard."""
        if model.shard_name != self.config.shard_name:
            return model.score(self.data)
        held = self.device_data
        bt = getattr(held.batch, "bt", None)
        rows = entries = None
        if bt is not None and held.train_rows is None and self.mesh is None:
            # The training batch is the whole shard in row order and carries
            # the tiles its fits read: score from them, and never build the
            # second copy of the entries.  Row-capacity pad rows hold no
            # entry (value 0.0: dropped by the tile builder) and lie past
            # the rows scored.
            rows = _score_pad(self)
            feats, dense = bt, False
            entries = rows * held.batch.ids.shape[1]
        else:
            feats, dense = _scoring_feats(self)
        count_sparse_entries(
            getattr(self, "telemetry", NULL_SESSION),
            getattr(self, "fault_name", self.config.shard_name), feats, dense,
            entries,
        )
        return model.margins_device(feats, dense, out_len=rows)


class RandomEffectCoordinate:
    """Per-entity batched GLM fits (reference: RandomEffectCoordinate).

    The reference maps ``SingleNodeOptimizationProblem.run`` over an
    ``RDD[(entityId, LocalDataset)]``; here each bucket's entities are solved
    by ONE vmapped optimizer call — per-lane line search and convergence are
    masked, so early-converging entities freeze while heavy ones iterate
    (SURVEY.md §7).
    """

    kind = "random"

    def __init__(
        self,
        data: GameDataset,
        config: RandomEffectCoordinateConfig,
        task_type: str,
        mesh=None,
        device_data: Optional[RandomEffectDeviceData] = None,
    ):
        self.data = data
        self.config = config
        self.task_type = task_type
        self.mesh = mesh
        self.device_data = device_data or RandomEffectDeviceData(data, config, mesh)
        self.dataset = self.device_data.dataset
        self.dim = self.dataset.dim
        obj = GlmObjective.create(task_type, config.problem.regularization)
        self.problem = GlmOptimizationProblem(obj, config.problem)
        # Shared vmapped solver (one traced program per static config +
        # bucket shape, module-cached): the objective rides along as a pytree
        # argument, so sweep configs differing only in reg weights reuse it.
        self._solver = functools.partial(
            self.problem.solver(vmapped=True), self.problem.objective
        )

    def _bin_routes(self) -> list:
        """Per-bin solver route (``newton``/``newton_cg``/``vmapped``/
        ``row_split``) —
        see game.batched_solve.solver_route.  Cached per coordinate (the
        descent loop calls train() every outer iteration; the routes only
        change when onboarding extends the bin layout, which the bin-count
        key detects — coordinates are rebuilt per sweep configuration, so
        the problem-config component never goes stale)."""
        from photon_tpu.game.batched_solve import solver_route

        cached = getattr(self, "_routes_cache", None)
        n_bins = len(self.device_data.device_buckets)
        if cached is not None and cached[0] == n_bins:
            return cached[1]
        routes = [
            solver_route(
                self.config.problem, dev["solve_dim"],
                row_split=self.device_data.row_split,
            )
            for dev in self.device_data.device_buckets
        ]
        self._routes_cache = (n_bins, routes)
        return routes

    def _solve_bin(self, route: str, batch, w0):
        """Dispatch one bin's batched solve along its resolved route: the
        batched-Cholesky Newton program (small-dim smooth bins), the
        matrix-free Newton-CG program (smooth bins past the dense-Hessian
        cap — no ``[B, d, d]`` materialization), the row-split psum solve,
        or the vmapped iterative solver (L1 / over-cap bins — every
        existing problem config still solves)."""
        if route == "newton":
            from photon_tpu.game.batched_solve import cached_newton_solver

            return cached_newton_solver(self.config.problem)(
                self.problem.objective, batch, w0,
                entity_shards=mesh_shards(self.mesh),
            )
        if route == "newton_cg":
            from photon_tpu.game.batched_solve import cached_newton_cg_solver

            return cached_newton_cg_solver(self.config.problem)(
                self.problem.objective, batch, w0
            )
        if route == "row_split":
            from photon_tpu.parallel.distributed import solve_entities_row_split

            return solve_entities_row_split(
                self.problem.objective, self.config.problem,
                batch, w0, self.mesh,
                axis_name=first_axis_name(self.mesh),
            )
        return self._solver(batch, w0)

    def _initial_table(self, initial_model: RandomEffectModel) -> Array:
        """Align a warm-start model's per-entity rows onto THIS dataset's
        vocabulary by key (the model may come from different training data —
        SURVEY.md §5 warm start); unseen entities start at zero.  The dummy
        slot at the end absorbs padded entities.

        The common case — coordinate descent re-passing the model THIS
        coordinate trained last iteration, whose ``keys`` is the dataset's
        own object — stays entirely on device: the table gets its dummy row
        appended by one device concatenate, no d2h fetch and no O(E) key
        join (the per-warm-start host path the ROADMAP flagged)."""
        if initial_model.dim != self.dim:
            raise ValueError(
                f"warm-start model dim {initial_model.dim} != coordinate dim {self.dim}"
            )
        # Only FOREIGN vocabularies (warm starts loaded from disk) pay the
        # host compare + join below; see data.keys_match.
        if keys_match(initial_model.keys, self.dataset.keys):
            table = jnp.asarray(initial_model.table, jnp.float32)
            return jnp.concatenate(
                [table, jnp.zeros((1, self.dim), table.dtype)]
            )
        # Foreign vocabulary: host key join, with the computed src_idx
        # CACHED per keys-object identity on the shared device data (the
        # sweep re-passes the same warm-start model once per configuration
        # × iteration) and its transfers counted — _align_foreign_table.
        return jnp.asarray(_align_foreign_table(self, initial_model))

    def train(
        self, offsets: np.ndarray, initial_model: Optional[RandomEffectModel] = None
    ) -> tuple[RandomEffectModel, dict]:
        """Solve every entity; returns the model + convergence summary."""
        num_entities = self.dataset.num_entities
        # Extra dummy slot absorbs padded entities' scatter writes.
        table = jnp.zeros((num_entities + 1, self.dim), jnp.float32)
        var_table = (
            jnp.zeros((num_entities + 1, self.dim), jnp.float32)
            if self.config.problem.variance_computation != "none"
            else None
        )
        init_table = (
            None if initial_model is None else self._initial_table(initial_model)
        )
        # Per-coordinate device stats accumulator: entities / converged /
        # iterations_max / quarantined / cg_iters fold in per bucket ON
        # DEVICE, and train() returns the handle — no host sync here at
        # all.  The descent loop drains every coordinate's accumulator in
        # its single per-iteration stats/quarantine sync
        # (descent.host_syncs).
        n_bins = len(self.device_data.buckets)
        acc = jnp.zeros(6 + n_bins, jnp.int32)
        from photon_tpu.fault.injection import consume_nan_injection
        from photon_tpu.game.projection import (
            IndexMapBucketProjection,
            RandomProjectionMatrix,
        )

        inject_nan = consume_nan_injection(getattr(self, "fault_name", None))
        routes = self._bin_routes()
        # Gauges describe the (static) bin layout: set them once per
        # coordinate, again only if onboarding extended the layout — not
        # once per outer descent iteration.
        if getattr(self, "_bins_recorded", None) != len(routes):
            from photon_tpu.game.batched_solve import record_bin_telemetry

            record_bin_telemetry(
                getattr(self, "telemetry", NULL_SESSION),
                getattr(self, "fault_name", self.config.shard_name),
                self.device_data.bin_stats, routes,
                [dev["solve_dim"] for dev in self.device_data.device_buckets],
                dense=[dev["dense"] for dev in self.device_data.device_buckets],
                entity_shards=mesh_shards(self.mesh),
            )
            self._bins_recorded = len(routes)
        for i, bucket in enumerate(self.device_data.buckets):
            offsets_b = _bucket_offsets(self.device_data, i, bucket, offsets)
            batch = self.device_data.batch_for(i, offsets_b)
            dev = self.device_data.device_buckets[i]
            entity_idx = dev["entity_index"]
            proj = dev["proj"]
            if init_table is not None:
                # Device gather against the bucket's entity index, then the
                # projection's device restriction (cached static buffers) —
                # the whole warm-start alignment stays on device.
                w0 = self.device_data._place_w0(
                    self.device_data.restrict_device(i, init_table[entity_idx])
                )
            else:
                w0 = dev["w0"]
            coefficients, result = self._solve_bin(routes[i], batch, w0)
            means, variances = coefficients.means, coefficients.variances
            if inject_nan and i == 0:
                # Fault injection (solve:nan): poison one entity's solve so
                # the quarantine path below is exercised end to end.
                means = means.at[0].set(jnp.nan)
            # Non-finite guard (graceful degradation): entities whose solve
            # diverged to NaN/Inf keep their previous iterate (warm-start
            # row, or zero on a cold start) instead of poisoning the table;
            # the count joins the ONE deferred host sync below.
            good = jnp.all(jnp.isfinite(means), axis=1)
            prev_rows = None if init_table is None else init_table[entity_idx]
            if proj is None:
                fallback = 0.0 if prev_rows is None else prev_rows
                table = table.at[entity_idx].set(
                    jnp.where(good[:, None], means, fallback)
                )
                if var_table is not None:
                    # Quarantined entities get zero variance: the previous
                    # model's variances are not carried through warm starts.
                    var_table = var_table.at[entity_idx].set(
                        jnp.where(good[:, None], variances, 0.0)
                    )
            elif isinstance(proj, IndexMapBucketProjection):
                # Scatter each local slot back to its global column; slots
                # are unique per entity, so add-on-zero-rows equals set, and
                # masked pad slots contribute exactly 0.  Quarantined
                # entities scatter zeros, then get their previous full row
                # added onto their (still-zero) table row.
                proj_ids, mask = proj.scatter_args()
                ids_j, mask_j = jnp.asarray(proj_ids), jnp.asarray(mask)
                safe_means = jnp.where(good[:, None], means, 0.0)
                table = table.at[entity_idx[:, None], ids_j].add(
                    safe_means * mask_j
                )
                if prev_rows is not None:
                    table = table.at[entity_idx].add(
                        jnp.where(good, 0.0, 1.0)[:, None] * prev_rows
                    )
                if var_table is not None:
                    var_table = var_table.at[entity_idx[:, None], ids_j].add(
                        jnp.where(good[:, None], variances, 0.0) * mask_j
                    )
            else:
                assert isinstance(proj, RandomProjectionMatrix)
                lifted = proj.lift(means)
                fallback = 0.0 if prev_rows is None else prev_rows
                table = table.at[entity_idx].set(
                    jnp.where(good[:, None], lifted, fallback)
                )
                if var_table is not None:
                    var_table = var_table.at[entity_idx].set(
                        jnp.where(good[:, None], proj.lift_variance(variances), 0.0)
                    )
            acc = _accumulate_solve_stats(
                acc, entity_idx, num_entities, result.converged,
                result.iterations, good,
                cg_iterations=getattr(result, "cg_iterations", None),
                bin_slot=dev["bin_slot"],
            )
        model = RandomEffectModel(
            table=table[:num_entities],
            keys=self.dataset.keys,
            entity_column=self.config.entity_column,
            shard_name=self.config.shard_name,
            task_type=self.task_type,
            variances=None if var_table is None else var_table[:num_entities],
        )
        # What each per-bin iteration count multiplies at the drain: the
        # bin's route and its padded entities x row capacity.
        return model, DeferredSolveStats(acc, extra={
            "bin_routes": list(routes),
            "bin_cells": [
                st["total_entities"] * st["capacity"]
                for st in self.device_data.bin_stats
            ],
        })

    def score(self, model: RandomEffectModel) -> np.ndarray:
        return model.score(self.data)

    def score_device(self, model: RandomEffectModel) -> Array:
        """Training-data margins as a device array (the residual engine's
        scoring path)."""
        return _random_score_device(self, model)


class FactoredRandomEffectCoordinate:
    """Latent-factor random effect: alternate vmapped per-entity latent
    solves (``z_e``, dim r, on features ``x @ L``) with one pooled L-BFGS
    solve of the shared projection ``L`` (margin linear in ``vec(L)``:
    ``x_i @ L @ z_{e(i)}``).  Exports a plain :class:`RandomEffectModel`
    with materialized ``w_e = L z_e`` so scoring, model IO, and warm start
    reuse the unfactored machinery (the reference's factored coordinate
    likewise yields per-entity GLMs)."""

    kind = "factored_random"

    def __init__(
        self,
        data: GameDataset,
        config: FactoredRandomEffectCoordinateConfig,
        task_type: str,
        mesh=None,
        device_data: Optional[RandomEffectDeviceData] = None,
    ):
        self.data = data
        self.config = config
        self.task_type = task_type
        self.mesh = mesh
        self.device_data = device_data or RandomEffectDeviceData(
            data, config.as_random_config(), mesh
        )
        self.dataset = self.device_data.dataset
        self.dim = self.dataset.dim
        self.r = config.latent_dim
        obj = GlmObjective.create(task_type, config.problem.regularization)
        self.problem = GlmOptimizationProblem(obj, config.problem)
        self._z_solver = functools.partial(
            self.problem.solver(vmapped=True), self.problem.objective
        )
        self._objective = obj
        # Device-resident pooled-solve arrays + ONE jitted objective, built
        # once: _solve_latent is called per latent iteration per sweep point,
        # and rebuilding arrays/closures there would re-upload the dataset
        # and recompile every call.  Under a mesh the per-row arrays are
        # padded (weight-0 rows) and sharded over the data axis; the jitted
        # objective then partitions via GSPMD (XLA inserts the all-reduce
        # for the scalar value and the replicated gradient automatically).
        if mesh is not None:
            n_shards = int(np.prod(list(mesh.shape.values())))
            n = self.data.num_examples
            self._pool_pad = (-n) % n_shards
        else:
            self._pool_pad = 0

        def place_rows(a):
            a = jnp.asarray(a)
            # Pad to the POOLED target length (residual-engine offsets
            # arrive pre-padded to the mesh multiple; host vectors don't).
            short = (self.data.num_examples + self._pool_pad) - a.shape[0]
            if short > 0:
                a = jnp.pad(a, [(0, short)] + [(0, 0)] * (a.ndim - 1))
            if mesh is None:
                return a
            ax = next(iter(mesh.shape))
            return reshard(
                a, NamedSharding(mesh, P(ax, *([None] * (a.ndim - 1))))
            )

        self._place_rows = place_rows
        shard = self.data.shard(config.shard_name)
        label = place_rows(jnp.asarray(self.data.label, jnp.float32))
        weight = place_rows(jnp.asarray(self.data.weight, jnp.float32))
        loss = obj.loss
        l2 = obj.l2_weight
        d, r = self.dim, self.r
        if isinstance(shard, DenseShard):
            x = place_rows(jnp.asarray(shard.x))

            def _latent_value(flat, z_rows, offsets):
                latent = flat.reshape(d, r)
                z = jnp.einsum("nd,dk,nk->n", x, latent, z_rows) + offsets
                return (
                    jnp.sum(weight * loss.value(z, label))
                    + 0.5 * l2 * jnp.dot(flat, flat)
                )
        else:
            ids = place_rows(jnp.asarray(shard.ids))
            vals = place_rows(jnp.asarray(shard.vals))

            def _latent_value(flat, z_rows, offsets):
                latent = flat.reshape(d, r)
                xl = jnp.einsum("njk,nj->nk", jnp.take(latent, ids, axis=0), vals)
                z = jnp.sum(xl * z_rows, axis=-1) + offsets
                return (
                    jnp.sum(weight * loss.value(z, label))
                    + 0.5 * l2 * jnp.dot(flat, flat)
                )

        self._latent_value_and_grad = jax.jit(jax.value_and_grad(_latent_value))

    # -- bucket features projected by the current L ---------------------------
    def _project_bucket(self, dev: dict, latent: Array) -> Array:
        if dev["dense"]:
            return jnp.einsum("erd,dk->erk", dev["feats"][0], latent)
        ids, vals = dev["feats"]
        # sum_k vals * L[ids]: [E, R, nnz, r] contracted over nnz.
        return jnp.einsum(
            "ernk,ern->erk", jnp.take(latent, ids, axis=0), vals
        )

    # -- pooled L solve -------------------------------------------------------
    def _solve_latent(self, z_rows: Array, offsets: Array, latent0: Array) -> Array:
        """Optimize ``L`` with all entities' ``z`` fixed: a GLM over
        ``vec(L)`` whose margins are ``(x_i @ L) . z_i``."""
        from photon_tpu.core.optimizers import lbfgs

        z_rows = self._place_rows(z_rows)
        offsets = self._place_rows(offsets)
        result = lbfgs(
            lambda w: self._latent_value_and_grad(w, z_rows, offsets),
            latent0.reshape(-1),
            self.config.problem.optimizer_config,
        )
        return result.w.reshape(self.dim, self.r)

    def _warm_start(self, initial_model: RandomEffectModel):
        """Recover (L, z) from a previous model's full-dim table via rank-r
        SVD (coordinate descent passes the previous iteration's model; a
        fresh random restart would discard all alternation progress).  Also
        returns the key-aligned previous table — the quarantine fallback
        rows — since the SVD fetched it to host anyway (the factored warm
        start is a known host-resident edge, see ROADMAP)."""
        # Key-aligned previous table via the shared (cached) foreign join;
        # the rank-r SVD below runs in numpy, once per warm start (not per
        # iteration) — the factored warm start is a known host-resident
        # edge, see ROADMAP.
        aligned = _align_foreign_table(self, initial_model)
        u, s, vt = np.linalg.svd(aligned, full_matrices=False)
        r = self.r
        sq = np.sqrt(s[:r])
        latent = (vt[:r].T * sq[None, :]).astype(np.float32)  # [d, r]
        z = (u[:, :r] * sq[None, :]).astype(np.float32)  # [E+1, r]
        # The aligned previous table stays HOST numpy: it is only needed
        # once, at the final quarantine-fallback where — uploading it here
        # would pin a full [E, dim] device copy through every alternation
        # of the train (the exact residency factoring exists to avoid).
        return jnp.asarray(latent), jnp.asarray(z), aligned[:-1]

    def train(
        self, offsets: np.ndarray, initial_model: Optional[RandomEffectModel] = None
    ) -> tuple[RandomEffectModel, dict]:
        num_entities = self.dataset.num_entities
        rng = np.random.default_rng(self.config.seed)
        latent = jnp.asarray(
            rng.standard_normal((self.dim, self.r)) / np.sqrt(self.dim),
            jnp.float32,
        )
        offsets_j = jnp.asarray(offsets, jnp.float32)
        entity_of_row = jnp.asarray(self.dataset.entity_idx_per_row, jnp.int32)
        z_table = jnp.zeros((num_entities + 1, self.r), jnp.float32)
        prev_table = None
        if initial_model is not None:
            latent, z_table, prev_table = self._warm_start(initial_model)
            # Warm-started L is already informed: refresh it from the new
            # offsets before the first z solve.
            latent = self._solve_latent(
                z_table[entity_of_row], offsets_j, latent
            )

        # Per-coordinate device stats accumulator (see
        # _accumulate_solve_stats): reset each latent alternation so the
        # reported counts cover the FINAL z pass, like the dict the seed
        # rebuilt per alternation; drained by the descent loop's one
        # boundary sync.
        acc = jnp.zeros(6, jnp.int32)
        for it in range(self.config.latent_iterations):
            last = it == self.config.latent_iterations - 1
            acc = jnp.zeros(6, jnp.int32)
            for i, bucket in enumerate(self.device_data.buckets):
                dev = self.device_data.device_buckets[i]
                offsets_b = self.device_data._place(
                    _bucket_offsets(self.device_data, i, bucket, offsets)
                )
                feats = self._project_bucket(dev, latent)
                batch = DenseBatch(feats, dev["label"], offsets_b, dev["weight"])
                entity_idx = dev["entity_index"]
                w0 = self.device_data._place(z_table[entity_idx])
                coefficients, result = self._z_solver(batch, w0)
                z_table = z_table.at[entity_idx].set(coefficients.means)
                acc = _accumulate_solve_stats(
                    acc, entity_idx, num_entities, result.converged,
                    result.iterations,
                    jnp.ones_like(result.converged, bool),
                    cg_iterations=getattr(result, "cg_iterations", None),
                )
            if not last:
                z_rows = z_table[entity_of_row]
                latent = self._solve_latent(z_rows, offsets_j, latent)

        # Materialize per-entity coefficients w_e = L z_e (padded slot drops).
        table = z_table[:num_entities] @ latent.T
        from photon_tpu.fault.injection import consume_nan_injection

        if consume_nan_injection(getattr(self, "fault_name", None)):
            table = table.at[0].set(jnp.nan)
        # Non-finite guard: entities whose materialized coefficients are
        # NaN/Inf (a diverged latent alternation) fall back to the
        # warm-start model's rows (aligned during the warm start's SVD
        # fetch), or zeros on a cold start — applied unconditionally on
        # device, and COUNTED into the accumulator's quarantined slot, so
        # the guard adds no host transfer at all.
        good = jnp.all(jnp.isfinite(table), axis=1)
        acc = _count_quarantined(acc, good)
        prev = (
            jnp.asarray(prev_table) if prev_table is not None
            else jnp.zeros_like(table)
        )
        table = jnp.where(good[:, None], table, prev)
        model = RandomEffectModel(
            table=table,
            keys=self.dataset.keys,
            entity_column=self.config.entity_column,
            shard_name=self.config.shard_name,
            task_type=self.task_type,
        )
        return model, DeferredSolveStats(
            acc, extra={"latent_iterations": self.config.latent_iterations}
        )

    def score(self, model: RandomEffectModel) -> np.ndarray:
        return model.score(self.data)

    def score_device(self, model: RandomEffectModel) -> Array:
        """Training-data margins as a device array (the residual engine's
        scoring path; the factored coordinate exports a plain
        :class:`RandomEffectModel`, so scoring is the same gather-join)."""
        return _random_score_device(self, model)


def build_coordinate(
    data: GameDataset,
    config: CoordinateConfig,
    task_type: str,
    mesh=None,
    normalization: Optional[NormalizationContext] = None,
    device_data=None,
):
    if isinstance(config, FixedEffectCoordinateConfig):
        return FixedEffectCoordinate(
            data, config, task_type, mesh, normalization, device_data
        )
    if isinstance(config, (RandomEffectCoordinateConfig,
                           FactoredRandomEffectCoordinateConfig)):
        if normalization is not None:
            raise ValueError(
                "normalization is not supported for random-effect coordinates "
                f"(coordinate on shard {config.shard_name!r})"
            )
        if isinstance(config, FactoredRandomEffectCoordinateConfig):
            return FactoredRandomEffectCoordinate(
                data, config, task_type, mesh, device_data
            )
        return RandomEffectCoordinate(data, config, task_type, mesh, device_data)
    raise TypeError(f"unknown coordinate config type {type(config)!r}")
