"""Epoch-style streamed GAME coordinate descent (out-of-core training).

The resident :class:`~photon_tpu.game.descent.CoordinateDescent` requires
every coordinate's training data AND the ``[C, n]`` score tables on device.
This module is the out-of-core mode (ISSUE 10): the dataset and the score
state stay at the host tier (:mod:`photon_tpu.game.tiles`), and each
coordinate's train / re-score / validate loop **maps over fixed-size row
chunks** streamed through a double-buffered h2d prefetch:

- **Fixed effect** — the whole-dataset GLM fit becomes a streamed L-BFGS
  (:func:`photon_tpu.data.streaming.streaming_lbfgs`): every objective
  evaluation is one pass over the chunks, each chunk's value+grad computed
  by the jitted per-chunk kernel (``_chunk_value_and_grad`` — the existing
  ``_fast_data_value_and_grad`` routing unchanged per chunk) and
  accumulated across chunks.  Chunk ``k+1``'s slice + upload runs on the
  io pool while chunk ``k``'s kernel executes.
- **Random effect** — each size bin's entities are split into
  **sub-blocks** sized to the chunk budget; blocks upload through the same
  prefetch pipeline and fold into the size-binned batched solves
  (``game.batched_solve`` routes — vmapped/Newton — are per-entity
  independent, so block composition cannot change any entity's solve).
- **Re-score / validate** — per-chunk device margins land back in the host
  score tiles; validation evaluates the tiled composite on host.

The descent keeps the one-host-sync-per-outer-iteration contract for
SOLVE STATS: per-coordinate device accumulators drain in ONE batched
``device_get`` at the iteration boundary (the chunk-cursor drain).  Score
data itself moves host<->device per chunk by design — that is the
out-of-core tier working as intended, and it is all bulk streaming
transfer, never a blocking scalar sync inside a chunk.

With a :class:`~photon_tpu.game.tiles.SpillContext` attached (ISSUE 11),
the residual tiles and feature chunks live one tier lower — disk part
files behind the LRU host cache — and the loop's shape is unchanged: the
chunk loads read disk→host→device, and every residual update writes the
dirty tiles back through the store (write-through, atomic per chunk).

Mid-epoch restartability: after EVERY coordinate the full restart state —
models, residual tiles, the **chunk cursor** (how far into the epoch's
update sequence the run got) and per-chunk **score-tile digests** — is
handed to the checkpointer, so a multi-hour streamed fit killed mid-epoch
resumes at the exact coordinate boundary with bit-identical state (the
digests are verified at load).  The ``descent:kill`` fault site fires both
at the iteration boundary (resident parity) and before each coordinate
(``coord=<name>`` scoping) to exercise the mid-epoch path.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Optional, Sequence

import numpy as np

from photon_tpu.evaluation.evaluators import MultiEvaluator
from photon_tpu.fault import QuarantineBudgetError
from photon_tpu.fault.checkpoint import DescentState, descent_fingerprint
from photon_tpu.fault.injection import fault_point
from photon_tpu.game.coordinate import (
    DeferredSolveStats,
    _accumulate_solve_stats,
    _align_foreign_table,
)
from photon_tpu.game.data import (
    DenseShard,
    EntityBucket,
    GameDataset,
    SparseShard,
    build_random_effect_dataset,
    entity_index_for,
    keys_match,
    merge_buckets,
    pad_bucket_entities,
)
from photon_tpu.game.descent import (
    DescentResult,
    _quarantine_count,
    _record_coordinate_info,
)
from photon_tpu.game.model import FixedEffectModel, GameModel, RandomEffectModel
from photon_tpu.game.tiles import (
    ChunkPlan,
    ChunkStreamer,
    NeumaierAccumulator,
    SpilledResidualTable,
    SpillContext,
    TiledResidualTable,
    TiledValidationTable,
    cached_entity_index,
    entity_index_cache,
    per_row_bytes,
    score_model_chunks,
)
from photon_tpu.telemetry import NULL_SESSION
from photon_tpu.utils.logging import PhotonLogger

# The streamed-mode marker in checkpoint fingerprints: a streamed fit's
# numerics depend on the chunked accumulation order, so its checkpoints are
# compatible only with streamed runs of the SAME chunk size — never with a
# resident fit (and vice versa).
STREAM_RESIDUAL_MODE = "stream"


def stream_fingerprint(
    task_type,
    coordinate_names,
    num_examples: int,
    chunk_rows: int,
    config_key=None,
    validation_key=None,
    locked=(),
    warm_start: bool = False,
    coordinate_kinds=None,
) -> dict:
    """The streamed descent's checkpoint fingerprint: the resident
    fingerprint with ``residual_mode == "stream"`` plus the chunk size
    (chunk boundaries fix the fixed-effect accumulation order, so resuming
    under a different ``chunk_rows`` would silently change numerics —
    refuse instead)."""
    fp = descent_fingerprint(
        task_type, coordinate_names, num_examples, STREAM_RESIDUAL_MODE,
        config_key=config_key, validation_key=validation_key, locked=locked,
        warm_start=warm_start, coordinate_kinds=coordinate_kinds,
    )
    fp["stream"] = {"chunk_rows": int(chunk_rows)}
    return fp


def _require_streamable_problem(config, what: str) -> None:
    """The streamed coordinate gates: fail LOUDLY at build time for
    configurations whose resident-only features have no streamed
    counterpart yet (rather than silently training something else)."""
    if config.problem.variance_computation != "none":
        raise ValueError(
            f"{what}: variance computation is not supported under "
            "--stream-chunks (the streamed solvers return means only)"
        )


# ---------------------------------------------------------------------------
# Streamed fixed-effect coordinate
# ---------------------------------------------------------------------------


class StreamedFixedEffectCoordinate:
    """Whole-dataset GLM fit that never holds the dataset on device: a
    streamed L-BFGS whose every objective evaluation maps the jitted
    per-chunk value+grad kernel over the chunk stream and reduces across
    chunks (DrJAX's MapReduce shape at the host-loop level)."""

    kind = "fixed"

    def __init__(
        self,
        data: GameDataset,
        config,
        task_type: str,
        plan: ChunkPlan,
        streamer: ChunkStreamer,
        normalization=None,
        source=None,
    ):
        from photon_tpu.core.objective import GlmObjective

        if config.downsampling_rate < 1.0:
            raise ValueError(
                "streamed GAME does not support fixed-effect downsampling "
                "(chunk layouts are contiguous row windows); train resident "
                "or drop downsample="
            )
        if config.problem.optimizer.lower() not in ("lbfgs", "l-bfgs"):
            raise ValueError(
                "streamed GAME fixed effect supports the lbfgs optimizer "
                f"(got {config.problem.optimizer!r}); OWL-QN/TRON have no "
                "streamed host loop yet"
            )
        if normalization is not None:
            raise ValueError(
                "streamed GAME does not support fixed-effect normalization "
                "(the per-chunk kernel cache requires a hashable objective)"
            )
        _require_streamable_problem(config, "streamed fixed effect")
        self.data = data
        self.config = config
        self.task_type = task_type
        self.plan = plan
        self.streamer = streamer
        self.mesh = None
        shard = data.shard(config.shard_name)
        self.dim = shard.dim
        self._dense = isinstance(shard, DenseShard)
        self.source = source  # None = host-resident slices (PR 10)
        self.objective = GlmObjective.create(
            task_type, config.problem.regularization
        )

    def _chunk_batch(self, k: int, offsets: list):
        """Worker-side chunk load: chunk ``k``'s feature rows, labels and
        weights (host slices, or the spilled disk tier through the host
        cache when a ``source`` is attached) + this coordinate's tiled
        training offsets, placed on device."""
        import jax.numpy as jnp

        from photon_tpu.data.batch import DenseBatch, SparseBatch

        if self.source is not None:
            sub = self.source.chunk(k)
            shard = sub.shard(self.config.shard_name)
            label_np, weight_np = sub.label, sub.weight
            feats = shard.x if self._dense else (shard.ids, shard.vals)
        else:
            lo, hi = self.plan.bounds(k)
            shard = self.data.shard(self.config.shard_name)
            label_np = self.data.label[lo:hi]
            weight_np = self.data.weight[lo:hi]
            feats = (
                shard.x[lo:hi] if self._dense
                else (shard.ids[lo:hi], shard.vals[lo:hi])
            )
        label = jnp.asarray(label_np)
        weight = jnp.asarray(weight_np)
        off = jnp.asarray(offsets[k])
        if self._dense:
            return DenseBatch(jnp.asarray(feats), label, off, weight)
        return SparseBatch(
            jnp.asarray(feats[0]), jnp.asarray(feats[1]),
            label, off, weight,
        )

    def _streamed_value_and_grad(self, w, offs):
        """One pass over the chunk stream: the jitted per-chunk kernel
        (``_chunk_value_and_grad`` — the existing
        ``_fast_data_value_and_grad`` routing unchanged per chunk) computes
        each chunk's data value+grad on device, and the CROSS-CHUNK reduce
        is a Neumaier-COMPENSATED float64 accumulation on host (ISSUE 11
        satellite) — the fixed-effect analog of the tiles' partials: the
        cross-chunk accumulation error is independent of the chunk count
        (a 1-chunk and a 1000-chunk pass reduce identically up to the
        per-chunk f32 inputs themselves), which keeps streamed-vs-resident
        parity at the two-solver f32 plateau floor instead of drifting
        with the chunk count."""
        import jax.numpy as jnp

        from photon_tpu.data.streaming import _chunk_value_and_grad

        data_obj = dataclasses.replace(
            self.objective, l2_weight=0.0, l1_weight=0.0
        )
        acc = NeumaierAccumulator(self.dim)
        for chunk in self.streamer.stream(
            lambda k: self._chunk_batch(k, offs), self.plan.num_chunks
        ):
            kernel = data_obj._sparse_kernel(chunk, self.dim)
            v, g = _chunk_value_and_grad(data_obj, kernel, w, chunk)
            # host-sync: the cross-chunk reduce — each chunk's scalar
            # value + [dim] gradient land on host and fold into the
            # compensated f64 accumulator (bulk dim-sized transfer).
            acc.add(float(v), np.asarray(g, np.float64))
        total_v, total_g = acc.value, acc.grad
        l2 = self.objective.l2_weight
        if l2:
            # host-sync: dim-sized regularization terms of the f64 reduce.
            w_host = np.asarray(w, np.float64)
            total_v += 0.5 * l2 * float(w_host @ w_host)
            total_g = total_g + l2 * w_host
        return (
            jnp.asarray(np.float32(total_v)),
            jnp.asarray(total_g.astype(np.float32)),
        )

    def train(self, offsets, initial_model: Optional[FixedEffectModel] = None):
        """One streamed GLM fit against the tiled offsets.  ``offsets`` is
        the tiled residual table's view for this coordinate (``chunk(k)``
        per-chunk host vectors, frozen for the duration of the train)."""
        import jax
        import jax.numpy as jnp

        from photon_tpu.core.optimizers import OptimizationStatesTracker
        from photon_tpu.data.streaming import streaming_lbfgs

        # The tiles cannot change during this train: materialize every
        # chunk's offsets once, then every streamed pass re-reads them.
        offs = [offsets.chunk(k) for k in range(self.plan.num_chunks)]
        coord = self

        class _Objective:
            """The streaming_lbfgs-facing surface: every evaluation is one
            streamed pass with the f64 cross-chunk reduce above."""

            def value_and_grad(self, w):
                return coord._streamed_value_and_grad(w, offs)

        sobj = _Objective()
        w0 = jnp.zeros(self.dim, jnp.float32)
        if initial_model is not None:
            w0 = jnp.asarray(initial_model.coefficients.means)
        t0 = time.monotonic()
        result = streaming_lbfgs(
            sobj, w0, self.config.problem.optimizer_config
        )
        jax.block_until_ready(result.w)
        tracker = OptimizationStatesTracker(result, time.monotonic() - t0)
        means = result.w
        from photon_tpu.fault.injection import consume_nan_injection
        from photon_tpu.models.glm import Coefficients, model_for_task

        if consume_nan_injection(getattr(self, "fault_name", None)):
            means = means.at[0].set(jnp.nan)
        # Non-finite guard, mirroring the resident coordinate: a poisoned
        # solve keeps the previous iterate (the streamed loop already
        # synced per pass, so this check costs one dim-sized host reduce).
        tracker.quarantined = 0
        if not bool(jnp.all(jnp.isfinite(means))):
            tracker.quarantined = 1
            means = (
                jnp.asarray(initial_model.coefficients.means)
                if initial_model is not None else jnp.zeros_like(means)
            )
        model = FixedEffectModel(
            model=model_for_task(self.task_type, Coefficients(means, None)),
            shard_name=self.config.shard_name,
        )
        return model, tracker

    def score_stream(self, model: FixedEffectModel) -> np.ndarray:
        """Training-data margins assembled chunk by chunk (host ``[n]``)."""
        if model.shard_name != self.config.shard_name:
            # host-sync: foreign-shard warm starts score through the
            # model's own host path (no chunk layout for that shard here).
            return np.asarray(model.score(self.data), np.float32)
        return score_model_chunks(
            model, self.data, self.plan, self.streamer, source=self.source
        )


# ---------------------------------------------------------------------------
# Streamed random-effect coordinate
# ---------------------------------------------------------------------------


class StreamedRandomEffectHostData:
    """Host-side bucketed layout of one random-effect coordinate: the same
    entity grouping + size-binned merge as the resident
    ``RandomEffectDeviceData``, but the padded ``[E, R, ...]`` bin blocks
    stay in HOST memory — the training pass uploads entity sub-blocks
    through the chunk streamer instead of pinning whole bins in HBM.
    Shared across sweep configurations by the estimator (the grouping is
    the expensive one-time host pass)."""

    def __init__(self, data: GameDataset, config):
        from photon_tpu.game.batched_solve import bin_layout

        self.config = config
        self.dataset = build_random_effect_dataset(
            data,
            entity_column=config.entity_column,
            shard_name=config.shard_name,
            active_row_cap=config.active_row_cap,
            seed=config.seed,
        )
        self.dim = self.dataset.dim
        raw = self.dataset.buckets
        self.bins = [
            merge_buckets([raw[i] for i in group])
            for group in bin_layout(raw)
        ]
        # Foreign-vocabulary warm-start join cache — same contract as the
        # resident device data (coordinate._foreign_src_idx reads it).
        self._warm_join_cache: dict = {}

    def entity_bytes(self, bucket: EntityBucket) -> int:
        """Approximate host/device bytes ONE entity of ``bucket`` occupies
        (feature block + labels/weights/offsets) — the sub-block sizing
        unit."""
        feats = bucket.features
        if isinstance(feats, DenseShard):
            per = feats.x.dtype.itemsize * feats.x.shape[2]
        else:
            per = (
                feats.ids.dtype.itemsize + feats.vals.dtype.itemsize
            ) * feats.ids.shape[2]
        # label + weight + offsets, f32 each.
        return bucket.row_capacity * (per + 12)


def _slice_bucket(bucket: EntityBucket, e0: int, e1: int) -> EntityBucket:
    """Entity-axis window ``[e0, e1)`` of a host bucket (numpy views)."""
    feats = bucket.features
    if isinstance(feats, DenseShard):
        feats = DenseShard(feats.x[e0:e1])
    else:
        feats = SparseShard(feats.ids[e0:e1], feats.vals[e0:e1], feats.dim_)
    return EntityBucket(
        row_capacity=bucket.row_capacity,
        entity_index=bucket.entity_index[e0:e1],
        row_index=bucket.row_index[e0:e1],
        row_weight=bucket.row_weight[e0:e1],
        label=bucket.label[e0:e1],
        features=feats,
    )


class StreamedRandomEffectCoordinate:
    """Per-entity batched GLM fits whose bin blocks stream through the
    chunk budget: each size bin's entities are solved in fixed-size
    sub-blocks (padded to one shape per bin — one compiled program per
    bin, like resident), uploaded double-buffered while the previous
    block's vmapped/Newton solve runs.  Per-entity independence of the
    batched solvers makes the block split numerically invisible."""

    kind = "random"

    def __init__(
        self,
        data: GameDataset,
        config,
        task_type: str,
        plan: ChunkPlan,
        streamer: ChunkStreamer,
        host_data: Optional[StreamedRandomEffectHostData] = None,
        source=None,
    ):
        from photon_tpu.core.objective import GlmObjective
        from photon_tpu.core.problem import GlmOptimizationProblem

        if config.projection != "none":
            raise ValueError(
                "streamed GAME random effects support projection=none only "
                f"(got {config.projection!r}); projected solves are a "
                "resident-mode feature"
            )
        if getattr(config, "row_split", False):
            raise ValueError(
                "row_split is a mesh feature; streamed GAME runs "
                "single-controller (see README §Out-of-core GAME)"
            )
        _require_streamable_problem(config, "streamed random effect")
        self.data = data
        self.config = config
        self.task_type = task_type
        self.plan = plan
        self.streamer = streamer
        self.mesh = None
        self.source = source  # spilled chunk features for re-scoring
        self.device_data = host_data or StreamedRandomEffectHostData(
            data, config
        )
        self.dataset = self.device_data.dataset
        self.dim = self.dataset.dim
        # The chunk budget in bytes bounds each in-flight entity block the
        # same way it bounds a row chunk.
        self._block_budget = max(
            1, plan.chunk_rows * per_row_bytes(data)
        )
        obj = GlmObjective.create(task_type, config.problem.regularization)
        self.problem = GlmOptimizationProblem(obj, config.problem)
        self._solver = functools.partial(
            self.problem.solver(vmapped=True), self.problem.objective
        )

    def _bin_blocks(self) -> list:
        """Flat block schedule ``[(bin_index, e0, e1, block_entities)]``:
        every bin's entity axis cut into budget-sized windows; the LAST
        window of a bin pads up to ``block_entities`` (one compiled shape
        per bin)."""
        blocks = []
        for i, bucket in enumerate(self.device_data.bins):
            e_bytes = self.device_data.entity_bytes(bucket)
            e_sub = max(1, min(
                bucket.num_entities, self._block_budget // max(1, e_bytes)
            ))
            for e0 in range(0, bucket.num_entities, e_sub):
                blocks.append(
                    (i, e0, min(bucket.num_entities, e0 + e_sub), e_sub)
                )
        return blocks

    def _routes(self) -> dict:
        from photon_tpu.game.batched_solve import solver_route

        return {
            i: solver_route(self.config.problem, self.dim, row_split=False)
            for i in range(len(self.device_data.bins))
        }

    def _load_block(self, block, offsets_full: np.ndarray):
        """Worker-side sub-block load: slice + pad the host bin, gather the
        block's training offsets from the tiled offsets vector, and place
        everything on device."""
        import jax.numpy as jnp

        from photon_tpu.data.batch import DenseBatch, SparseBatch

        i, e0, e1, e_sub = block
        sub = _slice_bucket(self.device_data.bins[i], e0, e1)
        if sub.num_entities < e_sub:
            sub = pad_bucket_entities(sub, e_sub, self.dataset.num_entities)
        off = offsets_full[sub.row_index] * (sub.row_weight > 0)
        label = jnp.asarray(sub.label)
        weight = jnp.asarray(sub.row_weight)
        off_dev = jnp.asarray(off.astype(np.float32))
        feats = sub.features
        if isinstance(feats, DenseShard):
            batch = DenseBatch(jnp.asarray(feats.x), label, off_dev, weight)
        else:
            batch = SparseBatch(
                jnp.asarray(feats.ids), jnp.asarray(feats.vals),
                label, off_dev, weight,
            )
        return i, batch, jnp.asarray(sub.entity_index.astype(np.int32))

    def _solve_block(self, route: str, batch, w0):
        if route == "newton":
            from photon_tpu.game.batched_solve import cached_newton_solver

            return cached_newton_solver(self.config.problem)(
                self.problem.objective, batch, w0
            )
        if route == "newton_cg":
            # Matrix-free large-dim route (ISSUE 14): streamed high-dim
            # bins get the same Hessian-vector-product CG program as
            # resident ones — no [B, d, d] block competes with the chunk
            # window for device memory.
            from photon_tpu.game.batched_solve import cached_newton_cg_solver

            return cached_newton_cg_solver(self.config.problem)(
                self.problem.objective, batch, w0
            )
        return self._solver(batch, w0)

    def _initial_table(self, initial_model: RandomEffectModel):
        """Key-aligned warm-start table with the trailing dummy slot —
        same-vocabulary models stay on device; foreign vocabularies go
        through the shared (cached, io-pool-prefetchable) host join."""
        import jax.numpy as jnp

        if initial_model.dim != self.dim:
            raise ValueError(
                f"warm-start model dim {initial_model.dim} != coordinate "
                f"dim {self.dim}"
            )
        if keys_match(initial_model.keys, self.dataset.keys):
            table = jnp.asarray(initial_model.table, jnp.float32)
            return jnp.concatenate(
                [table, jnp.zeros((1, self.dim), table.dtype)]
            )
        return jnp.asarray(_align_foreign_table(self, initial_model))

    def train(self, offsets, initial_model: Optional[RandomEffectModel] = None):
        """Solve every entity, streaming bin sub-blocks through the chunk
        budget; returns (model, DeferredSolveStats) — the stats accumulator
        stays on device for the descent boundary drain."""
        import jax.numpy as jnp

        from photon_tpu.fault.injection import consume_nan_injection

        num_entities = self.dataset.num_entities
        offsets_full = offsets.full()
        table = jnp.zeros((num_entities + 1, self.dim), jnp.float32)
        init_table = (
            None if initial_model is None
            else self._initial_table(initial_model)
        )
        acc = jnp.zeros(6, jnp.int32)
        inject_nan = consume_nan_injection(getattr(self, "fault_name", None))
        routes = self._routes()
        blocks = self._bin_blocks()
        first = True
        for i, batch, entity_idx in self.streamer.stream(
            lambda j: self._load_block(blocks[j], offsets_full), len(blocks)
        ):
            if init_table is not None:
                w0 = init_table[entity_idx]
            else:
                w0 = jnp.zeros((entity_idx.shape[0], self.dim), jnp.float32)
            coefficients, result = self._solve_block(routes[i], batch, w0)
            means = coefficients.means
            if inject_nan and first:
                means = means.at[0].set(jnp.nan)
            first = False
            good = jnp.all(jnp.isfinite(means), axis=1)
            prev_rows = (
                init_table[entity_idx] if init_table is not None else 0.0
            )
            table = table.at[entity_idx].set(
                jnp.where(good[:, None], means, prev_rows)
            )
            acc = _accumulate_solve_stats(
                acc, entity_idx, num_entities, result.converged,
                result.iterations, good,
                cg_iterations=getattr(result, "cg_iterations", None),
            )
        model = RandomEffectModel(
            table=table[:num_entities],
            keys=self.dataset.keys,
            entity_column=self.config.entity_column,
            shard_name=self.config.shard_name,
            task_type=self.task_type,
        )
        return model, DeferredSolveStats(acc)

    def score_stream(self, model: RandomEffectModel) -> np.ndarray:
        """Training-data margins assembled chunk by chunk (host ``[n]``)."""
        if (model.shard_name != self.config.shard_name
                or model.entity_column != self.config.entity_column):
            # host-sync: foreign-layout warm starts score through the
            # model's own host path.
            return np.asarray(model.score(self.data), np.float32)
        # host-sync: foreign-vocabulary key compare/join (warm starts from
        # disk); same-run models hit the identity check.
        if keys_match(model.keys, self.dataset.keys):
            idx = self.dataset.entity_idx_per_row
        else:
            idx = entity_index_for(
                self.data.id_columns[self.config.entity_column],
                # host-sync: foreign vocabularies are host numpy keys.
                np.asarray(model.keys),
            )
        return score_model_chunks(
            model, self.data, self.plan, self.streamer, entity_idx=idx,
            source=self.source,
        )


# ---------------------------------------------------------------------------
# The streamed descent loop
# ---------------------------------------------------------------------------


class StreamedCoordinateDescent:
    """Coordinate descent whose data plane is the chunk stream: same outer
    contract as :class:`~photon_tpu.game.descent.CoordinateDescent` (update
    order, residual passing, incremental validation, quarantine budget,
    preemption, checkpoint/resume), different residency — see module
    docstring.  Built by :class:`~photon_tpu.game.estimator.GameEstimator`
    when ``stream_chunks`` is set."""

    def __init__(
        self,
        coordinates: Dict[str, object],
        task_type: str,
        training_data: GameDataset,
        validation_data: Optional[GameDataset] = None,
        evaluators: Optional[MultiEvaluator] = None,
        plan: Optional[ChunkPlan] = None,
        streamer: Optional[ChunkStreamer] = None,
        logger: Optional[PhotonLogger] = None,
        telemetry=None,
        spill: Optional[SpillContext] = None,
    ):
        if not coordinates:
            raise ValueError(
                "StreamedCoordinateDescent needs at least one coordinate"
            )
        self.coordinates = dict(coordinates)
        self.task_type = task_type
        self.training_data = training_data
        self.validation_data = validation_data
        self.evaluators = evaluators
        self.logger = logger or PhotonLogger("photon_tpu.game.stream")
        self.telemetry = telemetry or NULL_SESSION
        self.plan = plan or ChunkPlan(
            training_data.num_examples, training_data.num_examples
        )
        self.streamer = streamer or ChunkStreamer(self.telemetry)
        self.spill = spill
        self._val_idx_cache = entity_index_cache()

    # -- helpers -------------------------------------------------------------
    def _fingerprint(self, config_key=None, locked=(), warm_start=False):
        has_validation = (
            self.validation_data is not None and self.evaluators is not None
        )
        return stream_fingerprint(
            self.task_type, self.coordinates,
            self.training_data.num_examples, self.plan.chunk_rows,
            config_key=config_key,
            validation_key=(
                self.evaluators.primary.name if has_validation else None
            ),
            locked=locked, warm_start=warm_start,
            coordinate_kinds={
                name: getattr(c, "kind", type(c).__name__)
                for name, c in self.coordinates.items()
            },
        )

    def _val_plan(self) -> ChunkPlan:
        return ChunkPlan(
            self.validation_data.num_examples, self.plan.chunk_rows
        )

    def _score_validation(self, model) -> np.ndarray:
        """One coordinate model's margins over the validation rows,
        streamed per chunk (entity joins cached per vocabulary)."""
        idx = None
        if isinstance(model, RandomEffectModel):
            idx = cached_entity_index(
                self._val_idx_cache, self.validation_data,
                model.entity_column, model.keys,
            )
        return score_model_chunks(
            model, self.validation_data, self._val_plan(), self.streamer,
            entity_idx=idx,
        )

    def _evaluate(self, val_table: TiledValidationTable) -> Dict[str, float]:
        """Host evaluation of the tiled composite margin (the compensated
        per-chunk partials carry host-f64-equivalent precision)."""
        composite = val_table.composite_full()
        data = self.validation_data
        entity_ids = dict(data.id_columns)
        return self.evaluators.evaluate(
            composite, data.label, data.weight, entity_ids
        )

    def _snapshot(
        self, iteration: int, cursor: int, num_iterations: int,
        models, best_model, best_metrics, best_iteration, history,
        residuals, quarantined: int, fp: dict,
    ) -> DescentState:
        # Monotonic checkpoint sequence across epoch/cursor positions:
        # mid-epoch snapshots of iteration i+1 (cursor 1..C) sort after the
        # end-of-iteration-i snapshot (cursor 0) and before i+1's.
        n_pos = len(self.coordinates) + 1
        seq = (iteration + 1) * n_pos + cursor
        return DescentState(
            iteration=iteration,
            num_iterations=num_iterations,
            task_type=self.task_type,
            models=dict(models),
            best_models=(
                dict(best_model.coordinates) if best_model is not None else {}
            ),
            best_metrics=dict(best_metrics),
            best_iteration=best_iteration,
            history=list(history),
            residual_rows=residuals.snapshot_rows(),
            quarantined=quarantined,
            fingerprint=fp,
            stream={
                "chunk_rows": int(self.plan.chunk_rows),
                "cursor": int(cursor),
                "seq": int(seq),
                "tile_digests": residuals.tile_digests(),
                # Informational: spilled snapshots carry EMPTY residual
                # rows — the on-disk tiles are referenced by the digests
                # above, not re-saved (resume re-adopts or rebuilds; the
                # spill residency itself is deliberately NOT fingerprinted
                # because spilled and host-resident tiles are bit-equal).
                "spilled": self.spill is not None,
            },
        )

    # -- run -----------------------------------------------------------------
    def run(
        self,
        num_iterations: int,
        initial_model: Optional[GameModel] = None,
        locked_coordinates: Sequence[str] = (),
        checkpoint_fn=None,
        checkpointer=None,
        resume_state: Optional[DescentState] = None,
        max_quarantined: Optional[int] = None,
        config_key: Optional[str] = None,
    ) -> DescentResult:
        try:
            result = self._run(
                num_iterations, initial_model=initial_model,
                locked_coordinates=locked_coordinates,
                checkpoint_fn=checkpoint_fn, checkpointer=checkpointer,
                resume_state=resume_state, max_quarantined=max_quarantined,
                config_key=config_key,
            )
        except BaseException:
            if checkpointer is not None and hasattr(checkpointer, "drain"):
                checkpointer.drain(reraise=False)
            raise
        finally:
            from photon_tpu.fault.watchdog import complete

            complete("descent.iteration")
        if checkpointer is not None and hasattr(checkpointer, "drain"):
            checkpointer.drain()
        return result

    def _run(
        self,
        num_iterations: int,
        initial_model: Optional[GameModel] = None,
        locked_coordinates: Sequence[str] = (),
        checkpoint_fn=None,
        checkpointer=None,
        resume_state: Optional[DescentState] = None,
        max_quarantined: Optional[int] = None,
        config_key: Optional[str] = None,
    ) -> DescentResult:
        locked = set(locked_coordinates)
        unknown = locked - set(self.coordinates)
        if unknown:
            raise KeyError(
                f"locked coordinates not in update sequence: {sorted(unknown)}"
            )
        if locked and initial_model is None:
            raise ValueError("locked coordinates require an initial model")
        for name in locked:
            if initial_model is not None and name not in initial_model.coordinates:
                raise KeyError(
                    f"locked coordinate {name!r} missing from initial model"
                )

        telemetry = self.telemetry
        fp = self._fingerprint(
            config_key, locked=locked, warm_start=initial_model is not None
        )
        models: Dict[str, object] = {}
        if self.spill is not None:
            residuals = SpilledResidualTable(
                self.training_data.offset, names=list(self.coordinates),
                plan=self.plan, store=self.spill.store,
                cache=self.spill.cache, telemetry=telemetry,
            )
            if resume_state is None:
                # A fresh fit must not read a previous run's published
                # tiles as its zero state.
                residuals.reset_store()
        else:
            residuals = TiledResidualTable(
                self.training_data.offset, names=list(self.coordinates),
                plan=self.plan, telemetry=telemetry,
            )
        val_table = None
        if self.validation_data is not None and self.evaluators is not None:
            val_table = TiledValidationTable(
                self.validation_data.offset,
                names=list(self.coordinates),
                plan=self._val_plan(), telemetry=telemetry,
            )

        best_model: Optional[GameModel] = None
        best_metrics: Dict[str, float] = {}
        best_iteration = -1
        history: list = []
        start_iteration = 0
        resume_cursor = 0
        quarantined_total = 0

        if resume_state is not None:
            from photon_tpu.fault.checkpoint import (
                CheckpointError,
                require_fingerprint,
            )

            require_fingerprint(resume_state, fp, "this streamed descent")
            models = dict(resume_state.models)
            stream_meta = resume_state.stream or {}
            saved_digests = stream_meta.get("tile_digests")
            rows = resume_state.residual_rows
            if rows:
                residuals.load_rows(rows)
            elif hasattr(residuals, "attach_resume"):
                # Spilled checkpoint: the tiles were REFERENCED, not
                # re-saved — adopt the on-disk part files (reads are
                # digest-verified; corruption is refused loudly).
                residuals.attach_resume()
            if saved_digests is not None:
                rebuilt = residuals.tile_digests()
                if rebuilt != list(saved_digests) and not rows:
                    # Referenced tiles are stale (a kill tore the
                    # update sequence mid-write-back, or the spill
                    # residency changed between runs).  The tiles are
                    # a pure function of the checkpointed models over
                    # the fingerprinted data+plan: rebuild them
                    # deterministically and re-verify.
                    telemetry.counter("tiles.rebuilt").inc()
                    self.logger.info(
                        "on-disk tiles do not match the checkpoint; "
                        "rebuilding from the checkpointed models"
                    )
                    if hasattr(residuals, "reset_store"):
                        # Spilled table: dropping the part files IS
                        # the zero state — no stale-tile reads, no
                        # zero-tile publishes that the model rebuild
                        # below would immediately overwrite.
                        residuals.reset_store()
                    else:
                        residuals.clear()
                    for name, coord_model in models.items():
                        residuals.update(
                            name,
                            self.coordinates[name].score_stream(
                                coord_model
                            ),
                        )
                    residuals.drain_guard_flags()  # checkpointed = guarded
                    rebuilt = residuals.tile_digests()
                if rebuilt != list(saved_digests):
                    raise CheckpointError(
                        "score-tile digests do not match the "
                        "checkpoint's (per-chunk state diverged); "
                        "refusing to resume"
                    )
            if val_table is not None:
                for name, model in models.items():
                    val_table.update(
                        name, self._score_validation(model)
                    )
                val_table.drain_guard_flags()  # checkpointed = guarded
            if resume_state.best_models:
                best_model = GameModel(
                    dict(resume_state.best_models), self.task_type
                )
            best_metrics = dict(resume_state.best_metrics)
            best_iteration = resume_state.best_iteration
            history = list(resume_state.history)
            quarantined_total = resume_state.quarantined
            start_iteration = resume_state.iteration + 1
            resume_cursor = int(stream_meta.get("cursor", 0))
            self.logger.info(
                "resumed streamed descent at iteration %d coordinate cursor "
                "%d", start_iteration, resume_cursor,
            )
        elif initial_model is not None:
            for name, coord_model in initial_model.coordinates.items():
                if name not in self.coordinates:
                    continue
                models[name] = coord_model
                residuals.update(
                    name,
                    self.coordinates[name].score_stream(coord_model),
                )
                if val_table is not None:
                    val_table.update(
                        name, self._score_validation(coord_model)
                    )
            # Overlap the remaining host-resident warm-start work (the
            # foreign-vocabulary key joins) with the first coordinate's
            # training — ISSUE 10 satellite; shared with the resident loop.
            from photon_tpu.game.coordinate import prefetch_warm_joins

            prefetch_warm_joins(self.coordinates, initial_model)

        # Seed-guard drain: rejected seed rows belong to the initial model
        # (same semantics as the resident loop).
        seed_rejected = set(residuals.poll_quarantined())
        if val_table is not None:
            seed_rejected |= set(val_table.poll_quarantined())
        bad_locked = sorted(seed_rejected & locked)
        if bad_locked:
            raise ValueError(
                f"locked coordinate(s) {bad_locked} produced non-finite "
                "scores from the initial model; a locked coordinate cannot "
                "be quarantined"
            )
        for name in sorted(seed_rejected):
            telemetry.counter(
                "descent.quarantined", coordinate=name, stage="seed"
            ).inc()
            quarantined_total += 1
            models.pop(name, None)
            self.logger.info(
                "coordinate %s: non-finite scores from the initial model "
                "quarantined (cold start instead)", name,
            )
        if max_quarantined is not None and quarantined_total > max_quarantined:
            raise QuarantineBudgetError(
                f"{quarantined_total} quarantined solves/score rows "
                f"exceed --max-quarantined {max_quarantined}"
            )

        if start_iteration >= num_iterations:
            last = GameModel(dict(models), self.task_type)
            return DescentResult(
                best_model=best_model if best_model is not None else last,
                last_model=last,
                best_metrics=best_metrics,
                history=history,
            )

        from photon_tpu.fault.preemption import (
            PreemptedError,
            consume_preempt_injection,
            preemption_requested,
            preemption_reason,
        )
        from photon_tpu.fault.watchdog import heartbeat

        def preempt_exit(where: str):
            telemetry.counter("descent.preempted").inc()
            if checkpointer is not None and hasattr(checkpointer, "drain"):
                checkpointer.drain()
                hint = "resume with --resume auto"
            else:
                hint = ("no checkpointer configured — a restart begins "
                        "from scratch (set --checkpoint-dir)")
            raise PreemptedError(
                f"preempted ({preemption_reason()}) {where}; {hint}"
            )

        order = list(self.coordinates)
        game_model = GameModel(dict(models), self.task_type)
        for it in range(start_iteration, num_iterations):
            fault_point("descent:kill", iteration=it)
            consume_preempt_injection(it)
            if preemption_requested():
                preempt_exit(f"before iteration {it}")
            heartbeat("descent.iteration")
            coord_logs: Dict[str, str] = {}
            trained = 0
            deferred: Dict[str, object] = {}
            skip = resume_cursor if it == start_iteration else 0
            with telemetry.span(
                "descent.iteration", iteration=it, mode=STREAM_RESIDUAL_MODE
            ) as iter_span:
                for pos, name in enumerate(order):
                    if name in locked or pos < skip:
                        continue
                    coord = self.coordinates[name]
                    # Mid-epoch kill/preempt points: the chunk-cursor
                    # checkpoint below makes a coordinate boundary a safe
                    # restart line, so both fire here too.
                    fault_point(
                        "descent:kill", iteration=it, coordinate=name
                    )
                    if preemption_requested():
                        preempt_exit(
                            f"mid-epoch before coordinate {name!r} of "
                            f"iteration {it}"
                        )
                    prev = models.get(name)
                    offsets = _TiledOffsets(residuals, name)
                    with self.logger.timed(f"iter{it}-{name}", span=False), \
                            telemetry.span(
                                "descent.coordinate", iteration=it,
                                coordinate=name,
                            ):
                        model, info = coord.train(
                            offsets, initial_model=models.get(name)
                        )
                    models[name] = model
                    residuals.update(name, coord.score_stream(model))
                    rejected = set(residuals.poll_quarantined())
                    if val_table is not None and name not in rejected:
                        val_table.update(
                            name, self._score_validation(model)
                        )
                        rejected |= set(val_table.poll_quarantined())
                    if name in rejected:
                        # Non-finite score row: roll the model back to the
                        # previous iterate (drop it entirely on a cold
                        # start) and re-sync BOTH tables — same semantics,
                        # handled immediately because the tiled guard is a
                        # host check.
                        telemetry.counter(
                            "descent.quarantined", coordinate=name,
                            stage="score_row",
                        ).inc()
                        quarantined_total += 1
                        if prev is not None:
                            models[name] = prev
                            residuals.update(
                                name, coord.score_stream(prev)
                            )
                            if val_table is not None:
                                val_table.update(
                                    name, self._score_validation(prev)
                                )
                        else:
                            models.pop(name, None)
                            residuals.update(
                                name, np.zeros(self.plan.n, np.float32)
                            )
                            if val_table is not None:
                                val_table.update(
                                    name,
                                    np.zeros(val_table.n, np.float32),
                                )
                        residuals.drain_guard_flags()
                        if val_table is not None:
                            val_table.drain_guard_flags()
                        self.logger.info(
                            "iter %d coordinate %s: non-finite scores "
                            "quarantined (previous iterate kept)", it, name,
                        )
                    trained += 1
                    if isinstance(info, DeferredSolveStats):
                        if checkpointer is not None:
                            # Checkpointed runs resolve each coordinate's
                            # stats NOW (one [6]-int32 fetch): the mid-epoch
                            # snapshot below must carry this coordinate's
                            # solve-stage quarantine count, or a kill+resume
                            # that skips past it would permanently lose the
                            # count — and with it --max-quarantined
                            # enforcement parity.  Unchecked runs keep the
                            # strict one-drain-per-iteration path.
                            info = info.resolve()
                        else:
                            deferred[name] = info
                    if not isinstance(info, DeferredSolveStats):
                        q = _quarantine_count(info)
                        if q:
                            telemetry.counter(
                                "descent.quarantined", coordinate=name,
                                stage="solve",
                            ).inc(q)
                            quarantined_total += q
                        _record_coordinate_info(telemetry, name, info)
                        summary = (
                            info.summary().splitlines()[0]
                            if hasattr(info, "summary") else str(info)
                        )
                        coord_logs[name] = summary
                        self.logger.info(
                            "iter %d coordinate %s: %s", it, name, summary
                        )
                    telemetry.counter(
                        "descent.coordinate_updates", coordinate=name
                    ).inc()
                    if max_quarantined is not None and (
                        quarantined_total > max_quarantined
                    ):
                        raise QuarantineBudgetError(
                            f"{quarantined_total} quarantined solves/score "
                            f"rows exceed --max-quarantined {max_quarantined}"
                        )
                    if checkpointer is not None:
                        # The chunk-cursor checkpoint: models + tiles +
                        # cursor after EVERY coordinate, so a mid-epoch
                        # kill resumes at this exact boundary.
                        state = self._snapshot(
                            it - 1, pos + 1, num_iterations, models,
                            best_model, best_metrics, best_iteration,
                            history, residuals, quarantined_total, fp,
                        )
                        with telemetry.span(
                            "descent.checkpoint.save", iteration=it,
                            cursor=pos + 1,
                        ):
                            checkpointer.save(state)

                # THE one stats host sync of the iteration (the
                # chunk-cursor drain): every coordinate's device stats
                # accumulator comes to host in a single batched device_get.
                import jax as _jax

                # host-sync: the sanctioned once-per-iteration stats drain
                # (descent.host_syncs counts it), same as resident.
                stats_host = _jax.device_get(
                    {name: ds.device for name, ds in deferred.items()}
                )
                telemetry.counter("descent.host_syncs", kind="stats").inc()
                for name, ds in deferred.items():
                    info = ds.resolve(stats_host[name])
                    q = int(info.get("quarantined", 0))
                    if q:
                        telemetry.counter(
                            "descent.quarantined", coordinate=name,
                            stage="solve",
                        ).inc(q)
                        quarantined_total += q
                    _record_coordinate_info(telemetry, name, info)
                    coord_logs[name] = str(info)
                    self.logger.info(
                        "iter %d coordinate %s: %s", it, name, info
                    )
                if max_quarantined is not None and (
                    quarantined_total > max_quarantined
                ):
                    raise QuarantineBudgetError(
                        f"{quarantined_total} quarantined solves/score rows "
                        f"exceed --max-quarantined {max_quarantined}"
                    )

                game_model = GameModel(dict(models), self.task_type)
                if checkpoint_fn is not None:
                    with telemetry.span("descent.checkpoint", iteration=it):
                        checkpoint_fn(it, game_model)
                metrics: Dict[str, float] = {}
                with telemetry.span("descent.validate", iteration=it):
                    if val_table is not None:
                        telemetry.counter("validation.score_reuse").inc(
                            (len(self.coordinates) - trained)
                            * self.validation_data.num_examples
                        )
                        metrics = self._evaluate(val_table)
                if metrics:
                    self.logger.info("iter %d validation %s", it, metrics)
                    iter_span.set_attribute("metrics", metrics)
                    for k, v in metrics.items():
                        telemetry.gauge(
                            "descent.validation_metric", metric=k
                        ).set(v)
            # Sweep-end write-back flush: every tile this iteration's C
            # coordinate updates dirtied publishes ONCE (the ISSUE 17
            # batching — the PR 11 write-through design republished each
            # full tile C times per sweep).  Runs before the end-of-
            # iteration checkpoint so its digests describe on-disk tiles
            # a resume can adopt directly.
            if hasattr(residuals, "flush"):
                with telemetry.span("tiles.writeback_flush", iteration=it):
                    residuals.flush()
            telemetry.counter("descent.iterations").inc()
            # The chunk-budget residency gauge: the streamer's measured
            # in-flight peak IS the device footprint of the streamed score
            # plane (there is no resident [C, n] table to account for).
            telemetry.gauge("residuals.device_bytes").set(
                self.streamer.peak_in_flight_bytes
            )
            history.append(
                {"iteration": it, "metrics": metrics,
                 "coordinates": coord_logs}
            )

            if not metrics:
                best_model, best_metrics, best_iteration = (
                    game_model, metrics, it
                )
            else:
                primary = self.evaluators.primary
                if best_model is None or primary.better_than(
                    metrics[primary.name], best_metrics[primary.name]
                ):
                    best_model, best_metrics, best_iteration = (
                        game_model, metrics, it
                    )

            if checkpointer is not None:
                state = self._snapshot(
                    it, 0, num_iterations, models, best_model, best_metrics,
                    best_iteration, history, residuals, quarantined_total,
                    fp,
                )
                with telemetry.span(
                    "descent.checkpoint.save", iteration=it
                ):
                    checkpointer.save(state)

        assert best_model is not None
        return DescentResult(
            best_model=best_model,
            last_model=game_model,
            best_metrics=best_metrics,
            history=history,
        )


@dataclasses.dataclass(frozen=True)
class _TiledOffsets:
    """A coordinate's view of its tiled training offsets: ``chunk(k)``
    feeds the streamed fixed-effect chunks, ``full()`` the random-effect
    host row gather.  Values are identical either way (see tiles.py)."""

    table: TiledResidualTable
    name: str

    def chunk(self, k: int) -> np.ndarray:
        return self.table.offsets_chunk(self.name, k)

    def full(self) -> np.ndarray:
        return self.table.offsets_full(self.name)
