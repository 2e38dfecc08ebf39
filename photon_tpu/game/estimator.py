"""GameEstimator: sweep over GAME optimization configurations.

Rebuild of the reference's ``estimators.GameEstimator`` (SURVEY.md §2.2):
``fit()`` runs CoordinateDescent once per :class:`GameOptimizationConfiguration`
in the sweep (the reference's per-coordinate regularization-weight grid),
evaluates each resulting model on validation data, and selects the best
(model, configuration) pair by the primary evaluator — the reference's
model-selection component.

Warm start / partial retraining (SURVEY.md §5 'Checkpoint'): an
``initial_model`` seeds every coordinate's first fit, and
``locked_coordinates`` keep their initial model entirely (scored, never
retrained).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Sequence

from photon_tpu.core.normalization import NormalizationContext
from photon_tpu.evaluation.evaluators import MultiEvaluator, default_evaluators_for_task
from photon_tpu.game.coordinate import CoordinateConfig, build_coordinate
from photon_tpu.game.data import GameDataset
from photon_tpu.game.descent import CoordinateDescent, DescentResult
from photon_tpu.game.model import GameModel
from photon_tpu.telemetry import NULL_SESSION
from photon_tpu.utils.logging import PhotonLogger


@dataclasses.dataclass(frozen=True)
class GameOptimizationConfiguration:
    """One point of the sweep: per-coordinate configs in update order +
    number of outer coordinate-descent iterations (the reference's
    GameOptimizationConfiguration + coordinateDescentIterations)."""

    coordinates: Dict[str, CoordinateConfig]
    descent_iterations: int = 1
    name: str = ""

    def __post_init__(self):
        if not self.coordinates:
            raise ValueError("configuration needs at least one coordinate")
        if self.descent_iterations < 1:
            raise ValueError("descent_iterations must be >= 1")


@dataclasses.dataclass
class GameResult:
    """One fitted sweep entry: (model, evaluation, configuration) — the
    reference's GameEstimator.fit return triple."""

    model: GameModel
    metrics: Dict[str, float]
    configuration: GameOptimizationConfiguration
    descent: DescentResult


class GameEstimator:
    """Builds coordinates per configuration and runs the descent sweep."""

    def __init__(
        self,
        task_type: str,
        training_data: GameDataset,
        validation_data: Optional[GameDataset] = None,
        evaluators: Optional[MultiEvaluator] = None,
        mesh=None,
        normalization: Optional[Dict[str, NormalizationContext]] = None,
        logger: Optional[PhotonLogger] = None,
        telemetry=None,
        residual_mode: Optional[str] = None,
        validation_mode: Optional[str] = None,
        stream_chunks: Optional[int] = None,
        spill_dir: Optional[str] = None,
        max_host_mb: Optional[float] = None,
        tile_dtype: Optional[str] = None,
    ):
        """``normalization`` is keyed by feature-shard name and applies to
        fixed-effect coordinates on that shard (the reference normalizes the
        fixed-effect objective only).  ``residual_mode`` selects how descent
        passes residuals between coordinates, ``validation_mode`` how it
        scores/evaluates validation data (``auto``/``device``/``host`` —
        see :mod:`photon_tpu.game.residuals`).

        ``stream_chunks`` (rows per chunk, > 0) switches every fit to the
        OUT-OF-CORE streamed descent (:mod:`photon_tpu.game.stream_descent`):
        training data and score state stay host-resident as fixed-size row
        chunks / score tiles, streamed through a double-buffered h2d
        prefetch — device residency is bounded by the chunk window instead
        of the dataset size.  Streamed mode is single-controller (no mesh)
        and replaces the residual/validation mode machinery.

        ``spill_dir`` (requires ``stream_chunks``) adds the DISK tier
        behind the stream (:mod:`photon_tpu.game.tile_store`): feature
        chunks and residual score tiles live in per-chunk part files, an
        LRU host cache bounded by ``max_host_mb`` (MB; ``None`` =
        unbounded cache, still disk-backed) serves them, and the prefetch
        pipeline becomes disk→host→device — the score plane and the
        fixed-effect feature stream are bounded by the cache budget
        instead of the dataset.  (The caller-provided ``training_data``
        itself and the random-effect bin layouts are still host-resident
        — the ROADMAP tiering item's remaining edges.)

        ``tile_dtype`` (requires ``spill_dir``) picks the disk tier's
        storage codec for feature blocks and score tiles —
        ``f32 | bf16 | int8`` (:mod:`photon_tpu.game.lowp`; default f32,
        the bit-exact tier).  Lossy tiers trade a bounded, measured fit-
        metric perturbation (``lowp.TILE_METRIC_TOL``) for 2-4× less
        disk traffic; all accumulation stays f32 and kill→resume parity
        stays exact per codec."""
        self.task_type = task_type
        self.training_data = training_data
        self.validation_data = validation_data
        if evaluators is None and validation_data is not None:
            evaluators = MultiEvaluator(default_evaluators_for_task(task_type))
        self.evaluators = evaluators
        self.mesh = mesh
        if isinstance(normalization, NormalizationContext):
            raise TypeError(
                "pass normalization as {shard_name: NormalizationContext}"
            )
        self.normalization = normalization or {}
        self.logger = logger or PhotonLogger("photon_tpu.game")
        self.telemetry = telemetry or NULL_SESSION
        self.residual_mode = residual_mode
        self.validation_mode = validation_mode
        self.stream_chunks = None
        if stream_chunks is not None:
            if int(stream_chunks) < 1:
                raise ValueError(
                    f"stream_chunks must be >= 1, got {stream_chunks}"
                )
            if mesh is not None:
                raise ValueError(
                    "stream_chunks (out-of-core GAME) runs single-controller"
                    " — drop the mesh or train resident"
                )
            if residual_mode not in (None, "auto") or (
                validation_mode not in (None, "auto")
            ):
                # Same refuse-loudly policy as every other unsupported
                # streamed configuration: an explicitly requested resident
                # engine must not be silently replaced by the tiled tables
                # (the CLI driver strips the flags itself and logs).
                raise ValueError(
                    "stream_chunks replaces the residual/validation "
                    "engines; drop the explicit residual_mode/"
                    "validation_mode (got "
                    f"{residual_mode!r}/{validation_mode!r})"
                )
            self.stream_chunks = int(stream_chunks)
        self.spill_dir = spill_dir
        self.max_host_mb = max_host_mb
        if spill_dir is not None and not self.stream_chunks:
            raise ValueError(
                "spill_dir (the disk-backed tile store) requires "
                "stream_chunks — the disk tier spills the STREAMED fit's "
                "host working set"
            )
        if max_host_mb is not None:
            if max_host_mb <= 0:
                raise ValueError(
                    f"max_host_mb must be > 0, got {max_host_mb}"
                )
            if spill_dir is None:
                raise ValueError(
                    "max_host_mb bounds the spill host cache; set "
                    "spill_dir (or let the driver derive one)"
                )
        from photon_tpu.game.lowp import TILE_DTYPES, check_dtype

        self.tile_dtype = check_dtype(tile_dtype, TILE_DTYPES, "tile dtype")
        if self.tile_dtype != "f32" and spill_dir is None:
            raise ValueError(
                "tile_dtype selects the DISK tier's storage codec; set "
                "spill_dir (host-resident tiles are always f32)"
            )
        # Device-resident data shared across sweep configurations: building
        # the bucketed random-effect datasets (the reference's shuffle) and
        # uploading feature blocks happens once per distinct data config.
        self._device_data_cache: Dict[tuple, object] = {}
        # Fixed-effect batch row-capacity headroom (ISSUE 18 satellite):
        # per data config, the amortized-doubling padded row count the next
        # FixedEffectDeviceData rebuild targets.  A refresh whose grown row
        # count still fits rebuilds at the SAME shape, so every solve
        # program compiled against the batch stays hot (zero recompiles
        # across online refreshes — the test_online pin).
        self._fixed_row_capacity: Dict[tuple, int] = {}
        # Streamed mode: host-side bucketed layouts + the shared chunk
        # streamer (overlap/stall telemetry accumulates across the sweep).
        self._stream_data_cache: Dict[tuple, object] = {}
        self._streamer = None
        self._spill = None
        # Validation scoring cache shared across the whole sweep: one upload
        # of the validation feature shards for ALL configurations.
        self._validation_cache = None

    def _validation_scoring_cache(self):
        """The shared device validation cache, when the resolved modes call
        for one (host-mode runs never pay the upload)."""
        from photon_tpu.game.model import DeviceScoringCache
        from photon_tpu.game.residuals import (
            resolve_residual_mode,
            resolve_validation_mode,
        )

        if self.validation_data is None or self.evaluators is None:
            return None
        mode = resolve_validation_mode(
            self.validation_mode, resolve_residual_mode(self.residual_mode)
        )
        if mode != "device":
            return None
        if self._validation_cache is None:
            self._validation_cache = DeviceScoringCache(
                self.validation_data, mesh=self.mesh, telemetry=self.telemetry
            )
        return self._validation_cache

    def _device_data(self, coord_config):
        from photon_tpu.game.coordinate import (
            FixedEffectCoordinateConfig,
            FixedEffectDeviceData,
            RandomEffectDeviceData,
        )

        key = coord_config.data_key
        if key not in self._device_data_cache:
            if isinstance(coord_config, FixedEffectCoordinateConfig):
                self._device_data_cache[key] = FixedEffectDeviceData(
                    self.training_data, coord_config, self.mesh,
                    row_capacity=self._fixed_row_capacity.get(key),
                )
            else:
                from photon_tpu.game.coordinate import (
                    FactoredRandomEffectCoordinateConfig,
                )

                rc = (
                    coord_config.as_random_config()
                    if isinstance(coord_config, FactoredRandomEffectCoordinateConfig)
                    else coord_config
                )
                self._device_data_cache[key] = RandomEffectDeviceData(
                    self.training_data, rc, self.mesh
                )
        return self._device_data_cache[key]

    def device_layout(self, coord_config):
        """The cached device-resident layout for one coordinate config
        (``FixedEffectDeviceData`` / ``RandomEffectDeviceData``), built on
        first use — the PUBLIC handle the online-learning loop grows
        vocabularies/warm starts against (reaching into the private cache
        would couple callers to its key structure)."""
        return self._device_data(coord_config)

    def entity_vocabularies(self) -> Dict[str, object]:
        """Current entity vocabulary per id column, from the LIVE
        random-effect device layouts (the onboarded state, which may be
        ahead of any saved model's keys)."""
        from photon_tpu.game.coordinate import RandomEffectDeviceData

        return {
            dd.config.entity_column: dd.dataset.keys
            for dd in self._device_data_cache.values()
            if isinstance(dd, RandomEffectDeviceData)
        }

    def _build_coordinates(self, config: GameOptimizationConfiguration):
        coords = {
            name: build_coordinate(
                self.training_data,
                coord_config,
                self.task_type,
                mesh=self.mesh,
                normalization=self.normalization.get(coord_config.shard_name),
                device_data=self._device_data(coord_config),
            )
            for name, coord_config in config.coordinates.items()
        }
        for name, coord in coords.items():
            # The coordinate's update-sequence name, so named fault-injection
            # sites (solve:nan:coord=<name>) and quarantine telemetry can
            # address it.
            coord.fault_name = name
            # The coordinates' own telemetry (bin-occupancy gauges,
            # warm-start transfer counters) lands in the run's session.
            coord.telemetry = self.telemetry
            self._record_placement(name, coord.device_data)
        cache = self._validation_scoring_cache()
        if cache is not None:
            for coord in coords.values():
                # A fixed effect whose training batch carries block tiles
                # (the selector's verdict for its shard) scores its
                # validation rows through tiles too: this estimator owns
                # both layouts, so it tells the cache.
                batch = getattr(coord.device_data, "batch", None)
                if getattr(batch, "bt", None) is not None:
                    cache.score_fixed_through_tiles(coord.config.shard_name)
        return coords

    def _record_placement(self, name: str, device_data) -> None:
        """``placement.devices`` / ``placement.slices`` per coordinate, read
        off the training arrays themselves: how many devices hold the
        fixed effect's rows (or the random effect's entity blocks) and how
        many DISTINCT slices they hold — equal to the mesh size when the
        data is really sharded, 1 slice when it sits replicated or on one
        device whatever the mesh says.

        ``placement.live_rows{coordinate, device}``: the live (weight > 0)
        training rows each device's slice holds, from the host's copies:
        the fixed effect's rows a shard less the mesh padding, each bin's
        ``row_weight > 0`` over the device's slice of entities.  A pass
        over host arrays, and this runs at every fit's start: taken once a
        layout (again when its row count grows), never inside later fits;
        with it, for the fixed effect, ``fixed_effect.layout``."""
        from photon_tpu.game.coordinate import FixedEffectDeviceData

        fixed = isinstance(device_data, FixedEffectDeviceData)
        if fixed:
            arrays = [device_data.batch.label]
        else:
            arrays = [b["label"] for b in device_data.device_buckets]
        if not arrays:
            return
        layout = (id(device_data), device_data.unpadded_n if fixed
                  else len(device_data.dataset.entity_idx_per_row))
        counted = vars(self).setdefault("_live_rows_counted", {})
        if counted.get(name) != layout:
            counted[name] = layout
            if fixed:
                self._count_fixed_layout(name, device_data)
            live: Dict[int, int] = {}
            for i, arr in enumerate(arrays):
                for s in arr.addressable_shards:
                    if fixed:
                        start, stop, _ = s.index[0].indices(arr.shape[0])
                        rows = min(stop, device_data.unpadded_n) - start
                    else:
                        weight = device_data.buckets[i].row_weight
                        rows = int((weight[s.index] > 0).sum())
                    live[s.device.id] = live.get(s.device.id, 0) + max(rows, 0)
            for device, rows in live.items():
                self.telemetry.gauge(
                    "placement.live_rows", coordinate=name, device=device
                ).set(rows)
        devices = set()
        slices = []
        for arr in arrays:
            shards = arr.addressable_shards
            devices |= {s.device for s in shards}
            slices.append(len({str(s.index) for s in shards}))
        self.telemetry.gauge("placement.devices", coordinate=name).set(
            len(devices)
        )
        self.telemetry.gauge("placement.slices", coordinate=name).set(
            min(slices)
        )

    def _count_fixed_layout(self, name: str, device_data) -> None:
        """``fixed_effect.layout{coordinate, kind, kernel}``, once a layout:
        whether the fixed effect's training batch is ``dense`` or ``sparse``
        and, for a sparse one, the value+gradient kernel its fits will be
        answered (``sparse_grad_select.carried_kernel``: the attach's
        verdict where it asked first; told without a measurement).  On a
        mesh the selection is each shard's own at trace time
        (``per_shard``); a dense batch has none."""
        from photon_tpu.data.batch import SparseBatch
        from photon_tpu.ops.sparse_grad_select import carried_kernel

        batch = device_data.batch
        if not isinstance(batch, SparseBatch):
            kind, kernel = "dense", "none"
        elif self.mesh is not None:
            kind, kernel = "sparse", "per_shard"
        else:
            kind, kernel = "sparse", carried_kernel(batch, device_data.dim)
        self.telemetry.counter(
            "fixed_effect.layout", coordinate=name, kind=kind, kernel=kernel
        ).inc()

    # -- streamed (out-of-core) mode -----------------------------------------
    def _stream_plan(self):
        from photon_tpu.game.tiles import ChunkPlan

        return ChunkPlan(self.training_data.num_examples, self.stream_chunks)

    def _stream_streamer(self):
        from photon_tpu.game.tiles import ChunkStreamer

        if self._streamer is None:
            self._streamer = ChunkStreamer(self.telemetry)
        return self._streamer

    def _spill_context(self):
        """The disk tier of a spilled streamed fit, built ONCE per
        estimator: the part-file store, the ``max_host_mb``-bounded LRU
        host cache, and the chunk feature source reading through them.
        Building it spills the training dataset's feature chunks (skipped
        when a previous run over the same dataset+plan already published
        them — mid-epoch resume reuses the store)."""
        if self.spill_dir is None:
            return None
        if self._spill is None:
            from photon_tpu.game.tile_store import TileStore
            from photon_tpu.game.tiles import (
                HostTileCache,
                SpillContext,
                SpilledChunkSource,
                spill_dataset,
            )

            store = TileStore(
                self.spill_dir, telemetry=self.telemetry,
                tile_dtype=self.tile_dtype,
            )
            cache = HostTileCache(
                max_bytes=(
                    None if self.max_host_mb is None
                    else int(self.max_host_mb * (1 << 20))
                ),
                telemetry=self.telemetry,
            )
            plan = self._stream_plan()
            spill_dataset(
                store, self.training_data, plan, telemetry=self.telemetry
            )
            self._spill = SpillContext(
                store=store, cache=cache,
                source=SpilledChunkSource(
                    store, plan, cache, telemetry=self.telemetry,
                ),
            )
        return self._spill

    def _build_stream_coordinates(self, config: GameOptimizationConfiguration):
        """Streamed counterparts of :meth:`_build_coordinates`: no device
        data is uploaded at build time — fixed coordinates stream row
        chunks, random coordinates stream entity sub-blocks from HOST bin
        layouts cached across sweep configurations."""
        from photon_tpu.game.coordinate import (
            FactoredRandomEffectCoordinateConfig,
            FixedEffectCoordinateConfig,
            RandomEffectCoordinateConfig,
        )
        from photon_tpu.game.stream_descent import (
            StreamedFixedEffectCoordinate,
            StreamedRandomEffectCoordinate,
            StreamedRandomEffectHostData,
        )

        plan, streamer = self._stream_plan(), self._stream_streamer()
        spill = self._spill_context()
        source = spill.source if spill is not None else None
        coords = {}
        for name, cc in config.coordinates.items():
            if isinstance(cc, FixedEffectCoordinateConfig):
                coords[name] = StreamedFixedEffectCoordinate(
                    self.training_data, cc, self.task_type, plan, streamer,
                    normalization=self.normalization.get(cc.shard_name),
                    source=source,
                )
            elif isinstance(cc, FactoredRandomEffectCoordinateConfig):
                raise ValueError(
                    f"coordinate {name!r}: factored_random coordinates have "
                    "no streamed path (the pooled latent solve is "
                    "whole-dataset); train resident"
                )
            elif isinstance(cc, RandomEffectCoordinateConfig):
                key = cc.data_key
                if key not in self._stream_data_cache:
                    self._stream_data_cache[key] = (
                        StreamedRandomEffectHostData(self.training_data, cc)
                    )
                coords[name] = StreamedRandomEffectCoordinate(
                    self.training_data, cc, self.task_type, plan, streamer,
                    host_data=self._stream_data_cache[key],
                    source=source,
                )
            else:
                raise TypeError(f"unknown coordinate config {type(cc)!r}")
        for name, coord in coords.items():
            coord.fault_name = name
            coord.telemetry = self.telemetry
        return coords

    def onboard_training_data(self, data: GameDataset,
                              absent_tail=None) -> None:
        """Incremental onboarding between fits: swap in a GROWN training
        dataset whose appended rows may reference BOTH new and existing
        random-effect entities (ISSUE 15: the continual-training loop's
        data-growth edge).

        The cached random-effect device layouts extend in place
        (:meth:`~photon_tpu.game.coordinate.RandomEffectDeviceData.onboard`
        — new entities as appended bins, existing entities' rows scattered
        into per-bin row-capacity headroom, migration past exhausted
        capacity; resident feature blocks untouched, ZERO full layout
        rebuilds — the contract the online service asserts via the
        ``estimator.device_data_rebuilds{kind}`` counter).  Fixed-effect
        device data is whole-dataset and is dropped for a lazy rebuild on
        the next fit, counted as ``kind="fixed"`` — but the rebuild pads to
        an amortized-doubling ROW CAPACITY (weight-0 pad rows), so while
        growth fits the previous capacity the batch shape is unchanged and
        the compiled solve programs stay hot; the ``kind="random"`` count
        stays 0 by construction.  Warm-start models from the previous fit can be grown
        to the merged vocabulary on device with
        :meth:`~photon_tpu.game.model.RandomEffectModel.with_entities`.

        ``absent_tail`` maps an id column to a bool mask over the appended
        rows marking rows that carry no id for that column (the online
        ingest's missing-column fill — those rows join no entity of the
        column's coordinates).
        """
        from photon_tpu.game.coordinate import RandomEffectDeviceData

        if data.num_examples < self.training_data.num_examples:
            raise ValueError(
                "onboard_training_data() needs the grown dataset (rows are "
                "append-only)"
            )
        absent_tail = absent_tail or {}
        with self.telemetry.span(
            "estimator.onboard", rows=data.num_examples
        ):
            # Validate EVERY layout's preconditions before mutating any:
            # one layout rejecting mid-loop must not leave the cache
            # half-onboarded (grown per-user bins against an old-length
            # offsets vector).
            for dd in self._device_data_cache.values():
                if isinstance(dd, RandomEffectDeviceData):
                    dd.check_onboard(
                        data,
                        absent_tail=absent_tail.get(dd.config.entity_column),
                    )
            for key, dd in list(self._device_data_cache.items()):
                if isinstance(dd, RandomEffectDeviceData):
                    before = dd.dataset.num_entities
                    dd.onboard(
                        data, telemetry=self.telemetry,
                        absent_tail=absent_tail.get(dd.config.entity_column),
                    )
                    self.telemetry.counter("estimator.entities_onboarded").inc(
                        dd.dataset.num_entities - before
                    )
                else:
                    # Record the amortized-doubling row capacity the lazy
                    # rebuild will pad to: while the grown row count still
                    # fits the previous capacity the rebuilt batch keeps
                    # its exact shape (weight-0 pad rows), so the solve
                    # programs compiled against it stay hot; past capacity,
                    # double (at least) so growth pays a recompile only
                    # O(log n) times.
                    from photon_tpu.utils import pow2_at_least

                    need = int(data.num_examples)
                    prev = self._fixed_row_capacity.get(
                        key, int(dd.batch.num_examples)
                    )
                    if need > prev:
                        prev = max(pow2_at_least(need), 2 * prev)
                    self._fixed_row_capacity[key] = prev
                    del self._device_data_cache[key]
                    self.telemetry.counter(
                        "estimator.device_data_rebuilds", kind="fixed"
                    ).inc()
        # Streamed host layouts have no incremental-onboard path (they are
        # cheap host structures): drop them for a lazy rebuild at the
        # grown row count.  The spill context follows — the grown dataset
        # re-spills under its new fingerprint on the next fit.
        self._stream_data_cache.clear()
        self._spill = None
        self.training_data = data

    def fit(
        self,
        configurations: Sequence[GameOptimizationConfiguration],
        initial_model: Optional[GameModel] = None,
        locked_coordinates: Sequence[str] = (),
        checkpoint_fn=None,
        checkpoint_dir: Optional[str] = None,
        resume: Optional[str] = None,
        max_quarantined: Optional[int] = None,
        checkpoint_async=None,
        checkpoint_max_staged_mb: Optional[float] = None,
    ) -> List[GameResult]:
        """``checkpoint_fn(iteration, model)`` is forwarded to each descent
        run (per-iteration intermediate model output — SURVEY.md §5).

        ``checkpoint_dir`` turns on preemption-safe descent checkpointing
        (one ``cfg-NNN`` subdirectory per configuration in this call);
        ``resume`` restores from it: ``auto`` resumes whatever is
        checkpointed (fresh start otherwise), ``latest`` requires a
        checkpoint, an explicit path names one checkpoint version (single-
        configuration fits only).  A configuration whose checkpoint already
        covers its final iteration is rebuilt from the snapshot without
        re-running — mid-sweep resume skips finished work.
        ``max_quarantined`` is the descent quarantine budget (None =
        unlimited; see :meth:`CoordinateDescent.run`).  ``checkpoint_async``
        gates the background checkpoint publisher (``'on'``/``'off'``/bool;
        None defers to ``PHOTON_CHECKPOINT_ASYNC``, default on — see
        :func:`photon_tpu.fault.checkpoint.resolve_checkpoint_async`).
        ``checkpoint_max_staged_mb`` bounds the async publisher's staged
        host copies (over the cap a snapshot publishes blocking — see
        :class:`~photon_tpu.fault.checkpoint.CheckpointPublisherBase`).

        Checkpoints are MESH-SHAPE PORTABLE: resume accepts a checkpoint
        written under a different device/process count — restored model
        tables are placed for THIS estimator's mesh and the engines re-pad/
        re-shard score rows onto it (the fingerprint pins the logical
        layout, never the mesh).
        """
        if not configurations:
            raise ValueError("fit() needs at least one configuration")
        if resume and checkpoint_dir is None and resume in ("auto", "latest"):
            raise ValueError(f"resume={resume!r} needs checkpoint_dir")
        if resume and resume not in ("auto", "latest") and len(configurations) > 1:
            raise ValueError(
                "an explicit checkpoint path resumes a single-configuration "
                "fit; use resume='auto' for sweeps"
            )
        from photon_tpu.fault.checkpoint import (
            DescentCheckpointer,
            configuration_key,
            descent_fingerprint,
            require_fingerprint,
        )
        from photon_tpu.game.residuals import resolve_residual_mode

        results = []
        for i, config in enumerate(configurations):
            label = config.name or f"config-{i}"
            config_key = configuration_key(config.coordinates)
            checkpointer = None
            resume_state = None
            if checkpoint_dir is not None:
                checkpointer = DescentCheckpointer(
                    os.path.join(checkpoint_dir, f"cfg-{i:03d}"),
                    telemetry=self.telemetry, logger=self.logger,
                    async_publish=checkpoint_async,
                    max_staged_mb=checkpoint_max_staged_mb,
                )
            if resume:
                # The load places restored model state for THIS run's mesh
                # — whatever shape it is (elastic resume).
                if resume in ("auto", "latest"):
                    resume_state = checkpointer.load(resume, mesh=self.mesh)
                else:
                    resume_state = DescentCheckpointer.load_path(
                        resume, mesh=self.mesh
                    )
            if resume_state is not None:
                # Validate compatibility HERE, before the completed
                # short-circuit below can return a foreign checkpoint's
                # model as this configuration's result.  The config key
                # digests the per-coordinate optimization configs, so a
                # sweep point with different regularization can never
                # adopt this checkpoint.
                has_validation = (
                    self.validation_data is not None
                    and self.evaluators is not None
                )
                kinds = {
                    name: getattr(cc, "kind", type(cc).__name__)
                    for name, cc in config.coordinates.items()
                }
                validation_key = (
                    self.evaluators.primary.name if has_validation else None
                )
                if self.stream_chunks:
                    from photon_tpu.game.stream_descent import (
                        stream_fingerprint,
                    )

                    expected = stream_fingerprint(
                        self.task_type, config.coordinates,
                        self.training_data.num_examples, self.stream_chunks,
                        config_key=config_key,
                        validation_key=validation_key,
                        locked=locked_coordinates,
                        warm_start=initial_model is not None,
                        coordinate_kinds=kinds,
                    )
                else:
                    expected = descent_fingerprint(
                        self.task_type, config.coordinates,
                        self.training_data.num_examples,
                        resolve_residual_mode(self.residual_mode),
                        config_key=config_key,
                        validation_key=validation_key,
                        locked=locked_coordinates,
                        warm_start=initial_model is not None,
                        coordinate_kinds=kinds,
                    )
                require_fingerprint(
                    resume_state, expected, f"configuration {label!r}"
                )
            # Completed means: covers THIS run's requested iterations (a
            # raised descent_iterations resumes and runs the extra passes).
            if (resume_state is not None
                    and resume_state.iteration + 1 >= config.descent_iterations):
                # This configuration already finished before the
                # interruption: rebuild its result from the snapshot.
                best = GameModel(dict(resume_state.best_models), self.task_type)
                descent = DescentResult(
                    best_model=best,
                    last_model=GameModel(
                        dict(resume_state.models), self.task_type
                    ),
                    best_metrics=dict(resume_state.best_metrics),
                    history=list(resume_state.history),
                )
                self.telemetry.counter("estimator.configurations_resumed").inc()
                self.logger.info(
                    "fit-%s restored from completed checkpoint", label
                )
                results.append(
                    GameResult(
                        model=best,
                        metrics=descent.best_metrics,
                        configuration=config,
                        descent=descent,
                    )
                )
                continue
            with self.telemetry.span("estimator.fit", configuration=label), \
                    self.logger.timed(f"fit-{label}", span=False):
                if self.stream_chunks:
                    from photon_tpu.game.stream_descent import (
                        StreamedCoordinateDescent,
                    )

                    loop = StreamedCoordinateDescent(
                        self._build_stream_coordinates(config),
                        self.task_type,
                        self.training_data,
                        self.validation_data,
                        self.evaluators,
                        plan=self._stream_plan(),
                        streamer=self._stream_streamer(),
                        logger=self.logger,
                        telemetry=self.telemetry,
                        spill=self._spill_context(),
                    )
                else:
                    loop = CoordinateDescent(
                        self._build_coordinates(config),
                        self.task_type,
                        self.training_data,
                        self.validation_data,
                        self.evaluators,
                        logger=self.logger,
                        telemetry=self.telemetry,
                        residual_mode=self.residual_mode,
                        validation_mode=self.validation_mode,
                        validation_cache=self._validation_scoring_cache(),
                    )
                descent = loop.run(
                    config.descent_iterations,
                    initial_model=initial_model,
                    locked_coordinates=locked_coordinates,
                    checkpoint_fn=checkpoint_fn,
                    checkpointer=checkpointer,
                    resume_state=resume_state,
                    max_quarantined=max_quarantined,
                    config_key=config_key,
                )
            self.telemetry.counter("estimator.configurations").inc()
            results.append(
                GameResult(
                    model=descent.best_model,
                    metrics=descent.best_metrics,
                    configuration=config,
                    descent=descent,
                )
            )
        return results

    def select_best(self, results: Sequence[GameResult]) -> GameResult:
        """Best sweep entry by the primary evaluator; without validation the
        first entry wins (reference behavior: selection needs a validation
        set)."""
        if self.evaluators is None or not any(r.metrics for r in results):
            return results[0]
        primary = self.evaluators.primary
        best = results[0]
        for r in results[1:]:
            if r.metrics and primary.better_than(
                r.metrics.get(primary.name, float("nan")),
                best.metrics.get(primary.name, float("nan")),
            ):
                best = r
        return best
