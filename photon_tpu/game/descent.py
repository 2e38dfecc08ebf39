"""CoordinateDescent: the GAME outer loop.

Rebuild of the reference's ``algorithm.CoordinateDescent``
(``descend``/``optimize`` — SURVEY.md §2.2, §3.1): cycle the coordinates in
update order for a fixed number of outer iterations; each coordinate trains
against the **residuals** of the others — its training offsets are the
dataset offset plus the sum of every other coordinate's current scores — then
re-scores the data.  After each full pass the composite model is evaluated on
validation data and the best model (by the primary evaluator) is tracked.

Locked coordinates (the reference's partial-retraining lock list) keep their
initial model: they are scored but never retrained.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

import numpy as np

from photon_tpu.evaluation.evaluators import MultiEvaluator
from photon_tpu.fault import QuarantineBudgetError
from photon_tpu.fault.checkpoint import DescentState
from photon_tpu.fault.injection import fault_point
from photon_tpu.game.coordinate import DeferredSolveStats
from photon_tpu.game.data import GameDataset
from photon_tpu.game.model import DeviceScoringCache, GameModel
from photon_tpu.game.residuals import (
    HostResiduals,
    ResidualEngine,
    ValidationEngine,
    resolve_residual_mode,
    resolve_validation_mode,
)
from photon_tpu.telemetry import NULL_SESSION
from photon_tpu.utils.logging import PhotonLogger


@dataclasses.dataclass
class DescentResult:
    """Outcome of one CoordinateDescent run."""

    best_model: GameModel
    last_model: GameModel
    best_metrics: Dict[str, float]
    history: list  # per outer iteration: {"iteration", "metrics", "coordinates"}

    @property
    def models_match(self) -> bool:
        return self.best_model is self.last_model


def _quarantine_count(info) -> int:
    """Quarantined-solve count reported by a coordinate's train() — dict key
    for random-effect stats, attribute for the fixed-effect tracker."""
    if isinstance(info, dict):
        return int(info.get("quarantined", 0))
    return int(getattr(info, "quarantined", 0))


def _record_coordinate_info(telemetry, name: str, info) -> None:
    """Record a coordinate's convergence info into the telemetry registry.

    Fixed-effect coordinates return an OptimizationStatesTracker (which
    knows how to record itself); random-effect coordinates return a stats
    dict over their per-entity vmapped solves."""
    if hasattr(info, "record_to"):
        info.record_to(telemetry.registry, coordinate=name)
    elif isinstance(info, dict) and "entities" in info:
        telemetry.counter("re_solver.entities", coordinate=name).inc(
            info["entities"]
        )
        telemetry.gauge("re_solver.iterations_max", coordinate=name).set(
            info.get("iterations_max", 0)
        )
        # Every bin's lockstep Newton iterations of THIS descent iteration
        # (they rode the boundary drain in the stats vector), and the cells
        # those iterations touched: padded entities x row capacity x
        # iterations.  Counters, so a fit's total is every iteration's
        # work, not the last one's (the re_solver.iterations_max gauge).
        for b, (its, route, cells) in enumerate(zip(
            info.get("bin_iterations", ()), info.get("bin_routes", ()),
            info.get("bin_cells", ()),
        )):
            if route.startswith("newton"):
                telemetry.counter(
                    "solves.newton_iterations", coordinate=name, bin=b
                ).inc(its)
                telemetry.counter(
                    "solves.cells", coordinate=name, bin=b
                ).inc(its * cells)
        cg = info.get("cg_iters", 0)
        if cg:
            # Newton-CG bins only (ISSUE 14): mean inner-CG iterations per
            # CG-ROUTED entity solve this outer iteration — the knob that
            # tells whether the Eisenstat-Walker tolerance and the Jacobi
            # preconditioner are doing their jobs.  The denominator is the
            # CG bins' own entity count, so a coordinate mixing CG and
            # dense/vmapped bins cannot dilute the mean.
            telemetry.histogram("solves.cg_iters", coordinate=name).observe(
                cg / max(info.get("cg_entities", 0), 1)
            )


class CoordinateDescent:
    """Cycles coordinate training with residual (offset) passing.

    ``coordinates`` maps name -> built Coordinate object; iteration order is
    the update order (the reference's coordinateUpdateSequence).

    Residual passing runs in one of two modes (``game.residuals``):
    ``device`` keeps every coordinate's score vector in a device-resident
    table and computes each coordinate's training offsets with one jitted
    kernel; ``host`` is the float64 numpy accumulate the seed shipped with
    (``PHOTON_RESIDUALS=host`` / ``--residuals host``).

    Validation runs in one of two modes too (``validation_mode``):
    ``device`` keeps a second score table over the validation rows
    (:class:`ValidationEngine` + a shared :class:`DeviceScoringCache`),
    re-scores ONLY the coordinates that retrained each outer iteration, and
    evaluates the jitted device metrics — the per-iteration host traffic is
    the per-metric scalars; ``host`` is the seed's full
    ``GameModel.score`` fetch + numpy evaluator pass.
    """

    def __init__(
        self,
        coordinates: Dict[str, object],
        task_type: str,
        training_data: GameDataset,
        validation_data: Optional[GameDataset] = None,
        evaluators: Optional[MultiEvaluator] = None,
        logger: Optional[PhotonLogger] = None,
        telemetry=None,
        residual_mode: Optional[str] = None,
        validation_mode: Optional[str] = None,
        validation_cache: Optional[DeviceScoringCache] = None,
    ):
        if not coordinates:
            raise ValueError("CoordinateDescent needs at least one coordinate")
        self.coordinates = dict(coordinates)
        self.task_type = task_type
        self.training_data = training_data
        self.validation_data = validation_data
        self.evaluators = evaluators
        self.logger = logger or PhotonLogger("photon_tpu.game")
        self.telemetry = telemetry or NULL_SESSION
        self.residual_mode = resolve_residual_mode(residual_mode)
        self.validation_mode = resolve_validation_mode(
            validation_mode, self.residual_mode
        )
        # Scoring-side device data for the validation rows, shared across
        # descent runs by the estimator (feature uploads happen once per
        # shard, not once per sweep configuration).
        self._validation_cache = validation_cache

    def _mesh(self):
        return next(
            (c.mesh for c in self.coordinates.values()
             if getattr(c, "mesh", None) is not None),
            None,
        )

    def _build_residuals(self):
        """The residual state for this run: the device engine, or the host
        float64 path (escape hatch)."""
        cls = ResidualEngine if self.residual_mode == "device" else HostResiduals
        return cls(
            self.training_data.offset,
            names=list(self.coordinates),
            mesh=self._mesh(),
            telemetry=self.telemetry,
        )

    def _build_validation(self):
        """The validation engine + scoring cache for a device-mode run (the
        cache is reused across runs when the estimator supplied one)."""
        cache = self._validation_cache
        if cache is None or cache.data is not self.validation_data:
            cache = DeviceScoringCache(
                self.validation_data, mesh=self._mesh(),
                telemetry=self.telemetry,
            )
            self._validation_cache = cache
        engine = ValidationEngine(
            self.validation_data.offset,
            names=list(self.coordinates),
            mesh=self._mesh(),
            telemetry=self.telemetry,
        )
        return engine, cache

    def _score(self, coord, model):
        """Score a coordinate's model over the training data: device path
        returns a device array (no host round-trip); host path returns the
        numpy vector the seed produced."""
        if self.residual_mode == "device" and hasattr(coord, "score_device"):
            return coord.score_device(model)
        return coord.score(model)

    def _evaluate(self, model: GameModel) -> Dict[str, float]:
        if self.validation_data is None or self.evaluators is None:
            return {}
        data = self.validation_data
        # host-sync: the HOST validation path (escape hatch) — every
        # coordinate's margins come to host and evaluators run in numpy.
        scores = model.score(data)
        entity_ids = dict(data.id_columns)
        return self.evaluators.evaluate(scores, data.label, data.weight, entity_ids)

    def _evaluate_device(self, engine: ValidationEngine,
                         cache: DeviceScoringCache) -> Dict[str, float]:
        """Device-resident validation: composite margin from the score
        table, jitted metrics over the cached labels/weights/entity codes.
        The per-metric ``float()`` scalars are the only d2h traffic."""
        composite = engine.composite()
        entity_ids = {
            ev.entity_column: cache.entity_codes(ev.entity_column)
            for ev in self.evaluators.evaluators
            if ev.entity_column is not None and ev.device_kind is not None
        }
        metrics = self.evaluators.evaluate(
            composite, cache.label, cache.weight, entity_ids
        )
        # host-sync: the per-metric scalars — the ONE host sync the device
        # validation pipeline performs per outer iteration.
        self.telemetry.counter(
            "descent.host_transfer_bytes", direction="d2h", path="validation"
        ).inc(4 * len(metrics))
        self.telemetry.gauge("validation.scoring_cache_bytes").set(
            cache.device_bytes
        )
        return metrics

    def _fingerprint(
        self, config_key: Optional[str] = None, locked=(),
        warm_start: bool = False,
    ) -> dict:
        from photon_tpu.fault.checkpoint import descent_fingerprint

        has_validation = (
            self.validation_data is not None and self.evaluators is not None
        )
        return descent_fingerprint(
            self.task_type, self.coordinates,
            self.training_data.num_examples, self.residual_mode,
            config_key=config_key,
            validation_key=(
                self.evaluators.primary.name if has_validation else None
            ),
            locked=locked,
            warm_start=warm_start,
            coordinate_kinds={
                name: getattr(c, "kind", type(c).__name__)
                for name, c in self.coordinates.items()
            },
        )

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
        """``checkpoint_fn(iteration, model)``, when given, is called after
        every full coordinate pass with the current composite model — the
        reference's per-iteration intermediate model output (SURVEY.md §5
        'Failure detection': restart-from-checkpoint is the recovery story).

        ``checkpointer`` (a :class:`~photon_tpu.fault.checkpoint.
        DescentCheckpointer`) snapshots the FULL restart state — models,
        residual score rows, best-model tracking, history — after every
        outer iteration; with its async publisher (the default) the loop
        only stages the d2h copies and the serialize+fsync+rename runs
        behind the next iteration's compute.  ``resume_state`` restores a
        snapshot mid-sweep (device tables rebuilt from the saved rows), so
        a resumed fit matches an uninterrupted one.  ``max_quarantined``
        bounds how many non-finite solves/score rows may be quarantined
        (previous iterate kept) before the run fails with
        :class:`QuarantineBudgetError` (None = unlimited).
        """
        try:
            result = self._run(
                num_iterations,
                initial_model=initial_model,
                locked_coordinates=locked_coordinates,
                checkpoint_fn=checkpoint_fn,
                checkpointer=checkpointer,
                resume_state=resume_state,
                max_quarantined=max_quarantined,
                config_key=config_key,
            )
        except BaseException:
            # Quiesce the async publisher without masking the real error
            # (an InjectedKillError must surface as itself; the in-flight
            # publish is allowed to land — a checkpoint more is strictly
            # better than one fewer).
            if checkpointer is not None and hasattr(checkpointer, "drain"):
                checkpointer.drain(reraise=False)
            raise
        finally:
            # Retire the iteration heartbeat: a finished (or dead) descent
            # going quiet is not a stall the watchdog should flag.
            from photon_tpu.fault.watchdog import complete

            complete("descent.iteration")
        if checkpointer is not None and hasattr(checkpointer, "drain"):
            # The final iteration drains: a completed fit returns only
            # after its last checkpoint is PUBLISHED, and a publish failure
            # from the tail iteration surfaces here, never silently.
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
            raise KeyError(f"locked coordinates not in update sequence: {sorted(unknown)}")
        if locked and initial_model is None:
            raise ValueError("locked coordinates require an initial model")
        for name in locked:
            if initial_model is not None and name not in initial_model.coordinates:
                raise KeyError(f"locked coordinate {name!r} missing from initial model")

        models: Dict[str, object] = {}
        residuals = self._build_residuals()
        val_engine = val_cache = None
        if (self.validation_data is not None and self.evaluators is not None
                and self.validation_mode == "device"):
            val_engine, val_cache = self._build_validation()

        best_model: Optional[GameModel] = None
        best_metrics: Dict[str, float] = {}
        best_iteration = -1
        history = []
        start_iteration = 0
        quarantined_total = 0

        if resume_state is not None:
            from photon_tpu.fault.checkpoint import require_fingerprint

            require_fingerprint(
                resume_state,
                self._fingerprint(
                    config_key, locked=locked,
                    warm_start=initial_model is not None,
                ),
                "this descent",
            )
            models = dict(resume_state.models)
            residuals.load_rows(resume_state.residual_rows)
            if val_engine is not None:
                # The validation table is NOT snapshotted: re-scoring
                # the restored models against the cached features is
                # the same deterministic kernel an uninterrupted run
                # used to fill these rows.
                for name, model in models.items():
                    val_engine.update(name, val_cache.score(model, name))
            best_model = GameModel(
                dict(resume_state.best_models), self.task_type
            )
            best_metrics = dict(resume_state.best_metrics)
            best_iteration = resume_state.best_iteration
            history = list(resume_state.history)
            quarantined_total = resume_state.quarantined
            start_iteration = resume_state.iteration + 1
            self.logger.info(
                "resumed descent after iteration %d", resume_state.iteration
            )
        elif initial_model is not None:
            for name, coord_model in initial_model.coordinates.items():
                if name not in self.coordinates:
                    continue
                models[name] = coord_model
                residuals.update(
                    name, self._score(self.coordinates[name], coord_model)
                )
                if val_engine is not None:
                    # Seed the validation score table: locked coordinates
                    # are never re-scored again (their rows are reused every
                    # iteration — validation.score_reuse counts them).
                    val_engine.update(
                        name, val_cache.score(coord_model, name)
                    )
            # Kick the foreign-vocabulary warm-start key joins onto the io
            # pool NOW: the fixed effect usually trains first, and by the
            # time a random coordinate's train() needs its aligned table
            # the join has run beside that compute instead of blocking it.
            from photon_tpu.game.coordinate import prefetch_warm_joins

            prefetch_warm_joins(self.coordinates, initial_model)

        # Drain guard flags from the seeding/resume updates BEFORE the loop:
        # a rejected seed row belongs to the INITIAL model, not to whatever
        # trains first in iteration 0 (misattributing it would roll a good
        # trained iterate back to the bad initial model).  The rejected
        # row already kept its zero state, so dropping the model is the
        # whole fix-up.
        seed_rejected = set(residuals.poll_quarantined())
        if val_engine is not None:
            seed_rejected |= set(val_engine.poll_quarantined())
        bad_locked = sorted(seed_rejected & locked)
        if bad_locked:
            raise ValueError(
                f"locked coordinate(s) {bad_locked} produced non-finite "
                "scores from the initial model; a locked coordinate cannot "
                "be quarantined"
            )
        for name in sorted(seed_rejected):
            self.telemetry.counter(
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
            # Resumed a completed descent: nothing left to run.
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

        telemetry = self.telemetry
        for it in range(start_iteration, num_iterations):
            # The preemption site fault injection exercises: between outer
            # iterations, where a killed run must restart from the last
            # published checkpoint.
            fault_point("descent:kill", iteration=it)
            # Preemption-aware shutdown: SIGTERM (or the injected `preempt`
            # site) lands here, at the iteration boundary where the
            # checkpoint state is consistent.  The previous iteration's
            # snapshot was already handed to the checkpointer — draining
            # forces that final save through synchronously, so the process
            # exits with its last completed iteration PUBLISHED (losing
            # zero completed work), then the driver maps PreemptedError to
            # the distinct preemption exit code.
            consume_preempt_injection(it)
            if preemption_requested():
                telemetry.counter("descent.preempted").inc()
                if checkpointer is not None and hasattr(checkpointer, "drain"):
                    checkpointer.drain()
                    self.logger.info(
                        "preempted (%s) before iteration %d: last completed "
                        "iteration's checkpoint published; exiting",
                        preemption_reason(), it,
                    )
                    hint = "resume with --resume auto"
                else:
                    # Be honest with the operator: nothing was saved, so
                    # the advertised recovery cannot be a resume.
                    hint = ("no checkpointer configured — a restart begins "
                            "from scratch (set --checkpoint-dir)")
                raise PreemptedError(
                    f"preempted ({preemption_reason()}) before iteration "
                    f"{it}; {hint}"
                )
            # Watchdog progress mark: one heartbeat per outer iteration
            # (a stalled heartbeat is how a hung run becomes visible).
            heartbeat("descent.iteration")
            coord_logs = {}
            trained = 0
            prev_iterates: Dict[str, object] = {}
            # Coordinates whose train() returned a device stats accumulator
            # (DeferredSolveStats): their telemetry/log/quarantine
            # accounting waits for the ONE boundary drain below.
            deferred: Dict[str, object] = {}
            with telemetry.span("descent.iteration", iteration=it) as iter_span:
                for name, coord in self.coordinates.items():
                    if name in locked:
                        continue
                    prev_iterates[name] = models.get(name)
                    offsets = residuals.offsets_for(name)
                    with self.logger.timed(f"iter{it}-{name}", span=False), \
                            telemetry.span(
                                "descent.coordinate", iteration=it,
                                coordinate=name,
                            ):
                        model, info = coord.train(
                            offsets, initial_model=models.get(name)
                        )
                    models[name] = model
                    residuals.update(name, self._score(coord, model))
                    if val_engine is not None:
                        # Incremental re-score: ONLY the coordinate that
                        # just trained touches its validation score row.
                        val_engine.update(name, val_cache.score(model, name))
                    trained += 1
                    if isinstance(info, DeferredSolveStats):
                        deferred[name] = info
                    else:
                        q = _quarantine_count(info)
                        if q:
                            # Non-finite solves quarantined inside train():
                            # those buckets kept their previous iterate.
                            telemetry.counter(
                                "descent.quarantined", coordinate=name,
                                stage="solve",
                            ).inc(q)
                            quarantined_total += q
                    cache_bytes = getattr(
                        getattr(coord, "device_data", None),
                        "_score_cache_bytes", 0,
                    )
                    if cache_bytes:
                        # The device scoring path's cached feature/index
                        # residency (a second copy of the shard, sharded
                        # over the mesh — see coordinate._scoring_feats;
                        # none for a fixed effect scored from its batch's
                        # tiles): the memory side of the transfer trade,
                        # next to the engine's residuals.device_bytes.
                        telemetry.gauge(
                            "residuals.scoring_cache_bytes", coordinate=name
                        ).set(cache_bytes)
                    telemetry.counter(
                        "descent.coordinate_updates", coordinate=name
                    ).inc()
                    if name not in deferred:
                        _record_coordinate_info(telemetry, name, info)
                        summary = (
                            info.summary().splitlines()[0]
                            if hasattr(info, "summary")
                            else str(info)
                        )
                        coord_logs[name] = summary
                        self.logger.info(
                            "iter %d coordinate %s: %s", it, name, summary
                        )

                # THE one stats/quarantine host sync of the iteration: the
                # per-coordinate device stats accumulators and BOTH score
                # tables' non-finite guard flags come to host in a single
                # batched device_get (the seed paid one deferred sync per
                # coordinate train instead).  A rejected row means the
                # coordinate's fresh scores were poisoned even though its
                # solve looked fine: roll the model back to the previous
                # iterate (drop it entirely on a cold start) and re-sync
                # BOTH engines' rows to the rolled-back model, so
                # composite, residual offsets, validation rows, and any
                # checkpoint stay consistent.  A coordinate rejected by
                # both engines is ONE quarantine event.
                import jax as _jax

                res_flags = residuals.drain_guard_flags()
                val_flags = (
                    val_engine.drain_guard_flags()
                    if val_engine is not None else []
                )
                # host-sync: the sanctioned once-per-iteration stats/
                # quarantine drain (descent.host_syncs counts it).
                stats_host, res_ok, val_ok = _jax.device_get((
                    {name: ds.device for name, ds in deferred.items()},
                    [ok for _, ok in res_flags],
                    [ok for _, ok in val_flags],
                ))
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
                rejected = {
                    name for (name, _), ok in zip(res_flags, res_ok)
                    if not bool(ok)
                }
                residuals.record_rejected(sorted(rejected))
                if val_engine is not None:
                    val_rejected = {
                        name for (name, _), ok in zip(val_flags, val_ok)
                        if not bool(ok)
                    }
                    val_engine.record_rejected(sorted(val_rejected))
                    rejected |= val_rejected
                bad_locked = sorted(rejected & locked)
                if bad_locked:
                    # A locked coordinate's scores come straight from the
                    # caller's initial model: quarantining it would silently
                    # drop the one coordinate the caller pinned.  Fail.
                    raise ValueError(
                        f"locked coordinate(s) {bad_locked} produced "
                        "non-finite scores from the initial model; a locked "
                        "coordinate cannot be quarantined"
                    )
                for name in sorted(rejected):
                    telemetry.counter(
                        "descent.quarantined", coordinate=name,
                        stage="score_row",
                    ).inc()
                    quarantined_total += 1
                    prev = prev_iterates.get(name)
                    if prev is not None:
                        models[name] = prev
                        residuals.update(
                            name, self._score(self.coordinates[name], prev)
                        )
                        if val_engine is not None:
                            val_engine.update(name, val_cache.score(prev, name))
                    else:
                        # No previous iterate: the coordinate leaves the
                        # composite entirely this iteration (zero rows ==
                        # absent coordinate), instead of keeping a model
                        # whose scores are non-finite.
                        models.pop(name, None)
                        residuals.update(
                            name,
                            np.zeros(
                                self.training_data.num_examples, np.float32
                            ),
                        )
                        if val_engine is not None:
                            val_engine.update(
                                name, np.zeros(val_cache.n, np.float32)
                            )
                    self.logger.info(
                        "iter %d coordinate %s: non-finite scores "
                        "quarantined (previous iterate kept)", it, name,
                    )
                if max_quarantined is not None and quarantined_total > max_quarantined:
                    raise QuarantineBudgetError(
                        f"{quarantined_total} quarantined solves/score rows "
                        f"exceed --max-quarantined {max_quarantined}"
                    )

                game_model = GameModel(dict(models), self.task_type)
                if checkpoint_fn is not None:
                    with telemetry.span("descent.checkpoint", iteration=it):
                        checkpoint_fn(it, game_model)
                with telemetry.span("descent.validate", iteration=it):
                    if val_engine is not None:
                        # Rows whose device scores were REUSED this
                        # iteration (locked / not-retrained coordinates):
                        # the host path re-scored every coordinate's margins
                        # each iteration regardless.
                        telemetry.counter("validation.score_reuse").inc(
                            (len(self.coordinates) - trained) * val_cache.n
                        )
                        metrics = self._evaluate_device(val_engine, val_cache)
                    else:
                        metrics = self._evaluate(game_model)
                if metrics:
                    self.logger.info("iter %d validation %s", it, metrics)
                    iter_span.set_attribute("metrics", metrics)
                    for k, v in metrics.items():
                        telemetry.gauge("descent.validation_metric", metric=k).set(v)
            telemetry.counter("descent.iterations").inc()
            history.append(
                {"iteration": it, "metrics": metrics, "coordinates": coord_logs}
            )

            if not metrics:
                best_model, best_metrics, best_iteration = game_model, metrics, it
            else:
                primary = self.evaluators.primary
                if best_model is None or primary.better_than(
                    metrics[primary.name], best_metrics[primary.name]
                ):
                    best_model, best_metrics, best_iteration = game_model, metrics, it

            if checkpointer is not None:
                # Async publishing: hand the checkpointer DEVICE row
                # handles — its staging step starts copy_to_host_async on
                # rows and model tables together and gathers once, instead
                # of the blocking per-table fetches the sync path keeps.
                rows = (
                    residuals.snapshot_rows_async()
                    if getattr(checkpointer, "async_publish", False)
                    else residuals.snapshot_rows()
                )
                state = DescentState(
                    iteration=it,
                    num_iterations=num_iterations,
                    task_type=self.task_type,
                    models=dict(models),
                    best_models=dict(best_model.coordinates),
                    best_metrics=dict(best_metrics),
                    best_iteration=best_iteration,
                    history=list(history),
                    residual_rows=rows,
                    quarantined=quarantined_total,
                    fingerprint=self._fingerprint(
                        config_key, locked=locked,
                        warm_start=initial_model is not None,
                    ),
                )
                with telemetry.span("descent.checkpoint.save", iteration=it):
                    checkpointer.save(state)

        assert best_model is not None
        return DescentResult(
            best_model=best_model,
            last_model=game_model,
            best_metrics=best_metrics,
            history=history,
        )
