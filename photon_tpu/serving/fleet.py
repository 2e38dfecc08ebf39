"""Serving fleet: N scorer replicas + router + optional socket ingest.

The one assembly point for the fleet tier (ISSUE 12 tentpole): builds N
:class:`~photon_tpu.serving.router.ScorerReplica` instances from ONE model
artifact (shared model distribution — the host-side model object is loaded
once; each replica uploads its OWN device-resident tables from it), wires
them behind a :class:`~photon_tpu.serving.router.FleetRouter` with
deadline-aware admission control, and optionally attaches the
:class:`~photon_tpu.serving.transport.ScoringServer` socket ingest.

Per-replica device residency: with ``devices="split"`` (the default) the
addressable devices are dealt round-robin across replicas and each scorer
places its tables on its own sub-mesh (``reshard_to_mesh`` under each
scorer's mesh) — on a multi-device platform replicas genuinely own
disjoint device memory; on a single device they share it (thread-backed
replicas, the CPU fixture's shape).

Rollout and model lifecycle ride the router: :meth:`ServingFleet.rollout`
is the staggered/canary ``swap_model`` (one replica first, mirrored-
traffic parity probe, then the rest), and capacity-headroom serving
tables (amortized doubling + movable zero row) mean a GROWN vocabulary
publishes in place fleet-wide with zero recompiles.

Residency contract (``tools/check_host_sync.py`` guards this module): the
fleet layer moves requests and models between components — it never
fetches device data itself.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Tuple

from photon_tpu.serving.router import (
    AdmissionPolicy,
    FleetRouter,
    ScorerReplica,
    host_score_request,
    parity_worst,
)
from photon_tpu.serving.scorer import (
    DEFAULT_MAX_BATCH,
    DEFAULT_MIN_BUCKET,
    GameScorer,
    ScoringRequest,
    ShardSpec,
    request_spec_for_model,
)
from photon_tpu.serving.batcher import DEFAULT_MAX_DELAY_S


class ReplicaRebuildError(RuntimeError):
    """A background-rebuild replacement failed its canary parity probe;
    the replacement was retired and the fleet is untouched."""


#: The capacity-plan refusal markers: a ``swap_model`` that cannot fit
#: the new model in the serving tables' headroom raises with ONE of
#: these texts (the scorer's plan comparison, or ``serving_table``'s
#: vocabulary-vs-capacity check underneath it) — and both survive the
#: subprocess boundary (the child's refusal travels back inside a typed
#: error frame's message).
CAPACITY_REFUSAL_MARKERS = (
    "requires a new GameScorer",
    "rebuild the scorer instead of hot-swapping",
)


def is_capacity_refusal(exc: BaseException) -> bool:
    """Does this exception chain carry the capacity-plan refusal?  Walks
    ``__cause__``/``__context__`` so a refusal wrapped by the transport
    (TransportError) or a retry layer still matches."""
    seen = set()
    while exc is not None and id(exc) not in seen:
        seen.add(id(exc))
        text = str(exc)
        if any(marker in text for marker in CAPACITY_REFUSAL_MARKERS):
            return True
        exc = exc.__cause__ or exc.__context__
    return False


def _replica_meshes(n_replicas: int, mesh, devices) -> List[object]:
    """One mesh (or None) per replica.  An explicit ``mesh`` is shared by
    every replica; ``devices="split"`` deals the addressable devices
    round-robin so each replica's tables live on its own sub-mesh; any
    other value places every replica on the default device."""
    if mesh is not None or devices != "split":
        return [mesh] * n_replicas
    import jax

    devs = list(jax.devices())
    if len(devs) <= 1:
        return [None] * n_replicas
    from photon_tpu.parallel.mesh import create_mesh

    groups = [devs[i::n_replicas] for i in range(n_replicas)]
    return [
        create_mesh(devices=groups[i % len(groups)] or [devs[i % len(devs)]])
        for i in range(n_replicas)
    ]


class ServingFleet:
    """N replicated scorers behind a deadline-aware router.

    Context-manager lifecycle; ``close()`` drains every replica's batcher
    and stamps the per-replica QPS gauges.  ``submit``/``score`` go
    through admission control (``deadline_s`` is a relative budget;
    sheds raise :class:`~photon_tpu.serving.router.RequestShedError`).

    ``backend`` picks the replica runtime: ``"thread"`` (the PR 12 shape —
    scorers in this process, per-replica sub-meshes via ``devices``) or
    ``"subprocess"`` (ISSUE 13 — each replica is a CHILD PROCESS with its
    own Python/jax runtime speaking the frame protocol over loopback,
    on the host platform only — refused under a TPU parent, which holds
    the chip; the shared model artifact lives under ``workdir``).  ``supervise()``
    attaches the self-healing supervisor — health probes, canary-gated
    resurrection, flap quarantine — over either backend.
    """

    def __init__(
        self,
        model,
        replicas: int = 2,
        mesh=None,
        devices: str = "split",
        backend: str = "thread",
        request_spec: Optional[Dict[str, ShardSpec]] = None,
        buckets=None,
        max_batch: int = DEFAULT_MAX_BATCH,
        min_bucket: int = DEFAULT_MIN_BUCKET,
        max_delay_s: float = DEFAULT_MAX_DELAY_S,
        telemetry=None,
        admission: Optional[AdmissionPolicy] = None,
        workdir: Optional[str] = None,
        child_env: Optional[Dict[str, str]] = None,
        spawn_timeout_s: float = 120.0,
        table_capacity_factor: int = 1,
        table_dtype: str = "f32",
        models: Optional[Dict[str, object]] = None,
        reserve_rows: int = 0,
    ):
        from photon_tpu.game.lowp import check_dtype
        from photon_tpu.telemetry import NULL_SESSION

        if replicas < 1:
            raise ValueError("a fleet needs at least one replica")
        if backend not in ("thread", "subprocess"):
            raise ValueError(f"unknown replica backend {backend!r} "
                             "(thread | subprocess)")
        # Multi-model arena fleet (ISSUE 18): ``models`` maps tenant id ->
        # GameModel; every replica hosts ALL of them in one shared arena
        # behind one compiled bucket ladder, and requests route by their
        # ``model`` field.  ``model`` (positional) may be None then; the
        # first hosted model becomes the default tenant.
        self.models: Optional[Dict[str, object]] = (
            dict(models) if models else None
        )
        self._reserve_rows = int(reserve_rows)
        if self.models and model is None:
            model = next(iter(self.models.values()))
        if self.models is not None and not self.models:
            raise ValueError("models= needs at least one hosted model")
        self.model = model
        self.backend = backend
        # Rebuild inputs (ISSUE 19): a zero-downtime background rebuild
        # re-spawns replicas at a larger table_capacity_factor, so the
        # fleet remembers the construction shape it built them from.
        self._table_capacity_factor = int(table_capacity_factor)
        self._request_spec_cfg = request_spec
        self._buckets = buckets
        self._max_batch = int(max_batch)
        self._min_bucket = int(min_bucket)
        self._replica_mesh_list: List[object] = []
        # Fleet-wide gather-table storage tier (ISSUE 17): every replica
        # serves the same dtype, and the canary/probe parity gates default
        # to the tier's measured bound (lowp.parity_tol_for).
        self.table_dtype = check_dtype(table_dtype)
        self.telemetry = telemetry or NULL_SESSION
        self._model_lock = threading.Lock()
        # Serializes whole PUBLISH operations (rollout, fleet rollback):
        # two concurrent publishes interleaving their per-replica swaps
        # would leave the fleet split across models.
        self._publish_lock = threading.Lock()
        self._model_version = 0
        self._rolling = 0
        self._previous_model = None
        self._supervisor = None
        self._store = None
        self._workdir_owned = False
        self.replicas: List[ScorerReplica] = []
        if backend == "subprocess":
            import tempfile

            from photon_tpu.serving.replica_proc import (
                ModelStore,
                SubprocessReplica,
            )
            from photon_tpu.utils.device import device_facts

            # A chip belongs to one process: this parent has touched JAX
            # (it loaded the model), so on a TPU it HOLDS the chip and a
            # child that needs it would fail or hang in backend init.
            # Refuse here instead; replicas on chips use the thread
            # backend, one device each (ROADMAP D7 is the redesign).
            platform = device_facts()["platform"]
            if platform != "cpu":
                raise RuntimeError(
                    f"ServingFleet(backend='subprocess') cannot run under "
                    f"a parent on platform {platform!r}: the parent process "
                    "holds the chip, so child replicas could never open "
                    "it.  Use backend='thread' (one device per replica)"
                )
            if workdir is None:
                workdir = tempfile.mkdtemp(prefix="photon-fleet-")
                self._workdir_owned = True
            self._store = ModelStore(workdir)
            if self.models:
                self._store.keep = max(self._store.keep,
                                       len(self.models) + 2)
                for m in self.models.values():
                    self._store.publish(m)
            else:
                self._store.publish(model)  # the v0 shared artifact
            spec = request_spec or request_spec_for_model(model)
            try:
                for i in range(int(replicas)):
                    env = {"JAX_PLATFORMS": "cpu", **(child_env or {})}
                    self.replicas.append(
                        SubprocessReplica(
                            f"r{i}", model, self._store,
                            request_spec=spec, buckets=buckets,
                            max_batch=max_batch, min_bucket=min_bucket,
                            max_delay_s=max_delay_s,
                            telemetry=self.telemetry,
                            child_env=env, spawn_timeout_s=spawn_timeout_s,
                            table_capacity_factor=table_capacity_factor,
                            table_dtype=self.table_dtype,
                            models=self.models,
                            reserve_rows=self._reserve_rows,
                        )
                    )
            except BaseException:
                # Partial-spawn failure: a half-built fleet has no close()
                # caller — reap the children already spawned and the owned
                # workdir here, or they leak past the raised error.
                for replica in self.replicas:
                    try:
                        replica.close()
                    except Exception:  # noqa: BLE001 — best-effort reap
                        pass
                if self._workdir_owned:
                    import shutil

                    shutil.rmtree(workdir, ignore_errors=True)
                raise
        else:
            meshes = _replica_meshes(int(replicas), mesh, devices)
            self._replica_mesh_list = list(meshes)
            for i in range(int(replicas)):
                if self.models:
                    from photon_tpu.serving.arena import MultiModelScorer

                    scorer = MultiModelScorer(
                        self.models,
                        mesh=meshes[i],
                        request_spec=request_spec,
                        buckets=buckets,
                        max_batch=max_batch,
                        min_bucket=min_bucket,
                        telemetry=self.telemetry,
                        table_capacity_factor=table_capacity_factor,
                        table_dtype=self.table_dtype,
                        reserve_rows=self._reserve_rows,
                    )
                else:
                    scorer = GameScorer(
                        model,
                        mesh=meshes[i],
                        request_spec=request_spec,
                        buckets=buckets,
                        max_batch=max_batch,
                        min_bucket=min_bucket,
                        telemetry=self.telemetry,
                        table_capacity_factor=table_capacity_factor,
                        table_dtype=self.table_dtype,
                    )
                self.replicas.append(
                    ScorerReplica(
                        f"r{i}", scorer,
                        max_batch=max_batch, max_delay_s=max_delay_s,
                        telemetry=self.telemetry,
                    )
                )
        if backend == "thread":
            # Thread replicas have no child artifact version; the fleet
            # stamps its own monotonic version on them so response spans
            # carry the served model version on either backend.
            for replica in self.replicas:
                replica.served_version = 0
        self.router = FleetRouter(
            self.replicas, telemetry=self.telemetry, admission=admission
        )
        self._server = None
        self.observer = None
        self.telemetry.gauge("serving.replicas").set(int(replicas))

    @classmethod
    def from_model_dir(cls, model_dir: str, telemetry=None, logger=None,
                       **kwargs) -> "ServingFleet":
        """Shared model-artifact distribution: the artifact is read ONCE
        (retried like any guarded model load) and every replica builds its
        device tables from the same host object."""
        from photon_tpu.fault.retry import retry_call
        from photon_tpu.game.model_io import load_game_model

        model, _ = retry_call(
            lambda: load_game_model(model_dir),
            site="model:load", telemetry=telemetry, logger=logger,
        )
        return cls(model, telemetry=telemetry, **kwargs)

    # -- serving -------------------------------------------------------------
    def warmup(self) -> "ServingFleet":
        """AOT-compile every replica's bucket ladder; after this the fleet
        can never recompile on any arrival pattern."""
        for replica in self.replicas:
            replica.scorer.warmup()
        return self

    @property
    def compilations(self) -> int:
        return sum(r.scorer.compilations for r in self.replicas)

    def submit(self, request: ScoringRequest,
               deadline_s: Optional[float] = None,
               model: Optional[str] = None):
        """Admit one request.  ``model`` stamps a tenant id onto it (a
        convenience for callers that route per call instead of building
        requests with ``model=`` set); a multi-model fleet scores it
        against that tenant's arena slice."""
        if model is not None:
            request = dataclasses.replace(request, model=model)
        return self.router.submit(request, deadline_s=deadline_s)

    def score(self, request: ScoringRequest,
              deadline_s: Optional[float] = None,
              model: Optional[str] = None):
        return self.submit(request, deadline_s=deadline_s,
                           model=model).result()

    # -- multi-model lifecycle -----------------------------------------------
    def add_model(self, model_id: str, model) -> None:
        """Onboard a tenant fleet-wide under live traffic: each replica's
        arena takes the new model as a slice scatter (zero recompiles
        unless the arena grows); in-flight batches finish on the tables
        they captured — zero requests dropped."""
        if self.models is None:
            raise RuntimeError(
                "add_model needs a multi-model fleet (pass models= at "
                "construction)"
            )
        with self._publish_lock:
            for replica in self.replicas:
                if replica.alive:
                    replica.scorer.add_model(model_id, model)
            with self._model_lock:
                self.models[model_id] = model

    def retire_model(self, model_id: str) -> None:
        """Retire a tenant fleet-wide: its rows stay in place (unreachable
        via routing) until the free extents are reused; requests still
        naming it shed with a KeyError."""
        if self.models is None:
            raise RuntimeError("retire_model needs a multi-model fleet")
        with self._publish_lock:
            for replica in self.replicas:
                if replica.alive:
                    replica.scorer.retire_model(model_id)
            with self._model_lock:
                self.models.pop(model_id, None)

    def current_model(self) -> Tuple[object, int]:
        """The model the fleet serves NOW and its monotonic version — the
        supervisor's resurrection target (a replica resurrected
        mid-rollout re-syncs against this, never the model it died on)."""
        with self._model_lock:
            return self.model, self._model_version

    def rollout(self, model, **kwargs) -> None:
        """Staggered/canary ``swap_model`` across the fleet (see
        :meth:`photon_tpu.serving.router.FleetRouter.rollout`).

        The fleet's (model, version) is published BEFORE the router
        rollout runs and rolled back if it fails: a resurrection that
        completes while the rollout is in flight must target the model
        the fleet is converging TO — publishing only on return would let
        a replica rejoin on the old model mid-promotion and leave the
        fleet split until the next parity probe killed it again.  (If the
        rollout aborts, a replica resurrected against the new model fails
        its next known-answer probe and is re-resurrected on the restored
        one — the rare-path analog of the same self-healing loop.)

        Whole publishes serialize on ``_publish_lock``: a rollout and the
        supervisor's fleet rollback interleaving their per-replica swaps
        would split the fleet across models.

        The canary parity gate defaults to the fleet's TABLE-DTYPE bound
        (``lowp.parity_tol_for`` — f32 keeps the exact-path 1e-3; bf16/
        int8 gate at their measured codec bounds): a lossy fleet probed at
        the f32 tolerance would fail every healthy rollout.  An explicit
        ``parity_tol`` kwarg still wins."""
        if "parity_tol" not in kwargs:
            from photon_tpu.game.lowp import parity_tol_for

            kwargs["parity_tol"] = parity_tol_for(self.table_dtype)
        model_id = kwargs.get("model_id")
        with self._publish_lock:
            with self._model_lock:
                previous_model = self.model
                previous_slice = None
                if model_id is None:
                    self.model = model
                elif self.models is not None:
                    previous_slice = self.models.get(model_id)
                    self.models[model_id] = model
                self._model_version += 1
                self._rolling += 1
            try:
                self.router.rollout(model, **kwargs)
            except BaseException:
                with self._model_lock:
                    if model_id is None:
                        self.model = previous_model
                    elif (self.models is not None
                            and previous_slice is not None):
                        self.models[model_id] = previous_slice
                    # The version stays MONOTONIC: the rollback is itself
                    # a new published state.  Restoring the old number
                    # would let a later rollout reuse it and defeat the
                    # supervisor's stale-oracle version check.
                    self._model_version += 1
                raise
            finally:
                with self._model_lock:
                    self._rolling -= 1
            with self._model_lock:
                # Promoted fleet-wide: keep the PREDECESSOR artifact as
                # the supervisor's fleet-rollback target (a post-swap
                # fleet-wide known-answer parity regression rolls back to
                # it instead of quarantining every replica — ROADMAP
                # fleet edge (d)).  A per-tenant rollout leaves the
                # DEFAULT-model rollback target alone — the fleet-wide
                # known-answer probe runs against the default model, and
                # its rollback must not revert an unrelated slice.
                if model_id is None:
                    self._previous_model = previous_model
                self._stamp_served_version()

    def rollout_with_rebuild(self, model, **kwargs) -> bool:
        """Rollout that survives the capacity boundary (ISSUE 19): try
        the in-place staggered rollout first (zero recompiles when the
        grown model still fits the serving tables' headroom); when the
        canary swap REFUSES for capacity (the amortized-doubling plan is
        exhausted — ``is_capacity_refusal``), fall through to a
        zero-downtime background :meth:`rebuild` at doubled capacity.
        Returns True when a rebuild was needed, False when the plain
        rollout sufficed."""
        try:
            self.rollout(model, **kwargs)
            return False
        except BaseException as e:
            if not is_capacity_refusal(e):
                raise
        self.rebuild(
            model=model,
            probe_requests=kwargs.get("probe_requests"),
            parity_tol=kwargs.get("parity_tol"),
        )
        return True

    def rebuild(self, model=None, table_capacity_factor: Optional[int] = None,
                parity_tol: Optional[float] = None,
                probe_requests: Optional[List[ScoringRequest]] = None) -> None:
        """Zero-downtime background replica rebuild (ISSUE 19 tentpole).

        For each replica: build a REPLACEMENT backend at
        ``table_capacity_factor`` (default: double the current factor)
        while the old backend keeps serving, warm it, canary the FIRST
        replacement with mirrored traffic against the host oracle, then
        atomically cut the serving path over (new submissions to the
        replacement, the old batcher drains against the old backend —
        zero shed, zero lost) and bump the router generation so any
        answer the retired backend still produces is fenced.  Replicas
        after the canary cut over without re-probing (same artifact,
        same parity surface).

        A canary parity failure retires the replacement and raises
        :class:`ReplicaRebuildError` with the fleet untouched.  A
        NON-canary replacement that fails to spawn is declared unhealthy
        (the supervisor heals it — at the new factor) rather than
        aborting a half-cut-over fleet.

        ``model=None`` rebuilds on the currently served model (a pure
        capacity grow); passing a model publishes it with the same
        version discipline as :meth:`rollout`."""
        if self.models:
            raise RuntimeError(
                "rebuild currently supports single-model fleets (a "
                "multi-model arena grows per-slice via add_model)"
            )
        if parity_tol is None:
            from photon_tpu.game.lowp import parity_tol_for

            parity_tol = parity_tol_for(self.table_dtype)
        factor = (
            int(table_capacity_factor) if table_capacity_factor
            else max(1, self._table_capacity_factor) * 2
        )
        with self._publish_lock:
            with self._model_lock:
                previous = self.model
                published = model is not None and model is not self.model
                if published:
                    self.model = model
                    self._model_version += 1
                target = self.model
            try:
                self._rebuild_replicas(
                    target, factor, float(parity_tol), probe_requests
                )
            except BaseException:
                with self._model_lock:
                    if published:
                        self.model = previous
                        # Monotonic, like rollout's abort path: the
                        # restore is itself a new published state.
                        self._model_version += 1
                raise
            self._table_capacity_factor = factor
            with self._model_lock:
                if published:
                    self._previous_model = previous
                self._stamp_served_version()
        self.telemetry.counter("serving.fleet_rebuilds").inc()

    def _rebuild_replicas(self, model, factor: int, parity_tol: float,
                          probe_requests) -> None:
        live = [r for r in self.replicas if r.alive and not r.quarantined]
        if not live:
            raise RuntimeError("rebuild aborted: every replica is dead")
        probes = self._rebuild_probes(model, probe_requests)
        canary = True
        for replica in live:
            try:
                proc, scorer = self._build_replacement(replica, model, factor)
            except BaseException as e:
                if canary:
                    raise
                # Post-canary spawn failure: don't abort a half-cut-over
                # fleet — declare and let the supervisor heal at the new
                # factor (the replica's stored factor is updated first).
                if hasattr(replica, "_table_capacity_factor"):
                    replica._table_capacity_factor = factor
                self.router.mark_unhealthy(
                    replica, "rebuild", f"replacement spawn failed: {e}"
                )
                replica.abandon_pending(
                    RuntimeError(f"replica {replica.replica_id} rebuild "
                                 f"replacement failed: {e}")
                )
                continue
            if canary:
                # Mirrored-traffic canary BEFORE the replacement takes any
                # caller traffic: probe responses never reach callers.
                try:
                    for req in probes:
                        worst = parity_worst(
                            scorer.score_batch(req),
                            host_score_request(model, req),
                        )
                        if worst > parity_tol:
                            raise ReplicaRebuildError(
                                f"replacement for {replica.replica_id} "
                                f"failed its canary parity probe (max "
                                f"|delta| {worst:.2e} > {parity_tol:g})"
                            )
                except BaseException:
                    self._retire_replacement(proc, scorer)
                    raise
                canary = False
            self._mark_rebuild(replica.replica_id, "cutover")
            if proc is not None:
                replica.cutover_to(scorer, proc=proc,
                                   table_capacity_factor=factor)
            else:
                replica.cutover_to(scorer)
            self.router.cutover(replica)

    def _rebuild_probes(self, model,
                        probe_requests) -> List[ScoringRequest]:
        """The canary's traffic sample: explicit probes, else the
        router's mirror of recent requests, else one synthetic
        known-answer probe.  Per-row-routed mirrors (model id arrays) are
        dropped — they have no single host oracle."""
        probes = (
            list(probe_requests) if probe_requests
            else self.router.recent_requests()
        )
        probes = [
            p for p in probes
            if getattr(p, "model", None) is None
            or isinstance(p.model, str)
        ]
        if not probes:
            from photon_tpu.serving.supervisor import probe_request_for

            spec = None
            for replica in self.replicas:
                spec = getattr(replica.scorer, "request_spec", None)
                if spec:
                    break
            if not spec:
                spec = request_spec_for_model(model)
            probes = [probe_request_for(model, spec)]
        return probes

    def _build_replacement(self, replica, model, factor: int):
        """``(proc_or_None, warmed scorer)`` at the new capacity factor —
        the old backend serves untouched while this builds."""
        build = getattr(replica, "build_replacement", None)
        if build is not None:  # subprocess replica: a fresh child
            return build(model, factor)
        idx = self.replicas.index(replica)
        meshes = self._replica_mesh_list
        scorer = GameScorer(
            model,
            mesh=meshes[idx] if idx < len(meshes) else None,
            request_spec=self._request_spec_cfg,
            buckets=self._buckets,
            max_batch=self._max_batch,
            min_bucket=self._min_bucket,
            telemetry=self.telemetry,
            table_capacity_factor=factor,
            table_dtype=self.table_dtype,
        ).warmup()
        return None, scorer

    def _retire_replacement(self, proc, scorer) -> None:
        disconnect = getattr(scorer, "disconnect", None)
        if disconnect is not None:
            try:
                disconnect()
            except OSError:
                pass
        if proc is not None and proc.poll() is None:
            proc.kill()
            try:
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 — reap is best-effort
                pass

    def _mark_rebuild(self, replica_id: str, phase: str) -> None:
        self.telemetry.counter(
            "serving.rebuild_phase", replica=replica_id, phase=phase
        ).inc()

    def _stamp_served_version(self) -> None:
        """Thread replicas: mirror the fleet's monotonic model version onto
        each live replica (subprocess replicas carry their child artifact
        version instead).  Caller holds ``_model_lock``."""
        for replica in self.replicas:
            if hasattr(replica, "served_version") and replica.alive:
                replica.served_version = self._model_version

    def rollback_to_previous(self, expected_version=None) -> bool:
        """Fleet-wide rollback to the predecessor artifact — the
        supervisor's answer to EVERY replica failing its known-answer
        probe right after a swap (a fleet-wide regression is a model/
        artifact fault, not N replica faults; N quarantines would scrap a
        healthy fleet).

        The predecessor is a model that already served and passed its own
        canary, so it republishes WITHOUT a canary stagger: the version
        bumps (monotonic — resurrected replicas re-sync against it), every
        live replica swaps back in place (zero recompiles: same capacity
        plan), and the predecessor slot clears so one regression cannot
        ping-pong.  Returns False when there is nothing to roll back to
        (no completed rollout yet, or a rollout is mid-flight) — the
        caller falls back to per-replica declarations.  Serialized with
        ``rollout`` on ``_publish_lock`` — the swaps of two publishes must
        never interleave — and version-guarded: ``expected_version`` is the
        model version the caller's probe evidence was collected against;
        if another publish landed while this call waited for the lock, the
        evidence is STALE (the probes never saw the new model) and the
        rollback refuses instead of reverting a fresh publish."""
        with self._publish_lock:
            return self._rollback_locked(expected_version)

    def _rollback_locked(self, expected_version) -> bool:
        with self._model_lock:
            if self._previous_model is None or self._rolling:
                return False
            if (expected_version is not None
                    and self._model_version != expected_version):
                return False
            target = self._previous_model
            self._previous_model = None
            self.model = target
            self._model_version += 1
            self._stamp_served_version()
        for replica in self.replicas:
            if not replica.alive:
                continue
            try:
                replica.scorer.swap_model(target)
            except Exception as e:  # noqa: BLE001 — a replica that cannot
                # take the restored model must not keep serving the bad one.
                self.router.mark_unhealthy(
                    replica, "swap", f"rollback swap failed: {e}"
                )
        self.telemetry.counter("serving.rollout_rollbacks").inc()
        return True

    def rollout_in_progress(self) -> bool:
        """True while a staggered rollout is mid-flight — the window in
        which different replicas legitimately serve different versions,
        so the supervisor must not read a known-answer parity mismatch
        as a replica fault."""
        with self._model_lock:
            return self._rolling > 0

    def supervise(self, policy=None, logger=None, start: bool = True):
        """Attach the self-healing supervisor (health probes, canary-gated
        resurrection, flap quarantine); returns the
        :class:`~photon_tpu.serving.supervisor.ReplicaSupervisor`.  With
        ``start=False`` the supervisor is built but not threaded — tests
        drive ``check_once()`` deterministically.

        Without an explicit policy, the known-answer/rejoin parity gates
        default to the fleet's table-dtype bound (a lossy fleet probed at
        the f32 tolerance would declare every healthy replica dead)."""
        from photon_tpu.serving.supervisor import (
            ReplicaSupervisor,
            SupervisorPolicy,
        )

        if self._supervisor is not None:
            raise RuntimeError("fleet already supervised")
        if policy is None and self.table_dtype != "f32":
            from photon_tpu.game.lowp import parity_tol_for

            policy = SupervisorPolicy(
                parity_tol=parity_tol_for(self.table_dtype)
            )
        self._supervisor = ReplicaSupervisor(
            self, policy=policy, telemetry=self.telemetry, logger=logger
        )
        if start:
            self._supervisor.start()
        return self._supervisor

    # -- observability -------------------------------------------------------
    def observe(self, policy=None, slos=None, flight_dir=None,
                start: bool = True):
        """Attach the fleet observability plane (cross-process tracing,
        live metrics, SLO burn rates, flight-recorder collection); returns
        the :class:`~photon_tpu.serving.observe.FleetObserver`.  Wires the
        router's request hook and each subprocess replica's span sink;
        the supervisor and online refresh pick the observer up via
        ``fleet.observer``.  ``flight_dir`` is where collected crash dumps
        persist (pass the run's output dir to land them next to the run
        report).  ``start=False`` builds it unthreaded — tests drive
        ``poll_once()`` deterministically."""
        from photon_tpu.serving.observe import FleetObserver

        if self.observer is not None:
            raise RuntimeError("fleet already observed")
        kwargs = {} if slos is None else {"slos": slos}
        observer = FleetObserver(
            fleet=self, telemetry=self.telemetry, policy=policy,
            flight_dir=flight_dir, **kwargs,
        )
        self.router.observer = observer
        for replica in self.replicas:
            if hasattr(replica, "span_sink"):
                replica.span_sink = observer.collector.merge_remote
        if getattr(observer.policy, "admission_guard", False):
            observer.attach_admission_guard(self.router)
        self.observer = observer
        if start:
            observer.start()
        return observer

    # -- transport -----------------------------------------------------------
    def serve(self, host: str = "127.0.0.1", port: int = 0):
        """Attach the socket ingest; returns the
        :class:`~photon_tpu.serving.transport.ScoringServer` (its
        ``.address`` is the bound ``(host, port)``)."""
        from photon_tpu.serving.transport import ScoringServer

        if self._server is not None:
            raise RuntimeError("fleet already serving")
        self._server = ScoringServer(
            self.router, host=host, port=port, telemetry=self.telemetry
        )
        return self._server

    # -- lifecycle -----------------------------------------------------------
    def close(self) -> None:
        # The supervisor stops FIRST: a teardown must not race a
        # resurrection re-spawning the replicas being closed.
        if self._supervisor is not None:
            self._supervisor.stop()
            self._supervisor = None
        # Observer closes while the children are still alive so its final
        # poll can drain pending span streams over the open control
        # connections; after router.close() those sockets are gone.
        if self.observer is not None:
            self.observer.close()
        if self._server is not None:
            self._server.close()
            self._server = None
        self.router.close()
        if self._workdir_owned and self._store is not None:
            import shutil

            shutil.rmtree(self._store.workdir, ignore_errors=True)
            self._store = None

    def __enter__(self) -> "ServingFleet":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
