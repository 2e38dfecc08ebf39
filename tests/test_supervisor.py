"""Self-healing serving fleet (ISSUE 13): process-backed replicas,
health-checked supervision, canary-gated resurrection.

The contracts pinned here:

- crash mid-batch (``replica:crash``) reroutes in-flight work exactly once
  (no lost, no duplicated responses), the supervisor resurrects the
  replica, and its return to the dispatch set is gated on a mirrored-
  traffic parity probe ≤ 1e-3 vs the host oracle;
- a hang (``replica:hang`` — probe timeout / stale heartbeat) is treated
  the same as a crash: declared, torn down, rerouted, resurrected;
- a flapping replica (N deaths inside the window) is quarantined
  PERMANENTLY (``serving.replica_quarantined``) and never respawned;
- a replica resurrected across an active rollout rejoins on the CURRENT
  model, never the one it died on;
- a kill→resurrect cycle triggers ZERO jax compile events after warmup
  (thread replicas re-warm against cached programs);
- a failed spawn (``replica:spawn``, retriable) backs off exponentially
  and eventually rejoins;
- a SUBPROCESS replica (own Python/jax runtime, frame protocol over
  loopback) scores identically to the thread-backed scorer ≤ 1e-6,
  hot-swaps over the wire, and survives a real SIGKILL through the same
  supervision loop;
- the admission projection charges PADDED rows and the projection error
  is measurable (``serving.admission_error_s``);
- the pipelined ``AsyncScoringClient`` drives open-loop load through the
  socket itself;
- the telemetry report renders the supervisor timeline.
"""

from __future__ import annotations

import os
import signal
import time

import numpy as np
import pytest

from photon_tpu.data.synthetic import make_game_dataset
from photon_tpu.fault.injection import FaultPlan, set_plan
from photon_tpu.game.model import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_tpu.models.glm import Coefficients, model_for_task
from photon_tpu.serving import (
    AsyncScoringClient,
    RequestShedError,
    ServingFleet,
    SupervisorPolicy,
    TrafficSpec,
    build_requests,
    generate_traffic,
    host_score_request,
    replay_open_loop,
    request_spec_for_dataset,
)
from photon_tpu.telemetry import TelemetrySession


@pytest.fixture(autouse=True)
def _no_fault_plan():
    yield
    set_plan(None)


def _fixture(seed=3, n_entities=40, fixed_dim=6, random_dim=4):
    data, _ = make_game_dataset(
        n_entities, 4, fixed_dim, random_dim, seed=seed
    )
    rng = np.random.default_rng(seed)
    keys = np.unique(data.id_columns["re0"])
    model = GameModel(
        coordinates={
            "fixed": FixedEffectModel(
                model_for_task("logistic_regression", Coefficients(
                    rng.standard_normal(fixed_dim).astype(np.float32)
                )),
                "global",
            ),
            "per_entity": RandomEffectModel(
                table=rng.standard_normal(
                    (len(keys), random_dim)
                ).astype(np.float32),
                keys=keys, entity_column="re0", shard_name="re0",
                task_type="logistic_regression",
            ),
        },
        task_type="logistic_regression",
    )
    return model, data


def _retrained(model: GameModel, seed: int) -> GameModel:
    rng = np.random.default_rng(seed)
    fixed = model.coordinates["fixed"]
    per_entity = model.coordinates["per_entity"]
    means = np.asarray(fixed.coefficients.means)
    return GameModel(
        coordinates={
            "fixed": FixedEffectModel(
                model_for_task(model.task_type, Coefficients(
                    (means + rng.standard_normal(means.shape)).astype(
                        np.float32
                    )
                )),
                fixed.shard_name,
            ),
            "per_entity": RandomEffectModel(
                table=rng.standard_normal(
                    (per_entity.num_entities, per_entity.dim)
                ).astype(np.float32),
                keys=per_entity.keys,
                entity_column=per_entity.entity_column,
                shard_name=per_entity.shard_name,
                task_type=model.task_type,
            ),
        },
        task_type=model.task_type,
    )


def _counter_total(session, name, **labels):
    total = 0
    for m in session.registry.snapshot()["counters"]:
        if m["name"] != name:
            continue
        if labels and any(
            str(m["labels"].get(k)) != str(v) for k, v in labels.items()
        ):
            continue
        total += m["value"]
    return total


def _fleet(model, data, session, replicas=2, max_batch=16, **kwargs):
    return ServingFleet(
        model, replicas=replicas,
        request_spec=request_spec_for_dataset(model, data),
        max_batch=max_batch, max_delay_s=0.001, telemetry=session,
        **kwargs,
    ).warmup()


def _supervisor(fleet, **overrides):
    defaults = dict(probe_interval_s=0.05, probe_deadline_s=10.0,
                    respawn_base_s=0.0, respawn_jitter=0.0)
    defaults.update(overrides)
    return fleet.supervise(SupervisorPolicy(**defaults), start=False)


def _resurrect(sup, replica, rounds=30, sleep_s=0.05) -> bool:
    for _ in range(rounds):
        sup.check_once()
        if replica.alive:
            return True
        time.sleep(sleep_s)
    return replica.alive


def _timeline(session, name="serving.supervisor_step"):
    steps = [
        (m["value"], m["labels"].get("replica"), m["labels"].get("phase"))
        for m in session.registry.snapshot()["gauges"]
        if m["name"] == name
    ]
    return [(rid, phase) for _, rid, phase in sorted(steps)]


# -- model wire artifact -------------------------------------------------------

def test_model_artifact_roundtrip_bit_exact(tmp_path):
    """The shared serving artifact (the frame-format model file every
    subprocess child loads) roundtrips bit-exactly — tables, coefficient
    vectors, and string/int key vocabularies alike."""
    from photon_tpu.serving.replica_proc import (
        load_model_artifact,
        save_model_artifact,
    )

    model, _ = _fixture(seed=5)
    # String keys exercise the <U* wire buffers.
    per = model.coordinates["per_entity"]
    import dataclasses

    string_model = GameModel(
        coordinates={
            "fixed": model.coordinates["fixed"],
            "per_entity": dataclasses.replace(
                per, keys=np.asarray([f"user-{k}" for k in per.keys])
            ),
        },
        task_type=model.task_type,
    )
    path = str(tmp_path / "model.bin")
    save_model_artifact(path, string_model, version=7)
    got, version = load_model_artifact(path)
    assert version == 7
    assert got.task_type == string_model.task_type
    assert list(got.coordinates) == list(string_model.coordinates)
    np.testing.assert_array_equal(
        np.asarray(got.coordinates["fixed"].coefficients.means),
        np.asarray(string_model.coordinates["fixed"].coefficients.means),
    )
    np.testing.assert_array_equal(
        np.asarray(got.coordinates["per_entity"].table),
        np.asarray(string_model.coordinates["per_entity"].table),
    )
    np.testing.assert_array_equal(
        got.coordinates["per_entity"].keys,
        string_model.coordinates["per_entity"].keys,
    )
    assert got.coordinates["per_entity"].keys.dtype.kind == "U"


# -- padded admission projection (ISSUE 13 satellite) --------------------------

def test_admission_projection_charges_padded_rows():
    """The per-replica wait projection folds bucket padding in (padded
    rows cost compute too) and the projection error lands in
    ``serving.admission_error_s``."""
    model, data = _fixture(seed=7)
    session = TelemetrySession("test-padded-admission")
    with _fleet(model, data, session, replicas=1) as fleet:
        replica = fleet.replicas[0]
        # Ladder is 8/16 for max_batch=16: 3 rows pad to 8, 20 rows chunk
        # into 16 + 8.
        assert replica.padded_rows(3) == 8
        assert replica.padded_rows(16) == 16
        assert replica.padded_rows(20) == 24
        replica.row_seconds = 0.5
        assert replica.projected_wait_s(3) == pytest.approx(
            (replica.pending_padded_rows() + 8) * 0.5
        )
        # Serve enough traffic that at least one dispatch runs with a live
        # pace estimate — that dispatch's projection error is recorded.
        replica.row_seconds = None
        for req in build_requests(data, model, [3] * 8):
            fleet.score(req)
    hists = {
        h["name"]: h for h in session.registry.snapshot()["histograms"]
    }
    assert "serving.admission_error_s" in hists
    assert hists["serving.admission_error_s"]["count"] >= 1


# -- open-loop load through the socket (ISSUE 13 satellite) --------------------

def test_async_client_drives_open_loop_through_socket():
    """The pipelined AsyncScoringClient: seq-tagged frames over a couple
    of connections, futures resolve out of submission order, admission
    sheds come back as typed frames, and ``replay_open_loop`` drives the
    TCP transport itself."""
    model, data = _fixture(seed=11)
    session = TelemetrySession("test-async-client")
    with _fleet(model, data, session, replicas=2) as fleet:
        server = fleet.serve()
        want = model.score(data)
        with AsyncScoringClient(server.address, connections=2,
                                telemetry=session) as client:
            requests = build_requests(data, model, [4] * 24)
            futures = [client.submit(r) for r in requests]
            pos = 0
            for fut in futures:
                rows = np.arange(pos, pos + 4) % data.num_examples
                np.testing.assert_allclose(
                    fut.result(timeout=30), want[rows],
                    rtol=1e-4, atol=1e-4,
                )
                pos = (pos + 4) % data.num_examples
            # A zero deadline sheds remotely; the shed rides back as a
            # typed frame and surfaces through the future.
            with pytest.raises(RequestShedError) as e:
                client.submit(requests[0], deadline_s=0.0).result(timeout=30)
            assert e.value.reason == "deadline"
            # The open-loop replay drives the socket directly.
            traffic = generate_traffic(
                data, model,
                TrafficSpec(requests=30, mean_rows=4, max_rows=16,
                            target_qps=400.0, seed=2),
            )
            outcomes = replay_open_loop(client.submit, traffic,
                                        timeout_s=60.0)
        assert all(o.status == "ok" for o in outcomes)
        for out in outcomes:
            np.testing.assert_allclose(
                out.scores, host_score_request(model, out.item.request),
                rtol=1e-4, atol=1e-4,
            )
            assert out.finished_at_s is not None


# -- crash: exactly-once reroute + resurrection --------------------------------

def test_crash_mid_stream_reroutes_exactly_once_then_resurrects():
    """ISSUE 13 acceptance: ``replica:crash`` mid-traffic yields
    exactly-once responses (none lost, none duplicated), then the
    supervisor re-spawns, re-warms, and rejoins the replica through the
    canary parity gate ≤ 1e-3 vs the host oracle."""
    model, data = _fixture(seed=13)
    session = TelemetrySession("test-crash-resurrect")
    with _fleet(model, data, session, replicas=2) as fleet:
        sup = _supervisor(fleet)
        requests = build_requests(data, model, [4] * 30)
        want = model.score(data)
        set_plan(FaultPlan.parse("replica:crash:replica=r0:times=1"))
        futures = [fleet.submit(r) for r in requests]
        results = [f.result(timeout=60) for f in futures]
        set_plan(None)
        pos = 0
        for got in results:  # every future resolved with its OWN scores
            rows = np.arange(pos, pos + 4) % data.num_examples
            np.testing.assert_allclose(got, want[rows], rtol=1e-4,
                                       atol=1e-4)
            pos = (pos + 4) % data.num_examples
        r0 = fleet.replicas[0]
        assert not r0.alive and r0.death_cause == "crash"
        assert _resurrect(sup, r0)
        # Post-rejoin: the resurrected replica serves its own correct
        # scores again (direct submit — dispatch-set membership is
        # asserted via alive + generation).
        assert r0.generation == 1
        got = r0.submit(requests[0]).result(timeout=30)
        np.testing.assert_allclose(got, want[np.arange(4)], rtol=1e-3,
                                   atol=1e-3)
    assert _counter_total(
        session, "serving.replica_deaths", replica="r0", cause="crash"
    ) == 1
    assert _counter_total(
        session, "serving.replica_resurrections", replica="r0"
    ) == 1
    phases = [p for rid, p in _timeline(session) if rid == "r0"]
    assert phases == ["died-crash", "respawn", "rejoin-probe", "rejoined"]


def test_hang_probe_timeout_treated_like_crash():
    """ISSUE 13 satellite: a wedged replica (``replica:hang`` — no
    failure, just no progress) is detected by the supervisor's deadline,
    declared dead like a crash, its in-flight futures reroute
    exactly-once, and it resurrects the same way."""
    model, data = _fixture(seed=17)
    session = TelemetrySession("test-hang")
    with _fleet(model, data, session, replicas=2) as fleet:
        sup = _supervisor(fleet, probe_deadline_s=0.5, hang_timeout_s=0.2)
        requests = build_requests(data, model, [4] * 20)
        want = model.score(data)
        set_plan(FaultPlan.parse("replica:hang:replica=r0:times=1"))
        futures = [fleet.submit(r) for r in requests]
        # Give the wedge time to latch (r0's batcher thread is stuck in
        # the injected hang; its heartbeat goes stale with work pending).
        time.sleep(0.4)
        sup.check_once()  # declares the hang, abandons, reroutes
        results = [f.result(timeout=60) for f in futures]
        set_plan(None)
        pos = 0
        for got in results:
            rows = np.arange(pos, pos + 4) % data.num_examples
            np.testing.assert_allclose(got, want[rows], rtol=1e-4,
                                       atol=1e-4)
            pos = (pos + 4) % data.num_examples
        r0 = fleet.replicas[0]
        assert _counter_total(
            session, "serving.replica_deaths", replica="r0", cause="hang"
        ) == 1
        assert _resurrect(sup, r0)
    assert _counter_total(
        session, "serving.replica_resurrections", replica="r0"
    ) == 1
    phases = [p for rid, p in _timeline(session) if rid == "r0"]
    assert phases[0] == "died-hang" and phases[-1] == "rejoined"


def test_flapping_replica_quarantined_permanently():
    """ISSUE 13 satellite: N deaths inside the flap window quarantine the
    replica permanently — no further respawn attempts, the fleet keeps
    serving on the survivor."""
    model, data = _fixture(seed=19)
    session = TelemetrySession("test-flap")
    with _fleet(model, data, session, replicas=2) as fleet:
        sup = _supervisor(fleet, max_deaths=2, flap_window_s=60.0)
        (req,) = build_requests(data, model, [4])
        want = host_score_request(model, req)
        r0 = fleet.replicas[0]
        # Death #1 -> resurrected.
        set_plan(FaultPlan.parse("replica:crash:replica=r0:times=1"))
        fleet.submit(req).result(timeout=30)
        set_plan(None)
        assert not r0.alive
        assert _resurrect(sup, r0)
        # Death #2 inside the window -> quarantined, never respawned.
        set_plan(FaultPlan.parse("replica:crash:replica=r0:times=1"))
        fleet.submit(req).result(timeout=30)
        set_plan(None)
        assert not r0.alive
        for _ in range(5):
            sup.check_once()
        assert r0.quarantined and not r0.alive
        assert _counter_total(
            session, "serving.replica_quarantined", replica="r0"
        ) == 1
        assert _counter_total(
            session, "serving.replica_resurrections", replica="r0"
        ) == 1
        assert _counter_total(
            session, "serving.replica_deaths", replica="r0"
        ) == 2
        # The fleet still serves (through the survivor).
        np.testing.assert_allclose(
            fleet.score(req), want, rtol=1e-4, atol=1e-4
        )
        assert ("r0", "quarantined") in _timeline(session)


def test_resurrection_during_rollout_rejoins_on_new_model():
    """ISSUE 13 satellite: a replica that dies before/through a rollout
    comes back on the CURRENT model — the supervisor re-syncs the model
    version at rejoin, so the fleet is never split across versions."""
    model, data = _fixture(seed=23)
    retrained = _retrained(model, seed=29)
    session = TelemetrySession("test-rollout-resurrect")
    with _fleet(model, data, session, replicas=2) as fleet:
        sup = _supervisor(fleet)
        requests = build_requests(data, model, [4] * 6)
        for req in requests:
            fleet.score(req)
        set_plan(FaultPlan.parse("replica:crash:replica=r0:times=1"))
        futs = [fleet.submit(r) for r in requests]
        [f.result(timeout=30) for f in futs]
        set_plan(None)
        r0 = fleet.replicas[0]
        assert not r0.alive
        # The rollout lands while r0 is dead: the canary is the survivor.
        fleet.rollout(retrained, probe_requests=requests[:2])
        assert fleet.current_model()[1] == 1
        assert _resurrect(sup, r0)
        # r0 rejoined on the NEW model.
        want_new = retrained.score(data)
        got = r0.submit(requests[0]).result(timeout=30)
        np.testing.assert_allclose(
            got, want_new[np.arange(4)], rtol=1e-3, atol=1e-3
        )


def test_kill_resurrect_cycle_zero_recompiles():
    """ISSUE 13 acceptance: a full kill→resurrect cycle triggers ZERO jax
    compile events after warmup — the thread replica's re-warm hits the
    cached bucket programs, and the rejoin probes ride them."""
    import jax.monitoring

    model, data = _fixture(seed=31)
    session = TelemetrySession("test-zero-recompile")
    compile_events = []

    def listener(event, **kwargs):
        if "compile" in event:
            compile_events.append(event)

    with _fleet(model, data, session, replicas=2) as fleet:
        sup = _supervisor(fleet)
        compiled = fleet.compilations
        requests = build_requests(data, model, [4] * 12)
        want = model.score(data)
        jax.monitoring.register_event_listener(listener)
        try:
            set_plan(FaultPlan.parse("replica:crash:replica=r0:times=1"))
            futs = [fleet.submit(r) for r in requests]
            [f.result(timeout=30) for f in futs]
            set_plan(None)
            assert _resurrect(sup, fleet.replicas[0])
            pos = 0
            for req in requests:  # post-rejoin traffic across the fleet
                rows = np.arange(pos, pos + 4) % data.num_examples
                np.testing.assert_allclose(
                    fleet.score(req), want[rows], rtol=1e-4, atol=1e-4
                )
                pos = (pos + 4) % data.num_examples
        finally:
            jax.monitoring.unregister_event_listener(listener)
        assert fleet.compilations == compiled
    assert compile_events == []


def test_spawn_failure_backs_off_and_eventually_rejoins():
    """``replica:spawn`` (retriable): failed respawn attempts count as
    ``serving.respawn_failures``, back off with the capped exponential
    policy, and a later attempt completes the resurrection."""
    model, data = _fixture(seed=37)
    session = TelemetrySession("test-spawn-backoff")
    with _fleet(model, data, session, replicas=2) as fleet:
        sup = _supervisor(fleet, respawn_base_s=0.05)
        (req,) = build_requests(data, model, [4])
        set_plan(FaultPlan.parse(
            "replica:crash:replica=r0:times=1,"
            "replica:spawn:replica=r0:times=2"
        ))
        fleet.submit(req).result(timeout=30)
        r0 = fleet.replicas[0]
        assert not r0.alive
        sup.check_once()  # death noted; respawn attempt 1 hits the fault
        assert _counter_total(
            session, "serving.respawn_failures", replica="r0"
        ) == 1
        sup.check_once()  # still inside the backoff window: no attempt
        assert _counter_total(
            session, "serving.respawn_failures", replica="r0"
        ) == 1
        assert _resurrect(sup, r0, rounds=40, sleep_s=0.05)
        set_plan(None)
        assert _counter_total(
            session, "serving.respawn_failures", replica="r0"
        ) == 2
        assert _counter_total(
            session, "serving.replica_resurrections", replica="r0"
        ) == 1
        # The timeline keeps one gauge per (replica, phase) — the failure
        # COUNT is the respawn_failures counter above; the timeline pins
        # the order: the last failure precedes the successful rejoin.
        phases = [p for rid, p in _timeline(session) if rid == "r0"]
        assert "respawn-failed" in phases
        assert phases.index("respawn-failed") < phases.index("rejoined")
        assert phases[-1] == "rejoined"


def test_probe_timeout_on_busy_replica_is_not_a_hang():
    """A saturated-but-PROGRESSING replica that misses the probe deadline
    by queueing is busy, not hung: only a stale heartbeat alongside the
    missed probe declares — otherwise a load spike would cascade into a
    mass abandon and, repeated, a permanent quarantine of a healthy
    fleet."""
    from concurrent.futures import Future

    from photon_tpu.fault.watchdog import heartbeat

    model, data = _fixture(seed=53)
    session = TelemetrySession("test-busy-not-hung")
    with _fleet(model, data, session, replicas=2) as fleet:
        sup = _supervisor(fleet, probe_deadline_s=0.1, hang_timeout_s=0.5)
        r0 = fleet.replicas[0]
        r0.submit = lambda request: Future()  # the probe never resolves
        heartbeat(r0.heartbeat_site)  # fresh scoring progress
        sup._health_check(r0)
        assert r0.alive  # busy, not hung
        time.sleep(0.6)  # now the progress mark is stale too
        sup._health_check(r0)
        assert not r0.alive and r0.death_cause == "hang"


def test_parity_gate_rejects_nan_and_shape_mismatch():
    """The probe/rejoin/rollout parity gate fails loudly on non-finite or
    misshapen served answers — ``np.abs(nan) > tol`` is False, so a
    NaN-scoring replica (or canary!) would otherwise slide through the
    gate and be promoted fleet-wide."""
    from photon_tpu.serving import router, supervisor
    from photon_tpu.serving.supervisor import parity_worst

    # The ONE comparison: the rollout canary gate and the supervision
    # probes must share this exact function, or their NaN semantics can
    # silently diverge.
    assert supervisor.parity_worst is router.parity_worst
    assert parity_worst([1.0, 2.0], np.asarray([1.0, 2.0])) == 0.0
    assert parity_worst([1.0, 2.5], [1.0, 2.0]) == pytest.approx(0.5)
    assert parity_worst([1.0, np.nan], [1.0, 2.0]) == float("inf")
    assert parity_worst([1.0], [1.0, 2.0]) == float("inf")
    assert parity_worst([], []) == 0.0


def test_failed_rollout_keeps_model_version_monotonic():
    """A failed rollout restores the MODEL but never the version number:
    reusing a version would let a probe that captured the failed
    rollout's (model, version) pass the supervisor's stale-oracle check
    against a later rollout's different model."""
    model, data = _fixture(seed=59)
    retrained = _retrained(model, seed=61)
    session = TelemetrySession("test-rollout-version")
    with _fleet(model, data, session, replicas=2) as fleet:
        probes = build_requests(data, model, [4])
        assert fleet.current_model() == (model, 0)

        def bad_oracle(req):
            return np.full(req.num_rows, 1e6, np.float32)

        with pytest.raises(Exception):
            fleet.rollout(retrained, probe_requests=probes,
                          probe_oracle=bad_oracle)
        m, v = fleet.current_model()
        assert m is model and v == 2  # bump + rollback-bump: monotonic
        assert not fleet.rollout_in_progress()
        fleet.rollout(retrained, probe_requests=probes)
        m2, v2 = fleet.current_model()
        assert m2 is retrained and v2 == 3


# -- subprocess backend --------------------------------------------------------

def test_subprocess_replicas_end_to_end():
    """ISSUE 13 acceptance (subprocess backend): children with their own
    Python/jax runtimes serve over the frame protocol — scores match the
    thread-backed scorer ≤ 1e-6 on identical requests; a model hot-swaps
    over the wire (canary rollout); a real SIGKILL mid-stream reroutes
    exactly-once, the supervisor detects the exit code, re-spawns a fresh
    child from the CURRENT model artifact, and gates its rejoin on the
    parity probe."""
    from photon_tpu.serving.scorer import GameScorer

    model, data = _fixture(seed=41)
    retrained = _retrained(model, seed=43)
    session = TelemetrySession("test-subprocess")
    spec = request_spec_for_dataset(model, data)
    fleet = ServingFleet(
        model, replicas=2, backend="subprocess", request_spec=spec,
        max_batch=16, max_delay_s=0.001, telemetry=session,
    ).warmup()
    try:
        requests = build_requests(data, model, [1, 5, 16, 4, 4, 4])
        # Parity vs the thread-backed scorer on identical requests.
        reference = GameScorer(model, request_spec=spec,
                               max_batch=16).warmup()
        for req in requests:
            got = fleet.score(req)
            want = reference.score_batch(req)
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
        # Liveness ping frame reports the child's state.
        pong = fleet.replicas[0].ping(10.0)
        assert pong["kind"] == "pong" and pong["compilations"] >= 1
        # Canary rollout over the wire: children swap from the shared
        # artifact with zero parent-side compiles.
        compiled = fleet.compilations
        fleet.rollout(retrained, probe_requests=requests[:2])
        assert fleet.compilations == compiled
        want_new = retrained.score(data)
        got = fleet.score(requests[3])
        np.testing.assert_allclose(
            got, want_new[np.arange(22, 26) % data.num_examples],
            rtol=1e-4, atol=1e-4,
        )
        # A REAL crash: SIGKILL the child mid-stream.
        sup = fleet.supervise(
            SupervisorPolicy(probe_interval_s=0.05, probe_deadline_s=30.0,
                             respawn_base_s=0.0, respawn_jitter=0.0),
            start=False,
        )
        r0 = fleet.replicas[0]
        os.kill(r0.child_pid, signal.SIGKILL)
        time.sleep(0.2)
        futs = [fleet.submit(r) for r in requests]
        results = [f.result(timeout=60) for f in futs]  # exactly-once
        for req, got in zip(requests, results):
            np.testing.assert_allclose(
                got, host_score_request(retrained, req),
                rtol=1e-4, atol=1e-4,
            )
        assert _resurrect(sup, r0, rounds=60, sleep_s=0.2)
        assert r0.poll_exit() is None  # a fresh child is running
        got = r0.submit(requests[1]).result(timeout=30)
        np.testing.assert_allclose(
            got, host_score_request(retrained, requests[1]),
            rtol=1e-3, atol=1e-3,
        )
    finally:
        fleet.close()
    assert _counter_total(
        session, "serving.replica_deaths", replica="r0", cause="crash"
    ) == 1
    assert _counter_total(
        session, "serving.replica_resurrections", replica="r0"
    ) == 1


def test_child_stats_frame_merges_into_parent_report():
    """ISSUE 14 satellite (ROADMAP fleet edge (e)): a subprocess replica's
    scorer-level ``serving.*`` counters accrue in the CHILD process; the
    ``stats`` control frame pulls them and merges deltas into the parent's
    registry under the same names + a replica label — idempotent across
    repeated pulls — and the fleet report renders the child-scorer row."""
    from photon_tpu.telemetry.report import render_markdown

    model, data = _fixture(seed=51)
    session = TelemetrySession("test-child-stats")
    spec = request_spec_for_dataset(model, data)
    fleet = ServingFleet(
        model, replicas=1, backend="subprocess", request_spec=spec,
        max_batch=16, max_delay_s=0.001, telemetry=session,
    ).warmup()
    try:
        requests = build_requests(data, model, [4, 9, 2])
        for req in requests:
            fleet.score(req)
        r0 = fleet.replicas[0]
        merged = r0.pull_stats()
        assert merged  # counters crossed the wire
        # Delta merge: a second pull with no new traffic adds nothing.
        assert r0.pull_stats() == {}
        syncs_after_first = _counter_total(
            session, "serving.host_syncs", replica="r0"
        )
        assert syncs_after_first == len(requests)  # 1 host sync per batch
        # The supervisor's health pass pulls too (new traffic arrives, the
        # next check_once folds it in — plus its own probe batch).
        for req in requests:
            fleet.score(req)
        sup = fleet.supervise(
            SupervisorPolicy(probe_interval_s=10.0, probe_deadline_s=30.0),
            start=False,
        )
        sup.check_once()
        syncs = _counter_total(session, "serving.host_syncs", replica="r0")
        batches = _counter_total(session, "serving.batches", replica="r0")
        assert syncs >= 2 * len(requests)
        assert syncs == batches  # the child's one-sync-per-batch contract
    finally:
        fleet.close()
    report = session.build_report()
    text = render_markdown(report)
    assert "child scorers" in text
    assert "r0: host_syncs=" in text


# -- report renderer -----------------------------------------------------------

def test_report_renders_supervisor_timeline():
    """ISSUE 13 satellite: the "Serving fleet" report section grows the
    supervisor block — deaths/resurrections/quarantine summary plus the
    event timeline."""
    from photon_tpu.telemetry.report import render_markdown

    model, data = _fixture(seed=47)
    session = TelemetrySession("test-supervisor-report")
    with _fleet(model, data, session, replicas=2) as fleet:
        sup = _supervisor(fleet, max_deaths=2)
        (req,) = build_requests(data, model, [4])
        for _ in range(2):
            set_plan(FaultPlan.parse("replica:crash:replica=r0:times=1"))
            fleet.submit(req).result(timeout=30)
            set_plan(None)
            _resurrect(sup, fleet.replicas[0])
        for _ in range(3):
            sup.check_once()
    report = {
        "driver": "test", "run_id": "x", "status": "ok",
        "metrics": session.registry.snapshot(),
    }
    md = render_markdown(report)
    assert "## Serving fleet" in md
    assert "**supervisor**" in md
    assert "resurrections=1" in md
    assert "quarantined=1 (r0)" in md
    assert "**supervisor timeline**" in md
    assert "r0:died-crash" in md and "r0:rejoined" in md
    assert "r0:quarantined" in md


# -- fleet-wide rollback (ISSUE 15 satellite / ROADMAP fleet edge (d)) --------

def test_fleet_wide_parity_regression_rolls_back_not_quarantines():
    """EVERY replica failing its known-answer probe right after a swap is
    a fleet-wide regression: the supervisor triggers ONE rollout rollback
    to the predecessor artifact — zero deaths, zero quarantines, every
    replica stays in the dispatch set serving the restored model."""
    model, data = _fixture(seed=61)
    model2 = _retrained(model, seed=62)
    skewed = _retrained(model, seed=63)  # what the replicas "really" serve
    session = TelemetrySession("t-fleet-rollback")
    fleet = _fleet(model, data, session)
    sup = _supervisor(fleet)
    probes = build_requests(data, model2, [4, 4])
    fleet.rollout(model2, probe_requests=probes)
    assert fleet.current_model()[0] is model2
    # Simulate post-swap fleet-wide artifact skew: every replica silently
    # serves a model that disagrees with the published one's oracle.
    for replica in fleet.replicas:
        replica.scorer.swap_model(skewed)
    sup.check_once()
    assert _counter_total(session, "serving.rollout_rollbacks") == 1
    assert _counter_total(session, "serving.replica_deaths") == 0
    assert _counter_total(session, "serving.replica_quarantined") == 0
    assert all(r.alive for r in fleet.replicas)
    # Rolled back to the PREDECESSOR (version monotonic), serving parity
    # restored end to end.
    current, version = fleet.current_model()
    assert current is model
    assert version == 2
    req = build_requests(data, model, [6])[0]
    got = fleet.score(req)
    np.testing.assert_allclose(
        got, host_score_request(model, req), atol=1e-5
    )
    # The next pass is clean (no lingering suspicion), and the timeline
    # carries the fleet-rollback marks.
    sup.check_once()
    assert _counter_total(session, "serving.rollout_rollbacks") == 1
    phases = [phase for _rid, phase in _timeline(session)]
    assert phases.count("fleet-rollback") == len(fleet.replicas)
    fleet.close()


def test_partial_parity_failure_still_declares_per_replica():
    """One replica wrong, the rest fine: NOT a fleet regression — the
    existing per-replica parity declaration (death + resurrection path)
    applies, and no rollback fires."""
    model, data = _fixture(seed=67)
    model2 = _retrained(model, seed=68)
    skewed = _retrained(model, seed=69)
    session = TelemetrySession("t-partial-parity")
    fleet = _fleet(model, data, session)
    sup = _supervisor(fleet, resurrect=False)
    fleet.rollout(model2, probe_requests=build_requests(data, model2, [4]))
    fleet.replicas[0].scorer.swap_model(skewed)
    sup.check_once()
    assert _counter_total(session, "serving.rollout_rollbacks") == 0
    assert _counter_total(
        session, "serving.replica_deaths", cause="parity"
    ) == 1
    assert not fleet.replicas[0].alive
    assert fleet.replicas[1].alive
    assert fleet.current_model()[0] is model2
    fleet.close()


def test_rollback_without_predecessor_falls_back_to_declarations():
    """A fleet that never completed a rollout has no predecessor: the
    all-replica parity failure declares per-replica exactly as before
    (rollback_to_previous returns False)."""
    model, data = _fixture(seed=71)
    skewed = _retrained(model, seed=72)
    session = TelemetrySession("t-no-predecessor")
    fleet = _fleet(model, data, session)
    sup = _supervisor(fleet, resurrect=False)
    assert fleet.rollback_to_previous() is False
    for replica in fleet.replicas:
        replica.scorer.swap_model(skewed)
    sup.check_once()
    assert _counter_total(session, "serving.rollout_rollbacks") == 0
    assert _counter_total(
        session, "serving.replica_deaths", cause="parity"
    ) == len(fleet.replicas)
    fleet.close()
