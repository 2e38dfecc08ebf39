"""Fleet serving (photon_tpu/serving fleet tier, ISSUE 12): socket
transport, replicated scorers behind the router, deadline-aware admission
control, traffic generation, canary rollout, replica-death rerouting.

The contracts pinned here:

- wire roundtrip: a request (dense + sparse + string/int keys + offset +
  deadline) survives pack→unpack bit-exactly; responses carry scores,
  sheds, and errors as typed frames;
- TCP serving parity: scores over the loopback ingest equal the host
  oracle; an injected ``transport:read`` fault is retried (reconnect +
  resend) to a correct response;
- overload: past-saturation offered load sheds deterministically
  (``serving.shed`` counted, every future resolves, admitted p99 bounded,
  ZERO jax compilations after warmup — the recompile-freedom contract
  holds under overload);
- cold-start storm: a burst of unknown entities rides the zero-row
  fallback (fixed-effect-only scores, ``serving.cold_entities`` counted,
  no recompiles);
- replica death: a ``serve:replica_kill`` mid-stream reroutes in-flight
  work with no lost or duplicated responses;
- canary rollout: one replica first, mirrored-traffic parity probe, then
  the rest — responses are always exactly ONE model's scores; a probe
  failure rolls the canary back; a canary killed mid-probe fails over to
  the next replica;
- the "Serving fleet" telemetry report section renders per-replica
  QPS/depth, the shed breakdown, and the rollout timeline.
"""

from __future__ import annotations

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
    AdmissionPolicy,
    RequestShedError,
    RolloutParityError,
    ScoringClient,
    ScoringRequest,
    ServingFleet,
    TrafficSpec,
    build_requests,
    generate_traffic,
    host_score_request,
    request_spec_for_dataset,
    run_closed_loop_outcomes,
)
from photon_tpu.serving.transport import (
    pack_request,
    pack_scores,
    pack_shed,
    unpack_request,
    unpack_response,
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


# -- wire format -------------------------------------------------------------

def test_subprocess_fleet_refuses_under_a_tpu_parent(monkeypatch):
    """One process per chip: a parent on a TPU holds the chip, so a child
    replica could only fail or hang in backend init — the fleet refuses at
    construction, before anything is spawned."""
    from photon_tpu.utils import device

    model, _ = _fixture()
    monkeypatch.setattr(device, "device_facts", lambda: {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1,
    })
    with pytest.raises(RuntimeError, match="holds the chip"):
        ServingFleet(model, replicas=2, backend="subprocess")


def test_transport_request_roundtrip():
    req = ScoringRequest(
        features={
            "dense": np.arange(12, dtype=np.float32).reshape(3, 4),
            "sparse": (
                np.arange(6, dtype=np.int32).reshape(3, 2),
                np.linspace(0, 1, 6, dtype=np.float32).reshape(3, 2),
            ),
        },
        entity_ids={
            "user": np.asarray([7, 9, 11], np.int64),
            "item": np.asarray(["a-1", "bb-22", "ccc-333"]),
        },
        offset=np.asarray([0.5, -1.0, 2.0], np.float32),
    )
    got, deadline = unpack_request(pack_request(req, deadline_s=0.025))
    assert abs(deadline - 0.025) < 1e-12
    np.testing.assert_array_equal(got.features["dense"],
                                  req.features["dense"])
    np.testing.assert_array_equal(got.features["sparse"][0],
                                  req.features["sparse"][0])
    np.testing.assert_array_equal(got.features["sparse"][1],
                                  req.features["sparse"][1])
    np.testing.assert_array_equal(got.entity_ids["user"],
                                  req.entity_ids["user"])
    np.testing.assert_array_equal(got.entity_ids["item"],
                                  req.entity_ids["item"])
    np.testing.assert_array_equal(got.offset, req.offset)
    assert got.entity_ids["item"].dtype == req.entity_ids["item"].dtype
    # No deadline → None on the other side.
    _, none_deadline = unpack_request(pack_request(req))
    assert none_deadline is None


def test_transport_response_roundtrips():
    scores = np.linspace(-2, 2, 7, dtype=np.float32)
    np.testing.assert_array_equal(
        unpack_response(pack_scores(scores)), scores
    )
    with pytest.raises(RequestShedError, match="queue projection") as e:
        unpack_response(pack_shed("overload", "queue projection blown"))
    assert e.value.reason == "overload"
    from photon_tpu.serving.transport import TransportError

    with pytest.raises(TransportError, match="boom"):
        unpack_response(
            __import__(
                "photon_tpu.serving.transport", fromlist=["pack_error"]
            ).pack_error("boom")
        )


# -- TCP serving -------------------------------------------------------------

def test_fleet_serves_over_tcp_matching_host_oracle():
    model, data = _fixture(seed=5)
    session = TelemetrySession("test-fleet-tcp")
    with _fleet(model, data, session, replicas=1) as fleet:
        server = fleet.serve()
        with ScoringClient(server.address, telemetry=session) as client:
            for req in build_requests(data, model, [1, 5, 16]):
                got = client.score(req, deadline_s=10.0)
                np.testing.assert_allclose(
                    got, host_score_request(model, req),
                    rtol=1e-4, atol=1e-4,
                )
    assert _counter_total(session, "serving.transport_connections") >= 1
    assert _counter_total(
        session, "serving.transport_bytes", direction="in"
    ) > 0


def test_transport_read_fault_retried_to_clean_response(monkeypatch):
    monkeypatch.setenv("PHOTON_IO_RETRY_BASE_S", "0")
    model, data = _fixture(seed=7)
    session = TelemetrySession("test-transport-fault")
    with _fleet(model, data, session, replicas=1) as fleet:
        server = fleet.serve()
        (req,) = build_requests(data, model, [6])
        set_plan(FaultPlan.parse("transport:read:times=2"))
        with ScoringClient(server.address, telemetry=session) as client:
            got = client.score(req)
        set_plan(None)
        np.testing.assert_allclose(
            got, host_score_request(model, req), rtol=1e-4, atol=1e-4
        )
    assert _counter_total(
        session, "io.retries", site="transport:read"
    ) >= 1


# -- router dispatch + admission ---------------------------------------------

def test_router_dispatches_across_replicas():
    model, data = _fixture(seed=9)
    session = TelemetrySession("test-dispatch")
    with _fleet(model, data, session, replicas=2) as fleet:
        outcomes, _ = run_closed_loop_outcomes(
            lambda tid: (
                lambda item: fleet.score(item.request)
            ),
            generate_traffic(
                data, model,
                TrafficSpec(requests=40, mean_rows=4, max_rows=16, seed=0),
            ).items,
            clients=4,
        )
    assert all(o.status == "ok" for o in outcomes)
    # Queue-depth-aware dispatch actually spread load: both replicas saw
    # traffic (40 requests, 4 concurrent clients, 1ms windows).
    assert _counter_total(
        session, "serving.replica_requests", replica="r0"
    ) > 0
    assert _counter_total(
        session, "serving.replica_requests", replica="r1"
    ) > 0
    assert _counter_total(session, "serving.admitted") == 40


def test_overload_sheds_deterministically_without_recompiles():
    """ISSUE 12 satellite: offered load past saturation sheds (counted,
    every future resolves, admitted p99 bounded) and the whole episode
    triggers ZERO jax compilations after warmup."""
    import jax.monitoring

    model, data = _fixture(seed=11)
    session = TelemetrySession("test-overload")
    compile_events = []

    def listener(event, **kwargs):
        if "compile" in event:
            compile_events.append(event)

    import time

    with _fleet(
        model, data, session, replicas=2,
        admission=AdmissionPolicy(max_queue_rows=64),
    ) as fleet:
        requests = build_requests(data, model, [4] * 150)
        want = model.score(data)
        jax.monitoring.register_event_listener(listener)
        try:
            # A single-thread flood far past the drain rate: the 64-row
            # depth cap must start shedding while every admitted request
            # still completes with its OWN rows' scores.
            admitted, sheds = [], 0
            latencies = []
            pos = 0
            for req in requests:
                rows = np.arange(pos, pos + 4) % data.num_examples
                pos = (pos + 4) % data.num_examples
                t0 = time.monotonic()
                try:
                    fut = fleet.submit(req)
                except RequestShedError as e:
                    assert e.reason in ("queue_full", "overload")
                    sheds += 1
                    continue
                fut.add_done_callback(
                    lambda f, t0=t0: latencies.append(
                        time.monotonic() - t0
                    )
                )
                admitted.append((fut, rows))
            results = [
                (f.result(timeout=60), rows) for f, rows in admitted
            ]
            # Deterministic deadline shed: a zero budget can never admit.
            with pytest.raises(RequestShedError) as shed_info:
                fleet.submit(requests[0], deadline_s=0.0)
        finally:
            jax.monitoring.unregister_event_listener(listener)
        assert shed_info.value.reason == "deadline"
        for got, rows in results:
            np.testing.assert_allclose(
                got, want[rows], rtol=1e-4, atol=1e-4
            )
    assert sheds > 0
    assert len(results) > 0
    assert len(results) + sheds == len(requests)
    assert _counter_total(session, "serving.shed") == sheds + 1
    # Every admitted request resolved, no recompiles, and the depth cap
    # keeps the admitted tail bounded (64 queued rows at CPU-fixture pace
    # drain in well under a second; 5s is the no-unbounded-queue pin).
    assert compile_events == []
    assert len(latencies) == len(results)
    assert float(np.percentile(latencies, 99)) < 5.0


def test_deadline_shed_and_hit_accounting():
    model, data = _fixture(seed=13)
    session = TelemetrySession("test-deadline")
    with _fleet(model, data, session, replicas=1) as fleet:
        (req,) = build_requests(data, model, [4])
        # Generous budget: admitted and met.
        got = fleet.score(req, deadline_s=30.0)
        np.testing.assert_allclose(
            got, host_score_request(model, req), rtol=1e-4, atol=1e-4
        )
        with pytest.raises(RequestShedError):
            fleet.submit(req, deadline_s=0.0)
    assert _counter_total(session, "serving.admitted") == 1
    assert _counter_total(session, "serving.shed", reason="deadline") == 1


def test_cold_start_storm_rides_zero_row_fallback():
    """ISSUE 12 satellite: a burst of unknown entities gets fixed-effect-
    only scores through the (movable) zero row, counted as cold — and
    never recompiles."""
    import jax.monitoring

    model, data = _fixture(seed=17)
    session = TelemetrySession("test-storm")
    traffic = generate_traffic(
        data, model,
        TrafficSpec(requests=30, mean_rows=4, max_rows=16,
                    storm_frac=0.3, storm_at=0.5, seed=3),
    )
    storm_items = [t for t in traffic.items if t.kind == "storm"]
    assert len(storm_items) == 9
    compile_events = []

    def listener(event, **kwargs):
        if "compile" in event:
            compile_events.append(event)

    with _fleet(model, data, session, replicas=2) as fleet:
        jax.monitoring.register_event_listener(listener)
        try:
            outcomes, _ = run_closed_loop_outcomes(
                lambda tid: (lambda item: fleet.score(item.request)),
                traffic.items, clients=3,
            )
        finally:
            jax.monitoring.unregister_event_listener(listener)
    assert all(o.status == "ok" for o in outcomes)
    for out in outcomes:
        np.testing.assert_allclose(
            out.scores, host_score_request(model, out.item.request),
            rtol=1e-4, atol=1e-4,
        )
    storm_rows = sum(t.request.num_rows for t in storm_items)
    assert _counter_total(session, "serving.cold_entities") == storm_rows
    assert compile_events == []


# -- replica death -----------------------------------------------------------

def test_replica_kill_mid_stream_reroutes_without_loss():
    """ISSUE 12 acceptance: a replica killed mid-replay reroutes its
    in-flight work — every submitted request resolves exactly once with
    its own correct scores (none lost, none duplicated), the death is
    counted, and the survivor serves the rest."""
    model, data = _fixture(seed=19)
    session = TelemetrySession("test-kill")
    with _fleet(model, data, session, replicas=2) as fleet:
        requests = build_requests(data, model, [4] * 30)
        set_plan(FaultPlan.parse("serve:replica_kill:replica=r0:times=1"))
        futures = [fleet.submit(r) for r in requests]
        results = [f.result(timeout=60) for f in futures]
        set_plan(None)
        want = model.score(data)
        pos = 0
        for got in results:
            rows = np.arange(pos, pos + 4) % data.num_examples
            np.testing.assert_allclose(
                got, want[rows], rtol=1e-4, atol=1e-4
            )
            pos = (pos + 4) % data.num_examples
        assert not fleet.replicas[0].alive
        assert fleet.replicas[1].alive
        # Post-kill traffic keeps serving through the survivor.
        np.testing.assert_allclose(
            fleet.score(requests[0]), want[np.arange(4)],
            rtol=1e-4, atol=1e-4,
        )
    assert _counter_total(
        session, "serving.replica_deaths", replica="r0"
    ) == 1
    assert _counter_total(session, "serving.rerouted") >= 1


def test_all_replicas_dead_sheds_no_replica():
    model, data = _fixture(seed=23)
    session = TelemetrySession("test-all-dead")
    with _fleet(model, data, session, replicas=1) as fleet:
        (req,) = build_requests(data, model, [4])
        set_plan(FaultPlan.parse("serve:replica_kill:times=1"))
        fut = fleet.submit(req)
        from photon_tpu.serving import NoHealthyReplicaError

        with pytest.raises(NoHealthyReplicaError):
            fut.result(timeout=30)
        set_plan(None)
        with pytest.raises(RequestShedError) as e:
            fleet.submit(req)
        assert e.value.reason == "no_replica"


# -- canary rollout ----------------------------------------------------------

def test_rollout_canary_probe_then_promote_under_load():
    """ISSUE 12 acceptance: a canary rollout completes under load with
    zero mixed-model responses — every response is wholly one model's
    scores, the stream's tail serves the new model, and nothing
    recompiles (same-layout swap, capacity-headroom tables)."""
    import jax.monitoring

    model, data = _fixture(seed=29)
    retrained = _retrained(model, seed=31)
    session = TelemetrySession("test-rollout")
    want_old = model.score(data)
    want_new = retrained.score(data)
    requests = build_requests(data, model, [8] * 40)
    windows = [np.arange(i * 8, (i + 1) * 8) % data.num_examples
               for i in range(40)]
    compile_events = []

    def listener(event, **kwargs):
        if "compile" in event:
            compile_events.append(event)

    with _fleet(model, data, session, replicas=2, max_batch=32) as fleet:
        compiled = fleet.compilations
        jax.monitoring.register_event_listener(listener)
        try:
            futures = []
            for i, req in enumerate(requests):
                if i == 20:
                    fleet.rollout(retrained)
                futures.append(fleet.submit(req))
            results = [f.result(timeout=60) for f in futures]
        finally:
            jax.monitoring.unregister_event_listener(listener)
        assert fleet.compilations == compiled
    for rows, got in zip(windows, results):
        ok_old = np.allclose(got, want_old[rows], rtol=1e-4, atol=1e-4)
        ok_new = np.allclose(got, want_new[rows], rtol=1e-4, atol=1e-4)
        assert ok_old or ok_new, "response matches neither model"
    assert np.allclose(
        results[-1], want_new[windows[-1]], rtol=1e-4, atol=1e-4
    )
    assert compile_events == []
    assert _counter_total(session, "serving.rollouts") == 1
    assert _counter_total(session, "serving.swaps") == 2  # canary + promote
    # Timeline gauges: canary then probe_ok then promoted.
    steps = {
        (m["labels"]["replica"], m["labels"]["phase"]): m["value"]
        for m in session.registry.snapshot()["gauges"]
        if m["name"] == "serving.rollout_step"
    }
    phases = [p for (_, p), _v in sorted(steps.items(), key=lambda kv: kv[1])]
    assert phases == ["canary", "probe_ok", "promoted"]


def test_rollout_aborts_and_rolls_back_on_parity_failure():
    model, data = _fixture(seed=37)
    retrained = _retrained(model, seed=41)
    session = TelemetrySession("test-rollout-abort")
    with _fleet(model, data, session, replicas=2) as fleet:
        probes = build_requests(data, model, [4, 4])
        bad_oracle = lambda req: np.full(  # noqa: E731 — tiny test stub
            req.num_rows, 1e6, np.float32
        )
        with pytest.raises(RolloutParityError, match="parity probe"):
            fleet.router.rollout(
                retrained, probe_requests=probes, probe_oracle=bad_oracle
            )
        # Canary rolled back: the WHOLE fleet still serves the old model.
        want_old = model.score(data)
        for _ in range(4):
            got = fleet.score(probes[0])
            np.testing.assert_allclose(
                got, want_old[np.arange(4)], rtol=1e-4, atol=1e-4
            )
    steps = {
        m["labels"]["phase"]
        for m in session.registry.snapshot()["gauges"]
        if m["name"] == "serving.rollout_step"
    }
    assert "rolled_back" in steps
    assert _counter_total(session, "serving.rollouts") == 0


def test_rollout_survives_canary_kill_mid_probe():
    """Mid-rollout kill (README failure-matrix row): the canary dies while
    its parity probe runs; the rollout fails over to the next healthy
    replica and completes — the fleet ends up serving the new model."""
    model, data = _fixture(seed=43)
    retrained = _retrained(model, seed=47)
    session = TelemetrySession("test-rollout-kill")
    with _fleet(model, data, session, replicas=2) as fleet:
        probes = build_requests(data, model, [4, 4])
        set_plan(FaultPlan.parse("serve:replica_kill:replica=r0:times=1"))
        fleet.rollout(retrained, probe_requests=probes)
        set_plan(None)
        assert not fleet.replicas[0].alive
        assert fleet.replicas[1].alive
        want_new = retrained.score(data)
        np.testing.assert_allclose(
            fleet.score(probes[0]), want_new[np.arange(4)],
            rtol=1e-4, atol=1e-4,
        )
    steps = {
        (m["labels"]["replica"], m["labels"]["phase"])
        for m in session.registry.snapshot()["gauges"]
        if m["name"] == "serving.rollout_step"
    }
    assert ("r0", "died") in steps
    assert ("r1", "probe_ok") in steps
    assert _counter_total(
        session, "serving.replica_deaths", replica="r0"
    ) == 1


def test_rollout_rolls_back_on_non_parity_probe_failure():
    """A probe failure that is NOT a parity disagreement (here: the oracle
    itself raising) must also roll the canary back — the fleet may never
    be left split across two models by an escaping probe error."""
    model, data = _fixture(seed=59)
    retrained = _retrained(model, seed=61)
    session = TelemetrySession("test-rollout-probe-err")
    with _fleet(model, data, session, replicas=2) as fleet:
        probes = build_requests(data, model, [4, 4])

        def broken_oracle(req):
            raise RuntimeError("oracle exploded")

        with pytest.raises(RuntimeError, match="oracle exploded"):
            fleet.router.rollout(
                retrained, probe_requests=probes, probe_oracle=broken_oracle
            )
        # Canary rolled back: the WHOLE fleet still serves the old model.
        want_old = model.score(data)
        for _ in range(4):
            np.testing.assert_allclose(
                fleet.score(probes[0]), want_old[np.arange(4)],
                rtol=1e-4, atol=1e-4,
            )
    steps = {
        m["labels"]["phase"]
        for m in session.registry.snapshot()["gauges"]
        if m["name"] == "serving.rollout_step"
    }
    assert "rolled_back" in steps
    assert _counter_total(session, "serving.rollouts") == 0


def test_rollout_promote_failure_marks_replica_dead():
    """A replica whose swap fails AT PROMOTE (after the canary probe
    passed) is marked dead — it must not keep serving the old model
    behind a fleet that promoted — and the rollout still completes."""
    model, data = _fixture(seed=67)
    retrained = _retrained(model, seed=71)
    session = TelemetrySession("test-promote-fail")
    with _fleet(model, data, session, replicas=2) as fleet:
        probes = build_requests(data, model, [4, 4])

        def refuse(_model):
            raise RuntimeError("device fell over at promote")

        fleet.replicas[1].scorer.swap_model = refuse
        fleet.rollout(retrained, probe_requests=probes)
        assert fleet.replicas[0].alive
        assert not fleet.replicas[1].alive
        want_new = retrained.score(data)
        np.testing.assert_allclose(
            fleet.score(probes[0]), want_new[np.arange(4)],
            rtol=1e-4, atol=1e-4,
        )
    steps = {
        (m["labels"]["replica"], m["labels"]["phase"])
        for m in session.registry.snapshot()["gauges"]
        if m["name"] == "serving.rollout_step"
    }
    assert ("r1", "died") in steps
    assert _counter_total(session, "serving.rollouts") == 1
    assert _counter_total(
        session, "serving.replica_deaths", replica="r1"
    ) == 1


def test_submit_after_close_sheds_closed_without_phantom_death():
    """A submit racing (or following) shutdown sheds ``closed`` — it must
    not funnel the closing batcher's error into the replica-death path and
    record phantom deaths/reroutes in the run report."""
    model, data = _fixture(seed=73)
    session = TelemetrySession("test-closed-shed")
    fleet = _fleet(model, data, session, replicas=2)
    (req,) = build_requests(data, model, [4])
    fleet.score(req)  # healthy while open
    fleet.close()
    with pytest.raises(RequestShedError) as e:
        fleet.submit(req)
    assert e.value.reason == "closed"
    assert all(r.alive for r in fleet.replicas)
    assert _counter_total(session, "serving.replica_deaths") == 0
    assert _counter_total(session, "serving.rerouted") == 0
    assert _counter_total(session, "serving.shed", reason="closed") == 1


# -- fault-site registry (ISSUE 12 satellite) --------------------------------

def test_new_fault_sites_registered_with_correct_semantics():
    """`serve:replica_kill` / `transport:read` ride the KNOWN_FAULT_SITES
    registry (the scan tests in test_fault_sites.py enforce docs +
    coverage); here their SEMANTICS are pinned: replica_kill is a KILL
    (InjectedKillError, replica-targetable), transport:read a retriable
    IO fault."""
    from photon_tpu.fault.injection import (
        KNOWN_FAULT_SITES,
        InjectedIOError,
        InjectedKillError,
        fault_point,
    )

    assert "serve:replica_kill" in KNOWN_FAULT_SITES
    assert "transport:read" in KNOWN_FAULT_SITES
    set_plan(FaultPlan.parse("serve:replica_kill:times=1"))
    with pytest.raises(InjectedKillError):
        fault_point("serve:replica_kill", replica="rX")
    set_plan(FaultPlan.parse("transport:read:times=1"))
    with pytest.raises(InjectedIOError):
        fault_point("transport:read")
    # Replica targeting: a rule scoped to r1 never fires on r0.
    set_plan(FaultPlan.parse("serve:replica_kill:replica=r1:times=1"))
    fault_point("serve:replica_kill", replica="r0")  # must not raise
    with pytest.raises(InjectedKillError):
        fault_point("serve:replica_kill", replica="r1")
    set_plan(None)


# -- traffic generator -------------------------------------------------------

def test_traffic_generator_is_deterministic():
    model, data = _fixture(seed=49)
    spec = TrafficSpec(requests=50, mean_rows=5, max_rows=16, alpha=1.2,
                       storm_frac=0.1, target_qps=500.0,
                       deadline_ms=20.0, seed=7)
    a = generate_traffic(data, model, spec)
    b = generate_traffic(data, model, spec)
    assert a.duration_s == b.duration_s
    for x, y in zip(a.items, b.items):
        assert x.at_s == y.at_s and x.kind == y.kind
        assert x.deadline_s == y.deadline_s == 0.02
        np.testing.assert_array_equal(
            x.request.entity_ids["re0"], y.request.entity_ids["re0"]
        )
        np.testing.assert_array_equal(
            x.request.features["global"], y.request.features["global"]
        )
    # Arrival times are a non-decreasing schedule over the target span.
    at = [t.at_s for t in a.items]
    assert all(s <= t for s, t in zip(at, at[1:]))
    assert a.duration_s == pytest.approx(50 / 500.0)


def test_powerlaw_popularity_skews_entity_traffic():
    model, data = _fixture(seed=53, n_entities=60)
    traffic = generate_traffic(
        data, model,
        TrafficSpec(requests=300, mean_rows=4, max_rows=16,
                    alpha=1.4, seed=11),
    )
    # Count requests per (single) entity: each powerlaw request samples
    # rows of ONE entity.
    per_entity: dict = {}
    for item in traffic.items:
        keys = np.unique(item.request.entity_ids["re0"])
        assert len(keys) == 1  # one user per request
        per_entity[keys[0]] = per_entity.get(keys[0], 0) + 1
    counts = sorted(per_entity.values(), reverse=True)
    # The hottest entity dominates far beyond the uniform share.
    assert counts[0] >= 3 * (300 / 60)


def test_geometric_traffic_matches_pr9_stream():
    """Bench continuity: ``popularity='geometric'`` reproduces the PR 9
    seeded stream (request_sizes + consecutive row windows) exactly."""
    from photon_tpu.drivers.serve_game import request_sizes

    model, data = _fixture(seed=59)
    traffic = generate_traffic(
        data, model,
        TrafficSpec(requests=20, mean_rows=8, max_rows=32,
                    popularity="geometric", seed=4),
    )
    sizes = request_sizes(20, 8.0, 32, seed=4)
    legacy = build_requests(data, model, sizes)
    assert len(traffic.items) == len(legacy)
    for item, old in zip(traffic.items, legacy):
        np.testing.assert_array_equal(
            item.request.features["global"], old.features["global"]
        )
        np.testing.assert_array_equal(
            item.request.entity_ids["re0"], old.entity_ids["re0"]
        )


# -- report renderer ---------------------------------------------------------

def test_report_renders_serving_fleet_section():
    """ISSUE 12 satellite: the telemetry report grows a "Serving fleet"
    section — per-replica table, shed breakdown, deadline hit rate,
    rollout timeline."""
    from photon_tpu.telemetry.report import render_markdown

    model, data = _fixture(seed=61)
    session = TelemetrySession("test-fleet-report")
    with _fleet(model, data, session, replicas=2) as fleet:
        requests = build_requests(data, model, [4] * 10)
        for req in requests:
            fleet.score(req, deadline_s=30.0)
        with pytest.raises(RequestShedError):
            fleet.submit(requests[0], deadline_s=0.0)
        fleet.rollout(_retrained(model, seed=67), probe_requests=requests[:1])
    report = {
        "driver": "test", "run_id": "x", "status": "ok",
        "metrics": session.registry.snapshot(),
    }
    md = render_markdown(report)
    assert "## Serving fleet" in md
    assert "| r0 |" in md and "| r1 |" in md
    assert "**shed**" in md and "deadline=1" in md
    assert "**deadline hit rate**" in md
    assert "**rollout timeline**" in md
    assert "canary" in md and "promoted" in md
    # A fleet-less report renders no fleet section.
    assert "## Serving fleet" not in render_markdown(
        {"driver": "t", "metrics": {"counters": [], "gauges": [],
                                    "histograms": []}}
    )


# -- driver ------------------------------------------------------------------

def test_serve_game_fleet_driver_end_to_end(tmp_path):
    """serve_game with replicas + tcp transport + powerlaw traffic +
    deadline: summary carries the fleet fields, scores parity-check
    against each request's host oracle, the run report renders the
    Serving fleet section."""
    import json

    from photon_tpu.drivers import serve_game
    from photon_tpu.game.model_io import save_game_model

    model, data = _fixture(seed=71)
    _, imaps = make_game_dataset(40, 4, 6, 4, seed=71)
    save_game_model(str(tmp_path / "model"), model, imaps)
    out = tmp_path / "served"
    summary = serve_game.run(serve_game.build_parser().parse_args([
        "--backend", "cpu",
        "--model", str(tmp_path / "model"),
        "--input", "synthetic-game:40:4:6:4:1:71",
        "--requests", "30",
        "--clients", "3",
        "--replicas", "2",
        "--transport", "tcp",
        "--traffic", "powerlaw",
        "--storm-frac", "0.1",
        "--deadline-ms", "2000",
        "--max-batch", "32",
        "--max-delay-ms", "1",
        "--supervise",
        "--output-dir", str(out),
    ]))
    assert summary["requests"] == 30
    assert summary["replicas"] == 2
    assert summary["replica_backend"] == "thread"
    assert summary["supervised"] is True
    # A healthy supervised run: nothing died, nothing resurrected.
    assert summary["replica_deaths"] == 0
    assert summary["resurrections"] == 0
    assert summary["transport"] == "tcp"
    assert summary["traffic"] == "powerlaw"
    # The summary names the device the policy resolved and where each
    # replica's tables really sit (8 virtual devices dealt over 2 replicas).
    assert summary["device"]["platform"] == "cpu"
    assert summary["device"]["device_count"] == 8
    assert summary["replica_devices"] == {
        "r0": [0, 2, 4, 6], "r1": [1, 3, 5, 7],
    }
    assert summary["compiled_during_traffic"] == 0
    assert summary["served"] + summary["shed"] == 30
    assert summary["served"] > 0
    assert summary["cold_entities"] > 0  # the storm rode the fallback
    scores = np.loadtxt(str(out / "scores.txt"))
    assert len(scores) == summary["rows"]
    with open(out / "telemetry" / "run_report.json") as f:
        report = json.load(f)
    names = {m["name"] for m in report["metrics"]["counters"]}
    assert {"serving.admitted", "serving.replica_requests",
            "serving.transport_connections"} <= names
    from photon_tpu.telemetry.report import render_markdown

    md = render_markdown(report)
    assert "## Serving fleet" in md
    assert "## Online serving" in md
