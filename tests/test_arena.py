"""Multi-model serving arena (photon_tpu/serving/arena, ISSUE 18): N
tenant models in ONE gather-table allocation behind ONE compiled bucket
ladder, model-id request routing, traffic splits, and per-tenant
admission isolation.

The contracts pinned here:

- the compiled-program count is independent of model count (model
  identity is a per-request offset vector, never a program key), and a
  mixed-tenant micro-batch scores in one dispatch with per-row parity
  against each tenant's host oracle;
- arena bytes stay within 1.15x the sum of the tenants' solo
  single-model tables (shared allocation, not duplication);
- onboard/retire/refresh under live traffic are slice publications:
  zero dropped requests, zero recompiles while reserve capacity lasts,
  a ``layout_version`` bump only when the arena actually grows;
- a dtype-mismatched slice publish is refused (the storage decode is
  baked into the shared ladder);
- requests route by ``ScoringRequest.model`` end to end: wire
  roundtrip (scalar and per-row), coalescing (all-same scalars stay
  scalar, mixes widen to per-row arrays), slicing;
- seeded traffic splits are deterministic hash-of-user assignments, and
  the split arm rides ``TimedRequest.arm`` / ``request.model``;
- per-tenant admission budgets isolate a storming tenant: the victim
  tenant's shed rate and tail stay at its solo baseline (ISSUE 18
  satellite);
- subprocess children host the same multi-model arena from per-tenant
  artifacts, swap one tenant's slice over the wire, and their span
  timestamps are de-skewed by the ping-measured clock offset (ISSUE 18
  satellite).
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from photon_tpu.data.synthetic import make_game_dataset
from photon_tpu.game.model import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_tpu.models.glm import Coefficients, model_for_task
from photon_tpu.serving import (
    AdmissionPolicy,
    RequestShedError,
    ScoringRequest,
    ServingFleet,
    TrafficSpec,
    build_requests,
    generate_traffic,
    host_score_request,
    request_spec_for_dataset,
    run_closed_loop_outcomes,
)
from photon_tpu.serving.arena import MultiModelScorer
from photon_tpu.serving.scorer import (
    GameScorer,
    concat_requests,
    slice_request,
)
from photon_tpu.serving.traffic import split_arm_for
from photon_tpu.serving.transport import pack_request, unpack_request
from photon_tpu.telemetry import TelemetrySession


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


def _retabled(model: GameModel, seed: int) -> GameModel:
    """Same coordinate structure/vocabulary, freshly seeded tables — a
    distinct tenant the arena hosts next to ``model``."""
    rng = np.random.default_rng(seed)
    fixed = model.coordinates["fixed"]
    per_entity = model.coordinates["per_entity"]
    dim = np.asarray(fixed.coefficients.means).shape[0]
    return GameModel(
        coordinates={
            "fixed": FixedEffectModel(
                model_for_task(model.task_type, Coefficients(
                    rng.standard_normal(dim).astype(np.float32)
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


def _tenants(model: GameModel, n: int) -> dict:
    return {
        f"m{i}": (model if i == 0 else _retabled(model, seed=100 + i))
        for i in range(n)
    }


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


def _compile_listener():
    import jax.monitoring

    events = []

    def listener(event, **kwargs):
        if "compile" in event:
            events.append(event)

    def attach():
        jax.monitoring.register_event_listener(listener)

    def detach():
        jax.monitoring.unregister_event_listener(listener)

    return events, attach, detach


# -- arena scorer: shared ladder + parity ------------------------------------

def test_eight_models_one_ladder_mixed_parity():
    """ISSUE 18 acceptance: 8 tenants share one compiled ladder (program
    count == a solo scorer's), every tenant scores at its own host
    oracle, a coalesced mixed-tenant batch resolves per row, and the
    whole mixed serve triggers ZERO post-warmup compilations."""
    model, data = _fixture(seed=3)
    models = _tenants(model, 8)
    spec = request_spec_for_dataset(model, data)
    solo = GameScorer(model, request_spec=spec, max_batch=16).warmup()
    scorer = MultiModelScorer(
        models, request_spec=spec, max_batch=16
    ).warmup()
    assert scorer.compilations == solo.compilations
    events, attach, detach = _compile_listener()
    import dataclasses as dc

    reqs = build_requests(data, model, [1, 5, 16, 8])
    attach()
    try:
        for mid, m in models.items():
            for req in reqs:
                got = scorer.score_batch(dc.replace(req, model=mid))
                np.testing.assert_allclose(
                    got, host_score_request(m, req), rtol=1e-4, atol=1e-4
                )
        # A coalesced mixed-tenant batch: per-row ids, one dispatch.
        mixed_ids = np.asarray(
            [f"m{i % 8}" for i in range(reqs[2].num_rows)], dtype=object
        )
        got = scorer.score_batch(dc.replace(reqs[2], model=mixed_ids))
        for mid in set(mixed_ids):
            rows = mixed_ids == mid
            np.testing.assert_allclose(
                got[rows],
                host_score_request(models[mid], reqs[2])[rows],
                rtol=1e-4, atol=1e-4,
            )
        # No model id → the default tenant.
        np.testing.assert_allclose(
            scorer.score_batch(reqs[0]),
            host_score_request(models["m0"], reqs[0]),
            rtol=1e-4, atol=1e-4,
        )
    finally:
        detach()
    assert events == []


def test_arena_bytes_bounded_by_solo_sum():
    model, data = _fixture(seed=5)
    models = _tenants(model, 8)
    spec = request_spec_for_dataset(model, data)
    import jax

    solo = GameScorer(model, request_spec=spec, max_batch=16).warmup()
    solo_bytes = 0
    for m in models.values():
        solo.swap_model(m)
        solo_bytes += sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(solo._tables)
        )
    scorer = MultiModelScorer(models, request_spec=spec, max_batch=16)
    assert scorer.arena.arena_bytes() <= 1.15 * solo_bytes


def test_unhosted_model_refused():
    model, data = _fixture(seed=7)
    scorer = MultiModelScorer(
        _tenants(model, 2),
        request_spec=request_spec_for_dataset(model, data), max_batch=16,
    ).warmup()
    (req,) = build_requests(data, model, [4])
    import dataclasses as dc

    with pytest.raises(KeyError, match="ghost"):
        scorer.score_batch(dc.replace(req, model="ghost"))
    # Per-row arrays routing to an unhosted id refuse too.
    ids = np.asarray(["m0", "ghost", "m1", "m0"], dtype=object)
    with pytest.raises(KeyError, match="ghost"):
        scorer.score_batch(dc.replace(req, model=ids))


# -- model lifecycle under live state ----------------------------------------

def test_onboard_retire_refresh_without_recompiles():
    """Reserve-rows headroom makes onboard/retire/refresh pure slice
    publications: zero compile events, ``layout_version`` unchanged; the
    retired tenant's id is refused afterwards."""
    model, data = _fixture(seed=9)
    models = _tenants(model, 3)
    spec = request_spec_for_dataset(model, data)
    scorer = MultiModelScorer(
        models, request_spec=spec, max_batch=16, reserve_rows=256,
    ).warmup()
    import dataclasses as dc

    (req,) = build_requests(data, model, [6])
    # Warm the slice-scatter program shapes once (a publish compiles its
    # scatter on first use; after that every same-shaped publish reuses
    # it — the contract under test).
    scorer.swap_model(models["m1"], model_id="m1")
    version0 = scorer.arena.layout_version
    events, attach, detach = _compile_listener()
    newcomer = _retabled(model, seed=201)
    refreshed = _retabled(model, seed=202)
    attach()
    try:
        scorer.add_model("m9", newcomer)
        np.testing.assert_allclose(
            scorer.score_batch(dc.replace(req, model="m9")),
            host_score_request(newcomer, req), rtol=1e-4, atol=1e-4,
        )
        scorer.swap_model(refreshed, model_id="m2")
        np.testing.assert_allclose(
            scorer.score_batch(dc.replace(req, model="m2")),
            host_score_request(refreshed, req), rtol=1e-4, atol=1e-4,
        )
        scorer.retire_model("m9")
        with pytest.raises(KeyError, match="m9"):
            scorer.score_batch(dc.replace(req, model="m9"))
        # Untouched tenants still serve their own tables.
        np.testing.assert_allclose(
            scorer.score_batch(dc.replace(req, model="m0")),
            host_score_request(models["m0"], req), rtol=1e-4, atol=1e-4,
        )
    finally:
        detach()
    assert events == []
    assert scorer.arena.layout_version == version0


def test_arena_growth_bumps_layout_and_keeps_parity():
    """Onboarding past free capacity grows the arena (amortized
    doubling): ``layout_version`` bumps, every hosted tenant still
    scores at its oracle afterwards."""
    model, data = _fixture(seed=11)
    models = _tenants(model, 2)
    spec = request_spec_for_dataset(model, data)
    scorer = MultiModelScorer(
        models, request_spec=spec, max_batch=16, reserve_rows=0,
    ).warmup()
    version0 = scorer.arena.layout_version
    added = {}
    for i in range(4):
        added[f"g{i}"] = _retabled(model, seed=300 + i)
        scorer.add_model(f"g{i}", added[f"g{i}"])
    assert scorer.arena.layout_version > version0
    import dataclasses as dc

    (req,) = build_requests(data, model, [8])
    for mid, m in {**models, **added}.items():
        np.testing.assert_allclose(
            scorer.score_batch(dc.replace(req, model=mid)),
            host_score_request(m, req), rtol=1e-4, atol=1e-4,
        )


def test_retire_last_model_refused():
    model, data = _fixture(seed=13)
    scorer = MultiModelScorer(
        {"only": model},
        request_spec=request_spec_for_dataset(model, data), max_batch=16,
    )
    with pytest.raises(ValueError, match="last hosted"):
        scorer.retire_model("only")


def test_dtype_mismatched_slice_publish_refused():
    """The storage decode is baked into the shared ladder: one tenant
    cannot publish a slice at a different table dtype."""
    model, data = _fixture(seed=15)
    scorer = MultiModelScorer(
        _tenants(model, 2),
        request_spec=request_spec_for_dataset(model, data),
        max_batch=16, table_dtype="bf16",
    )
    with pytest.raises(ValueError, match="bf16"):
        scorer.swap_model(
            _retabled(model, seed=401), model_id="m1", table_dtype="f32"
        )
    # Matching dtype (or unspecified) publishes fine.
    scorer.swap_model(
        _retabled(model, seed=402), model_id="m1", table_dtype="bf16"
    )


# -- request routing: wire, coalescing, slicing ------------------------------

def test_model_routing_survives_wire_and_coalescing():
    model, data = _fixture(seed=17)
    reqs = build_requests(data, model, [3, 2, 4])
    import dataclasses as dc

    a = dc.replace(reqs[0], model="tenant-a")
    b = dc.replace(reqs[1], model="tenant-b")
    c = reqs[2]  # unrouted

    # Wire: a scalar id rides the header; a per-row array rides as data.
    got, _ = unpack_request(pack_request(a))
    assert got.model == "tenant-a"
    per_row = dc.replace(
        reqs[2], model=np.asarray(["x", "y", "x", "y"], dtype=object)
    )
    got, _ = unpack_request(pack_request(per_row))
    np.testing.assert_array_equal(
        np.asarray(got.model, dtype=object),
        np.asarray(per_row.model, dtype=object),
    )
    got, _ = unpack_request(pack_request(c))
    assert got.model is None

    # Coalescing: all-same scalars stay scalar; a mix (including
    # unrouted rows) widens to a per-row object array.
    same = concat_requests([a, dc.replace(reqs[1], model="tenant-a")])
    assert same.model == "tenant-a"
    mixed = concat_requests([a, b, c])
    assert not isinstance(mixed.model, str)
    np.testing.assert_array_equal(
        np.asarray(mixed.model, dtype=object),
        np.asarray(
            ["tenant-a"] * 3 + ["tenant-b"] * 2 + [None] * 4, dtype=object
        ),
    )
    # Slicing a coalesced batch keeps each row's id.
    window = slice_request(mixed, 2, 6)
    np.testing.assert_array_equal(
        np.asarray(window.model, dtype=object),
        np.asarray(["tenant-a", "tenant-b", "tenant-b", None],
                   dtype=object),
    )
    assert slice_request(a, 0, 2).model == "tenant-a"


# -- traffic splits ----------------------------------------------------------

def test_split_arms_deterministic_and_weighted():
    splits = {"control": 0.5, "treat": 0.5}
    arms = [split_arm_for(7, user, splits) for user in range(2000)]
    # Deterministic: the same (seed, user) always lands the same arm.
    assert arms == [split_arm_for(7, user, splits) for user in range(2000)]
    # A different seed reshuffles the assignment.
    assert arms != [split_arm_for(8, user, splits) for user in range(2000)]
    frac = arms.count("treat") / len(arms)
    assert 0.44 < frac < 0.56
    # Weights steer the allocation.
    skew = [
        split_arm_for(7, user, {"a": 0.9, "b": 0.1})
        for user in range(2000)
    ]
    assert skew.count("a") > 1600


def test_generated_traffic_stamps_split_arms():
    model, data = _fixture(seed=19)
    spec = TrafficSpec(
        requests=60, mean_rows=4, max_rows=16, popularity="powerlaw",
        seed=5, splits={"m0": 0.5, "m1": 0.5},
    )
    t1 = generate_traffic(data, model, spec)
    t2 = generate_traffic(data, model, spec)
    arms1 = [item.arm for item in t1.items]
    assert arms1 == [item.arm for item in t2.items]
    assert set(arms1) == {"m0", "m1"}
    for item in t1.items:
        assert item.request.model == item.arm
    # Splits leave the request stream itself untouched (PR 9 seeded
    # byte-exactness): same spec without splits, same rows per request.
    plain = generate_traffic(
        data, model,
        TrafficSpec(requests=60, mean_rows=4, max_rows=16,
                    popularity="powerlaw", seed=5),
    )
    assert [i.request.num_rows for i in t1.items] == [
        i.request.num_rows for i in plain.items
    ]


# -- fleet: mixed traffic, lifecycle under load, isolation -------------------

def _multi_fleet(models, data, session, replicas=1, **kwargs):
    first = next(iter(models.values()))
    return ServingFleet(
        None, models=models, replicas=replicas,
        request_spec=request_spec_for_dataset(first, data),
        max_batch=16, max_delay_s=0.001, telemetry=session, **kwargs,
    ).warmup()


def test_fleet_serves_mixed_split_traffic_with_onboard_mid_stream():
    """ISSUE 18 acceptance: a fleet hosting N tenants serves mixed
    split-arm traffic; onboarding a new tenant mid-traffic drops ZERO
    requests, and the newcomer serves immediately after."""
    model, data = _fixture(seed=21)
    models = _tenants(model, 4)
    session = TelemetrySession("test-arena-fleet")
    fleet = _multi_fleet(models, data, session, replicas=2,
                         reserve_rows=256)
    try:
        traffic = generate_traffic(data, model, TrafficSpec(
            requests=80, mean_rows=4, max_rows=16, popularity="powerlaw",
            seed=2, splits={mid: 0.25 for mid in models},
        ))
        newcomer = _retabled(model, seed=500)
        onboarded = threading.Event()

        def onboard_mid_stream():
            time.sleep(0.01)
            fleet.add_model("late", newcomer)
            onboarded.set()

        t = threading.Thread(target=onboard_mid_stream)
        t.start()
        outcomes, _ = run_closed_loop_outcomes(
            lambda tid: (lambda item: fleet.score(item.request)),
            traffic.items, clients=4,
        )
        t.join(timeout=30)
        assert onboarded.is_set()
        assert all(o.status == "ok" for o in outcomes)
        for out in outcomes:
            np.testing.assert_allclose(
                out.scores,
                host_score_request(models[out.item.arm],
                                   out.item.request),
                rtol=1e-4, atol=1e-4,
            )
        (req,) = build_requests(data, model, [5])
        np.testing.assert_allclose(
            fleet.score(req, model="late"),
            host_score_request(newcomer, req), rtol=1e-4, atol=1e-4,
        )
        fleet.retire_model("late")
        assert "late" not in fleet.models
    finally:
        fleet.close()


def test_per_tenant_rollout_swaps_one_slice():
    """fleet.rollout(model_id=...) canaries ONE tenant's slice: the
    target serves the new tables afterwards, other tenants are
    untouched, and nothing recompiles."""
    model, data = _fixture(seed=25)
    models = _tenants(model, 3)
    session = TelemetrySession("test-arena-rollout")
    fleet = _multi_fleet(models, data, session, replicas=2,
                         reserve_rows=256)
    try:
        reqs = build_requests(data, model, [4, 4])
        # Warm the publish path's scatter shapes before listening.
        fleet.rollout(_retabled(model, seed=601), model_id="m1",
                      probe_requests=reqs)
        events, attach, detach = _compile_listener()
        new_m1 = _retabled(model, seed=602)
        attach()
        try:
            fleet.rollout(new_m1, model_id="m1", probe_requests=reqs)
        finally:
            detach()
        assert events == []
        (req,) = build_requests(data, model, [6])
        np.testing.assert_allclose(
            fleet.score(req, model="m1"),
            host_score_request(new_m1, req), rtol=1e-4, atol=1e-4,
        )
        np.testing.assert_allclose(
            fleet.score(req, model="m0"),
            host_score_request(models["m0"], req), rtol=1e-4, atol=1e-4,
        )
        assert fleet.models["m1"] is new_m1
    finally:
        fleet.close()


def test_tenant_budget_isolates_storm():
    """ISSUE 18 satellite: tenant A's storm burns A's OWN admission
    budget (shed ``tenant_budget``); tenant B replaying steady traffic
    keeps a ZERO shed rate — its solo baseline — and a bounded tail."""
    model, data = _fixture(seed=27)
    models = {"a": model, "b": _retabled(model, seed=701)}
    session = TelemetrySession("test-tenant-budget")
    fleet = _multi_fleet(
        models, data, session, replicas=1,
        admission=AdmissionPolicy(tenant_queue_rows=32),
    )
    try:
        b_requests = build_requests(data, model, [4] * 30)
        want_b = [host_score_request(models["b"], r) for r in b_requests]

        def replay_b():
            lat = []
            for req, want in zip(b_requests, want_b):
                t0 = time.monotonic()
                got = fleet.score(req, model="b")
                lat.append(time.monotonic() - t0)
                np.testing.assert_allclose(got, want, rtol=1e-4,
                                           atol=1e-4)
            return float(np.percentile(lat, 99))

        p99_solo = replay_b()

        a_requests = build_requests(data, model, [8] * 300)
        a_state = {"shed": 0, "futs": []}

        def storm_a():
            for req in a_requests:
                try:
                    a_state["futs"].append(fleet.submit(req, model="a"))
                except RequestShedError as e:
                    assert e.reason == "tenant_budget"
                    a_state["shed"] += 1

        storm = threading.Thread(target=storm_a)
        storm.start()
        p99_storm = replay_b()  # B's shed rate stays 0: every score ok
        storm.join(timeout=60)
        for fut in a_state["futs"]:
            fut.result(timeout=60)
        assert a_state["shed"] > 0
        # The storm burned the TENANT gate, not the global queue.
        assert _counter_total(
            session, "serving.shed", reason="tenant_budget"
        ) == a_state["shed"]
        assert _counter_total(
            session, "serving.shed", reason="queue_full"
        ) == 0
        # B's tail under the storm stays within its solo baseline's
        # envelope (the budget caps how many of A's rows can queue
        # ahead of B; generous floor absorbs 1-core scheduler noise).
        assert p99_storm <= max(8 * p99_solo, 1.0)
    finally:
        fleet.close()


# -- subprocess children: per-tenant artifacts + clock de-skew ---------------

def test_subprocess_multimodel_swap_and_clock_offset():
    """Subprocess children boot the SAME arena from per-tenant
    artifacts: per-tenant parity over the wire, a one-tenant slice swap
    via the control frame, and the child's ping-measured clock offset
    lands on the replica (span de-skew input, ISSUE 18 satellite)."""
    model, data = _fixture(seed=31)
    models = {"a": model, "b": _retabled(model, seed=801)}
    session = TelemetrySession("test-arena-subprocess")
    fleet = _multi_fleet(models, data, session, replicas=1,
                         backend="subprocess", reserve_rows=256)
    try:
        reqs = build_requests(data, model, [3, 8])
        for mid, m in models.items():
            for req in reqs:
                np.testing.assert_allclose(
                    fleet.score(req, model=mid),
                    host_score_request(m, req), rtol=1e-4, atol=1e-4,
                )
        r0 = fleet.replicas[0]
        pong = r0.ping(30.0)
        assert pong["kind"] == "pong"
        # Loopback, same host clock: the EWMA offset is measured and
        # small (it exists to de-skew cross-machine span timestamps).
        assert abs(r0.scorer.clock_offset_s) < 0.5
        new_b = _retabled(model, seed=802)
        fleet.rollout(new_b, model_id="b", probe_requests=reqs)
        np.testing.assert_allclose(
            fleet.score(reqs[0], model="b"),
            host_score_request(new_b, reqs[0]), rtol=1e-4, atol=1e-4,
        )
        np.testing.assert_allclose(
            fleet.score(reqs[0], model="a"),
            host_score_request(model, reqs[0]), rtol=1e-4, atol=1e-4,
        )
    finally:
        fleet.close()


def test_shift_span_times_de_skews_child_spans():
    from photon_tpu.telemetry.distributed import shift_span_times

    spans = [
        {"name": "score", "start": 100.5, "duration_s": 0.25,
         "events": [{"t": 100.6, "msg": "batch"}]},
        {"name": "noise", "events": None},
    ]
    out = shift_span_times(spans, 2.0)
    assert out[0]["start"] == pytest.approx(98.5)
    assert out[0]["events"][0]["t"] == pytest.approx(98.6)
    assert out[0]["duration_s"] == 0.25  # durations are monotonic-local
    # Zero offset is the identity (no copy, no mutation needed).
    again = [{"start": 5.0, "events": [{"t": 5.5}]}]
    assert shift_span_times(again, 0.0)[0]["start"] == 5.0
