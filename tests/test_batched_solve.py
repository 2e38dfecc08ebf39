"""Size-binned batched Cholesky/Newton random-effect solves (ISSUE 8).

Parity strategy (see game/batched_solve.py + README "Batched entity
solver"): the f32 objective's value-criterion stall basin is ~1e-4 wide, so
two DIFFERENT f32 solvers run independently cannot agree to 1e-5 — what is
pinned at ≤1e-5 is (a) the batched restructuring itself (size-binned block
vs per-capacity bucket loop under the SAME solver — means AND variances),
and (b) the batched Newton path against an f64 ground-truth optimum (it
polishes past the value stall, landing ~1e-7 from the true optimum — closer
than the seed's L-BFGS ever got).  Cross-solver agreement with the seed's
vmapped iterative path is pinned at the f32 floor (≤5e-3, the tolerance the
suite always used for cross-solver comparisons).
"""

import contextlib
import functools
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.core.objective import RegularizationContext
from photon_tpu.core.optimizers import OptimizerConfig
from photon_tpu.core.optimizers.newton import (
    _spd_solve_xla,
    factorization_kind,
    spd_solve,
)
from photon_tpu.core.problem import ProblemConfig
from photon_tpu.data.synthetic import make_game_data
from photon_tpu.game.batched_solve import (
    bin_layout,
    cached_newton_solver,
    solver_route,
)
from photon_tpu.game.coordinate import (
    RandomEffectCoordinate,
    RandomEffectCoordinateConfig,
    RandomEffectDeviceData,
    _accumulate_solve_stats,
)
from photon_tpu.game.data import (
    DenseShard,
    GameDataset,
    build_random_effect_dataset,
    merge_buckets,
    plan_size_bins,
)
from photon_tpu.telemetry import TelemetrySession


def _dataset(n_entities=50, rows_mean=6, dim=4, seed=3):
    raw = make_game_data(
        n_entities=n_entities, rows_per_entity_mean=rows_mean,
        fixed_dim=5, random_dim=dim, seed=seed,
    )
    return GameDataset.create(
        label=raw["label"],
        shards={"per_entity": DenseShard(raw["x_random"]["re0"])},
        id_columns={"userId": raw["entity_ids"]["re0"]},
    )


def _problem(optimizer="lbfgs", reg=("l2", 1.0), variance="none",
             max_iterations=100):
    return ProblemConfig(
        optimizer=optimizer,
        regularization=RegularizationContext(*reg),
        optimizer_config=OptimizerConfig(
            max_iterations=max_iterations, tolerance=0.0,
            gradient_tolerance=1e-8,
        ),
        variance_computation=variance,
    )


def _config(problem=None, **kw):
    return RandomEffectCoordinateConfig(
        shard_name="per_entity", entity_column="userId",
        problem=problem or _problem(), **kw,
    )


@contextlib.contextmanager
def _solve_env(binning: str, newton: str):
    saved = {
        k: os.environ.get(k)
        for k in ("PHOTON_SOLVE_BINNING", "PHOTON_SOLVE_NEWTON")
    }
    os.environ["PHOTON_SOLVE_BINNING"] = binning
    os.environ["PHOTON_SOLVE_NEWTON"] = newton
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _train(data, config, task="logistic_regression", binning="on",
           newton="on", mesh=None, initial_model=None, telemetry=None):
    with _solve_env(binning, newton):
        coord = RandomEffectCoordinate(data, config, task, mesh=mesh)
        if telemetry is not None:
            coord.telemetry = telemetry
        model, stats = coord.train(
            np.zeros(data.num_examples, np.float32),
            initial_model=initial_model,
        )
    return coord, model, stats


# ---------------------------------------------------------------------------
# Bin policy
# ---------------------------------------------------------------------------


def _fake_buckets(caps_and_counts):
    return [
        types.SimpleNamespace(row_capacity=c, num_entities=n)
        for c, n in caps_and_counts
    ]


def test_plan_size_bins_respects_max_bins_and_waste():
    buckets = _fake_buckets(
        [(1, 1000), (2, 800), (4, 500), (8, 200), (16, 50), (32, 10)]
    )
    groups = plan_size_bins(buckets, max_bins=3, waste_cap=2.0)
    assert len(groups) <= 3
    # Every bucket appears exactly once, groups ascend in capacity.
    flat = [i for g in groups for i in g]
    assert sorted(flat) == list(range(6))
    assert [max(g) for g in groups] == sorted(max(g) for g in groups)
    # Deterministic.
    assert groups == plan_size_bins(buckets, max_bins=3, waste_cap=2.0)


def test_plan_size_bins_waste_cap_limits_greedy_merge():
    # A huge cap-1 cohort must NOT be padded 32x into the cap-32 bin when
    # the waste budget says no.
    buckets = _fake_buckets([(1, 100_000), (32, 10)])
    groups = plan_size_bins(buckets, max_bins=4, waste_cap=2.0)
    assert groups == [[0], [1]]
    # With max_bins=1 the merge is forced regardless of waste.
    assert plan_size_bins(buckets, max_bins=1, waste_cap=2.0) == [[0, 1]]


def test_merge_buckets_preserves_rows_and_weights():
    data = _dataset()
    ds = build_random_effect_dataset(data, "userId", "per_entity")
    merged = merge_buckets(list(ds.buckets))
    assert merged.row_capacity == max(b.row_capacity for b in ds.buckets)
    assert merged.num_entities == sum(b.num_entities for b in ds.buckets)
    # Same live rows, same total weight mass, per entity.
    mask = merged.row_weight > 0
    seen = np.sort(merged.row_index[mask])
    assert seen.tolist() == sorted(
        np.concatenate([
            b.row_index[b.row_weight > 0] for b in ds.buckets
        ]).tolist()
    )
    np.testing.assert_allclose(
        np.sort(merged.row_weight.sum(axis=1)),
        np.sort(np.concatenate([b.row_weight.sum(axis=1) for b in ds.buckets])),
        rtol=1e-6,
    )


def test_bin_layout_off_is_one_bucket_per_bin():
    data = _dataset()
    ds = build_random_effect_dataset(data, "userId", "per_entity")
    with _solve_env("off", "off"):
        assert bin_layout(ds.buckets) == [[i] for i in range(len(ds.buckets))]
    with _solve_env("on", "on"):
        assert len(bin_layout(ds.buckets)) <= 4


def test_solver_route_selection():
    smooth = _problem()
    assert solver_route(smooth, 8) == "newton"
    assert solver_route(smooth, 8, row_split=True) == "row_split"
    assert solver_route(smooth, 10_000) == "vmapped"  # over the dim cap
    l1 = _problem(optimizer="owlqn", reg=("l1", 0.5))
    assert solver_route(l1, 8) == "vmapped"
    with _solve_env("on", "off"):
        assert solver_route(smooth, 8) == "vmapped"


# ---------------------------------------------------------------------------
# Solver parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("task", [
    "logistic_regression", "linear_regression", "poisson_regression",
])
@pytest.mark.parametrize("optimizer", ["lbfgs", "tron"])
def test_batched_parity_across_tasks(task, optimizer):
    data = _dataset()
    config = _config(_problem(optimizer=optimizer))
    _, batched, stats = _train(data, config, task)
    _, loop_newton, _ = _train(data, config, task, binning="off")
    _, loop_seed, _ = _train(data, config, task, binning="off", newton="off")
    b, ln, ls = (np.asarray(m.table) for m in (batched, loop_newton, loop_seed))
    # The batched restructuring is exact: same solver, ≤1e-5.
    np.testing.assert_allclose(b, ln, atol=1e-5, rtol=0)
    # Cross-solver agreement with the seed's iterative path: f32 floor.
    np.testing.assert_allclose(b, ls, atol=5e-3, rtol=0)
    assert stats["entities"] == 50 and stats["quarantined"] == 0


def test_newton_matches_f64_ground_truth():
    """The batched path's accuracy claim: within 1e-5 of the TRUE optimum
    (f64 numpy Newton run to 1e-14), past the f32 value-stall basin the
    seed's L-BFGS parks in."""
    data = _dataset()
    raw_x = data.shards["per_entity"].x.astype(np.float64)
    ids = data.id_columns["userId"]
    _, model, _ = _train(data, _config(), "logistic_regression")
    table = np.asarray(model.table)
    d = raw_x.shape[1]
    for e in range(model.num_entities):
        rows = ids == model.keys[e]
        xe = raw_x[rows]
        ye = data.label[rows].astype(np.float64)
        w = np.zeros(d)
        for _ in range(200):
            p = 1.0 / (1.0 + np.exp(-(xe @ w)))
            g = xe.T @ (p - ye) + w
            h = (xe * (p * (1 - p))[:, None]).T @ xe + np.eye(d)
            step = np.linalg.solve(h, -g)
            w += step
            if np.abs(step).max() < 1e-14:
                break
        np.testing.assert_allclose(table[e], w, atol=1e-5, rtol=0)


@pytest.mark.parametrize("variance", ["simple", "full"])
def test_variance_parity(variance):
    data = _dataset()
    config = _config(_problem(variance=variance))
    _, batched, _ = _train(data, config)
    _, loop, _ = _train(data, config, binning="off")
    assert batched.variances is not None
    np.testing.assert_allclose(
        np.asarray(batched.table), np.asarray(loop.table), atol=1e-5, rtol=0
    )
    np.testing.assert_allclose(
        np.asarray(batched.variances), np.asarray(loop.variances),
        atol=1e-5, rtol=0,
    )


@pytest.mark.parametrize("projection,kw", [
    ("index_map", {}),
    ("random", {"projected_dim": 3}),
])
def test_projection_parity(projection, kw):
    data = _dataset(dim=6)
    config = _config(projection=projection, **kw)
    _, batched, _ = _train(data, config)
    _, loop, _ = _train(data, config, binning="off")
    np.testing.assert_allclose(
        np.asarray(batched.table), np.asarray(loop.table), atol=1e-5, rtol=0
    )


def test_l1_bin_routes_through_vmapped_and_solves():
    data = _dataset()
    config = _config(_problem(optimizer="owlqn", reg=("l1", 0.3)))
    coord, batched, stats = _train(data, config)
    assert set(coord._bin_routes()) == {"vmapped"}
    assert stats["entities"] == 50
    # Same (OWL-QN) solver both sides; only the batched restructuring
    # differs.  L1 solutions are sparse: the zero pattern must survive.
    _, loop, _ = _train(data, config, binning="off")
    np.testing.assert_allclose(
        np.asarray(batched.table), np.asarray(loop.table), atol=1e-4, rtol=0
    )
    assert (np.asarray(batched.table) == 0.0).any()


def test_row_split_composes_with_binning():
    from photon_tpu.parallel.mesh import create_mesh

    data = _dataset(n_entities=24, rows_mean=8)
    config = _config(row_split=True)
    mesh = create_mesh()
    coord, batched, _ = _train(data, config, mesh=mesh)
    assert set(coord._bin_routes()) == {"row_split"}
    assert len(coord.device_data.buckets) <= 4
    _, loop, _ = _train(data, config, mesh=mesh, binning="off")
    # Row-split solves psum per-entity data terms across the mesh; bin
    # merging changes the padded-row layout and with it the psum reduction
    # order, which the iterative trajectory amplifies — same tolerance
    # class as tests/test_row_split.py's colocated-vs-split comparison.
    np.testing.assert_allclose(
        np.asarray(batched.table), np.asarray(loop.table), atol=2e-3, rtol=2e-2
    )


def test_warm_start_parity_and_join_cache():
    data = _dataset()
    config = _config()
    session = TelemetrySession("t-warm")
    _, first, _ = _train(data, config)
    # FOREIGN vocabulary warm start (fresh keys array -> host key join).
    from photon_tpu.game.model import RandomEffectModel
    import dataclasses

    # Shift the vocabulary so only part of it overlaps: a genuinely FOREIGN
    # warm start (a value-equal copy would pass keys_match and skip the
    # join entirely).
    foreign = dataclasses.replace(first, keys=first.keys + 6)
    assert isinstance(foreign, RandomEffectModel)
    with _solve_env("on", "on"):
        coord = RandomEffectCoordinate(data, config, "logistic_regression")
        coord.telemetry = session
        coord.train(np.zeros(data.num_examples, np.float32),
                    initial_model=foreign)
        assert len(coord.device_data._warm_join_cache) == 1
        cached = next(iter(coord.device_data._warm_join_cache.values()))
        assert cached[0] is foreign.keys
        # Second warm start with the SAME keys object: cache hit, no growth.
        coord.train(np.zeros(data.num_examples, np.float32),
                    initial_model=foreign)
        assert len(coord.device_data._warm_join_cache) == 1
    joins = [
        c for c in session.registry.snapshot()["counters"]
        if c["name"] == "descent.host_transfer_bytes"
        and c["labels"].get("path") == "warm_start"
    ]
    assert joins and all(c["value"] > 0 for c in joins)


# ---------------------------------------------------------------------------
# Quarantine + stats accounting
# ---------------------------------------------------------------------------


def test_nan_quarantine_stays_per_entity_in_batched_solve():
    from photon_tpu.fault.injection import FaultPlan, set_plan

    data = _dataset()
    config = _config()
    with _solve_env("on", "on"):
        coord = RandomEffectCoordinate(data, config, "logistic_regression")
        coord.fault_name = "re0"
        set_plan(FaultPlan.parse("solve:nan:coord=re0"))
        try:
            model, stats = coord.train(
                np.zeros(data.num_examples, np.float32)
            )
        finally:
            set_plan(None)
    table = np.asarray(model.table)
    assert np.isfinite(table).all()
    assert stats["quarantined"] == 1
    # The poisoned entity cold-starts at zero; its bin-mates are solved.
    poisoned = int(coord.device_data.device_buckets[0]["entity_index"][0])
    assert np.all(table[poisoned] == 0.0)
    assert np.abs(table).sum() > 0
    # A quarantined entity is NOT counted converged (the accumulator fix).
    assert stats["converged"] <= stats["entities"] - 1


def test_accumulate_stats_masks_padded_and_quarantined():
    import jax.numpy as jnp

    acc = jnp.zeros(6, jnp.int32)
    # 3 real entities + 2 bin-padding slots (index == num_entities == 3).
    entity_index = jnp.asarray([0, 1, 2, 3, 3])
    converged = jnp.asarray([True, True, False, True, True])
    iterations = jnp.asarray([2, 5, 9, 99, 99])
    good = jnp.asarray([True, False, True, True, True])
    out = np.asarray(
        _accumulate_solve_stats(acc, entity_index, 3, converged, iterations, good)
    )
    # entities: only real; converged: real AND good AND converged;
    # iterations_max: padded slots' 99 masked out; quarantined: real ~good;
    # cg_iters/cg_entities: no per-entity CG counts supplied -> 0.
    assert out.tolist() == [3, 1, 9, 1, 0, 0]
    # Newton-CG bins supply per-entity inner-iteration totals: summed over
    # REAL entities only (padded slots' counts masked out), and the same
    # bins' real entities land in cg_entities (the per-entity-mean
    # denominator for mixed-route coordinates).
    cg = jnp.asarray([7, 11, 2, 50, 50])
    out = np.asarray(
        _accumulate_solve_stats(
            acc, entity_index, 3, converged, iterations, good,
            cg_iterations=cg,
        )
    )
    assert out.tolist() == [3, 1, 9, 1, 20, 3]


# ---------------------------------------------------------------------------
# Incremental entity onboarding
# ---------------------------------------------------------------------------


def _grown_datasets(seed=11):
    """(base, grown): ``grown`` appends rows for 12 NEW entities (keys
    offset past the base vocabulary) to the base dataset."""
    base = _dataset(n_entities=30, seed=seed)
    extra_raw = make_game_data(
        n_entities=12, rows_per_entity_mean=5, fixed_dim=5, random_dim=4,
        seed=seed + 1,
    )
    new_ids = extra_raw["entity_ids"]["re0"] + 10_000
    grown = GameDataset.create(
        label=np.concatenate([base.label, extra_raw["label"]]),
        shards={
            "per_entity": DenseShard(np.concatenate([
                base.shards["per_entity"].x,
                extra_raw["x_random"]["re0"],
            ])),
        },
        id_columns={
            "userId": np.concatenate([base.id_columns["userId"], new_ids]),
        },
    )
    return base, grown


def test_onboarding_matches_full_rebuild():
    base, grown = _grown_datasets()
    config = _config()
    with _solve_env("on", "on"):
        dd = RandomEffectDeviceData(base, config)
        n_bins_before = len(dd.buckets)
        dd.onboard(grown)
        assert dd.dataset.num_entities == 42
        assert len(dd.buckets) > n_bins_before  # layout EXTENDED, not rebuilt
        coord = RandomEffectCoordinate(
            grown, config, "logistic_regression", device_data=dd
        )
        onboarded, stats = coord.train(
            np.zeros(grown.num_examples, np.float32)
        )
        rebuilt_coord = RandomEffectCoordinate(
            grown, config, "logistic_regression"
        )
        rebuilt, _ = rebuilt_coord.train(
            np.zeros(grown.num_examples, np.float32)
        )
    assert stats["entities"] == 42
    np.testing.assert_array_equal(onboarded.keys, rebuilt.keys)
    np.testing.assert_allclose(
        np.asarray(onboarded.table), np.asarray(rebuilt.table),
        atol=1e-5, rtol=0,
    )


def test_onboarding_rejects_shrunk_data_and_grows_existing_rows():
    base, _ = _grown_datasets()
    config = _config()
    dd = RandomEffectDeviceData(base, config)
    from photon_tpu.game.data import take_rows

    with pytest.raises(ValueError, match="append-only|GROWN"):
        dd.onboard(take_rows(base, np.arange(base.num_examples - 5)))
    # Appending rows that reference an EXISTING entity GROWS the layout in
    # place (ISSUE 15 blocker fix — tests/test_online_growth.py pins the
    # fit parity; here: the vocabulary is unchanged and the rows landed).
    dup = GameDataset.create(
        label=np.concatenate([base.label, base.label[:3]]),
        shards={
            "per_entity": DenseShard(np.concatenate([
                base.shards["per_entity"].x, base.shards["per_entity"].x[:3],
            ])),
        },
        id_columns={
            "userId": np.concatenate([
                base.id_columns["userId"], base.id_columns["userId"][:3],
            ]),
        },
    )
    dd.onboard(dup)
    assert dd.dataset.num_entities == 30
    assert len(dd.dataset.entity_idx_per_row) == dup.num_examples
    live_rows = sum(st["live_rows"] for st in dd.bin_stats)
    assert live_rows == dup.num_examples


def test_estimator_onboarding_is_atomic_across_coordinates():
    """A per-user + per-item estimator onboarding a batch that one
    coordinate must reject (its feature shard has the wrong dim in the
    grown data) rejects up front and leaves EVERY cached layout untouched
    — not grow the per-user layout and then throw on the per-item one (a
    half-onboarded cache would mix grown row indices with old-length
    offset vectors)."""
    from photon_tpu.game.estimator import (
        GameEstimator,
        GameOptimizationConfiguration,
    )

    raw = make_game_data(
        n_entities=20, rows_per_entity_mean=4, fixed_dim=5, random_dim=4,
        seed=5, n_random_coords=2,
    )
    base = GameDataset.create(
        label=raw["label"],
        shards={
            "re0": DenseShard(raw["x_random"]["re0"]),
            "re1": DenseShard(raw["x_random"]["re1"]),
        },
        id_columns={
            "re0": raw["entity_ids"]["re0"],
            "re1": raw["entity_ids"]["re1"],
        },
    )
    n_new = 6
    grown = GameDataset.create(
        label=np.concatenate([base.label, base.label[:n_new]]),
        shards={
            # per-user's shard grows correctly; per-item's shard comes
            # back at the WRONG dim — its layout must reject.
            "re0": DenseShard(np.concatenate([
                base.shards["re0"].x, base.shards["re0"].x[:n_new]
            ])),
            "re1": DenseShard(np.concatenate([
                base.shards["re1"].x, base.shards["re1"].x[:n_new]
            ], axis=0)[:, :3]),
        },
        id_columns={
            "re0": np.concatenate(
                [base.id_columns["re0"],
                 np.arange(10_000, 10_000 + n_new, dtype=np.int64)]
            ),
            "re1": np.concatenate(
                [base.id_columns["re1"], base.id_columns["re1"][:n_new]]
            ),
        },
    )
    config = GameOptimizationConfiguration(
        coordinates={
            "per_user": RandomEffectCoordinateConfig(
                "re0", "re0", problem=_problem(max_iterations=5)
            ),
            "per_item": RandomEffectCoordinateConfig(
                "re1", "re1", problem=_problem(max_iterations=5)
            ),
        },
        descent_iterations=1,
    )
    estimator = GameEstimator("logistic_regression", base)
    estimator.fit([config])
    with pytest.raises(ValueError, match="dim"):
        estimator.onboard_training_data(grown)
    # NOTHING mutated: every cached layout still holds the base vocabulary
    # and the base row count, and another fit on the base data still runs.
    for dd in estimator._device_data_cache.values():
        assert dd.dataset.num_entities == 20
        assert len(dd.dataset.entity_idx_per_row) == base.num_examples
    assert estimator.training_data is base
    estimator.fit([config])


def test_model_with_entities_grows_on_device():
    base, grown = _grown_datasets()
    config = _config()
    _, model, _ = _train(base, config)
    dd = RandomEffectDeviceData(grown, config)
    bigger = model.with_entities(dd.dataset.keys)
    assert bigger.num_entities == 42
    # Existing entities keep their rows at the new sorted positions.
    from photon_tpu.game.data import entity_index_for

    idx = entity_index_for(model.keys, bigger.keys)
    np.testing.assert_array_equal(
        np.asarray(bigger.table)[idx], np.asarray(model.table)
    )
    # New entities start at zero.
    new_mask = np.ones(42, bool)
    new_mask[idx] = False
    assert np.all(np.asarray(bigger.table)[new_mask] == 0.0)
    with pytest.raises(ValueError, match="merged keys"):
        model.with_entities(model.keys[:5])


def test_estimator_onboarding_end_to_end():
    from photon_tpu.game.estimator import (
        GameEstimator,
        GameOptimizationConfiguration,
    )

    base, grown = _grown_datasets()
    config = GameOptimizationConfiguration(
        coordinates={"per_entity": _config()}, descent_iterations=1
    )
    session = TelemetrySession("t-onboard")
    with _solve_env("on", "on"):
        estimator = GameEstimator(
            "logistic_regression", base, telemetry=session
        )
        first = estimator.fit([config])[0]
        estimator.onboard_training_data(grown)
        dd = estimator._device_data_cache[
            config.coordinates["per_entity"].data_key
        ]
        warm = first.model.coordinate("per_entity").with_entities(
            dd.dataset.keys
        )
        from photon_tpu.game.model import GameModel

        second = estimator.fit(
            [config],
            initial_model=GameModel(
                {"per_entity": warm}, "logistic_regression"
            ),
        )[0]
        fresh = GameEstimator("logistic_regression", grown).fit(
            [config],
            initial_model=GameModel(
                {"per_entity": warm}, "logistic_regression"
            ),
        )[0]
    got = second.model.coordinate("per_entity")
    want = fresh.model.coordinate("per_entity")
    assert got.num_entities == 42
    np.testing.assert_allclose(
        np.asarray(got.table), np.asarray(want.table), atol=1e-5, rtol=0
    )
    onboarded = session.counter("estimator.entities_onboarded").value
    assert onboarded == 12


def test_residual_engine_grow_preserves_rows():
    from photon_tpu.game.residuals import HostResiduals, ResidualEngine

    rng = np.random.default_rng(0)
    base_offset = rng.standard_normal(20).astype(np.float32)
    rows = {
        "a": rng.standard_normal(20).astype(np.float32),
        "b": rng.standard_normal(20).astype(np.float32),
    }
    grown_offset = np.concatenate(
        [base_offset, rng.standard_normal(8).astype(np.float32)]
    )
    for cls in (ResidualEngine, HostResiduals):
        engine = cls(base_offset, names=["a", "b"])
        for name, row in rows.items():
            engine.update(name, row.copy())
        engine.grow(grown_offset)
        got = np.asarray(engine.offsets_for("a"), np.float32)[:28]
        # Fresh engine over the grown rows (appended scores zero) is the
        # reference the grown engine must match.
        fresh = cls(grown_offset, names=["a", "b"])
        for name, row in rows.items():
            fresh.update(name, np.pad(row, (0, 8)))
        want = np.asarray(fresh.offsets_for("a"), np.float32)[:28]
        np.testing.assert_allclose(got, want, atol=1e-6)
        with pytest.raises(ValueError, match="appends"):
            engine.grow(base_offset)


# ---------------------------------------------------------------------------
# The SPD factor-and-solve behind the Newton step (ISSUE 28): batched, the
# batch rides the lane axis in an unrolled Cholesky; unbatched or above
# ``LANES_MAX_DIM`` it is XLA's cho_factor / cho_solve
# ---------------------------------------------------------------------------


def _spd_systems(d, batch, cond, seed=0):
    """``[batch, d, d]`` SPD matrices with eigenvalues spread log-uniformly
    over ``[1, cond]`` (both ends present), and right-hand sides, float64."""
    rng = np.random.default_rng(1000 * d + batch + seed)
    q = np.linalg.qr(rng.normal(size=(batch, d, d)))[0]
    eig = np.exp(rng.uniform(0.0, np.log(cond), size=(batch, d)))
    eig[:, 0], eig[:, -1] = 1.0, cond
    h = np.einsum("bij,bj,bkj->bik", q, eig, q)
    return 0.5 * (h + h.transpose(0, 2, 1)), rng.normal(size=(batch, d))


def _solve_errors(h, g, x):
    """(forward, backward) relative errors of ``x`` as a solution of ``h x =
    g``: against a float64 NumPy solve, and the residual over ``|h| |x|``
    — the second is what a stable solver keeps at float32's rounding
    whatever the conditioning."""
    x = np.asarray(x, np.float64)
    want = np.linalg.solve(h, g[..., None])[..., 0]
    forward = np.linalg.norm(x - want, axis=1) / np.linalg.norm(want, axis=1)
    residual = np.einsum("bij,bj->bi", h, x) - g
    backward = np.linalg.norm(residual, axis=1) / (
        np.linalg.norm(h, axis=(1, 2), ord=2) * np.linalg.norm(x, axis=1)
    )
    return forward.max(), backward.max()


@functools.cache
def _vmapped(fn):
    return jax.jit(jax.vmap(fn))


@pytest.mark.parametrize("cond", [10.0, 1e4], ids=["well", "ill"])
@pytest.mark.parametrize("batch", [1, 7, 130])
@pytest.mark.parametrize("d", [1, 2, 8, 16, 32, 40])
def test_spd_solve_under_vmap_matches_lapack_and_f64(d, batch, cond):
    """d = 40 is above ``LANES_MAX_DIM``: the same call, XLA's form."""
    assert factorization_kind(d) == ("lanes" if d <= 32 else "xla")
    h, g = _spd_systems(d, batch, cond if d > 1 else 1.0)
    h32, g32 = jnp.asarray(h, jnp.float32), jnp.asarray(g, jnp.float32)
    fwd, bwd = _solve_errors(h, g, _vmapped(spd_solve)(h32, g32))
    ref_fwd, ref_bwd = _solve_errors(
        h, g, _vmapped(_spd_solve_xla)(h32, g32)
    )
    # The residual is at float32's rounding at either conditioning, and no
    # worse than 3 x LAPACK's own.  The distance from the float64 solution
    # is bounded by cond x rounding for any stable float32 solver (one
    # system's reading swings inside that bound), so it is held to 5e-6
    # and 3 x LAPACK's where the conditioning allows, to the bound else.
    assert bwd <= 5e-6 and bwd <= 3 * ref_bwd + 1e-7
    if cond == 10.0:
        assert fwd <= 5e-6 and fwd <= 3 * ref_fwd + 1e-7
    else:
        assert fwd <= cond * 2.0 ** -23


@pytest.mark.parametrize("d", [2, 16])
def test_spd_solve_non_positive_definite_lane_is_non_finite(d):
    h, g = _spd_systems(d, 7, 10.0)
    h32, g32 = jnp.asarray(h, jnp.float32), jnp.asarray(g, jnp.float32)
    solve = _vmapped(spd_solve)
    good = np.asarray(solve(h32, g32))
    for bad_h in (-h32[3], h32[3].at[d - 1, d - 1].set(-1.0),
                  jnp.zeros_like(h32[3])):
        got = np.asarray(solve(h32.at[3].set(bad_h), g32))
        assert not np.isfinite(got[3]).any()
        keep = np.arange(7) != 3
        np.testing.assert_array_equal(got[keep], good[keep])


def test_spd_solve_unbatched_operand_and_nested_vmap():
    h, g = _spd_systems(8, 5, 10.0)
    h32, g32 = jnp.asarray(h, jnp.float32), jnp.asarray(g, jnp.float32)
    want = np.linalg.solve(h, g[..., None])[..., 0]
    one_h = jax.vmap(spd_solve, in_axes=(None, 0))(h32[0], g32)
    np.testing.assert_allclose(
        one_h, np.linalg.solve(h[0], g.T).T, rtol=2e-5, atol=1e-6)
    one_g = jax.vmap(spd_solve, in_axes=(0, None))(h32, g32[0])
    np.testing.assert_allclose(
        one_g, np.linalg.solve(h, g[0][None, :, None])[..., 0],
        rtol=2e-5, atol=1e-6)
    nested = jax.vmap(jax.vmap(spd_solve))(
        jnp.stack([h32, h32]), jnp.stack([g32, 2 * g32]))
    np.testing.assert_allclose(nested[1], 2 * want, rtol=2e-5, atol=1e-6)


def _newton_bin_batch(d, entities=6, rows=8):
    from photon_tpu.data.batch import DenseBatch

    rng = np.random.default_rng(0)
    return DenseBatch(
        x=jnp.asarray(rng.normal(size=(entities, rows, d)), jnp.float32),
        label=jnp.asarray(rng.integers(0, 2, size=(entities, rows)),
                          jnp.float32),
        offset=jnp.zeros((entities, rows), jnp.float32),
        weight=jnp.ones((entities, rows), jnp.float32),
    )


def _factorization_ops(lowered) -> bool:
    text = lowered.as_text().lower()
    return "cholesky" in text or "triangular_solve" in text


@pytest.mark.parametrize("d,batched,xla_call", [
    (16, True, False),   # the cell's width: the lane form, no custom call
    (40, True, True),    # above LANES_MAX_DIM: XLA's batched Cholesky
    (16, False, True),   # an unbatched newton is cho_factor / cho_solve
])
def test_entity_solve_newton_lowering(d, batched, xla_call):
    from photon_tpu.core.objective import GlmObjective
    from photon_tpu.game.batched_solve import _run_newton_fit

    problem = _problem()
    objective = GlmObjective.create(
        "logistic_regression", problem.regularization)
    batch = _newton_bin_batch(d)
    w0 = jnp.zeros((batch.x.shape[0], d), jnp.float32)
    if batched:
        lowered = cached_newton_solver(problem).lower(objective, batch, w0)
        assert "entity_solve_newton" in lowered.as_text()
    else:
        one = jax.tree.map(lambda leaf: leaf[0], batch)
        lowered = jax.jit(functools.partial(
            _run_newton_fit, cfg=problem.optimizer_config, variance="none",
        )).lower(objective, one, w0[0])
    assert _factorization_ops(lowered) == xla_call


@pytest.mark.parametrize(
    "task", ["logistic_regression", "poisson_regression"])
def test_batched_newton_matches_unbatched_entity_by_entity(task, monkeypatch):
    """A 3-bin toy coordinate through ``cached_newton_solver`` (the lane
    form) against ``newton`` run one entity at a time (cho_factor /
    cho_solve): the same fit to 1e-6."""
    from photon_tpu.game.batched_solve import _run_newton_fit
    from photon_tpu.game.coordinate import _bucket_offsets

    monkeypatch.setenv("PHOTON_SOLVE_BIN_WASTE", "1.2")
    monkeypatch.setenv("PHOTON_SOLVE_MAX_BINS", "3")
    data = _dataset(n_entities=40, rows_mean=8, dim=8)
    config = _config()
    coord = RandomEffectCoordinate(data, config, task)
    device_data = coord.device_data
    assert len(device_data.buckets) == 3
    assert coord._bin_routes() == ["newton"] * 3
    solver = cached_newton_solver(config.problem)
    single = jax.jit(functools.partial(
        _run_newton_fit, cfg=config.problem.optimizer_config,
        variance="none",
    ))
    offsets = np.zeros(data.num_examples, np.float32)
    for i, bucket in enumerate(device_data.buckets):
        batch = device_data.batch_for(
            i, _bucket_offsets(device_data, i, bucket, offsets))
        w0 = device_data.device_buckets[i]["w0"]
        coefficients, result = solver(coord.problem.objective, batch, w0)
        assert bool(np.all(np.asarray(result.converged)))
        for e in range(w0.shape[0]):
            want, _ = single(
                coord.problem.objective,
                jax.tree.map(lambda leaf: leaf[e], batch), w0[e],
            )
            np.testing.assert_allclose(
                np.asarray(coefficients.means[e]), np.asarray(want.means),
                atol=1e-6, rtol=0,
            )


# ---------------------------------------------------------------------------
# The lane form of a dense bin's sums (ISSUE 39): margins, gradient and
# Hessian with the entities on the minor axis
# ---------------------------------------------------------------------------

_LANE_TASKS = {
    "logistic": "logistic_regression", "poisson": "poisson_regression",
    "linear": "linear_regression",
}


def _lane_bin(task, d, entities=130, rows=8, seed=0):
    """A dense bin whose entity count is no multiple of 128, with 1-3
    zero-weight pad rows an entity (zero features, as the bins pad)."""
    from photon_tpu.data.batch import DenseBatch

    rng = np.random.default_rng(seed)
    live = (
        np.arange(rows)[None, :] < rng.integers(rows - 3, rows, entities)[:, None]
    ).astype(np.float32)
    x = (0.5 * rng.normal(size=(entities, rows, d))).astype(np.float32)
    x[:, :, 0] = 1.0  # the intercept a shift needs
    x *= live[:, :, None]
    z = np.einsum("brd,bd->br", x, 0.3 * rng.normal(size=(entities, d)))
    if task == "logistic":
        label = rng.random((entities, rows)) < 1 / (1 + np.exp(-z))
    elif task == "poisson":
        label = rng.poisson(np.exp(np.clip(z, -3, 2)))
    else:
        label = z + 0.1 * rng.normal(size=z.shape)
    return DenseBatch(
        x=jnp.asarray(x), label=jnp.asarray(label * live, jnp.float32),
        offset=jnp.asarray(0.1 * rng.normal(size=z.shape) * live, jnp.float32),
        weight=jnp.asarray(live),
    )


def _lane_objective(task, d, normalized):
    from photon_tpu.core.normalization import NormalizationContext
    from photon_tpu.core.objective import GlmObjective

    rng = np.random.default_rng(7)
    norm = None
    if normalized:
        norm = NormalizationContext(
            factors=jnp.asarray(rng.uniform(0.5, 2.0, d), jnp.float32).at[0].set(1.0),
            shifts=jnp.asarray(0.2 * rng.normal(size=d), jnp.float32).at[0].set(0.0),
            intercept_id=0,
        )
    return GlmObjective.create(
        _LANE_TASKS[task], RegularizationContext("l2", 1.0), normalization=norm
    )


def _at_margins(objective, lanes):
    """Value, gradient and Hessian of one entity through the margins, as
    ``newton.MarginForm`` evaluates them."""
    def evaluate(w, batch):
        z = objective.margins(w, batch, lanes)
        return (
            objective.value_at_margins(z, w, batch),
            objective.grad_at_margins(z, w, batch, lanes),
            objective.hessian_at_margins(z, w, batch, lanes),
        )
    return evaluate


@pytest.mark.parametrize("normalized", [False, True], ids=["plain", "shift+factor"])
@pytest.mark.parametrize("d", [8, 16, 32, 40])
@pytest.mark.parametrize("task", ["logistic", "poisson", "linear"])
def test_lane_form_matches_the_unbatched_expressions(task, d, normalized):
    """Value, gradient and Hessian through the margins, in the lane form
    under ``vmap`` (features mapped at their minor axis) and in the rows
    form, against ``value_and_grad`` / ``hessian_matrix`` one entity at a
    time; and the fit through ``cached_newton_solver`` (``lanes`` up to
    ``LANES_MAX_DIM``, the ``rows`` form at 40) against ``newton`` over
    those expressions, to the file's parity bound."""
    from photon_tpu.core.optimizers.newton import newton, reduction_kind
    from photon_tpu.data.batch import DenseBatch

    batch = _lane_bin(task, d)
    entities = batch.x.shape[0]
    objective = _lane_objective(task, d, normalized)
    w = jnp.asarray(
        0.2 * np.random.default_rng(1).normal(size=(entities, d)), jnp.float32)
    lanes = jax.jit(jax.vmap(
        _at_margins(objective, True),
        in_axes=(0, DenseBatch(x=-1, label=0, offset=0, weight=0)),
    ))(w, batch._replace(x=jnp.moveaxis(batch.x, 0, -1)))
    rows = jax.jit(jax.vmap(_at_margins(objective, False)))(w, batch)
    one = jax.jit(lambda w, b: (
        *objective.value_and_grad(w, b), objective.hessian_matrix(w, b)))
    for e in (0, 57, entities - 1):
        want = one(w[e], jax.tree.map(lambda leaf: leaf[e], batch))
        for form in (lanes, rows):
            for got, ref in zip(form, want):
                scale = float(jnp.max(jnp.abs(ref)))
                np.testing.assert_allclose(
                    got[e], ref, rtol=1e-5, atol=1e-5 * scale)
    # One entity, not under vmap: the same closed form, no batch axis.
    alone = _at_margins(objective, True)(
        w[3], jax.tree.map(lambda leaf: leaf[3], batch))
    for got, ref in zip(alone, lanes):
        np.testing.assert_allclose(got, ref[3], rtol=1e-5, atol=1e-5)

    kind = reduction_kind(True, d, entities, batch.label.shape[1])
    assert kind == ("lanes" if d <= 32 else "rows")
    problem = _problem()
    w0 = jnp.zeros((entities, d), jnp.float32)
    solver = cached_newton_solver(problem)
    # The program's own text says which form it took: the rows form's
    # products are dot_generals, the lane form has none.
    assert ("dot_general" in solver.lower(objective, batch, w0).as_text()) == (
        kind == "rows")
    coefficients, result = solver(objective, batch, w0)
    assert coefficients.means.shape == (entities, d)
    assert result.iterations.shape == (entities,)
    assert bool(np.all(np.asarray(result.converged)))
    # ``newton`` over value_and_grad / hessian_matrix, no margins carried.
    single = jax.jit(lambda b, w0: newton(
        lambda w: objective.value_and_grad(w, b), w0,
        problem.optimizer_config,
        hess=lambda w: objective.hessian_matrix(w, b),
    ).w)
    for e in (0, 57, entities - 1):
        want = single(jax.tree.map(lambda leaf: leaf[e], batch), w0[e])
        np.testing.assert_allclose(
            np.asarray(coefficients.means[e]), np.asarray(want),
            atol=1e-6 * max(1.0, float(jnp.max(jnp.abs(want)))), rtol=0,
        )


@pytest.mark.parametrize("storage", ["dense", "sparse"])
@pytest.mark.parametrize("task", ["logistic", "poisson", "linear"])
def test_newton_margin_form_lands_on_the_same_optimum(task, storage):
    """``newton`` over a ``MarginForm`` (margins carried, a trial the value
    along ``z + t X step``) against ``newton`` over value_and_grad /
    hessian_matrix (a trial a value and a gradient at ``w + t step``): the
    same optimum after as many iterations and trials, for dense and sparse
    rows."""
    from photon_tpu.core.optimizers.newton import MarginForm, newton
    from photon_tpu.data.batch import SparseBatch

    d = 8
    batch = jax.tree.map(lambda leaf: leaf[5], _lane_bin(task, d, entities=8))
    if storage == "sparse":
        rows = batch.x.shape[0]
        batch = SparseBatch(
            ids=jnp.tile(jnp.arange(d, dtype=jnp.int32), (rows, 1)),
            vals=batch.x, label=batch.label, offset=batch.offset,
            weight=batch.weight,
        )
    objective = _lane_objective(task, d, normalized=(storage == "dense"))
    cfg = _problem().optimizer_config
    w0 = jnp.zeros(d, jnp.float32)
    plain = jax.jit(lambda w0: newton(
        lambda w: objective.value_and_grad(w, batch), w0, cfg,
        hess=lambda w: objective.hessian_matrix(w, batch),
    ))(w0)
    carried = jax.jit(lambda w0: newton(None, w0, cfg, form=MarginForm(
        margins=lambda w: objective.margins(w, batch),
        direction=lambda v: objective.direction_margins(v, batch),
        value=lambda z, w: objective.value_at_margins(z, w, batch),
        grad=lambda z, w: objective.grad_at_margins(z, w, batch),
        hess=lambda z, w: objective.hessian_at_margins(z, w, batch),
    )))(w0)
    # (The last iteration sits on float32's rounding of a 1e-8 gradient
    # tolerance: one more or less.)
    assert abs(int(carried.iterations) - int(plain.iterations)) <= 1
    assert int(plain.iterations) > 1
    assert bool(carried.converged) and bool(plain.converged)
    assert abs(int(carried.evaluations) - int(plain.evaluations)) <= 3
    np.testing.assert_allclose(carried.w, plain.w, atol=1e-6, rtol=0)
    np.testing.assert_allclose(carried.value, plain.value, rtol=1e-6)


@pytest.mark.parametrize("variance", ["simple", "full"])
def test_lane_form_variances_match_the_rows_form(variance):
    from photon_tpu.game.batched_solve import _run_newton_fit

    batch = _lane_bin("logistic", 8)
    objective = _lane_objective("logistic", 8, False)
    problem = _problem(variance=variance)
    w0 = jnp.zeros(batch.x.shape[::2], jnp.float32)
    got, _ = cached_newton_solver(problem)(objective, batch, w0)
    want, _ = jax.jit(jax.vmap(functools.partial(
        _run_newton_fit, cfg=problem.optimizer_config, variance=variance,
    ), in_axes=(None, 0, 0)))(objective, batch, w0)  # lanes=False: rows
    np.testing.assert_allclose(got.variances, want.variances, rtol=1e-5)
    np.testing.assert_allclose(got.means, want.means, atol=1e-6, rtol=0)


def test_lane_form_on_a_mesh_pads_each_device_and_moves_nothing():
    """Four CPU devices, the entity axis sharded: each device's 130
    entities are padded to 256 in place, the fit is the one-device fit, and
    the compiled program holds no collective."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    batch = _lane_bin("logistic", 16, entities=4 * 130)
    objective = _lane_objective("logistic", 16, False)
    w0 = jnp.zeros((4 * 130, 16), jnp.float32)
    want, _ = cached_newton_solver(_problem())(objective, batch, w0)
    placed, placed_w0 = jax.tree.map(
        lambda leaf: jax.device_put(leaf, NamedSharding(
            mesh, P("data", *([None] * (leaf.ndim - 1))))),
        (batch, w0),
    )
    solver = cached_newton_solver(_problem())
    got, result = solver(objective, placed, placed_w0, entity_shards=4)
    assert got.means.sharding.is_equivalent_to(placed_w0.sharding, 2)
    np.testing.assert_allclose(got.means, want.means, atol=1e-6, rtol=0)
    assert bool(np.all(np.asarray(result.converged)))
    text = solver.lower(
        objective, placed, placed_w0, entity_shards=4).compile().as_text()
    assert "f32[8,16,256]" in text  # a device's slab: 130 entities -> 256
    for collective in ("all-gather", "all-to-all", "collective-permute",
                       "reduce-scatter"):
        assert f" {collective}(" not in text, collective
    # What crosses devices is the lockstep loops' "any lane active?" alone.
    reduced = set(re.findall(r"= (\S+) all-reduce\(", text))
    assert reduced <= {"pred[]"}, reduced


# ---------------------------------------------------------------------------
# Telemetry + report
# ---------------------------------------------------------------------------


def test_bin_telemetry_gauges():
    data = _dataset()
    session = TelemetrySession("t-bins")
    coord, _, _ = _train(data, _config(), telemetry=session)
    gauges = {
        (g["name"], g["labels"]["bin"]): g
        for g in session.registry.snapshot()["gauges"]
        if g["name"].startswith("solves.")
    }
    assert gauges
    occupancy = sum(
        g["value"] for (name, _), g in gauges.items()
        if name == "solves.bin_occupancy"
    )
    assert occupancy == coord.dataset.num_entities
    for (name, _), g in gauges.items():
        if name == "solves.padded_fraction":
            assert 0.0 <= g["value"] < 1.0
        assert g["labels"]["route"] == "newton"


def test_reductions_counter_follows_density_dim_and_entities(monkeypatch):
    """``solves.reductions{coordinate,kind}`` counts each ``newton`` bin's
    live entities once, under the form of its dense products: ``lanes``
    for a dense bin under 128 rows an entity, of at least 128 entities a
    device, at d <= 32, ``rows`` else; the run report's "Entity solves"
    section shows it."""
    from photon_tpu.telemetry.report import render_markdown

    def kinds(session):
        return {
            c["labels"]["kind"]: c["value"]
            for c in session.registry.snapshot()["counters"]
            if c["name"] == "solves.reductions"
        }

    monkeypatch.setenv("PHOTON_SOLVE_MAX_BINS", "1")
    monkeypatch.setenv("PHOTON_SOLVE_BIN_WASTE", "1000")
    session = TelemetrySession("t-reductions")
    data = _dataset(n_entities=150, dim=6)
    coord, model, _ = _train(data, _config(), telemetry=session)
    assert len(coord.device_data.buckets) == 1
    assert kinds(session) == {"lanes": 150}
    # The same fit with the bucket loop (bins under 128 entities): rows.
    small = TelemetrySession("t-reductions-rows")
    _, loop_model, _ = _train(data, _config(), telemetry=small, binning="off")
    assert set(kinds(small)) <= {"lanes", "rows"} and kinds(small)["rows"] > 0
    assert sum(kinds(small).values()) == 150
    np.testing.assert_allclose(
        np.asarray(model.table), np.asarray(loop_model.table), atol=1e-5)
    wide = TelemetrySession("t-reductions-wide")
    _train(_dataset(n_entities=150, dim=40), _config(), telemetry=wide)
    assert kinds(wide) == {"rows": 150}
    text = render_markdown({
        "driver": "t", "run_id": "r", "status": "ok", "duration_s": 1.0,
        "metrics": session.registry.snapshot(),
    })
    section = text[text.index("## Entity solves"):]
    assert "| coordinate | reductions | live entities |" in section
    assert "| per_entity | lanes | 150 |" in section


def test_report_renders_entity_solves_section():
    from photon_tpu.telemetry.report import render_markdown

    report = {
        "driver": "t", "run_id": "r", "status": "ok", "duration_s": 1.0,
        "metrics": {
            "counters": [],
            "gauges": [
                {"name": "solves.bin_occupancy", "value": 90,
                 "labels": {"coordinate": "per_user", "bin": "0",
                            "capacity": "8", "route": "newton"}},
                {"name": "solves.padded_fraction", "value": 0.31,
                 "labels": {"coordinate": "per_user", "bin": "0",
                            "capacity": "8", "route": "newton"}},
            ],
            "histograms": [],
        },
    }
    text = render_markdown(report)
    assert "## Entity solves" in text
    assert "per_user" in text and "newton" in text and "0.31" in text


# ---------------------------------------------------------------------------
# Bench integration (the 1M curve point is slow-marked; tier-1 runs a
# small-capped smoke of the same code path, assertions included)
# ---------------------------------------------------------------------------


def test_bench_entities_smoke(capsys):
    import bench

    bench._bench_entities(max_entities=3000)
    out = capsys.readouterr().out
    line = [ln for ln in out.splitlines()
            if '"game_entity_solves_per_sec"' in ln]
    assert line, out
    import json

    payload = json.loads(line[-1])
    detail = payload["detail"]
    assert detail["descent_parity"]["host_syncs_per_iteration"] == 1.0
    assert all(p["max_same_solver_diff"] <= 1e-5 for p in detail["curve"])
    # The high-dim Newton-CG leg (ISSUE 14) rides the same mode: its
    # ≥1×-the-L-BFGS-rate bar at d=256 is asserted inside the bench.
    hidim = [ln for ln in out.splitlines()
             if "game_entity_solves_per_sec_hidim" in ln]
    assert hidim, out
    hdetail = json.loads(hidim[-1])["detail"]
    assert hdetail["dim"] == 256
    assert hdetail["speedup_vs_vmapped_lbfgs"] >= 1.0
    assert [p["dim"] for p in hdetail["curve"]] == [64, 256, 1024]


@pytest.mark.slow
def test_bench_entities_full_curve(capsys):
    """The full 10k -> 1M CPU scaling curve (the ISSUE 8 acceptance run):
    asserts internally that the batched path beats the bucket loop at
    >=100k entities, parity <=1e-5, and host_syncs == 1/iter."""
    import bench

    bench._bench_entities(max_entities=1_000_000)
    out = capsys.readouterr().out
    assert "game_entity_solves_per_sec" in out
