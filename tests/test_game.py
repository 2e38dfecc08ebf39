"""GAME engine tests: bucketing, batched solves, coordinate descent.

Mirrors the reference's integration-test strategy (SURVEY.md §4): the
batched/vmapped random-effect solver is cross-checked against independent
sequential per-entity solves (the distributed-vs-local trick), and full
GameEstimator fits on tiny synthetic GAME data must converge with improving
validation metrics.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.core.objective import GlmObjective, RegularizationContext
from photon_tpu.core.optimizers import OptimizerConfig
from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig
from photon_tpu.data.batch import dense_batch
from photon_tpu.data.synthetic import make_game_data
from photon_tpu.evaluation.evaluators import MultiEvaluator, get_evaluator
from photon_tpu.game import (
    CoordinateDescent,
    DenseShard,
    FixedEffectCoordinate,
    FixedEffectCoordinateConfig,
    GameDataset,
    GameEstimator,
    GameOptimizationConfiguration,
    RandomEffectCoordinate,
    RandomEffectCoordinateConfig,
    build_random_effect_dataset,
)
from photon_tpu.parallel import create_mesh


def _game_dataset(seed=0, n_entities=40, rows_mean=6, fixed_dim=5, random_dim=3):
    raw = make_game_data(
        n_entities=n_entities,
        rows_per_entity_mean=rows_mean,
        fixed_dim=fixed_dim,
        random_dim=random_dim,
        seed=seed,
    )
    return GameDataset.create(
        label=raw["label"],
        shards={
            "global": DenseShard(raw["x_fixed"]),
            "per_entity": DenseShard(raw["x_random"]["re0"]),
        },
        id_columns={"userId": raw["entity_ids"]["re0"]},
        weight=raw["weight"],
    )


# ---------------------------------------------------------------------------
# Random-effect dataset bucketing
# ---------------------------------------------------------------------------


def test_bucketing_partitions_all_rows_once():
    data = _game_dataset()
    ds = build_random_effect_dataset(data, "userId", "per_entity")
    seen = []
    for bucket in ds.buckets:
        mask = bucket.row_weight > 0
        assert bucket.row_capacity >= mask.sum(axis=1).max()
        # power-of-two capacities
        assert bucket.row_capacity & (bucket.row_capacity - 1) == 0
        seen.append(bucket.row_index[mask])
    seen = np.concatenate(seen)
    assert sorted(seen.tolist()) == list(range(data.num_examples))
    # every entity present exactly once across buckets
    all_entities = np.concatenate([b.entity_index for b in ds.buckets])
    assert sorted(all_entities.tolist()) == list(range(ds.num_entities))


def test_bucketing_respects_active_row_cap_with_weight_correction():
    data = _game_dataset(rows_mean=10)
    cap = 4
    ds = build_random_effect_dataset(data, "userId", "per_entity", active_row_cap=cap)
    raw_counts = np.bincount(
        ds.entity_idx_per_row[ds.entity_idx_per_row >= 0], minlength=ds.num_entities
    )
    for bucket in ds.buckets:
        assert bucket.row_capacity <= cap
        mask = bucket.row_weight > 0
        for i, e in enumerate(bucket.entity_index):
            rows_kept = int(mask[i].sum())
            assert rows_kept == min(raw_counts[e], cap)
            # weight mass is preserved in expectation: kept rows upweighted
            expected_mass = data.weight[
                ds.entity_idx_per_row == e
            ].sum()
            np.testing.assert_allclose(
                bucket.row_weight[i].sum(), expected_mass, rtol=1e-5
            )


def test_entity_index_for_unseen_keys():
    data = _game_dataset()
    ds = build_random_effect_dataset(data, "userId", "per_entity")
    idx = ds.entity_index_for(np.array([0, 10**9, 1]))
    assert idx[0] >= 0 and idx[2] >= 0
    assert idx[1] == -1


def test_missing_marker_rows_stay_out_of_cold_rebuild_vocab():
    """A cold rebuild over a merged dataset must reproduce the incremental
    path's missing-id semantics (ISSUE 19 satellite): rows whose id column
    carries the dtype-relative missing marker map to per-row entity index
    -1 — zero margin, no bin membership — instead of materializing a
    marker "entity" that trains its own random effect."""
    from photon_tpu.game.data import missing_key

    data = _game_dataset()
    raw = data.id_columns["userId"].copy()
    marker = missing_key(raw.dtype)
    absent = np.zeros(len(raw), bool)
    absent[::7] = True
    raw[absent] = marker
    marked = GameDataset.create(
        label=data.label,
        shards=dict(data.shards),
        id_columns={"userId": raw},
        weight=data.weight,
    )
    ds = build_random_effect_dataset(marked, "userId", "per_entity")
    assert marker not in ds.keys
    assert (ds.entity_idx_per_row[absent] == -1).all()
    assert (ds.entity_idx_per_row[~absent] >= 0).all()
    # Every bucket row belongs to a REAL entity: the marked rows carry no
    # bin membership anywhere.
    covered = np.concatenate([
        b.row_index[b.row_weight > 0] for b in ds.buckets
    ])
    assert not np.intersect1d(covered, np.nonzero(absent)[0]).size
    # An explicit vocabulary is the caller's verbatim choice: not filtered.
    pinned = build_random_effect_dataset(
        marked, "userId", "per_entity",
        vocab=np.concatenate([np.unique(raw)]),
    )
    assert marker in pinned.keys
    # Disabling the hook restores the historical behavior (the marker
    # becomes an ordinary entity).
    legacy = build_random_effect_dataset(
        marked, "userId", "per_entity", missing_marker=None,
    )
    assert marker in legacy.keys
    assert (legacy.entity_idx_per_row >= 0).all()


# ---------------------------------------------------------------------------
# Batched (vmapped) random-effect solves vs sequential per-entity solves
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("optimizer", ["lbfgs", "tron"])
def test_vmapped_solves_match_sequential(optimizer):
    data = _game_dataset(seed=3, n_entities=12, rows_mean=5)
    config = RandomEffectCoordinateConfig(
        shard_name="per_entity",
        entity_column="userId",
        problem=ProblemConfig(
            optimizer=optimizer,
            regularization=RegularizationContext("l2", 0.5),
            optimizer_config=OptimizerConfig(max_iterations=50),
        ),
    )
    coord = RandomEffectCoordinate(data, config, "logistic_regression")
    offsets = np.zeros(data.num_examples, np.float32)
    model, stats = coord.train(offsets)
    assert stats["entities"] == coord.dataset.num_entities

    # Sequential reference: solve each entity's rows independently.
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 0.5))
    problem = GlmOptimizationProblem(obj, config.problem)
    shard = data.shards["per_entity"]
    for e in range(coord.dataset.num_entities):
        rows = np.nonzero(coord.dataset.entity_idx_per_row == e)[0]
        batch = dense_batch(
            shard.x[rows], data.label[rows], weight=data.weight[rows]
        )
        coefficients, _ = problem.run(batch, jnp.zeros(shard.dim, jnp.float32))
        np.testing.assert_allclose(
            model.table[e], coefficients.means, rtol=5e-3, atol=5e-3
        )


def test_random_effect_scores_zero_for_unseen_entities():
    train = _game_dataset(seed=1, n_entities=10)
    config = RandomEffectCoordinateConfig(
        shard_name="per_entity", entity_column="userId",
        problem=ProblemConfig(
            regularization=RegularizationContext("l2", 1.0),
            optimizer_config=OptimizerConfig(max_iterations=20),
        ),
    )
    coord = RandomEffectCoordinate(train, config, "logistic_regression")
    model, _ = coord.train(np.zeros(train.num_examples, np.float32))
    # Score a dataset containing unseen entity keys.
    other = GameDataset.create(
        label=train.label[:4],
        shards={"per_entity": DenseShard(train.shards["per_entity"].x[:4])},
        id_columns={"userId": np.array([10**6, 10**6 + 1, 0, 1], np.int64)},
    )
    scores = model.score(other)
    assert scores[0] == 0.0 and scores[1] == 0.0
    assert scores[2] != 0.0 or scores[3] != 0.0


# ---------------------------------------------------------------------------
# Coordinate descent / estimator
# ---------------------------------------------------------------------------


def _configs(descent_iterations=2, lam_fixed=0.01, lam_re=1.0):
    return GameOptimizationConfiguration(
        coordinates={
            "fixed": FixedEffectCoordinateConfig(
                shard_name="global",
                problem=ProblemConfig(
                    regularization=RegularizationContext("l2", lam_fixed),
                    optimizer_config=OptimizerConfig(max_iterations=60),
                ),
            ),
            "per-user": RandomEffectCoordinateConfig(
                shard_name="per_entity",
                entity_column="userId",
                problem=ProblemConfig(
                    regularization=RegularizationContext("l2", lam_re),
                    optimizer_config=OptimizerConfig(max_iterations=30),
                ),
            ),
        },
        descent_iterations=descent_iterations,
    )


def _split_rows(data: GameDataset, frac=0.25, seed=0):
    """Row-wise train/validation split of one GameDataset (same ground-truth
    model on both sides — the valid way to test generalization here)."""
    rng = np.random.default_rng(seed)
    val_mask = rng.random(data.num_examples) < frac

    def subset(mask):
        rows = np.nonzero(mask)[0]
        from photon_tpu.game.data import _gather_shard_rows

        return GameDataset(
            label=data.label[rows],
            offset=data.offset[rows],
            weight=data.weight[rows],
            shards={k: _gather_shard_rows(s, rows) for k, s in data.shards.items()},
            id_columns={k: v[rows] for k, v in data.id_columns.items()},
        )

    return subset(~val_mask), subset(val_mask)


def test_game_estimator_beats_fixed_effect_alone():
    full = _game_dataset(seed=7, n_entities=60, rows_mean=20)
    train, val = _split_rows(full)
    evaluators = MultiEvaluator([get_evaluator("auc"), get_evaluator("logistic_loss")])

    estimator = GameEstimator(
        "logistic_regression", train, val, evaluators=evaluators
    )
    game_results = estimator.fit([_configs()])
    best = estimator.select_best(game_results)

    # Fixed-effect-only baseline on the same data.
    fixed_only = GameEstimator(
        "logistic_regression", train, val, evaluators=evaluators
    ).fit(
        [
            GameOptimizationConfiguration(
                coordinates={
                    "fixed": _configs().coordinates["fixed"],
                },
                descent_iterations=1,
            )
        ]
    )[0]
    assert best.metrics["AUC"] > fixed_only.metrics["AUC"]
    assert best.metrics["LOGISTIC_LOSS"] < fixed_only.metrics["LOGISTIC_LOSS"]


def test_game_model_score_is_offset_plus_coordinate_sum():
    train = _game_dataset(seed=2, n_entities=20)
    result = GameEstimator("logistic_regression", train).fit(
        [_configs(descent_iterations=1)]
    )[0]
    model = result.model
    total = model.score(train)
    parts = sum(np.asarray(m.score(train)) for m in model.coordinates.values())
    np.testing.assert_allclose(total, train.offset + parts, rtol=1e-5, atol=1e-5)


def test_sweep_selects_best_configuration():
    train, val = _split_rows(_game_dataset(seed=4, n_entities=40, rows_mean=16))
    estimator = GameEstimator("logistic_regression", train, val)
    results = estimator.fit(
        [_configs(lam_re=1000.0), _configs(lam_re=1.0)]
    )
    best = estimator.select_best(results)
    assert best is results[int(np.argmax([r.metrics["AUC"] for r in results]))]


def test_warm_start_and_locked_coordinates():
    train, val = _split_rows(_game_dataset(seed=9, n_entities=25, rows_mean=12))
    estimator = GameEstimator("logistic_regression", train, val)
    first = estimator.fit([_configs(descent_iterations=1)])[0]

    # Retrain with the fixed effect locked: its coefficients must not move.
    second = estimator.fit(
        [_configs(descent_iterations=1)],
        initial_model=first.model,
        locked_coordinates=["fixed"],
    )[0]
    np.testing.assert_array_equal(
        np.asarray(second.model.coordinate("fixed").coefficients.means),
        np.asarray(first.model.coordinate("fixed").coefficients.means),
    )
    # The unlocked coordinate was retrained from the warm start.
    assert "per-user" in second.model.coordinates


def test_warm_start_aligns_entity_vocabularies_by_key():
    """A warm-start model trained on a different entity set must be joined
    by key, not by index (review finding: silent index misalignment)."""
    train = _game_dataset(seed=13, n_entities=12)
    config = RandomEffectCoordinateConfig(
        shard_name="per_entity", entity_column="userId",
        problem=ProblemConfig(
            regularization=RegularizationContext("l2", 1.0),
            optimizer_config=OptimizerConfig(max_iterations=5),
        ),
    )
    coord = RandomEffectCoordinate(train, config, "logistic_regression")
    model, _ = coord.train(np.zeros(train.num_examples, np.float32))
    # Shift the model's keys so only some overlap with the dataset's vocab.
    from photon_tpu.game.model import RandomEffectModel

    shifted = RandomEffectModel(
        table=model.table,
        keys=model.keys + 6,  # keys 6..17 vs dataset keys 0..11
        entity_column=model.entity_column,
        shard_name=model.shard_name,
        task_type=model.task_type,
    )
    init_table = np.asarray(coord._initial_table(shifted))
    for e, key in enumerate(coord.dataset.keys):
        src = np.searchsorted(shifted.keys, key)
        if src < len(shifted.keys) and shifted.keys[src] == key:
            np.testing.assert_array_equal(init_table[e], np.asarray(model.table)[src])
        else:
            np.testing.assert_array_equal(init_table[e], 0.0)


def test_locked_coordinate_without_initial_model_raises():
    train = _game_dataset(seed=11, n_entities=10)
    estimator = GameEstimator("logistic_regression", train)
    with pytest.raises(ValueError):
        estimator.fit([_configs(descent_iterations=1)], locked_coordinates=["fixed"])


# ---------------------------------------------------------------------------
# Mesh-sharded GAME training (8 virtual devices)
# ---------------------------------------------------------------------------


def test_game_training_on_mesh_matches_single_device():
    train = _game_dataset(seed=12, n_entities=30, rows_mean=5)
    config = _configs(descent_iterations=1)
    single = GameEstimator("logistic_regression", train).fit([config])[0]
    mesh = create_mesh()
    sharded = GameEstimator("logistic_regression", train, mesh=mesh).fit([config])[0]
    np.testing.assert_allclose(
        np.asarray(single.model.coordinate("fixed").coefficients.means),
        np.asarray(sharded.model.coordinate("fixed").coefficients.means),
        rtol=1e-3, atol=1e-3,
    )
    np.testing.assert_allclose(
        np.asarray(single.model.coordinate("per-user").table),
        np.asarray(sharded.model.coordinate("per-user").table),
        rtol=1e-3, atol=1e-3,
    )


def test_factored_random_effect_coordinate():
    """FactoredRandomEffectCoordinate (SURVEY.md §2.2 [K?]): when the true
    per-entity effects share a low-rank subspace and rows are scarce, the
    rank-constrained fit w_e = L z_e must generalize BETTER than the free
    per-entity fit (that sharing is the component's entire point)."""
    import numpy as np

    from photon_tpu.core.objective import RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import ProblemConfig
    from photon_tpu.data.index_map import IndexMap, feature_key
    from photon_tpu.evaluation.evaluators import get_evaluator
    from photon_tpu.game.coordinate import (
        FactoredRandomEffectCoordinate,
        FactoredRandomEffectCoordinateConfig,
        RandomEffectCoordinate,
        RandomEffectCoordinateConfig,
    )
    from photon_tpu.game.data import DenseShard, GameDataset

    rng = np.random.default_rng(17)
    n_entities, rows_tr, rows_va, d, true_rank = 60, 6, 8, 10, 2
    u_true = rng.standard_normal((d, true_rank)) * 1.6
    z_true = rng.standard_normal((n_entities, true_rank))
    w_true = z_true @ u_true.T  # [entities, d] — rank-2 effects

    def make(rows_per):
        n = n_entities * rows_per
        ent = np.repeat(np.arange(n_entities), rows_per)
        x = rng.standard_normal((n, d)).astype(np.float32)
        margin = np.einsum("nd,nd->n", x, w_true[ent])
        label = (rng.random(n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
        return GameDataset(
            shards={"re0": DenseShard(x)},
            label=label,
            offset=np.zeros(n, np.float32),
            weight=np.ones(n, np.float32),
            id_columns={"re0": ent},
        )

    train_ds, val_ds = make(rows_tr), make(rows_va)
    prob = ProblemConfig(
        regularization=RegularizationContext("l2", 1.0),
        optimizer_config=OptimizerConfig(max_iterations=10),
    )
    offsets = np.zeros(train_ds.num_examples, np.float32)
    auc = get_evaluator("AUC")

    fc = FactoredRandomEffectCoordinate(
        train_ds,
        FactoredRandomEffectCoordinateConfig(
            "re0", "re0", latent_dim=2, latent_iterations=4, problem=prob
        ),
        "logistic_regression",
    )
    m_fact, stats = fc.train(offsets)
    assert stats["entities"] == n_entities
    val_fact = auc.evaluate(
        np.asarray(m_fact.score(val_ds)), val_ds.label, val_ds.weight
    )

    rc = RandomEffectCoordinate(
        train_ds, RandomEffectCoordinateConfig("re0", "re0", problem=prob),
        "logistic_regression",
    )
    m_free, _ = rc.train(offsets)
    val_free = auc.evaluate(
        np.asarray(m_free.score(val_ds)), val_ds.label, val_ds.weight
    )
    assert val_fact > 0.78, f"factored val AUC too low: {val_fact}"
    assert val_fact > val_free + 0.03, (val_fact, val_free)


def test_factored_random_effect_driver_spec(tmp_path):
    """type=factored_random parses and trains end-to-end in train_game."""
    from photon_tpu.drivers import train_game

    summary = train_game.run(train_game.build_parser().parse_args([
        "--backend", "cpu",
        "--input", "synthetic-game:24:4:8:4:1:7",
        "--coordinate", "fixed:type=fixed,shard=global,max_iters=8",
        "--coordinate",
        "per_user:type=factored_random,shard=re0,entity=re0,"
        "latent_dim=2,latent_iterations=2,max_iters=6",
        "--descent-iterations", "2",  # iteration 2 exercises the SVD warm start
        "--validation-split", "0.25",
        "--output-dir", str(tmp_path / "out"),
    ]))
    assert summary["best_metrics"]["AUC"] > 0.5
    import os
    assert os.path.isdir(
        os.path.join(tmp_path, "out", "best_model", "random-effect", "per_user")
    )


def test_factored_random_effect_on_mesh_matches_single():
    """The pooled projection solve partitions over the mesh via GSPMD; an
    8-virtual-device run must match single-device results."""
    import numpy as np

    from photon_tpu.core.objective import RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import ProblemConfig
    from photon_tpu.game.coordinate import (
        FactoredRandomEffectCoordinate,
        FactoredRandomEffectCoordinateConfig,
    )
    from photon_tpu.game.data import DenseShard, GameDataset
    from photon_tpu.parallel.mesh import create_mesh

    rng = np.random.default_rng(23)
    n_entities, rows, d = 24, 5, 8
    n = n_entities * rows
    ent = np.repeat(np.arange(n_entities), rows)
    x = rng.standard_normal((n, d)).astype(np.float32)
    label = (rng.random(n) < 0.5).astype(np.float32)
    data = GameDataset(
        shards={"re0": DenseShard(x)}, label=label,
        offset=np.zeros(n, np.float32), weight=np.ones(n, np.float32),
        id_columns={"re0": ent},
    )
    cfg = FactoredRandomEffectCoordinateConfig(
        "re0", "re0", latent_dim=2, latent_iterations=2,
        problem=ProblemConfig(
            regularization=RegularizationContext("l2", 1.0),
            optimizer_config=OptimizerConfig(max_iterations=6),
        ),
    )
    offsets = np.zeros(n, np.float32)
    m_single, _ = FactoredRandomEffectCoordinate(
        data, cfg, "logistic_regression"
    ).train(offsets)
    m_mesh, _ = FactoredRandomEffectCoordinate(
        data, cfg, "logistic_regression", mesh=create_mesh(8)
    ).train(offsets)
    np.testing.assert_allclose(
        np.asarray(m_mesh.table), np.asarray(m_single.table),
        rtol=5e-3, atol=5e-4,
    )


def test_fixed_effect_pallas_kernel_on_sparse_shard(monkeypatch):
    """A sparse-shard GAME fixed effect under PHOTON_SPARSE_GRAD=pallas
    attaches the aligned layout and trains to the same optimum as the fm
    path (the coordinate-level wiring of the third kernel)."""
    rng = np.random.default_rng(44)
    n, k, d = 160, 4, 40
    ids = np.sort(
        rng.integers(0, d, size=(n, k)).astype(np.int32), axis=1
    )
    vals = rng.standard_normal((n, k)).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    from photon_tpu.game.data import GameDataset, SparseShard

    data = GameDataset.create(y, {"global": SparseShard(ids, vals, d)})
    problem = ProblemConfig(
        regularization=RegularizationContext("l2", 1.0),
        optimizer_config=OptimizerConfig(max_iterations=10),
    )
    results = {}
    for kernel in ("pallas", "fm"):
        monkeypatch.setenv("PHOTON_SPARSE_GRAD", kernel)
        coord = FixedEffectCoordinate(
            data, FixedEffectCoordinateConfig("global", problem),
            "logistic_regression",
        )
        if kernel == "pallas":
            assert coord.device_data.batch.al is not None
        else:
            assert coord.device_data.batch.al is None
        model, tracker = coord.train(np.zeros(data.num_examples, np.float32))
        results[kernel] = (tracker.iterations, np.asarray(model.coefficients.means))
    assert results["pallas"][0] == results["fm"][0], "iteration paths diverged"
    np.testing.assert_allclose(
        results["pallas"][1], results["fm"][1], rtol=1e-3, atol=1e-4
    )


# ---------------------------------------------------------------------------
# A sparse fixed effect scored through its block tiles (PR 37)
# ---------------------------------------------------------------------------


def _sparse_fixed_game(seed, n_entities, d=300, k=6):
    """A GAME data set whose ``global`` shard is sparse, with what a tiled
    score has to get right: an id twice in a row, explicit zeros."""
    from photon_tpu.game.data import SparseShard

    raw = make_game_data(
        n_entities=n_entities, rows_per_entity_mean=6, fixed_dim=5,
        random_dim=3, seed=seed,
    )
    n = len(raw["label"])
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, d, size=(n, k)).astype(np.int32)
    ids[:, 1] = ids[:, 0]
    vals = rng.standard_normal((n, k)).astype(np.float32)
    vals[:, 2] = 0.0
    return GameDataset.create(
        label=raw["label"],
        shards={
            "global": SparseShard(ids, vals, d),
            "per_entity": DenseShard(raw["x_random"]["re0"]),
        },
        id_columns={"userId": raw["entity_ids"]["re0"]},
        weight=raw["weight"],
    )


def _gather_margins(data, model):
    from photon_tpu.game.model import _fixed_margins

    shard = data.shard("global")
    return np.asarray(_fixed_margins(
        jnp.asarray(model.coefficients.means),
        (jnp.asarray(shard.ids), jnp.asarray(shard.vals)), dense=False,
    ))


def _score_counts(session):
    return {
        (c["name"], c["labels"].get("kernel")): c["value"]
        for c in session.registry.snapshot()["counters"]
        if c["name"].startswith("score.")
    }


def test_game_fit_scores_a_tiled_sparse_fixed_effect_through_its_tiles(
    monkeypatch,
):
    """Under kernel ``blocked`` the fixed effect's training batch carries
    block tiles: both its scores (training rows, validation rows) run
    ``blocked/xw`` and equal the gather's to float32 summation order, the
    second copy of the entries (``_scoring_feats``) is never built, the
    validation cache holds tiles in place of ``(ids, vals)``, and the entry
    counter reads what it read before."""
    from photon_tpu.ops.block_tiles import BlockTiles
    from photon_tpu.telemetry import TelemetrySession

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")
    train, val = _sparse_fixed_game(0, 30), _sparse_fixed_game(1, 11)
    problem = ProblemConfig(
        regularization=RegularizationContext("l2", 1.0),
        optimizer_config=OptimizerConfig(max_iterations=2),
    )
    config = GameOptimizationConfiguration(
        coordinates={
            "fixed": FixedEffectCoordinateConfig("global", problem),
            "re0": RandomEffectCoordinateConfig(
                "per_entity", "userId", problem),
        },
        descent_iterations=2,
    )
    session = TelemetrySession("t")
    estimator = GameEstimator(
        "logistic_regression", train, validation_data=val,
        evaluators=MultiEvaluator([get_evaluator("auc")]), telemetry=session,
    )
    result = estimator.fit([config])[0]
    model = result.model.coordinates["fixed"]
    held = estimator.device_layout(config.coordinates["fixed"])
    assert held.batch.bt is not None
    assert held._score_feats is None and held._score_cache_bytes == 0
    k = train.shard("global").ids.shape[1]
    assert _score_counts(session) == {
        ("score.fixed_dispatches", "blocked"): 4.0,
        ("score.sparse_entries", None):
            2.0 * (train.num_examples + val.num_examples) * k,
    }
    coord = FixedEffectCoordinate(
        train, config.coordinates["fixed"], "logistic_regression",
        device_data=held,
    )
    np.testing.assert_allclose(
        np.asarray(coord.score_device(model)), _gather_margins(train, model),
        rtol=1e-5, atol=1e-6,
    )
    cache = estimator._validation_scoring_cache()
    assert "global" not in cache._feats
    assert isinstance(cache._fixed_tiles["global"], BlockTiles)
    scored = np.asarray(cache.score(model, "fixed"))
    assert scored.shape == (val.num_examples,)
    np.testing.assert_allclose(
        scored, _gather_margins(val, model), rtol=1e-5, atol=1e-6
    )
    assert np.isfinite(result.metrics["AUC"])


@pytest.mark.parametrize("case", [
    "row_capacity", "down_sampled", "one_device_mesh", "no_tiles",
    "untileable_validation",
])
def test_sparse_fixed_effect_score_takes_tiles_only_where_they_hold_the_rows(
    monkeypatch, case,
):
    """Tiles score the training rows only where the batch IS the shard in
    row order on one device (row-capacity pad rows are cut; a down-sampled
    batch, a mesh and a batch without tiles take the gather and keep their
    ``_scoring_feats``); validation rows whose grid cannot be tiled keep
    their ``(ids, vals)``.  Every form gives the gather's margins."""
    from photon_tpu.game.coordinate import FixedEffectDeviceData
    from photon_tpu.game.model import DeviceScoringCache, FixedEffectModel
    from photon_tpu.models.glm import Coefficients, model_for_task
    from photon_tpu.ops import block_tiles
    from photon_tpu.telemetry import TelemetrySession

    monkeypatch.setenv(
        "PHOTON_SPARSE_GRAD", "fm" if case == "no_tiles" else "blocked"
    )
    data = _sparse_fixed_game(2, 12)
    n, k = data.shard("global").ids.shape
    config = FixedEffectCoordinateConfig(
        "global",
        ProblemConfig(regularization=RegularizationContext("l2", 1.0)),
        downsampling_rate=0.5 if case == "down_sampled" else 1.0,
    )
    mesh = create_mesh(1) if case == "one_device_mesh" else None
    held = FixedEffectDeviceData(
        data, config, mesh,
        row_capacity=n + 37 if case == "row_capacity" else None,
    )
    assert (held.batch.bt is None) == (case == "no_tiles")
    w = np.random.default_rng(3).standard_normal(300).astype(np.float32)
    model = FixedEffectModel(
        model_for_task("logistic_regression", Coefficients(jnp.asarray(w))),
        "global",
    )
    session = TelemetrySession("t")
    if case == "untileable_validation":
        monkeypatch.setattr(block_tiles, "MAX_CELLS", 1)
        cache = DeviceScoringCache(data, telemetry=session)
        cache.score_fixed_through_tiles("global")
        scored, tiled = cache.score(model, "fixed"), False
        assert "global" in cache._feats and not cache._fixed_tiles
    else:
        coord = FixedEffectCoordinate(
            data, config, "logistic_regression", mesh=mesh, device_data=held
        )
        coord.telemetry = session
        scored = coord.score_device(model)
        tiled = case == "row_capacity"
        assert (held._score_cache_bytes == 0) == tiled
    assert scored.shape == (n,)
    np.testing.assert_allclose(
        np.asarray(scored), _gather_margins(data, model), rtol=1e-5, atol=1e-6
    )
    assert _score_counts(session) == {
        ("score.fixed_dispatches", "blocked" if tiled else "gather"): 1.0,
        ("score.sparse_entries", None): float(n * k),
    }
