"""In-place device-data growth for EXISTING entities (ISSUE 15 blocker
fix): per-bin row-capacity headroom writes, entity migration past
exhausted capacity, absent-row masks, atomicity, and the capacity-headroom
accounting gauges — in isolation from the online service."""

from __future__ import annotations

import numpy as np
import pytest

from photon_tpu.core.objective import RegularizationContext
from photon_tpu.core.optimizers import OptimizerConfig
from photon_tpu.core.problem import ProblemConfig
from photon_tpu.data.synthetic import make_game_data
from photon_tpu.game.coordinate import (
    FixedEffectCoordinateConfig,
    RandomEffectCoordinate,
    RandomEffectCoordinateConfig,
    RandomEffectDeviceData,
)
from photon_tpu.game.data import DenseShard, GameDataset
from photon_tpu.game.estimator import (
    GameEstimator,
    GameOptimizationConfiguration,
)
from photon_tpu.telemetry import TelemetrySession


def _problem(max_iterations=30):
    return ProblemConfig(
        regularization=RegularizationContext("l2", 1.0),
        optimizer_config=OptimizerConfig(max_iterations=max_iterations),
    )


def _config(**kw):
    return RandomEffectCoordinateConfig("pe", "uid", _problem(), **kw)


def _dataset(n_entities, seed, keep=None, fixed=False):
    raw = make_game_data(
        n_entities, 4, 5, 4, seed=seed,
        n_random_coords=1,
    )
    ids = raw["entity_ids"]["re0"]
    sel = slice(None) if keep is None else keep(ids)
    shards = {"pe": DenseShard(raw["x_random"]["re0"][sel])}
    if fixed:
        shards["global"] = DenseShard(raw["x_fixed"][sel])
    return GameDataset.create(
        raw["label"][sel], shards, id_columns={"uid": ids[sel]}
    )


def _grown(base, seed, existing_below=10, new_from=35, n_source=40):
    """Append rows for EXISTING entities (< existing_below) AND NEW
    entities (>= new_from) onto ``base``."""
    raw = make_game_data(n_source, 3, 5, 4, seed=seed, n_random_coords=1)
    ids = raw["entity_ids"]["re0"]
    keep = (ids < existing_below) | (ids >= new_from)
    shards = {"pe": DenseShard(np.concatenate([
        base.shards["pe"].x, raw["x_random"]["re0"][keep]
    ]))}
    if "global" in base.shards:
        shards["global"] = DenseShard(np.concatenate([
            base.shards["global"].x, raw["x_fixed"][keep]
        ]))
    return GameDataset.create(
        np.concatenate([base.label, raw["label"][keep]]),
        shards,
        id_columns={"uid": np.concatenate([base.id_columns["uid"],
                                           ids[keep]])},
    )


def _train(data, config, dd=None):
    coord = RandomEffectCoordinate(
        data, config, "logistic_regression", device_data=dd
    )
    model, stats = coord.train(np.zeros(data.num_examples, np.float32))
    return model, stats


# ---------------------------------------------------------------------------
# Device-data level: grown-in-place fit == full rebuild
# ---------------------------------------------------------------------------


def test_grow_existing_rows_matches_full_rebuild():
    """The blocker fix: appended rows for EXISTING entities scatter into
    the owning bins' row-capacity headroom — and the resulting fit matches
    a full rebuild of the device data ≤1e-5."""
    base = _dataset(30, seed=11)
    grown = _grown(base, seed=12)
    config = _config()
    session = TelemetrySession("t-grow")
    dd = RandomEffectDeviceData(base, config)
    n_bins = len(dd.buckets)
    dd.onboard(grown, telemetry=session)
    model, stats = _train(grown, config, dd)
    rebuilt, _ = _train(grown, config)
    np.testing.assert_array_equal(model.keys, rebuilt.keys)
    np.testing.assert_allclose(
        np.asarray(model.table), np.asarray(rebuilt.table),
        atol=1e-5, rtol=0,
    )
    assert stats["entities"] == dd.dataset.num_entities
    # Growth telemetry: existing-entity rows landed IN PLACE (the base
    # fixture's bins have pow2 headroom) and the new entities appended.
    counters = {
        (m["name"], (m.get("labels") or {}).get("column")): m["value"]
        for m in session.registry.snapshot()["counters"]
    }
    assert counters.get(("onboard.rows_in_place", "uid"), 0) > 0
    assert counters.get(("onboard.entities_new", "uid"), 0) > 0
    # Layout EXTENDED (appended bins for new/migrated entities), never
    # rebuilt from scratch.
    assert len(dd.buckets) >= n_bins


def test_repeated_growth_matches_full_rebuild():
    """Two successive onboards onto the SAME layout (steady-state online
    ingest) still match a from-scratch rebuild."""
    base = _dataset(30, seed=21)
    config = _config()
    dd = RandomEffectDeviceData(base, config)
    g1 = _grown(base, seed=22)
    dd.onboard(g1)
    g2 = _grown(g1, seed=23, existing_below=15, new_from=38, n_source=45)
    dd.onboard(g2)
    model, _ = _train(g2, config, dd)
    rebuilt, _ = _train(g2, config)
    np.testing.assert_allclose(
        np.asarray(model.table), np.asarray(rebuilt.table),
        atol=1e-5, rtol=0,
    )


def test_migration_when_bin_capacity_exhausted():
    """An entity whose appended rows exceed its bin's row capacity
    migrates to an appended bin at the next power of two; its old slot is
    neutralized (dummy index, zero weights) and the fit still matches a
    rebuild."""
    base = _dataset(20, seed=31)
    config = _config()
    dd = RandomEffectDeviceData(base, config)
    # One entity gets a LOT of new rows — guaranteed past any bin's
    # capacity in this fixture.
    rng = np.random.default_rng(7)
    n_new = 64
    grown = GameDataset.create(
        np.concatenate([base.label, (rng.random(n_new) < 0.5).astype(
            np.float32)]),
        {"pe": DenseShard(np.concatenate([
            base.shards["pe"].x,
            rng.normal(size=(n_new, 4)).astype(np.float32),
        ]))},
        id_columns={"uid": np.concatenate([
            base.id_columns["uid"],
            np.full(n_new, base.id_columns["uid"][0], np.int64),
        ])},
    )
    session = TelemetrySession("t-migrate")
    dd.onboard(grown, telemetry=session)
    counters = {
        m["name"]: m["value"]
        for m in session.registry.snapshot()["counters"]
    }
    assert counters.get("onboard.entities_migrated", 0) == 1
    assert counters.get("onboard.rows_migrated", 0) == n_new
    # The migrated entity appears in exactly ONE live slot.
    e = int(np.searchsorted(dd.dataset.keys, base.id_columns["uid"][0]))
    live_slots = sum(
        int((b.entity_index == e).sum()) for b in dd.buckets
    )
    assert live_slots == 1
    model, _ = _train(grown, config, dd)
    rebuilt, _ = _train(grown, config)
    np.testing.assert_allclose(
        np.asarray(model.table), np.asarray(rebuilt.table),
        atol=1e-5, rtol=0,
    )


def test_projected_config_grows_via_migration():
    """Per-bin projections (index_map) cannot accept in-place rows (the
    new rows would invalidate the bucket's feature transform): existing-
    entity growth routes through migration and still matches a rebuild."""
    base = _dataset(25, seed=41)
    grown = _grown(base, seed=42, existing_below=8, new_from=100)
    config = _config(projection="index_map")
    session = TelemetrySession("t-proj")
    dd = RandomEffectDeviceData(base, config)
    dd.onboard(grown, telemetry=session)
    counters = {
        m["name"]: m["value"]
        for m in session.registry.snapshot()["counters"]
    }
    assert counters.get("onboard.rows_in_place", 0) == 0
    assert counters.get("onboard.entities_migrated", 0) > 0
    model, _ = _train(grown, config, dd)
    rebuilt, _ = _train(grown, config)
    np.testing.assert_allclose(
        np.asarray(model.table), np.asarray(rebuilt.table),
        atol=1e-5, rtol=0,
    )


def test_active_row_cap_growth_stays_unbiased_and_finite():
    """Entities pushed past ``active_row_cap`` migrate with a per-entity
    seeded re-subsample and the cap's weight correction; the fit stays
    finite and covers every entity."""
    base = _dataset(25, seed=51)
    grown = _grown(base, seed=52, existing_below=8, new_from=100)
    config = _config(active_row_cap=4)
    dd = RandomEffectDeviceData(base, config)
    dd.onboard(grown)
    model, stats = _train(grown, config, dd)
    assert np.isfinite(np.asarray(model.table)).all()
    assert stats["entities"] == dd.dataset.num_entities
    # Unbiasedness accounting: a capped entity's kept rows carry the
    # count/cap correction.
    e = int(np.searchsorted(dd.dataset.keys, 0))
    total = int((dd.dataset.entity_idx_per_row == e).sum())
    if total > 4:
        for b in dd.buckets:
            slot = np.nonzero(b.entity_index == e)[0]
            if len(slot):
                w = b.row_weight[slot[0]]
                np.testing.assert_allclose(
                    w[w > 0], total / 4.0, rtol=1e-6
                )


def test_absent_rows_join_no_entity():
    """Rows masked absent (the online ingest's missing-id fill) keep
    per-row entity index -1 and no bin membership."""
    base = _dataset(20, seed=61)
    grown = _grown(base, seed=62)
    n_tail = grown.num_examples - base.num_examples
    dd = RandomEffectDeviceData(base, _config())
    dd.onboard(grown, absent_tail=np.ones(n_tail, bool))
    assert dd.dataset.num_entities == 20
    assert (dd.dataset.entity_idx_per_row[base.num_examples:] == -1).all()
    # Fit unchanged vs the base layout (the absent rows are invisible).
    model, _ = _train(grown, _config(), dd)
    base_model, _ = _train(base, _config())
    np.testing.assert_allclose(
        np.asarray(model.table), np.asarray(base_model.table),
        atol=1e-6, rtol=0,
    )


def test_capacity_headroom_gauges():
    """The onboard publishes per-bin capacity/live/headroom gauges — the
    accounting that says how much room the next append has."""
    base = _dataset(20, seed=71)
    grown = _grown(base, seed=72)
    session = TelemetrySession("t-headroom")
    dd = RandomEffectDeviceData(base, _config())
    dd.onboard(grown, telemetry=session)
    gauges = {
        (m["name"], (m.get("labels") or {}).get("bin")): m["value"]
        for m in session.registry.snapshot()["gauges"]
        if m["name"].startswith("onboard.bin_")
    }
    assert gauges, "no headroom gauges published"
    for i, st in enumerate(dd.bin_stats):
        cells = st["capacity"] * st["total_entities"]
        assert gauges[("onboard.bin_row_capacity", str(i))] == cells
        assert gauges[("onboard.bin_rows_live", str(i))] == st["live_rows"]
        assert gauges[("onboard.bin_row_headroom", str(i))] == (
            cells - st["live_rows"]
        )
        # Live rows actually live in the blocks (the gauge is honest).
        n_e = dd.dataset.num_entities
        live = sum(
            int((b.row_weight[b.entity_index < n_e] > 0).sum())
            for j, b in enumerate(dd.buckets) if j == i
        )
        assert live == st["live_rows"]


# ---------------------------------------------------------------------------
# Estimator level
# ---------------------------------------------------------------------------


def test_estimator_growth_matches_fresh_estimator():
    """Estimator-level: onboard (grown in place) + warm-started fit ==
    fresh estimator on the merged data + the same warm start, ≤1e-5 —
    with ZERO random-layout rebuilds counted."""
    from photon_tpu.game.model import GameModel

    base = _dataset(30, seed=81, fixed=True)
    grown = _grown(base, seed=82)
    config = GameOptimizationConfiguration(
        coordinates={
            "fixed": FixedEffectCoordinateConfig("global", _problem()),
            "per_entity": _config(),
        },
        descent_iterations=2,
    )
    session = TelemetrySession("t-est-grow")
    estimator = GameEstimator("logistic_regression", base,
                              telemetry=session)
    first = estimator.fit([config])[0]
    estimator.onboard_training_data(grown)
    dd = estimator._device_data_cache[
        config.coordinates["per_entity"].data_key
    ]
    warm = GameModel(
        {
            "fixed": first.model.coordinate("fixed"),
            "per_entity": first.model.coordinate("per_entity")
            .with_entities(dd.dataset.keys),
        },
        "logistic_regression",
    )
    second = estimator.fit([config], initial_model=warm)[0]
    fresh = GameEstimator("logistic_regression", grown).fit(
        [config], initial_model=warm
    )[0]
    for name in config.coordinates:
        got, want = second.model.coordinate(name), fresh.model.coordinate(name)
        got_t = getattr(got, "table", None)
        if got_t is None:
            got_t = got.coefficients.means
            want_t = want.coefficients.means
        else:
            want_t = want.table
        np.testing.assert_allclose(
            np.asarray(got_t), np.asarray(want_t), atol=1e-5, rtol=0
        )
    counters = [
        (m["name"], (m.get("labels") or {}).get("kind"), m["value"])
        for m in session.registry.snapshot()["counters"]
        if m["name"] == "estimator.device_data_rebuilds"
    ]
    assert not any(kind == "random" for _, kind, _ in counters)
    assert any(kind == "fixed" for _, kind, _ in counters)


def test_estimator_growth_is_atomic_on_rejected_batch():
    """Bin-migration atomicity: a batch one coordinate must reject (its
    feature shard is missing from the grown data) mutates NOTHING — the
    other coordinate's layout is not grown first."""
    raw = make_game_data(20, 4, 5, 4, seed=5, n_random_coords=2)
    base = GameDataset.create(
        raw["label"],
        {"re0": DenseShard(raw["x_random"]["re0"]),
         "re1": DenseShard(raw["x_random"]["re1"])},
        id_columns={"re0": raw["entity_ids"]["re0"],
                    "re1": raw["entity_ids"]["re1"]},
    )
    n_new = 6
    # Grown data LACKS re1's shard: the per-item layout must reject.
    grown = GameDataset.create(
        np.concatenate([base.label, base.label[:n_new]]),
        {"re0": DenseShard(np.concatenate([
            base.shards["re0"].x, base.shards["re0"].x[:n_new]
        ]))},
        id_columns={
            name: np.concatenate([col, col[:n_new]])
            for name, col in base.id_columns.items()
        },
    )
    config = GameOptimizationConfiguration(
        coordinates={
            "per_user": RandomEffectCoordinateConfig(
                "re0", "re0", _problem(5)
            ),
            "per_item": RandomEffectCoordinateConfig(
                "re1", "re1", _problem(5)
            ),
        },
        descent_iterations=1,
    )
    estimator = GameEstimator("logistic_regression", base)
    estimator.fit([config])
    with pytest.raises(KeyError, match="re1"):
        estimator.onboard_training_data(grown)
    for dd in estimator._device_data_cache.values():
        assert dd.dataset.num_entities == 20
        assert len(dd.dataset.entity_idx_per_row) == base.num_examples
    assert estimator.training_data is base
    estimator.fit([config])


def test_onboard_still_rejects_shrunk_data_and_bad_mask():
    base = _dataset(20, seed=91)
    dd = RandomEffectDeviceData(base, _config())
    from photon_tpu.game.data import take_rows

    with pytest.raises(ValueError, match="GROWN"):
        dd.onboard(take_rows(base, np.arange(base.num_examples - 5)))
    grown = _grown(base, seed=92)
    with pytest.raises(ValueError, match="absent_tail"):
        dd.onboard(grown, absent_tail=np.ones(3, bool))
    # Nothing mutated by the rejections.
    assert dd.dataset.num_entities == 20
    assert len(dd.dataset.entity_idx_per_row) == base.num_examples


def test_onboard_rejects_layout_kind_mismatch_before_mutating():
    """A dense appended shard over a sparse-built layout (or vice versa)
    is refused in check_onboard — BEFORE any remap/write — instead of
    crashing mid-apply with a half-mutated layout."""
    from photon_tpu.game.data import SparseShard

    rng = np.random.default_rng(5)
    n = 40
    sparse = SparseShard(
        rng.integers(0, 6, (n, 3)).astype(np.int32),
        rng.standard_normal((n, 3)).astype(np.float32),
        6,
    )
    base = GameDataset.create(
        (rng.random(n) < 0.5).astype(np.float32),
        {"pe": sparse},
        id_columns={"uid": np.repeat(np.arange(10, dtype=np.int64), 4)},
    )
    cfg = RandomEffectCoordinateConfig("pe", "uid", _problem())
    dd = RandomEffectDeviceData(base, cfg)
    keys_before = dd.dataset.keys
    grown = GameDataset.create(
        np.concatenate([base.label, base.label[:4]]),
        {"pe": DenseShard(np.zeros((n + 4, 6), np.float32))},  # DENSE
        id_columns={"uid": np.concatenate([
            base.id_columns["uid"],
            np.arange(100, 104, dtype=np.int64),
        ])},
    )
    with pytest.raises(ValueError, match="dense"):
        dd.onboard(grown)
    # Nothing mutated: same vocabulary object, same per-row map length.
    assert dd.dataset.keys is keys_before
    assert len(dd.dataset.entity_idx_per_row) == base.num_examples


def test_fixed_batch_row_capacity_zero_recompiles_across_refresh():
    """ISSUE 18 satellite: the fixed-effect training batch carries
    row-capacity headroom (weight-0 pad rows, amortized doubling), so an
    online refresh whose grown row count still fits the capacity rebuilds
    the batch at the SAME padded shape — the solve programs compiled
    against it stay hot (ZERO compile events on the refreshed train) —
    and the pad rows are exact (the padded fit matches an unpadded one).

    Pinned at the COORDINATE level: the descent loop's residual/validation
    engines are sized off the true row count by design (their elementwise
    kernels recompile cheaply per refresh); the expensive artifact this
    satellite protects is the fixed-effect BATCH and its solve."""
    import jax.monitoring

    from photon_tpu.game.coordinate import (
        FixedEffectCoordinate,
        FixedEffectDeviceData,
    )
    from photon_tpu.utils import pow2_at_least

    base = _dataset(30, seed=71, fixed=True)
    g1 = _grown(base, seed=72)
    g2 = _grown(g1, seed=73)
    cfg = FixedEffectCoordinateConfig("global", _problem())
    cap = max(pow2_at_least(g1.num_examples), 2 * base.num_examples)
    assert g2.num_examples <= cap  # the refresh lands inside the headroom

    def train_at(data, row_capacity):
        dd = FixedEffectDeviceData(data, cfg, row_capacity=row_capacity)
        coord = FixedEffectCoordinate(
            data, cfg, "logistic_regression", device_data=dd
        )
        model, _ = coord.train(np.zeros(data.num_examples, np.float32))
        return dd, model

    dd1, _ = train_at(g1, cap)
    assert dd1.batch.num_examples == cap
    assert dd1.unpadded_n == g1.num_examples

    events = []

    def listener(event, **kwargs):
        if "compile" in event:
            events.append(event)

    jax.monitoring.register_event_listener(listener)
    try:
        # The refresh: MORE rows, SAME capacity — same batch shape, so
        # the rebuilt batch replays entirely against compiled programs.
        dd2, padded = train_at(g2, cap)
    finally:
        jax.monitoring.unregister_event_listener(listener)
    assert events == []
    assert dd2.batch.num_examples == cap
    assert dd2.unpadded_n == g2.num_examples

    # Pad rows are weight-0 and therefore EXACT: the capacity-padded fit
    # equals the unpadded fit on the same data.
    _, unpadded = train_at(g2, None)
    np.testing.assert_allclose(
        np.asarray(padded.coefficients.means),
        np.asarray(unpadded.coefficients.means),
        atol=1e-5, rtol=0,
    )


def test_estimator_fixed_row_capacity_amortized_doubling():
    """The estimator's capacity policy: the FIRST build is exact (no
    padding — existing single-fit flows see unchanged shapes); the first
    growth sets an amortized-doubled capacity; a later onboard that fits
    rebuilds at the SAME capacity (the coordinate-level zero-recompile
    contract above is what that buys)."""
    base = _dataset(30, seed=71, fixed=True)
    g1 = _grown(base, seed=72)
    g2 = _grown(g1, seed=73)
    config = GameOptimizationConfiguration(
        coordinates={
            "fixed": FixedEffectCoordinateConfig("global", _problem()),
        },
        descent_iterations=1,
    )
    estimator = GameEstimator("logistic_regression", base)
    estimator.fit([config])
    fixed_key = config.coordinates["fixed"].data_key
    assert estimator._fixed_row_capacity == {}  # no growth yet: exact
    batch0 = estimator._device_data_cache[fixed_key].batch
    assert batch0.num_examples == base.num_examples

    estimator.onboard_training_data(g1)
    estimator.fit([config])  # pays the ONE growth rebuild, sets capacity
    cap1 = estimator._fixed_row_capacity[fixed_key]
    dd1 = estimator._device_data_cache[fixed_key]
    assert cap1 >= g1.num_examples
    assert dd1.batch.num_examples == cap1
    assert dd1.unpadded_n == g1.num_examples
    assert g2.num_examples <= cap1

    estimator.onboard_training_data(g2)
    estimator.fit([config])
    assert estimator._fixed_row_capacity[fixed_key] == cap1
    dd2 = estimator._device_data_cache[fixed_key]
    assert dd2.batch.num_examples == cap1  # SAME padded shape
    assert dd2.unpadded_n == g2.num_examples
