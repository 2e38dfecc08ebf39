"""Out-of-core GAME training (ISSUE 10): tiled score tables, the
double-buffered chunk streamer, the streamed epoch-style descent.

Contracts pinned here:

- per-chunk Neumaier partials reduce to the resident engine's global
  total (chunking never changes an offset or composite value);
- streamed-vs-resident fit parity ≤ 1e-4 against BOTH residual modes
  (linear task; the logistic fixture sits at the chunked-accumulation
  solver floor and gets its own documented bound);
- chunk-boundary edge cases: a partial last chunk, an exactly-divisible
  plan, and the single-chunk degenerate plan all converge to the same fit;
- mid-epoch ``descent:kill`` → ``--resume auto`` reproduces the
  uninterrupted streamed fit EXACTLY (chunk cursor + tile digests);
- device residency stays inside the chunk window
  (``residuals.device_bytes`` = streamer in-flight peak ≤ (prefetch+1) ×
  chunk bytes) and the prefetch telemetry records real overlap;
- the driver's ``--stream-chunks`` / ``--max-resident-mb`` auto-enable;
- the first-hit foreign-vocabulary warm-start join prefetches on the io
  pool (satellite).
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from photon_tpu.core.objective import RegularizationContext
from photon_tpu.core.optimizers import OptimizerConfig
from photon_tpu.core.problem import ProblemConfig
from photon_tpu.data.synthetic import make_game_dataset
from photon_tpu.game.coordinate import (
    FixedEffectCoordinateConfig,
    RandomEffectCoordinateConfig,
)
from photon_tpu.game.data import split_game_dataset
from photon_tpu.game.estimator import (
    GameEstimator,
    GameOptimizationConfiguration,
)
from photon_tpu.game.tiles import (
    PREFETCH_DEPTH,
    ChunkPlan,
    ChunkStreamer,
    TiledResidualTable,
    chunk_rows_for_budget,
    per_row_bytes,
    resident_bytes_estimate,
)
from photon_tpu.telemetry import TelemetrySession

CHUNK = 37  # deliberately not a divisor of the row count: partial last chunk


def _problem(lam, max_iters=80):
    # Tight tolerances: parity tests compare two solver implementations
    # (jitted vs streamed-host-loop L-BFGS) at their common optimum — the
    # tighter both converge, the tighter they agree.
    return ProblemConfig(
        regularization=RegularizationContext("l2", lam),
        optimizer_config=OptimizerConfig(
            max_iterations=max_iters, tolerance=1e-11,
            gradient_tolerance=1e-8,
        ),
    )


def _config(iters=2):
    return GameOptimizationConfiguration(
        coordinates={
            "fixed": FixedEffectCoordinateConfig("global", _problem(1.0)),
            "re0": RandomEffectCoordinateConfig("re0", "re0", _problem(1.0)),
        },
        descent_iterations=iters,
        name="ooc",
    )


@pytest.fixture(scope="module")
def game_data():
    data, _ = make_game_dataset(100, 5, 6, 3, seed=0, n_random_coords=1)
    return split_game_dataset(data, 0.25, seed=1)


@pytest.fixture(scope="module")
def fits(game_data):
    """One linear-task fit per mode (device / host / streamed), shared by
    the parity tests."""
    train, val = game_data
    out = {}
    for mode, kwargs in (
        ("device", {"residual_mode": "device"}),
        ("host", {"residual_mode": "host"}),
        ("stream", {"stream_chunks": CHUNK}),
    ):
        out[mode] = GameEstimator(
            "linear_regression", train, validation_data=val, **kwargs
        ).fit([_config()])[0]
    return out


# -- chunk plan + tiled-table unit contracts ---------------------------------

def test_chunk_plan_partial_and_degenerate():
    plan = ChunkPlan(100, 37)
    assert plan.num_chunks == 3
    assert [plan.bounds(k) for k in range(3)] == [(0, 37), (37, 74), (74, 100)]
    assert plan.rows(2) == 26  # partial last chunk
    exact = ChunkPlan(100, 25)
    assert exact.num_chunks == 4 and exact.rows(3) == 25
    one = ChunkPlan(100, 1000)  # single-chunk degenerate
    assert one.num_chunks == 1 and one.bounds(0) == (0, 100)
    with pytest.raises(IndexError):
        plan.bounds(3)
    with pytest.raises(ValueError):
        ChunkPlan(10, 0)


def test_budget_helpers(game_data):
    train, _ = game_data
    rb = per_row_bytes(train)
    n = train.num_examples
    assert rb > 0
    # Feature blocks ×2 (training + scoring cache) + the two [C, n] score
    # tables at the given coordinate count.
    assert resident_bytes_estimate(train) == 2 * rb * n + 2 * 2 * n * 4
    assert resident_bytes_estimate(train, n_coordinates=3) == (
        2 * rb * n + 2 * 3 * n * 4
    )
    rows = chunk_rows_for_budget(train, 0.01)
    # The in-flight window — (prefetch + 1) chunks — fits the budget.
    assert (PREFETCH_DEPTH + 1) * rows * rb <= 0.01 * (1 << 20) or rows == 1
    assert chunk_rows_for_budget(train, 1e9) == train.num_examples
    with pytest.raises(ValueError):
        chunk_rows_for_budget(train, 0)


def test_tiled_partials_match_unchunked_totals():
    """The per-chunk Neumaier partials concatenate to the SAME offsets and
    composite a single-chunk (resident-equivalent) table produces — the
    chunk partition is numerically invisible."""
    rng = np.random.default_rng(0)
    n = 101
    base = rng.standard_normal(n).astype(np.float32)
    scores = {
        "a": rng.standard_normal(n).astype(np.float32) * 100,
        "b": rng.standard_normal(n).astype(np.float32),
        "c": rng.standard_normal(n).astype(np.float32) * 0.01,
    }
    tiled = TiledResidualTable(base, ["a", "b", "c"], ChunkPlan(n, 17))
    whole = TiledResidualTable(base, ["a", "b", "c"], ChunkPlan(n, n))
    for name, s in scores.items():
        tiled.update(name, s)
        whole.update(name, s)
    for name in scores:
        np.testing.assert_array_equal(
            tiled.offsets_full(name), whole.offsets_full(name)
        )
        np.testing.assert_array_equal(
            np.concatenate([
                tiled.offsets_chunk(name, k)
                for k in range(tiled.num_chunks)
            ]),
            whole.offsets_full(name),
        )
    np.testing.assert_array_equal(
        tiled.composite_full(), whole.composite_full()
    )
    # The compensated total carries ~f64 precision for the f32 rows.
    want = base.astype(np.float64) + sum(
        s.astype(np.float64) for s in scores.values()
    )
    np.testing.assert_allclose(
        tiled.composite_full(), want, rtol=1e-6, atol=1e-5
    )


def test_tiled_table_guard_and_snapshot_roundtrip():
    base = np.zeros(10, np.float32)
    table = TiledResidualTable(base, ["a", "b"], ChunkPlan(10, 4))
    good = np.arange(10, dtype=np.float32)
    table.update("a", good)
    bad = good.copy()
    bad[3] = np.nan
    table.update("b", bad)
    assert table.poll_quarantined() == ["b"]
    # Rejected row kept its previous (zero) state.
    np.testing.assert_array_equal(table.scores_for("b"), np.zeros(10))
    snap = table.snapshot_rows()
    restored = TiledResidualTable(base, ["a", "b"], ChunkPlan(10, 4))
    restored.load_rows(snap)
    np.testing.assert_array_equal(restored.scores_for("a"), good)
    assert restored.tile_digests() == table.tile_digests()
    # A changed tile changes its chunk's digest (and only its chunk's).
    table.update("a", good + 1)
    assert table.tile_digests() != restored.tile_digests()


def test_chunk_streamer_orders_and_measures():
    session = TelemetrySession("t-streamer")
    streamer = ChunkStreamer(session, prefetch=2)
    import time as _time

    def load(k):
        _time.sleep(0.002)
        return np.full(8, k, np.float32)

    out = list(streamer.stream(load, 7))
    assert [int(a[0]) for a in out] == list(range(7))
    snap = session.registry.snapshot()
    counters = {m["name"]: m["value"] for m in snap["counters"]}
    assert counters["stream.chunks"] == 7
    assert counters["stream.stall_s"] >= 0
    # With 2 workers prefetching 2ms loads, SOME load time hides behind
    # the consumer.
    assert counters["stream.prefetch_overlap_s"] > 0
    assert streamer.peak_in_flight_bytes >= 32


# -- streamed-vs-resident fit parity -----------------------------------------

def test_streamed_fit_matches_resident_both_modes(fits, game_data):
    """The ISSUE 10 acceptance bar: streamed GAME ≤ 1e-4 from the resident
    fit, against BOTH residual modes — on validation metrics and RMS score
    parity.  Worst-case single-row |Δscore| sits at the floor set by
    comparing two L-BFGS implementations (jitted whole-batch vs streamed
    host-loop) stopping on the f32 value plateau (~2e-4 here; see ROADMAP
    'Out-of-core GAME' edge (d)) and is pinned at 5e-4 so a real
    regression — wrong offsets, corrupted tiles — still fails loudly."""
    _, val = game_data
    stream = fits["stream"].model.score(val)
    for mode in ("device", "host"):
        resident = fits[mode].model.score(val)
        diff = resident - stream
        assert float(np.sqrt(np.mean(diff * diff))) <= 1e-4, mode
        assert np.abs(diff).max() <= 5e-4, mode
        for name, value in fits[mode].metrics.items():
            assert abs(value - fits["stream"].metrics[name]) <= 1e-4, (
                mode, name,
            )


def test_streamed_logistic_fit_tracks_resident(game_data):
    """Logistic parity now sits at the TWO-SOLVER f32 plateau floor
    (~4–6e-4 on this fixture): the ISSUE 11 Neumaier-compensated f64
    cross-chunk value+grad accumulator removed the chunk-count drift the
    ROADMAP flagged (the streamed fit is now identical across chunk
    sizes), so the pin tightens 2e-3 → 1e-3; the remainder is the two
    L-BFGS implementations stopping on the f32 value plateau, not the
    chunked accumulation."""
    train, val = game_data
    config = _config()
    resident = GameEstimator(
        "logistic_regression", train, validation_data=val,
        residual_mode="device",
    ).fit([config])[0]
    streamed = GameEstimator(
        "logistic_regression", train, validation_data=val,
        stream_chunks=CHUNK,
    ).fit([config])[0]
    diff = np.abs(
        resident.model.score(val) - streamed.model.score(val)
    ).max()
    assert diff <= 1e-3, diff


def test_single_chunk_and_divisible_plans_match_partial_chunk_fit(game_data):
    """Chunk-boundary edges: the single-chunk degenerate plan and an
    exactly-divisible plan produce the same streamed fit as the
    partial-last-chunk plan up to the chunk-accumulation floor (for the
    linear task the per-chunk sums re-associate only across chunk
    boundaries)."""
    train, val = game_data
    config = _config()

    def fit(chunk_rows):
        return GameEstimator(
            "linear_regression", train, validation_data=val,
            stream_chunks=chunk_rows,
        ).fit([config])[0].model.score(val)

    partial = fit(CHUNK)                      # 37 ∤ n: partial last chunk
    single = fit(train.num_examples + 10)     # one chunk == resident shape
    divisible = fit(25)
    assert np.abs(partial - single).max() <= 1e-4
    assert np.abs(partial - divisible).max() <= 1e-4


# -- mid-epoch kill -> resume ------------------------------------------------

def test_mid_epoch_kill_then_resume_exact(game_data, tmp_path):
    from photon_tpu.fault.injection import (
        FaultPlan,
        InjectedKillError,
        set_plan,
    )

    train, val = game_data
    config = _config(iters=2)

    def estimator():
        return GameEstimator(
            "linear_regression", train, validation_data=val,
            stream_chunks=CHUNK,
        )

    baseline = estimator().fit([config])[0]
    ck = str(tmp_path / "ck")
    # Kill MID-EPOCH: before coordinate re0 of iteration 1 — the fixed
    # effect of iteration 1 has already trained and checkpointed.
    set_plan(FaultPlan.parse("descent:kill:iter=1:coord=re0"))
    try:
        with pytest.raises(InjectedKillError):
            estimator().fit([config], checkpoint_dir=ck, resume="auto")
    finally:
        set_plan(None)
    # The published chain holds a MID-EPOCH snapshot: cursor > 0, tile
    # digests stamped.
    from photon_tpu.fault.checkpoint import DescentCheckpointer

    ckpt = DescentCheckpointer(os.path.join(ck, "cfg-000"))
    state = ckpt.load("latest")
    assert state.stream is not None
    assert state.stream["cursor"] == 1
    assert state.stream["chunk_rows"] == CHUNK
    assert len(state.stream["tile_digests"]) == ChunkPlan(
        train.num_examples, CHUNK
    ).num_chunks
    assert not state.completed

    resumed = estimator().fit([config], checkpoint_dir=ck, resume="auto")[0]
    np.testing.assert_array_equal(
        baseline.model.score(val), resumed.model.score(val)
    )
    assert baseline.metrics == resumed.metrics
    np.testing.assert_array_equal(
        baseline.model.score(train), resumed.model.score(train)
    )


def test_stream_checkpoint_refuses_other_chunk_size(game_data, tmp_path):
    """chunk_rows is part of the streamed fingerprint: a checkpoint written
    under one chunk size cannot silently resume under another (the
    accumulation order would change)."""
    from photon_tpu.fault.checkpoint import CheckpointError
    from photon_tpu.fault.injection import (
        FaultPlan,
        InjectedKillError,
        set_plan,
    )

    train, val = game_data
    config = _config(iters=2)
    ck = str(tmp_path / "ck")
    set_plan(FaultPlan.parse("descent:kill:iter=1"))
    try:
        with pytest.raises(InjectedKillError):
            GameEstimator(
                "linear_regression", train, validation_data=val,
                stream_chunks=CHUNK,
            ).fit([config], checkpoint_dir=ck, resume="auto")
    finally:
        set_plan(None)
    with pytest.raises(CheckpointError, match="fingerprint"):
        GameEstimator(
            "linear_regression", train, validation_data=val,
            stream_chunks=CHUNK + 5,
        ).fit([config], checkpoint_dir=ck, resume="auto")


# -- device-residency bound + telemetry --------------------------------------

def test_streamed_device_bytes_bounded_by_chunk_window(game_data):
    train, val = game_data
    session = TelemetrySession("t-ooc")
    estimator = GameEstimator(
        "linear_regression", train, validation_data=val,
        stream_chunks=CHUNK, telemetry=session,
    )
    estimator.fit([_config()])
    snap = session.registry.snapshot()
    gauges = {
        m["name"]: m["value"] for m in snap["gauges"] if not m["labels"]
    }
    counters = {
        m["name"]: m["value"] for m in snap["counters"] if not m["labels"]
    }
    tiered = {
        (m["name"], m["labels"].get("tier")): m["value"]
        for m in snap["counters"] if "tier" in m["labels"]
    }
    assert counters["stream.chunks"] > 0
    assert ("stream.stall_s", "h2d") in tiered
    assert ("stream.prefetch_overlap_s", "h2d") in tiered
    # The acceptance bound: peak in-flight device residency stays inside
    # the (prefetch + 1)-chunk window of the budget.  Entity sub-blocks
    # are sized by the same budget, so the whole streamed fit obeys it.
    bound = (PREFETCH_DEPTH + 1) * CHUNK * per_row_bytes(train)
    assert 0 < gauges["residuals.device_bytes"] <= bound
    assert estimator._streamer.peak_in_flight_bytes == (
        gauges["residuals.device_bytes"]
    )


# -- estimator / coordinate gates --------------------------------------------

def test_stream_mode_gates(game_data):
    train, val = game_data
    with pytest.raises(ValueError, match="stream_chunks"):
        GameEstimator("linear_regression", train, stream_chunks=-1)
    with pytest.raises(ValueError, match="stream_chunks"):
        GameEstimator("linear_regression", train, stream_chunks=0)
    # An explicitly requested resident engine must not be silently
    # replaced by the tiled tables.
    with pytest.raises(ValueError, match="residual"):
        GameEstimator(
            "linear_regression", train, residual_mode="host",
            stream_chunks=CHUNK,
        )
    # Unsupported resident-only features fail loudly at build time.
    cases = [
        ({"fixed": FixedEffectCoordinateConfig(
            "global", _problem(0.1), downsampling_rate=0.5)},
         "downsampling"),
        ({"fixed": FixedEffectCoordinateConfig(
            "global", ProblemConfig(
                optimizer="tron",
                regularization=RegularizationContext("l2", 0.1)))},
         "lbfgs"),
        ({"re0": RandomEffectCoordinateConfig(
            "re0", "re0", _problem(1.0), projection="random",
            projected_dim=2)},
         "projection"),
    ]
    for coords, match in cases:
        est = GameEstimator(
            "linear_regression", train, validation_data=val,
            stream_chunks=CHUNK,
        )
        with pytest.raises(ValueError, match=match):
            est.fit([GameOptimizationConfiguration(
                coordinates=coords, descent_iterations=1, name="bad"
            )])


# -- driver integration ------------------------------------------------------

def test_train_game_stream_chunks_driver(tmp_path):
    from photon_tpu.drivers import train_game

    out = tmp_path / "out"
    summary = train_game.run(train_game.build_parser().parse_args([
        "--input", "synthetic-game:60:4:6:3",
        "--task", "linear_regression",
        "--coordinate", "fixed:type=fixed,shard=global,max_iters=25",
        "--coordinate", "re0:type=random,shard=re0,entity=re0,max_iters=25",
        "--descent-iterations", "1",
        "--validation-split", "0.25",
        "--stream-chunks", "53",
        "--output-dir", str(out),
    ]))
    assert summary["best_metrics"]
    assert (out / "best_model").is_dir()


def test_train_game_max_resident_mb_auto_enables(tmp_path):
    """A budget the dataset exceeds auto-enables streaming with a fitted
    chunk size; a generous budget keeps the resident path."""
    import json

    from photon_tpu.drivers import train_game

    def run(budget_mb, out):
        return train_game.run(train_game.build_parser().parse_args([
            "--input", "synthetic-game:60:4:6:3",
            "--task", "linear_regression",
            "--coordinate", "fixed:type=fixed,shard=global,max_iters=25",
            "--coordinate",
            "re0:type=random,shard=re0,entity=re0,max_iters=25",
            "--descent-iterations", "1",
            "--validation-split", "0.25",
            "--max-resident-mb", str(budget_mb),
            "--output-dir", str(out),
        ]))

    run(0.01, tmp_path / "small")  # far under the resident estimate
    with open(
        tmp_path / "small" / "telemetry" / "run_report.json"
    ) as f:
        report = json.load(f)
    gauges = {m["name"]: m["value"] for m in report["metrics"]["gauges"]}
    assert gauges["stream.chunk_rows"] >= 1
    counters = {m["name"] for m in report["metrics"]["counters"]}
    assert "stream.chunks" in counters

    run(10_000, tmp_path / "big")  # generous budget: resident path
    with open(tmp_path / "big" / "telemetry" / "run_report.json") as f:
        report = json.load(f)
    gauges = {m["name"]: m["value"] for m in report["metrics"]["gauges"]}
    assert "stream.chunk_rows" not in gauges


# -- warm-start join prefetch (satellite) ------------------------------------

def test_warm_join_prefetch_overlaps_and_matches(game_data):
    from photon_tpu.game.coordinate import (
        RandomEffectCoordinate,
        _align_foreign_table,
        prefetch_warm_joins,
    )
    from photon_tpu.game.model import GameModel, RandomEffectModel

    train, _ = game_data
    coord = RandomEffectCoordinate(
        train, RandomEffectCoordinateConfig("re0", "re0", _problem(1.0)),
        "linear_regression",
    )
    coord.telemetry = TelemetrySession("t-warmjoin")
    # A FOREIGN vocabulary: the run's keys plus one unseen entity, as a
    # fresh array object (identity check must miss).
    foreign_keys = np.unique(np.concatenate(
        [coord.dataset.keys, np.asarray(["zzz-unseen"])]
    ))
    rng = np.random.default_rng(0)
    foreign = RandomEffectModel(
        table=rng.standard_normal(
            (len(foreign_keys), coord.dim)
        ).astype(np.float32),
        keys=foreign_keys, entity_column="re0", shard_name="re0",
        task_type="linear_regression",
    )
    # Un-prefetched reference result first, on a twin coordinate.
    twin = RandomEffectCoordinate(
        train, RandomEffectCoordinateConfig("re0", "re0", _problem(1.0)),
        "linear_regression",
    )
    want = _align_foreign_table(twin, foreign)

    scheduled = prefetch_warm_joins(
        {"re0": coord},
        GameModel({"re0": foreign}, "linear_regression"),
    )
    assert scheduled == 1
    from concurrent.futures import Future

    cached = coord.device_data._warm_join_cache[id(foreign.keys)]
    assert isinstance(cached[1], Future)
    got = _align_foreign_table(coord, foreign)
    np.testing.assert_array_equal(got, want)
    # The future resolved into the cache; a second align is a pure hit.
    cached = coord.device_data._warm_join_cache[id(foreign.keys)]
    assert isinstance(cached[1], np.ndarray)
    # Same-vocabulary models schedule nothing.
    own = RandomEffectModel(
        table=np.zeros((coord.dataset.num_entities, coord.dim), np.float32),
        keys=coord.dataset.keys, entity_column="re0", shard_name="re0",
        task_type="linear_regression",
    )
    assert prefetch_warm_joins(
        {"re0": coord}, GameModel({"re0": own}, "linear_regression")
    ) == 0


# -- disk-backed tile store (ISSUE 11) ---------------------------------------

def _spilled_estimator(train, val, spill_dir, **kwargs):
    return GameEstimator(
        "linear_regression", train, validation_data=val,
        stream_chunks=CHUNK, spill_dir=str(spill_dir), **kwargs,
    )


@pytest.fixture(scope="module")
def spilled_fit(game_data, tmp_path_factory):
    """One spilled fit under a host budget of ~1.5 feature chunks: big
    enough that no single entry exceeds the budget (the gauge bound is
    strict), small enough that streaming all chunks + tiles MUST evict."""
    train, val = game_data
    spill_dir = tmp_path_factory.mktemp("tile_store")
    session = TelemetrySession("t-spilled-fit")
    budget_bytes = int(1.5 * CHUNK * per_row_bytes(train))
    result = _spilled_estimator(
        train, val, spill_dir, max_host_mb=budget_bytes / (1 << 20),
        telemetry=session,
    ).fit([_config()])[0]
    return result, session, spill_dir, budget_bytes


def test_spilled_fit_matches_host_resident_streamed_bitwise(
    spilled_fit, fits, game_data
):
    """The ISSUE 11 acceptance bar: a spilled streamed fit is
    BIT-IDENTICAL to the host-resident streamed fit — the disk roundtrip
    and the cache/eviction churn change nothing."""
    train, val = game_data
    result, _, _, _ = spilled_fit
    host = fits["stream"]
    for name, host_model in host.model.coordinates.items():
        sp_model = result.model.coordinates[name]
        if hasattr(host_model, "table"):
            assert np.array_equal(
                np.asarray(host_model.table), np.asarray(sp_model.table)
            ), name
        else:
            assert np.array_equal(
                np.asarray(host_model.model.coefficients.means),
                np.asarray(sp_model.model.coefficients.means),
            ), name
    np.testing.assert_array_equal(
        host.model.score(val), result.model.score(val)
    )
    for name, value in host.metrics.items():
        assert abs(value - result.metrics[name]) <= 1e-6, name


def test_spilled_tiles_on_disk_match_recomputation(
    spilled_fit, game_data
):
    """The PUBLISHED tiles equal a bit-exact recomputation from the final
    models (write-through write-back worked; roundtrip lossless)."""
    from photon_tpu.game.tile_store import TileStore
    from photon_tpu.game.tiles import RESIDUAL_TILE_KIND as TILES
    from photon_tpu.game.tiles import score_model_chunks

    train, _ = game_data
    result, _, spill_dir, _ = spilled_fit
    plan = ChunkPlan(train.num_examples, CHUNK)
    store = TileStore(str(spill_dir))
    last = result.descent.last_model.coordinates
    names = list(last)
    oracle = ChunkStreamer()
    rows = {
        name: score_model_chunks(last[name], train, plan, oracle)
        for name in names
    }
    for k in range(plan.num_chunks):
        arrays, meta = store.read(TILES, k)
        lo, hi = plan.bounds(k)
        want = np.stack([rows[name][lo:hi] for name in names])
        assert np.array_equal(arrays["tile"], want), k
        assert len(meta["tile_digest"]) == 16


def test_spilled_eviction_respects_host_budget(spilled_fit):
    """The host budget is ~1.5 feature chunks while the full tile+feature
    set spans 3 chunks: eviction MUST fire, and the cache gauge must end
    inside the budget (every entry is smaller than the budget, so the
    oversized-entry allowance never applies)."""
    _, session, _, budget_bytes = spilled_fit
    snap = session.registry.snapshot()
    counters = {
        m["name"]: m["value"] for m in snap["counters"] if not m["labels"]
    }
    gauges = {
        m["name"]: m["value"] for m in snap["gauges"] if not m["labels"]
    }
    assert counters["tiles.cache_evictions"] > 0
    assert counters["tiles.cache_misses"] > 0
    assert 0 < gauges["tiles.host_cache_bytes"] <= budget_bytes
    assert gauges["tiles.disk_bytes"] > 0
    # Per-tier stalls measured on BOTH edges.
    tiered = {
        (m["name"], m["labels"].get("tier")): m["value"]
        for m in snap["counters"] if "tier" in m["labels"]
    }
    assert ("stream.stall_s", "disk") in tiered
    assert ("stream.stall_s", "h2d") in tiered


def test_spilled_mid_epoch_kill_then_resume_exact(game_data, tmp_path):
    """Mid-epoch kill→resume with SPILLED tiles: the checkpoint carries
    digests only (rows empty — on-disk tiles referenced, not re-saved)
    and the resumed fit is exact."""
    from photon_tpu.fault.checkpoint import DescentCheckpointer
    from photon_tpu.fault.injection import (
        FaultPlan,
        InjectedKillError,
        set_plan,
    )

    train, val = game_data
    config = _config(iters=2)
    spill_dir = tmp_path / "store"
    baseline = _spilled_estimator(train, val, spill_dir).fit([config])[0]
    ck = str(tmp_path / "ck")
    set_plan(FaultPlan.parse("descent:kill:iter=1:coord=re0"))
    try:
        with pytest.raises(InjectedKillError):
            _spilled_estimator(train, val, spill_dir).fit(
                [config], checkpoint_dir=ck, resume="auto"
            )
    finally:
        set_plan(None)
    state = DescentCheckpointer(os.path.join(ck, "cfg-000")).load("latest")
    assert state.stream["cursor"] == 1
    assert state.stream["spilled"] is True
    assert state.residual_rows == {}  # referenced, not re-saved
    assert len(state.stream["tile_digests"]) == ChunkPlan(
        train.num_examples, CHUNK
    ).num_chunks
    resumed = _spilled_estimator(train, val, spill_dir).fit(
        [config], checkpoint_dir=ck, resume="auto"
    )[0]
    np.testing.assert_array_equal(
        baseline.model.score(val), resumed.model.score(val)
    )
    np.testing.assert_array_equal(
        baseline.model.score(train), resumed.model.score(train)
    )
    assert baseline.metrics == resumed.metrics


def test_spilled_resume_with_corrupt_tile_refused(game_data, tmp_path):
    """A corrupted on-disk tile is refused via digest at read during
    resume — never silently adopted."""
    from photon_tpu.fault.injection import (
        FaultPlan,
        InjectedKillError,
        set_plan,
    )
    from photon_tpu.game.tile_store import CorruptTileError, TileStore
    from photon_tpu.game.tiles import RESIDUAL_TILE_KIND as TILES

    train, val = game_data
    config = _config(iters=2)
    spill_dir = tmp_path / "store"
    ck = str(tmp_path / "ck")
    set_plan(FaultPlan.parse("descent:kill:iter=1:coord=re0"))
    try:
        with pytest.raises(InjectedKillError):
            _spilled_estimator(train, val, spill_dir).fit(
                [config], checkpoint_dir=ck, resume="auto"
            )
    finally:
        set_plan(None)
    store = TileStore(str(spill_dir))
    path = store.path(TILES, 0)
    blob = bytearray(open(path, "rb").read())
    blob[-5] ^= 0xFF
    with open(path, "wb") as f:
        f.write(blob)
    with pytest.raises(CorruptTileError):
        _spilled_estimator(train, val, spill_dir).fit(
            [config], checkpoint_dir=ck, resume="auto"
        )


def test_spilled_resume_rebuilds_stale_tiles(game_data, tmp_path):
    """A STALE (valid but torn-sequence) on-disk tile set is rebuilt
    deterministically from the checkpointed models: resume stays exact
    even after the store lost a write-back."""
    from photon_tpu.fault.injection import (
        FaultPlan,
        InjectedKillError,
        set_plan,
    )
    from photon_tpu.game.tile_store import TileStore
    from photon_tpu.game.tiles import RESIDUAL_TILE_KIND as TILES

    train, val = game_data
    config = _config(iters=2)
    spill_dir = tmp_path / "store"
    baseline = _spilled_estimator(train, val, spill_dir).fit([config])[0]
    ck = str(tmp_path / "ck")
    set_plan(FaultPlan.parse("descent:kill:iter=1:coord=re0"))
    try:
        with pytest.raises(InjectedKillError):
            _spilled_estimator(train, val, spill_dir).fit(
                [config], checkpoint_dir=ck, resume="auto"
            )
    finally:
        set_plan(None)
    # Simulate a torn update sequence: drop one published tile (a VALID
    # store state that no longer matches the checkpoint digests).
    TileStore(str(spill_dir)).delete(TILES, 1)
    session = TelemetrySession("t-rebuild")
    resumed = _spilled_estimator(
        train, val, spill_dir, telemetry=session
    ).fit([config], checkpoint_dir=ck, resume="auto")[0]
    counters = {
        m["name"]: m["value"]
        for m in session.registry.snapshot()["counters"]
        if not m["labels"]
    }
    assert counters.get("tiles.rebuilt", 0) == 1
    np.testing.assert_array_equal(
        baseline.model.score(val), resumed.model.score(val)
    )
    assert baseline.metrics == resumed.metrics


def test_spilled_fit_with_injected_tile_read_faults(
    game_data, tmp_path, monkeypatch
):
    """Transient ``tile:read`` faults during a spilled fit are retried to
    a clean, bit-identical run (the retry/backoff triangle on the disk
    edge)."""
    from photon_tpu.fault.injection import FaultPlan, set_plan

    monkeypatch.setenv("PHOTON_IO_RETRY_BASE_S", "0")
    monkeypatch.setenv("PHOTON_IO_RETRIES", "8")
    train, val = game_data
    config = _config(iters=1)
    clean = _spilled_estimator(train, val, tmp_path / "clean").fit(
        [config]
    )[0]
    session = TelemetrySession("t-tilefaults")
    set_plan(FaultPlan.parse("tile:read:p=0.5", seed=7))
    try:
        faulted = _spilled_estimator(
            train, val, tmp_path / "faulted", telemetry=session
        ).fit([config])[0]
    finally:
        set_plan(None)
    np.testing.assert_array_equal(
        clean.model.score(val), faulted.model.score(val)
    )
    counters = {
        (m["name"], tuple(sorted(m["labels"].items()))): m["value"]
        for m in session.registry.snapshot()["counters"]
    }
    assert counters.get(("io.retries", (("site", "tile:read"),)), 0) > 0


def test_spilled_fit_with_compression_bit_identical(
    game_data, tmp_path, monkeypatch
):
    """`PHOTON_TILE_COMPRESS=1` (delta + byte-shuffle + zlib) trades CPU
    for disk bandwidth without touching a single bit of the result."""
    monkeypatch.setenv("PHOTON_TILE_COMPRESS", "1")
    train, val = game_data
    config = _config(iters=1)
    host = GameEstimator(
        "linear_regression", train, validation_data=val,
        stream_chunks=CHUNK,
    ).fit([config])[0]
    compressed = _spilled_estimator(train, val, tmp_path / "store").fit(
        [config]
    )[0]
    np.testing.assert_array_equal(
        host.model.score(val), compressed.model.score(val)
    )
    from photon_tpu.game.tile_store import TileStore

    assert TileStore(str(tmp_path / "store")).compress


def test_spill_estimator_gates(game_data):
    train, val = game_data
    with pytest.raises(ValueError, match="spill_dir"):
        GameEstimator("linear_regression", train, spill_dir="/tmp/x")
    with pytest.raises(ValueError, match="max_host_mb"):
        GameEstimator(
            "linear_regression", train, stream_chunks=CHUNK,
            spill_dir="/tmp/x", max_host_mb=0,
        )
    with pytest.raises(ValueError, match="spill_dir"):
        GameEstimator(
            "linear_regression", train, stream_chunks=CHUNK,
            max_host_mb=1.0,
        )


def test_train_game_max_host_mb_auto_enables_spilling(tmp_path):
    """ISSUE 11 satellite: the auto-enable gate folds the HOST estimate
    in — a dataset past ``--max-host-mb`` auto-enables streaming AND the
    disk-backed tile store instead of OOM-ing the host cache."""
    import json

    from photon_tpu.drivers import train_game

    out = tmp_path / "out"
    train_game.run(train_game.build_parser().parse_args([
        "--input", "synthetic-game:60:4:6:3",
        "--task", "linear_regression",
        "--coordinate", "fixed:type=fixed,shard=global,max_iters=25",
        "--coordinate", "re0:type=random,shard=re0,entity=re0,max_iters=25",
        "--descent-iterations", "1",
        "--validation-split", "0.25",
        "--max-host-mb", "0.001",
        "--output-dir", str(out),
    ]))
    assert (out / "tile_store").is_dir()
    with open(out / "telemetry" / "run_report.json") as f:
        report = json.load(f)
    gauges = {m["name"]: m["value"] for m in report["metrics"]["gauges"]}
    assert gauges["stream.spilled"] == 1
    assert gauges["stream.chunk_rows"] >= 1
    assert gauges["stream.host_estimate_bytes"] > 0.001 * (1 << 20)
    assert gauges["tiles.disk_bytes"] > 0
    # A generous host budget keeps the non-spilled path.
    out2 = tmp_path / "out2"
    train_game.run(train_game.build_parser().parse_args([
        "--input", "synthetic-game:60:4:6:3",
        "--task", "linear_regression",
        "--coordinate", "fixed:type=fixed,shard=global,max_iters=25",
        "--coordinate", "re0:type=random,shard=re0,entity=re0,max_iters=25",
        "--descent-iterations", "1",
        "--validation-split", "0.25",
        "--stream-chunks", "53",
        "--max-host-mb", "10000",
        "--output-dir", str(out2),
    ]))
    assert not (out2 / "tile_store").exists()
    with open(out2 / "telemetry" / "run_report.json") as f:
        report = json.load(f)
    gauges = {m["name"]: m["value"] for m in report["metrics"]["gauges"]}
    assert "stream.spilled" not in gauges


def test_train_game_spill_dir_requires_streaming(tmp_path):
    from photon_tpu.drivers import train_game

    with pytest.raises(ValueError, match="streamed mode"):
        train_game.run(train_game.build_parser().parse_args([
            "--input", "synthetic-game:60:4:6:3",
            "--task", "linear_regression",
            "--coordinate", "fixed:type=fixed,shard=global,max_iters=25",
            "--descent-iterations", "1",
            "--spill-dir", str(tmp_path / "store"),
            "--output-dir", str(tmp_path / "out"),
        ]))


def test_mid_epoch_checkpoint_carries_solve_quarantine(game_data, tmp_path):
    """A checkpointed streamed run resolves each coordinate's solve stats
    BEFORE its mid-epoch snapshot, so solve-stage quarantines survive a
    kill+resume that skips past the coordinate (code-review finding: the
    deferred-drain count must not be lost to the cursor)."""
    from photon_tpu.fault.checkpoint import DescentCheckpointer
    from photon_tpu.fault.injection import FaultPlan, set_plan

    train, val = game_data
    config = _config(iters=1)
    ck = str(tmp_path / "ck")
    set_plan(FaultPlan.parse("solve:nan:coord=re0"))
    try:
        GameEstimator(
            "linear_regression", train, validation_data=val,
            stream_chunks=CHUNK,
        ).fit([config], checkpoint_dir=ck, resume="auto")
    finally:
        set_plan(None)
    state = DescentCheckpointer(os.path.join(ck, "cfg-000")).load("latest")
    assert state.quarantined >= 1
