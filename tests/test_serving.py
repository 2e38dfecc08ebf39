"""Online GAME scoring service (photon_tpu/serving): device-resident
tables, recompile-free bucketed micro-batching, async batcher, drivers.

The contracts pinned here:

- parity: the serving gather-table path scores exactly what the host
  ``GameModel.score`` oracle scores (requests, whole datasets, both mesh
  shapes);
- recompile freedom: after :meth:`GameScorer.warmup`, 50 batches of varied
  sizes spanning BOTH padded buckets trigger ZERO jax compilations (jax
  monitoring listener + the scorer's own compile counter) and exactly one
  host sync per batch (``serving.host_syncs``);
- cold entities: unknown keys fall back to fixed-effect-only scores through
  the zero gather row and are counted;
- the batcher coalesces under max-delay/max-batch, preserves per-request
  result slices, and surfaces scorer failures through futures;
- the batched model-export d2h (ONE ``jax.device_get`` for all coordinate
  tables, ``descent.host_transfer_bytes{path=export}``);
- the batch ``score_game`` route shares the scorer with serving.
"""

from __future__ import annotations

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
    GameScorer,
    RequestBatcher,
    ScoringRequest,
    build_requests,
    concat_requests,
    request_from_dataset,
    request_spec_for_dataset,
    run_closed_loop,
    slice_request,
)
from photon_tpu.telemetry import TelemetrySession


def _fixture(seed=3, n_entities=40, fixed_dim=6, random_dim=4):
    """Constructed (not fitted) GAME model + matching dataset: serving
    tests measure scoring, and a fit would slow every test for nothing."""
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


@pytest.fixture(scope="module")
def served():
    model, data = _fixture()
    session = TelemetrySession("test-serving")
    scorer = GameScorer(
        model, request_spec=request_spec_for_dataset(model, data),
        max_batch=64, telemetry=session,
    ).warmup()
    return model, data, scorer, session


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


# -- scorer parity -----------------------------------------------------------

def test_request_scores_match_host_oracle(served):
    model, data, scorer, _ = served
    want = model.score(data)
    sizes = [1, 3, 17, 64, 64]
    pos = 0
    for req, size in zip(build_requests(data, model, sizes), sizes):
        rows = np.arange(pos, pos + size) % data.num_examples
        got = scorer.score_batch(req)
        np.testing.assert_allclose(got, want[rows], rtol=1e-4, atol=1e-4)
        pos = (pos + size) % data.num_examples


def test_score_dataset_matches_host_oracle(served):
    model, data, scorer, _ = served
    np.testing.assert_allclose(
        scorer.score_dataset(data), model.score(data), rtol=1e-4, atol=1e-4
    )


def test_scorer_under_mesh_matches_host_oracle():
    """Mesh parity, stress-looped: the replica-aliasing donation bug this
    pins (one replica's output clobbering a zero-copy-shared input buffer)
    corrupted only a FRACTION of batches — a single comparison passed most
    runs; thirty back-to-back batches fail reliably on regression."""
    from photon_tpu.parallel.mesh import create_mesh

    model, data = _fixture(seed=5)
    scorer = GameScorer(
        model, mesh=create_mesh(),
        request_spec=request_spec_for_dataset(model, data), max_batch=32,
    ).warmup()
    want = model.score(data)
    np.testing.assert_allclose(
        scorer.score_dataset(data), want, rtol=1e-4, atol=1e-4
    )
    rng = np.random.default_rng(1)
    sizes = rng.integers(1, 33, size=30).tolist()
    pos = 0
    for req, size in zip(build_requests(data, model, sizes), sizes):
        rows = np.arange(pos, pos + size) % data.num_examples
        np.testing.assert_allclose(
            scorer.score_batch(req), want[rows], rtol=1e-4, atol=1e-4
        )
        pos = (pos + size) % data.num_examples


def test_sparse_request_spec_roundtrip():
    """Avro-shaped input (padded-COO sparse shards) serves through the same
    scorer: spec carries the nonzero width, parity holds."""
    from photon_tpu.data.game_io import read_game_avro, write_game_avro

    model, data = _fixture(seed=11)
    import tempfile, os

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "req.avro")
        _, imaps = make_game_dataset(40, 4, 6, 4, seed=11)
        write_game_avro(path, data, imaps)
        sparse_data, _ = read_game_avro(
            path, {n: n for n in data.shards}, ["re0"], index_maps=imaps
        )
    scorer = GameScorer(
        model, request_spec=request_spec_for_dataset(model, sparse_data),
        max_batch=32,
    ).warmup()
    got = scorer.score_dataset(sparse_data)
    want = model.score(sparse_data)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_oversize_batch_is_chunked(served):
    model, data, scorer, _ = served
    n = data.num_examples
    assert n > scorer.max_bucket
    req = request_from_dataset(data, model)
    np.testing.assert_allclose(
        scorer.score_batch(req), model.score(data), rtol=1e-4, atol=1e-4
    )


# -- cold entities -----------------------------------------------------------

def test_unknown_entities_fall_back_to_fixed_effect(served):
    model, data, scorer, session = served
    before = _counter_total(session, "serving.cold_entities")
    x_fixed = data.shards["global"].x[:3]
    x_rand = data.shards["re0"].x[:3]
    req = ScoringRequest(
        features={"global": x_fixed, "re0": x_rand},
        entity_ids={"re0": np.array([10 ** 9, 10 ** 9 + 1, 10 ** 9 + 2])},
    )
    got = scorer.score_batch(req)
    fixed_only = x_fixed @ np.asarray(
        model.coordinates["fixed"].coefficients.means
    )
    np.testing.assert_allclose(got, fixed_only, rtol=1e-5, atol=1e-5)
    assert _counter_total(session, "serving.cold_entities") == before + 3


def test_padding_rows_not_counted_cold(served):
    """A 3-row request pads to the 8-bucket with entity index -1; only the
    REAL unknown rows may count as cold."""
    model, data, scorer, session = served
    before = _counter_total(session, "serving.cold_entities")
    (req,) = build_requests(data, model, [3])
    scorer.score_batch(req)  # all known entities
    assert _counter_total(session, "serving.cold_entities") == before


# -- recompile freedom (the ISSUE acceptance contract) -----------------------

def test_no_recompiles_after_warmup_across_buckets(served):
    """50 post-warmup batches of varied sizes spanning both padded buckets:
    ZERO jax compilations and exactly one host sync per batch."""
    import jax.monitoring

    model, data, scorer, session = served
    compile_events = []

    def listener(event, **kwargs):
        if "compile" in event:
            compile_events.append(event)

    rng = np.random.default_rng(0)
    sizes = rng.integers(1, scorer.max_bucket + 1, size=50).tolist()
    # Spanning "both padded buckets" must be true by construction, not by
    # RNG luck: force one batch into the smallest and one into the largest.
    sizes[0], sizes[-1] = 1, scorer.max_bucket
    requests = build_requests(data, model, sizes)
    compilations_before = scorer.compilations
    syncs_before = _counter_total(session, "serving.host_syncs")
    batches_before = _counter_total(session, "serving.batches")

    jax.monitoring.register_event_listener(listener)
    try:
        for req in requests:
            scorer.score_batch(req)
    finally:
        jax.monitoring.unregister_event_listener(listener)

    assert compile_events == []
    assert scorer.compilations == compilations_before
    assert _counter_total(session, "serving.compilations") == \
        compilations_before
    batches = _counter_total(session, "serving.batches") - batches_before
    assert batches == 50
    # serving.host_syncs <= 1 per batch (exactly 1 here).
    assert _counter_total(session, "serving.host_syncs") - syncs_before == 50
    # The varied sizes really did exercise more than one bucket.
    buckets_hit = {
        m["labels"]["bucket"]
        for m in session.registry.snapshot()["counters"]
        if m["name"] == "serving.batches"
    }
    assert len(buckets_hit) >= 2


def test_off_ladder_shape_raises_after_warmup(served):
    # A bucket no other test can have cached (score_dataset legitimately
    # adds the dataset's own pow2 shape to the compiled set).
    _, _, scorer, _ = served
    with pytest.raises(RuntimeError, match="never recompile"):
        scorer._program(scorer.max_bucket * 4096)


def test_warmup_compiles_whole_ladder():
    model, data = _fixture(seed=9)
    scorer = GameScorer(
        model, request_spec=request_spec_for_dataset(model, data),
        max_batch=64,
    )
    assert scorer.compilations == 0
    scorer.warmup()
    assert scorer.compilations == len(scorer.buckets)
    assert scorer.buckets == (8, 16, 32, 64)
    assert scorer.bucket_for(1) == 8
    assert scorer.bucket_for(9) == 16
    assert scorer.bucket_for(64) == 64
    with pytest.raises(ValueError, match="exceeds max bucket"):
        scorer.bucket_for(65)


# -- request plumbing --------------------------------------------------------

def test_request_validation_errors(served):
    model, data, scorer, _ = served
    (req,) = build_requests(data, model, [4])
    with pytest.raises(ValueError, match="missing shard"):
        scorer.score_batch(ScoringRequest(
            features={"global": req.features["global"]},
            entity_ids=req.entity_ids,
        ))
    with pytest.raises(ValueError, match="missing id column"):
        scorer.score_batch(ScoringRequest(
            features=req.features, entity_ids={},
        ))
    with pytest.raises(ValueError, match="want"):
        scorer.score_batch(ScoringRequest(
            features={"global": req.features["global"][:, :2],
                      "re0": req.features["re0"]},
            entity_ids=req.entity_ids,
        ))


def test_slice_and_concat_roundtrip(served):
    model, data, _, _ = served
    req = request_from_dataset(data, model)
    parts = [slice_request(req, 0, 10), slice_request(req, 10, req.num_rows)]
    merged = concat_requests(parts)
    assert merged.num_rows == req.num_rows
    np.testing.assert_array_equal(
        merged.features["global"], req.features["global"]
    )
    np.testing.assert_array_equal(
        merged.entity_ids["re0"], req.entity_ids["re0"]
    )
    np.testing.assert_array_equal(merged.offset, req.offset)


# -- batcher -----------------------------------------------------------------

def test_batcher_coalesces_and_preserves_request_slices(served):
    model, data, scorer, session = served
    want = model.score(data)
    sizes = [2] * 20
    requests = build_requests(data, model, sizes)
    batches_before = _counter_total(session, "serving.batches")
    with RequestBatcher(scorer, max_delay_s=0.05) as batcher:
        futures = [batcher.submit(r) for r in requests]
        results = [f.result(timeout=30) for f in futures]
    pos = 0
    for size, got in zip(sizes, results):
        rows = np.arange(pos, pos + size) % data.num_examples
        np.testing.assert_allclose(got, want[rows], rtol=1e-4, atol=1e-4)
        pos = (pos + size) % data.num_examples
    # 40 rows in 2-row requests under a generous window: far fewer
    # batches than requests (coalescing actually happened).
    batches = _counter_total(session, "serving.batches") - batches_before
    assert batches < len(requests)


def test_batcher_closed_loop_and_latency_telemetry(served):
    model, data, scorer, session = served
    requests = build_requests(data, model, [1, 5, 9, 30, 2, 7])
    with RequestBatcher(scorer, max_delay_s=0.001) as batcher:
        scores, latencies, wall = run_closed_loop(batcher, requests, clients=3)
    assert len(scores) == len(requests)
    assert all(lat is not None and lat >= 0 for lat in latencies)
    hist = next(
        h for h in session.registry.snapshot()["histograms"]
        if h["name"] == "serving.request_latency_s"
    )
    assert hist["count"] >= len(requests)
    assert hist["p99"] is not None


def test_batcher_surfaces_scorer_failure(served):
    model, data, scorer, _ = served
    (good,) = build_requests(data, model, [4])
    bad = ScoringRequest(
        features={"global": good.features["global"]},  # missing re0 shard
        entity_ids=good.entity_ids,
    )
    with RequestBatcher(scorer, max_delay_s=0.001) as batcher:
        fut = batcher.submit(bad)
        with pytest.raises(ValueError, match="missing shard"):
            fut.result(timeout=30)
        # The batcher thread survives a failed batch.
        ok = batcher.submit(good).result(timeout=30)
    assert ok.shape == (4,)
    with pytest.raises(RuntimeError, match="closed"):
        batcher.submit(good)


def test_batcher_respects_max_batch_rows(served):
    model, data, scorer, session = served
    sizes = [30, 30, 30]  # 90 rows > max_batch 60 -> at least two batches
    requests = build_requests(data, model, sizes)
    batches_before = _counter_total(session, "serving.batches")
    with RequestBatcher(scorer, max_batch=60, max_delay_s=0.2) as batcher:
        futures = [batcher.submit(r) for r in requests]
        for f in futures:
            f.result(timeout=30)
    assert _counter_total(session, "serving.batches") - batches_before >= 2


# -- batched export d2h (satellite) ------------------------------------------

def test_save_game_model_single_batched_device_get(tmp_path, monkeypatch):
    import jax

    from photon_tpu.game.model_io import load_game_model, save_game_model

    model, data = _fixture(seed=13)
    _, imaps = make_game_dataset(40, 4, 6, 4, seed=13)
    session = TelemetrySession("test-export")
    calls = []
    real = jax.device_get

    def counting(x):
        calls.append(1)
        return real(x)

    # _fetch_model_tables resolves jax.device_get at call time, so the
    # global patch counts the export's d2h dispatches.
    monkeypatch.setattr(jax, "device_get", counting)
    save_game_model(str(tmp_path / "m"), model, imaps, telemetry=session)
    assert len(calls) == 1  # ONE d2h for every coordinate's tables
    moved = _counter_total(
        session, "descent.host_transfer_bytes", direction="d2h", path="export"
    )
    table = model.coordinates["per_entity"].table
    fixed = model.coordinates["fixed"].coefficients.means
    assert moved == table.nbytes + np.asarray(fixed).nbytes
    loaded, _ = load_game_model(str(tmp_path / "m"))
    np.testing.assert_allclose(
        loaded.score(data), model.score(data), rtol=1e-5, atol=1e-5
    )


# -- drivers -----------------------------------------------------------------

def test_score_game_batch_routes_through_scorer(tmp_path, monkeypatch):
    """The non-streamed batch driver scores through the serving gather
    tables; the host escape hatch reproduces the old path and both agree."""
    from photon_tpu.drivers import score_game
    from photon_tpu.game.model_io import save_game_model

    model, data = _fixture(seed=17)
    _, imaps = make_game_dataset(40, 4, 6, 4, seed=17)
    save_game_model(str(tmp_path / "model"), model, imaps)

    def run(outdir, env=None):
        if env:
            monkeypatch.setenv("PHOTON_BATCH_SCORER", env)
        else:
            monkeypatch.delenv("PHOTON_BATCH_SCORER", raising=False)
        score_game.run(score_game.build_parser().parse_args([
            "--backend", "cpu",
            "--input", "synthetic-game:40:4:6:4:1:17",
            "--model", str(tmp_path / "model"),
            "--output-dir", str(tmp_path / outdir),
        ]))
        return np.loadtxt(str(tmp_path / outdir / "scores.txt"))

    device = run("out-device")
    host = run("out-host", env="host")
    np.testing.assert_allclose(device, host, rtol=1e-4, atol=1e-4)


def test_serve_game_driver_end_to_end(tmp_path):
    from photon_tpu.drivers import serve_game
    from photon_tpu.game.model_io import save_game_model

    model, data = _fixture(seed=21)
    _, imaps = make_game_dataset(40, 4, 6, 4, seed=21)
    save_game_model(str(tmp_path / "model"), model, imaps)
    out = tmp_path / "served"
    summary = serve_game.run(serve_game.build_parser().parse_args([
        "--backend", "cpu",
        "--model", str(tmp_path / "model"),
        "--input", "synthetic-game:40:4:6:4:1:21",
        "--requests", "25",
        "--clients", "3",
        # The PR 9 stream (consecutive row windows), kept as --traffic
        # geometric for bench continuity: the scores.txt spot-check below
        # relies on request windows starting at row 0.
        "--traffic", "geometric",
        "--max-batch", "32",
        "--max-delay-ms", "1",
        "--output-dir", str(out),
    ]))
    assert summary["requests"] == 25
    assert summary["served"] == 25 and summary["shed"] == 0
    assert summary["qps"] > 0
    assert summary["latency_p99_ms"] >= summary["latency_p50_ms"]
    scores = np.loadtxt(str(out / "scores.txt"))
    assert len(scores) == summary["rows"]
    # Scores must be the model's (spot-check the first request window
    # against the host oracle; request windows start at row 0).
    want = model.score(data)
    np.testing.assert_allclose(
        scores[:10], want[:10], rtol=1e-4, atol=1e-4
    )
    # Run report carries the serving block.
    import json

    with open(out / "telemetry" / "run_report.json") as f:
        report = json.load(f)
    names = {m["name"] for m in report["metrics"]["counters"]}
    assert {"serving.requests", "serving.batches",
            "serving.host_syncs"} <= names
    from photon_tpu.telemetry.report import render_markdown

    md = render_markdown(report)
    assert "## Online serving" in md
    assert "serving.host_syncs per batch | 1 |" in md


# -- model hot-swap (ISSUE 10 satellite) -------------------------------------

def _retrained(model: GameModel, seed: int) -> GameModel:
    """A 'retrained' model: same coordinate layout and vocabularies,
    different coefficients — the production hot-swap shape."""
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


def test_swap_model_scores_new_model_without_recompiles():
    model, data = _fixture(seed=23)
    session = TelemetrySession("test-swap")
    scorer = GameScorer(
        model, request_spec=request_spec_for_dataset(model, data),
        max_batch=32, telemetry=session,
    ).warmup()
    compiled = scorer.compilations
    req = build_requests(data, model, [16])[0]
    np.testing.assert_allclose(
        scorer.score_batch(req), model.score(data)[:16],
        rtol=1e-4, atol=1e-4,
    )
    retrained = _retrained(model, seed=29)
    scorer.swap_model(retrained)
    # Zero recompiles, scores are the NEW model's, and the swap counted.
    np.testing.assert_allclose(
        scorer.score_batch(req), retrained.score(data)[:16],
        rtol=1e-4, atol=1e-4,
    )
    assert scorer.compilations == compiled
    assert _counter_total(session, "serving.swaps") == 1


def test_swap_model_mid_closed_loop_no_dropped_requests():
    """Swap while a closed-loop request stream is in flight: every request
    completes, every response matches the model that was live when its
    batch dispatched (old XOR new — never a mix), and scores before/after
    the swap pin both models."""
    model, data = _fixture(seed=31)
    session = TelemetrySession("test-swap-loop")
    scorer = GameScorer(
        model, request_spec=request_spec_for_dataset(model, data),
        max_batch=32, telemetry=session,
    ).warmup()
    retrained = _retrained(model, seed=37)
    want_old = model.score(data)
    want_new = retrained.score(data)
    requests = build_requests(data, model, [8] * 40)
    windows = [np.arange(i * 8, (i + 1) * 8) % data.num_examples
               for i in range(40)]
    batcher = RequestBatcher(scorer, max_batch=32, max_delay_s=0.001)
    swap_at = 20
    results = []
    with batcher:
        futures = []
        for i, req in enumerate(requests):
            if i == swap_at:
                scorer.swap_model(retrained)
            futures.append(batcher.submit(req))
        results = [f.result(timeout=30) for f in futures]
    assert len(results) == len(requests)
    for rows, got in zip(windows, results):
        # Every response is exactly ONE model's scores — old XOR new,
        # never a mix of the two tables/vocabularies.
        ok_old = np.allclose(got, want_old[rows], rtol=1e-4, atol=1e-4)
        ok_new = np.allclose(got, want_new[rows], rtol=1e-4, atol=1e-4)
        assert ok_old or ok_new, "response matches neither model"
    # The tail of the stream (submitted well after the swap) must be the
    # new model's scores.
    assert np.allclose(
        results[-1], want_new[windows[-1]], rtol=1e-4, atol=1e-4
    )
    assert _counter_total(session, "serving.swaps") == 1


def test_swap_model_grown_vocabulary_within_capacity():
    """Satellite (ISSUE 12): the serving tables carry amortized-doubling
    capacity headroom and a MOVABLE zero-row index, so a model whose grown
    vocabulary still fits the served capacity hot-swaps in place — zero
    recompiles, the new entity scores its own (non-zero) row, and it is no
    longer counted cold."""
    import dataclasses

    import jax.numpy as jnp

    model, data = _fixture(seed=41)
    session = TelemetrySession("test-grow-swap")
    scorer = GameScorer(
        model, request_spec=request_spec_for_dataset(model, data),
        max_batch=16, telemetry=session,
    ).warmup()
    compiled = scorer.compilations
    per_entity = model.coordinates["per_entity"]
    new_key = np.asarray([10_000], per_entity.keys.dtype)
    grown = per_entity.with_entities(
        np.unique(np.concatenate([per_entity.keys, new_key]))
    )
    # Give the onboarded entity a real (non-zero) coefficient row so its
    # served margin is distinguishable from the cold fallback.
    new_idx = int(np.searchsorted(grown.keys, new_key[0]))
    new_row = np.arange(1, grown.dim + 1, dtype=np.float32)
    grown = dataclasses.replace(
        grown, table=jnp.asarray(grown.table).at[new_idx].set(new_row)
    )
    bigger = GameModel(
        coordinates={**model.coordinates, "per_entity": grown},
        task_type=model.task_type,
    )
    scorer.swap_model(bigger)

    x_fixed = data.shards["global"].x[:2]
    x_rand = data.shards["re0"].x[:2]
    cold_before = _counter_total(session, "serving.cold_entities")
    got = scorer.score_batch(ScoringRequest(
        features={"global": x_fixed, "re0": x_rand},
        entity_ids={"re0": np.asarray(
            [10_000, 999_999], per_entity.keys.dtype
        )},
    ))
    fixed_only = x_fixed @ np.asarray(
        model.coordinates["fixed"].coefficients.means
    )
    np.testing.assert_allclose(
        got, fixed_only + np.array([x_rand[0] @ new_row, 0.0]),
        rtol=1e-4, atol=1e-4,
    )
    # The grown entity is served (not cold); the truly unknown one still
    # rides the (moved) zero row and counts.
    assert _counter_total(session, "serving.cold_entities") == \
        cold_before + 1
    assert scorer.compilations == compiled
    assert _counter_total(session, "serving.swaps") == 1


def test_swap_model_rejects_layout_changes():
    model, data = _fixture(seed=41)
    scorer = GameScorer(
        model, request_spec=request_spec_for_dataset(model, data),
        max_batch=16,
    ).warmup()
    per_entity = model.coordinates["per_entity"]
    # Growth PAST the table capacity is a layout-shape change: the compiled
    # programs' gather-table shape would have to grow — refuse (rebuild).
    capacity = 1
    while capacity < per_entity.num_entities + 1:
        capacity *= 2
    extra = np.arange(
        20_000, 20_000 + capacity, dtype=per_entity.keys.dtype
    )
    grown = per_entity.with_entities(
        np.unique(np.concatenate([per_entity.keys, extra]))
    )
    bigger = GameModel(
        coordinates={**model.coordinates, "per_entity": grown},
        task_type=model.task_type,
    )
    with pytest.raises(ValueError, match="layout-shape change"):
        scorer.swap_model(bigger)
    # A changed coordinate SET refuses too (plan mismatch).
    with pytest.raises(ValueError, match="swap_model"):
        scorer.swap_model(GameModel(
            coordinates={"fixed": model.coordinates["fixed"]},
            task_type=model.task_type,
        ))
