"""Telemetry subsystem: registry, spans, run reports, driver integration."""

import json
import os
import threading

import numpy as np
import pytest

from photon_tpu.telemetry import (
    NULL_SESSION,
    MetricsRegistry,
    TelemetrySession,
    Tracer,
    telemetry_enabled,
)
from photon_tpu.telemetry.report import (
    render_markdown,
    resolve_report_path,
)
from photon_tpu.telemetry import report as report_cli


# ---------------------------------------------------------------- registry


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2.5)
    assert reg.counter("c").value == 3.5
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)

    assert reg.gauge("g").value is None
    reg.gauge("g").set(7)
    reg.gauge("g").set(5)
    assert reg.gauge("g").value == 5.0

    h = reg.histogram("h")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and s["sum"] == 10.0
    assert s["min"] == 1.0 and s["max"] == 4.0 and s["mean"] == 2.5


def test_labels_create_distinct_series_and_kind_conflicts_raise():
    reg = MetricsRegistry()
    reg.counter("solves", lam="0.1").inc()
    reg.counter("solves", lam="1").inc(2)
    assert reg.counter("solves", lam="0.1").value == 1
    assert reg.counter("solves", lam="1").value == 2
    # Same (name, labels) under a different kind is a registration bug.
    with pytest.raises(TypeError):
        reg.gauge("solves", lam="0.1")
    # Label VALUES are stringified, so 1 and "1" are the same series.
    reg.counter("solves", lam=1).inc()
    assert reg.counter("solves", lam="1").value == 3


def test_histogram_reservoir_bounded_and_percentiles_sane():
    reg = MetricsRegistry()
    h = reg.histogram("big")
    n = 10_000
    for i in range(n):
        h.observe(float(i))
    assert h.count == n and h.sum == sum(range(n))
    assert len(h._kept) <= 256 + 1
    # Kept samples sweep the sequence evenly -> percentiles land close.
    assert abs(h.percentile(50) - n / 2) < n * 0.05
    assert h.percentile(0) == 0.0
    assert h.summary()["p99"] > n * 0.9


def test_snapshot_is_sorted_and_json_ready():
    reg = MetricsRegistry()
    reg.counter("b").inc()
    reg.counter("a", x="2").inc()
    reg.counter("a", x="1").inc()
    reg.gauge("g").set(1.5)
    reg.histogram("h").observe(3)
    snap = reg.snapshot()
    names = [(e["name"], e["labels"]) for e in snap["counters"]]
    assert names == [("a", {"x": "1"}), ("a", {"x": "2"}), ("b", {})]
    json.dumps(snap)  # must serialize


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("optimizer.solves", lam="0.1").inc(3)
    reg.gauge("train.best_lambda").set(0.1)
    reg.gauge("unset")  # never set -> omitted
    reg.histogram("solve_seconds").observe(2.0)
    text = reg.to_prometheus()
    assert 'optimizer_solves{lam="0.1"} 3' in text
    assert "train_best_lambda 0.1" in text
    assert "unset" not in text
    assert 'solve_seconds{quantile="0.5"} 2' in text
    assert "solve_seconds_count 1" in text


def test_registry_thread_safety():
    reg = MetricsRegistry()

    def work():
        for _ in range(1000):
            reg.counter("n").inc()
            reg.histogram("h").observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("n").value == 4000
    assert reg.histogram("h").count == 4000


# ----------------------------------------------------------------- tracing


def test_span_nesting_and_attributes():
    tracer = Tracer()
    with tracer.span("outer", kind="test") as outer:
        with tracer.span("inner") as inner:
            assert tracer.current_span() is inner
            inner.set_attribute("rows", 10)
        assert tracer.current_span() is outer
    assert tracer.current_span() is None
    spans = tracer.export()
    # Children finish first.
    assert [s["name"] for s in spans] == ["inner", "outer"]
    by_name = {s["name"]: s for s in spans}
    assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
    assert by_name["outer"]["parent_id"] is None
    assert by_name["inner"]["attributes"]["rows"] == 10
    assert by_name["outer"]["attributes"]["kind"] == "test"
    assert all(s["duration_s"] >= 0 for s in spans)


def test_span_error_status_recorded_and_reraised():
    tracer = Tracer()
    with pytest.raises(RuntimeError, match="boom"):
        with tracer.span("failing"):
            raise RuntimeError("boom")
    (span,) = tracer.export()
    assert span["status"] == "error"
    assert "RuntimeError: boom" in span["error"]
    assert span["duration_s"] is not None


def test_spans_on_worker_threads_are_roots():
    tracer = Tracer()

    def work():
        with tracer.span("worker"):
            pass

    with tracer.span("main"):
        t = threading.Thread(target=work)
        t.start()
        t.join()
    worker = next(s for s in tracer.export() if s["name"] == "worker")
    assert worker["parent_id"] is None  # not a child of "main"
    assert worker["thread"] != "MainThread"


def test_phase_totals_and_jsonl(tmp_path):
    tracer = Tracer()
    for _ in range(3):
        with tracer.span("phase-a"):
            pass
    with tracer.span("phase-b"):
        pass
    totals = tracer.phase_totals()
    assert set(totals) == {"phase-a", "phase-b"}
    path = str(tmp_path / "spans.jsonl")
    tracer.write_jsonl(path)
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 4


# ----------------------------------------------------------------- session


def test_disabled_session_is_a_full_noop(tmp_path):
    session = TelemetrySession("test", enabled=False)
    session.counter("c").inc()
    session.gauge("g").set(1)
    session.histogram("h").observe(1)
    with session.span("phase") as sp:
        sp.set_attribute("k", "v")
    assert session.finalize(str(tmp_path)) is None
    assert not os.path.exists(str(tmp_path / "telemetry"))
    # The shared NULL_SESSION behaves identically (library default arg).
    with NULL_SESSION.span("x") as sp:
        sp.set_attribute("a", 1)


def test_session_finalize_writes_artifacts(tmp_path):
    session = TelemetrySession("unittest")
    session.counter("rows").inc(5)
    with session.span("load"):
        pass
    report = session.finalize(str(tmp_path), extra={"note": "hi"})
    assert report["status"] == "success"
    assert report["driver"] == "unittest"
    assert report["extra"] == {"note": "hi"}
    tdir = tmp_path / "telemetry"
    with open(tdir / "run_report.json") as f:
        persisted = json.load(f)
    assert persisted["metrics"]["counters"][0]["value"] == 5
    assert [s["name"] for s in persisted["spans"]] == ["load"]
    assert (tdir / "spans.jsonl").exists()
    # Finalize is idempotent: the error path after a success write is a no-op.
    again = session.finalize(str(tmp_path), status="error", error="nope")
    assert again["status"] == "success"


def test_finalize_survives_non_json_attributes(tmp_path):
    """Telemetry must never crash the run it observes: non-JSON span
    attributes (numpy scalars etc.) degrade to strings at write time."""
    session = TelemetrySession("hardening")
    with session.span("phase") as sp:
        sp.set_attribute("np_scalar", np.float32(1.5))
        sp.set_attribute("array", np.arange(3))
    report = session.finalize(str(tmp_path))
    assert report["status"] == "success"
    persisted = json.load(open(tmp_path / "telemetry" / "run_report.json"))
    assert persisted["spans"][0]["attributes"]["np_scalar"] == "1.5"


def test_finalize_never_raises_on_unwritable_dir(tmp_path):
    """A telemetry write failure must not crash an otherwise-successful
    run — and on the driver error path must not replace the real
    exception with a telemetry traceback."""
    # Output dir nested under a regular FILE: makedirs fails regardless of
    # uid (chmod-based denial is a no-op when the suite runs as root).
    blocker = tmp_path / "blocker"
    blocker.write_text("")
    target = blocker / "out"
    session = TelemetrySession("hardening")
    session.counter("c").inc()
    report = session.finalize(str(target))  # must not raise
    assert report is not None and report["status"] == "success"
    assert not os.path.exists(str(target))


def test_session_write_gate_skips_files(tmp_path):
    session = TelemetrySession("rank1")
    session.write = False  # non-primary rank
    report = session.finalize(str(tmp_path))
    assert report is not None  # report still built...
    assert not os.path.exists(str(tmp_path / "telemetry"))  # ...nothing written


def test_env_var_gate(monkeypatch):
    assert telemetry_enabled(None) is True
    assert telemetry_enabled(False) is False
    monkeypatch.setenv("PHOTON_TELEMETRY", "off")
    assert telemetry_enabled(None) is False
    assert telemetry_enabled(True) is False  # env wins over the flag
    monkeypatch.setenv("PHOTON_TELEMETRY", "on")
    assert telemetry_enabled(None) is True


def test_logger_timed_feeds_tracer():
    from photon_tpu.utils import PhotonLogger

    logger = PhotonLogger("photon_tpu.test_telemetry")
    session = TelemetrySession("logger-test")
    session.attach(logger)
    with logger.timed("outer-phase"):
        with logger.timed("inner-phase"):
            pass
    assert "outer-phase" in logger.phase_times  # legacy dict still fed
    spans = {s["name"]: s for s in session.tracer.export()}
    assert spans["inner-phase"]["parent_id"] == spans["outer-phase"]["span_id"]


# ----------------------------------------------------- optimizer recording


def test_tracker_record_to():
    from photon_tpu.core.optimizers import OptimizationStatesTracker
    from photon_tpu.core.optimizers.base import OptimizerResult

    result = OptimizerResult(
        w=np.zeros(3, np.float32),
        value=np.float32(1.5),
        grad_norm=np.float32(0.01),
        iterations=np.int32(4),
        converged=np.bool_(True),
        reason=np.int32(2),  # FUNCTION_VALUES_TOLERANCE
        history_value=np.array([3.0, 2.0, 1.8, 1.6, 1.5, 0.0], np.float32),
        history_grad_norm=np.array([1.0, 0.5, 0.1, 0.05, 0.01, 0.0], np.float32),
        history_valid=np.array([1, 1, 1, 1, 1, 0], bool),
    )
    tracker = OptimizationStatesTracker(result, wall_time_s=0.25)
    reg = MetricsRegistry()
    tracker.record_to(reg, lam=0.5)
    assert reg.counter("optimizer.solves", lam="0.5").value == 1
    assert reg.counter("optimizer.iterations", lam="0.5").value == 4
    assert reg.counter("optimizer.converged_solves", lam="0.5").value == 1
    assert reg.counter(
        "optimizer.stop_reason", lam="0.5",
        reason="FUNCTION_VALUES_TOLERANCE",
    ).value == 1
    assert reg.histogram("optimizer.solve_seconds", lam="0.5").count == 1
    assert reg.gauge("optimizer.final_value", lam="0.5").value == pytest.approx(1.5)


# ----------------------------------------------------------------- reports


def test_render_markdown_and_cli(tmp_path, capsys):
    session = TelemetrySession("render-test")
    session.counter("rows", kind="train").inc(7)
    session.histogram("seconds").observe(0.5)
    with session.span("load"):
        with session.span("parse"):
            pass
    session.finalize(str(tmp_path))
    text = render_markdown(
        json.load(open(tmp_path / "telemetry" / "run_report.json"))
    )
    assert "# Run report: render-test" in text
    assert "| rows | kind=train | 7 |" in text
    assert "- load:" in text and "  - parse:" in text  # tree indentation

    # CLI: a driver output dir resolves to its nested run_report.json.
    assert resolve_report_path(str(tmp_path)).endswith(
        os.path.join("telemetry", "run_report.json")
    )
    out_md = str(tmp_path / "report.md")
    report_cli.main([str(tmp_path), "-o", out_md])
    assert "# Run report: render-test" in open(out_md).read()
    report_cli.main([str(tmp_path)])
    assert "# Run report: render-test" in capsys.readouterr().out


def test_render_markdown_checkpoint_pipeline_section(tmp_path):
    """Publisher lag/blocked histograms and io-pool gauges surface as their
    own section (ISSUE 5 satellite); absent metrics -> absent section."""
    session = TelemetrySession("pipeline-test")
    session.counter("checkpoint.saves").inc(3)
    session.histogram("checkpoint.write_seconds").observe(0.01)
    session.histogram("checkpoint.blocked_s").observe(0.0)
    session.histogram("checkpoint.publish_lag_s").observe(0.2)
    session.gauge("io_pool.workers").set(4)
    session.gauge("io_pool.in_flight_peak").set(8)
    session.finalize(str(tmp_path))
    text = render_markdown(
        json.load(open(tmp_path / "telemetry" / "run_report.json"))
    )
    assert "## Checkpoint pipeline" in text
    assert "**saves**: 3" in text
    assert "checkpoint.publish_lag_s" in text
    assert "## Host-IO pool" in text
    assert "**io_pool.in_flight_peak**: 8" in text

    plain = TelemetrySession("no-pipeline")
    plain.counter("rows").inc()
    plain.finalize(str(tmp_path / "plain"))
    text2 = render_markdown(
        json.load(open(tmp_path / "plain" / "telemetry" / "run_report.json"))
    )
    assert "## Checkpoint pipeline" not in text2
    assert "## Host-IO pool" not in text2


def test_render_markdown_streaming_tiers_section(tmp_path):
    """The stream.*/tiles.* row block (ISSUE 11): per-tier stall/overlap
    table + the host-cache/disk-store shape of a spilled run; absent
    metrics -> absent section."""
    session = TelemetrySession("ooc-test")
    session.counter("stream.chunks").inc(24)
    session.counter("stream.stall_s", tier="h2d").inc(0.25)
    session.counter("stream.stall_s", tier="disk").inc(1.5)
    session.counter("stream.prefetch_overlap_s", tier="h2d").inc(0.75)
    session.counter("stream.prefetch_overlap_s", tier="disk").inc(2.0)
    session.counter("tiles.cache_hits").inc(90)
    session.counter("tiles.cache_misses").inc(10)
    session.counter("tiles.cache_evictions").inc(4)
    session.gauge("tiles.host_cache_bytes").set(8192)
    session.gauge("tiles.disk_bytes").set(1 << 20)
    session.finalize(str(tmp_path))
    text = render_markdown(
        json.load(open(tmp_path / "telemetry" / "run_report.json"))
    )
    assert "## Streaming tiers" in text
    assert "**chunks delivered**: 24" in text
    assert "| disk | 1.5 | 2 |" in text
    assert "| h2d | 0.25 | 0.75 |" in text
    assert "**tiles.cache_evictions**: 4" in text
    assert "**tiles.host_cache_bytes**: 8192" in text
    assert "**tiles.disk_bytes**:" in text

    plain = TelemetrySession("no-stream")
    plain.counter("rows").inc()
    plain.finalize(str(tmp_path / "plain"))
    text2 = render_markdown(
        json.load(open(tmp_path / "plain" / "telemetry" / "run_report.json"))
    )
    assert "## Streaming tiers" not in text2


def test_render_markdown_serving_section(tmp_path):
    """The serving.* row block (ISSUE 9 satellite): request/batch counters,
    the coalescing and host-syncs-per-batch ratios, latency distributions;
    absent metrics -> absent section."""
    session = TelemetrySession("serving-test")
    session.counter("serving.requests").inc(40)
    session.counter("serving.batches", bucket=8).inc(6)
    session.counter("serving.batches", bucket=64).inc(4)
    session.counter("serving.rows").inc(320)
    session.counter("serving.host_syncs").inc(10)
    session.counter("serving.cold_entities", coordinate="per_user").inc(3)
    session.counter("serving.compilations").inc(5)
    session.gauge("serving.qps").set(1234.5)
    session.histogram("serving.request_latency_s").observe(0.002)
    session.histogram("serving.padded_fraction").observe(0.25)
    session.finalize(str(tmp_path))
    text = render_markdown(
        json.load(open(tmp_path / "telemetry" / "run_report.json"))
    )
    assert "## Online serving" in text
    assert "| serving.requests | 40 |" in text
    assert "| serving.batches | 10 |" in text  # summed over bucket labels
    assert "| requests per batch (coalescing) | 4 |" in text
    assert "| serving.host_syncs per batch | 1 |" in text
    assert "| serving.cold_entities | 3 |" in text
    assert "| serving.qps | 1234.5 |" in text
    assert "serving.request_latency_s" in text
    assert "serving.padded_fraction" in text

    plain = TelemetrySession("no-serving")
    plain.counter("rows").inc()
    plain.finalize(str(tmp_path / "plain"))
    text2 = render_markdown(
        json.load(open(tmp_path / "plain" / "telemetry" / "run_report.json"))
    )
    assert "## Online serving" not in text2


def test_render_markdown_online_section(tmp_path):
    """The online.* row block (ISSUE 15 satellite): ingest/refresh/lock
    counters, the in-place growth split, the refresh-latency distribution,
    and the per-bin capacity-headroom table; absent metrics -> absent
    section."""
    session = TelemetrySession("online-test")
    session.counter("online.refreshes").inc(3)
    session.counter("online.batches_ingested").inc(4)
    session.counter("online.rows_ingested").inc(500)
    session.counter("online.coordinates_refreshed").inc(7)
    session.counter("online.coordinates_locked").inc(2)
    session.counter("online.publishes").inc(3)
    session.counter("onboard.rows_in_place", column="userId").inc(420)
    session.counter("onboard.rows_migrated", column="userId").inc(60)
    session.counter("onboard.entities_migrated", column="userId").inc(2)
    session.counter("onboard.entities_new", column="userId").inc(9)
    session.gauge("online.staleness_s").set(0.0)
    session.gauge("onboard.bin_row_capacity", column="userId", bin=0).set(64)
    session.gauge("onboard.bin_rows_live", column="userId", bin=0).set(50)
    session.gauge("onboard.bin_row_headroom", column="userId", bin=0).set(14)
    session.histogram("online.refresh_latency_s").observe(1.5)
    session.finalize(str(tmp_path))
    text = render_markdown(
        json.load(open(tmp_path / "telemetry" / "run_report.json"))
    )
    assert "## Online learning" in text
    assert "| online.refreshes | 3 |" in text
    assert "| online.rows_ingested | 500 |" in text
    assert "| online.coordinates_refreshed | 7 |" in text
    assert "| online.coordinates_locked | 2 |" in text
    assert "| onboard.rows_in_place | 420 |" in text
    assert "| onboard.entities_migrated | 2 |" in text
    assert "online.refresh_latency_s" in text
    assert "| userId | 0 | 64 | 50 | 14 |" in text

    plain = TelemetrySession("no-online")
    plain.counter("rows").inc()
    plain.finalize(str(tmp_path / "plain"))
    text2 = render_markdown(
        json.load(open(tmp_path / "plain" / "telemetry" / "run_report.json"))
    )
    assert "## Online learning" not in text2


# ------------------------------------------------------ driver integration


@pytest.fixture(scope="module")
def trained_run(tmp_path_factory):
    from photon_tpu.drivers import train as train_driver

    out = str(tmp_path_factory.mktemp("telem_train") / "out")
    summary = train_driver.run(train_driver.build_parser().parse_args([
        "--input", "synthetic:logistic_regression:200:8:0",
        "--validation-input", "synthetic:logistic_regression:100:8:1:0",
        "--reg-weights", "0.5,2.0", "--max-iterations", "10",
        "--output-dir", out, "--backend", "cpu",
    ]))
    return out, summary


def test_train_driver_writes_run_report(trained_run):
    out, _ = trained_run
    with open(os.path.join(out, "telemetry", "run_report.json")) as f:
        report = json.load(f)
    assert report["status"] == "success" and report["error"] is None
    assert report["driver"] == "train"
    counters = {
        (e["name"], tuple(sorted(e["labels"].items()))): e["value"]
        for e in report["metrics"]["counters"]
    }
    # One solve per lambda, recorded by the optimizer tracker.
    assert counters[("optimizer.solves", (("lam", "0.5"), ("optimizer", "lbfgs")))] == 1
    assert counters[("optimizer.solves", (("lam", "2"), ("optimizer", "lbfgs")))] == 1
    assert counters[("train.sweep_entries", ())] == 2
    span_names = {s["name"] for s in report["spans"]}
    # Span names are a closed set: the lambda is an attribute, not a name.
    assert {"load-data", "train.lambda", "save-models"} <= span_names
    assert sorted(
        s["attributes"]["reg_weight"] for s in report["spans"]
        if s["name"] == "train.lambda"
    ) == [0.5, 2.0]
    assert report["environment"]["jax"]["backend"] == "cpu"
    # Spans mirror the logger's phase-times dict.
    assert set(report["phase_totals"]) == span_names


def test_train_summary_stays_telemetry_free(trained_run):
    """training_summary.json must stay byte-stable across identical runs
    (the determinism contract) — all wall-clock telemetry lives in the
    separate telemetry/ artifacts."""
    out, summary = trained_run
    assert "telemetry" not in summary
    with open(os.path.join(out, "training_summary.json")) as f:
        assert "run_id" not in json.load(f)


def test_no_telemetry_flag_writes_nothing(tmp_path):
    from photon_tpu.drivers import train as train_driver

    out = str(tmp_path / "out")
    train_driver.run(train_driver.build_parser().parse_args([
        "--input", "synthetic:logistic_regression:100:6:0",
        "--reg-weights", "1.0", "--max-iterations", "5",
        "--output-dir", out, "--backend", "cpu", "--no-telemetry",
    ]))
    assert os.path.exists(os.path.join(out, "best_model.avro"))
    assert not os.path.exists(os.path.join(out, "telemetry"))


def test_failed_run_leaves_error_report(tmp_path):
    from photon_tpu.drivers import train as train_driver

    out = str(tmp_path / "out")
    with pytest.raises(FileNotFoundError):
        train_driver.run(train_driver.build_parser().parse_args([
            "--input", str(tmp_path / "does-not-exist.libsvm"),
            "--output-dir", out, "--backend", "cpu",
        ]))
    with open(os.path.join(out, "telemetry", "run_report.json")) as f:
        report = json.load(f)
    assert report["status"] == "error"
    assert "FileNotFoundError" in report["error"]


def test_multiprocess_prebody_failure_writes_rank0_only(tmp_path):
    """A distributed run that dies before the driver body learns its rank
    from jax.process_index() (bad input path on every rank) must not have
    N processes writing the same run_report.json: telemetry_run gates the
    error-path write on the operator-declared --process-id."""
    import argparse

    from photon_tpu.drivers.common import telemetry_run
    from photon_tpu.utils import PhotonLogger

    def attempt(outdir, **distributed):
        args = argparse.Namespace(
            telemetry=True, output_dir=str(outdir), **distributed
        )
        logger = PhotonLogger("photon_tpu.test_telemetry")
        with pytest.raises(RuntimeError):
            with telemetry_run(args, "train", logger):
                raise RuntimeError("pre-body failure")
        return os.path.exists(
            os.path.join(str(outdir), "telemetry", "run_report.json")
        )

    assert attempt(tmp_path / "rank1", coordinator="h:1", process_id=1,
                   num_processes=2) is False
    assert attempt(tmp_path / "rank0", coordinator="h:1", process_id=0,
                   num_processes=2) is True
    assert attempt(tmp_path / "single") is True  # no --coordinator: write


def test_stream_score_parts_keeps_one_span(tmp_path):
    """Streamed scoring exists for beyond-host-memory part layouts, so it
    must not retain one Span per part file: the loop gets a single
    stream-score span (per-chunk timing lives in the bounded stream.*
    histograms), while the per-file phase logs/phase_times stay."""
    from types import SimpleNamespace

    from photon_tpu.drivers.common import stream_score_parts
    from photon_tpu.utils import PhotonLogger

    parts = tmp_path / "parts"
    parts.mkdir()
    for i in range(3):
        (parts / f"part-{i:05d}").write_text("x\n")

    logger = PhotonLogger("photon_tpu.test_telemetry")
    session = TelemetrySession("stream-test")
    session.attach(logger)
    chunk = SimpleNamespace(num_examples=2)
    n = stream_score_parts(
        str(parts),
        lambda path: chunk,
        lambda c: (np.zeros(2), np.zeros(2), c.num_examples),
        str(tmp_path / "scores.txt"),
        logger, telemetry=session,
    )
    assert n == 6
    names = [s["name"] for s in session.tracer.export()]
    assert names == ["stream-score"]  # one span total, not one per file
    assert session.registry.histogram("stream.chunk_seconds").count == 3
    # The per-file phase timing still reaches the legacy phase_times dict.
    assert sum(1 for k in logger.phase_times if k.startswith("score-")) == 3


def test_game_driver_telemetry(tmp_path):
    from photon_tpu.drivers import train_game

    out = str(tmp_path / "out")
    train_game.run(train_game.build_parser().parse_args([
        "--input", "synthetic-game:12:4:6:3:1:5",
        "--validation-split", "0.25",
        "--coordinate", "fixed:type=fixed,shard=global,max_iters=5",
        "--coordinate", "per0:type=random,shard=re0,entity=re0,max_iters=3",
        "--descent-iterations", "2",
        "--output-dir", out, "--backend", "cpu",
    ]))
    with open(os.path.join(out, "telemetry", "run_report.json")) as f:
        report = json.load(f)
    assert report["status"] == "success"
    counters = {
        (e["name"], tuple(sorted(e["labels"].items()))): e["value"]
        for e in report["metrics"]["counters"]
    }
    assert counters[("descent.iterations", ())] == 2
    assert counters[("descent.coordinate_updates", (("coordinate", "fixed"),))] == 2
    assert counters[("estimator.configurations", ())] == 1
    # Fixed effect records through the tracker, random through entity stats.
    assert counters[("optimizer.solves", (("coordinate", "fixed"),))] == 2
    assert counters[("re_solver.entities", (("coordinate", "per0"),))] > 0
    span_names = [s["name"] for s in report["spans"]]
    assert span_names.count("descent.iteration") == 2
    assert "estimator.fit" in span_names
    # The descent iteration span carries the validation metrics.
    iter_spans = [s for s in report["spans"] if s["name"] == "descent.iteration"]
    assert any("metrics" in s.get("attributes", {}) for s in iter_spans)
    gauges = {e["name"] for e in report["metrics"]["gauges"]}
    assert "descent.validation_metric" in gauges
    # The run report reads the program's own names (ISSUE 27): spans by
    # name, the optimizer's evaluations, the per-bin solve work, the bytes
    # the layout build handed to the device.
    text = render_markdown(report)
    for heading in ("## Spans by name", "## Optimizer work",
                    "## Entity solves", "## Layout bytes"):
        assert heading in text, heading
    assert "| descent.iteration | 2 |" in text
    assert "| to device: entity_bins |" in text
    evaluations = counters[("optimizer.evaluations", (("coordinate", "fixed"),))]
    trials = counters[
        ("optimizer.line_search_steps", (("coordinate", "fixed"),))
    ]
    assert "| fixed | 2 | " in text
    assert f"| {evaluations:g} | {trials:g} |" in text
    its = counters[
        ("solves.newton_iterations", (("bin", "0"), ("coordinate", "per0")))
    ]
    cells = counters[("solves.cells", (("bin", "0"), ("coordinate", "per0")))]
    assert f"| {its:g} | {cells:g} |" in text


def test_report_program_work_sections_from_counters():
    """Each table of the program-work section reads its counters exactly and
    is absent when they are."""
    session = TelemetrySession("work")
    with session.span("descent.coordinate", coordinate="fixed"):
        pass
    with session.span("descent.coordinate", coordinate="per_user"):
        pass
    session.counter("optimizer.solves", coordinate="fixed").inc(2)
    session.counter("optimizer.iterations", coordinate="fixed").inc(13)
    session.counter("optimizer.evaluations", coordinate="fixed").inc(19)
    session.counter("optimizer.line_search_steps", coordinate="fixed").inc(13)
    session.counter("optimizer.evaluations", coordinate="tron").inc(4)
    session.counter("optimizer.margin_passes_spared", coordinate="tron").inc(6)
    session.counter("layout.h2d_bytes", what="aligned").inc(3 * 2**20)
    session.counter("layout.cache_bytes", op="write").inc(2**19)
    session.counter("layout.skipped", layout="fm").inc()
    session.counter(
        "fixed_effect.layout", coordinate="fixed", kind="sparse",
        kernel="blocked").inc()
    session.counter(
        "score.fixed_dispatches", coordinate="fixed", kernel="blocked").inc(4)
    session.counter("score.sparse_entries", coordinate="fixed").inc(1024)
    report = session.build_report()
    text = render_markdown(report)
    (row,) = [
        line for line in text.splitlines()
        if line.startswith("| descent.coordinate |")
        and line.count("|") == 5  # not the two-column phase table's row
    ]
    total = sum(s["duration_s"] for s in report["spans"])
    assert row.split(" | ")[1:3] == ["2", f"{total:.3f}"]
    assert "| fixed | 2 | 13 | 19 | 13 | — |" in text
    assert "| tron | — | — | 4 | — | 6 |" in text
    assert "| to device: aligned | 3.0 |" in text
    assert "| layout cache write | 0.5 |" in text
    assert "| not built, another kernel won the probe: fm | none (x1) |" in text
    assert ("| fixed | layout: sparse, value+gradient kernel blocked | 1 |"
            in text)
    assert "| fixed | scores by blocked | 4 |" in text
    assert "| fixed | sparse entries those scores read | 1024 |" in text

    plain = render_markdown({"driver": "t", "metrics": {"counters": []}})
    for heading in ("Spans by name", "Optimizer work", "Layout bytes",
                    "Fixed effect"):
        assert heading not in plain


# ------------------------------------------------------- compile accounting


def _compile_report(programs: int) -> dict:
    """A report whose counters hold ``programs`` programs' compile rows:
    program ``i`` asked ``i + 1`` times (one miss, the rest hits), its
    seconds growing with ``i``."""
    registry = MetricsRegistry()  # not a session: its report would append
    # the process registry's own compile rows, which earlier tests left
    for i in range(programs):
        name = f"jit_program_{i:02d}"
        registry.counter(
            "compile.requests", program=name, outcome="hit").inc(i)
        registry.counter(
            "compile.requests", program=name, outcome="miss").inc()
        for phase, seconds in (("trace", 1.0), ("lower", 0.5),
                               ("cache_load", 0.25), ("xla_compile", 2.0)):
            registry.counter(
                "compile.seconds", program=name, phase=phase
            ).inc(seconds * (i + 1))
    # Traced inside another program: seconds and no request.
    registry.counter(
        "compile.seconds", program="jit__where", phase="trace").inc(0.125)
    return {"driver": "compile", "metrics": registry.snapshot()}


@pytest.mark.parametrize("programs", [2, 30])
def test_report_compile_section_from_counters(programs):
    """One row a program, dearest first: requests, hits, misses, seconds by
    phase and their sum; past twenty the rest in one row; a last line with
    the totals."""
    text = render_markdown(_compile_report(programs))
    section = text.split("## Compile\n", 1)[1].split("\n## ", 1)[0]
    rows = [line for line in section.splitlines() if line.startswith("| ")]
    assert rows[0].startswith(
        "| program | requests | hits | misses | trace (s) | lower (s) "
        "| cache_load (s) | xla_compile (s) | total (s) |")
    last = programs - 1
    assert rows[1] == (
        f"| jit_program_{last:02d} | {last + 1} | {last} | 1 "
        f"| {last + 1:.3f} | {(last + 1) * 0.5:.3f} "
        f"| {(last + 1) * 0.25:.3f} | {(last + 1) * 2.0:.3f} "
        f"| {(last + 1) * 3.75:.3f} |")
    named = [r for r in rows[1:] if r.startswith("| jit_")]
    n = programs * (programs + 1) // 2  # 1 + 2 + ... + programs
    totals = (f"| **all {programs + 1} programs** | {n} | {n - programs} "
              f"| {programs} | {n + 0.125:.3f} | {n * 0.5:.3f} "
              f"| {n * 0.25:.3f} | {n * 2.0:.3f} | {n * 3.75 + 0.125:.3f} |")
    assert rows[-1] == totals
    if programs <= 20:
        assert len(named) == programs + 1
        assert named[-1] == ("| jit__where | 0 | 0 | 0 | 0.125 | 0.000 "
                             "| 0.000 | 0.000 | 0.125 |")
    else:
        assert len(named) == 20
        m = 10  # programs 00..09: 1 + ... + 10 requests = 55
        assert rows[-2] == (
            f"| {m + 1} more programs | 55 | {55 - m} | {m} "
            f"| {55 + 0.125:.3f} | {55 * 0.5:.3f} | {55 * 0.25:.3f} "
            f"| {55 * 2.0:.3f} | {55 * 3.75 + 0.125:.3f} |")


def test_report_has_no_compile_section_without_its_counters():
    assert "## Compile" not in render_markdown(
        {"driver": "t", "metrics": {"counters": []}})


_COMPILE_RUN = {"counters": {"counters": [
    {"name": "compile.seconds",
     "labels": {"program": "jit_glm_fit_lbfgs", "phase": "trace"},
     "value": 2.0},
    {"name": "compile.seconds",
     "labels": {"program": "jit__where", "phase": "trace"}, "value": 0.5},
    {"name": "compile.seconds",
     "labels": {"program": "jit_glm_fit_lbfgs", "phase": "lower"},
     "value": 0.75},
    {"name": "compile.seconds",
     "labels": {"program": "jit_glm_fit_lbfgs", "phase": "cache_load"},
     "value": 0.25},
    {"name": "compile.seconds",
     "labels": {"program": "jit_metric_auc", "phase": "xla_compile"},
     "value": 41.0},
    {"name": "compile.requests",
     "labels": {"program": "jit_glm_fit_lbfgs", "outcome": "hit"},
     "value": 2.0},
    {"name": "compile.requests",
     "labels": {"program": "jit_metric_auc", "outcome": "miss"},
     "value": 1.0},
    {"name": "compile.requests",
     "labels": {"program": "jit_iota", "outcome": "uncached"}, "value": 1.0},
    {"name": "span.seconds", "labels": {"span": "kernels.probe"},
     "value": 8.0},
], "gauges": []}}


@pytest.mark.parametrize("metric, wanted", [
    ("setup.trace_s", 2.5),
    ("setup.lower_s", 0.75),
    ("setup.cache_load_s", 0.25),
    ("setup.xla_compile_s", 41.0),
    ("setup.programs", 4.0),
])
def test_compile_reader_on_a_hand_made_run(metric, wanted):
    """The benchmark's five readers of the compile accounting: the value
    where the rows are there, nothing where the program publishes none (the
    parent commit), and 0 for a phase nothing ran in (a warm run's
    ``xla_compile``) so long as the accounting is there at all."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks import run as harness

    read = harness.load_module(
        os.path.join(root, "benchmarks", "layer_metrics"), metric).read
    assert read(_COMPILE_RUN) == wanted
    others = {"counters": {"counters": [
        row for row in _COMPILE_RUN["counters"]["counters"]
        if not row["name"].startswith("compile.")], "gauges": []}}
    assert read(others) is None
    assert read({"counters": {"counters": [], "gauges": []}}) is None
    if metric.endswith("_s"):
        phase = metric[len("setup."):-len("_s")]
        without = {"counters": {"counters": [
            row for row in _COMPILE_RUN["counters"]["counters"]
            if row["labels"].get("phase") != phase], "gauges": []}}
        assert read(without) == 0.0
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == metric]
    assert entry == {
        "name": metric, "unit": "s" if metric.endswith("_s") else "count",
        "better": "lower", "source": "program_counter",
        "layer": "drivers + device policy", "moves": "setup_s",
    }
