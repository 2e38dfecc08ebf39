"""The per-layer metrics that read the program's own spans, counters and
device program names (PR 27): each reader on a hand-built ``run`` dict — its
value, and ``None`` when the span or program name is absent (the parent
commit, or a refactor that lost the name) — and the CPU rehearsal of both
cells, traced, with the metrics that can be read on the host."""

import json
import os

import pytest

from benchmarks import run as harness

LAYER_DIR = os.path.join(harness.ROOT, "benchmarks", "layer_metrics")
NEW = (
    "layout.sort_s", "layout.aligned_pack_s", "layout.cache_io_s",
    "layout.entity_bins_s", "setup.kernel_probe_s",
    "optimizer.evaluations_per_fit", "solves.newton_iterations_per_fit",
    "solves.device_s", "validation.device_s",
)


def read(metric: str, run: dict):
    return harness.load_module(LAYER_DIR, metric).read(run)


def row(name, value, **labels):
    return {"name": name, "labels": {k: str(v) for k, v in labels.items()},
            "value": float(value)}


def span(name, seconds, count=1):
    return [row("span.seconds", seconds, span=name),
            row("span.count", count, span=name)]


def run_dict(counters=(), steps=3, by_module=(), traced_steps=1) -> dict:
    return {
        "counters": {"counters": list(counters), "gauges": []},
        "steps": [{} for _ in range(steps)],
        "trace": {"by_module": [list(m) for m in by_module],
                  "busy_s": 5.0, "window_s": 5.1},
        "traced_steps": traced_steps,
    }


# What the parent commit's program gives the readers: kernel selections only.
PARENT = run_dict(
    [row("kernels.selected", 12, kernel="autodiff")],
    by_module=[("jit__run_newton_fit(1)", 4.0), ("jit__unknown(2)", 0.1),
               ("jit_area_under_roc_curve(3)", 0.5)],
)


@pytest.mark.parametrize("metric", NEW)
def test_absent_name_is_an_absent_metric(metric):
    assert read(metric, PARENT) is None
    assert read(metric, run_dict()) is None


def test_every_new_metric_has_an_entry_and_a_reader():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for metric in NEW:
        assert os.path.exists(os.path.join(LAYER_DIR, metric + ".py"))
        assert entries[metric]["workloads"], metric
    assert [n for n in entries if n in NEW] == list(NEW)  # appended, in order


def test_layout_span_readers():
    counters = (
        span("layout.feature_major", 44.5)
        + span("layout.aligned_pack", 70.0, count=2)  # two directions
        + span("layout.cache_key", 1.25) + span("layout.cache_write", 4.0)
        + span("layout.cache_read", 0.75)
        + span("layout.entity_bins", 3.5, count=8)
        + span("kernels.probe", 5.25)
        + span("descent.coordinate", 9.0)
    )
    run = run_dict(counters)
    assert read("layout.sort_s", run) == 44.5
    # The bin-packing alone: the span less its cache children.
    assert read("layout.aligned_pack_s", run) == 70.0 - 4.0 - 0.75
    assert read("layout.cache_io_s", run) == 1.25 + 4.0 + 0.75
    assert read("layout.entity_bins_s", run) == 3.5
    assert read("setup.kernel_probe_s", run) == 5.25
    # A layout under the cache's size floor is neither hashed nor stored.
    uncached = run_dict(span("layout.aligned_pack", 2.0))
    assert read("layout.aligned_pack_s", uncached) == 2.0
    assert read("layout.cache_io_s", uncached) is None


def test_evaluations_per_fit_reads_the_process_registry():
    # GLM: 4 fits (3 + warm-up) through GlmOptimizationProblem.run.
    glm = run_dict([row("optimizer.evaluations", 44)])
    assert read("optimizer.evaluations_per_fit", glm) == 11.0
    # GAME: the same unlabelled counter holds the fixed effect's fits; the
    # session's {coordinate=...} rows of the same fits are not added to it.
    game = run_dict([
        row("optimizer.evaluations", 120),
        row("optimizer.evaluations", 120, coordinate="fixed"),
    ], steps=3)
    assert read("optimizer.evaluations_per_fit", game) == 30.0
    labelled_only = run_dict(
        [row("optimizer.evaluations", 120, coordinate="fixed")]
    )
    assert read("optimizer.evaluations_per_fit", labelled_only) is None


def test_newton_iterations_per_fit_sums_every_bin():
    run = run_dict([
        row("solves.newton_iterations", 40, coordinate="per_user", bin=0),
        row("solves.newton_iterations", 24, coordinate="per_user", bin=1),
        row("solves.newton_iterations", 36, coordinate="per_item", bin=0),
        row("solves.cells", 1e9, coordinate="per_user", bin=0),
    ], steps=4)
    assert read("solves.newton_iterations_per_fit", run) == 20.0


def test_device_seconds_by_program_name():
    bins = [
        row("solves.newton_iterations", 40, coordinate=c, bin=b)
        for c, b in (("per_user", 0), ("per_user", 1), ("per_item", 0))
    ]
    by_module = [
        ("jit_entity_solve_newton(11)", 3.0),
        ("jit_entity_solve_newton(12)", 1.0),
        ("jit_entity_solve_newton_cg(13)", 0.5),
        ("jit_metric_auc(14)", 0.75),
        ("jit_metric_logloss(15)", 0.25),
        ("jit_score_random(16)", 0.5),   # training and validation rows alike
        ("jit_score_table_update(17)", 0.25),
        ("jit_glm_fit_lbfgs(18)", 0.1),
    ]
    run = run_dict(bins, by_module=by_module, traced_steps=2)
    assert read("solves.device_s", run) == 4.5 / 2
    assert read("validation.device_s", run) == 1.0 / 2
    untraced = dict(run, trace=None)
    assert read("solves.device_s", untraced) is None
    assert read("validation.device_s", untraced) is None
    # by_module keeps ten entries: a bin program cut from it (fewer solve
    # entries than bins counted) is an absent metric, not a smaller one.
    cut = run_dict(bins, by_module=by_module[1:], traced_steps=2)
    assert read("solves.device_s", cut) is None
    assert read("validation.device_s", cut) == 1.0 / 2


# -- the rehearsal: the real program feeds the real readers --------------------


def _rehearse(cell: str) -> dict:
    result = harness.run_cell(cell, 2 ** 31 + 27, 0.3, trace=True,
                              rehearsal=True)
    assert result["correct"], result["compared"]
    return {k: v["value"] for k, v in result["metrics"].items()}


def test_rehearsal_glm_reports_the_program_metrics(monkeypatch):
    # Tiny layouts sit under the probe's and the layout cache's size floors
    # and the host runs no Mosaic: the test (not the benchmark) lowers the
    # floors and pins the aligned kernel so that every GLM metric has
    # something to read on the host.
    from photon_tpu.ops import sparse_grad_select

    monkeypatch.setenv("PHOTON_LAYOUT_CACHE_FLOOR", "1")
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    metrics = _rehearse("glm_sparse_fit")
    for name in ("layout.sort_s", "layout.aligned_pack_s",
                 "layout.cache_io_s"):
        assert metrics[name] > 0, name
    assert metrics["optimizer.evaluations_per_fit"] >= (
        metrics["optimizer.iterations_per_fit"] + 3
    )
    assert metrics["layout.sort_s"] + metrics["layout.aligned_pack_s"] \
        + metrics["layout.cache_io_s"] <= metrics["setup.layout_s"]
    # The probe runs under auto selection only.
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "auto")
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_FLOOR", "1")
    sparse_grad_select._CACHE.clear()
    metrics = _rehearse("glm_sparse_fit")
    assert metrics["setup.kernel_probe_s"] > 0
    assert "layout.entity_bins_s" not in metrics  # GAME's, not this cell's


def test_rehearsal_game_reports_the_program_metrics():
    metrics = _rehearse("game_fit")
    assert metrics["layout.entity_bins_s"] > 0
    assert metrics["solves.newton_iterations_per_fit"] > 0
    assert metrics["optimizer.evaluations_per_fit"] > 0
    assert metrics["descent.host_syncs_per_fit"] == 2
    # The host's trace has no device plane: the two device_trace metrics
    # need the chip (their readers are tested above on by_module rows).
    assert "solves.device_s" not in metrics
    assert "layout.sort_s" not in metrics  # GLM's, not this cell's
