"""A new configuration, traffic mix, runner and per-layer metric are new
files plus ``BENCHMARK.json`` entries: nothing that is there is edited."""

import json
import os
import textwrap

from benchmarks import run as harness


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(text))


def test_new_cell_is_new_files_only(tmp_path):
    root = str(tmp_path)
    _write(f"{root}/extra/configs/toy.json", """
        {"name": "toy", "generator": "none", "task": "none",
         "sizes": {"n": 64}, "rehearsal_sizes": {"n": 8}}
    """)
    _write(f"{root}/extra/traffic/toy_mix.json", """
        {"name": "toy_mix", "runner": "toy_runner", "traced_steps": 1,
         "limits": {"toy_gap": 0.5}}
    """)
    _write(f"{root}/extra/runners/toy_runner.py", """
        import jax.numpy as jnp

        def setup(config, traffic, seed, clock):
            with clock("data"):
                return {"n": config["sizes"]["n"], "seed": seed, "steps": 0}

        def step(state):
            state["steps"] += 1
            float(jnp.sum(jnp.ones(state["n"])))
            return {"sum": state["n"]}

        def counters(state):
            return {"counters": [{"name": "toy.steps", "labels": {},
                                  "value": state["steps"]}], "gauges": []}

        def work(state, steps):
            return {"n": state["n"]}

        def floor(state, steps, peak):
            return {"seconds": 1e-9, "flops": 1.0, "phases": {}}

        def check(state):
            return {"toy_gap": 0.25, "not_compared": 7.0}
    """)
    _write(f"{root}/extra/layer_metrics/toy.steps_seen.py", """
        def read(run):
            rows = [m["value"] for m in run["counters"]["counters"]
                    if m["name"] == "toy.steps"]
            return rows[0] if rows else None
    """)
    _write(f"{root}/extra/layer_metrics/toy.absent.py", """
        def read(run):
            return None  # nothing to read: left out of the line
    """)
    bench = {
        "command": ["python3", "benchmarks/run.py"], "paths": ["extra"],
        "run_seconds": 1,
        "configs": [{"name": "toy", "source": "x", "reduced": [],
                     "file": "extra/configs/toy.json", "why": "x"}],
        "workloads": [{"name": "toy_cell", "config": "toy",
                       "traffic": "toy_mix", "chips": 1, "why": "x"}],
        "end_to_end": [
            {"name": "fit_s", "unit": "s", "better": "lower", "bound": 0.03,
             "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [
            {"name": "toy.steps_seen", "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "toy", "moves": "fit_s"},
            {"name": "toy.absent", "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "toy", "moves": "fit_s"},
            {"name": "setup.data_s", "unit": "s", "better": "lower",
             "source": "host_clock", "layer": "x", "moves": "setup_s",
             "workloads": ["some_other_cell"]}],
    }
    path = f"{root}/BENCHMARK.json"
    with open(path, "w") as f:
        json.dump(bench, f)

    timed = harness.run_cell("toy_cell", 2 ** 31 + 5, 0.2, False,
                             rehearsal=True, benchmark_path=path)
    assert timed["correct"] is True
    assert set(timed["metrics"]) == {"fit_s", "setup_s"}
    assert timed["metrics"]["fit_s"]["value"] > 0
    assert timed["attempted"] >= 1 and timed["failed"] == 0
    assert list(timed)[-1] == "compared"
    assert timed["compared"]["toy_gap"] == {"value": 0.25, "limit": 0.5}

    traced = harness.run_cell("toy_cell", 7, 0.2, True, rehearsal=True,
                              benchmark_path=path)
    # The reader that found nothing is left out; the metric of another cell
    # is not asked for; the new reader's number is there.
    assert set(traced["metrics"]) == {"toy.steps_seen"}
    assert traced["metrics"]["toy.steps_seen"]["value"] >= 2
    assert "breakdown" in traced and "busy_s" in traced["device"]


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        spec = harness.load_cell(cell["name"])
        assert os.path.exists(os.path.join(
            spec["runner_dir"], spec["traffic"]["runner"] + ".py"))
        for metric in spec["per_layer"]:
            assert os.path.exists(os.path.join(
                spec["layer_dir"], metric["name"] + ".py")), metric["name"]
        assert set(spec["traffic"]["limits"])
        moved = {m["moves"] for m in spec["per_layer"]}
        assert moved <= {m["name"] for m in spec["end_to_end"]}
