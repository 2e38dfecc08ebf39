#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, in this process, on this machine.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, runner and per-layer metric readers
are files found by the names in ``BENCHMARK.json`` (see README.md here), so a
new cell is new files and new entries, never an edit.  The last line of
standard output is the result: one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``compared`` (each number ``correct`` was decided on
beside its limit).  Without a TPU (or with fewer chips than the cell asks
for) it exits 1 and prints no result.  ``--cpu-rehearsal`` is the one way to
run it on the host: tiny sizes, every line labelled ``cpu``, no result line.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def log(*parts, rehearsal: bool = False) -> None:
    print(*((("cpu",) if rehearsal else ()) + parts), file=sys.stderr,
          flush=True)


# -- what BENCHMARK.json names --------------------------------------------------


def load_cell(workload: str, benchmark_path: str | None = None) -> dict:
    """The cell's entry, its configuration file, its traffic file and the
    per-layer metrics it reports."""
    with open(benchmark_path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    base = os.path.dirname(
        os.path.abspath(benchmark_path)) if benchmark_path else ROOT
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"unknown workload {workload!r}; BENCHMARK.json has "
            f"{sorted(cells)}"
        )
    cell = cells[workload]
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(base, entry["file"])) as f:
        config = json.load(f)
    traffic_dir = os.path.join(
        base, os.path.dirname(os.path.dirname(entry["file"])), "traffic"
    )
    with open(os.path.join(traffic_dir, cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def reported(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "cell": cell, "config": config, "traffic": traffic,
        "layer_dir": os.path.join(os.path.dirname(traffic_dir),
                                  "layer_metrics"),
        "runner_dir": os.path.join(os.path.dirname(traffic_dir), "runners"),
        "end_to_end": [m for m in bench["end_to_end"] if reported(m)],
        "per_layer": [m for m in bench["per_layer"] if reported(m)],
    }


def load_module(directory: str, name: str):
    """``<directory>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- instruments ------------------------------------------------------------------


class CompileCounter:
    """XLA compile requests: those that went to the compiler (persistent
    cache misses) and those the persistent cache served (copy of
    ``chip_smoke.CompileCounter``)."""

    def __init__(self):
        import jax.monitoring

        self.compiled = self.cache_hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.compiled += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compiled, self.cache_hits


class Clock:
    """Host spans by name; each is also a ``bench.<name>`` annotation in the
    profiler's trace when one is being taken."""

    def __init__(self):
        self.seconds: dict = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) + (
            time.monotonic() - t0
        )


def device_or_exit(chips: int, rehearsal: bool) -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    wanted = "cpu" if rehearsal else "tpu"
    if platform != wanted or len(devices) < chips:
        log(f"benchmarks/run.py: jax {jax.__version__} found {len(devices)} "
            f"{platform!r} device(s) ({devices[0].device_kind}); this cell "
            f"needs {chips} {wanted!r} chip(s)")
        raise SystemExit(1)
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()
    ]
    return int(max(peaks))


# -- one run ----------------------------------------------------------------------


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearsal: bool = False, benchmark_path: str | None = None,
             hook=None, dump: str | None = None) -> dict:
    """Everything after the look for a chip.  ``hook(runner, state)`` runs
    after set-up's build and before the warm-up step: the tests break the
    timed path there."""
    import jax

    from benchmarks import rooflines, trace_reduce

    spec = load_cell(workload, benchmark_path)
    config, traffic = spec["config"], spec["traffic"]
    if rehearsal:
        config = dict(config, sizes=dict(config["sizes"],
                                         **config["rehearsal_sizes"]))
    say = lambda *p: log(*p, rehearsal=rehearsal)  # noqa: E731
    runner = load_module(spec["runner_dir"], traffic["runner"])
    compiles = CompileCounter()
    clock = Clock()

    # Set-up: data, layout, compile or cache load, one warm-up step.
    state = runner.setup(config, traffic, seed, clock)
    if hook is not None:
        hook(runner, state)
    with clock("warmup"):
        warm = runner.step(state)
    setup_compiles, setup_hits = compiles.snapshot()
    setup_s = time.monotonic() - T0
    say(f"[{workload}] set-up {setup_s:.1f}s "
        + " ".join(f"{k}={v:.1f}s" for k, v in clock.seconds.items())
        + f" compiled={setup_compiles} cache_hits={setup_hits} warm={warm}")

    # The window: whole steps until --seconds have passed.
    traced_steps = int(traffic.get("traced_steps", 2)) if trace else 0
    steps, ends = [], []
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = None
        if hasattr(jax.profiler, "ProfileOptions"):
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, **(
            {"profiler_options": options} if options is not None else {}
        ))
    t_start = t_untraced = time.monotonic()
    while True:
        with jax.profiler.TraceAnnotation("bench.step"):
            steps.append(runner.step(state))
        ends.append(time.monotonic())
        if trace and len(steps) == traced_steps:
            jax.profiler.stop_trace()
            t_untraced = time.monotonic()
        if ends[-1] - t_start >= seconds and len(steps) > traced_steps:
            break
    window_compiles = sum(compiles.snapshot()) - setup_compiles - setup_hits
    say(f"[{workload}] window: {len(steps)} step(s) in "
        f"{ends[-1] - t_start:.3f}s; compile requests in the window: "
        f"{window_compiles} (must be 0); steps: {steps[-1]}")
    counters = runner.counters(state)
    selected = {
        m["labels"]["kernel"]: int(m["value"]) for m in counters["counters"]
        if m["name"] == "kernels.selected"
    }
    if selected:
        say(f"[{workload}] kernels selected (process-wide): {selected}")
    memory_peak = memory_peak_bytes()
    device = device_or_exit(spec["cell"]["chips"], rehearsal)
    device["memory_peak_bytes"] = memory_peak

    metrics: dict = {}
    result: dict = {"correct": False, "attempted": len(steps), "failed": 0,
                    "metrics": metrics, "device": device}
    if not trace:
        values = {
            "setup_s": setup_s,
            "fit_s": (ends[-1] - t_start) / len(steps),
        }
        for metric in spec["end_to_end"]:
            metrics[metric["name"]] = {"value": values[metric["name"]],
                                       "unit": metric["unit"]}
    else:
        reduced = trace_reduce.reduce(TRACE_DIR)
        if dump:
            with open(dump, "w") as f:
                json.dump({"describe": trace_reduce.describe(TRACE_DIR),
                           "reduce": trace_reduce.reduce(TRACE_DIR, top=60)},
                          f, indent=1)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"], device["window_s"] = (
            reduced["busy_s"], reduced["window_s"]
        )
        untraced = len(steps) - traced_steps
        run = {
            "clock": clock.seconds, "steps": steps, "counters": counters,
            "trace": reduced, "traced_steps": traced_steps,
            "seconds_per_step": (ends[-1] - t_untraced) / untraced,
            "setup_compiles": setup_compiles,
            "memory_peak_bytes": memory_peak,
        }
        if not rehearsal:
            peak = rooflines.peaks(device["kind"])
            run["floor"] = runner.floor(state, steps, peak)
            run["work"] = runner.work(state, steps)
            say(f"[{workload}] floor of one step: "
                f"{run['floor']['seconds']:.6f}s, bound by "
                f"{run['floor']['phases']}; FLOP-only share of peak: "
                f"{100 * run['floor']['flops'] / peak['flops_per_s'] / run['seconds_per_step']:.6f}%"
                f" at {run['seconds_per_step']:.4f}s a step")
        for metric in spec["per_layer"]:
            value = load_module(spec["layer_dir"], metric["name"]).read(run)
            if value is not None:
                metrics[metric["name"]] = {"value": value,
                                           "unit": metric["unit"]}
        result["breakdown"] = {
            "device_ops": reduced["by_op"], "idle_gaps": reduced["gaps"],
        }
        say(f"[{workload}] device time by module: {reduced['by_module']}")

    # Correctness: after the window, the peak read and the program freed.
    t_check = time.monotonic()
    numbers = runner.check(state)
    limits = traffic["limits"]
    compared = {
        name: {"value": numbers[name], "limit": limits[name]}
        for name in limits
    }
    result["correct"] = window_compiles == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in compared.values()
    )
    result["compared"] = dict(
        compared, compiles_in_window={"value": window_compiles, "limit": 0}
    )
    say(f"[{workload}] reference and comparison took "
        f"{time.monotonic() - t_check:.1f}s; not compared: "
        + str({k: v for k, v in numbers.items() if k not in limits}))
    say(f"correct = {result['correct']}; each number compared, beside its "
        "limit:")
    for name, c in result["compared"].items():
        say(f"compared {name} = {c['value']:.6g} (limit {c['limit']:.6g})")
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--cpu-rehearsal", action="store_true",
                        help="tiny sizes on the host; every line is "
                        "labelled cpu and no result line is printed")
    parser.add_argument("--dump", default=None,
                        help="with --trace 1: also write the trace's planes, "
                        "lines and a longer reduction to this file (for a "
                        "look by hand; the driver never passes it)")
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "photon_tpu")):
        log("benchmarks/run.py: no photon_tpu/ beside benchmarks/: nothing "
            "to measure")
        return 1
    if args.cpu_rehearsal:
        # Explicit CPU is a request the device policy honours.
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    from photon_tpu.utils import compilation_cache

    spec = load_cell(args.workload)
    device_or_exit(spec["cell"]["chips"], args.cpu_rehearsal)
    compilation_cache.enable()  # <checkout>/.jax_cache unless the env names one
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), rehearsal=args.cpu_rehearsal,
                      dump=args.dump)
    if args.cpu_rehearsal:
        log("rehearsal done (no result line: results come from a chip); "
            f"correct={result['correct']}", rehearsal=True)
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
