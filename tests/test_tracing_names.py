"""One span system, one process registry, stable device names (ISSUE 27).

What a later perf PR leans on when it says "the trace shows the saving
here": the program's spans are in the profiler's trace under their own
names, totals by span name survive in the registry, counts that live on the
device never add a fetch to the timed path, the optimizers count the
evaluations they run, and the device programs and their phases carry names
the program chose.
"""

from __future__ import annotations

import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import telemetry
from photon_tpu.telemetry import NULL_SESSION, MetricsRegistry, TelemetrySession
from photon_tpu.utils import compilation_cache, device
from photon_tpu.utils.device import named_jit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _counters(registry) -> dict:
    return {
        (c["name"], tuple(sorted(c["labels"].items()))): c["value"]
        for c in registry.snapshot()["counters"]
    }


# -- one span, three sinks -----------------------------------------------------


def _trace_events(trace_dir: str) -> list:
    """``(plane, line, name, start_ns, end_ns, stats)`` of every event."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return [
        (plane.name, line.name, ev.name, int(ev.start_ns),
         int(ev.start_ns + ev.duration_ns), dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for ev in line.events
    ]


@pytest.mark.parametrize("opened_by", ["session", "process"])
def test_span_is_in_the_profiler_trace_and_encloses_its_work(
        tmp_path, opened_by):
    work = named_jit(
        "traced_work", lambda x: jnp.sum(jnp.sin(x) @ jnp.cos(x).T)
    )
    x = jnp.ones((128, 128))
    work(x).block_until_ready()  # compiled before the trace starts
    span = (
        TelemetrySession("t").span if opened_by == "session"
        else telemetry.span
    )
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with span("descent.coordinate", iteration=3, coordinate="re0"):
            work(x).block_until_ready()
        work(x).block_until_ready()  # outside the span
    finally:
        jax.profiler.stop_trace()
    events = _trace_events(str(tmp_path))
    (found,) = [e for e in events if e[2] == "descent.coordinate"]
    plane, _, _, start, end, stats = found
    assert plane.startswith("/host:")
    assert stats["iteration"] == 3 and stats["coordinate"] == "re0"
    # The device-side events of the program (XLA:CPU runs them on host
    # threads, tagged with their module) on the same clock: the first run
    # of the work lies inside the span, the second after it.
    ops = sorted(
        (s, e) for _, _, _, s, e, st in events
        if st.get("hlo_module") == "jit_traced_work"
    )
    inside = [(s, e) for s, e in ops if start <= s and e <= end]
    after = [(s, e) for s, e in ops if s >= end]
    assert inside and after and len(inside) + len(after) == len(ops)


def test_span_totals_equal_the_spans_own_durations():
    session = TelemetrySession("t")
    for i in range(3):
        with session.span("descent.coordinate", iteration=i):
            with session.span("residuals.update"):
                pass
    by_name: dict = {}
    for sp in session.tracer.finished:
        by_name.setdefault(sp.name, []).append(sp.duration_s)
    counters = _counters(session.registry)
    for name, durations in by_name.items():
        key = (("span", name),)
        assert counters[("span.count", key)] == len(durations) == 3
        assert counters[("span.seconds", key)] == pytest.approx(
            sum(durations), rel=1e-12
        )
    assert session.tracer.phase_totals() == pytest.approx({
        name: sum(d) for name, d in by_name.items()
    })


def test_process_span_keeps_totals_but_no_span_objects():
    registry = telemetry.process_registry()
    before = _counters(registry).get(
        ("span.count", (("span", "layout.feature_major"),)), 0.0
    )
    with telemetry.span("layout.feature_major", entries=8) as sp:
        sp.set_attribute("note", "kept on the span only")
    assert _counters(registry)[
        ("span.count", (("span", "layout.feature_major"),))
    ] == before + 1
    assert telemetry._PROCESS_TRACER.finished == []


def test_disabled_session_and_error_spans():
    with NULL_SESSION.span("descent.iteration", iteration=0) as sp:
        sp.set_attribute("k", 1)
    NULL_SESSION.counter("optimizer.evaluations").inc_deferred(jnp.ones(()))
    assert NULL_SESSION.registry.snapshot()["counters"] == []
    session = TelemetrySession("t")
    with pytest.raises(RuntimeError):
        with session.span("estimator.fit"):
            raise RuntimeError("boom")
    (sp,) = session.tracer.finished
    assert sp.status == "error" and "boom" in sp.error
    assert _counters(session.registry)[
        ("span.count", (("span", "estimator.fit"),))
    ] == 1


def test_jax_free_process_spans_stay_jax_free():
    # ``import photon_tpu`` itself pulls jax in (the package imports its
    # math core): telemetry is loaded under a bare stand-in for the package,
    # which is all a jax-free tool needs of it.
    code = (
        "import sys, types\n"
        "pkg = types.ModuleType('photon_tpu')\n"
        "pkg.__path__ = ['photon_tpu']\n"
        "sys.modules['photon_tpu'] = pkg\n"
        "from photon_tpu import telemetry\n"
        "s = telemetry.TelemetrySession('index')\n"
        "with s.span('scan', files=3):\n"
        "    pass\n"
        "with telemetry.span('layout.entity_bins'):\n"
        "    pass\n"
        "c = telemetry.process_registry().counter('optimizer.evaluations')\n"
        "c.inc_deferred(4)\n"
        "rows = telemetry.process_registry().snapshot()['counters']\n"
        "assert 'jax' not in sys.modules, 'telemetry imported jax'\n"
        "assert s.tracer.phase_totals().keys() == {'scan'}\n"
        "print(sorted((r['name'], r['value']) for r in rows\n"
        "             if r['name'] != 'span.seconds'))\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, timeout=120,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip() == (
        "[('optimizer.evaluations', 4.0), ('span.count', 1.0)]"
    )


# -- compile accounting (utils/compilation_cache.py) ---------------------------

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def _play(sequence) -> None:
    """Feed the listeners a recorded sequence through ``jax.monitoring`` as
    jax 0.9 emits it (``dispatch.LogElapsedTimeContextManager``): a span is
    a scalar when it opens, a duration and a time span when it closes; the
    cache's verdict comes between, without a name."""
    from jax import monitoring

    for kind, event, *rest in sequence:
        if kind == "open":
            name, start = rest
            monitoring.record_scalar(event, start, fun_name=name)
        elif kind == "close":
            name, start, end = rest
            monitoring.record_event_duration_secs(
                event, end - start, fun_name=name)
            monitoring.record_event_time_span(
                event, start, end, fun_name=name)
        elif kind == "duration":
            monitoring.record_event_duration_secs(event, *rest)
        else:
            monitoring.record_event(event)


def _compile_rows() -> dict:
    """``{(counter, program, phase or outcome): value}`` of the process
    registry's compile accounting."""
    return {
        (name, dict(labels)["program"],
         dict(labels).get("phase") or dict(labels)["outcome"]): value
        for (name, labels), value in _counters(
            telemetry.process_registry()).items()
        if name.startswith("compile.")
    }


# Each case: the sequence, the rows it must leave (nothing else under
# compile.*), and the seconds the outermost spans cover.
_RECORDED = {
    # Tracing `outer` traces `inner_a` (which traces `leaf`) and `inner_b`,
    # inner first: each second once, the outer program its self time.
    "nested_trace": ([
        ("open", _TRACE, "outer", 100.0),
        ("open", _TRACE, "inner_a", 100.25),
        ("open", _TRACE, "leaf", 100.25),
        ("close", _TRACE, "leaf", 100.25, 100.375),
        ("close", _TRACE, "inner_a", 100.25, 100.5),
        ("open", _TRACE, "inner_b", 100.5),
        ("close", _TRACE, "inner_b", 100.5, 100.75),
        ("close", _TRACE, "outer", 100.0, 101.0),
    ], {
        ("compile.seconds", "jit_outer", "trace"): 0.5,
        ("compile.seconds", "jit_inner_a", "trace"): 0.125,
        ("compile.seconds", "jit_leaf", "trace"): 0.125,
        ("compile.seconds", "jit_inner_b", "trace"): 0.25,
    }, 1.0),
    # A hit: the whole request under cache_load, nothing under xla_compile.
    "hit": ([
        ("open", _BACKEND, "jit(served)", 10.0),
        ("event", "/jax/compilation_cache/compile_requests_use_cache"),
        ("event", _HIT),
        ("duration", "/jax/compilation_cache/compile_time_saved_sec", 3.0),
        ("duration", "/jax/compilation_cache/cache_retrieval_time_sec",
         0.0625),
        ("close", _BACKEND, "jit(served)", 10.0, 10.0625),
    ], {
        ("compile.requests", "jit_served", "hit"): 1,
        ("compile.seconds", "jit_served", "cache_load"): 0.0625,
    }, 0.0625),
    # A miss: the reverse.
    "miss": ([
        ("open", _BACKEND, "jit(compiled)", 20.0),
        ("event", "/jax/compilation_cache/compile_requests_use_cache"),
        ("event", _MISS),
        ("close", _BACKEND, "jit(compiled)", 20.0, 22.5),
    ], {
        ("compile.requests", "jit_compiled", "miss"): 1,
        ("compile.seconds", "jit_compiled", "xla_compile"): 2.5,
    }, 2.5),
    # No cache event since the last request: uncached, and the verdict of
    # the hit before it is not carried over.
    "uncached_after_a_hit": ([
        ("open", _BACKEND, "jit(served)", 10.0),
        ("event", _HIT),
        ("close", _BACKEND, "jit(served)", 10.0, 10.125),
        ("open", _BACKEND, "jit(never_offered)", 11.0),
        ("close", _BACKEND, "jit(never_offered)", 11.0, 11.5),
    ], {
        ("compile.requests", "jit_served", "hit"): 1,
        ("compile.seconds", "jit_served", "cache_load"): 0.125,
        ("compile.requests", "jit_never_offered", "uncached"): 1,
        ("compile.seconds", "jit_never_offered", "xla_compile"): 0.5,
    }, 0.625),
    # One name, three shapes: one row, three requests.  The module's name
    # and the function's come to one form, a lambda's as JAX sanitises it.
    "one_label_a_name": ([
        step for start in (30.0, 31.0, 32.0) for step in (
            ("open", _TRACE, "entity_solve_newton", start),
            ("close", _TRACE, "entity_solve_newton", start, start + 0.25),
            ("open", _LOWER, "jit(entity_solve_newton)", start + 0.25),
            ("close", _LOWER, "jit(entity_solve_newton)", start + 0.25,
             start + 0.5),
            ("open", _BACKEND, "jit(entity_solve_newton)", start + 0.5),
            ("event", _HIT),
            ("close", _BACKEND, "jit(entity_solve_newton)", start + 0.5,
             start + 0.75),
        )
    ] + [
        ("open", _TRACE, "<lambda>", 40.0),
        ("close", _TRACE, "<lambda>", 40.0, 40.5),
        ("open", _LOWER, "jit(<lambda>)", 40.5),
        ("close", _LOWER, "jit(<lambda>)", 40.5, 41.0),
    ], {
        ("compile.requests", "jit_entity_solve_newton", "hit"): 3,
        ("compile.seconds", "jit_entity_solve_newton", "trace"): 0.75,
        ("compile.seconds", "jit_entity_solve_newton", "lower"): 0.75,
        ("compile.seconds", "jit_entity_solve_newton", "cache_load"): 0.75,
        ("compile.seconds", "jit__lambda", "trace"): 0.5,
        ("compile.seconds", "jit__lambda", "lower"): 0.5,
    }, 3.25),
    # A probe inside a trace lowers and compiles: the phases nest across
    # kinds too, and still no second is counted twice.
    "compile_inside_a_trace": ([
        ("open", _TRACE, "glm_fit_lbfgs", 60.0),
        ("open", _TRACE, "candidate", 60.5),
        ("close", _TRACE, "candidate", 60.5, 60.75),
        ("open", _LOWER, "jit(candidate)", 60.75),
        ("close", _LOWER, "jit(candidate)", 60.75, 61.0),
        ("open", _BACKEND, "jit(candidate)", 61.0),
        ("event", _MISS),
        ("close", _BACKEND, "jit(candidate)", 61.0, 63.0),
        ("close", _TRACE, "glm_fit_lbfgs", 60.0, 64.0),
    ], {
        ("compile.seconds", "jit_glm_fit_lbfgs", "trace"): 1.5,
        ("compile.seconds", "jit_candidate", "trace"): 0.25,
        ("compile.seconds", "jit_candidate", "lower"): 0.25,
        ("compile.requests", "jit_candidate", "miss"): 1,
        ("compile.seconds", "jit_candidate", "xla_compile"): 2.0,
    }, 4.0),
}


@pytest.mark.parametrize("case", sorted(_RECORDED))
def test_compile_listeners_on_a_recorded_sequence(case):
    sequence, wanted, covered = _RECORDED[case]
    compilation_cache.enable()
    telemetry.process_registry().clear()
    _play(sequence)
    rows = _compile_rows()
    assert rows == pytest.approx(wanted)
    # The per-program rows sum to the totals: every second the outermost
    # spans cover is under exactly one (program, phase).
    assert sum(
        v for (name, _, _), v in rows.items() if name == "compile.seconds"
    ) == pytest.approx(covered)
    assert compilation_cache.request_counts() == {
        outcome: sum(v for (name, _, o), v in wanted.items()
                     if name == "compile.requests" and o == outcome)
        for outcome in ("hit", "miss", "uncached")
    }


def test_enable_twice_installs_one_set_of_listeners():
    first = compilation_cache.enable()
    assert compilation_cache.enable() == first
    telemetry.process_registry().clear()
    _play(_RECORDED["miss"][0])
    assert _compile_rows() == pytest.approx(_RECORDED["miss"][1])


def test_named_jit_leaves_its_compile_rows_once():
    """Live on the host: the first call of a named program traces, lowers
    and makes one backend request under ``jit_<name>``; the second call of
    the same shape adds nothing."""
    compilation_cache.enable()
    telemetry.process_registry().clear()
    probe = named_jit("probe_me", lambda x: jnp.cos(x) * 3.0 + x)
    x = jnp.arange(7, dtype=jnp.float32)
    probe(x).block_until_ready()
    mine = {k: v for k, v in _compile_rows().items()
            if k[1] == "jit_probe_me"}
    assert {k[2] for k in mine if k[0] == "compile.seconds"} in (
        {"trace", "lower", "cache_load"}, {"trace", "lower", "xla_compile"})
    assert all(v > 0 for v in mine.values())
    (request,) = [k for k in mine if k[0] == "compile.requests"]
    assert mine[request] == 1 and request[2] in ("hit", "miss")
    after_first = _compile_rows()
    probe(x).block_until_ready()
    assert _compile_rows() == after_first
    # Another shape of the same name: the same row, a second request.
    probe(jnp.ones((3, 2))).block_until_ready()
    assert sum(v for k, v in _compile_rows().items()
               if k[:2] == ("compile.requests", "jit_probe_me")) == 2


# -- one process registry ------------------------------------------------------


def test_kernel_metrics_rows_keep_their_shape():
    telemetry.process_registry().clear()
    assert device.kernel_metrics() == []
    device.record_kernel_selected("autodiff")
    device.record_kernel_selected("autodiff")
    device.record_kernel_selected("fm")
    first = device.record_kernel_refusal(
        "pallas", RuntimeError("Mosaic failed to compile\nsecond line")
    )
    assert first == "Mosaic failed to compile"
    # Byte for byte what utils/device.py's own dicts used to give.
    assert device.kernel_metrics() == [
        {"name": "kernels.refused", "labels": {"kernel": "pallas"},
         "value": 1.0},
        {"name": "kernels.selected", "labels": {"kernel": "autodiff"},
         "value": 2.0},
        {"name": "kernels.selected", "labels": {"kernel": "fm"},
         "value": 1.0},
    ]
    # ... and every other counter of the process registry rides along, into
    # every run report of the process.
    with telemetry.span("kernels.probe", candidates=2, size=1024):
        pass
    names = [row["name"] for row in device.kernel_metrics()]
    assert names == ["kernels.refused", "kernels.selected",
                     "kernels.selected", "span.count", "span.seconds"]
    report = TelemetrySession("t").build_report()
    assert {"name": "span.count", "labels": {"span": "kernels.probe"},
            "value": 1.0} in report["metrics"]["counters"]
    # The compile accounting's rows, label for label (README "Telemetry";
    # the benchmark's setup.* readers and the report's Compile table read
    # these names).
    compilation_cache.enable()
    _play([("open", _BACKEND, "jit(score_fixed)", 50.0),
           ("event", "/jax/compilation_cache/cache_hits"),
           ("close", _BACKEND, "jit(score_fixed)", 50.0, 50.25)])
    assert [row for row in device.kernel_metrics()
            if row["name"].startswith("compile.")] == [
        {"name": "compile.requests",
         "labels": {"outcome": "hit", "program": "jit_score_fixed"},
         "value": 1.0},
        {"name": "compile.seconds",
         "labels": {"phase": "cache_load", "program": "jit_score_fixed"},
         "value": 0.25},
    ]
    assert compilation_cache.PHASES == (
        "trace", "lower", "cache_load", "xla_compile")
    assert compilation_cache.OUTCOMES == ("hit", "miss", "uncached")
    assert not any(
        isinstance(value, (dict, list, set))
        for name, value in vars(device).items() if not name.startswith("__")
    ), "utils/device.py holds no metric state of its own"


class _Pending:
    """A device scalar whose program has not finished: counts fetches."""

    def __init__(self, value):
        self.value, self.ready, self.fetches = value, False, 0

    def is_ready(self):
        return self.ready

    def __array__(self, dtype=None, copy=None):
        self.fetches += 1
        return np.asarray(self.value, dtype)


def test_deferred_increment_is_not_fetched_at_inc_time(monkeypatch):
    registry = MetricsRegistry()
    counter = registry.counter("optimizer.evaluations")
    gets = []
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda x: gets.append(x) or real_get(x)
    )
    slow, slower = _Pending(11), _Pending(13)
    counter.inc_deferred(slow)
    counter.inc_deferred(slower)
    assert gets == [] and slow.fetches == slower.fetches == 0
    assert counter.value == 0.0  # not in the host total yet
    # Ready or not, nothing is fetched while the backlog is short ...
    slow.ready = True
    third = _Pending(1)
    counter.inc_deferred(third)
    assert gets == [] and counter.value == 0.0
    # ... and once it is long, only what is ready is folded: no wait.
    from photon_tpu.telemetry import registry as registry_module

    monkeypatch.setattr(registry_module, "_SWEEP_AT", 4)
    counter.inc_deferred(_Pending(0))
    assert counter.value == 11.0 and len(gets) == 1 and len(gets[0]) == 1
    assert slower.fetches == third.fetches == 0
    # snapshot(): one batched fetch of everything pending, exact.
    (row,) = registry.snapshot()["counters"]
    assert row["value"] == 25.0 and len(gets) == 2 and len(gets[1]) == 3
    assert registry.snapshot()["counters"][0]["value"] == 25.0
    assert len(gets) == 2  # nothing pending, nothing fetched


def test_deferred_increment_of_a_device_array_is_exact():
    registry = MetricsRegistry()
    total = 0
    for i in range(5):
        registry.counter("optimizer.evaluations").inc_deferred(
            jnp.asarray(7 + i, jnp.int32)
        )
        total += 7 + i
    assert "optimizer_evaluations 45" in registry.to_prometheus()
    assert _counters(registry)[("optimizer.evaluations", ())] == total


# -- the work counts -----------------------------------------------------------


def _tiny_logistic():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((64, 4)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, 64), jnp.float32)

    def value(w):
        z = x @ w
        return jnp.sum(jnp.logaddexp(0.0, z) - y * z) + 0.5 * jnp.dot(w, w)

    return value


@pytest.mark.parametrize("optimizer", ["lbfgs", "newton"])
def test_evaluations_equal_a_hand_count(optimizer):
    from photon_tpu.core.optimizers import OptimizerConfig, lbfgs, newton

    value = _tiny_logistic()
    calls = [0]

    def fun(w):
        jax.debug.callback(lambda: calls.__setitem__(0, calls[0] + 1))
        return jax.value_and_grad(value)(w)

    config = OptimizerConfig(max_iterations=25)
    if optimizer == "lbfgs":
        result = lbfgs(fun, jnp.zeros(4), config)
    else:
        result = newton(fun, jnp.zeros(4), config, hess=jax.hessian(value))
    jax.block_until_ready(result.w)
    jax.effects_barrier()
    assert int(result.evaluations) == calls[0]
    # The initial point, the line-search trials, the two polish steps; one
    # trial an iteration at least.
    steps = int(result.line_search_steps)
    assert int(result.evaluations) == 1 + steps + 2
    assert steps >= int(result.iterations) > 0


def test_problem_run_hands_its_counts_to_the_process_registry():
    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.core.optimizers import (
        OptimizationStatesTracker,
        OptimizerConfig,
    )
    from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig
    from photon_tpu.data.batch import dense_batch

    rng = np.random.default_rng(0)
    batch = dense_batch(
        rng.standard_normal((96, 5)).astype(np.float32),
        rng.integers(0, 2, 96).astype(np.float32),
    )
    reg = RegularizationContext("l2", 1.0)
    problem = GlmOptimizationProblem(
        GlmObjective.create("logistic_regression", reg),
        ProblemConfig(regularization=reg,
                      optimizer_config=OptimizerConfig(max_iterations=12)),
    )
    telemetry.process_registry().clear()
    want = {"evaluations": 0, "line_search_steps": 0}
    for _ in range(2):
        _, result = problem.run(batch, dim=5)
        tracker = OptimizationStatesTracker(result)
        want["evaluations"] += tracker.evaluations
        want["line_search_steps"] += tracker.line_search_steps
    counters = _counters(telemetry.process_registry())
    assert counters[("optimizer.evaluations", ())] == want["evaluations"] > 0
    assert counters[("optimizer.line_search_steps", ())] == (
        want["line_search_steps"]
    )
    # The tracker records the same counts under a session's labels.
    session = TelemetrySession("t")
    tracker.record_to(session.registry, coordinate="fixed")
    assert _counters(session.registry)[
        ("optimizer.evaluations", (("coordinate", "fixed"),))
    ] == tracker.evaluations


def _game_fixture(iters: int):
    from photon_tpu.core.objective import RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import ProblemConfig
    from photon_tpu.data.synthetic import make_game_dataset
    from photon_tpu.game.coordinate import (
        FixedEffectCoordinateConfig,
        RandomEffectCoordinateConfig,
    )
    from photon_tpu.game.data import split_game_dataset
    from photon_tpu.game.estimator import GameOptimizationConfiguration

    def problem(lam, its):
        return ProblemConfig(
            regularization=RegularizationContext("l2", lam),
            optimizer_config=OptimizerConfig(max_iterations=its),
        )

    data, _ = make_game_dataset(40, 5, 6, 3, seed=7)
    train, val = split_game_dataset(data, 0.25)
    config = GameOptimizationConfiguration(
        coordinates={
            "fixed": FixedEffectCoordinateConfig("global", problem(0.01, 8)),
            "re0": RandomEffectCoordinateConfig("re0", "re0", problem(1.0, 6)),
        },
        descent_iterations=iters, name="names",
    )
    return train, val, config


def test_newton_iterations_count_every_descent_iteration():
    """``solves.newton_iterations`` sums every bin's lockstep count over
    BOTH descent iterations (the ``re_solver.iterations_max`` gauge keeps
    only the last one's), rides the one boundary drain, and the spans of
    the fit nest under their closed names."""
    import ast

    from photon_tpu.game.estimator import GameEstimator

    train, val, config = _game_fixture(iters=2)
    session = TelemetrySession("t")
    (result,) = GameEstimator(
        "logistic_regression", train, val, telemetry=session
    ).fit([config])
    history = [
        ast.literal_eval(h["coordinates"]["re0"])
        for h in result.descent.history
    ]
    assert len(history) == 2
    counters = _counters(session.registry)
    n_bins = len(history[0]["bin_iterations"])
    for h in history:
        assert set(h["bin_routes"]) == {"newton"}
        assert max(h["bin_iterations"]) == h["iterations_max"] > 0
    for b in range(n_bins):
        labels = (("bin", str(b)), ("coordinate", "re0"))
        its = sum(h["bin_iterations"][b] for h in history)
        assert counters[("solves.newton_iterations", labels)] == its
        assert counters[("solves.cells", labels)] == sum(
            h["bin_iterations"][b] * h["bin_cells"][b] for h in history
        )
    if n_bins == 1:
        assert counters[
            ("solves.newton_iterations", (("bin", "0"), ("coordinate", "re0")))
        ] == sum(h["iterations_max"] for h in history)
    # Still ONE host sync an iteration: the per-bin counts rode the drain.
    assert counters[("descent.host_syncs", (("kind", "stats"),))] == 2
    # The fixed effect's evaluations are counted per coordinate.
    assert counters[
        ("optimizer.evaluations", (("coordinate", "fixed"),))
    ] >= counters[("optimizer.iterations", (("coordinate", "fixed"),))] + 2
    # One layout, one count, whatever the fits: a dense fixed effect has no
    # sparse kernel and scores no sparse entries.
    assert counters[("fixed_effect.layout", (
        ("coordinate", "fixed"), ("kernel", "none"), ("kind", "dense"),
    ))] == 1
    assert not [k for k in counters if k[0] == "score.sparse_entries"]
    # Closed span names, nested: estimator.fit > descent.iteration >
    # descent.coordinate; the iteration and the coordinate are attributes.
    spans = {sp.span_id: sp for sp in session.tracer.finished}
    names = {sp.name for sp in spans.values()}
    assert {"estimator.fit", "descent.iteration", "descent.coordinate",
            "residuals.update", "descent.validate"} <= names
    assert not any(re.search(r"\d", name) for name in names), names
    coordinate_spans = [
        sp for sp in spans.values() if sp.name == "descent.coordinate"
    ]
    assert sorted(
        (sp.attributes["iteration"], sp.attributes["coordinate"])
        for sp in coordinate_spans
    ) == [(0, "fixed"), (0, "re0"), (1, "fixed"), (1, "re0")]
    for sp in coordinate_spans:
        parent = spans[sp.parent_id]
        assert parent.name == "descent.iteration"
        assert spans[parent.parent_id].name == "estimator.fit"
    assert counters[
        ("span.count", (("span", "descent.coordinate"),))
    ] == 4
    # Entity binning ran with no session in reach: the process registry.
    assert _counters(telemetry.process_registry())[
        ("span.count", (("span", "layout.entity_bins"),))
    ] >= 2


# -- stable device names -------------------------------------------------------


def _hlo(lowered) -> tuple:
    text = lowered.compile().as_text()
    return text.split(",", 1)[0], set(re.findall(r'op_name="([^"]+)"', text))


def _scopes(op_names: set, program: str) -> set:
    """The named-scope paths found in a program's ``op_name`` metadata."""
    found = set()
    for name in op_names:
        assert "jit(_unknown)" not in name and "<lambda>" not in name, name
        if f"jit({program})" in name:
            found |= set(re.findall(
                r"((?:valuegrad|lbfgs|newton|fm|pallas|residuals|validation"
                r"|score_fixed|tron|blocked)/[a-z_]+)", name,
            ))
    return found


def test_glm_fit_lbfgs_hlo_carries_program_and_scope_names(monkeypatch):
    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig
    from photon_tpu.data.batch import (
        attach_feature_major,
        sparse_batch_from_rows,
    )

    rng = np.random.default_rng(0)
    rows = [
        (rng.choice(32, 4, replace=False), rng.standard_normal(4))
        for _ in range(24)
    ]
    batch = attach_feature_major(sparse_batch_from_rows(
        rows, rng.integers(0, 2, 24).astype(np.float32)
    ))
    reg = RegularizationContext("l2", 1.0)
    problem = GlmOptimizationProblem(
        GlmObjective.create("logistic_regression", reg),
        ProblemConfig(regularization=reg,
                      optimizer_config=OptimizerConfig(max_iterations=3)),
    )
    w0 = jnp.zeros(32, jnp.float32)
    wanted = {
        "autodiff": {"valuegrad/margins", "valuegrad/loss"},
        "fm": {"valuegrad/margins", "valuegrad/loss", "valuegrad/grad",
               "fm/gather", "fm/segment_sum"},
    }
    ops = {}
    for kernel, scopes in wanted.items():
        monkeypatch.setenv("PHOTON_SPARSE_GRAD", kernel)
        jax.clear_caches()
        module, ops[kernel] = _hlo(
            problem.solver().lower(problem.objective, batch, w0)
        )
        assert module == "HloModule jit_glm_fit_lbfgs"
        assert _scopes(ops[kernel], "glm_fit_lbfgs") >= scopes | {
            "lbfgs/direction", "lbfgs/line_search",
        }, kernel
    # Autodiff's gradient is the transpose of the forward: its scatter-add
    # reads transpose(jvp(valuegrad/margins)), the forward's gather
    # jvp(valuegrad/margins).
    assert any(
        "transpose(jvp(valuegrad/margins))" in name and "scatter" in name
        for name in ops["autodiff"]
    )
    assert any(
        "/jvp(valuegrad/margins)" in name and "gather" in name
        for name in ops["autodiff"]
    )
    assert problem.solver(vmapped=True).__name__ == "entity_fit_lbfgs"


def test_glm_fit_tron_hlo_carries_its_phase_scopes(monkeypatch):
    """ISSUE 40: TRON's three phases carry their scopes, and the
    Hessian-vector product's two ``blocked`` directions run inside the CG
    loop (``tron/cg``).  ISSUE 41: TRON carries the margins, so neither the
    curvature nor the trial runs a ``blocked/xw`` of its own; the trial's
    gradient pass stays."""
    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig
    from photon_tpu.data.batch import (
        attach_feature_major,
        sparse_batch_from_rows,
    )

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")
    jax.clear_caches()
    rng = np.random.default_rng(0)
    rows = [
        (np.sort(rng.choice(64, 4, replace=False)), rng.standard_normal(4))
        for _ in range(48)
    ]
    batch = attach_feature_major(sparse_batch_from_rows(
        rows, rng.poisson(1.0, 48).astype(np.float32)
    ), aligned_dim=64)
    assert batch.bt is not None
    reg = RegularizationContext("l2", 1.0)
    problem = GlmOptimizationProblem(
        GlmObjective.create("poisson_regression", reg),
        ProblemConfig(optimizer="tron", regularization=reg,
                      optimizer_config=OptimizerConfig(
                          max_iterations=2, cg_max_iterations=3)),
    )
    module, ops = _hlo(problem.solver().lower(
        problem.objective, batch, jnp.zeros(64, jnp.float32)))
    assert module == "HloModule jit_glm_fit_tron"
    assert _scopes(ops, "glm_fit_tron") >= {
        "tron/curvature", "tron/cg", "tron/trial", "blocked/xw",
        "blocked/xtdz", "valuegrad/margins", "valuegrad/grad",
    }
    for direction in ("blocked/xw", "blocked/xtdz"):
        assert any(f"tron/cg/while/body/{direction}" in name
                   for name in ops), direction
    assert not any(
        f"{phase}/" in name and "blocked/xw" in name
        for name in ops for phase in ("tron/curvature", "tron/trial"))
    assert any("tron/trial/valuegrad/grad/blocked/xtdz" in name
               for name in ops)


def _poisson_problem(rows: int, dim: int, seed: int):
    """A sparse Poisson problem of ``rows`` x 8 entries over ``dim``."""
    from photon_tpu.data.batch import SparseBatch

    rng = np.random.default_rng(seed)
    ids = np.sort(rng.integers(0, dim, (rows, 8)), axis=1).astype(np.int32)
    vals = rng.standard_normal((rows, 8)).astype(np.float32)
    label = rng.poisson(np.exp(vals.sum(1) / 8)).astype(np.float32)
    return SparseBatch(
        ids=jnp.asarray(ids), vals=jnp.asarray(vals),
        label=jnp.asarray(label), offset=jnp.zeros(rows, jnp.float32),
        weight=jnp.ones(rows, jnp.float32),
    )


@pytest.mark.parametrize("dim", [16, 64])
def test_tron_counts_equal_a_hand_count(dim):
    """``cg_iterations`` is the number of Hessian-vector products that ran
    (a finished, masked iteration adds none), ``evaluations`` the objective
    calls, and ``trust_region_rejections`` the iterations that did not move
    ``w`` (both problems make TRON reject trials: few rows a coefficient and
    the exp link)."""
    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig, tron

    batch = _poisson_problem(256, dim, seed=dim)
    objective = GlmObjective.create(
        "poisson_regression", RegularizationContext("l2", 1.0))
    calls = {"hv": 0, "fun": 0}

    def counted(key):
        jax.debug.callback(lambda: calls.__setitem__(key, calls[key] + 1))

    def fun(w):
        counted("fun")
        return objective.value_and_grad(w, batch)

    def hvp_at(w):
        op = objective.hvp_operator(w, batch)

        def hv(v):
            counted("hv")
            return op(v)

        return hv

    result = tron(fun, jnp.zeros(dim, jnp.float32),
                  OptimizerConfig(max_iterations=12, cg_max_iterations=6),
                  hvp_at=hvp_at)
    jax.block_until_ready(result.w)
    jax.effects_barrier()
    assert int(result.cg_iterations) == calls["hv"] > 0
    assert int(result.evaluations) == calls["fun"]
    accepted = int(np.asarray(result.history_valid).sum()) - 1
    assert int(result.trust_region_rejections) == (
        int(result.iterations) - accepted)
    assert int(result.trust_region_rejections) > 0


def test_problem_run_hands_tron_counts_to_the_process_registry():
    """``optimizer.cg_iterations`` / ``optimizer.trust_region_rejections``
    ride the same deferred path as ``optimizer.evaluations`` (ISSUE 40)."""
    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig

    batch = _poisson_problem(256, 64, seed=64)
    reg = RegularizationContext("l2", 1.0)
    problem = GlmOptimizationProblem(
        GlmObjective.create("poisson_regression", reg),
        ProblemConfig(optimizer="tron", regularization=reg,
                      optimizer_config=OptimizerConfig(
                          max_iterations=12, cg_max_iterations=6)),
    )
    telemetry.process_registry().clear()
    want = {"evaluations": 0, "cg_iterations": 0,
            "trust_region_rejections": 0}
    for _ in range(2):
        _, result = problem.run(batch, dim=64)
        for name in want:
            want[name] += int(getattr(result, name))
    counters = _counters(telemetry.process_registry())
    for name, total in want.items():
        assert counters[(f"optimizer.{name}", ())] == total, name
    assert want["cg_iterations"] > 0 and want["trust_region_rejections"] > 0
    assert ("optimizer.line_search_steps", ()) not in counters


def test_entity_solve_newton_hlo_carries_program_and_scope_names():
    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import ProblemConfig
    from photon_tpu.data.batch import DenseBatch
    from photon_tpu.game.batched_solve import (
        cached_newton_cg_solver,
        cached_newton_solver,
    )

    reg = RegularizationContext("l2", 1.0)
    config = ProblemConfig(
        regularization=reg, optimizer_config=OptimizerConfig(max_iterations=4)
    )
    objective = GlmObjective.create("logistic_regression", reg)
    rng = np.random.default_rng(1)
    batch = DenseBatch(
        jnp.asarray(rng.standard_normal((3, 8, 4)), jnp.float32),
        jnp.asarray(rng.integers(0, 2, (3, 8)), jnp.float32),
        jnp.zeros((3, 8), jnp.float32), jnp.ones((3, 8), jnp.float32),
    )
    w0 = jnp.zeros((3, 4), jnp.float32)
    module, ops = _hlo(
        cached_newton_solver(config).lower(objective, batch, w0)
    )
    assert module == "HloModule jit_entity_solve_newton"
    assert _scopes(ops, "entity_solve_newton") >= {
        "newton/hessian", "newton/cholesky", "newton/step",
        "newton/gradient", "valuegrad/margins", "valuegrad/loss",
    }
    assert cached_newton_cg_solver(config).__name__ == (
        "entity_solve_newton_cg"
    )


def _lane_solver_lowered(entities=130, rows=8, d=16):
    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import ProblemConfig
    from photon_tpu.data.batch import DenseBatch
    from photon_tpu.game.batched_solve import cached_newton_solver

    reg = RegularizationContext("l2", 1.0)
    config = ProblemConfig(
        regularization=reg, optimizer_config=OptimizerConfig(max_iterations=4)
    )
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    batch = DenseBatch(
        shape(entities, rows, d), shape(entities, rows),
        shape(entities, rows), shape(entities, rows),
    )
    return cached_newton_solver(config).lower(
        GlmObjective.create("logistic_regression", reg), batch,
        shape(entities, d),
    )


def test_entity_solve_newton_lane_form_scopes_and_loop_bodies():
    """A dense d = 16 bin of 130 entities (ISSUE 39): the program carries
    the solve's scopes and ``newton/to_lanes``; the turn to entity-minor
    happens at the entry, so inside the loops no ``dot_general`` is left
    and the only transposes of the features are the pair ``vmap`` and the
    lane rule make of each other (identity, folded by XLA)."""
    lowered = _lane_solver_lowered()
    module, ops = _hlo(lowered)
    assert module == "HloModule jit_entity_solve_newton"
    assert _scopes(ops, "entity_solve_newton") >= {
        "newton/hessian", "newton/cholesky", "newton/step",
        "newton/direction", "newton/line_search", "newton/gradient",
        "newton/to_lanes", "valuegrad/margins", "valuegrad/loss",
        "valuegrad/grad",
    }
    text = lowered.as_text(dialect="hlo")
    computations = re.split(r"\n(?=(?:ENTRY )?%?[\w.\-]+ (?:\([^)]*\) -> |\{))", text)
    entry = [c for c in computations if c.startswith("ENTRY")]
    assert len(entry) == 1
    features = 8 * 16 * 256  # rows x dim x 130 entities padded to 256
    # The Newton loop, the line search's inside it, the polish steps'.
    assert text.count(" while(") == 3
    for computation in computations:
        if computation.startswith("ENTRY"):
            continue
        assert " dot(" not in computation, computation[:200]
        # name -> (operand, permutation) of each features-sized transpose
        turns = {
            m.group(1): (m.group(3), tuple(int(i) for i in m.group(4).split(",")))
            for m in re.finditer(
                r"(?m)^\s*(?:ROOT )?(\S+) = f32\[([\d,]+)\][^ ]* "
                r"transpose\((?:[^ ]+ )?(\S+?)\), dimensions=\{([\d,]+)\}",
                computation,
            )
            if np.prod([int(n) for n in m.group(2).split(",")]) == features
        }
        paired = set()
        for name, (operand, second) in turns.items():
            if operand in turns:
                first = turns[operand][1]
                assert tuple(first[i] for i in second) == (0, 1, 2)
                paired |= {name, operand}
        assert paired == set(turns), sorted(set(turns) - paired)
    # The entry holds the one real turn (scope ``newton/to_lanes``, above).
    assert re.search(r"= f32\[8,16,256\]\S* transpose\(", entry[0])


def _text_hash(text: str) -> str:
    import hashlib

    # Source locations and line numbers are not the program.
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"(?m)^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _sparse_blocked_fit_lowered(monkeypatch):
    """``glm_fit_lbfgs`` over a sparse batch that carries ``bt``, under
    ``blocked``: what the fixed effect of ``glm_sparse_fit`` and
    ``game_sparse_fit`` runs."""
    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig
    from photon_tpu.data.batch import (
        attach_feature_major,
        sparse_batch_from_rows,
    )

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")
    jax.clear_caches()
    rng = np.random.default_rng(0)
    rows = [
        (np.sort(rng.choice(64, 4, replace=False)), rng.standard_normal(4))
        for _ in range(48)
    ]
    batch = attach_feature_major(sparse_batch_from_rows(
        rows, rng.integers(0, 2, 48).astype(np.float32)
    ), aligned_dim=64)
    assert batch.bt is not None
    reg = RegularizationContext("l2", 1.0)
    objective = GlmObjective.create("logistic_regression", reg)
    problem = GlmOptimizationProblem(objective, ProblemConfig(
        regularization=reg, optimizer_config=OptimizerConfig(max_iterations=3),
    ))
    return problem.solver().lower(objective, batch, jnp.zeros(64, jnp.float32))


def _dense_fit_lowered(monkeypatch):
    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig
    from photon_tpu.data.batch import DenseBatch

    reg = RegularizationContext("l2", 1.0)
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    batch = DenseBatch(shape(64, 128), shape(64), shape(64), shape(64))
    objective = GlmObjective.create("logistic_regression", reg)
    problem = GlmOptimizationProblem(objective, ProblemConfig(
        regularization=reg, optimizer_config=OptimizerConfig(max_iterations=3),
    ))
    return problem.solver().lower(objective, batch, shape(128))


def _dense_hessian_lowered(monkeypatch):
    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.data.batch import DenseBatch

    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    objective = GlmObjective.create(
        "logistic_regression", RegularizationContext("l2", 1.0))
    small = DenseBatch(shape(8, 16), shape(8), shape(8), shape(8))
    return jax.jit(objective.hessian_matrix).lower(shape(16), small)


@pytest.mark.parametrize("lowered, digest", [
    # ISSUE 39 (parent 54fc018): the fixed effect's fit over a dense
    # d = 128 batch, and an unbatched ``hessian_matrix``.
    ((_dense_fit_lowered, _dense_hessian_lowered),
     ("68157ddbbfb91127", "11766d26094c5fb9")),
    # ISSUE 41 (parent c8be8ef): L-BFGS over ``blocked`` tiles, through
    # the value and gradient that TRON's margin form shares.
    ((_sparse_blocked_fit_lowered,), ("5e7a46fc0f078865",)),
], ids=["dense", "sparse_blocked"])
def test_rows_form_programs_are_the_parents(monkeypatch, lowered, digest):
    """What ISSUE 39 and ISSUE 41 leave alone keeps its program, opcode for
    opcode (each hash is its parent commit's, over the HLO text without its
    source metadata)."""
    for lower, want in zip(lowered, digest):
        assert _text_hash(lower(monkeypatch).as_text(dialect="hlo")) == want


def test_published_program_names():
    from photon_tpu.evaluation import metrics
    from photon_tpu.game import coordinate, model, residuals

    compilation_cache.enable()

    programs = {
        metrics.area_under_roc_curve: "metric_auc",
        metrics.logistic_loss_metric: "metric_logloss",
        metrics.rmse: "metric_rmse",
        metrics._sharded_auc_kernel: "metric_sharded_auc",
        coordinate._gather_bucket_offsets: "gather_bucket_offsets",
        residuals._set_row_and_resum: "score_table_update",
        residuals._offsets_kernel: "residuals_offsets",
        residuals._composite_kernel: "validation_composite",
        model._fixed_margins: "score_fixed",
        model._random_margins: "score_random",
    }
    for program, name in programs.items():
        assert program.__name__ == name
    scores = jnp.linspace(-1.0, 1.0, 16)
    labels = (scores > 0).astype(jnp.float32)
    module, ops = _hlo(
        metrics.area_under_roc_curve.lower(scores, labels, jnp.ones(16))
    )
    assert module == "HloModule jit_metric_auc"
    assert "validation/auc" in _scopes(ops, "metric_auc")
    module, ops = _hlo(residuals._set_row_and_resum.lower(
        jnp.ones((2, 8)), jnp.ones(8), jnp.zeros(8), 0, jnp.ones(8)
    ))
    assert module == "HloModule jit_score_table_update"
    assert "residuals/update" in _scopes(ops, "score_table_update")
    # The compile accounting files both under the module's own name.
    rows = _compile_rows()
    for program in ("jit_metric_auc", "jit_score_table_update"):
        assert rows["compile.seconds", program, "lower"] > 0
        assert sum(v for k, v in rows.items()
                   if k[:2] == ("compile.requests", program)) >= 1


def test_score_fixed_sparse_branch_carries_its_scopes_and_counts():
    """A sparse fixed effect's score (PR 36): the gather of ``w`` at the ids
    and the row sums carry scope names (the dense branch, one matrix
    product, has none and keeps its program), and every dispatch adds the
    entries it reads to ``score.sparse_entries{coordinate}`` from the ids'
    shape.  From the shard's block tiles (PR 37) the same program name
    carries one scope, ``score_fixed/blocked_xw``, and no gather; the
    entries counted are the same shard's whichever form scores it, and
    ``score.fixed_dispatches{kernel}`` says which did."""
    from photon_tpu.game import model
    from photon_tpu.ops.block_tiles import build_block_tiles

    w = jnp.ones(32, jnp.float32)
    ids = jnp.zeros((8, 4), jnp.int32)
    vals = jnp.ones((8, 4), jnp.float32)
    module, ops = _hlo(model._fixed_margins.lower(w, (ids, vals), dense=False))
    assert module == "HloModule jit_score_fixed"
    assert _scopes(ops, "score_fixed") == {
        "score_fixed/gather", "score_fixed/reduce"}
    assert any("score_fixed/gather" in n and "gather" in n.rsplit("/", 1)[-1]
               for n in ops)
    module, ops = _hlo(model._fixed_margins.lower(
        w, jnp.ones((8, 32), jnp.float32), dense=True))
    assert module == "HloModule jit_score_fixed"
    assert _scopes(ops, "score_fixed") == set()
    tiles = jax.tree.map(
        jnp.asarray, build_block_tiles(np.asarray(ids), np.asarray(vals), 32)
    )
    module, ops = _hlo(
        model._fixed_margins.lower(w, tiles, dense=False, out_len=8))
    assert module == "HloModule jit_score_fixed"
    assert _scopes(ops, "score_fixed") == {"score_fixed/blocked_xw"}
    # No gather of ``w`` from HBM: the one gather left is the kernel's own
    # lane gather inside a VMEM window, which interpret mode (the host)
    # spells as an XLA op and Mosaic (the chip) as part of the custom call.
    assert all("jit(_cell_products)" in n for n in ops
               if n.rsplit("/", 1)[-1] == "gather")
    session = TelemetrySession("t")
    model.count_sparse_entries(session, "fixed", (ids, vals), dense=False)
    model.count_sparse_entries(session, "fixed", (ids, vals), dense=False)
    model.count_sparse_entries(session, "fixed", vals, dense=True)
    model.count_sparse_entries(
        session, "fixed", tiles, dense=False, entries=tiles.n_rows * 4
    )
    assert _counters(session.registry) == {
        ("score.sparse_entries", (("coordinate", "fixed"),)): 96.0,
        ("score.fixed_dispatches",
         (("coordinate", "fixed"), ("kernel", "gather"))): 2.0,
        ("score.fixed_dispatches",
         (("coordinate", "fixed"), ("kernel", "blocked"))): 1.0,
    }


def test_metric_auc_is_one_sort_and_no_gather_or_loop():
    """The validation AUC sorts once, weights riding with their score, and
    carries tie-group sums by scans: a binary search (a ``while`` around a
    gather, 44 passes over the scores a call, 0.49 s of a 1.9 s GAME fit on
    the v5e before PR 35) cannot come back unseen."""
    from photon_tpu.evaluation import metrics

    x = jnp.linspace(-1.0, 1.0, 4096)
    lowered = metrics.area_under_roc_curve.lower(x, (x > 0) * 1.0, x * 0 + 1)
    for text in (lowered.as_text(dialect="hlo"), lowered.compile().as_text()):
        # The opcode of every instruction: "%x = <type, maybe a tuple> op(".
        ops = re.findall(r"(?m)^\s*(?:ROOT )?\S+ = .*? ([a-z][\w\-]*)\(", text)
        assert ops.count("sort") == 1, sorted(set(ops))
        assert not {"gather", "while", "dynamic-slice", "scatter"} & set(ops)


def test_descent_loop_gained_no_sanctioned_host_sync():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from check_host_sync import main

    assert main([]) == 0
    with open(os.path.join(REPO, "photon_tpu", "game", "descent.py")) as f:
        source = f.read()
    # The markers the descent loop had before the per-bin counts rode its
    # drain: the host validation path, the per-metric scalars, the drain.
    assert source.count("host-sync:") == 3
