"""The one persistent-compile-cache contract, and the compile accounting.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that directory
and this module sets no other.  If it is not, the cache lives at ONE fixed
path inside the checkout (``<repo>/.jax_cache``, git-ignored), resolved
from this file — never from ``$HOME``, ``$TMPDIR``, a pid, a time or a
digest: the directory is part of JAX's cache key, so a cache that moves
never hits.  Drivers, ``bench.py`` and ``chip_smoke.py`` all come through
:func:`enable`; child processes inherit the directory through the
environment.

:func:`enable` also installs, once a process, listeners on
``jax.monitoring`` that fold JAX's own compile events into the process
registry (``photon_tpu.telemetry.process_registry``), so what a program
costs before it first runs is in every run report and benchmark line:

- ``compile.seconds{program, phase}``, ``phase`` one of ``trace``
  (function to jaxpr), ``lower`` (jaxpr to an MLIR module), ``cache_load``
  (a backend request the persistent cache answered: its key, the file
  read, deserialise, load) and ``xla_compile`` (a backend request that went
  to the compiler: a cache miss with its write, or a request the cache does
  not take).  JAX's events nest on a thread (tracing ``glm_fit_lbfgs``
  traces every jitted function it calls; a probe inside a trace lowers and
  compiles): each event counts its SELF time, what lies inside it less the
  events it encloses, so no second is counted twice and the four phases
  add up.
- ``compile.requests{program, outcome}``, ``outcome`` one of ``hit``,
  ``miss``, ``uncached``: one count a backend request, decided by the
  persistent cache's own event on the same thread since the last request
  (``cache_hits`` / ``cache_misses``; neither: ``uncached``).

``program`` is the device program's name as the profiler's ``XLA Modules``
line shows it with the fingerprint cut off (``jit_<name>``, README
"Telemetry"): one label a name, however many shapes compile under it.  A
listener runs only when JAX traces, lowers or compiles; it touches no
device value and sits on no dispatch path.
"""

from __future__ import annotations

import os
import re
import threading

from photon_tpu.utils.caches import CHECKOUT_ROOT

_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")

# jax 0.9: jax/_src/dispatch.py (the three spans, each announced by a
# scalar event when it opens and a time-span event when it closes, with
# ``fun_name``) and jax/_src/compiler.py, compilation_cache.py (the cache's
# verdict, inside the backend span, without a name).
_SPAN_PHASE = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # cache_load or xla_compile: the request's outcome says which
    "/jax/core/compile/backend_compile_duration": None,
}
_CACHE_OUTCOME = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
PHASES = ("trace", "lower", "cache_load", "xla_compile")
OUTCOMES = ("hit", "miss", "uncached")

_NOT_IN_A_MODULE_NAME = re.compile(r"[^\w.-]")
_installed = False
# Per thread: ``open`` holds, for each compile span now open, the seconds of
# the spans that closed inside it; ``outcome`` the cache's last verdict.
_thread = threading.local()


def enable() -> str:
    """Make sure a persistent compilation cache is on and the compile
    accounting listens; return the cache's directory.  Call before the
    first compile; calling it again adds nothing."""
    import jax

    cache_dir = os.environ.get(_ENV_VAR)
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # Exported so subprocess replicas and bench workers share it.
        os.environ[_ENV_VAR] = cache_dir
    # Driver programs are many and small (one per size bin / bucket); the
    # JAX defaults (>= 1 s compiles only) would leave most of a warm run
    # recompiling.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _install_listeners()
    return cache_dir


def program_name(fun_name: str) -> str:
    """JAX's name of a function (``glm_fit_lbfgs``, the trace event's) or of
    its module (``jit(glm_fit_lbfgs)``, the lower and backend events') as
    the compiled module is called: ``jit_glm_fit_lbfgs``."""
    if "(" not in fun_name:
        fun_name = f"jit({fun_name})"
    return _NOT_IN_A_MODULE_NAME.sub("_", fun_name).rstrip("_")


def request_counts() -> dict:
    """``compile.requests`` of this process so far, summed over programs:
    ``{"hit": n, "miss": n, "uncached": n}``."""
    from photon_tpu.telemetry import process_registry

    counts = dict.fromkeys(OUTCOMES, 0)
    for row in process_registry().snapshot()["counters"]:
        if row["name"] == "compile.requests":
            counts[row["labels"]["outcome"]] += int(row["value"])
    return counts


def _install_listeners() -> None:
    global _installed
    if _installed:
        return
    _installed = True
    from jax import monitoring

    monitoring.register_scalar_listener(_on_span_open)
    monitoring.register_event_time_span_listener(_on_span_close)
    monitoring.register_event_listener(_on_cache_event)


def _on_span_open(event: str, value, **_) -> None:
    if event in _SPAN_PHASE:
        if not hasattr(_thread, "open"):
            _thread.open = []
        _thread.open.append(0.0)


def _on_span_close(event: str, start_time: float, end_time: float,
                   fun_name: str = "", **_) -> None:
    if event not in _SPAN_PHASE:
        return
    from photon_tpu.telemetry import process_registry

    open_spans = getattr(_thread, "open", None)
    seconds = end_time - start_time
    inside = open_spans.pop() if open_spans else 0.0
    if open_spans:
        open_spans[-1] += seconds
    registry = process_registry()
    program = program_name(fun_name)
    phase = _SPAN_PHASE[event]
    if phase is None:
        outcome = getattr(_thread, "outcome", "uncached")
        _thread.outcome = "uncached"  # a verdict answers one request
        registry.counter(
            "compile.requests", program=program, outcome=outcome
        ).inc()
        phase = "cache_load" if outcome == "hit" else "xla_compile"
    registry.counter("compile.seconds", program=program, phase=phase).inc(
        max(seconds - inside, 0.0)
    )


def _on_cache_event(event: str, **_) -> None:
    outcome = _CACHE_OUTCOME.get(event)
    if outcome is not None:
        _thread.outcome = outcome
