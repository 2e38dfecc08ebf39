"""Tracing spans: nested wall-clock timing with attributes and status.

The structured upgrade of the reference's ``Timed { }`` phase logs
(SURVEY.md §5 'Tracing / profiling'): each instrumented region becomes a
span with a parent (nesting reconstructs the phase tree: driver run →
fit-config → descent iteration → coordinate solve), wall-clock duration,
free-form attributes, and an ok/error status recorded even when the region
raises.

One span, three sinks.  ``Tracer.span(name, **attributes)`` is the single
instrumentation point and feeds:

1. the span tree of the run report (the kept :class:`Span` objects);
2. the profiler's trace: the span's life is also a
   ``jax.profiler.TraceAnnotation(name, **attributes)``, so whenever a trace
   is being taken (``--profile-dir``, the benchmark's ``--trace 1``) the span
   sits on the profiler's clock in the host plane beside the device's
   ``XLA Ops`` line; with no trace on it costs one inactive TraceMe.  JAX is
   used only if the process has ALREADY imported it (the index driver runs
   jax-free; same rule as ``report.capture_environment``);
3. the metrics registry given to the tracer: on exit the span adds its
   duration to ``span.seconds{span=<name>}`` and 1 to
   ``span.count{span=<name>}``, so totals by name survive without keeping
   every ``Span`` (a tracer built with ``keep=False`` keeps none).

Span NAMES are a closed, documented set (README "Telemetry"): a name never
embeds a counter, an id, a parameter value or a path — those are
attributes — so ``span.seconds`` has one row per instrumented place and a
trace reader can match a name across runs.

The active-span stack is thread-local, so spans opened on IO-pool worker
threads become roots of their own trees instead of corrupting the main
thread's nesting.
"""

from __future__ import annotations

import contextlib
import json
import sys
import threading
import time
from typing import Iterator, List, Optional


def _trace_annotation(name: str, attributes: dict):
    """The profiler annotation for a span, or a null context in a process
    that has not imported JAX.  Attributes ride as TraceMe metadata (the
    trace viewer's and ``ProfileData``'s per-event stats); only plain
    scalars go — a metrics dict set later via ``set_attribute`` stays in the
    span tree."""
    jax_mod = sys.modules.get("jax")
    if jax_mod is None:
        return contextlib.nullcontext()
    return jax_mod.profiler.TraceAnnotation(name, **{
        # TraceMe packs metadata as "name#k=v,k=v#": those three characters
        # inside a value (a sweep label "fixed=1,per_user=1") would split it.
        k: v.translate(_TRACEME_SAFE) if isinstance(v, str) else v
        for k, v in attributes.items()
        if isinstance(v, (str, int, float, bool))
    })


_TRACEME_SAFE = str.maketrans({"#": "_", ",": ";", "=": ":"})


class Span:
    """One timed region.  ``duration_s`` is None while the span is open."""

    __slots__ = (
        "name", "span_id", "parent_id", "start_time", "duration_s",
        "attributes", "status", "error", "thread",
    )

    def __init__(self, name: str, span_id: int, parent_id: Optional[int],
                 start_time: float, thread: str):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.start_time = start_time  # epoch seconds (for cross-run ordering)
        self.duration_s: Optional[float] = None
        self.attributes: dict = {}
        self.status = "ok"
        self.error: Optional[str] = None
        self.thread = thread

    def set_attribute(self, key: str, value) -> None:
        self.attributes[key] = value

    def to_dict(self) -> dict:
        out = {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start_time": self.start_time,
            "duration_s": self.duration_s,
            "status": self.status,
        }
        if self.attributes:
            out["attributes"] = self.attributes
        if self.error is not None:
            out["error"] = self.error
        if self.thread != "MainThread":
            out["thread"] = self.thread
        return out


class Tracer:
    """Creates spans, tracks the per-thread active stack, keeps finished
    spans for export (append order == completion order, children before
    parents) unless ``keep`` is False, and records every span's duration
    into ``registry`` (``span.seconds`` / ``span.count`` by name) when one
    is given."""

    def __init__(self, registry=None, keep: bool = True):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._next_id = 0
        self._registry = registry
        self._keep = keep
        self.finished: List[Span] = []

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def current_span(self) -> Optional[Span]:
        stack = self._stack()
        return stack[-1] if stack else None

    @contextlib.contextmanager
    def span(self, name: str, **attributes) -> Iterator[Span]:
        stack = self._stack()
        parent = stack[-1].span_id if stack else None
        with self._lock:
            span_id = self._next_id
            self._next_id += 1
        sp = Span(name, span_id, parent, time.time(),
                  threading.current_thread().name)
        sp.attributes.update(attributes)
        # Built before the push: if it raises, no span is left on the stack.
        annotation = _trace_annotation(name, attributes)
        stack.append(sp)
        t0 = time.monotonic()
        try:
            with annotation:
                yield sp
        except BaseException as e:
            sp.status = "error"
            sp.error = f"{type(e).__name__}: {e}"
            raise
        finally:
            sp.duration_s = time.monotonic() - t0
            stack.pop()
            if self._keep:
                with self._lock:
                    self.finished.append(sp)
            if self._registry is not None:
                self._registry.counter("span.seconds", span=name).inc(
                    sp.duration_s
                )
                self._registry.counter("span.count", span=name).inc()

    def export(self) -> List[dict]:
        with self._lock:
            return [sp.to_dict() for sp in self.finished]

    def write_jsonl(self, path: str) -> None:
        with open(path, "w") as f:
            for entry in self.export():
                # default=str: never crash a run over an attribute type.
                f.write(json.dumps(entry, default=str) + "\n")

    def phase_totals(self) -> dict:
        """Total seconds per span name over finished spans — the run
        report's wall-clock breakdown table (same shape as PhotonLogger's
        ``phase_times``, derived from spans instead of a parallel dict)."""
        totals: dict = {}
        with self._lock:
            spans = list(self.finished)
        for sp in spans:
            if sp.duration_s is not None:
                totals[sp.name] = totals.get(sp.name, 0.0) + sp.duration_s
        return totals
