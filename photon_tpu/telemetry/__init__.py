"""Structured telemetry: metrics registry, tracing spans, run reports.

The observability layer of the rebuild (SURVEY.md §5 'Tracing /
profiling').  One :class:`TelemetrySession` spans one driver run and owns:

- a :class:`~photon_tpu.telemetry.registry.MetricsRegistry` — labeled
  counters/gauges/histograms written by drivers, optimizers
  (:meth:`~photon_tpu.core.optimizers.base.OptimizationStatesTracker.record_to`),
  and the GAME descent loop;
- a :class:`~photon_tpu.telemetry.tracing.Tracer` — nested wall-clock spans
  (``PhotonLogger.timed`` phases feed it automatically once the session is
  attached to the logger), each also an annotation in the profiler's trace
  and a ``span.seconds`` / ``span.count`` total in the session's registry;
- finalization into ``<output-dir>/telemetry/`` run-report artifacts
  (:mod:`photon_tpu.telemetry.report`).

Beside the sessions there is ONE process-wide registry
(:func:`process_registry`) for facts recorded far from any session: layout
builds, the kernel probe, kernel selections and refusals, the evaluation
count of a fit run through ``GlmOptimizationProblem.run`` directly.  Library
code opens such spans with the module-level :func:`span`; every run report
and both benchmark runners append the process registry's counters to their
own (``utils.device.kernel_metrics``).

Telemetry is on by default and gated twice: per-run by the drivers'
``--no-telemetry`` flag, globally by ``PHOTON_TELEMETRY=off`` (or 0/false).
A disabled session is a full no-op object — spans yield a null span,
instruments swallow writes, finalize writes nothing — so library code takes
a session unconditionally (``telemetry or NULL_SESSION``) and never
branches.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Iterator, Optional

from photon_tpu.telemetry.registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from photon_tpu.telemetry.tracing import Span, Tracer  # noqa: F401
from photon_tpu.telemetry.distributed import (  # noqa: F401
    FlightRecorder,
    MergeableHistogram,
    SpanRecord,
    TraceCollector,
    TraceContext,
    TraceSampler,
    activate_trace,
    attach_trace,
    current_trace,
    new_trace_id,
    span_of,
    trace_of,
)

# photon_tpu.telemetry.report is imported lazily (build_report below): it is
# also the `python -m photon_tpu.telemetry.report` CLI, and importing it here
# would make runpy warn about the double import.

_ENV_VAR = "PHOTON_TELEMETRY"
_OFF_VALUES = ("off", "0", "false", "no")


def telemetry_enabled(flag: Optional[bool] = None) -> bool:
    """Resolve the two gates: the env var kills telemetry process-wide
    (operator override, e.g. benchmark runs); otherwise the driver flag
    (default True) decides."""
    if os.environ.get(_ENV_VAR, "").strip().lower() in _OFF_VALUES:
        return False
    return True if flag is None else bool(flag)


class _NullMetric:
    """Write-only sink standing in for every instrument when disabled."""

    def inc(self, amount: float = 1.0) -> None:
        pass

    def inc_deferred(self, amount) -> None:
        pass

    def set(self, value: float) -> None:
        pass

    def observe(self, value: float) -> None:
        pass

    @property
    def value(self):
        return None


_NULL_METRIC = _NullMetric()


class _NullRegistry:
    def counter(self, name: str, **labels) -> _NullMetric:
        return _NULL_METRIC

    gauge = counter
    histogram = counter

    def snapshot(self) -> dict:
        return {"counters": [], "gauges": [], "histograms": []}

    def to_prometheus(self) -> str:
        return ""


class _NullSpan:
    def set_attribute(self, key: str, value) -> None:
        pass


_NULL_SPAN = _NullSpan()


class TelemetrySession:
    """Run-scoped telemetry: registry + tracer + report finalization.

    ``write`` (default True) lets multi-process drivers restrict artifact
    output to the primary rank after they learn their process index —
    instruments still record everywhere (cheap, and keeps rank behavior
    identical up to the filesystem).
    """

    def __init__(self, driver: str, enabled: bool = True):
        self.driver = driver
        self.enabled = enabled
        self.write = True
        self.registry = MetricsRegistry() if enabled else _NullRegistry()
        self.tracer = Tracer(registry=self.registry) if enabled else None
        self.started_at = time.time()
        self._t0 = time.monotonic()
        self.run_id = (
            f"{driver}-{time.strftime('%Y%m%d-%H%M%S', time.localtime(self.started_at))}"
            f"-{os.getpid()}"
        )
        self._finalized: Optional[dict] = None

    # -- instruments --------------------------------------------------------
    def counter(self, name: str, **labels):
        return self.registry.counter(name, **labels)

    def gauge(self, name: str, **labels):
        return self.registry.gauge(name, **labels)

    def histogram(self, name: str, **labels):
        return self.registry.histogram(name, **labels)

    @contextlib.contextmanager
    def span(self, name: str, **attributes) -> Iterator[object]:
        if self.tracer is None:
            yield _NULL_SPAN
            return
        with self.tracer.span(name, **attributes) as sp:
            yield sp

    def attach(self, logger) -> None:
        """Route the logger's ``timed()`` phases through this session's
        tracer (phase logs and spans stay one instrumentation point)."""
        if self.enabled:
            logger.tracer = self.tracer

    # -- finalization -------------------------------------------------------
    def build_report(self, status: str = "success",
                     error: Optional[str] = None,
                     extra: Optional[dict] = None) -> dict:
        from photon_tpu.telemetry.report import capture_environment
        from photon_tpu.utils.device import kernel_metrics

        metrics = self.registry.snapshot()
        # The process registry's counters (kernel refusals/selections,
        # layout spans and bytes: recorded far from any session) ride in
        # every report of the process, so a refused kernel can never go
        # unseen.
        metrics["counters"] = metrics["counters"] + kernel_metrics()
        report = {
            "driver": self.driver,
            "run_id": self.run_id,
            "status": status,
            "error": error,
            "started_at": self.started_at,
            "duration_s": time.monotonic() - self._t0,
            "environment": capture_environment(),
            "phase_totals": self.tracer.phase_totals() if self.tracer else {},
            "metrics": metrics,
            "spans": self.tracer.export() if self.tracer else [],
        }
        if extra:
            report["extra"] = extra
        return report

    def finalize(self, output_dir: str, status: str = "success",
                 error: Optional[str] = None,
                 extra: Optional[dict] = None) -> Optional[dict]:
        """Build the run report and write ``telemetry/{run_report.json,
        spans.jsonl}`` under ``output_dir``.  Idempotent: a second call
        (e.g. the error path after a failed success-path write) returns the
        first report unchanged.  Returns None when disabled.  Never raises:
        a telemetry failure (unwritable output dir, disk quota) must not
        crash an otherwise-successful run, nor — on the error path —
        replace the driver's real exception with a telemetry traceback."""
        if not self.enabled:
            return None
        if self._finalized is not None:
            return self._finalized
        import json
        import logging

        try:
            report = self.build_report(status=status, error=error, extra=extra)
        except Exception as e:
            logging.getLogger("photon_tpu.telemetry").warning(
                "telemetry report build failed (%s: %s); run continues",
                type(e).__name__, e,
            )
            return None
        self._finalized = report
        if self.write and output_dir:
            try:
                tdir = os.path.join(output_dir, "telemetry")
                os.makedirs(tdir, exist_ok=True)
                with open(os.path.join(tdir, "run_report.json"), "w") as f:
                    # default=str: a non-JSON attribute (numpy scalar, Path)
                    # degrades to its repr.
                    json.dump(report, f, indent=1, default=str)
                self.tracer.write_jsonl(os.path.join(tdir, "spans.jsonl"))
            except Exception as e:
                logging.getLogger("photon_tpu.telemetry").warning(
                    "telemetry write to %s failed (%s: %s); run continues",
                    output_dir, type(e).__name__, e,
                )
        return report


NULL_SESSION = TelemetrySession("null", enabled=False)

# The process registry and its tracer.  The tracer keeps no Span objects (a
# long-lived process would grow without bound): its spans live in the
# profiler's trace and in span.seconds / span.count.
_PROCESS_REGISTRY = MetricsRegistry()
_PROCESS_TRACER = Tracer(registry=_PROCESS_REGISTRY, keep=False)


def process_registry() -> MetricsRegistry:
    """The one process-wide registry (module docstring).  Not gated by
    ``PHOTON_TELEMETRY``: a kernel refusal must reach the log and every
    report whatever the run-scoped switch says."""
    return _PROCESS_REGISTRY


def span(name: str, **attributes):
    """A span of library code that runs with no session in reach
    (``attach_feature_major``, the kernel probe, entity binning): in the
    profiler's trace under ``name``, and in the process registry's
    ``span.seconds`` / ``span.count``."""
    return _PROCESS_TRACER.span(name, **attributes)
