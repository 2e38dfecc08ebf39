"""Metrics registry: labeled counters, gauges, and histograms.

The reference publishes run statistics through Spark accumulators and the
driver logs (SURVEY.md §5 'Tracing'); this process-local registry is the
rebuild's equivalent: cheap thread-safe instruments that drivers, optimizers,
and the GAME descent loop write into, snapshotted at the end of a run into
the structured run report (:mod:`photon_tpu.telemetry.report`).

Instruments are created lazily and keyed by ``(name, labels)`` so call sites
can re-request a metric (``registry.counter("optimizer.runs", lam="0.1")``)
without holding a handle.  All values are host-side Python floats.  The one
edge to the device is :meth:`Counter.inc_deferred`: a count that lives on the
device (an optimizer's evaluation count, returned by an asynchronously
dispatched program) is handed over as it is and fetched later, so counting
never blocks the path that dispatched it.  JAX is touched only to fetch such
scalars, never imported here.
"""

from __future__ import annotations

import sys
import threading
from typing import Dict, List, Tuple

LabelKey = Tuple[Tuple[str, str], ...]


def _label_key(labels: Dict[str, object]) -> LabelKey:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


class Counter:
    """Monotonically increasing count (rows scored, solves run, ...)."""

    __slots__ = ("_lock", "_value", "_pending")

    def __init__(self, lock: threading.RLock, pending: list | None = None):
        self._lock = lock
        self._value = 0.0
        # The owning registry's list of (counter, device scalar) pairs not
        # yet folded into a host total (see inc_deferred).
        self._pending = [] if pending is None else pending

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError(f"counters only go up (inc by {amount})")
        with self._lock:
            self._value += float(amount)

    def inc_deferred(self, amount) -> None:
        """Add a count that is still on the device: ``amount`` is a scalar
        ``jax.Array`` (typically the output of a program dispatched a moment
        ago) and is NOT fetched here.  It joins the host total at the
        registry's next :meth:`MetricsRegistry.snapshot` (one batched
        fetch).  A long-lived process that never snapshots holds a bounded
        backlog: once ``_SWEEP_AT`` scalars are pending, those whose
        programs have finished (``is_ready()``: no wait) are folded here."""
        with self._lock:
            self._pending.append((self, amount))
            if len(self._pending) >= _SWEEP_AT:
                _fold(self._pending, only_ready=True)

    @property
    def value(self) -> float:
        """The host total; deferred increments not yet folded (see
        :meth:`inc_deferred`) are not in it until the next snapshot."""
        return self._value


# Pending deferred increments at which inc_deferred starts folding the ready
# ones: high enough that a benchmark window or a sweep never fetches on its
# timed path, low enough that the backlog is a few KB.
_SWEEP_AT = 64


def _fold(pending: list, only_ready: bool) -> None:
    """Fold ``pending`` (counter, device scalar) pairs into their counters'
    host totals, in place, under the registry lock the caller holds: all of
    them with one batched ``device_get``, or — ``only_ready`` — just those
    whose value is already computed (``is_ready()``: no wait)."""
    if not pending:
        return
    if only_ready:
        take = [
            i for i, (_, v) in enumerate(pending)
            if getattr(v, "is_ready", lambda: True)()
        ]
    else:
        take = list(range(len(pending)))
    if not take:
        return
    values = [pending[i][1] for i in take]
    jax_mod = sys.modules.get("jax")
    if jax_mod is not None:
        values = jax_mod.device_get(values)
    for i, v in zip(take, values):
        pending[i][0]._value += float(v)
    for i in reversed(take):
        del pending[i]


class Gauge:
    """Last-written value (dataset size, best lambda, rows/s)."""

    __slots__ = ("_lock", "_value")

    def __init__(self, lock: threading.RLock):
        self._lock = lock
        self._value: float | None = None

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    @property
    def value(self) -> float | None:
        return self._value


class Histogram:
    """Distribution of observations (per-solve seconds, chunk sizes).

    Keeps exact count/sum/min/max plus a bounded, deterministic reservoir
    for percentiles: once the reservoir fills it is decimated to every
    second sample and the keep-stride doubles, so memory stays O(cap) while
    the kept samples remain an even sweep of the observation sequence (no
    RNG — runs stay reproducible).
    """

    __slots__ = ("_lock", "count", "sum", "min", "max", "_kept", "_stride", "_cap")

    def __init__(self, lock: threading.RLock, cap: int = 256):
        self._lock = lock
        self.count = 0
        self.sum = 0.0
        self.min: float | None = None
        self.max: float | None = None
        self._kept: List[float] = []
        self._stride = 1
        self._cap = cap

    def observe(self, value: float) -> None:
        value = float(value)
        with self._lock:
            self.min = value if self.min is None else min(self.min, value)
            self.max = value if self.max is None else max(self.max, value)
            if self.count % self._stride == 0:
                self._kept.append(value)
                if len(self._kept) > self._cap:
                    self._kept = self._kept[::2]
                    self._stride *= 2
            self.count += 1
            self.sum += value

    @property
    def mean(self) -> float | None:
        return self.sum / self.count if self.count else None

    def percentile(self, p: float) -> float | None:
        """Approximate percentile from the kept reservoir (p in [0, 100])."""
        with self._lock:
            kept = sorted(self._kept)
        if not kept:
            return None
        idx = min(len(kept) - 1, max(0, round(p / 100.0 * (len(kept) - 1))))
        return kept[idx]

    def summary(self) -> dict:
        return {
            "count": self.count,
            "sum": self.sum,
            "min": self.min,
            "max": self.max,
            "mean": self.mean,
            "p50": self.percentile(50),
            "p90": self.percentile(90),
            "p99": self.percentile(99),
        }


class MetricsRegistry:
    """Thread-safe registry of labeled instruments.

    One registry per run (owned by the
    :class:`~photon_tpu.telemetry.TelemetrySession`); ``snapshot()`` is the
    JSON-ready export embedded in the run report, ``to_prometheus()`` the
    text exposition for scraping a long-lived process.
    """

    _KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}

    def __init__(self):
        self._lock = threading.RLock()
        self._metrics: Dict[Tuple[str, LabelKey], Tuple[str, object]] = {}
        self._pending: list = []  # deferred counter increments, unfetched

    def _get(self, kind: str, name: str, labels: Dict[str, object]):
        key = (name, _label_key(labels))
        with self._lock:
            existing = self._metrics.get(key)
            if existing is not None:
                existing_kind, metric = existing
                if existing_kind != kind:
                    raise TypeError(
                        f"metric {name!r}{dict(key[1])} already registered "
                        f"as {existing_kind}, requested as {kind}"
                    )
                return metric
            if kind == "counter":
                metric = Counter(self._lock, self._pending)
            else:
                metric = self._KINDS[kind](self._lock)
            self._metrics[key] = (kind, metric)
            return metric

    def counter(self, name: str, **labels) -> Counter:
        return self._get("counter", name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get("gauge", name, labels)

    def histogram(self, name: str, **labels) -> Histogram:
        return self._get("histogram", name, labels)

    def clear(self) -> None:
        """Forget every instrument and pending increment (tests isolate the
        process registry with it; handles taken earlier are orphaned)."""
        with self._lock:
            self._metrics.clear()
            del self._pending[:]

    def snapshot(self) -> dict:
        """JSON-ready ``{"counters": [...], "gauges": [...], "histograms":
        [...]}``, each entry ``{"name", "labels", ...value(s)}``, sorted by
        (name, labels) so identical runs export identical structures.
        Formats under the registry lock (the instruments share it, so a
        mid-``observe`` count/sum pair can never tear)."""
        out: dict = {"counters": [], "gauges": [], "histograms": []}
        with self._lock:
            _fold(self._pending, only_ready=False)
            for (name, labels), (kind, metric) in sorted(self._metrics.items()):
                entry = {"name": name, "labels": dict(labels)}
                if kind == "histogram":
                    entry.update(metric.summary())
                else:
                    entry["value"] = metric.value
                out[kind + "s"].append(entry)
        return out

    def to_prometheus(self) -> str:
        """Prometheus text exposition: one ``# TYPE`` line per metric name,
        label values escaped per the text format, histograms exported as
        summaries with quantile labels.  Formats under the registry lock
        (see :meth:`snapshot`)."""

        def sanitize(name: str) -> str:
            return "".join(c if c.isalnum() or c == "_" else "_" for c in name)

        def escape(value: str) -> str:
            return (
                value.replace("\\", "\\\\").replace('"', '\\"')
                .replace("\n", "\\n")
            )

        def fmt_labels(labels: dict, extra: dict | None = None) -> str:
            merged = {**labels, **(extra or {})}
            if not merged:
                return ""
            body = ",".join(
                f'{sanitize(k)}="{escape(str(v))}"'
                for k, v in sorted(merged.items())
            )
            return "{" + body + "}"

        lines: List[str] = []
        typed: set = set()
        with self._lock:
            _fold(self._pending, only_ready=False)
            for (name, labels), (kind, metric) in sorted(self._metrics.items()):
                pname = sanitize(name)
                labels = dict(labels)
                if kind == "gauge" and metric.value is None:
                    continue
                prom_type = "summary" if kind == "histogram" else kind
                if pname not in typed:  # one TYPE line per name, ever
                    typed.add(pname)
                    lines.append(f"# TYPE {pname} {prom_type}")
                if kind in ("counter", "gauge"):
                    lines.append(f"{pname}{fmt_labels(labels)} {metric.value:g}")
                else:
                    for q in (0.5, 0.9, 0.99):
                        v = metric.percentile(q * 100)
                        if v is not None:
                            lines.append(
                                f"{pname}"
                                f"{fmt_labels(labels, {'quantile': f'{q:g}'})}"
                                f" {v:g}"
                            )
                    lines.append(f"{pname}_sum{fmt_labels(labels)} {metric.sum:g}")
                    lines.append(
                        f"{pname}_count{fmt_labels(labels)} {metric.count:g}"
                    )
        return "\n".join(lines) + ("\n" if lines else "")
