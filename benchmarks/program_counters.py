"""What the new per-layer readers share: totals of the program's counters
and spans out of the ``run`` dict ``run.py`` builds.

``run["counters"]["counters"]`` holds the session's counters (GAME) and the
process registry's (``photon_tpu.utils.device.kernel_metrics()``, both
cells), each row ``{"name", "labels", "value"}``.  A span the program opened
is two of those rows: ``span.seconds{span=<name>}`` and
``span.count{span=<name>}``.  Everything here returns ``None`` when the
program recorded nothing under the name asked for — the parent commit of the
PR that added a name, or a refactor that lost it — so a missing name shows
as a missing metric, never as 0.
"""

from __future__ import annotations


def counter_total(run: dict, name: str, **labels) -> float | None:
    """Sum of the counter rows called ``name`` whose labels include
    ``labels``; ``None`` if there is no such row."""
    rows = [
        row["value"] for row in run["counters"]["counters"]
        if row["name"] == name and all(
            row["labels"].get(k) == str(v) for k, v in labels.items()
        )
    ]
    return sum(rows) if rows else None


def span_seconds(run: dict, span: str) -> float | None:
    """Host seconds the program spent inside spans called ``span``."""
    return counter_total(run, "span.seconds", span=span)


def fits(run: dict) -> int:
    """Fits the process ran: the window's steps and set-up's warm-up step.
    The program's counters run from process start, so per-fit numbers put
    the warm-up in both the total and the count."""
    return len(run["steps"]) + 1


def module_seconds(run: dict, prefixes: tuple,
                   expected: int = 1) -> float | None:
    """Device seconds per traced step of the XLA modules (jitted programs)
    whose name starts with one of ``prefixes``, from the trace reduction's
    ``by_module``: one entry per compiled shape, ``jit_<name>(<fingerprint>)``,
    and only the ten longest of the run.  A program below the tenth entry is
    not in the sum, and which programs are cut depends on how they rank
    against unrelated ones — so the caller says how many entries it
    ``expected``, and with fewer listed the metric is absent rather than
    smaller."""
    trace = run.get("trace")
    if not trace or not run.get("traced_steps"):
        return None
    seconds = [
        s for name, s in trace["by_module"] if name.startswith(prefixes)
    ]
    if len(seconds) < max(expected, 1):
        return None
    return sum(seconds) / run["traced_steps"]
