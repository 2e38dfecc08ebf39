"""From a profiler trace (``.xplane.pb``) to numbers.

``reduce(path)`` reads the trace with ``jax.profiler.ProfileData`` alone and
returns, for the traced window:

* ``window_s``: first to last device event;
* ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices that ran any;
* ``by_op`` / ``by_module``: device seconds by XLA op (self time: a
  ``while`` without its body) and by XLA module (jitted program), under the
  names the trace gives, an op's HLO text cut to its name and opcode;
* ``gaps``: the longest intervals with no device operation, each named by
  the benchmark's host annotation (``jax.profiler.TraceAnnotation`` whose
  name starts with ``bench.``) that covers its middle;
* ``annotations``: seconds by such host annotation.

A device plane is one whose name starts with ``/device:TPU:`` (any
``/device:`` plane that is not a host's when there is no TPU: the recorded
CPU traces of the tests).  On it, the line ``XLA Ops`` holds the operations
and ``XLA Modules`` the programs; where those lines are absent every line
of the plane counts as operations.
"""

from __future__ import annotations

import glob
import os
import re

ANNOTATION_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: list) -> tuple:
    """Total covered length and the sorted gaps of ``[(start, end), ...]``."""
    covered, gaps, cur_s, cur_e = 0, [], None, None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            covered += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        covered += cur_e - cur_s
    return covered, gaps


def _device_planes(planes) -> list:
    tpu = [p for p in planes if p.name.startswith("/device:TPU:")]
    if tpu:
        return tpu
    return [p for p in planes if p.name.startswith("/device:")
            and "host" not in p.name.lower()]


def short_name(name: str) -> str:
    """``%fusion.38 fusion`` from the HLO text a TPU trace names an op by
    (``%fusion.38 = f32[...] fusion(...), kind=...``); other names as is."""
    match = re.match(r"^(%[\w.\-]+) = .*? ([a-z][\w\-]*)\(", name)
    if not match:
        return name[:120]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{match.group(1)} {match.group(2)}" + (
        f" {target.group(1)}" if target else ""
    )


def _self_times(events: list) -> dict:
    """Seconds by name with every event's children taken out of it: a
    ``while`` holds its body's operations on the same line, and counting
    both would count the body twice."""
    totals: dict = {}
    stack: list = []  # (end, name, children_ns) of the open events

    def close():
        end, name, start, children = stack.pop()
        totals[name] = totals.get(name, 0) + (end - start) - children
        if stack:
            stack[-1][3] += end - start

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= s:
            close()
        stack.append([e, name, s, 0])
    while stack:
        close()
    return totals


def _events(line) -> list:
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def reduce_planes(planes, top: int = 10) -> dict:
    """The reduction over already-read planes: objects with ``name`` and
    ``lines``, each line with ``name`` and ``events`` (``name``,
    ``start_ns``, ``duration_ns``)."""
    by_op: dict = {}
    by_module: dict = {}
    busy_total, devices, op_intervals_all = 0, 0, []
    for plane in _device_planes(planes):
        lines = {line.name: line for line in plane.lines}
        op_lines = [lines["XLA Ops"]] if "XLA Ops" in lines else [
            line for name, line in lines.items() if name != "XLA Modules"
        ]
        intervals = []
        for line in op_lines:
            events = _events(line)
            intervals.extend((s, e) for _, s, e in events)
            for name, ns in _self_times(events).items():
                name = short_name(name)
                by_op[name] = by_op.get(name, 0) + ns
        if "XLA Modules" in lines:
            for name, s, e in _events(lines["XLA Modules"]):
                by_module[name] = by_module.get(name, 0) + (e - s)
        if not intervals:
            continue
        covered, _ = _union(intervals)
        busy_total += covered
        devices += 1
        op_intervals_all.extend(intervals)
    if not devices:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": 0, "by_op": [],
                "by_module": [], "gaps": [], "annotations": {}}
    start = min(s for s, _ in op_intervals_all)
    end = max(e for _, e in op_intervals_all)
    # Gaps: where NO device runs anything.
    _, gaps = _union(op_intervals_all)
    spans = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if name.startswith(ANNOTATION_PREFIX):
                    spans.append((name, s, e))
    annotations: dict = {}
    for name, s, e in spans:
        annotations[name] = annotations.get(name, 0.0) + (e - s) / 1e9

    def covering(mid):
        best = None
        for name, s, e in spans:  # the innermost (shortest) covering span
            if s <= mid <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0] if best else "unannotated"

    named: dict = {}
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        name = covering((s + e) // 2)
        named[name] = named.get(name, 0) + (e - s)

    def ranked(table):
        return [[name, ns / 1e9] for name, ns in
                sorted(table.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (end - start) / 1e9,
        "busy_s": busy_total / devices / 1e9,
        "devices": devices,
        "by_op": ranked(by_op),
        "by_module": ranked(by_module),
        "gaps": ranked(named),
        "gap_count": len(gaps),
        "annotations": annotations,
    }


def reduce(path: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    return reduce_planes(list(ProfileData.from_file(path).planes), top=top)


def describe(path: str) -> dict:
    """Planes, lines and event counts of a trace: what to look at by hand
    before trusting a reduction."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            names: dict = {}
            for ev in events:
                names[ev.name] = names.get(ev.name, 0) + 1
            lines[line.name] = {
                "events": len(events),
                "top_names": sorted(names.items(), key=lambda kv: -kv[1])[:8],
            }
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps({"describe": describe(sys.argv[1]),
                      "reduce": reduce(sys.argv[1], top=40)}, indent=1))
