"""What a trace over several devices says that ``trace_reduce.reduce`` sums
away: the time inside collective operations, and each device's busy time.

``reduce(path)`` reads the same ``.xplane.pb`` with the same plane and line
rules as ``trace_reduce`` (its helpers are used, nothing is copied) and
returns, for the traced window:

* ``devices``: the device planes that ran anything;
* ``collective_s``: seconds a device spent inside collective operations
  (``all-reduce``, ``all-gather``, ``reduce-scatter``, ``all-to-all``,
  ``collective-permute`` and their ``-start`` / ``-done`` halves), the
  union of their intervals on each device, mean over the devices;
* ``collective_s_by_device`` and ``busy_s_by_device``: the same union, and
  the union of every operation's interval, device by device (plane order);
* ``collective_ops``: how many such events there were, all devices.

An operation is a collective by its opcode: the one ``trace_reduce.
short_name`` cuts out of the HLO text a TPU trace names an op by, else the
event's own name less its ``%`` and its ``.<n>``.  A fusion that XLA named
after a collective it absorbed is not one.
"""

from __future__ import annotations

import os
import re

from benchmarks import trace_reduce

COLLECTIVES = frozenset((
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
))


def is_collective(name: str) -> bool:
    words = trace_reduce.short_name(name).split()
    if not words:
        return False
    opcode = words[1] if len(words) > 1 and words[0].startswith("%") else (
        re.sub(r"\.\d+$", "", words[0].lstrip("%"))
    )
    return re.sub(r"-(start|done)$", "", opcode) in COLLECTIVES


def reduce_planes(planes) -> dict:
    collective, busy, ops = [], [], 0
    for plane in trace_reduce._device_planes(planes):
        lines = {line.name: line for line in plane.lines}
        op_lines = [lines["XLA Ops"]] if "XLA Ops" in lines else [
            line for name, line in lines.items() if name != "XLA Modules"
        ]
        events = [ev for line in op_lines for ev in trace_reduce._events(line)]
        if not events:
            continue
        inside = [(s, e) for name, s, e in events if is_collective(name)]
        ops += len(inside)
        collective.append(trace_reduce._union(inside)[0] / 1e9)
        busy.append(trace_reduce._union([(s, e) for _, s, e in events])[0] / 1e9)
    return {
        "devices": len(busy),
        "collective_s": sum(collective) / len(busy) if busy else None,
        "collective_s_by_device": collective,
        "busy_s_by_device": busy,
        "collective_ops": ops,
    }


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    return reduce_planes(list(ProfileData.from_file(path).planes))


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(reduce(sys.argv[1]), indent=1))
