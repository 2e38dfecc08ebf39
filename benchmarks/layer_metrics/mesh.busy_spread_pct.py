"""How much longer the busiest device of the mesh ran than the mean one
over the traced fit: 100 x (max of the devices' busy times / their mean -
1).  0 is an even mesh; what is above it is time the other chips wait at
the next collective.  From the mesh runner's own reduction of the trace
(``benchmarks/trace_collectives.py``, ``mesh_trace``); absent without it."""


def read(run):
    mesh = run["counters"].get("mesh_trace")
    if not mesh or not mesh["devices"]:
        return None
    busy = mesh["busy_s_by_device"]
    mean = sum(busy) / len(busy)
    return 100.0 * (max(busy) / mean - 1.0) if mean else None
