"""The sparse value+gradient's share of its HBM roofline: the least time
``iterations + 1`` evaluations could take (``rooflines.bytes_valuegrad`` over
the peak bytes/s: shapes and the iteration count only, whichever kernel ran)
over the device-busy time of one traced fit."""


def read(run):
    floor, trace = run.get("floor"), run.get("trace")
    if not floor or "valuegrad_seconds" not in floor:
        return None
    if not trace or not trace["busy_s"] or not run["traced_steps"]:
        return None
    return 100.0 * floor["valuegrad_seconds"] / (
        trace["busy_s"] / run["traced_steps"]
    )
