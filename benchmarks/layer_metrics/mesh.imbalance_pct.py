"""How unevenly the placement spread the live training rows: 100 x (the
fullest device's rows / the mean device's - 1) of the program's
``placement.live_rows{coordinate, device}`` gauges, summed over the
coordinates.  A program that publishes no such gauge (the parent of the PR
that added it) reads nothing."""


def read(run):
    rows = {}
    for g in run["counters"]["gauges"]:
        if g["name"] == "placement.live_rows":
            device = g["labels"]["device"]
            rows[device] = rows.get(device, 0.0) + g["value"]
    if not rows:
        return None
    mean = sum(rows.values()) / len(rows)
    return 100.0 * (max(rows.values()) / mean - 1.0) if mean else None
