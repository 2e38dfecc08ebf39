"""Share of the entity-solve bins' entity x row cells that are padding: the
``solves.padded_fraction`` gauges weighted by each bin's cells."""


def read(run):
    bins = {}
    for g in run["counters"]["gauges"]:
        if g["name"] in ("solves.padded_fraction", "solves.bin_occupancy",
                         "solves.bin_entities_padded"):
            key = (g["labels"]["coordinate"], g["labels"]["bin"])
            bins.setdefault(key, {"capacity": int(g["labels"]["capacity"])})
            bins[key][g["name"]] = g["value"]
    cells = padded = 0.0
    for b in bins.values():
        if len(b) < 4:
            continue
        n = (b["solves.bin_occupancy"] + b["solves.bin_entities_padded"]) \
            * b["capacity"]
        cells += n
        padded += n * b["solves.padded_fraction"]
    return 100.0 * padded / cells if cells else None
