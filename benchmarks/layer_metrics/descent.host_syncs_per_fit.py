"""``descent.host_syncs`` per fit: the once-an-iteration stats drain."""


def read(run):
    counts = [s["host_syncs"] for s in run["steps"] if "host_syncs" in s]
    return sum(counts) / len(counts) if counts else None
