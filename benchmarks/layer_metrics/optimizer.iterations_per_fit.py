"""``OptimizerResult.iterations`` of the window's fits, averaged."""


def read(run):
    counts = [s["iterations"] for s in run["steps"] if "iterations" in s]
    return sum(counts) / len(counts) if counts else None
