"""CG steps (Hessian-vector products) TRON ran in a fit: the process
registry's unlabelled ``optimizer.cg_iterations``, fed by
``GlmOptimizationProblem.run`` from ``OptimizerResult.cg_iterations``, over
the fits of the process.  A program that does not count them reads
nothing."""

from benchmarks.program_counters import fits


def read(run):
    rows = [
        row["value"] for row in run["counters"]["counters"]
        if row["name"] == "optimizer.cg_iterations" and not row["labels"]
    ]
    return sum(rows) / fits(run) if rows else None
