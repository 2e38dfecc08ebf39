"""Objective (value+gradient) evaluations a fit ran, line-search trials and
the final polish included: the process registry's unlabelled
``optimizer.evaluations``, fed by ``GlmOptimizationProblem.run`` (the GLM
cell's fit; the fixed effect's fits of a GAME fit together), over the fits
of the process."""

from benchmarks.program_counters import fits


def read(run):
    rows = [
        row["value"] for row in run["counters"]["counters"]
        if row["name"] == "optimizer.evaluations" and not row["labels"]
    ]
    return sum(rows) / fits(run) if rows else None
