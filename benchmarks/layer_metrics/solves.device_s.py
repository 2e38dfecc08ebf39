"""Device seconds of the batched entity solves in one traced fit: the XLA
modules the program names ``jit_entity_solve_*`` (``entity_solve_newton``,
``entity_solve_newton_cg``), one entry per bin shape.  ``by_module`` keeps
the run's ten longest entries only, so the sum is taken only when it lists
as many solve programs as the program counted bins
(``solves.newton_iterations{coordinate,bin}`` rows): a bin program that
falls below the tenth entry, or two bins that come to share one compiled
shape, make the metric absent, not smaller."""

from benchmarks.program_counters import module_seconds


def read(run):
    bins = {
        (row["labels"].get("coordinate"), row["labels"].get("bin"))
        for row in run["counters"]["counters"]
        if row["name"] == "solves.newton_iterations"
    }
    return module_seconds(run, ("jit_entity_solve",), expected=len(bins))
