"""Lockstep Newton iterations the entity-solve bin programs ran in one GAME
fit, every descent iteration and every bin: the program's
``solves.newton_iterations{coordinate,bin}`` counters over the fits of the
process."""

from benchmarks.program_counters import counter_total, fits


def read(run):
    total = counter_total(run, "solves.newton_iterations")
    return None if total is None else total / fits(run)
