"""Device seconds of the fixed effect's fits in one traced GAME fit: the XLA
module the program names ``jit_glm_fit_lbfgs`` (one compiled shape: every
descent iteration's fit runs it, warm-started, with new offsets).  Absent
where it is not among ``by_module``'s ten longest entries."""

from benchmarks.program_counters import module_seconds


def read(run):
    return module_seconds(run, ("jit_glm_fit_lbfgs",))
