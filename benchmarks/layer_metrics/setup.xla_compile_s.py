"""Host seconds set-up spent in backend requests that went to the compiler
(cache misses with their write, and requests the cache does not take): the
program's ``compile.seconds{phase=xla_compile}``, all programs.  About 0 on a
warm run: the seconds behind ``setup.compiles``.

The counter runs from process start and the window holds no compile request,
so what it holds is set-up's."""

from benchmarks.program_counters import counter_total


def read(run):
    if counter_total(run, "compile.seconds") is None:
        return None  # the program publishes no compile accounting
    # Accounting with no row under this phase: nothing ran in it.
    return counter_total(run, "compile.seconds", phase="xla_compile") or 0.0
