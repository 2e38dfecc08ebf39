"""Host seconds set-up spent in backend requests the persistent compile cache
answered (the key, the file read, deserialise, load): the program's
``compile.seconds{phase=cache_load}``, all programs.

The counter runs from process start and the window holds no compile request,
so what it holds is set-up's."""

from benchmarks.program_counters import counter_total


def read(run):
    if counter_total(run, "compile.seconds") is None:
        return None  # the program publishes no compile accounting
    # Accounting with no row under this phase: nothing ran in it.
    return counter_total(run, "compile.seconds", phase="cache_load") or 0.0
