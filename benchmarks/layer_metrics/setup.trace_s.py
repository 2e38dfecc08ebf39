"""Host seconds set-up spent tracing functions to jaxprs: the program's
``compile.seconds{phase=trace}``, all programs (JAX's
``jaxpr_trace_duration`` events, each counted for its self time, so a
function traced inside another is not counted twice).

The counter runs from process start and the window holds no compile request,
so what it holds is set-up's."""

from benchmarks.program_counters import counter_total


def read(run):
    if counter_total(run, "compile.seconds") is None:
        return None  # the program publishes no compile accounting
    # Accounting with no row under this phase: nothing ran in it.
    return counter_total(run, "compile.seconds", phase="trace") or 0.0
