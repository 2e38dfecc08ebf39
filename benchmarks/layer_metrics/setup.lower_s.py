"""Host seconds set-up spent lowering jaxprs to MLIR modules: the program's
``compile.seconds{phase=lower}``, all programs (JAX's
``jaxpr_to_mlir_module_duration`` events).

The counter runs from process start and the window holds no compile request,
so what it holds is set-up's."""

from benchmarks.program_counters import counter_total


def read(run):
    if counter_total(run, "compile.seconds") is None:
        return None  # the program publishes no compile accounting
    # Accounting with no row under this phase: nothing ran in it.
    return counter_total(run, "compile.seconds", phase="lower") or 0.0
