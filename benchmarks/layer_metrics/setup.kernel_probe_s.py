"""Host seconds of the sparse-gradient kernel selection's trace-time
wall-clock probe (inside the warm-up fit): the program's ``kernels.probe``
span."""

from benchmarks.program_counters import span_seconds


def read(run):
    return span_seconds(run, "kernels.probe")
