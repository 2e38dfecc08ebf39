"""Backend compile requests of set-up, whatever the persistent cache
answered: the program's ``compile.requests``, all programs and outcomes
(``hit`` + ``miss`` + ``uncached``).  One count a compiled shape, so six bin
shapes of one program name count six."""

from benchmarks.program_counters import counter_total


def read(run):
    return counter_total(run, "compile.requests")
