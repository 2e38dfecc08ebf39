"""Host seconds in ``attach_feature_major``'s argsort and reorder gathers
of the flat entries: the program's ``layout.feature_major`` span."""

from benchmarks.program_counters import span_seconds


def read(run):
    return span_seconds(run, "layout.feature_major")
