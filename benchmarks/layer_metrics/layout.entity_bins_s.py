"""Host seconds of GAME's entity binning (grouping rows by entity,
bucketing, merging buckets into size bins, padding): the program's
``layout.entity_bins`` span."""

from benchmarks.program_counters import span_seconds


def read(run):
    return span_seconds(run, "layout.entity_bins")
