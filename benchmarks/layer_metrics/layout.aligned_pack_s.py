"""Host seconds of the aligned bin-packing alone: the program's
``layout.aligned_pack`` span (all of ``load_or_build_aligned_layout``) less
its children ``layout.cache_read`` / ``layout.cache_write``, the layout
cache's file IO."""

from benchmarks.program_counters import span_seconds


def read(run):
    whole = span_seconds(run, "layout.aligned_pack")
    if whole is None:
        return None
    return whole - sum(
        span_seconds(run, child) or 0.0
        for child in ("layout.cache_read", "layout.cache_write")
    )
