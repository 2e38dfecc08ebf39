"""Host seconds the layout cache costs: the content hash that keys it
(``layout.cache_key``) and its file reads and writes (``layout.cache_read``,
``layout.cache_write``), as the program's spans have them."""

from benchmarks.program_counters import span_seconds


def read(run):
    parts = [
        span_seconds(run, span) for span in
        ("layout.cache_key", "layout.cache_read", "layout.cache_write")
    ]
    found = [p for p in parts if p is not None]
    return sum(found) if found else None
