"""Host seconds building and uploading the row-block x feature-block entry
tiles the ``blocked`` kernel reads: the program's ``layout.block_tiles``
span.  A program without that layout has no such span: nothing is read."""

from benchmarks.program_counters import span_seconds


def read(run):
    return span_seconds(run, "layout.block_tiles")
