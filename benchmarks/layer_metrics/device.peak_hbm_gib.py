"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the
window, before the reference runs."""


def read(run):
    peak = run.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
