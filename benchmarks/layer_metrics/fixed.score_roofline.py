"""The sparse fixed effect's scoring as a share of its HBM roofline: the
entries the program scored in a fit (its ``score.sparse_entries{coordinate=
fixed}`` counter, added at dispatch from the static shape of the ids) x 8 B
(an id and a value) + the rows they make x 4 B (a margin), over the peak
bytes/s, over the device seconds of ``jit_score_fixed`` in one traced fit.
A program that does not count the entries (the parent of the PR that added
the counter) reads nothing."""

from benchmarks.rooflines_game_sparse import bytes_score, score_device_seconds


def read(run):
    work, floor = run.get("work") or {}, run.get("floor") or {}
    entries, device_s = work.get("sparse_entries"), score_device_seconds(run)
    if not entries or not device_s or not floor.get("hbm_bytes_per_s"):
        return None
    least = bytes_score(entries, entries / work["fixed_nnz"]) \
        / floor["hbm_bytes_per_s"]
    return 100.0 * least / device_s
