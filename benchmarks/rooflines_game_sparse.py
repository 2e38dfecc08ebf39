"""The least work of one GAME fit whose fixed effect is sparse.

``rooflines.game_fit_floor`` with its two dense fixed-effect terms taken out
and the sparse ones put in their place, each a function of shapes and of
counts the fit reports, whichever kernel ran:

* **the fixed effect's fits**: ``rooflines.bytes_valuegrad(E, d, n)`` /
  ``flops_valuegrad(E, n)`` an objective evaluation, times the evaluations
  the program counted (``optimizer.evaluations{coordinate=fixed}``: the start
  of each fit, every line-search trial, the polish; not ``iterations + 1``);
* **the fixed effect's scores**: after each of its updates the training rows
  and the validation rows are scored once: an id and a value read an entry
  (8 B), a margin written a row (4 B), a multiply-add an entry.  The
  coefficient vector (1 MB) is not counted: it fits on the chip's fast
  memory and is read from HBM at most once a score;
* entity solves and the random effects' scores: ``game_fit_floor``'s own
  terms, from a call with the fixed effect's width and counts at 0.
"""

from __future__ import annotations

from benchmarks import rooflines
from benchmarks.program_counters import module_seconds

SCORE_BYTES_PER_ENTRY = 8
SCORE_BYTES_PER_ROW = 4


def bytes_score(entries: float, rows: float) -> float:
    """The fixed effect's margins over ``rows`` rows of ``entries`` padded-COO
    nonzeros, once."""
    return SCORE_BYTES_PER_ENTRY * entries + SCORE_BYTES_PER_ROW * rows


def flops_score(entries: float) -> float:
    return 2 * entries


def score_device_seconds(run: dict) -> float | None:
    """Device seconds of ``jit_score_fixed`` in one traced fit, every shape
    the fit scores (the training rows, and the validation rows where it
    validates); with fewer entries among ``by_module``'s ten, nothing."""
    work = run.get("work") or {}
    shapes = 1 + bool(work.get("validation_rows"))
    return module_seconds(run, ("jit_score_fixed",), expected=shapes)


def game_sparse_fit_floor(work: dict, peak: dict) -> dict:
    """``work``: ``game_fit``'s keys with ``fixed_nnz`` and
    ``fixed_evaluations`` (a fit's, all its fixed-effect fits together)."""
    rows, val_rows = work["rows"], work["validation_rows"]
    nnz, dim = work["fixed_nnz"], work["fixed_dim"]
    rest = rooflines.game_fit_floor(
        dict(work, fixed_dim=0, fixed_iterations=0, fixed_fits=0), peak)
    phases = dict(rest["phases"])
    del phases["fixed_valuegrad"]  # no work left in it
    evaluations = work["fixed_evaluations"]
    flops = evaluations * rooflines.flops_valuegrad(rows * nnz, rows)
    t_fit, phases["fixed_valuegrad"] = rooflines.least_seconds(
        flops,
        evaluations * rooflines.bytes_valuegrad(rows * nnz, dim, rows), peak,
    )
    scored = work["descent_iterations"] * (rows + val_rows)
    t_score, phases["fixed_scoring"] = rooflines.least_seconds(
        flops_score(scored * nnz), bytes_score(scored * nnz, scored), peak,
    )
    return {
        "seconds": rest["seconds"] + t_fit + t_score,
        "fixed_valuegrad_seconds": t_fit,
        "fixed_scoring_seconds": t_score,
        "flops": rest["flops"] + flops + flops_score(scored * nnz),
        "phases": phases,
        "hbm_bytes_per_s": peak["hbm_bytes_per_s"],  # for the score's reader
    }
