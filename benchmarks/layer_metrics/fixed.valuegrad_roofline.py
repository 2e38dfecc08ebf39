"""The sparse fixed effect's value+gradient as a share of its HBM roofline,
inside a GAME fit: the least time the evaluations the fixed coordinate ran
in a fit could take (``optimizer.evaluations{coordinate=fixed}`` a fit x
``rooflines.bytes_valuegrad(E, d, n)`` over the peak bytes/s; shapes and the
program's own evaluation count, whichever kernel ran:
``rooflines_game_sparse.game_sparse_fit_floor``) over the device seconds of
``jit_glm_fit_lbfgs`` in one traced fit."""

from benchmarks.program_counters import module_seconds


def read(run):
    floor = run.get("floor") or {}
    device_s = module_seconds(run, ("jit_glm_fit_lbfgs",))
    if not device_s or not floor.get("fixed_valuegrad_seconds"):
        return None
    return 100.0 * floor["fixed_valuegrad_seconds"] / device_s
