"""Device seconds scoring the fixed effect in one traced GAME fit: the XLA
modules the program names ``jit_score_fixed``, one entry a compiled shape
(the training rows, and the validation rows where the fit validates), every
coordinate update of the fit.  ``by_module`` keeps the run's ten longest
entries: with fewer ``jit_score_fixed`` entries listed than the fit scores
shapes, the metric is absent, not smaller."""

from benchmarks.rooflines_game_sparse import score_device_seconds


def read(run):
    return score_device_seconds(run)
