"""Device seconds of the validation metrics in one traced fit: the XLA
modules the program names ``jit_metric_*`` (``metric_auc``,
``metric_logloss``, ...).  Valid only for the metric programs that reach
``by_module``'s ten longest entries: in ``game_fit`` that is ``jit_metric_auc``
(0.49 s) alone, ``jit_metric_logloss`` (0.00003 s) never does, and if
``jit_metric_auc`` itself is cut the metric is absent.  Scoring the
validation rows runs the same ``jit_score_*`` programs as scoring the
training rows and is not in it."""

from benchmarks.program_counters import module_seconds


def read(run):
    return module_seconds(run, ("jit_metric_",))
