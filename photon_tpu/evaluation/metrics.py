"""Metric functions on (scores, labels, weights) arrays.

All metrics treat ``weight == 0`` rows as absent — the padding convention —
so they compose directly with padded/sharded batches.  The headline metrics
are jit-compatible vectorized JAX.  Per-entity (sharded) aggregation has two
paths: :func:`sharded_metric` is the host numpy reference (one jitted metric
call per entity group — the reference's separate Spark evaluator pass), and
:func:`sharded_metric_device` is a single jitted segment-reduce program over
integer entity codes — the on-device validation pipeline's path
(``game.descent``), which under a sharded mesh lets GSPMD place the sort /
psum collectives (the DrJAX shape, arXiv:2403.07128) and syncs exactly one
scalar per metric.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.utils import pow2_at_least
from photon_tpu.utils.device import named_jit

from photon_tpu.core.losses import get_loss

Array = jax.Array


def _weights_or_ones(scores, weights):
    if weights is None:
        return jnp.ones_like(scores)
    return weights


# The metric programs' published device names are jit_metric_<what>
# (README "Telemetry"): validation's device time is read off the trace by
# that prefix, whatever the Python functions are called.
@functools.partial(named_jit, "metric_auc")
def area_under_roc_curve(scores: Array, labels: Array, weights: Array | None = None) -> Array:
    """Weighted, tie-corrected AUC (Mann-Whitney U formulation).

    AUC = sum_i w+_i * (W-_below(s_i) + W-_tied(s_i)/2) / (W+ * W-), computed
    by one multi-operand sort (the weights travel with their score) and three
    scans that carry each tie group's prefix sums over the group — O(n log n),
    no gather and no binary search (the reference's
    AreaUnderROCCurveEvaluator computes the same statistic via Spark's
    ranking).
    """
    with jax.named_scope("validation/auc"):
        return _auc(scores, labels, _weights_or_ones(scores, weights))


def _flip_negatives(bits: Array) -> Array:
    """IEEE floats' bits as signed integers in the floats' own order, and
    back (the map is its own inverse): a negative float's magnitude bits
    count downward, so they are inverted; the sign bit stays."""
    return jnp.where(bits < 0, bits ^ jnp.iinfo(bits.dtype).max, bits)


def _auc(scores: Array, labels: Array, w: Array) -> Array:
    pos_w = w * labels
    neg_w = w * (1.0 - labels)
    # One sort carries the weights with their score: nothing is read back
    # through an index, so the program holds no gather and no loop.  The key
    # is the score's bits as an integer: the same order (-0.0 just under 0.0,
    # one tie group below), and the TPU compiler takes half as long over an
    # integer key with two payloads as over a float one (PERF.md, PR 35).
    scores = scores.astype(jnp.promote_types(scores.dtype, jnp.float32))
    int_t = jnp.int32 if scores.dtype == jnp.float32 else jnp.int64
    key = _flip_negatives(jax.lax.bitcast_convert_type(scores, int_t))
    key, posw, negw = jax.lax.sort((key, pos_w, neg_w), num_keys=1)
    s = jax.lax.bitcast_convert_type(_flip_negatives(key), scores.dtype)
    edge = jnp.ones(1, bool)
    differs = s[1:] != s[:-1]
    new_tie = jnp.concatenate([edge, differs])  # first row of a tie group
    last_tie = jnp.concatenate([differs, edge])  # last row of a tie group
    csneg = jnp.cumsum(negw)
    csneg_ex = jnp.concatenate([jnp.zeros(1, csneg.dtype), csneg[:-1]])
    # A prefix sum of weights >= 0 never falls, so a running max carries the
    # prefix at a group's first row forward over the group, and a reversed
    # running min carries the prefix at its last row backward.
    below = jax.lax.cummax(jnp.where(new_tie, csneg_ex, 0.0))
    upto = jax.lax.cummin(jnp.where(last_tie, csneg, jnp.inf), reverse=True)
    tied = upto - below
    num = jnp.sum(posw * (below + 0.5 * tied))
    wpos = jnp.sum(pos_w)
    wneg = jnp.sum(neg_w)
    return jnp.where((wpos > 0) & (wneg > 0), num / (wpos * wneg), 0.5)


@functools.partial(named_jit, "metric_rmse")
def rmse(scores: Array, labels: Array, weights: Array | None = None) -> Array:
    with jax.named_scope("validation/rmse"):
        w = _weights_or_ones(scores, weights)
        se = w * (scores - labels) ** 2
        return jnp.sqrt(jnp.sum(se) / jnp.maximum(jnp.sum(w), 1e-30))


def _mean_loss(loss_name: str, short: str) -> Callable:
    loss = get_loss(loss_name)

    def metric(scores: Array, labels: Array, weights: Array | None = None) -> Array:
        with jax.named_scope(f"validation/{short}"):
            w = _weights_or_ones(scores, weights)
            return jnp.sum(w * loss.value(scores, labels)) / jnp.maximum(
                jnp.sum(w), 1e-30
            )

    return named_jit(f"metric_{short}", metric)


logistic_loss_metric = _mean_loss("logistic", "logloss")
poisson_loss_metric = _mean_loss("poisson", "poisson_loss")
squared_loss_metric = _mean_loss("squared", "squared_loss")
smoothed_hinge_loss_metric = _mean_loss("smoothed_hinge", "hinge_loss")


def precision_at_k(
    scores: Array, labels: Array, weights: Array | None = None, k: int = 10
) -> Array:
    """Fraction of positives among the k highest-scoring (non-padded) rows."""
    w = _weights_or_ones(scores, weights)
    masked = jnp.where(w > 0, scores, -jnp.inf)
    k_eff = min(k, int(scores.shape[0]))
    _, top_idx = jax.lax.top_k(masked, k_eff)
    valid = jnp.take(w, top_idx) > 0
    hits = jnp.take(labels, top_idx) * valid
    return jnp.sum(hits) / jnp.maximum(jnp.sum(valid), 1)


def sharded_metric(
    metric: Callable,
    scores: np.ndarray,
    labels: np.ndarray,
    entity_ids: np.ndarray,
    weights: np.ndarray | None = None,
    require_both_classes: bool = False,
    **kw,
) -> float:
    """Average a metric over entity groups (the reference's sharded
    evaluators, e.g. per-query AUC averaged over queries).

    Groups where the metric is undefined (e.g. single-class for AUC when
    ``require_both_classes``) are skipped, matching the reference.
    """
    # host-sync: the HOST sharded path — device callers use
    # sharded_metric_device instead.
    scores = np.asarray(scores)
    labels = np.asarray(labels)
    entity_ids = np.asarray(entity_ids)
    w = np.ones_like(scores) if weights is None else np.asarray(weights)
    live = w > 0
    scores, labels, entity_ids, w = (
        scores[live], labels[live], entity_ids[live], w[live]
    )
    total, count = 0.0, 0
    for eid in np.unique(entity_ids):
        sel = entity_ids == eid
        if require_both_classes:
            pos = float(np.sum(w[sel] * labels[sel]))
            neg = float(np.sum(w[sel] * (1.0 - labels[sel])))
            if pos <= 0 or neg <= 0:
                continue
        # Pad each group to a power-of-two size with weight-0 rows so the
        # jitted metric compiles O(log max_group) times, not once per
        # distinct group size.
        n = int(sel.sum())
        padded = pow2_at_least(n)
        s = np.zeros(padded, scores.dtype)
        l = np.zeros(padded, labels.dtype)
        ww = np.zeros(padded, w.dtype)
        s[:n], l[:n], ww[:n] = scores[sel], labels[sel], w[sel]
        total += float(metric(s, l, ww, **kw))
        count += 1
    return total / count if count else float("nan")


def _segment_starts(order_key: Array) -> Array:
    """For a SORTED key vector, the index of each row's segment start
    (``cummax`` over the boundary indices — O(n), no host sync)."""
    n = order_key.shape[0]
    idx = jnp.arange(n)
    new = jnp.concatenate(
        [jnp.ones(1, bool), order_key[1:] != order_key[:-1]]
    )
    return jax.lax.cummax(jnp.where(new, idx, 0))


def _segmented_cumsum(x: Array, new_seg: Array) -> Array:
    """Inclusive cumulative sum that RESETS at each segment boundary.

    A segmented-sum associative scan — the sums stay segment-local, so late
    segments never pay the cancellation error a global-cumsum-and-subtract
    would (difference of two large prefixes in f32)."""

    def combine(a, b):
        a_sum, a_new = a
        b_sum, b_new = b
        return jnp.where(b_new, b_sum, a_sum + b_sum), a_new | b_new

    total, _ = jax.lax.associative_scan(combine, (x, new_seg))
    return total


@functools.partial(
    named_jit, "metric_sharded_auc", static_argnames=("num_segments",)
)
def _sharded_auc_kernel(
    scores: Array, labels: Array, weights: Array, codes: Array,
    num_segments: int,
) -> tuple[Array, Array]:
    """Per-entity weighted tie-corrected AUC, averaged over entities with
    both classes present, as ONE program: sort by (entity, score), take
    segment-local cumulative negative weight with tie-group correction, and
    segment-sum the Mann-Whitney numerators.  Matches ``sharded_metric(
    area_under_roc_curve, ..., require_both_classes=True)``."""
    pos = weights * labels
    neg = weights * (1.0 - labels)
    order = jnp.lexsort((scores, codes))
    s, e = scores[order], codes[order]
    pw, nw = pos[order], neg[order]
    n = s.shape[0]
    idx = jnp.arange(n)
    new_seg = jnp.concatenate([jnp.ones(1, bool), e[1:] != e[:-1]])
    new_tie = new_seg | jnp.concatenate(
        [jnp.ones(1, bool), s[1:] != s[:-1]]
    )
    tie_start = jax.lax.cummax(jnp.where(new_tie, idx, 0))
    # Segment-local EXCLUSIVE negative-weight prefix, evaluated at each
    # row's tie-group start: the weight of strictly-lower-scored negatives
    # in the same entity.
    csneg_ex = _segmented_cumsum(nw, new_seg) - nw
    below = csneg_ex[tie_start]
    tie_gid = jnp.cumsum(new_tie) - 1
    tied = jax.ops.segment_sum(nw, tie_gid, num_segments=n)[tie_gid]
    num = jax.ops.segment_sum(
        pw * (below + 0.5 * tied), e, num_segments=num_segments
    )
    wpos = jax.ops.segment_sum(pw, e, num_segments=num_segments)
    wneg = jax.ops.segment_sum(nw, e, num_segments=num_segments)
    valid = (wpos > 0) & (wneg > 0)
    auc = jnp.where(valid, num / jnp.maximum(wpos * wneg, 1e-30), 0.0)
    count = jnp.sum(valid)
    return jnp.sum(auc) / jnp.maximum(count, 1), count


@functools.partial(
    named_jit, "metric_sharded_precision",
    static_argnames=("num_segments", "k"),
)
def _sharded_precision_kernel(
    scores: Array, labels: Array, weights: Array, codes: Array,
    num_segments: int, k: int,
) -> tuple[Array, Array]:
    """Per-entity precision@k averaged over entities with any live row:
    sort by (entity, -masked score); a row is selected when its within-
    segment rank is below ``k`` and its weight is live.  Matches
    ``sharded_metric(precision_at_k, ..., k=k)``."""
    masked = jnp.where(weights > 0, scores, -jnp.inf)
    order = jnp.lexsort((-masked, codes))
    e, l, w = codes[order], labels[order], weights[order]
    idx = jnp.arange(scores.shape[0])
    rank = idx - _segment_starts(e)
    sel = (rank < k) & (w > 0)
    hits = jax.ops.segment_sum(l * sel, e, num_segments=num_segments)
    cnt = jax.ops.segment_sum(
        sel.astype(jnp.float32), e, num_segments=num_segments
    )
    live = jax.ops.segment_sum(
        (w > 0).astype(jnp.float32), e, num_segments=num_segments
    )
    valid = live > 0
    prec = jnp.where(valid, hits / jnp.maximum(cnt, 1.0), 0.0)
    count = jnp.sum(valid)
    return jnp.sum(prec) / jnp.maximum(count, 1), count


def sharded_metric_device(
    kind: str,
    scores: Array,
    labels: Array,
    entity_codes: Array,
    num_segments: int,
    weights: Array | None = None,
    k: int = 10,
) -> Array:
    """Device-resident :func:`sharded_metric`: per-entity metric averaged
    over entities, as one jitted segment-reduce program on integer entity
    codes (``kind``: ``auc`` | ``precision``).

    Inputs stay device arrays end to end (sharded inputs run SPMD — GSPMD
    inserts the sort/psum collectives); the return value is a device scalar,
    NaN when no entity qualifies — ``float()`` it for the one host sync.
    Weight-0 rows (padding) are invisible, and segments holding only
    weight-0 rows don't count, matching the host path's live-row filter.
    """
    w = jnp.ones_like(scores) if weights is None else weights
    if kind == "auc":
        mean, count = _sharded_auc_kernel(
            scores, labels, w, entity_codes, num_segments
        )
    elif kind == "precision":
        mean, count = _sharded_precision_kernel(
            scores, labels, w, entity_codes, num_segments, k
        )
    else:
        raise KeyError(f"unknown device sharded metric {kind!r}")
    return jnp.where(count > 0, mean, jnp.nan)
