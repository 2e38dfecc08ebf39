"""Evaluator checks vs hand-computed values and sklearn-free references."""

import numpy as np
import pytest

from photon_tpu.evaluation import get_evaluator
from photon_tpu.evaluation.metrics import (
    area_under_roc_curve,
    precision_at_k,
    rmse,
    sharded_metric,
)


def _auc_bruteforce(scores, labels, weights=None):
    """Pairwise count, float64 sums (the scores compare as given)."""
    w = np.ones(len(scores)) if weights is None else np.float64(weights)
    num = den = 0.0
    for i in range(len(scores)):
        for j in range(len(scores)):
            if labels[i] == 1 and labels[j] == 0:
                pair_w = w[i] * w[j]
                den += pair_w
                if scores[i] > scores[j]:
                    num += pair_w
                elif scores[i] == scores[j]:
                    num += 0.5 * pair_w
    return num / den


def test_auc_matches_bruteforce():
    rng = np.random.default_rng(0)
    scores = rng.normal(size=60).astype(np.float32)
    scores[::7] = scores[3]  # inject ties
    labels = (rng.random(60) < 0.4).astype(np.float32)
    got = float(area_under_roc_curve(scores, labels))
    np.testing.assert_allclose(got, _auc_bruteforce(scores, labels), rtol=1e-5)


def _auc_case(name):
    """``(scores, labels, weights, want, tolerance)`` of one tie / padding /
    size shape the sort-and-scan AUC has to get right."""
    rng = np.random.default_rng(7)
    f32 = np.float32
    if name == "all_tied":
        labels = (rng.random(50) < 0.4).astype(f32)
        return np.full(50, 0.25, f32), labels, np.ones(50, f32), 0.5, 1e-6
    if name == "single_class":
        scores = rng.normal(size=33).astype(f32)
        return scores, np.ones(33, f32), np.ones(33, f32), 0.5, 0.0
    if name == "n1":
        return np.zeros(1, f32), np.ones(1, f32), np.ones(1, f32), 0.5, 0.0
    if name == "n2":
        scores, labels = np.array([0.5, -0.5], f32), np.array([1, 0], f32)
        return scores, labels, np.ones(2, f32), 1.0, 0.0
    if name == "n2_tied":
        scores, labels = np.array([0.5, 0.5], f32), np.array([1, 0], f32)
        return scores, labels, np.ones(2, f32), 0.5, 0.0
    if name == "signed_zeros":
        # -0.0 == 0.0: one tie group, whatever order the sort leaves them in.
        scores = np.array([-0.0, 0.0, 0.0, -0.0, 1.0, -1.0, 0.0, -0.0], f32)
        labels = np.array([1, 0, 1, 0, 1, 0, 0, 1], f32)
        weights = rng.uniform(0.5, 2.0, 8).astype(f32)
    elif name == "three_levels":
        scores = rng.integers(0, 3, 80).astype(f32) - 1.0
        labels = (rng.random(80) < 0.5).astype(f32)
        weights = rng.uniform(0.1, 3.0, 80).astype(f32)
    elif name.startswith("padded_"):
        # sharded_metric's padding: weight-0 rows that carry score 0 and
        # label 0, among live rows whose scores straddle 0 (some exactly 0).
        n, share = 96, int(name.split("_")[1]) / 100.0
        scores = np.round(rng.normal(size=n), 1).astype(f32)
        labels = (rng.random(n) < 0.5).astype(f32)
        weights = rng.uniform(0.5, 2.0, n).astype(f32)
        pad = rng.permutation(n)[: int(n * share)]
        scores[pad], labels[pad], weights[pad] = 0.0, 0.0, 0.0
    elif name == "continuous_2p20":
        n = 2**20
        scores = rng.normal(size=n).astype(f32)
        labels = (rng.random(n) < 1 / (1 + np.exp(-scores))).astype(f32)
        # No pairwise loop reaches this size: the float64 rank AUC that the
        # benchmark's `correct` is decided on (unweighted).
        from benchmarks.reference import game as reference

        return scores, labels, np.ones(n, f32), reference.auc(scores, labels), 1e-6
    else:
        raise KeyError(name)
    return scores, labels, weights, _auc_bruteforce(scores, labels, weights), 1e-6


@pytest.mark.parametrize("case", [
    "all_tied", "three_levels", "padded_10", "padded_30", "padded_50",
    "single_class", "n1", "n2", "n2_tied", "signed_zeros", "continuous_2p20",
])
def test_auc_ties_padding_and_sizes(case):
    scores, labels, weights, want, tol = _auc_case(case)
    got = float(area_under_roc_curve(scores, labels, weights))
    assert abs(got - want) <= tol, (case, got, want)


def test_auc_weighted_and_padded():
    rng = np.random.default_rng(1)
    scores = rng.normal(size=40).astype(np.float32)
    labels = (rng.random(40) < 0.5).astype(np.float32)
    weights = rng.uniform(0.5, 2.0, 40).astype(np.float32)
    weights[30:] = 0.0  # padded rows must be invisible
    got = float(area_under_roc_curve(scores, labels, weights))
    want = _auc_bruteforce(scores[:30], labels[:30], weights[:30])
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_auc_perfect_and_random():
    scores = np.array([0.1, 0.2, 0.8, 0.9], np.float32)
    labels = np.array([0, 0, 1, 1], np.float32)
    assert float(area_under_roc_curve(scores, labels)) == 1.0
    assert float(area_under_roc_curve(scores, 1 - labels)) == 0.0


def test_rmse():
    s = np.array([1.0, 2.0, 3.0], np.float32)
    l = np.array([1.0, 1.0, 1.0], np.float32)
    np.testing.assert_allclose(float(rmse(s, l)), np.sqrt(5.0 / 3.0), rtol=1e-6)


def test_precision_at_k():
    scores = np.array([0.9, 0.8, 0.7, 0.1], np.float32)
    labels = np.array([1, 0, 1, 1], np.float32)
    np.testing.assert_allclose(float(precision_at_k(scores, labels, k=2)), 0.5)
    np.testing.assert_allclose(float(precision_at_k(scores, labels, k=3)), 2 / 3)


def test_sharded_auc_skips_single_class_groups():
    scores = np.array([0.9, 0.1, 0.8, 0.2, 0.5, 0.6], np.float32)
    labels = np.array([1, 0, 1, 0, 1, 1], np.float32)
    groups = np.array([0, 0, 1, 1, 2, 2])
    got = sharded_metric(
        area_under_roc_curve, scores, labels, groups, require_both_classes=True
    )
    np.testing.assert_allclose(got, 1.0)  # groups 0,1 perfect; group 2 skipped


def test_evaluator_registry_and_direction():
    auc = get_evaluator("AUC")
    assert auc.maximize and auc.better_than(0.9, 0.8)
    rmse_ev = get_evaluator("rmse")
    assert not rmse_ev.maximize and rmse_ev.better_than(0.1, 0.2)
    p5 = get_evaluator("precision@5")
    assert p5.name == "PRECISION@5"
    sauc = get_evaluator("sharded_auc:userId")
    assert sauc.entity_column == "userId"
    with pytest.raises(KeyError):
        get_evaluator("f1")  # not in the reference's evaluator set


def test_sharded_evaluator_end_to_end():
    ev = get_evaluator("sharded_auc:user")
    scores = np.array([0.9, 0.1, 0.2, 0.8], np.float32)
    labels = np.array([1, 0, 0, 1], np.float32)
    ids = np.array([7, 7, 9, 9])
    assert ev.evaluate(scores, labels, entity_ids=ids) == 1.0
