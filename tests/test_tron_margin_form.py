"""TRON carries its margins (ISSUE 41): under ``GlmObjective.tron_form`` the
curvature is built from the accepted point's margins and the trial is
evaluated at ``z + X s``, summed from CG's own ``X d`` products.  The same
iterates as TRON on ``value_and_grad`` and ``hvp_operator``, with two
forward passes an iteration spared."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import telemetry
from photon_tpu.core.normalization import NormalizationContext
from photon_tpu.core.objective import GlmObjective, RegularizationContext
from photon_tpu.core.optimizers import OptimizerConfig, tron
from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig, hvp_at_for
from photon_tpu.data.batch import (
    DenseBatch,
    SparseBatch,
    attach_feature_major,
    sparse_batch_from_rows,
)

DIM = 64
ROWS = 96
# The fits stop on the function tolerance before the objective's float32
# resolution: a trial there changes ``f`` by an ulp or two, and whether it
# is accepted turns on the last bit of ``z`` (the linear loss's fourth trial
# does), which the two paths round differently.
CFG = OptimizerConfig(max_iterations=12, cg_max_iterations=6, tolerance=1e-4)
REG = RegularizationContext("l2", 1.0)


def _labels(loss: str, margin: np.ndarray, rng) -> np.ndarray:
    if loss == "logistic_regression":
        return (rng.random(margin.shape) < 1 / (1 + np.exp(-margin))).astype(
            np.float32)
    if loss == "poisson_regression":
        return rng.poisson(np.exp(margin)).astype(np.float32)
    return (margin + 0.1 * rng.standard_normal(margin.shape)).astype(
        np.float32)


def _batch(kind: str, loss: str, monkeypatch, seed: int = 0):
    rng = np.random.default_rng(seed)
    ids = np.sort(np.stack(
        [rng.choice(DIM, 6, replace=False) for _ in range(ROWS)]), axis=1)
    vals = (rng.standard_normal((ROWS, 6)) * 0.5).astype(np.float32)
    margin = vals.sum(1) / 3
    label = _labels(loss, margin, rng)
    offset = (0.1 * rng.standard_normal(ROWS)).astype(np.float32)
    weight = rng.uniform(0.5, 1.5, ROWS).astype(np.float32)
    if kind == "dense":
        x = np.zeros((ROWS, DIM), np.float32)
        np.put_along_axis(x, ids, vals, axis=1)
        return DenseBatch(*map(jnp.asarray, (x, label, offset, weight)))
    rows = list(zip(ids.astype(np.int32), vals))
    batch = sparse_batch_from_rows(rows, label, offset, weight)
    if kind == "ids":
        return batch
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")
    jax.clear_caches()
    batch = attach_feature_major(batch, aligned_dim=DIM)
    assert batch.bt is not None
    return batch


def _fits(objective, batch, dim=DIM, cfg=CFG):
    """TRON on today's path and on the carried form, each one program."""
    form = objective.tron_form(batch, dim)
    assert form is not None
    fun = lambda w: objective.value_and_grad(w, batch)  # noqa: E731
    hvp_at = hvp_at_for(objective, batch)
    w0 = jnp.zeros(dim, jnp.float32)
    today = jax.jit(lambda w: tron(fun, w, cfg, hvp_at=hvp_at))(w0)
    carried = jax.jit(lambda w: tron(None, w, cfg, form=form))(w0)
    return today, carried


def _assert_same_fit(today, carried):
    for name in ("iterations", "cg_iterations", "trust_region_rejections"):
        assert int(getattr(carried, name)) == int(getattr(today, name)), name
    w_t, w_c = np.asarray(today.w), np.asarray(carried.w)
    assert np.linalg.norm(w_c - w_t) <= 1e-5 * np.linalg.norm(w_t)
    assert abs(float(carried.value) - float(today.value)) <= 1e-6 * abs(
        float(today.value))
    assert int(today.margin_passes_spared) == 0
    assert int(carried.margin_passes_spared) == 2 * int(carried.iterations)


@pytest.mark.parametrize("kind", ["dense", "ids", "blocked"])
@pytest.mark.parametrize(
    "loss", ["logistic_regression", "poisson_regression", "linear_regression"])
def test_carried_margins_reach_todays_fit(monkeypatch, kind, loss):
    objective = GlmObjective.create(loss, REG)
    today, carried = _fits(objective, _batch(kind, loss, monkeypatch))
    assert int(carried.iterations) >= 2
    _assert_same_fit(today, carried)


def _rejecting_problem():
    """Few rows a coefficient and the exp link: TRON rejects trials."""
    rng = np.random.default_rng(16)
    ids = np.sort(rng.integers(0, 16, (256, 8)), axis=1).astype(np.int32)
    vals = rng.standard_normal((256, 8)).astype(np.float32)
    label = rng.poisson(np.exp(vals.sum(1) / 8)).astype(np.float32)
    return SparseBatch(
        ids=jnp.asarray(ids), vals=jnp.asarray(vals),
        label=jnp.asarray(label), offset=jnp.zeros(256, jnp.float32),
        weight=jnp.ones(256, jnp.float32),
    )


def test_a_rejected_trial_keeps_the_old_margins():
    """After a rejected trial the next curvature and trial start from the
    old point's ``z``: had the carry taken the trial's, the iterates after
    it would leave today's path."""
    batch = _rejecting_problem()
    objective = GlmObjective.create("poisson_regression", REG)
    today, carried = _fits(objective, batch, dim=16, cfg=OptimizerConfig(
        max_iterations=12, cg_max_iterations=16))
    assert int(carried.trust_region_rejections) > 0
    assert int(carried.iterations) > int(carried.trust_region_rejections)
    _assert_same_fit(today, carried)
    # The value the carry ends on is the value at its own iterate.
    f_end, _ = objective.value_and_grad(carried.w, batch)
    assert abs(float(f_end) - float(carried.value)) <= 1e-6 * abs(float(f_end))


@pytest.mark.parametrize("kind", ["dense", "ids", "blocked"])
@pytest.mark.parametrize(
    "loss", ["logistic_regression", "poisson_regression", "linear_regression"])
def test_carried_margins_hold_over_a_long_fit(monkeypatch, kind, loss):
    """The carry never takes ``z`` afresh from ``X w``: each accepted step
    adds its ``X s``.  Run to ``max_iterations`` with no tolerance to stop
    on (tens of accepted steps, most of them past the objective's float32
    resolution), the value and gradient norm TRON returns are still those
    of ``value_and_grad`` at its own ``w``: the value to 1e-6 relative, the
    gradient norm to 1e-5 of the start's (measured over these batches at
    60 iterations, l2 1e-3 and 1: 3.3e-7 and 8.3e-8 at most)."""
    objective = GlmObjective.create(
        loss, RegularizationContext("l2", 1e-3))
    batch = _batch(kind, loss, monkeypatch)
    form = objective.tron_form(batch, DIM)
    cfg = OptimizerConfig(max_iterations=40, cg_max_iterations=6,
                          tolerance=0.0)
    result = jax.jit(lambda w: tron(None, w, cfg, form=form))(
        jnp.zeros(DIM, jnp.float32))
    assert int(result.iterations) - int(result.trust_region_rejections) >= 10
    f, g = objective.value_and_grad(result.w, batch)
    assert abs(float(result.value) - float(f)) <= 1e-6 * abs(float(f))
    g0 = float(result.history_grad_norm[0])
    assert abs(float(result.grad_norm) - float(jnp.linalg.norm(g))) <= (
        1e-5 * g0)


def test_tron_takes_a_form_or_fun_not_both(monkeypatch):
    batch = _batch("dense", "logistic_regression", monkeypatch)
    objective = GlmObjective.create("logistic_regression", REG)
    form = objective.tron_form(batch, DIM)
    fun = lambda w: objective.value_and_grad(w, batch)  # noqa: E731
    w0 = jnp.zeros(DIM, jnp.float32)
    with pytest.raises(ValueError, match="not both"):
        tron(fun, w0, CFG, form=form)
    with pytest.raises(ValueError, match="not both"):
        tron(None, w0, CFG, hvp_at=hvp_at_for(objective, batch), form=form)
    with pytest.raises(ValueError, match="fun or a MarginForm"):
        tron(None, w0, CFG)


def _counters():
    return {
        c["name"]: c["value"]
        for c in telemetry.process_registry().snapshot()["counters"]
        if not c["labels"]
    }


def _spared_through_problem(objective, batch):
    problem = GlmOptimizationProblem(objective, ProblemConfig(
        optimizer="tron", regularization=REG, optimizer_config=CFG))
    telemetry.process_registry().clear()
    _, result = problem.run(batch, dim=DIM)
    return int(result.iterations), _counters()["optimizer.margin_passes_spared"]


@pytest.mark.parametrize("route", ["form", "normalized", "distributed"])
def test_margin_passes_spared_counts_what_the_carry_spared(monkeypatch, route):
    """``optimizer.margin_passes_spared``: two a trust-region iteration
    under the form, none where TRON keeps ``value_and_grad`` (a normalized
    objective, the mesh wrapper)."""
    batch = _batch("dense", "logistic_regression", monkeypatch)
    objective = GlmObjective.create("logistic_regression", REG)
    if route == "normalized":
        objective = GlmObjective.create(
            "logistic_regression", REG, normalization=NormalizationContext(
                factors=jnp.full(DIM, 2.0, jnp.float32)))
        assert objective.tron_form(batch, DIM) is None
    if route == "distributed":
        from photon_tpu.parallel import (
            DistributedGlmObjective,
            create_mesh,
            shard_batch,
        )

        mesh = create_mesh()
        objective = DistributedGlmObjective(objective, mesh)
        batch = shard_batch(batch, mesh)
    iterations, spared = _spared_through_problem(objective, batch)
    assert iterations >= 2
    assert spared == (2 * iterations if route == "form" else 0)
