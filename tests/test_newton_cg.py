"""Matrix-free Newton-CG entity solver (ISSUE 14).

Parity strategy mirrors tests/test_batched_solve.py: at dims ≤ 64 the
Newton-CG route is pinned ≤1e-5 against the dense-Cholesky Newton route —
both polish on the f32 gradient's zero, so agreement is at the ground-truth
scale, means AND variances (the same ``_compute_variances`` formula).  At
high dim (d=256, where the dense route never ran) the pin is against an
f64 numpy Newton ground truth.  The memory claim — no ``[B, d, d]``
materialization, peak intermediate O(B·d) — is asserted structurally on
the traced program's jaxpr, platform-independent.
"""

import contextlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_tpu.core.objective import GlmObjective, RegularizationContext
from photon_tpu.core.optimizers import (
    OptimizerConfig,
    get_optimizer,
    newton_cg,
)
from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig
from photon_tpu.data.batch import DenseBatch, SparseBatch
from photon_tpu.game.batched_solve import (
    newton_cg_max_dim,
    solver_route,
)
from photon_tpu.game.coordinate import (
    RandomEffectCoordinate,
    RandomEffectCoordinateConfig,
)
from photon_tpu.game.data import DenseShard, GameDataset
from photon_tpu.telemetry import TelemetrySession

_ENV_KEYS = (
    "PHOTON_SOLVE_BINNING", "PHOTON_SOLVE_NEWTON", "PHOTON_SOLVE_NEWTON_CG",
    "PHOTON_NEWTON_MAX_DIM", "PHOTON_NEWTON_CG_MAX_DIM",
)


@contextlib.contextmanager
def _env(**kw):
    saved = {k: os.environ.get(k) for k in _ENV_KEYS}
    for k, v in kw.items():
        os.environ[k] = v
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# Forces the Newton-CG route at EVERY dim (the dense-Newton window closes).
_FORCE_CG = {"PHOTON_SOLVE_NEWTON_CG": "on", "PHOTON_NEWTON_MAX_DIM": "0"}


def _dataset(n_entities=40, rows_mean=6, dim=4, seed=3):
    rng = np.random.default_rng(seed)
    counts = np.maximum(1, rng.geometric(1.0 / rows_mean, n_entities))
    n = int(counts.sum())
    ent = np.repeat(np.arange(n_entities, dtype=np.int64), counts)
    x = rng.standard_normal((n, dim)).astype(np.float32)
    x[:, -1] = 1.0
    w_true = (rng.standard_normal((n_entities, dim)) * 0.5).astype(np.float32)
    z = np.einsum("nd,nd->n", x, w_true[ent])
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)
    return GameDataset.create(
        y, {"per_entity": DenseShard(x)}, id_columns={"userId": ent}
    )


def _problem(optimizer="lbfgs", variance="none", max_iterations=100):
    return ProblemConfig(
        optimizer=optimizer,
        regularization=RegularizationContext("l2", 1.0),
        optimizer_config=OptimizerConfig(
            max_iterations=max_iterations, tolerance=0.0,
            gradient_tolerance=1e-8,
        ),
        variance_computation=variance,
    )


def _config(problem=None, **kw):
    return RandomEffectCoordinateConfig(
        shard_name="per_entity", entity_column="userId",
        problem=problem or _problem(), **kw,
    )


def _train(data, config, task="logistic_regression", telemetry=None, **env):
    with _env(**env):
        coord = RandomEffectCoordinate(data, config, task)
        if telemetry is not None:
            coord.telemetry = telemetry
        routes = coord._bin_routes()
        model, stats = coord.train(np.zeros(data.num_examples, np.float32))
    return coord, model, stats, routes


# ---------------------------------------------------------------------------
# Route selection
# ---------------------------------------------------------------------------


def test_solver_route_newton_cg_selection():
    smooth = _problem()
    # The dense-Newton window is untouched; the CG window opens above it.
    assert solver_route(smooth, 64) == "newton"
    assert solver_route(smooth, 65) == "newton_cg"
    assert solver_route(smooth, 1024) == "newton_cg"
    assert solver_route(smooth, 1025) == "vmapped"
    assert newton_cg_max_dim() == 1024
    # row_split placement still wins.
    assert solver_route(smooth, 200, row_split=True) == "row_split"
    # L1 problems keep their orthant solver at every dim.
    l1 = ProblemConfig(
        optimizer="owlqn",
        regularization=RegularizationContext("l1", 0.5),
    )
    assert solver_route(l1, 200) == "vmapped"
    # The gate and the cap are env-tunable.
    with _env(PHOTON_SOLVE_NEWTON_CG="off"):
        assert solver_route(smooth, 200) == "vmapped"
    with _env(PHOTON_NEWTON_CG_MAX_DIM="128"):
        assert solver_route(smooth, 129) == "vmapped"
        assert solver_route(smooth, 128) == "newton_cg"
    # An explicitly requested newton_cg problem routes there at ANY dim.
    explicit = _problem(optimizer="newton_cg")
    assert solver_route(explicit, 8) == "newton_cg"
    assert solver_route(explicit, 5000) == "newton_cg"


def test_registry_exposes_newton_cg():
    from photon_tpu.core.optimizers.newton_cg import newton_cg as fn

    assert get_optimizer("newton_cg") is fn
    assert get_optimizer("newton-cg") is fn
    # ProblemConfig validates through the registry.
    assert _problem(optimizer="newton_cg").optimizer == "newton_cg"
    with pytest.raises(KeyError):
        get_optimizer("newton_gc")


# ---------------------------------------------------------------------------
# HVP machinery
# ---------------------------------------------------------------------------


def _fixed_batches(n=30, d=7, k=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    offs = rng.standard_normal(n).astype(np.float32) * 0.1
    w8 = (0.5 + rng.random(n)).astype(np.float32)
    dense = DenseBatch(jnp.asarray(x), jnp.asarray(y), jnp.asarray(offs),
                       jnp.asarray(w8))
    ids = rng.integers(0, d, (n, k))
    vals = rng.standard_normal((n, k)).astype(np.float32)
    sparse = SparseBatch(jnp.asarray(ids), jnp.asarray(vals),
                         jnp.asarray(y), jnp.asarray(offs), jnp.asarray(w8))
    return dense, sparse


@pytest.mark.parametrize("task", [
    "logistic_regression", "linear_regression", "poisson_regression",
])
def test_hessian_vector_product_matches_dense_hessian(task):
    """The matrix-free ``Xᵀ(D·(X v)) + λ₂ v`` agrees with an explicit
    ``H @ v`` on dense AND sparse batches — the identity the whole CG
    route rests on."""
    rng = np.random.default_rng(1)
    obj = GlmObjective.create(task, RegularizationContext("l2", 0.7))
    for batch in _fixed_batches():
        d = 7
        w = jnp.asarray(rng.standard_normal(d).astype(np.float32) * 0.3)
        v = jnp.asarray(rng.standard_normal(d).astype(np.float32))
        hv = obj.hessian_vector_product(w, v, batch)
        want = obj.hessian_matrix(w, batch) @ v
        np.testing.assert_allclose(np.asarray(hv), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)
        # The operator form reuses one precomputed D(w) across products.
        op = obj.hvp_operator(w, batch)
        np.testing.assert_allclose(np.asarray(op(v)), np.asarray(hv),
                                   atol=0, rtol=0)


def test_hvp_normalized_objective_falls_back_exactly():
    """Normalized objectives route through jvp-of-gradient (the fast
    algebra would be silently half-normalized) — still matrix-free, still
    exact vs the dense normalized Hessian."""
    from photon_tpu.core.normalization import NormalizationContext

    rng = np.random.default_rng(2)
    dense, _ = _fixed_batches()
    d = 7
    norm = NormalizationContext(
        factors=jnp.asarray(0.5 + rng.random(d).astype(np.float32)),
        shifts=jnp.asarray(rng.standard_normal(d).astype(np.float32) * 0.2),
    )
    obj = GlmObjective.create(
        "logistic_regression", RegularizationContext("l2", 0.3),
        normalization=norm,
    )
    w = jnp.asarray(rng.standard_normal(d).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.standard_normal(d).astype(np.float32))
    hv = obj.hessian_vector_product(w, v, dense)
    want = obj.hessian_matrix(w, dense) @ v
    np.testing.assert_allclose(np.asarray(hv), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Solver behavior
# ---------------------------------------------------------------------------


def test_negative_curvature_falls_back_to_steepest_descent():
    """On a concave objective every curvature probe is negative: CG must
    bail to the (preconditioned) steepest-descent direction and the Armijo
    search must still make damped, finite progress — never a NaN step."""
    def fun(w):
        v = -0.5 * jnp.dot(w, w)
        return v, -w

    w0 = jnp.asarray([1.0, -2.0, 0.5])
    cfg = OptimizerConfig(max_iterations=5, tolerance=0.0,
                          gradient_tolerance=1e-12)
    res = newton_cg(fun, w0, cfg)
    assert bool(jnp.all(jnp.isfinite(res.w)))
    assert float(res.value) < float(fun(w0)[0])  # descent happened
    assert not bool(res.converged)  # unbounded below: ran out of iters
    assert int(res.cg_iterations) >= 1


def test_newton_cg_core_matches_dense_newton_core():
    """Same fun, same config: the CG solver lands where the dense-Cholesky
    solver lands (both polish past the f32 value stall)."""
    from photon_tpu.core.optimizers import newton

    rng = np.random.default_rng(4)
    dense, _ = _fixed_batches(n=50)
    obj = GlmObjective.create(
        "logistic_regression", RegularizationContext("l2", 1.0)
    )
    fun = lambda w: obj.value_and_grad(w, dense)  # noqa: E731
    cfg = OptimizerConfig(max_iterations=100, tolerance=0.0,
                          gradient_tolerance=1e-8)
    w0 = jnp.zeros(7)
    res_cg = newton_cg(
        fun, w0, cfg,
        hvp_at=lambda w: obj.hvp_operator(w, dense),
        diag=lambda w: obj.hessian_diagonal(w, dense),
    )
    res_dn = newton(fun, w0, cfg, hess=lambda w: obj.hessian_matrix(w, dense))
    np.testing.assert_allclose(np.asarray(res_cg.w), np.asarray(res_dn.w),
                               atol=1e-5, rtol=0)
    assert bool(res_cg.converged)


# ---------------------------------------------------------------------------
# Route parity: CG vs dense Newton (dims <= 64), means AND variances
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("task", [
    "logistic_regression", "linear_regression", "poisson_regression",
])
@pytest.mark.parametrize("projection,kw", [
    ("none", {}),
    ("index_map", {}),
    ("random", {"projected_dim": 3}),
])
def test_cg_parity_vs_dense_newton(task, projection, kw):
    data = _dataset(dim=6)
    config = _config(_problem(variance="simple"), projection=projection, **kw)
    _, cg_model, _, cg_routes = _train(data, config, task, **_FORCE_CG)
    _, dn_model, _, dn_routes = _train(data, config, task)
    assert all(r == "newton_cg" for r in cg_routes), cg_routes
    assert all(r == "newton" for r in dn_routes), dn_routes
    np.testing.assert_allclose(
        np.asarray(cg_model.table), np.asarray(dn_model.table),
        atol=1e-5, rtol=0,
    )
    np.testing.assert_allclose(
        np.asarray(cg_model.variances), np.asarray(dn_model.variances),
        atol=1e-5, rtol=0,
    )


def test_cg_parity_full_variance():
    """FULL variances ride the same ``_compute_variances`` formula, so the
    CG route's diag(H⁻¹) matches the dense route's ≤1e-5 too."""
    data = _dataset()
    config = _config(_problem(variance="full"))
    _, cg_model, _, _ = _train(data, config, **_FORCE_CG)
    _, dn_model, _, _ = _train(data, config)
    np.testing.assert_allclose(
        np.asarray(cg_model.variances), np.asarray(dn_model.variances),
        atol=1e-5, rtol=0,
    )


def test_newton_cg_high_dim_matches_f64_ground_truth():
    """The lifted-ceiling accuracy claim: at d=256 — past anything the
    dense route ever solved — the CG path lands ≤1e-5 from the true
    optimum (f64 numpy Newton run to 1e-14)."""
    data = _dataset(n_entities=10, rows_mean=24, dim=256, seed=9)
    _, model, stats, routes = _train(data, _config(), **_FORCE_CG)
    assert all(r == "newton_cg" for r in routes)
    assert stats["cg_iters"] > 0
    table = np.asarray(model.table)
    raw_x = data.shards["per_entity"].x.astype(np.float64)
    ids = data.id_columns["userId"]
    for e in range(model.num_entities):
        rows = ids == model.keys[e]
        xe = raw_x[rows]
        ye = data.label[rows].astype(np.float64)
        w = np.zeros(256)
        for _ in range(200):
            p = 1.0 / (1.0 + np.exp(-(xe @ w)))
            g = xe.T @ (p - ye) + w
            h = (xe * (p * (1 - p))[:, None]).T @ xe + np.eye(256)
            step = np.linalg.solve(h, -g)
            w += step
            if np.abs(step).max() < 1e-14:
                break
        np.testing.assert_allclose(table[e], w, atol=1e-5, rtol=0)


def test_nan_quarantine_preserved_through_newton_cg_route():
    from photon_tpu.fault.injection import FaultPlan, set_plan

    data = _dataset()
    with _env(**_FORCE_CG):
        coord = RandomEffectCoordinate(
            data, _config(), "logistic_regression"
        )
        assert all(r == "newton_cg" for r in coord._bin_routes())
        coord.fault_name = "re0"
        set_plan(FaultPlan.parse("solve:nan:coord=re0"))
        try:
            model, stats = coord.train(
                np.zeros(data.num_examples, np.float32)
            )
        finally:
            set_plan(None)
    table = np.asarray(model.table)
    assert np.isfinite(table).all()
    assert stats["quarantined"] == 1
    poisoned = int(coord.device_data.device_buckets[0]["entity_index"][0])
    assert np.all(table[poisoned] == 0.0)
    assert np.abs(table).sum() > 0
    assert stats["converged"] <= stats["entities"] - 1


# ---------------------------------------------------------------------------
# The memory claim: no [B, d, d] ever materializes
# ---------------------------------------------------------------------------


def _max_intermediate_elems(jaxpr) -> int:
    """Largest array any equation of ``jaxpr`` (recursively, through
    scan/while/cond sub-jaxprs) produces, in elements."""
    def sub_jaxprs(p):
        out = []
        if hasattr(p, "jaxpr"):  # ClosedJaxpr
            out.append(p.jaxpr)
        elif hasattr(p, "eqns"):  # Jaxpr
            out.append(p)
        elif isinstance(p, (list, tuple)):
            for q in p:
                out.extend(sub_jaxprs(q))
        return out

    best = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", ())
            best = max(best, int(np.prod(shape, dtype=np.int64)))
        for p in eqn.params.values():
            for sub in sub_jaxprs(p):
                best = max(best, _max_intermediate_elems(sub))
    return best


def test_newton_cg_never_materializes_dense_hessians():
    """ISSUE 14 acceptance: the traced Newton-CG program contains NO
    ``[B, d, d]`` intermediate — its peak array is O(B·d·R) (the batch
    itself) — while the dense-Newton program provably does."""
    import functools

    from photon_tpu.game.batched_solve import (
        _run_newton_cg_fit,
        _run_newton_fit,
    )

    rng = np.random.default_rng(6)
    B, R, d = 24, 4, 96
    obj = GlmObjective.create(
        "logistic_regression", RegularizationContext("l2", 1.0)
    )
    batch = DenseBatch(
        jnp.asarray(rng.standard_normal((B, R, d)).astype(np.float32)),
        jnp.asarray((rng.random((B, R)) < 0.5).astype(np.float32)),
        jnp.zeros((B, R), jnp.float32),
        jnp.ones((B, R), jnp.float32),
    )
    w0 = jnp.zeros((B, d), jnp.float32)
    cfg = OptimizerConfig(max_iterations=50)

    def trace(run_fit):
        fn = jax.vmap(
            functools.partial(run_fit, cfg=cfg, variance="none"),
            in_axes=(None, 0, 0),
        )
        return jax.make_jaxpr(fn)(obj, batch, w0).jaxpr

    cg_peak = _max_intermediate_elems(trace(_run_newton_cg_fit))
    dense_peak = _max_intermediate_elems(trace(_run_newton_fit))
    # The dense route materializes the [B, d, d] block ...
    assert dense_peak >= B * d * d
    # ... the CG route's peak stays O(B·d): bounded by the batch features
    # plus a few coefficient-sized vectors per lane, nowhere near B·d·d.
    assert cg_peak <= max(B * R * d, 8 * B * d)
    assert cg_peak * 4 <= B * d * d


# ---------------------------------------------------------------------------
# Telemetry: cg_iters histogram + routed-entities counter
# ---------------------------------------------------------------------------


def test_cg_iters_flow_into_stats_and_histogram():
    data = _dataset(dim=6)
    session = TelemetrySession("t-cg-iters")
    _, _, stats, routes = _train(
        data, _config(), telemetry=session, **_FORCE_CG
    )
    assert all(r == "newton_cg" for r in routes)
    resolved = stats.resolve()
    assert resolved["cg_iters"] > 0
    assert resolved["entities"] == 40
    # Every entity went through a CG bin here, so the mean denominator
    # (cg_entities — CG-routed entities only, the mixed-route guard)
    # equals the coordinate's entity count.
    assert resolved["cg_entities"] == 40
    # The descent boundary drain records the per-CG-entity mean into the
    # solves.cg_iters histogram.
    from photon_tpu.game.descent import _record_coordinate_info

    _record_coordinate_info(session, "per_entity", resolved)
    snap = session.registry.snapshot()
    hists = [h for h in snap["histograms"] if h["name"] == "solves.cg_iters"]
    assert len(hists) == 1
    want_mean = resolved["cg_iters"] / resolved["cg_entities"]
    assert hists[0]["count"] == 1
    assert abs(hists[0]["mean"] - want_mean) < 1e-9
    # A mixed-route stats dict must NOT dilute the mean with non-CG
    # entities: the denominator is the CG bins' own count.
    mixed = TelemetrySession("t-cg-iters-mixed")
    _record_coordinate_info(
        mixed, "mixed",
        {"entities": 1000, "converged": 1000, "iterations_max": 5,
         "quarantined": 0, "cg_iters": 500, "cg_entities": 10},
    )
    hist = [h for h in mixed.registry.snapshot()["histograms"]
            if h["name"] == "solves.cg_iters"][0]
    assert abs(hist["mean"] - 50.0) < 1e-9
    # Non-CG routes contribute no observation.
    _, _, dn_stats, _ = _train(data, _config())
    assert dn_stats["cg_iters"] == 0 and dn_stats["cg_entities"] == 0


def test_routed_entities_counter_per_route():
    """ISSUE 14 satellite: ``solves.routed{route}`` counts the live
    entities each route received — a downgraded bin is visible, not
    inferred."""
    data = _dataset(dim=6)
    session = TelemetrySession("t-routed")
    _train(data, _config(), telemetry=session, **_FORCE_CG)

    def routed(session, route):
        return sum(
            c["value"] for c in session.registry.snapshot()["counters"]
            if c["name"] == "solves.routed"
            and c["labels"]["route"] == route
        )

    assert routed(session, "newton_cg") == 40
    assert routed(session, "vmapped") == 0
    # The downgrade case: over-cap dims fall back to vmapped, and the
    # counter says so.
    session2 = TelemetrySession("t-routed-2")
    _train(
        data, _config(), telemetry=session2,
        PHOTON_SOLVE_NEWTON="off", PHOTON_SOLVE_NEWTON_CG="off",
    )
    assert routed(session2, "vmapped") == 40
    assert routed(session2, "newton_cg") == 0


def test_factorization_counter_follows_the_solve_dim():
    """ISSUE 28 satellite: ``solves.factorization{coordinate,kind}`` counts
    each ``newton`` bin's live entities once, under the form its
    factor-and-solve takes at the bin's static solve dim (``lanes`` up to
    ``LANES_MAX_DIM``, ``xla`` above), and the run report's "Entity solves"
    section shows it.  Other routes factor nothing and count nothing."""
    from photon_tpu.core.optimizers.newton import LANES_MAX_DIM
    from photon_tpu.telemetry.report import render_markdown

    def forms(session):
        return {
            (c["labels"]["coordinate"], c["labels"]["kind"]): c["value"]
            for c in session.registry.snapshot()["counters"]
            if c["name"] == "solves.factorization"
        }

    small = TelemetrySession("t-form-lanes")
    data = _dataset(dim=6)
    coord, _, _, routes = _train(data, _config(), telemetry=small)
    assert set(routes) == {"newton"} and len(routes) > 1
    assert forms(small) == {("per_entity", "lanes"): 40}
    # A second descent iteration over the same layout does not count again.
    coord.train(np.zeros(data.num_examples, np.float32))
    assert forms(small) == {("per_entity", "lanes"): 40}

    wide = TelemetrySession("t-form-xla")
    dim = LANES_MAX_DIM + 4
    _, _, _, routes = _train(_dataset(dim=dim), _config(), telemetry=wide)
    assert set(routes) == {"newton"}
    assert forms(wide) == {("per_entity", "xla"): 40}

    cg = TelemetrySession("t-form-cg")
    _train(_dataset(dim=6), _config(), telemetry=cg, **_FORCE_CG)
    assert forms(cg) == {}

    text = render_markdown({
        "driver": "t", "run_id": "r", "status": "ok", "duration_s": 1.0,
        "metrics": small.registry.snapshot(),
    })
    section = text[text.index("## Entity solves"):]
    assert "| coordinate | factorization | live entities |" in section
    assert "| per_entity | lanes | 40 |" in section


# ---------------------------------------------------------------------------
# Explicit newton_cg as a first-class optimizer (fixed effects too)
# ---------------------------------------------------------------------------


def test_explicit_newton_cg_problem_solves_fixed_effect():
    dense, _ = _fixed_batches(n=60)
    obj = GlmObjective.create(
        "logistic_regression", RegularizationContext("l2", 1.0)
    )
    cfg = _problem(optimizer="newton_cg")
    problem = GlmOptimizationProblem(obj, cfg)
    coefficients, result = problem.run(dense, dim=7)
    base = GlmOptimizationProblem(obj, _problem())
    want, _ = base.run(dense, dim=7)
    # Cross-solver agreement at the f32 floor; newton_cg itself converges.
    np.testing.assert_allclose(
        np.asarray(coefficients.means), np.asarray(want.means),
        atol=5e-3, rtol=0,
    )
    assert bool(result.converged)
    assert int(result.cg_iterations) > 0


# -- TRON through the precomputed-curvature operator (ISSUE 15 satellite) ----

def test_tron_hvp_operator_route_matches_per_call_hvp():
    """`tron(hvp_at=...)` (the hvp_operator closure — margins/D(w) once
    per outer iteration) matches the legacy per-call `hvp` route and the
    derived jvp-of-grad default ≤1e-6, directly and through the cached
    GAME solver path."""
    import jax.numpy as jnp

    from photon_tpu.core.optimizers.tron import tron
    from photon_tpu.core.problem import (
        GlmOptimizationProblem,
        hvp_at_for,
    )
    from photon_tpu.data.synthetic import make_glm_data

    batch, _ = make_glm_data(300, 10, task="logistic_regression", seed=9)
    objective = GlmObjective.create(
        "logistic_regression", RegularizationContext("l2", 0.5)
    )
    fun = lambda w: objective.value_and_grad(w, batch)  # noqa: E731
    w0 = jnp.zeros(10)
    cfg = OptimizerConfig(max_iterations=30)
    legacy = tron(
        fun, w0, cfg,
        hvp=lambda w, v: objective.hessian_vector(w, v, batch),
    )
    operator = tron(fun, w0, cfg, hvp_at=hvp_at_for(objective, batch))
    derived = tron(fun, w0, cfg)
    assert float(jnp.abs(legacy.w - operator.w).max()) <= 1e-6
    assert float(jnp.abs(legacy.w - derived.w).max()) <= 1e-6
    # The problem route (what GAME coordinates run) wires hvp_at now.
    problem = GlmOptimizationProblem(
        objective,
        ProblemConfig(optimizer="tron", optimizer_config=cfg),
    )
    coefficients, _result = problem.run(batch, None, dim=10)
    assert float(jnp.abs(coefficients.means - operator.w).max()) <= 1e-6


def test_tron_vmapped_entity_route_unchanged():
    """The vmapped per-entity TRON route (GAME random effects): the
    operator wiring (`hvp_at`, what `_run_fit` passes on this route)
    produces the same per-lane solutions as the legacy per-call `hvp`
    wiring under the same vmap — the rewire changes where the curvature is
    built, not what any entity converges to."""
    import jax
    import jax.numpy as jnp

    from photon_tpu.core.optimizers.tron import tron
    from photon_tpu.core.problem import cached_solver, hvp_at_for
    from photon_tpu.data.batch import DenseBatch
    from photon_tpu.data.synthetic import make_glm_data

    objective = GlmObjective.create(
        "logistic_regression", RegularizationContext("l2", 1.0)
    )
    cfg = OptimizerConfig(max_iterations=25)
    batches = []
    for seed in range(4):
        b, _ = make_glm_data(16, 6, task="logistic_regression", seed=seed)
        batches.append(b)
    stacked = DenseBatch(
        jnp.stack([b.x for b in batches]),
        jnp.stack([b.label for b in batches]),
        jnp.stack([b.offset for b in batches]),
        jnp.stack([b.weight for b in batches]),
    )
    w0 = jnp.zeros((4, 6))

    def legacy_lane(batch, w):
        fun = lambda u: objective.value_and_grad(u, batch)  # noqa: E731
        return tron(
            fun, w, cfg,
            hvp=lambda ww, v: objective.hessian_vector(ww, v, batch),
        ).w

    def operator_lane(batch, w):
        fun = lambda u: objective.value_and_grad(u, batch)  # noqa: E731
        return tron(fun, w, cfg, hvp_at=hvp_at_for(objective, batch)).w

    legacy = jax.jit(jax.vmap(legacy_lane))(stacked, w0)
    operator = jax.jit(jax.vmap(operator_lane))(stacked, w0)
    assert float(jnp.abs(legacy - operator).max()) <= 1e-6
    # And the cached GAME solver route (the production wiring) matches: the
    # entity lanes do not carry TRON's margins (ISSUE 41).
    solver = cached_solver("tron", cfg, "none", vmapped=True)
    coeff, result = solver(objective, stacked, w0)
    assert float(jnp.abs(coeff.means - operator).max()) <= 1e-6
    assert int(jnp.sum(result.margin_passes_spared)) == 0
