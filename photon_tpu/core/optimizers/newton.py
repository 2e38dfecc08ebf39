"""Damped Newton with a direct SPD solve — the small-dim direct method.

The per-entity GAME solves are tiny strongly-convex GLMs (``dim`` in the
tens): exactly the regime where a direct second-order method beats the
quasi-Newton loops — Snap ML (PAPERS.md, 1803.06333) solves the same
hierarchical-GLM subproblems with direct second-order methods.  Under
``jax.vmap`` the Hessians stack to ``[B, dim, dim]``, and
:func:`spd_solve` factors and solves them in the form that shape wants:
up to ``LANES_MAX_DIM`` an unrolled Cholesky with the batch on the lane
axis (``[dim, dim, B]``: every intermediate is lane-dense in ``B``), above
it XLA's batched ``cho_factor``/``cho_solve`` custom call, which is also
what an unbatched call runs.  On the v5e the custom call spends 1.7 us on
each 16 x 16 matrix, 16 of 128 lanes carrying data; it was 60 % of a GAME
fit (PERF.md, PR 28).

Same contract as :func:`~photon_tpu.core.optimizers.lbfgs.lbfgs`: a single
``lax.while_loop`` machine whose state updates are all masked on an
``active`` flag, so converged lanes FREEZE under vmap while heavy entities
keep iterating (masked convergence — finished entities stop contributing
work beyond the lockstep evaluation).  Tolerance semantics, history arrays,
and convergence reasons match the shared base exactly; a fit that converges
here lands on the same optimum as the L-BFGS/TRON path (the objective is
identical), which is what the batched-vs-vmapped parity tests pin.

Robustness: the Hessian gets a tiny relative ridge before factorization
(flat directions — e.g. an entity whose rows never touch a feature — keep
the factorization defined, matching core/problem.py's full-variance
jitter), a non-finite or non-descent Newton step falls back to steepest
descent for that iteration, and an Armijo backtracking line search (shared
with L-BFGS) guards against overshoot far from the optimum (Poisson's exp
margins).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from typing import NamedTuple

from photon_tpu.core.optimizers.base import (
    ConvergenceReason,
    OptimizerConfig,
    OptimizerResult,
    check_convergence,
    init_history,
    reason_is_converged,
    record_history,
    tree_where,
)
from photon_tpu.core.optimizers.lbfgs import _backtracking_line_search

Array = jax.Array

# Relative ridge added to the Hessian diagonal before factorization: large
# enough to keep Cholesky defined on flat directions, orders of magnitude
# below any curvature that moves the solution at the 1e-5 parity tolerance.
_RIDGE = 1e-9
_POLISH_STEPS = 2  # full steps after the loop, one evaluation each
# Largest dim at which a BATCHED solve is unrolled with the batch on the
# lane axis (`_spd_solve_lanes`); above it, and unbatched, XLA's Cholesky.
# Fixed from the v5e (PERF.md, PR 28): 13,312 systems take 0.6 / 0.7 /
# 2.6 ms at d = 8 / 16 / 32 against 11.7 / 23.4 / 48.3 ms through the
# batched custom call, and the d = 32 unroll compiles in 4 s; past it
# nothing is measured, and the unroll's work and compile grow as d^3.
LANES_MAX_DIM = 32


def _spd_solve_xla(h: Array, g: Array) -> Array:
    return jax.scipy.linalg.cho_solve(jax.scipy.linalg.cho_factor(h), g)


def _spd_solve_lanes(h: Array, g: Array) -> Array:
    """``[B, d, d]``, ``[B, d]`` -> ``[B, d]``: Cholesky and both
    substitutions, unrolled over ``d`` with the batch as the minor (lane)
    axis — every intermediate is a ``[rows, B]`` or ``[rows, columns, B]``
    slab of float32 elementwise work, one ``rsqrt`` and one divide a
    column.  Only the lower triangle of ``h`` is used.  A matrix that is not
    positive definite gives a non-finite solution in its own lane.

    Right-looking: ``t`` holds the columns still to factor, ``g`` riding as
    one more row under them, so the forward substitution is the same slab
    arithmetic as the factor.  ``raw[j]`` is column ``j`` before its
    scaling, ``L[j, j] * (L[j:, j], y[j])``, and the back substitution
    solves ``raw[j][0] x[j] + sum_k raw[j][k] x[k] = raw[j][-1]``: linear
    in entries that each carry one rounding of ``h``'s scale, with no pivot
    computed twice.  (A pivot's reciprocal used again in the substitutions
    is not safe under XLA: a fusion recomputes the cancelling sum behind it
    with its own rounding, and at cond 1e4 a 1e-5 mismatch costs 50 x the
    backward error.)"""
    d = h.shape[-1]
    t = lax.concatenate(
        [jnp.moveaxis(h, 0, -1), jnp.moveaxis(g, 0, -1)[None]], 0
    )  # [d + 1, d, B]
    # ``lax`` calls, not operators: the unroll is traced three times a bin
    # program, and an operator on a tracer costs five times its ``lax`` op.
    raw = []
    for j in range(d):
        c = lax.index_in_dim(t, 0, axis=1, keepdims=False)
        raw.append(c)  # rows j.., then g's: [d - j + 1, B]
        if j + 1 < d:
            inv = lax.rsqrt(lax.slice_in_dim(c, 0, 1))
            col = lax.mul(lax.slice_in_dim(c, 1, None), inv)
            t = lax.sub(
                lax.slice(t, (1, 1, 0), t.shape),
                lax.mul(
                    lax.expand_dims(col, (1,)),
                    lax.expand_dims(lax.slice_in_dim(col, 0, -1), (0,)),
                ),
            )
    nan = jnp.full_like(raw[0][:1], jnp.nan)
    x = raw[0][:0]  # x[j + 1:], so far none
    for c in reversed(raw):  # L^T x = y, each row times L[j, j]
        pivot = lax.slice_in_dim(c, 0, 1)
        rhs = lax.sub(
            lax.slice_in_dim(c, -1, None),
            jnp.sum(lax.mul(lax.slice_in_dim(c, 1, -1), x), 0, keepdims=True),
        )
        x_j = lax.div(rhs, lax.select(lax.gt(pivot, 0.0), pivot, nan))
        x = lax.concatenate([x_j, x], 0)
    return jnp.moveaxis(x, 0, -1)


def factorization_kind(dim: int) -> str:
    """Which form a batched :func:`spd_solve` takes at static ``dim``:
    ``lanes`` (unrolled, batch on the lane axis) or ``xla`` (the batched
    ``Cholesky`` custom call) — what ``solves.factorization{kind}`` counts."""
    return "lanes" if dim <= LANES_MAX_DIM else "xla"


@jax.custom_batching.custom_vmap
def spd_solve(h: Array, g: Array) -> Array:
    """``h^-1 g`` for a symmetric positive definite ``h``.  Unbatched it is
    ``cho_factor`` / ``cho_solve``; under ``vmap`` the same factor-and-solve
    runs in the form the batch shape wants (:func:`factorization_kind`)."""
    return _spd_solve_xla(h, g)


@spd_solve.def_vmap
def _spd_solve_vmap(axis_size, in_batched, h, g):
    if not in_batched[0]:
        h = jnp.broadcast_to(h, (axis_size, *h.shape))
    if not in_batched[1]:
        g = jnp.broadcast_to(g, (axis_size, *g.shape))
    if factorization_kind(h.shape[-1]) == "lanes":
        return _spd_solve_lanes(h, g), True
    return jax.vmap(_spd_solve_xla)(h, g), True


class _State(NamedTuple):
    w: Array
    f: Array
    g: Array
    it: Array
    ls: Array  # line-search trials so far (one objective evaluation each)
    active: Array
    reason: Array
    hv: Array
    hg: Array
    hvalid: Array


def newton(
    fun: Callable[[Array], tuple[Array, Array]],
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
    hess: Callable[[Array], Array] | None = None,
) -> OptimizerResult:
    """Minimize ``fun`` (returning (value, grad)) with full Newton steps.

    ``hess(w) -> [d, d]`` supplies the dense Hessian (for GLM objectives,
    ``objective.hessian_matrix``); if None it is derived from ``fun`` by
    forward-mode differentiation of the gradient (exact, d jvp passes).
    Pure JAX: safe under jit and vmap (the GAME batched entity solves).
    """
    if hess is None:
        def hess(w):  # noqa: ANN001
            return jax.jacfwd(lambda u: fun(u)[1])(w)

    d = w0.shape[0]
    eye = jnp.eye(d, dtype=w0.dtype)
    f0, g0 = fun(w0)
    gnorm0 = jnp.linalg.norm(g0)
    conv0 = gnorm0 == 0.0
    hv, hg, hvalid = init_history(config.max_iterations, f0, gnorm0)

    init = _State(
        w=w0, f=f0, g=g0,
        it=jnp.asarray(0, jnp.int32),
        ls=jnp.asarray(0, jnp.int32),
        active=~conv0,
        reason=jnp.where(
            conv0, ConvergenceReason.GRADIENT_TOLERANCE,
            ConvergenceReason.NOT_CONVERGED,
        ).astype(jnp.int32),
        hv=hv, hg=hg, hvalid=hvalid,
    )

    def cond(s: _State):
        return s.active

    def solve(w, g):
        """The Newton step ``-(H(w) + ridge I)^-1 g``."""
        with jax.named_scope("newton/hessian"):
            h = hess(w)
            ridge = _RIDGE * (1.0 + jnp.max(jnp.abs(jnp.diagonal(h))))
            h = h + ridge * eye
        with jax.named_scope("newton/cholesky"):
            return -spd_solve(h, g)

    def body(s: _State):
        step = solve(s.w, s.g)
        with jax.named_scope("newton/step"):
            dir_deriv = jnp.dot(s.g, step)
            # A failed factorization (non-PD curvature -> NaN) or a
            # non-descent step falls back to steepest descent for this
            # iteration.
            bad = ~jnp.all(jnp.isfinite(step)) | (dir_deriv >= 0.0)
            step = jnp.where(bad, -s.g, step)
            dir_deriv = jnp.where(bad, -jnp.dot(s.g, s.g), dir_deriv)
            t0 = jnp.where(
                bad, 1.0 / jnp.maximum(jnp.linalg.norm(s.g), 1.0), 1.0
            )

        t, f_new, g_new, ls_ok, trials = _backtracking_line_search(
            fun, s.w, step, s.f, dir_deriv, t0, config.max_line_search,
            s.active, scope="newton/gradient",
        )
        w_new = s.w + t * step

        gnorm_new = jnp.linalg.norm(g_new)
        converged, reason = check_convergence(
            f_new, s.f, gnorm_new, gnorm0, config
        )
        stop_ls = ~ls_ok
        reason = jnp.where(
            stop_ls, ConvergenceReason.OBJECTIVE_NOT_IMPROVING, reason
        )
        it_new = s.it + 1
        hit_max = it_new >= config.max_iterations
        reason = jnp.where(
            hit_max & ~(converged | stop_ls),
            ConvergenceReason.MAX_ITERATIONS, reason,
        )
        still_active = s.active & ~(converged | stop_ls | hit_max)

        # On line-search failure keep the old iterate (matching lbfgs).
        w_out = jnp.where(ls_ok, w_new, s.w)
        f_out = jnp.where(ls_ok, f_new, s.f)
        g_out = jnp.where(ls_ok, g_new, s.g)
        hv, hg, hvalid = record_history(
            s.hv, s.hg, s.hvalid, it_new, f_out, jnp.linalg.norm(g_out),
            s.active & ls_ok,
        )

        new = _State(
            w=w_out, f=f_out, g=g_out,
            it=it_new, ls=s.ls + trials, active=still_active,
            reason=reason.astype(jnp.int32),
            hv=hv, hg=hg, hvalid=hvalid,
        )
        return tree_where(s.active, new, s)

    final = lax.while_loop(cond, body, init)

    # Full-step polish: the line-searched loop above stops where f32
    # FUNCTION differences round to zero — a basin ~1e-4 wide around the
    # true optimum (any value-criterion f32 solver stalls there, the seed's
    # L-BFGS included).  The Newton map ``w -> w - H(w)^{-1} g(w)`` keeps
    # contracting on the f32 GRADIENT's zero well past that, so two
    # unconditional full steps land within ~1e-6 of the true optimum —
    # what makes the batched path's ≤1e-5 ground-truth parity hold.
    # Guarded: a step is only taken when it is small relative to the
    # iterate (a lane that stopped far from its optimum — max_iterations,
    # degenerate curvature — must not take an unsearched full step) and
    # the stepped point stays finite.
    def polish(carry, _):
        w, f, g = carry
        step = solve(w, g)
        near = jnp.all(jnp.isfinite(step)) & (
            jnp.linalg.norm(step)
            <= 1e-3 * jnp.maximum(jnp.linalg.norm(w), 1.0)
        )
        w_new = jnp.where(near, w + step, w)
        f_new, g_new = fun(w_new)
        keep = near & jnp.isfinite(f_new) & jnp.all(jnp.isfinite(g_new))
        return (
            jnp.where(keep, w_new, w),
            jnp.where(keep, f_new, f),
            jnp.where(keep, g_new, g),
        ), None

    (w_out, f_out, g_out), _ = lax.scan(
        polish, (final.w, final.f, final.g), None, length=_POLISH_STEPS
    )
    return OptimizerResult(
        w=w_out,
        value=f_out,
        grad_norm=jnp.linalg.norm(g_out),
        iterations=final.it,
        converged=reason_is_converged(final.reason),
        reason=final.reason,
        history_value=final.hv,
        history_grad_norm=final.hg,
        history_valid=final.hvalid,
        # The initial point, every line-search trial, the two polish steps.
        evaluations=final.ls + (1 + _POLISH_STEPS),
        line_search_steps=final.ls,
    )
