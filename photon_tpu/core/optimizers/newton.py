"""Damped Newton with a batched Cholesky solve — the small-dim direct method.

The per-entity GAME solves are tiny strongly-convex GLMs (``dim`` in the
tens): exactly the regime where a direct second-order method beats the
quasi-Newton loops — Snap ML (PAPERS.md, 1803.06333) solves the same
hierarchical-GLM subproblems with direct second-order methods, and "Large
Scale Distributed Linear Algebra With TPUs" (PAPERS.md, 2112.09017) grounds
the padded batched-factorization shape this vmaps into: under ``jax.vmap``
the Hessians stack to ``[B, dim, dim]`` and the factorization becomes one
batched Cholesky (``cho_factor``/``cho_solve``) per Newton iteration.

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
            chol = jax.scipy.linalg.cho_factor(h)
            return -jax.scipy.linalg.cho_solve(chol, g)

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
