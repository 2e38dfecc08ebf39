"""Matrix-free damped Newton with a preconditioned-CG inner solve.

The batched Cholesky Newton (``newton.py``) materializes a dense
``[dim, dim]`` Hessian per iteration — under ``jax.vmap`` that is a
``[B, dim, dim]`` block whose memory and factorization cost cap the GAME
entity solves at ``PHOTON_NEWTON_MAX_DIM`` (ISSUE 14).  This solver keeps
the SAME outer structure (masked ``lax.while_loop`` damped Newton, the
shared Armijo backtracking, the guarded full-step gradient polish) but
computes each Newton step by conjugate gradients on Hessian-VECTOR
products: for GLM objectives ``H v = Xᵀ(D(w)·(X v)) + λ₂ v`` — two sparse
matvecs, never a matrix (Snap ML, PAPERS.md 1803.06333, solves the same
hierarchical per-partition GLM subproblems second-order; the dense
factorizations this route avoids are exactly the shapes 2112.09017
distributes when a single one no longer fits).

Design points:

- **Curvature operator per outer iteration** — ``hvp_at(w)`` returns a
  closure evaluating ``H(w)·v``; the GLM objective's ``hvp_operator``
  precomputes the per-row curvature ``D(w)`` once, so each CG iteration
  costs two matvecs, not a margin recomputation.
- **Jacobi preconditioner** — ``diag(w)`` (the cheap
  ``objective.hessian_diagonal``) scales the CG residual; for the skewed
  per-entity feature scales of random-effect bins this is the difference
  between O(rank) and O(κ) inner iterations.
- **Eisenstat-Walker forcing** — the inner tolerance is per-lane adaptive,
  ``η_k = min(0.5, sqrt(‖g_k‖/‖g_0‖))``: early outer iterations solve the
  Newton system loosely (a handful of CG steps), late ones tightly enough
  to keep the quadratic contraction — the classic inexact-Newton rule.
- **Negative-curvature fallback** — GLM+L2 Hessians are PD, but a flat or
  injected direction with ``dᵀHd ≤ 0`` stops CG at the current iterate;
  a first-iteration hit falls back to the preconditioned steepest-descent
  direction, which the Armijo search then damps (same guard philosophy as
  ``newton.py``'s non-PD Cholesky fallback).

Same contract as the other optimizers: every state update is masked on
``active`` so converged lanes FREEZE under vmap, tolerance semantics match
``base.check_convergence``, and the result's ``cg_iterations`` field
carries the total inner-CG work for the ``solves.cg_iters`` telemetry.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

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

# Floor on the Jacobi preconditioner diagonal: keeps the scaling defined on
# flat directions (an entity whose rows never touch a feature) without
# moving the preconditioned system for any live curvature.
_DIAG_FLOOR = 1e-12
# Relative CG tolerance of the two polish steps: loose enough to stay
# O(rank) iterations, tight enough that the Newton contraction still lands
# ~1e-6 from the optimum after two steps (see the polish note below).
_POLISH_ETA = 1e-2


class _CGState(NamedTuple):
    p: Array
    r: Array
    z: Array
    dvec: Array
    rz: Array
    it: Array
    done: Array


def _pcg(hv, g: Array, mdiag: Array, tol: Array, max_cg: int, active):
    """Jacobi-preconditioned CG on ``H p = -g``; returns ``(p, iters)``.

    Stops on ``‖r‖ ≤ tol``, ``max_cg`` iterations, or negative curvature
    (``dᵀHd ≤ 0`` — the current iterate is returned; on the FIRST
    iteration that is the preconditioned steepest-descent direction, the
    documented fallback).  Inert when ``active`` is False (vmap freeze).
    """
    b = -g
    z0 = b / mdiag
    rz0 = jnp.dot(b, z0)
    init = _CGState(
        p=jnp.zeros_like(g), r=b, z=z0, dvec=z0, rz=rz0,
        it=jnp.asarray(0, jnp.int32),
        done=~active | (jnp.linalg.norm(b) <= tol) | ~jnp.isfinite(rz0),
    )

    def cond(c: _CGState):
        return ~c.done

    def body(c: _CGState):
        hd = hv(c.dvec)
        dhd = jnp.dot(c.dvec, hd)
        neg = dhd <= 0.0
        alpha = c.rz / jnp.where(neg, 1.0, dhd)
        p_new = c.p + alpha * c.dvec
        r_new = c.r - alpha * hd
        z_new = r_new / mdiag
        rz_new = jnp.dot(r_new, z_new)
        beta = rz_new / jnp.where(c.rz > 0.0, c.rz, 1.0)
        d_new = z_new + beta * c.dvec
        # Negative curvature keeps the best iterate so far: the current p,
        # or the preconditioned gradient on a first-iteration hit (c.z is
        # still z0 there) — always a descent direction for the outer
        # Armijo search to damp.
        p_out = jnp.where(
            neg, jnp.where(c.it == 0, c.z, c.p), p_new
        )
        it_new = c.it + 1
        done_new = (
            neg
            | (jnp.linalg.norm(r_new) <= tol)
            | (it_new >= max_cg)
            | ~jnp.isfinite(rz_new)
        )
        nxt = _CGState(
            p=p_out, r=r_new, z=z_new, dvec=d_new, rz=rz_new,
            it=it_new, done=done_new,
        )
        return tree_where(c.done, c, nxt)

    final = lax.while_loop(cond, body, init)
    return final.p, final.it


def newton_cg(
    fun: Callable[[Array], tuple[Array, Array]],
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
    hvp_at: Optional[Callable[[Array], Callable[[Array], Array]]] = None,
    diag: Optional[Callable[[Array], Array]] = None,
) -> OptimizerResult:
    """Minimize ``fun`` (returning (value, grad)) by inexact Newton-CG.

    ``hvp_at(w)`` returns the curvature operator ``v -> H(w)·v`` (for GLM
    objectives, ``objective.hvp_operator(w, batch)`` — the per-row
    curvature is precomputed once per outer iteration); if None it is
    derived from ``fun`` by jvp of the gradient (exact, matrix-free).
    ``diag(w)`` supplies the Jacobi-preconditioner diagonal (for GLMs,
    ``objective.hessian_diagonal``); if None the identity is used.
    ``config.cg_max_iterations`` bounds the inner loop (0 → ``min(dim,
    256)``).  Pure JAX: safe under jit and vmap (the GAME batched
    large-dim entity solves).
    """
    if hvp_at is None:
        def hvp_at(w):  # noqa: ANN001 — jvp-of-grad fallback
            return lambda v: jax.jvp(lambda u: fun(u)[1], (w,), (v,))[1]
    if diag is None:
        def diag(w):  # noqa: ANN001
            return jnp.ones_like(w)

    d = w0.shape[0]
    max_cg = (
        config.cg_max_iterations
        if config.cg_max_iterations > 0
        else min(int(d), 256)
    )
    f0, g0 = fun(w0)
    gnorm0 = jnp.linalg.norm(g0)
    conv0 = gnorm0 == 0.0
    hv0, hg0, hvalid0 = init_history(config.max_iterations, f0, gnorm0)

    class _State(NamedTuple):
        w: Array
        f: Array
        g: Array
        it: Array
        active: Array
        reason: Array
        cg: Array
        ls: Array
        hv: Array
        hg: Array
        hvalid: Array

    init = _State(
        w=w0, f=f0, g=g0,
        it=jnp.asarray(0, jnp.int32),
        active=~conv0,
        reason=jnp.where(
            conv0, ConvergenceReason.GRADIENT_TOLERANCE,
            ConvergenceReason.NOT_CONVERGED,
        ).astype(jnp.int32),
        cg=jnp.asarray(0, jnp.int32),
        ls=jnp.asarray(0, jnp.int32),
        hv=hv0, hg=hg0, hvalid=hvalid0,
    )

    def cond(s: _State):
        return s.active

    def body(s: _State):
        hv = hvp_at(s.w)
        mdiag = jnp.maximum(diag(s.w), _DIAG_FLOOR)
        gnorm = jnp.linalg.norm(s.g)
        # Eisenstat-Walker forcing term (sqrt variant): loose early, tight
        # near the optimum — superlinear outer convergence at O(rank)
        # inner iterations per step.
        eta = jnp.minimum(0.5, jnp.sqrt(gnorm / jnp.maximum(gnorm0, 1e-30)))
        step, cg_it = _pcg(hv, s.g, mdiag, eta * gnorm, max_cg, s.active)
        dir_deriv = jnp.dot(s.g, step)
        # A non-finite or non-descent CG result falls back to steepest
        # descent for this iteration (same guard as newton.py).
        bad = ~jnp.all(jnp.isfinite(step)) | (dir_deriv >= 0.0)
        step = jnp.where(bad, -s.g, step)
        dir_deriv = jnp.where(bad, -jnp.dot(s.g, s.g), dir_deriv)
        t0 = jnp.where(bad, 1.0 / jnp.maximum(gnorm, 1.0), 1.0)

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
        hv_h, hg_h, hvalid_h = record_history(
            s.hv, s.hg, s.hvalid, it_new, f_out, jnp.linalg.norm(g_out),
            s.active & ls_ok,
        )

        new = _State(
            w=w_out, f=f_out, g=g_out,
            it=it_new, active=still_active,
            reason=reason.astype(jnp.int32),
            cg=s.cg + cg_it,
            ls=s.ls + trials,
            hv=hv_h, hg=hg_h, hvalid=hvalid_h,
        )
        return tree_where(s.active, new, s)

    final = lax.while_loop(cond, body, init)

    # Full-step polish — the same contraction-on-the-f32-gradient trick as
    # newton.py (its docstring carries the full argument): the line-searched
    # loop stalls where f32 FUNCTION differences round to zero, ~1e-4 from
    # the true optimum; two guarded full Newton steps (here: CG solves at a
    # tight relative tolerance) keep contracting on the f32 GRADIENT's zero
    # and land ~1e-6 away — what the ≤1e-5 ground-truth parity pins.
    # Guarded identically: only near-steps (small relative to the iterate)
    # with finite outcomes are kept.
    def polish(carry, _):
        w, f, g, cg = carry
        hv = hvp_at(w)
        mdiag = jnp.maximum(diag(w), _DIAG_FLOOR)
        gnorm = jnp.linalg.norm(g)
        step, cg_it = _pcg(
            hv, g, mdiag, _POLISH_ETA * gnorm, max_cg, jnp.asarray(True)
        )
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
            cg + cg_it,
        ), None

    (w_out, f_out, g_out, cg_out), _ = lax.scan(
        polish, (final.w, final.f, final.g, final.cg), None, length=2
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
        cg_iterations=cg_out,
        # The initial point, every line-search trial, the two polish steps.
        evaluations=final.ls + 3,
        line_search_steps=final.ls,
    )
