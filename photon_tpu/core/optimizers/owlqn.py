"""OWL-QN: orthant-wise limited-memory quasi-Newton for L1/elastic-net.

Rebuild of the reference's ``OWLQN`` (photon-lib .../optimization/OWLQN.scala,
wrapping ``breeze.optimize.OWLQN`` — SURVEY.md §2.1), re-expressed as a jitted
``lax.while_loop`` following Andrew & Gao (2007):

- the *pseudo-gradient* replaces the gradient of the (non-differentiable)
  L1 term,
- the L-BFGS two-loop direction (built from smooth-gradient (s, y) pairs) is
  *projected* onto the pseudo-gradient's descent orthant,
- each line-search trial point is *orthant-projected*: coordinates that cross
  zero are clamped to zero, which is what produces exact sparsity.

The smooth part of the objective (including any L2 term for elastic net) comes
from ``fun``; ``l1_weight`` is applied here, matching the reference's split
where L2 folds into the objective and L1 lives in the optimizer.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

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
from photon_tpu.core.optimizers.lbfgs import _two_loop_direction

Array = jax.Array

_ARMIJO_C1 = 1e-4
_PAIR_EPS = 1e-10


def _pseudo_gradient(w: Array, g: Array, l1: Array) -> Array:
    """Andrew & Gao eq. (4): subgradient choice minimizing the norm."""
    left = g - l1
    right = g + l1
    at_zero = jnp.where(right < 0.0, right, jnp.where(left > 0.0, left, 0.0))
    return jnp.where(w > 0.0, right, jnp.where(w < 0.0, left, at_zero))


def _project_direction(d: Array, pg: Array) -> Array:
    """Zero out components of d not aligned with the steepest-descent
    direction -pg (orthant-wise projection of the quasi-Newton direction)."""
    return jnp.where(d * pg < 0.0, d, 0.0)


def _orthant_project(w_new: Array, xi: Array) -> Array:
    """Clamp coordinates that left the chosen orthant xi to zero."""
    return jnp.where(w_new * xi > 0.0, w_new, 0.0)


class _LineSearchState(NamedTuple):
    t: Array
    w: Array
    f: Array  # smooth value at w
    g: Array  # smooth grad at w
    ok: Array  # current trial satisfies the projected Armijo test
    it: Array
    halt: Array  # stop without success


class _State(NamedTuple):
    w: Array
    f: Array  # smooth value
    g: Array  # smooth grad
    S: Array
    Y: Array
    rho: Array
    num_pairs: Array
    insert_pos: Array
    gamma: Array
    it: Array
    ls: Array  # line-search trials so far (one objective evaluation each)
    active: Array
    reason: Array
    hv: Array
    hg: Array
    hvalid: Array


def owlqn(
    fun: Callable[[Array], tuple[Array, Array]],
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
    l1_weight: float | Array = 0.0,
) -> OptimizerResult:
    """Minimize ``fun(w) + l1_weight * ||w||_1``.

    ``fun`` returns (smooth value, smooth grad).  With ``l1_weight == 0`` this
    degenerates to L-BFGS with a projected line search that never projects.
    History/tolerances are on the *total* (smooth + L1) objective, matching
    the reference's convergence semantics.
    """
    m = config.history_length
    d = w0.shape[0]
    l1 = jnp.asarray(l1_weight, w0.dtype)

    def total(w, f_smooth):
        return f_smooth + l1 * jnp.sum(jnp.abs(w))

    f0s, g0 = fun(w0)
    f0 = total(w0, f0s)
    pg0 = _pseudo_gradient(w0, g0, l1)
    gnorm0 = jnp.linalg.norm(pg0)
    conv0 = gnorm0 == 0.0
    hv, hg, hvalid = init_history(config.max_iterations, f0, gnorm0)

    init = _State(
        w=w0, f=f0s, g=g0,
        S=jnp.zeros((m, d), w0.dtype),
        Y=jnp.zeros((m, d), w0.dtype),
        rho=jnp.zeros(m, w0.dtype),
        num_pairs=jnp.asarray(0, jnp.int32),
        insert_pos=jnp.asarray(0, jnp.int32),
        gamma=jnp.asarray(1.0, w0.dtype),
        it=jnp.asarray(0, jnp.int32),
        ls=jnp.asarray(0, jnp.int32),
        active=~conv0,
        reason=jnp.where(
            conv0, ConvergenceReason.GRADIENT_TOLERANCE, ConvergenceReason.NOT_CONVERGED
        ).astype(jnp.int32),
        hv=hv, hg=hg, hvalid=hvalid,
    )

    def cond(s: _State):
        return s.active

    def body(s: _State):
        pg = _pseudo_gradient(s.w, s.g, l1)
        dvec = _two_loop_direction(
            pg, s.S, s.Y, s.rho, s.num_pairs, s.insert_pos, s.gamma, m
        )
        dvec = _project_direction(dvec, pg)
        dir_deriv = jnp.dot(pg, dvec)
        bad = dir_deriv >= 0.0
        dvec = jnp.where(bad, -pg, dvec)
        dir_deriv = jnp.where(bad, -jnp.dot(pg, pg), dir_deriv)
        # Orthant choice: sign(w), or sign(-pg) where w == 0.
        xi = jnp.where(s.w != 0.0, jnp.sign(s.w), -jnp.sign(pg))

        f_total_old = total(s.w, s.f)
        pgnorm = jnp.linalg.norm(pg)
        t0 = jnp.where(s.num_pairs == 0, 1.0 / jnp.maximum(pgnorm, 1.0), 1.0)

        def trial(t):
            w_t = _orthant_project(s.w + t * dvec, xi)
            f_s, g_s = fun(w_t)
            # Armijo on the total objective with the projected step:
            # f(w_t) <= f(w) + c1 * pg . (w_t - w)   (Andrew & Gao).
            descent = jnp.dot(pg, w_t - s.w)
            ok = (
                total(w_t, f_s) <= f_total_old + _ARMIJO_C1 * descent
            ) & jnp.isfinite(f_s)
            return w_t, f_s, g_s, ok

        w_i, f_i, g_i, ok_i = trial(t0)

        def ls_cond(ls: _LineSearchState):
            return ~(ls.ok | ls.halt)

        def ls_body(ls: _LineSearchState):
            t_new = ls.t * 0.5
            w_n, f_n, g_n, ok_n = trial(t_new)
            return _LineSearchState(
                t=t_new, w=w_n, f=f_n, g=g_n, ok=ok_n, it=ls.it + 1,
                halt=ls.it + 1 >= config.max_line_search,
            )

        ls0 = _LineSearchState(
            t=jnp.asarray(t0), w=w_i, f=f_i, g=g_i, ok=ok_i,
            it=jnp.asarray(0, jnp.int32), halt=~s.active,
        )
        ls = lax.while_loop(ls_cond, ls_body, ls0)

        svec = ls.w - s.w
        yvec = ls.g - s.g
        sy = jnp.dot(svec, yvec)
        pair_ok = ls.ok & (sy > _PAIR_EPS)
        S_new = s.S.at[s.insert_pos].set(jnp.where(pair_ok, svec, s.S[s.insert_pos]))
        Y_new = s.Y.at[s.insert_pos].set(jnp.where(pair_ok, yvec, s.Y[s.insert_pos]))
        rho_new = s.rho.at[s.insert_pos].set(
            jnp.where(pair_ok, 1.0 / jnp.where(pair_ok, sy, 1.0), s.rho[s.insert_pos])
        )
        num_pairs = jnp.where(pair_ok, jnp.minimum(s.num_pairs + 1, m), s.num_pairs)
        insert_pos = jnp.where(pair_ok, (s.insert_pos + 1) % m, s.insert_pos)
        gamma = jnp.where(pair_ok, sy / jnp.maximum(jnp.dot(yvec, yvec), 1e-30), s.gamma)

        pg_new = _pseudo_gradient(ls.w, ls.g, l1)
        pgnorm_new = jnp.linalg.norm(pg_new)
        f_total_new = total(ls.w, ls.f)
        converged, reason = check_convergence(
            f_total_new, f_total_old, pgnorm_new, gnorm0, config
        )
        stop_ls = ~ls.ok
        reason = jnp.where(stop_ls, ConvergenceReason.OBJECTIVE_NOT_IMPROVING, reason)
        it_new = s.it + 1
        hit_max = it_new >= config.max_iterations
        reason = jnp.where(
            hit_max & ~(converged | stop_ls), ConvergenceReason.MAX_ITERATIONS, reason
        )
        still_active = s.active & ~(converged | stop_ls | hit_max)

        w_out = jnp.where(ls.ok, ls.w, s.w)
        f_out = jnp.where(ls.ok, ls.f, s.f)
        g_out = jnp.where(ls.ok, ls.g, s.g)
        hv, hg, hvalid = record_history(
            s.hv, s.hg, s.hvalid, it_new,
            total(w_out, f_out), pgnorm_new, s.active & ls.ok,
        )

        new = _State(
            w=w_out, f=f_out, g=g_out,
            S=S_new, Y=Y_new, rho=rho_new,
            num_pairs=num_pairs, insert_pos=insert_pos, gamma=gamma,
            it=it_new, ls=s.ls + ls.it + 1, active=still_active,
            reason=reason.astype(jnp.int32),
            hv=hv, hg=hg, hvalid=hvalid,
        )
        return tree_where(s.active, new, s)

    final = lax.while_loop(cond, body, init)

    # Full-step polish (same graft as lbfgs.py): two unsearched steps of
    # the final quasi-Newton map, run through OWL-QN's machinery — the
    # direction is built from the PSEUDO-gradient, projected onto its
    # descent orthant, and the stepped point is orthant-projected, so
    # polish can only sharpen coordinates inside the orthant the loop
    # settled in (exact zeros stay exactly zero).  Kept per lane only if
    # the step is small relative to the iterate, everything stays
    # finite, and the pseudo-gradient norm does not grow.
    def polish(carry, _):
        w, f, g = carry
        pg = _pseudo_gradient(w, g, l1)
        step = _project_direction(
            _two_loop_direction(
                pg, final.S, final.Y, final.rho, final.num_pairs,
                final.insert_pos, final.gamma, m,
            ),
            pg,
        )
        near = jnp.all(jnp.isfinite(step)) & (
            jnp.linalg.norm(step)
            <= 1e-3 * jnp.maximum(jnp.linalg.norm(w), 1.0)
        )
        xi = jnp.where(w != 0.0, jnp.sign(w), -jnp.sign(pg))
        w_new = jnp.where(near, _orthant_project(w + step, xi), w)
        f_new, g_new = fun(w_new)
        pg_new = _pseudo_gradient(w_new, g_new, l1)
        keep = (
            near & jnp.isfinite(f_new) & jnp.all(jnp.isfinite(g_new))
            & (jnp.linalg.norm(pg_new) <= jnp.linalg.norm(pg))
        )
        return (
            jnp.where(keep, w_new, w),
            jnp.where(keep, f_new, f),
            jnp.where(keep, g_new, g),
        ), None

    (w_out, f_out, g_out), _ = lax.scan(
        polish, (final.w, final.f, final.g), None, length=2
    )
    pg_final = _pseudo_gradient(w_out, g_out, l1)
    return OptimizerResult(
        w=w_out,
        value=total(w_out, f_out),
        grad_norm=jnp.linalg.norm(pg_final),
        iterations=final.it,
        converged=reason_is_converged(final.reason),
        reason=final.reason,
        history_value=final.hv,
        history_grad_norm=final.hg,
        history_valid=final.hvalid,
        # The initial point, every line-search trial, the two polish steps.
        evaluations=final.ls + 3,
        line_search_steps=final.ls,
    )
