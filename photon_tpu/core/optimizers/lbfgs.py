"""L-BFGS as a single jit-compiled ``lax.while_loop``.

Rebuild of the reference's ``LBFGS`` (photon-lib .../optimization/LBFGS.scala),
which wraps ``breeze.optimize.LBFGS`` — SURVEY.md §2.1.  Here the two-loop
recursion runs over a fixed ring buffer of (s, y) pairs and the backtracking
line search is an inner ``lax.while_loop``, so the whole optimize() call is
one XLA program: no host round-trips between iterations (the reference pays a
driver↔executor broadcast + treeAggregate per function evaluation).

Every state update is masked on an ``active`` flag, which makes the loop
vmap-correct for GAME's batched per-entity solves: converged lanes freeze
while the rest keep iterating (SURVEY.md §7 'hard parts').
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

Array = jax.Array

_ARMIJO_C1 = 1e-4
_POLISH_STEPS = 2  # full steps after the loop, one evaluation each
_PAIR_EPS = 1e-10


class _LineSearchState(NamedTuple):
    t: Array
    f: Array
    g: Array
    ok: Array  # current trial satisfies Armijo
    it: Array
    halt: Array  # stop without success (out of steps / inactive lane)


def _backtracking_line_search(fun, w, d, f0, dir_deriv, t0, max_steps, active,
                              scope: str = "line_search"):
    """:func:`_backtracking` along ``w + t d``: a trial is one (value, grad)
    evaluation of ``fun``.  Returns (t, f_t, g_t, success, trials)."""
    return _backtracking(
        lambda t: fun(w + t * d), f0, dir_deriv, t0, max_steps, active, scope
    )


def _backtracking(phi, f0, dir_deriv, t0, max_steps, active,
                  scope: str = "line_search"):
    """Armijo backtracking from step ``t0``, halving on failure, over
    ``phi(t) -> (value, aux)`` (``aux`` any pytree: the gradient at the
    trial point, or nothing).  ``scope`` names the trials' operations in
    the device trace (``lbfgs/line_search``, ``newton/gradient``,
    ``newton/line_search``).

    Returns (t, f_t, aux_t, success, trials).  The acceptance test lives in
    the loop condition, so exactly one evaluation of ``phi`` happens per
    trial — an accepted first step costs a single evaluation, and ``trials``
    (int32) is the number of evaluations this search ran.  Inert when
    ``active`` is False.
    """

    def trial(t):
        f, g = phi(t)
        # NaN/Inf trial values (e.g. Poisson exp overflow) never pass Armijo.
        ok = (f <= f0 + _ARMIJO_C1 * t * dir_deriv) & jnp.isfinite(f)
        return f, g, ok

    with jax.named_scope(scope):
        f_i, g_i, ok_i = trial(t0)

    def cond(s: _LineSearchState):
        return ~(s.ok | s.halt)

    def body(s: _LineSearchState):
        t_new = s.t * 0.5
        f_new, g_new, ok_new = trial(t_new)
        return _LineSearchState(
            t=t_new, f=f_new, g=g_new, ok=ok_new, it=s.it + 1,
            halt=s.it + 1 >= max_steps,
        )

    init = _LineSearchState(
        t=jnp.asarray(t0), f=f_i, g=g_i, ok=ok_i,
        it=jnp.asarray(0, jnp.int32), halt=~active,
    )
    with jax.named_scope(scope):
        final = lax.while_loop(cond, body, init)
    return final.t, final.f, final.g, final.ok, final.it + 1


def _two_loop_direction(g, S, Y, rho, num_pairs, insert_pos, gamma, m):
    """Classic L-BFGS two-loop recursion over a ring buffer.

    Slots are valid for j < num_pairs; newest pair sits at (insert_pos-1) % m.
    """

    def body1(j, carry):
        q, alphas = carry
        idx = (insert_pos - 1 - j) % m
        valid = j < num_pairs
        alpha = jnp.where(valid, rho[idx] * jnp.dot(S[idx], q), 0.0)
        q = q - alpha * Y[idx]
        alphas = alphas.at[idx].set(alpha)
        return q, alphas

    q, alphas = lax.fori_loop(0, m, body1, (g, jnp.zeros(m, g.dtype)))
    r = gamma * q

    def body2(j, r):
        idx = (insert_pos - num_pairs + j) % m
        valid = j < num_pairs
        beta = jnp.where(valid, rho[idx] * jnp.dot(Y[idx], r), 0.0)
        return r + jnp.where(valid, alphas[idx] - beta, 0.0) * S[idx]

    r = lax.fori_loop(0, m, body2, r)
    return -r


class _State(NamedTuple):
    w: Array
    f: Array
    g: Array
    S: Array  # [m, d]
    Y: Array  # [m, d]
    rho: Array  # [m]
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


def lbfgs(
    fun: Callable[[Array], tuple[Array, Array]],
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
) -> OptimizerResult:
    """Minimize ``fun`` (returning (value, grad)) starting from ``w0``.

    Pure JAX: safe under jit, vmap (batched entity solves), and shard_map
    (the function may psum internally; the optimizer only sees full
    gradients).
    """
    m = config.history_length
    d = w0.shape[0]
    f0, g0 = fun(w0)
    gnorm0 = jnp.linalg.norm(g0)
    # The gradient test is relative to ||g0||, so at the initial point it
    # only fires for an exactly-zero gradient.
    conv0 = gnorm0 == 0.0
    hv, hg, hvalid = init_history(config.max_iterations, f0, gnorm0)

    init = _State(
        w=w0, f=f0, g=g0,
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
        with jax.named_scope("lbfgs/direction"):
            dvec = _two_loop_direction(
                s.g, s.S, s.Y, s.rho, s.num_pairs, s.insert_pos, s.gamma, m
            )
        dir_deriv = jnp.dot(s.g, dvec)
        # Fall back to steepest descent if the direction is not a descent one.
        bad = dir_deriv >= 0.0
        dvec = jnp.where(bad, -s.g, dvec)
        dir_deriv = jnp.where(bad, -jnp.dot(s.g, s.g), dir_deriv)
        gnorm = jnp.linalg.norm(s.g)
        t0 = jnp.where(s.num_pairs == 0, 1.0 / jnp.maximum(gnorm, 1.0), 1.0)

        t, f_new, g_new, ls_ok, trials = _backtracking_line_search(
            fun, s.w, dvec, s.f, dir_deriv, t0, config.max_line_search,
            s.active, scope="lbfgs/line_search",
        )

        w_new = s.w + t * dvec
        svec = w_new - s.w
        yvec = g_new - s.g
        sy = jnp.dot(svec, yvec)
        # Cautious update: only store pairs with positive curvature.
        pair_ok = ls_ok & (sy > _PAIR_EPS)
        S_new = s.S.at[s.insert_pos].set(jnp.where(pair_ok, svec, s.S[s.insert_pos]))
        Y_new = s.Y.at[s.insert_pos].set(jnp.where(pair_ok, yvec, s.Y[s.insert_pos]))
        rho_new = s.rho.at[s.insert_pos].set(
            jnp.where(pair_ok, 1.0 / jnp.where(pair_ok, sy, 1.0), s.rho[s.insert_pos])
        )
        num_pairs = jnp.where(pair_ok, jnp.minimum(s.num_pairs + 1, m), s.num_pairs)
        insert_pos = jnp.where(pair_ok, (s.insert_pos + 1) % m, s.insert_pos)
        gamma = jnp.where(pair_ok, sy / jnp.maximum(jnp.dot(yvec, yvec), 1e-30), s.gamma)

        gnorm_new = jnp.linalg.norm(g_new)
        converged, reason = check_convergence(f_new, s.f, gnorm_new, gnorm0, config)
        stop_ls = ~ls_ok
        reason = jnp.where(stop_ls, ConvergenceReason.OBJECTIVE_NOT_IMPROVING, reason)
        it_new = s.it + 1
        hit_max = it_new >= config.max_iterations
        reason = jnp.where(
            hit_max & ~(converged | stop_ls), ConvergenceReason.MAX_ITERATIONS, reason
        )
        still_active = s.active & ~(converged | stop_ls | hit_max)

        # On line-search failure keep the old iterate.
        w_out = jnp.where(ls_ok, w_new, s.w)
        f_out = jnp.where(ls_ok, f_new, s.f)
        g_out = jnp.where(ls_ok, g_new, s.g)
        hv, hg, hvalid = record_history(
            s.hv, s.hg, s.hvalid, it_new, f_out, jnp.linalg.norm(g_out), s.active & ls_ok
        )

        new = _State(
            w=w_out, f=f_out, g=g_out,
            S=S_new, Y=Y_new, rho=rho_new,
            num_pairs=num_pairs, insert_pos=insert_pos, gamma=gamma,
            it=it_new, ls=s.ls + trials, active=still_active,
            reason=reason.astype(jnp.int32),
            hv=hv, hg=hg, hvalid=hvalid,
        )
        return tree_where(s.active, new, s)

    final = lax.while_loop(cond, body, init)

    # Full-step polish (the Newton-solver trick, grafted): the line-
    # searched loop stops where f32 FUNCTION differences round to zero —
    # a basin ~1e-4 wide around the true optimum.  The quasi-Newton map
    # built from the final ring buffer keeps contracting on the f32
    # GRADIENT's zero well past that, so two unconditional full steps
    # tighten the iterate at the cost of two extra evaluations.  Guards
    # (all vmap-safe, per lane): the step must be small relative to the
    # iterate (a lane stopped far from its optimum — max_iterations,
    # degenerate curvature — must not take an unsearched full step), the
    # stepped point must stay finite, AND — unlike Newton, whose exact
    # Hessian certifies the step — the gradient norm must not grow (a
    # stale ring buffer's direction carries no such certificate).
    def polish(carry, _):
        w, f, g = carry
        step = _two_loop_direction(
            g, final.S, final.Y, final.rho, final.num_pairs,
            final.insert_pos, final.gamma, m,
        )
        near = jnp.all(jnp.isfinite(step)) & (
            jnp.linalg.norm(step)
            <= 1e-3 * jnp.maximum(jnp.linalg.norm(w), 1.0)
        )
        w_new = jnp.where(near, w + step, w)
        f_new, g_new = fun(w_new)
        keep = (
            near & jnp.isfinite(f_new) & jnp.all(jnp.isfinite(g_new))
            & (jnp.linalg.norm(g_new) <= jnp.linalg.norm(g))
        )
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
