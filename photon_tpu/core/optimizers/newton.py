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

A GLM objective goes in as a :class:`MarginForm`: its margins are affine
in ``w``, so the solver carries them, searches the line ``z + t X step``
with one pass over the features (``X step``) and a loss over ``[rows]`` a
trial, and takes gradient and Hessian at the accepted point from the
stepped margins — three passes an iteration.  Under ``vmap`` that is what
counts: the line search runs in lockstep, so with a value and a gradient
over the features a trial every entity of a bin paid two passes for each
trial of the one entity that backtracked longest (PERF.md, PR 39).

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
from photon_tpu.core.optimizers.lbfgs import (
    _backtracking,
    _backtracking_line_search,
)

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
# One vector register's lanes.  A dense bin's features sit on the v5e with
# their ROWS on the lanes whatever their logical order (the compiler lays a
# ``[B, 256, 16]`` array out rows-minor; an entity-minor slab only where the
# entity count needs no padding), so a bin of at least this many rows an
# entity fills the lanes as it is and its Hessian is an MXU product; under
# it most lanes idle, and the bin is turned entity-minor instead
# (:func:`reduction_kind`), its entities padded to a multiple of this.
# Fixed from the v5e (PERF.md, PR 39; one bin program alone, margins
# carried in both): ``[13,124, 32, 16]`` 19.6 ms a call rows-minor, 10.3 ms
# entity-minor; ``[25,238, 256, 16]`` 44.6 against 87.9 ms; ``[1,567,
# 1,024, 16]`` 6.7 against 11.7 ms.
LANES = 128


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


def reduction_kind(dense: bool, dim: int, entities: int, rows: int) -> str:
    """Which form the three dense products of a batched ``newton`` bin take
    (``X v``, ``Xᵀ u``, ``Xᵀ D X``): ``lanes`` — the features turned
    entity-minor once a call, float32 products and sums over the non-lane
    axes (``objective._lane_form``) — for dense features at the
    factorization's lane dims whose ``rows`` an entity leave lanes idle
    (under ``LANES``) and whose ``entities`` a device fill them (at least
    ``LANES``); else ``rows``, the products over ``[B, rows, dim]``.  What
    ``solves.reductions{kind}`` counts."""
    lanes = (
        dense and dim <= LANES_MAX_DIM and rows < LANES and entities >= LANES
    )
    return "lanes" if lanes else "rows"


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


class MarginForm(NamedTuple):
    """An objective through its margins ``z = margins(w)``, affine in ``w``
    (a GLM's ``X w + offset``): value, gradient and Hessian at ``w`` as
    functions of ``(z, w)``.  :func:`newton` then carries ``z``: a Newton
    iteration is three passes over the features (``direction``, ``grad``,
    ``hess``) however many trials its line search runs, each trial the loss
    over ``z + t X step``.  Without it a trial is a value AND a gradient,
    two passes, and under ``vmap`` every entity of a bin pays for the trials
    of the one that backtracks longest (on the v5e 0.55 s of a 1.42 s GAME
    fit: PERF.md, PR 39)."""

    margins: Callable[[Array], Array]  # w -> z
    direction: Callable[[Array], Array]  # v -> margins(w + v) - margins(w)
    value: Callable[[Array, Array], Array]  # (z, w) -> f
    grad: Callable[[Array, Array], Array]  # (z, w) -> g
    hess: Callable[[Array, Array], Array]  # (z, w) -> [d, d]


class _State(NamedTuple):
    w: Array
    f: Array
    g: Array
    z: Array  # margins(w) under a MarginForm, else a placeholder
    it: Array
    ls: Array  # line-search trials so far (one objective evaluation each)
    active: Array
    reason: Array
    hv: Array
    hg: Array
    hvalid: Array


def newton(
    fun: Callable[[Array], tuple[Array, Array]] | None,
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
    hess: Callable[[Array], Array] | None = None,
    form: MarginForm | None = None,
) -> OptimizerResult:
    """Minimize ``fun`` (returning (value, grad)) with full Newton steps.

    ``hess(w) -> [d, d]`` supplies the dense Hessian (for GLM objectives,
    ``objective.hessian_matrix``); if None it is derived from ``fun`` by
    forward-mode differentiation of the gradient (exact, d jvp passes).
    ``form``: the objective as a :class:`MarginForm`, in place of ``fun``
    and ``hess``; the same iterates, the margins carried between them.
    Pure JAX: safe under jit and vmap (the GAME batched entity solves).
    """
    if form is None and hess is None:
        def hess(w):  # noqa: ANN001
            return jax.jacfwd(lambda u: fun(u)[1])(w)

    def evaluate(w):
        """``(z, f, g)`` at ``w``, the margins taken afresh."""
        if form is None:
            return (jnp.zeros((), w.dtype), *fun(w))
        z = form.margins(w)
        return z, form.value(z, w), form.grad(z, w)

    d = w0.shape[0]
    eye = jnp.eye(d, dtype=w0.dtype)
    z0, f0, g0 = evaluate(w0)
    gnorm0 = jnp.linalg.norm(g0)
    conv0 = gnorm0 == 0.0
    hv, hg, hvalid = init_history(config.max_iterations, f0, gnorm0)

    init = _State(
        w=w0, f=f0, g=g0, z=z0,
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

    def solve(w, g, z):
        """The Newton step ``-(H(w) + ridge I)^-1 g``."""
        with jax.named_scope("newton/hessian"):
            h = hess(w) if form is None else form.hess(z, w)
            ridge = _RIDGE * (1.0 + jnp.max(jnp.abs(jnp.diagonal(h))))
            h = h + ridge * eye
        with jax.named_scope("newton/cholesky"):
            return -spd_solve(h, g)

    def body(s: _State):
        step = solve(s.w, s.g, s.z)
        with jax.named_scope("newton/step"):
            # Sums of float32 products, not ``dot``: a batched dot is a
            # ``dot_general`` at the backend's matmul precision.
            dir_deriv = jnp.sum(s.g * step)
            # A failed factorization (non-PD curvature -> NaN) or a
            # non-descent step falls back to steepest descent for this
            # iteration.
            bad = ~jnp.all(jnp.isfinite(step)) | (dir_deriv >= 0.0)
            step = jnp.where(bad, -s.g, step)
            dir_deriv = jnp.where(bad, -jnp.sum(s.g * s.g), dir_deriv)
            t0 = jnp.where(
                bad, 1.0 / jnp.maximum(jnp.linalg.norm(s.g), 1.0), 1.0
            )

        if form is None:
            t, f_new, g_new, ls_ok, trials = _backtracking_line_search(
                fun, s.w, step, s.f, dir_deriv, t0, config.max_line_search,
                s.active, scope="newton/gradient",
            )
            w_new, z_new = s.w + t * step, s.z
        else:
            with jax.named_scope("newton/direction"):
                u = form.direction(step)
            # A trial is the value along the carried margins, no gradient.
            t, f_new, _, ls_ok, trials = _backtracking(
                lambda t: (form.value(s.z + t * u, s.w + t * step), ()),
                s.f, dir_deriv, t0, config.max_line_search, s.active,
                scope="newton/line_search",
            )
            w_new, z_new = s.w + t * step, s.z + t * u
            with jax.named_scope("newton/gradient"):
                g_new = form.grad(z_new, w_new)

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
        z_out = jnp.where(ls_ok, z_new, s.z)
        hv, hg, hvalid = record_history(
            s.hv, s.hg, s.hvalid, it_new, f_out, jnp.linalg.norm(g_out),
            s.active & ls_ok,
        )

        new = _State(
            w=w_out, f=f_out, g=g_out, z=z_out,
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
    def polish(carry):
        k, w, f, g, z = carry
        step = solve(w, g, z)
        near = jnp.all(jnp.isfinite(step)) & (
            jnp.linalg.norm(step)
            <= 1e-3 * jnp.maximum(jnp.linalg.norm(w), 1.0)
        )
        w_new = jnp.where(near, w + step, w)
        # Under a MarginForm the margins are taken afresh here, not stepped
        # along: the polish lands on the float32 gradient's own zero.
        z_new, f_new, g_new = evaluate(w_new)
        keep = near & jnp.isfinite(f_new) & jnp.all(jnp.isfinite(g_new))
        return (
            k + 1,
            jnp.where(keep, w_new, w),
            jnp.where(keep, f_new, f),
            jnp.where(keep, g_new, g),
            jnp.where(keep, z_new, z),
        )

    # A ``while_loop`` on a counter, not a ``scan``: under ``vmap`` a scan
    # moves every mapped constant's axis to the front, which would turn an
    # entity-minor bin (``reduction_kind``) back inside each polish step.
    _, w_out, f_out, g_out, _ = lax.while_loop(
        lambda carry: carry[0] < _POLISH_STEPS, polish,
        (jnp.asarray(0, jnp.int32), final.w, final.f, final.g, final.z),
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
