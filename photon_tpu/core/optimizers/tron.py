"""TRON: trust-region Newton with a conjugate-gradient inner loop.

Rebuild of the reference's ``TRON`` (photon-lib .../optimization/TRON.scala,
itself a port of LIBLINEAR's tron.cpp — SURVEY.md §2.1): an outer trust-region
loop whose step comes from CG on Hessian-vector products, truncated at the
trust boundary.  Constants (eta0/1/2, sigma1/2/3, CG tolerance xi = 0.1)
follow LIBLINEAR so convergence behavior matches the reference closely
(SURVEY.md §7 'TRON parity').

Hessian-vector products are exact via ``jax.jvp`` of the gradient — the
reference's ``HessianVectorAggregator`` treeAggregate collapsed into the same
XLA program as the outer loop.  Both loops are masked ``lax.while_loop``s, so
TRON vmaps for batched per-entity GAME solves.

What it reports besides the iterate: ``evaluations`` (the initial point and
one trial a trust-region iteration), ``cg_iterations`` (the CG steps that
ran, one Hessian-vector product each; a finished, masked iteration adds
none) and ``trust_region_rejections`` (trials whose reduction the model did
not predict well enough, so ``w`` stayed).  Its phases carry named scopes:
``tron/curvature`` (the operator's per-row curvature at ``w``), ``tron/cg``
(the CG loop and its products) and ``tron/trial`` (the evaluation at
``w + s``).

A GLM objective goes in as a :class:`MarginForm`: its margins
``z = X w + offset`` are affine in ``w``, so TRON carries them.  The
curvature at the accepted point is built from its carried ``z``, CG adds
``alpha X d`` to ``X s`` with each step (``X d`` is half of its own
Hessian-vector product), and the trial is evaluated at ``z + X s``: a
trust-region iteration then makes no forward pass over the features of its
own, two fewer than with ``fun`` and ``hvp_at`` (``margin_passes_spared``).

Departure from liblinear noted for reviewers: rejected trust-region trials
count against ``max_iterations`` here (the loop must be bounded for XLA);
liblinear only counts accepted steps.  With the standard radius-shrink logic
the difference shows up only on pathological problems.
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

Array = jax.Array

_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0


class MarginForm(NamedTuple):
    """An objective through its margins ``z = margins(w)``, affine in ``w``
    (a GLM's ``X w + offset``), as :func:`tron` carries them: the start's
    forward pass, value and gradient at ``(z, w)`` with no forward pass,
    and the curvature operator at ``z`` whose product hands back ``X v``
    beside ``H v``."""

    margins: Callable[[Array], Array]  # w -> z
    value_and_grad: Callable[[Array, Array], tuple[Array, Array]]  # (z, w)
    curvature: Callable[[Array], Callable[[Array], tuple[Array, Array]]]
    # z -> (v -> (H v, X v))


class _CGState(NamedTuple):
    s: Array
    r: Array
    d: Array
    rtr: Array
    it: Array
    done: Array
    at_boundary: Array
    xs: Optional[Array]  # X s under a MarginForm, else None


def _trcg(hvp, g, delta, max_cg, active, cg_tolerance=0.1, xs0=None):
    """LIBLINEAR trcg: approximately solve H s = -g with ||s|| <= delta.

    ``hvp(v) -> (H v, X v or None)``.  Returns (s, r, at_boundary, steps,
    xs) where r = -g - H s is the residual, ``steps`` the CG steps that ran
    (0 where ``active`` is false) and ``xs`` = X s, summed from the
    products' ``X d`` starting at ``xs0`` (None where they give none)."""
    cg_tol = cg_tolerance * jnp.linalg.norm(g)

    def cond(c: _CGState):
        return ~c.done

    def body(c: _CGState):
        hd, xd = hvp(c.d)
        dhd = jnp.dot(c.d, hd)
        # Guard: curvature can be ~0 for flat directions; stop there.
        alpha = c.rtr / jnp.where(dhd > 1e-30, dhd, 1.0)
        bad_curv = dhd <= 1e-30
        s_try = c.s + alpha * c.d
        over = jnp.linalg.norm(s_try) > delta

        # Truncate to the trust boundary along d from the previous s.
        std = jnp.dot(c.s, c.d)
        sts = jnp.dot(c.s, c.s)
        dtd = jnp.dot(c.d, c.d)
        dsq = delta * delta
        rad = jnp.sqrt(jnp.maximum(std * std + dtd * (dsq - sts), 0.0))
        alpha_b = jnp.where(
            std >= 0.0,
            (dsq - sts) / jnp.maximum(std + rad, 1e-30),
            (rad - std) / jnp.maximum(dtd, 1e-30),
        )
        s_bound = c.s + alpha_b * c.d
        r_bound = c.r - alpha_b * hd

        s_in = s_try
        r_in = c.r - alpha * hd
        rtr_new = jnp.dot(r_in, r_in)
        beta = rtr_new / jnp.maximum(c.rtr, 1e-30)
        d_new = r_in + beta * c.d

        small_res = jnp.sqrt(rtr_new) <= cg_tol
        out_of_iters = c.it + 1 >= max_cg
        stop_boundary = over | bad_curv

        nxt = _CGState(
            s=jnp.where(stop_boundary, s_bound, s_in),
            r=jnp.where(stop_boundary, r_bound, r_in),
            d=d_new,
            rtr=rtr_new,
            it=c.it + 1,
            done=stop_boundary | small_res | out_of_iters,
            at_boundary=stop_boundary,
            xs=None if xd is None else jnp.where(
                stop_boundary, c.xs + alpha_b * xd, c.xs + alpha * xd),
        )
        return tree_where(c.done, c, nxt)

    z = jnp.zeros_like(g)
    init = _CGState(
        s=z, r=-g, d=-g,
        rtr=jnp.dot(g, g),
        it=jnp.asarray(0, jnp.int32),
        done=~active | (jnp.sqrt(jnp.dot(g, g)) <= cg_tol),
        at_boundary=jnp.asarray(False),
        xs=xs0,
    )
    final = lax.while_loop(cond, body, init)
    return final.s, final.r, final.at_boundary, final.it, final.xs


class _State(NamedTuple):
    w: Array
    f: Array
    g: Array
    z: Optional[Array]  # margins(w) under a MarginForm, else None
    delta: Array
    it: Array
    accepted_iters: Array
    active: Array
    reason: Array
    hv: Array
    hg: Array
    hvalid: Array
    cg_steps: Array
    rejections: Array


def tron(
    fun: Callable[[Array], tuple[Array, Array]] | None,
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
    hvp: Callable[[Array, Array], Array] | None = None,
    hvp_at: Callable[[Array], Callable[[Array], Array]] | None = None,
    form: MarginForm | None = None,
) -> OptimizerResult:
    """Minimize ``fun`` (value, grad) with Hessian-vector products.

    ``hvp_at(w) -> (v -> H(w) v)`` is the preferred form (ISSUE 15
    satellite / ROADMAP solver edge (e)): the operator is built ONCE per
    outer trust-region iteration, so a curvature-closure operator
    (``GlmObjective.hvp_operator`` — per-row curvature ``D(w)`` precomputed
    from the margins) pays the margin pass once and each inner CG iteration
    costs two matvecs, instead of recomputing margins per product as the
    per-call form does.  ``hvp(w, v) -> H(w) v`` is the legacy per-call
    form (wrapped); with neither, the product derives from ``fun`` by jvp
    of the gradient component (exact, one extra forward-over-reverse pass
    per product — unchanged math, since jvp re-linearizes at the same
    ``w`` every call).  ``form``: the objective as a :class:`MarginForm`,
    in place of ``fun`` and the products (pass one or the other); the same
    iterates but for rounding (``z + X s`` is not ``X (w + s)`` to the last
    bit), the margins carried between them.
    """
    if form is not None and any(
            f is not None for f in (fun, hvp, hvp_at)):
        raise ValueError("tron: pass a MarginForm or fun and its products, "
                         "not both")
    if form is None and fun is None:
        raise ValueError("tron: pass fun or a MarginForm")
    if form is None and hvp_at is None:
        if hvp is not None:
            def hvp_at(w):  # noqa: ANN001 — legacy per-call wrapper
                return lambda v: hvp(w, v)
        else:
            def hvp_at(w):  # noqa: ANN001 — jvp-of-grad fallback
                return lambda v: jax.jvp(
                    lambda u: fun(u)[1], (w,), (v,)
                )[1]

    d = w0.shape[0]
    max_cg = config.cg_max_iterations or min(d, 100)

    if form is None:
        z0 = None
        f0, g0 = fun(w0)
    else:
        z0 = form.margins(w0)
        f0, g0 = form.value_and_grad(z0, w0)
    gnorm0 = jnp.linalg.norm(g0)
    conv0 = gnorm0 == 0.0
    hv0, hg0, hvalid0 = init_history(config.max_iterations, f0, gnorm0)

    init = _State(
        w=w0, f=f0, g=g0, z=z0,
        delta=gnorm0,
        it=jnp.asarray(0, jnp.int32),
        accepted_iters=jnp.asarray(0, jnp.int32),
        active=~conv0,
        reason=jnp.where(
            conv0, ConvergenceReason.GRADIENT_TOLERANCE, ConvergenceReason.NOT_CONVERGED
        ).astype(jnp.int32),
        hv=hv0, hg=hg0, hvalid=hvalid0,
        cg_steps=jnp.asarray(0, jnp.int32),
        rejections=jnp.asarray(0, jnp.int32),
    )

    def cond(s: _State):
        return s.active

    def body(s: _State):
        # ONE curvature operator per outer iteration: the precomputed-
        # curvature closure's margin pass runs here, not per CG product;
        # under a MarginForm it reads the carried margins, and no pass.
        with jax.named_scope("tron/curvature"):
            if form is None:
                op = hvp_at(s.w)
                hv_op = lambda v: (op(v), None)  # noqa: E731
            else:
                hv_op = form.curvature(s.z)
        with jax.named_scope("tron/cg"):
            step, resid, _, cg_steps, xs = _trcg(
                hv_op, s.g, s.delta, max_cg, s.active,
                cg_tolerance=config.cg_tolerance,
                xs0=None if form is None else jnp.zeros_like(s.z),
            )
        w_new = s.w + step
        with jax.named_scope("tron/trial"):
            if form is None:
                z_new = None
                f_new, g_new = fun(w_new)
            else:
                z_new = s.z + xs  # margins(w + s), from CG's own X d
                f_new, g_new = form.value_and_grad(z_new, w_new)

        gs = jnp.dot(s.g, step)
        prered = -0.5 * (gs - jnp.dot(step, resid))
        actred = s.f - f_new
        snorm = jnp.linalg.norm(step)

        # First successful iteration clamps the radius to the step size.
        delta = jnp.where(s.accepted_iters == 0, jnp.minimum(s.delta, snorm), s.delta)

        denom = f_new - s.f - gs
        alpha = jnp.where(denom <= 0.0, _SIGMA3, jnp.maximum(_SIGMA1, -0.5 * (gs / jnp.where(denom <= 0.0, 1.0, denom))))

        delta = jnp.where(
            actred < _ETA0 * prered,
            jnp.minimum(jnp.maximum(alpha, _SIGMA1) * snorm, _SIGMA2 * delta),
            jnp.where(
                actred < _ETA1 * prered,
                jnp.maximum(_SIGMA1 * delta, jnp.minimum(alpha * snorm, _SIGMA2 * delta)),
                jnp.where(
                    actred < _ETA2 * prered,
                    jnp.maximum(_SIGMA1 * delta, jnp.minimum(alpha * snorm, _SIGMA3 * delta)),
                    jnp.maximum(delta, jnp.minimum(alpha * snorm, _SIGMA3 * delta)),
                ),
            ),
        )

        accept = (actred > _ETA0 * prered) & jnp.isfinite(f_new)
        w_out = jnp.where(accept, w_new, s.w)
        f_out = jnp.where(accept, f_new, s.f)
        g_out = jnp.where(accept, g_new, s.g)
        z_out = None if form is None else jnp.where(accept, z_new, s.z)
        gnorm_new = jnp.linalg.norm(g_out)

        converged, reason = check_convergence(f_out, s.f, gnorm_new, gnorm0, config)
        converged = converged & accept  # only test after accepted steps
        reason = jnp.where(accept, reason, ConvergenceReason.NOT_CONVERGED)
        # Degenerate model: no predicted reduction possible.
        degenerate = (prered <= 0.0) & (actred <= 0.0)
        reason = jnp.where(
            degenerate, ConvergenceReason.OBJECTIVE_NOT_IMPROVING, reason
        )
        it_new = s.it + 1
        hit_max = it_new >= config.max_iterations
        reason = jnp.where(
            hit_max & ~(converged | degenerate), ConvergenceReason.MAX_ITERATIONS, reason
        )
        still_active = s.active & ~(converged | degenerate | hit_max)

        hv, hg, hvalid = record_history(
            s.hv, s.hg, s.hvalid, it_new, f_out, gnorm_new, s.active & accept
        )

        new = _State(
            w=w_out, f=f_out, g=g_out, z=z_out,
            delta=delta,
            it=it_new,
            accepted_iters=s.accepted_iters + accept.astype(jnp.int32),
            active=still_active,
            reason=reason.astype(jnp.int32),
            hv=hv, hg=hg, hvalid=hvalid,
            cg_steps=s.cg_steps + cg_steps,
            rejections=s.rejections + (~accept).astype(jnp.int32),
        )
        return tree_where(s.active, new, s)

    final = lax.while_loop(cond, body, init)
    return OptimizerResult(
        w=final.w,
        value=final.f,
        grad_norm=jnp.linalg.norm(final.g),
        iterations=final.it,
        converged=reason_is_converged(final.reason),
        reason=final.reason,
        history_value=final.hv,
        history_grad_norm=final.hg,
        history_valid=final.hvalid,
        # The initial point plus one trial point per trust-region iteration
        # (TRON has no line search: line_search_steps stays None).
        evaluations=final.it + 1,
        cg_iterations=final.cg_steps,
        trust_region_rejections=final.rejections,
        # The curvature's and the trial's forward passes, each iteration.
        margin_passes_spared=(
            jnp.zeros_like(final.it) if form is None else 2 * final.it
        ),
    )
