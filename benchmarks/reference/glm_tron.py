"""Plain sparse fixed-effect Poisson GLM fit: sum_i weight_i * (exp(z_i) -
y_i z_i) + (l2 / 2) |w|^2 with z = x_i . w, minimised by TRON written from
LIBLINEAR's ``tron.cpp`` (the trust-region Newton method whose port is
photon-ml's ``TRON.scala``), driven from the host.

The algorithm, with LIBLINEAR's constants (eta 1e-4 / 0.25 / 0.75, sigma
0.25 / 0.5 / 4): the radius starts at |g0| and is clamped to the first
step's length; each iteration solves H s = -g by conjugate gradients
truncated at the trust boundary (``trcg``), takes the trial ``w + s``,
accepts it when the actual reduction is over eta0 x the predicted one, and
moves the radius by the ratio of the two.  Departures, each the
configuration's: CG stops after ``max_cg`` steps as well as on its residual
(photon-ml's cap on CG iterations); a rejected trial counts against
``max_iterations`` (the repo's documented departure: its loop is bounded
for XLA; LIBLINEAR counts accepted steps alone); the relative function and
gradient tolerances are photon-ml's (``|f - f_new| / |f| <= tol`` or ``|g| <=
gtol max(|g0|, 1)`` after an accepted step).  Every decision is taken on the
host from float32 device scalars.

Value, gradient, the per-row curvature ``D = weight exp(z)`` and ``Xᵀ(D X
v)`` are computed in row blocks (gather of the coefficients at the ids,
scatter-add into the feature space).  ``lowp`` is the control: the same
arithmetic with the feature values, the coefficients and every per-row
factor rounded to bfloat16 before a product (float32 sums).  The exp is
plain: the program's loss continues linearly past z = 30, which no row of a
sane fit reaches.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.common import round_to as _round

BLOCK_ROWS = 1 << 20
ETA0, ETA1, ETA2 = 1e-4, 0.25, 0.75
SIGMA1, SIGMA2, SIGMA3 = 0.25, 0.5, 4.0


def _margins(w, ids, vals, lowp):
    return jnp.sum(jnp.take(_round(w, lowp), ids, axis=0)
                   * _round(vals, lowp), axis=-1)


def _xt(u, ids, vals, dim, lowp):
    return jnp.zeros(dim, jnp.float32).at[ids].add(
        _round(u, lowp)[:, None] * _round(vals, lowp))


@functools.partial(jax.jit, static_argnames=("lowp",))
def _block_value_grad(w, ids, vals, label, weight, lowp=False):
    z = _margins(w, ids, vals, lowp)
    rate = jnp.exp(z)
    return (jnp.sum(weight * (rate - label * z)),
            _xt(weight * (rate - label), ids, vals, w.shape[0], lowp))


@functools.partial(jax.jit, static_argnames=("lowp",))
def _block_curvature(w, ids, vals, weight, lowp=False):
    return weight * jnp.exp(_margins(w, ids, vals, lowp))


@functools.partial(jax.jit, static_argnames=("lowp",))
def _block_hv(v, ids, vals, curvature, lowp=False):
    return _xt(curvature * _margins(v, ids, vals, lowp), ids, vals,
               v.shape[0], lowp)


def _norm(x) -> float:
    return float(jnp.linalg.norm(x))


def _dot(x, y) -> float:
    return float(jnp.dot(x, y))


def trcg(hv, g, delta: float, max_cg: int, cg_tolerance: float):
    """LIBLINEAR's ``trcg``: CG on H s = -g from s = 0 until the residual is
    under ``cg_tolerance |g|``, ``max_cg`` steps ran, or the step leaves the
    ball of radius ``delta``, where it is cut back to the boundary along the
    last direction.  Returns ``(s, r, steps)``, ``r = -g - H s``."""
    s = jnp.zeros_like(g)
    r = -g
    d = r
    rtr = _dot(r, r)
    cgtol = cg_tolerance * _norm(g)
    steps = 0
    while math.sqrt(rtr) > cgtol and steps < max_cg:
        steps += 1
        hd = hv(d)
        alpha = rtr / _dot(d, hd)
        s = s + alpha * d
        if _norm(s) > delta:
            s = s - alpha * d
            std, sts, dtd = _dot(s, d), _dot(s, s), _dot(d, d)
            dsq = delta * delta
            rad = math.sqrt(std * std + dtd * (dsq - sts))
            alpha = ((dsq - sts) / (std + rad) if std >= 0
                     else (rad - std) / dtd)
            s = s + alpha * d
            r = r - alpha * hd
            break
        r = r - alpha * hd
        rnew = _dot(r, r)
        d = r + (rnew / rtr) * d
        rtr = rnew
    return s, r, steps


def tron(fun, hv_at, w0, max_iterations: int, max_cg: int,
         cg_tolerance: float, tolerance: float, gradient_tolerance: float):
    """Returns ``{"w", "values", "grad_norms", "iterations", "cg_iterations",
    "rejections"}``: the objective and gradient norm at the start and after
    every accepted step (``values`` ends with the final objective), the
    trust-region iterations run (rejected trials included) and the CG steps
    (Hessian-vector products) over all of them."""
    w = w0
    f, g = fun(w)
    f = float(f)
    gnorm0 = _norm(g)
    values, grad_norms = [f], [gnorm0]
    delta = gnorm0
    iterations = accepted = cg_total = rejections = 0
    search = gnorm0 > 0.0
    while search and iterations < max_iterations:
        s, r, steps = trcg(hv_at(w), g, delta, max_cg, cg_tolerance)
        cg_total += steps
        w_new = w + s
        f_new, g_new = fun(w_new)
        f_new = float(f_new)
        gs = _dot(g, s)
        prered = -0.5 * (gs - _dot(s, r))
        actred = f - f_new
        snorm = _norm(s)
        if accepted == 0:  # LIBLINEAR's iter == 1: adjust the initial bound
            delta = min(delta, snorm)
        alpha = SIGMA3 if f_new - f - gs <= 0 else max(
            SIGMA1, -0.5 * (gs / (f_new - f - gs)))
        if actred < ETA0 * prered:
            delta = min(max(alpha, SIGMA1) * snorm, SIGMA2 * delta)
        elif actred < ETA1 * prered:
            delta = max(SIGMA1 * delta, min(alpha * snorm, SIGMA2 * delta))
        elif actred < ETA2 * prered:
            delta = max(SIGMA1 * delta, min(alpha * snorm, SIGMA3 * delta))
        else:
            delta = max(delta, min(alpha * snorm, SIGMA3 * delta))
        iterations += 1
        if actred > ETA0 * prered and math.isfinite(f_new):
            accepted += 1
            rel = abs(f - f_new) / max(abs(f), 1e-12)
            w, f, g = w_new, f_new, g_new
            gnorm = _norm(g)
            values.append(f)
            grad_norms.append(gnorm)
            if rel <= tolerance or gnorm <= gradient_tolerance * max(
                    gnorm0, 1.0):
                break
        else:
            rejections += 1
        # LIBLINEAR's two guards: no reduction possible, or both reductions
        # under the objective's last digits (float32 has stalled).
        if actred <= 0 and prered <= 0:
            break
        if abs(actred) <= 1e-12 * abs(f) and abs(prered) <= 1e-12 * abs(f):
            break
    return {"w": w, "values": values + [f], "grad_norms": grad_norms,
            "iterations": iterations, "cg_iterations": cg_total,
            "rejections": rejections}


def fit(data, l2: float, max_iterations: int, max_cg: int,
        cg_tolerance: float, tolerance: float, gradient_tolerance: float,
        lowp: bool = False, weight=None):
    """Fit from w = 0 on ``data`` (a ``generate.SparseGlmData`` with count
    labels); ``tron``'s dict with host floats and a host coefficient
    vector."""
    import numpy as np

    n = data.rows
    weight = np.ones(n, np.float32) if weight is None else weight
    blocks = [
        tuple(
            jnp.asarray(a[s:s + BLOCK_ROWS])
            for a in (data.ids, data.vals, data.label, weight)
        )
        for s in range(0, n, BLOCK_ROWS)
    ]

    def fun(w):
        value = 0.5 * l2 * jnp.dot(w, w)
        grad = l2 * w
        for ids, vals, label, wt in blocks:
            v, g = _block_value_grad(w, ids, vals, label, wt, lowp=lowp)
            value, grad = value + v, grad + g
        return value, grad

    def hv_at(w):
        curvature = [_block_curvature(w, ids, vals, wt, lowp=lowp)
                     for ids, vals, _, wt in blocks]

        def hv(v):
            out = l2 * v
            for (ids, vals, _, _), c in zip(blocks, curvature):
                out = out + _block_hv(v, ids, vals, c, lowp=lowp)
            return out

        return hv

    with jax.default_matmul_precision("highest"):
        out = tron(fun, hv_at, jnp.zeros(data.dim, jnp.float32),
                   max_iterations, max_cg, cg_tolerance, tolerance,
                   gradient_tolerance)
    out["w"] = np.asarray(out["w"])
    return out
