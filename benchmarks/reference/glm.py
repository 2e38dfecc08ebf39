"""Plain sparse fixed-effect logistic GLM fit: sum_i weight_i *
logloss(x_i . w, y_i) + (l2 / 2) |w|^2, minimised by the reference L-BFGS.

Value and gradient are computed in row blocks (gather of ``w`` at the ids
for the margins, scatter-add of ``dz * val`` for the gradient).  ``lowp``
is the control: the same arithmetic with the feature values and the
coefficients rounded to bfloat16 before every product (float32 sums), the
precision a later PR would be tempted by.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.common import logloss, round_to as _round
from benchmarks.reference.lbfgs import lbfgs

BLOCK_ROWS = 1 << 20


@functools.partial(jax.jit, static_argnames=("lowp",))
def _block_value_grad(w, ids, vals, label, weight, lowp=False):
    vals = _round(vals, lowp)
    z = jnp.sum(jnp.take(_round(w, lowp), ids, axis=0) * vals, axis=-1)
    dz = weight * (jax.nn.sigmoid(z) - label)
    grad = jnp.zeros_like(w).at[ids].add(_round(dz, lowp)[:, None] * vals)
    return jnp.sum(weight * logloss(z, label)), grad


def fit(data, l2: float, max_iterations: int, tolerance: float,
        gradient_tolerance: float, lowp: bool = False, weight=None):
    """Fit from w = 0 on ``data`` (a ``generate.SparseGlmData``); returns
    ``{"w", "values", "grad_norms", "iterations"}`` with host floats and a
    host coefficient vector."""
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

    with jax.default_matmul_precision("highest"):
        w, values, grad_norms, iterations = lbfgs(
            fun, jnp.zeros(data.dim, jnp.float32), max_iterations,
            tolerance, gradient_tolerance,
        )
    return {"w": np.asarray(w), "values": values, "grad_norms": grad_norms,
            "iterations": iterations}
