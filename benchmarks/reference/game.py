"""Plain GAME fit by coordinate descent: a dense fixed effect and per-entity
random effects, logistic loss, L2 on every coefficient.

One descent iteration updates the coordinates in order.  Each is fitted
against the sum of the other coordinates' scores as offsets: the fixed
effect by the reference L-BFGS (warm-started, stated iteration budget and
tolerances), every entity's coefficients by damped Newton run to the optimum
of its strongly convex problem (Cholesky solve, halving until the objective
falls).  After each iteration the validation rows are scored with every
coordinate and AUC and mean logistic loss are taken in float64 on the host.

Entities are grouped by their row count rounded up to a power of two and
their rows gathered into padded ``[entities, rows, dim]`` blocks (weight 0
on padding), so each Newton iteration is a few batched products.  ``lowp``
is the control (features and coefficients rounded to bfloat16 before every
product, float32 sums); ``weight`` plants a fault (rows left out).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.common import logloss as _logloss, round_to as _round
from benchmarks.reference.lbfgs import lbfgs

NEWTON_ITERATIONS = 20
HALVINGS = 8


# -- fixed effect ---------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("lowp",))
def _fixed_value_grad(w, x, y, offset, weight, l2, lowp=False):
    z = x @ _round(w, lowp) + offset
    dz = weight * (jax.nn.sigmoid(z) - y)
    value = jnp.sum(weight * _logloss(z, y)) + 0.5 * l2 * jnp.dot(w, w)
    return value, _round(dz, lowp) @ x + l2 * w


# -- random effects ---------------------------------------------------------------


def entity_blocks(entity_ids: np.ndarray, n_entities: int) -> list:
    """Power-of-two row-capacity classes: ``[(entities [E], row_index [E, R],
    mask [E, R]), ...]`` over the rows of ``entity_ids``."""
    order = np.argsort(entity_ids, kind="stable")
    counts = np.bincount(entity_ids, minlength=n_entities)
    starts = np.cumsum(counts) - counts
    capacity = np.where(
        counts > 0,
        1 << np.ceil(np.log2(np.maximum(counts, 1))).astype(np.int64), 0,
    )
    blocks = []
    for cap in np.unique(capacity[capacity > 0]):
        entities = np.nonzero(capacity == cap)[0]
        slot = np.arange(cap)[None, :]
        mask = slot < counts[entities][:, None]
        index = np.minimum(starts[entities][:, None] + slot, len(order) - 1)
        blocks.append((
            entities, np.where(mask, order[index], 0).astype(np.int32),
            mask.astype(np.float32),
        ))
    return blocks


@functools.partial(jax.jit, static_argnames=("lowp",))
def _newton_solve(w0, x, y, mask, offset, l2, lowp=False):
    """Every entity's optimum from ``w0`` ([E, d]); x [E, R, d]."""
    eye = jnp.eye(x.shape[-1], dtype=x.dtype)

    def value(w):
        z = jnp.einsum("erd,ed->er", x, _round(w, lowp)) + offset
        return jnp.sum(mask * _logloss(z, y), axis=1) + 0.5 * l2 * jnp.sum(
            w * w, axis=1
        )

    def step(_, w):
        z = jnp.einsum("erd,ed->er", x, _round(w, lowp)) + offset
        p = jax.nn.sigmoid(z)
        g = jnp.einsum("erd,er->ed", x, _round(mask * (p - y), lowp)) + l2 * w
        h = jnp.einsum(
            "erd,er,erf->edf", x, _round(mask * p * (1.0 - p), lowp), x
        ) + l2 * eye
        chol = jnp.linalg.cholesky(h)
        delta = jax.scipy.linalg.cho_solve((chol, True), g[..., None])[..., 0]
        f0 = value(w)
        slope = jnp.sum(g * delta, axis=1)

        def halve(k, carry):
            t, done = carry
            ok = value(w - t[:, None] * delta) <= f0 - 1e-4 * t * slope
            done_new = done | ok
            return jnp.where(done_new, t, t * 0.5), done_new

        t, done = jax.lax.fori_loop(
            0, HALVINGS, halve,
            (jnp.ones(w.shape[0], w.dtype), jnp.zeros(w.shape[0], bool)),
        )
        return jnp.where(done[:, None], w - t[:, None] * delta, w)

    return jax.lax.fori_loop(0, NEWTON_ITERATIONS, step, w0)


@jax.jit
def _random_scores(table, x, ids):
    return jnp.sum(x * jnp.take(table, ids, axis=0), axis=1)


# -- validation metrics -----------------------------------------------------------


def auc(scores: np.ndarray, labels: np.ndarray) -> float:
    """Tie-corrected AUC (Mann-Whitney) in float64."""
    s = np.asarray(scores, np.float64)
    ss = np.sort(s)
    rank = 0.5 * (
        np.searchsorted(ss, s, side="left")
        + np.searchsorted(ss, s, side="right") + 1
    )
    pos = labels > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float(
        (rank[pos].sum() - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg)
    )


def mean_logistic_loss(scores: np.ndarray, labels: np.ndarray) -> float:
    z = np.asarray(scores, np.float64)
    y = np.asarray(labels, np.float64)
    return float(np.mean(np.maximum(z, 0) + np.log1p(np.exp(-np.abs(z))) - y * z))


# -- the fit ------------------------------------------------------------------------


def fit(data, spec: dict, lowp: bool = False, weight=None) -> dict:
    """``data``: a ``generate.GameData``.  ``spec``: ``l2``,
    ``descent_iterations``, ``fixed_max_iterations``, ``tolerance``,
    ``gradient_tolerance``.  Returns the coefficients (``fixed`` [d] and one
    ``[n_entities, d]`` table per random coordinate), the fixed effect's
    objective at the end of each of its fits and the validation metrics
    after each descent iteration."""
    train, val = data.train, data.validation
    n = train.rows
    l2 = jnp.float32(spec["l2"])
    weight_np = np.ones(n, np.float32) if weight is None else weight
    weight_dev = jnp.asarray(weight_np)
    with jax.default_matmul_precision("highest"):
        y = jnp.asarray(train.label)
        x_fixed = _round(jnp.asarray(train.x_fixed), lowp)
        x_fixed_val = _round(jnp.asarray(val.x_fixed), lowp)
        names = ("fixed",) + tuple(data.coordinates)
        random = {}
        for name in data.coordinates:
            xr = _round(jnp.asarray(train.x_random[name]), lowp)
            ids = train.entity_ids[name]
            blocks = []
            for entities, index, mask in entity_blocks(ids, data.n_entities):
                index_dev = jnp.asarray(index)
                blocks.append({
                    "entities": jnp.asarray(entities),
                    "index": index_dev,
                    "x": jnp.take(xr, index_dev, axis=0),
                    "y": jnp.take(y, index_dev, axis=0),
                    "mask": jnp.asarray(mask) * jnp.take(
                        weight_dev, index_dev, axis=0
                    ),
                })
            random[name] = {
                "x": xr, "ids": jnp.asarray(ids), "blocks": blocks,
                "x_val": _round(jnp.asarray(val.x_random[name]), lowp),
                "ids_val": jnp.asarray(val.entity_ids[name]),
            }
        coefficients = {
            "fixed": jnp.zeros(train.x_fixed.shape[1], jnp.float32),
            **{
                name: jnp.zeros(
                    (data.n_entities, train.x_random[name].shape[1]),
                    jnp.float32,
                )
                for name in data.coordinates
            },
        }
        scores = {name: jnp.zeros(n, jnp.float32) for name in names}
        val_scores = {
            name: jnp.zeros(val.rows, jnp.float32) for name in names
        }
        fixed_values, fixed_grad0, metrics = [], [], []
        for _ in range(int(spec["descent_iterations"])):
            for name in names:
                offset = sum(
                    (scores[other] for other in names if other != name),
                    jnp.zeros(n, jnp.float32),
                )
                if name == "fixed":
                    fun = functools.partial(
                        _fixed_value_grad, x=x_fixed, y=y, offset=offset,
                        weight=weight_dev, l2=l2, lowp=lowp,
                    )
                    w, values, grad_norms, _ = lbfgs(
                        fun, coefficients["fixed"],
                        int(spec["fixed_max_iterations"]),
                        spec["tolerance"], spec["gradient_tolerance"],
                    )
                    coefficients["fixed"] = w
                    fixed_values.append(values[-1])
                    fixed_grad0.append(grad_norms[0])
                    scores[name] = x_fixed @ _round(w, lowp)
                    val_scores[name] = x_fixed_val @ _round(w, lowp)
                    continue
                coord = random[name]
                table = coefficients[name]
                for block in coord["blocks"]:
                    solved = _newton_solve(
                        jnp.take(table, block["entities"], axis=0),
                        block["x"], block["y"], block["mask"],
                        jnp.take(offset, block["index"], axis=0), l2,
                        lowp=lowp,
                    )
                    table = table.at[block["entities"]].set(solved)
                coefficients[name] = table
                scores[name] = _random_scores(
                    _round(table, lowp), coord["x"], coord["ids"]
                )
                val_scores[name] = _random_scores(
                    _round(table, lowp), coord["x_val"], coord["ids_val"]
                )
            total = np.asarray(sum(val_scores.values()), np.float64)
            metrics.append({
                "AUC": auc(total, val.label),
                "LOGISTIC_LOSS": mean_logistic_loss(total, val.label),
            })
        return {
            "coefficients": {k: np.asarray(v) for k, v in coefficients.items()},
            "fixed_values": fixed_values,
            "fixed_grad0": fixed_grad0,
            "metrics": metrics,
        }
