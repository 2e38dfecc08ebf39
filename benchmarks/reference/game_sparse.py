"""``game.fit`` for a data set whose fixed effect is sparse: the same plain
GAME fit by coordinate descent, with the fixed effect's shard given as
padded-COO ``(ids, vals)`` rows of a ``fixed_dim``-wide feature space.

What differs from ``game.py`` is the fixed effect alone, and it is
``glm.py``'s arithmetic: the margins are ``sum(w[ids] * vals)`` over a row
(a gather of ``w`` at the ids), the gradient a ``segment_sum`` of
``dz * vals`` over the ids, both taken in blocks of ``ROW_BLOCK`` rows so
that the ``[rows, nnz]`` gathered block is a transient of one row block and
the whole fits beside the random effects' blocks on one chip; the L2 term
is counted once.  Its L-BFGS is ``lbfgs.py``'s, every decision on the host.
Everything else (entity blocks, the damped Newton solves run to their
optimum, the random effects' scores, AUC and mean logistic loss in float64
on the host, float32 and ``highest`` on the device) is ``game.py``'s own,
imported from it, and the descent loop is ``game.fit``'s line for line:
with a dense matrix written sparsely (ids ``0..d-1`` in every row) the two
agree to float32 rounding (``tests/test_game_sparse_cell.py``).

``lowp`` is the control (values, coefficients and per-row factors rounded to
bfloat16 before every product, float32 sums); ``weight`` plants a fault
(rows left out).  Nothing is imported from ``photon_tpu``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import game
from benchmarks.reference.common import logloss as _logloss, round_to as _round
from benchmarks.reference.game_blocked import _row_spans
from benchmarks.reference.lbfgs import lbfgs

ROW_BLOCK = 1 << 20


@functools.partial(jax.jit, static_argnames=("lowp",))
def _block_margins(w, ids, vals, lowp=False):
    return jnp.sum(jnp.take(_round(w, lowp), ids, axis=0) * vals, axis=-1)


@functools.partial(jax.jit, static_argnames=("lowp",))
def _block_value_grad(w, ids, vals, y, offset, weight, lowp=False):
    """One row block's loss and gradient, without the L2 term."""
    z = _block_margins(w, ids, vals, lowp=lowp) + offset
    dz = weight * (jax.nn.sigmoid(z) - y)
    grad = jax.ops.segment_sum(
        (_round(dz, lowp)[:, None] * vals).reshape(-1), ids.reshape(-1),
        num_segments=w.shape[0],
    )
    return jnp.sum(weight * _logloss(z, y)), grad


def fit(data, spec: dict, lowp: bool = False, weight=None,
        row_block: int = ROW_BLOCK) -> dict:
    """``data``: a ``generate_game_sparse.SparseGameData``.  ``spec`` and
    the result are ``game.fit``'s; ``row_block`` is the fixed effect's
    block size (the tests shrink it)."""
    train, val = data.train, data.validation
    n = train.rows
    l2 = jnp.float32(spec["l2"])
    weight_np = np.ones(n, np.float32) if weight is None else weight
    weight_dev = jnp.asarray(weight_np)
    spans, val_spans = _row_spans(n, row_block), _row_spans(val.rows, row_block)
    with jax.default_matmul_precision("highest"):
        y = jnp.asarray(train.label)
        fixed = [(jnp.asarray(train.ids_fixed[s:e]),
                  _round(jnp.asarray(train.vals_fixed[s:e]), lowp),
                  y[s:e], weight_dev[s:e]) for s, e in spans]
        fixed_val = [(jnp.asarray(val.ids_fixed[s:e]),
                      _round(jnp.asarray(val.vals_fixed[s:e]), lowp))
                     for s, e in val_spans]
        names = ("fixed",) + tuple(data.coordinates)
        random = {}
        for name in data.coordinates:
            xr = _round(jnp.asarray(train.x_random[name]), lowp)
            ids = train.entity_ids[name]
            blocks = []
            for entities, index, mask in game.entity_blocks(
                    ids, data.n_entities):
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
            "fixed": jnp.zeros(data.fixed_dim, jnp.float32),
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

        def fixed_value_grad(w, offsets):
            value, grad = 0.5 * l2 * jnp.dot(w, w), l2 * w
            for (ids, vals, y_b, weight_b), offset_b in zip(fixed, offsets):
                v, g = _block_value_grad(
                    w, ids, vals, y_b, offset_b, weight_b, lowp=lowp)
                value, grad = value + v, grad + g
            return value, grad

        def fixed_scores(w, blocks):
            return jnp.concatenate([
                _block_margins(w, block[0], block[1], lowp=lowp)
                for block in blocks
            ])

        fixed_values, fixed_grad0, metrics = [], [], []
        for _ in range(int(spec["descent_iterations"])):
            for name in names:
                offset = sum(
                    (scores[other] for other in names if other != name),
                    jnp.zeros(n, jnp.float32),
                )
                if name == "fixed":
                    offsets = [offset[s:e] for s, e in spans]
                    w, values, grad_norms, _ = lbfgs(
                        lambda w: fixed_value_grad(w, offsets),
                        coefficients["fixed"],
                        int(spec["fixed_max_iterations"]),
                        spec["tolerance"], spec["gradient_tolerance"],
                    )
                    coefficients["fixed"] = w
                    fixed_values.append(values[-1])
                    fixed_grad0.append(grad_norms[0])
                    scores[name] = fixed_scores(w, fixed)
                    val_scores[name] = fixed_scores(w, fixed_val)
                    continue
                coord = random[name]
                table = coefficients[name]
                for block in coord["blocks"]:
                    solved = game._newton_solve(
                        jnp.take(table, block["entities"], axis=0),
                        block["x"], block["y"], block["mask"],
                        jnp.take(offset, block["index"], axis=0), l2,
                        lowp=lowp,
                    )
                    table = table.at[block["entities"]].set(solved)
                coefficients[name] = table
                scores[name] = game._random_scores(
                    _round(table, lowp), coord["x"], coord["ids"]
                )
                val_scores[name] = game._random_scores(
                    _round(table, lowp), coord["x_val"], coord["ids_val"]
                )
            total = np.asarray(sum(val_scores.values()), np.float64)
            metrics.append({
                "AUC": game.auc(total, val.label),
                "LOGISTIC_LOSS": game.mean_logistic_loss(total, val.label),
            })
        return {
            "coefficients": {k: np.asarray(v) for k, v in coefficients.items()},
            "fixed_values": fixed_values,
            "fixed_grad0": fixed_grad0,
            "metrics": metrics,
        }
