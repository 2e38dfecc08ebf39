"""``game.fit`` for a data set that does not fit one device whole: the same
plain GAME fit, computed in row blocks and entity blocks.

At ``game_config5_host_share``'s size (12.8 M training rows) the fixed
effect's ``[rows, 128]`` float32 block is 6.6 GB, every ``[rows, 16]`` block
is tiled to 128 lanes on the device (6.6 GB again), and the entities' padded
``[entities, rows, 16]`` blocks are 9 GB a coordinate: ``game.fit`` holds all
of them at once.  Here, on the one device the reference runs on:

* **row blocks**: the fixed effect's features stay on the device as a list
  of ``ROW_BLOCK``-row blocks; its objective and gradient are the sums of
  the blocks' (``game._fixed_value_grad`` a block, the L2 term counted
  once), its scores the blocks' scores end to end.  A random effect's
  scores are taken block by block from the host's array;
* **entity blocks**: each power-of-two row-capacity class of
  ``game.entity_blocks`` is cut into blocks of at most ``BLOCK_CELLS``
  entity x row cells, gathered on the host once and handed to
  ``game._newton_solve`` one at a time.  A class's last block is filled up
  to the common size with entities of no rows, which are solved to 0 and
  written to a spare table row that is dropped at the end: one compiled
  program a class.

Nothing else differs: the per-block functions, the host L-BFGS, the Newton
iteration, the validation metrics, float32 and ``highest`` are ``game.py``'s
own, imported from it.  In one block the result is ``game.fit``'s to the last
bit.  In several, a float32 sum taken block by block rounds otherwise than
one taken whole, and an entity solved in another batch stops at another
point of the flat its Newton iteration ends on (its step is accepted on the
objective's value, which float32 stops telling apart about a thousandth of
a coefficient from the optimum): ``compare`` of the two reads at most a
fifth of any of the cell's limits (``tests/test_game_mesh_cell.py``, at a
size both can hold).  ``lowp`` and ``weight`` are ``game.fit``'s control and
planted fault.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import game
from benchmarks.reference.common import round_to as _round
from benchmarks.reference.lbfgs import lbfgs

ROW_BLOCK = 1 << 20
BLOCK_CELLS = 1 << 20  # [cells, 16] float32 is 512 MB on the device, tiled


def _row_spans(n: int, block: int) -> list:
    return [(s, min(s + block, n)) for s in range(0, n, block)]


def _entity_blocks(entity_ids: np.ndarray, n_entities: int,
                   block_cells: int) -> list:
    """``game.entity_blocks`` with every class cut into equal blocks of at
    most ``block_cells`` cells; filling entities carry index
    ``n_entities`` (the spare row), row 0 and mask 0."""
    blocks = []
    for entities, index, mask in game.entity_blocks(entity_ids, n_entities):
        count, capacity = index.shape
        parts = max(1, -(-count * capacity // block_cells))
        size = -(-count // parts)
        fill = parts * size - count
        entities = np.concatenate(
            [entities, np.full(fill, n_entities, entities.dtype)])
        index = np.concatenate([index, np.zeros((fill, capacity), index.dtype)])
        mask = np.concatenate([mask, np.zeros((fill, capacity), mask.dtype)])
        for p in range(parts):
            cut = slice(p * size, (p + 1) * size)
            blocks.append((entities[cut], index[cut], mask[cut]))
    return blocks


def fit(data, spec: dict, lowp: bool = False, weight=None,
        row_block: int = ROW_BLOCK, block_cells: int = BLOCK_CELLS) -> dict:
    """``game.fit``'s arguments and result; ``row_block`` and
    ``block_cells`` are the block sizes (the tests shrink them)."""
    train, val = data.train, data.validation
    n = train.rows
    l2 = jnp.float32(spec["l2"])
    weight_np = np.ones(n, np.float32) if weight is None else weight
    weight_dev = jnp.asarray(weight_np)
    spans, val_spans = _row_spans(n, row_block), _row_spans(val.rows, row_block)
    with jax.default_matmul_precision("highest"):
        y = jnp.asarray(train.label)
        x_fixed = [_round(jnp.asarray(train.x_fixed[s:e]), lowp)
                   for s, e in spans]
        x_fixed_val = [_round(jnp.asarray(val.x_fixed[s:e]), lowp)
                       for s, e in val_spans]
        names = ("fixed",) + tuple(data.coordinates)
        random = {}
        for name in data.coordinates:
            ids = train.entity_ids[name]
            blocks = []
            for entities, index, mask in _entity_blocks(
                    ids, data.n_entities, block_cells):
                index_dev = jnp.asarray(index)
                blocks.append({
                    "entities": jnp.asarray(entities),
                    "index": index_dev,
                    "x_host": train.x_random[name][index],
                    "y": jnp.take(y, index_dev, axis=0),
                    "mask": jnp.asarray(mask) * jnp.take(
                        weight_dev, index_dev, axis=0
                    ),
                })
            random[name] = {"ids": jnp.asarray(ids), "blocks": blocks,
                            "ids_val": jnp.asarray(val.entity_ids[name])}
        coefficients = {
            "fixed": jnp.zeros(train.x_fixed.shape[1], jnp.float32),
            **{
                name: jnp.zeros(
                    (data.n_entities + 1, train.x_random[name].shape[1]),
                    jnp.float32,
                )
                for name in data.coordinates
            },
        }
        scores = {name: jnp.zeros(n, jnp.float32) for name in names}
        val_scores = {
            name: jnp.zeros(val.rows, jnp.float32) for name in names
        }

        rows_fixed = [(y[s:e], weight_dev[s:e]) for s, e in spans]

        def fixed_value_grad(w, offsets):
            value, grad = 0.5 * l2 * jnp.dot(w, w), l2 * w
            for x, (y_b, weight_b), offset_b in zip(x_fixed, rows_fixed,
                                                    offsets):
                # The block's own L2 term is switched off: it is added once.
                v, g = game._fixed_value_grad(
                    w, x, y_b, offset_b, weight_b, jnp.float32(0.0),
                    lowp=lowp,
                )
                value, grad = value + v, grad + g
            return value, grad

        def random_scores(table, x_host, ids, row_spans):
            return jnp.concatenate([
                game._random_scores(
                    table, _round(jnp.asarray(x_host[s:e]), lowp), ids[s:e])
                for s, e in row_spans
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
                    scores[name] = jnp.concatenate(
                        [x @ _round(w, lowp) for x in x_fixed])
                    val_scores[name] = jnp.concatenate(
                        [x @ _round(w, lowp) for x in x_fixed_val])
                    continue
                coord = random[name]
                table = coefficients[name]
                for block in coord["blocks"]:
                    solved = game._newton_solve(
                        jnp.take(table, block["entities"], axis=0),
                        _round(jnp.asarray(block["x_host"]), lowp),
                        block["y"], block["mask"],
                        jnp.take(offset, block["index"], axis=0), l2,
                        lowp=lowp,
                    )
                    table = table.at[block["entities"]].set(solved)
                coefficients[name] = table
                scores[name] = random_scores(
                    _round(table, lowp), train.x_random[name], coord["ids"],
                    spans)
                val_scores[name] = random_scores(
                    _round(table, lowp), val.x_random[name], coord["ids_val"],
                    val_spans)
            total = np.asarray(sum(val_scores.values()), np.float64)
            metrics.append({
                "AUC": game.auc(total, val.label),
                "LOGISTIC_LOSS": game.mean_logistic_loss(total, val.label),
            })
        coefficients = {
            k: np.asarray(v if k == "fixed" else v[:data.n_entities])
            for k, v in coefficients.items()
        }
        return {
            "coefficients": coefficients,
            "fixed_values": fixed_values,
            "fixed_grad0": fixed_grad0,
            "metrics": metrics,
        }
