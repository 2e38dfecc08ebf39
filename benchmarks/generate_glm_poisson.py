"""Data from ``--seed`` for a sparse fixed-effect Poisson GLM.

``generate.sparse_glm``'s rows with count labels: the ids (one per stride of
``dim / nnz``, uniform inside it, ascending in a row) and the values
(standard normal clipped to +-9) are drawn as ``generate.sparse_glm`` draws
them from the same streams, so one ``structure_seed`` gives the two
configurations the same feature matrix; the labels are
``Poisson(exp(vals . w_true))`` with ``w_true ~ 0.1 N(0, 1)`` by position (every
id of stride ``j`` carries ``w_true[j]``), float32.

``generate.py``'s two rules hold: the data set belongs to the
configuration's ``structure_seed``, and ``--seed`` gives its rows in another
order.  Nothing is imported from the program.
"""

from __future__ import annotations

import numpy as np

from benchmarks.generate import SparseGlmData, _fill_normal, _rng
from benchmarks.generate_game_sparse import _fill_ids

W_TRUE_SCALE = 0.1


def glm_poisson(sizes: dict, seed: int) -> SparseGlmData:
    n, nnz, dim = int(sizes["rows"]), int(sizes["nnz_per_row"]), int(sizes["dim"])
    content = sizes["structure_seed"]
    # The data set, from the configuration's own seed (generate.sparse_glm's
    # streams 0 and 1 for the ids and the values).
    ids = np.empty((n, nnz), np.int32)
    _fill_ids(ids, dim // nnz, content, 0)
    vals = np.empty((n, nnz), np.float32)
    _fill_normal(vals, content, 1)
    np.clip(vals, -9.0, 9.0, out=vals)
    w_true = (
        _rng(content, 2).standard_normal(nnz) * W_TRUE_SCALE
    ).astype(np.float32)
    label = _rng(content, 3).poisson(np.exp(vals @ w_true)).astype(np.float32)
    # --seed: the same rows in another order.
    order = _rng(seed, 4).permutation(n)
    return SparseGlmData(ids=ids[order], vals=vals[order],
                         label=label[order], dim=dim)
