"""Data from ``--seed`` for GAME with a sparse fixed effect.

``generate.game``'s data set with its fixed shard swapped for
``generate.sparse_glm``'s: entities, rows per entity, the validation split,
the dense random shards and their true per-entity coefficients are drawn as
``generate.game`` draws them (``game_structure``, ``_rng``, ``_fill_normal``
are imported, and the streams are the same, so the two configurations of one
``structure_seed`` share entities, rows and random shards); the fixed shard
repeats ``generate.sparse_glm``'s arithmetic row for row: one feature id per
stride of ``fixed_dim / fixed_nnz_per_row``, uniform inside it, ascending in
a row; standard-normal values clipped to +-9; true coefficients by position
(every id of stride ``j`` carries ``w_true[j]``), no intercept column.  A
dense ``[rows, fixed_dim]`` block is never formed.

``generate.py``'s two rules hold: the data set belongs to the
configuration's ``structure_seed``; ``--seed`` renames the entities of every
coordinate and gives the validation rows in another order, and the training
rows keep theirs.  Nothing is imported from the program.
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.generate import (
    _THREADS,
    _chunks,
    _fill_normal,
    _rng,
    game_structure,
)


@dataclasses.dataclass
class SparseGameSplit:
    """One side (train or validation) of the data set."""

    ids_fixed: np.ndarray  # [n, nnz] int32, ascending in a row
    vals_fixed: np.ndarray  # [n, nnz] float32
    x_random: dict  # name -> [n, random_dim] float32
    entity_ids: dict  # name -> [n] int64
    label: np.ndarray  # [n] float32

    @property
    def rows(self) -> int:
        return len(self.label)


@dataclasses.dataclass
class SparseGameData:
    train: SparseGameSplit
    validation: SparseGameSplit
    n_entities: int
    coordinates: tuple  # random-effect names, ("re0", "re1", ...)
    fixed_dim: int

    @property
    def fit_rows(self) -> int:
        return self.train.rows


def _fill_ids(out: np.ndarray, stride: int, seed: int, stream: int) -> None:
    """One id per stride into ``out`` ([n, nnz] int32), chunk by chunk."""
    spans = _chunks(out.shape[0])
    base = (np.arange(out.shape[1], dtype=np.int32) * stride)[None, :]

    def fill(i):
        s, e = spans[i]
        out[s:e] = base + _rng(seed, stream, i).integers(
            0, stride, size=(e - s, out.shape[1]), dtype=np.int32
        )

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, range(len(spans))))


def game_sparse(sizes: dict, seed: int) -> SparseGameData:
    structure = game_structure(sizes)
    content = sizes["structure_seed"]
    n_entities = int(sizes["entities_per_coordinate"])
    fixed_dim, nnz = int(sizes["fixed_dim"]), int(sizes["fixed_nnz_per_row"])
    random_dim = int(sizes["random_dim"])
    stride = fixed_dim // nnz
    names = tuple(structure["entity_ids"])
    w_fixed = (
        _rng(content, 10).standard_normal(nnz) * 0.5
    ).astype(np.float32)
    w_random = {
        name: (
            _rng(content, 11, c).standard_normal((n_entities, random_dim))
            * 0.5
        ).astype(np.float32)
        for c, name in enumerate(names)
    }
    rename = {
        name: _rng(seed, 12, c).permutation(n_entities)
        for c, name in enumerate(names)
    }

    def split(rows: np.ndarray, stream: int, reorder: bool) -> SparseGameSplit:
        n = len(rows)
        order = _rng(seed, stream).permutation(n) if reorder else slice(None)
        ids_fixed = np.empty((n, nnz), np.int32)
        _fill_ids(ids_fixed, stride, content, stream + 8)
        vals_fixed = np.empty((n, nnz), np.float32)
        _fill_normal(vals_fixed, content, stream)
        np.clip(vals_fixed, -9.0, 9.0, out=vals_fixed)
        z = vals_fixed @ w_fixed
        x_random, ids = {}, {}
        for c, name in enumerate(names):
            xr = np.empty((n, random_dim), np.float32)
            _fill_normal(xr, content, stream + 1 + c)
            xr[:, -1] = 1.0  # intercept
            entity = structure["entity_ids"][name][rows]
            z += np.einsum("nd,nd->n", xr, w_random[name][entity])
            x_random[name] = xr[order] if reorder else xr
            ids[name] = rename[name][entity[order]]
        p = 1.0 / (1.0 + np.exp(-z))
        label = (
            _rng(content, stream + 9).random(n, dtype=np.float32) < p
        ).astype(np.float32)
        if reorder:
            ids_fixed, vals_fixed = ids_fixed[order], vals_fixed[order]
            label = label[order]
        return SparseGameSplit(ids_fixed=ids_fixed, vals_fixed=vals_fixed,
                               x_random=x_random, entity_ids=ids, label=label)

    return SparseGameData(
        train=split(structure["train_rows"], 20, reorder=False),
        validation=split(structure["validation_rows"], 40, reorder=True),
        n_entities=n_entities,
        coordinates=names,
        fixed_dim=fixed_dim,
    )
