"""Data from ``--seed`` for every cell: the one general generator.

A configuration file names a ``generator`` (``sparse_glm`` or ``game``) and
its sizes; the generator draws the data.  Two rules make the runs of one
cell alike whatever the seed:

* **The data set belongs to the configuration, its order to the seed.**  A
  fit's work is not only its shapes: how many iterations an optimizer runs
  before its tolerance fires, and how many Newton steps the slowest entity of
  a bin needs, depend on the values and the labels (measured: GAME fits of
  differently drawn data of one shape differed by 9 % in time).  So the rows
  themselves (ids, values, labels, rows per entity, the validation split) are
  drawn from the configuration's ``structure_seed``, and ``--seed`` gives
  them in another order: it permutes the sparse GLM's rows, and for GAME
  renames the entities and permutes the validation rows.  A new seed is new
  inputs of exactly the same shapes and the same work: nothing recompiles
  and the fit time does not move with the seed.
* **No file round-trip.**  Arrays go straight from the generator to the
  program's own containers.

The arithmetic is a copy of ``chip_smoke.write_libsvm`` (sparse GLM: one
feature id per stride, standard-normal values clipped to +-9, labels from a
logistic model over the row's positions) and of
``photon_tpu.data.synthetic.make_game_data`` (GAME: geometric rows per
entity, dense standard-normal blocks whose last column is the intercept,
labels from fixed + per-entity logistic effects).  Nothing is imported from
the program.
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# Independent numpy generators fill disjoint chunks in parallel (numpy
# releases the GIL inside a fill); the result depends on the chunking, never
# on the number of threads.
_THREADS = 4
_CHUNK_ROWS = 1 << 18


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def _chunks(n: int):
    return [(s, min(s + _CHUNK_ROWS, n)) for s in range(0, n, _CHUNK_ROWS)]


def _fill_normal(out: np.ndarray, seed: int, stream: int) -> None:
    """Standard normals into ``out`` ([n, k] float32), chunk by chunk."""
    spans = _chunks(out.shape[0])

    def fill(i):
        s, e = spans[i]
        _rng(seed, stream, i).standard_normal(
            out=out[s:e], dtype=np.float32
        )

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, range(len(spans))))


# -- sparse fixed-effect GLM ---------------------------------------------------


@dataclasses.dataclass
class SparseGlmData:
    ids: np.ndarray  # [n, nnz] int32, one id per stride, ascending in a row
    vals: np.ndarray  # [n, nnz] float32
    label: np.ndarray  # [n] float32 in {0, 1}
    dim: int

    @property
    def rows(self) -> int:
        return self.ids.shape[0]

    @property
    def entries(self) -> int:
        return int(self.ids.size)

    @property
    def fit_rows(self) -> int:
        return self.rows


def sparse_glm(sizes: dict, seed: int) -> SparseGlmData:
    n, nnz, dim = int(sizes["rows"]), int(sizes["nnz_per_row"]), int(sizes["dim"])
    stride = dim // nnz
    # The data set, from the configuration's own seed.
    ids = np.empty((n, nnz), np.int32)
    spans = _chunks(n)
    base = (np.arange(nnz, dtype=np.int32) * stride)[None, :]

    def fill_ids(i):
        s, e = spans[i]
        ids[s:e] = base + _rng(sizes["structure_seed"], 0, i).integers(
            0, stride, size=(e - s, nnz), dtype=np.int32
        )

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill_ids, range(len(spans))))
    content = sizes["structure_seed"]
    vals = np.empty((n, nnz), np.float32)
    _fill_normal(vals, content, 1)
    np.clip(vals, -9.0, 9.0, out=vals)
    w_true = (_rng(content, 2).standard_normal(nnz) * 0.5).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(vals @ w_true)))
    label = (
        _rng(content, 3).random(n, dtype=np.float32) < p
    ).astype(np.float32)
    # --seed: the same rows in another order.
    order = _rng(seed, 4).permutation(n)
    return SparseGlmData(ids=ids[order], vals=vals[order],
                         label=label[order], dim=dim)


# -- GAME ----------------------------------------------------------------------


@dataclasses.dataclass
class GameSplit:
    """One side (train or validation) of a GAME data set."""

    x_fixed: np.ndarray  # [n, fixed_dim] float32
    x_random: dict  # name -> [n, random_dim] float32
    entity_ids: dict  # name -> [n] int64
    label: np.ndarray  # [n] float32

    @property
    def rows(self) -> int:
        return len(self.label)


@dataclasses.dataclass
class GameData:
    train: GameSplit
    validation: GameSplit
    n_entities: int
    coordinates: tuple  # random-effect names, ("re0", "re1", ...)

    @property
    def fit_rows(self) -> int:
        return self.train.rows


def game_structure(sizes: dict) -> dict:
    """Rows per entity, each random coordinate's row -> entity map and the
    validation rows: the same for every ``--seed``."""
    rng = _rng(sizes["structure_seed"], 0)
    n_entities = int(sizes["entities_per_coordinate"])
    counts = np.maximum(
        1, rng.geometric(1.0 / sizes["rows_per_entity_mean"], n_entities)
    )
    n = int(counts.sum())
    ids0 = np.repeat(np.arange(n_entities, dtype=np.int64), counts)
    entity_ids = {}
    for c in range(int(sizes["random_coordinates"])):
        entity_ids[f"re{c}"] = ids0 if c == 0 else ids0[rng.permutation(n)]
    perm = rng.permutation(n)
    n_val = min(n - 1, max(1, int(round(n * sizes["validation_split"]))))
    return {
        "rows": n,
        "entity_ids": entity_ids,
        "validation_rows": np.sort(perm[:n_val]),
        "train_rows": np.sort(perm[n_val:]),
    }


def game(sizes: dict, seed: int) -> GameData:
    structure = game_structure(sizes)
    content = sizes["structure_seed"]
    n_entities = int(sizes["entities_per_coordinate"])
    fixed_dim, random_dim = int(sizes["fixed_dim"]), int(sizes["random_dim"])
    names = tuple(structure["entity_ids"])
    w_fixed = (
        _rng(content, 10).standard_normal(fixed_dim) * 0.5
    ).astype(np.float32)
    w_random = {
        name: (
            _rng(content, 11, c).standard_normal((n_entities, random_dim))
            * 0.5
        ).astype(np.float32)
        for c, name in enumerate(names)
    }
    # --seed: every entity under another id, the validation rows in another
    # order.  The training rows keep theirs: an entity's solve sums its rows
    # in data order, a bin iterates until its SLOWEST entity converges, and
    # another rounding in 40,000 entities moves that maximum (measured: fits
    # of one data set with its training rows reordered differed by 2 % in
    # time, in steps; with the order kept, by 0.03 %).
    rename = {
        name: _rng(seed, 12, c).permutation(n_entities)
        for c, name in enumerate(names)
    }

    def split(rows: np.ndarray, stream: int, reorder: bool) -> GameSplit:
        n = len(rows)
        order = (
            _rng(seed, stream).permutation(n) if reorder else np.arange(n)
        )
        x_fixed = np.empty((n, fixed_dim), np.float32)
        _fill_normal(x_fixed, content, stream)
        x_fixed[:, -1] = 1.0  # intercept
        z = x_fixed @ w_fixed
        x_random, ids = {}, {}
        for c, name in enumerate(names):
            xr = np.empty((n, random_dim), np.float32)
            _fill_normal(xr, content, stream + 1 + c)
            xr[:, -1] = 1.0
            entity = structure["entity_ids"][name][rows]
            z += np.einsum("nd,nd->n", xr, w_random[name][entity])
            x_random[name] = xr[order]
            ids[name] = rename[name][entity[order]]
        p = 1.0 / (1.0 + np.exp(-z))
        label = (
            _rng(content, stream + 9).random(n, dtype=np.float32) < p
        ).astype(np.float32)
        return GameSplit(x_fixed=x_fixed[order], x_random=x_random,
                         entity_ids=ids, label=label[order])

    return GameData(
        train=split(structure["train_rows"], 20, reorder=False),
        validation=split(structure["validation_rows"], 40, reorder=True),
        n_entities=n_entities,
        coordinates=names,
    )


GENERATORS = {"sparse_glm": sparse_glm, "game": game}


def make(config: dict, seed: int):
    """The data of one configuration (a loaded ``configs/<name>.json``)."""
    return GENERATORS[config["generator"]](config["sizes"], seed)
