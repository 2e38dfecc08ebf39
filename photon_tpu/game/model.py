"""GAME models: fixed-effect, random-effect, and the composite GameModel.

Rebuild of the reference's photon-api model layer (SURVEY.md §2.2 'GAME
models'): ``FixedEffectModel`` (broadcast coefficients + feature-shard id),
``RandomEffectModel`` (an ``RDD[(entityId, GeneralizedLinearModel)]``), and
``GameModel`` (ordered per-coordinate container), plus the scoring join
(``ModelDataScores`` accumulation — SURVEY.md §3.3).

TPU-native shape: a random-effect model is a dense coefficient **table**
``[num_entities, dim]`` resident in device memory — the per-entity model RDD
collapses into one array, and the scoring-time shuffle-join becomes a gather
by entity index.  Per-coordinate scores are raw margins (no offset, no link);
the dataset offset is added once when combining, exactly like the
reference's ``CoordinateDataScores -> ModelDataScores`` accumulation.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.core.losses import get_loss
from photon_tpu.data.batch import DenseBatch, SparseBatch
from photon_tpu.game.data import (
    DenseShard,
    GameDataset,
    Shard,
    SparseShard,
    keys_match,
)
from photon_tpu.parallel.mesh import to_host
from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel, model_for_task
from photon_tpu.utils import pow2_at_least
from photon_tpu.utils.device import named_jit

Array = jax.Array


def shard_to_batch(
    shard: Shard,
    label: np.ndarray,
    offset: Optional[np.ndarray] = None,
    weight: Optional[np.ndarray] = None,
):
    """Device batch for one feature shard of a GameDataset."""
    n = len(label)
    label = jnp.asarray(label, jnp.float32)
    offset = (
        jnp.zeros(n, jnp.float32) if offset is None else jnp.asarray(offset, jnp.float32)
    )
    weight = (
        jnp.ones(n, jnp.float32) if weight is None else jnp.asarray(weight, jnp.float32)
    )
    if isinstance(shard, DenseShard):
        return DenseBatch(jnp.asarray(shard.x), label, offset, weight)
    return SparseBatch(
        jnp.asarray(shard.ids), jnp.asarray(shard.vals), label, offset, weight
    )


@partial(named_jit, "score_fixed", static_argnames=("dense", "out_len"))
def _fixed_margins(
    w: Array, feats, dense: bool, out_len: Optional[int] = None
) -> Array:
    """``X w`` a row, by the form of ``feats``: a dense ``[n, d]`` matrix, a
    padded-COO ``(ids, vals)`` pair (an XLA gather of ``w`` at the ids), or
    the shard's :class:`~photon_tpu.ops.block_tiles.BlockTiles` (the
    ``blocked`` kernel's ``Xw``, both accesses in VMEM: the same float32
    products, summed in another order), cut to ``out_len`` rows (the tiles
    may hold row-capacity pad rows past them)."""
    if dense:
        return feats @ w
    if not isinstance(feats, tuple):
        from photon_tpu.ops.block_tiles import block_tiles_product

        with jax.named_scope("score_fixed/blocked_xw"):
            return block_tiles_product(
                w, feats, feats.n_rows if out_len is None else out_len
            )
    ids, vals = feats
    with jax.named_scope("score_fixed/gather"):
        gathered = jnp.take(w, ids, axis=0)
    with jax.named_scope("score_fixed/reduce"):
        return jnp.sum(gathered * vals, axis=-1)


def count_sparse_entries(
    telemetry, coordinate: str, feats, dense: bool,
    entries: Optional[int] = None,
) -> None:
    """What a sparse fixed-effect score is about to do, added where the
    score is dispatched, from static shapes (no device value is touched):
    ``score.sparse_entries{coordinate}``, the padded-COO entries of the rows
    scored, and ``score.fixed_dispatches{coordinate, kernel}``, one count by
    the form of ``feats`` (``gather``: an ``(ids, vals)`` pair; ``blocked``:
    tiles).  Tiles do not know the shard's entries a row (their slots carry
    the layout's padding), so their holder gives ``entries`` (rows scored x
    the shard's ``k``): the same number whichever kernel runs.  A dense
    shard counts nothing."""
    if dense:
        return
    tiled = not isinstance(feats, tuple)
    telemetry.counter("score.sparse_entries", coordinate=coordinate).inc(
        entries if tiled else feats[0].size
    )
    telemetry.counter(
        "score.fixed_dispatches", coordinate=coordinate,
        kernel="blocked" if tiled else "gather",
    ).inc()


@partial(jax.jit, static_argnames=("dense",))
def serving_gather_margins(table, safe_idx: Array, feats, dense: bool) -> Array:
    """Margins via the serving gather convention: ``safe_idx`` is already
    in-table (unknown entities pre-mapped to the trailing all-zero row by the
    caller — :meth:`RandomEffectModel.serving_table`), so the gather itself
    produces the fixed-effect-only fallback with no output mask.  The online
    scoring hot path (photon_tpu.serving) runs this inside its per-bucket
    compiled programs; it is defined HERE so the serving path and the batch
    ``margins_device`` path share one model layer.

    ``table`` is the serving STORAGE form (ISSUE 17 precision tiers): an
    f32 or bf16 ``[capacity, dim]`` array, or an int8 ``(q, scale)`` tuple
    (per-row absmax scale).  The gather moves the narrow stored bytes —
    that IS the bandwidth win — and the decode runs on the gathered
    ``[n, d]`` block; every multiply-accumulate stays f32.  The storage
    form is part of the traced pytree structure, so each dtype compiles
    its own bucket program at warmup and never again."""
    if isinstance(table, tuple):
        q, scale = table
        row_scale = scale[safe_idx].astype(jnp.float32)
        if dense:
            rows = q[safe_idx].astype(jnp.float32) * row_scale[:, None]
            return jnp.einsum("nd,nd->n", feats, rows)
        ids, vals = feats
        gathered = q[safe_idx[:, None], ids].astype(jnp.float32)
        return jnp.sum(gathered * row_scale[:, None] * vals, axis=-1)
    if dense:
        return jnp.einsum(
            "nd,nd->n", feats, table[safe_idx].astype(jnp.float32)
        )
    ids, vals = feats
    return jnp.sum(
        table[safe_idx[:, None], ids].astype(jnp.float32) * vals, axis=-1
    )


@partial(named_jit, "score_random", static_argnames=("dense",))
def _random_margins(table: Array, entity_idx: Array, feats, dense: bool) -> Array:
    """Margins via gather of per-row entity coefficients; unseen entities -> 0."""
    safe = jnp.maximum(entity_idx, 0)
    if dense:
        m = jnp.einsum("nd,nd->n", feats, table[safe])
    else:
        ids, vals = feats
        # table[entity, feature] gathered per nonzero: [n, k].
        m = jnp.sum(table[safe[:, None], ids] * vals, axis=-1)
    return jnp.where(entity_idx >= 0, m, 0.0)


def _shard_feats(shard: Shard):
    if isinstance(shard, DenseShard):
        return jnp.asarray(shard.x), True
    return (jnp.asarray(shard.ids), jnp.asarray(shard.vals)), False


def _shard_feats_padded(shard: Shard, n_pad: int):
    """Host-side feature leaves padded to ``n_pad`` rows (zero rows on the
    padding — they produce zero margins and carry weight 0 everywhere), in
    upload-ready numpy form: ``(leaves, dense)`` like :func:`_shard_feats`.
    """
    if isinstance(shard, DenseShard):
        x = shard.x
        if n_pad != x.shape[0]:
            x = np.pad(x, [(0, n_pad - x.shape[0]), (0, 0)])
        return x, True
    ids, vals = shard.ids, shard.vals
    if n_pad != ids.shape[0]:
        widths = [(0, n_pad - ids.shape[0]), (0, 0)]
        ids, vals = np.pad(ids, widths), np.pad(vals, widths)
    return (ids, vals), False


@dataclasses.dataclass(frozen=True)
class FixedEffectModel:
    """Global GLM on one feature shard (reference: FixedEffectModel)."""

    model: GeneralizedLinearModel
    shard_name: str

    @property
    def coefficients(self) -> Coefficients:
        return self.model.coefficients

    def score(self, data: GameDataset) -> np.ndarray:
        """Raw margins ``w . x_i`` over the dataset's shard (no offset)."""
        feats, dense = _shard_feats(data.shard(self.shard_name))
        return to_host(_fixed_margins(self.coefficients.means, feats, dense))

    def margins_device(
        self, feats, dense: bool, out_len: Optional[int] = None
    ) -> Array:
        """Device-resident margins against pre-uploaded shard features —
        the residual engine's scoring path (no host round-trip).  ``feats``
        in any form :func:`_fixed_margins` takes; ``out_len`` goes with
        tiles."""
        return _fixed_margins(
            jnp.asarray(self.coefficients.means), feats, dense, out_len=out_len
        )

    def serving_weights(self, mesh=None) -> Array:
        """Device-resident coefficient vector for the online scoring
        service: placed once (replicated — every shard reads the whole
        vector) and then closed over by every pre-compiled bucket program,
        so serving requests never re-upload model state."""
        from photon_tpu.parallel.mesh import put_replicated

        return put_replicated(
            jnp.asarray(self.coefficients.means, jnp.float32), mesh
        )


@dataclasses.dataclass(frozen=True)
class RandomEffectModel:
    """Per-entity coefficient table for one random-effect coordinate.

    ``table[i]`` is entity ``keys[i]``'s coefficient vector; entities never
    seen in training keep (implicit) zero coefficients and contribute zero
    score — matching the reference's left-outer scoring join.
    """

    table: Array  # [num_entities, dim]
    keys: np.ndarray  # sorted entity vocabulary
    entity_column: str
    shard_name: str
    task_type: str
    variances: Optional[Array] = None  # [num_entities, dim]

    @property
    def num_entities(self) -> int:
        return len(self.keys)

    @property
    def dim(self) -> int:
        return self.table.shape[1]

    def model_for_entity(self, key) -> Optional[GeneralizedLinearModel]:
        """Single-entity view (the reference's per-entity GLM objects)."""
        idx = np.searchsorted(self.keys, key)
        if idx >= len(self.keys) or self.keys[idx] != key:
            return None
        variances = None if self.variances is None else self.variances[idx]
        return model_for_task(self.task_type, Coefficients(self.table[idx], variances))

    def with_entities(self, keys: np.ndarray) -> "RandomEffectModel":
        """Grow this model to a larger entity vocabulary ON DEVICE — the
        incremental-onboarding warm start.

        ``keys`` is the merged (superset) vocabulary — typically the
        onboarded device data's ``dataset.keys``.  Existing entities keep
        their rows, scattered to their new sorted positions with one device
        scatter (no host table rebuild); new entities start at zero
        coefficients (they have never been fit), exactly what a cold start
        would give them.  Variances scatter alongside when present."""
        merged = np.asarray(keys)
        from photon_tpu.game.data import entity_index_for

        idx = entity_index_for(self.keys, merged)
        if (idx < 0).any():
            raise ValueError(
                "with_entities() grows the vocabulary: every existing "
                "entity key must appear in the merged keys"
            )
        idx_dev = jnp.asarray(idx)

        def scatter(table):
            grown = jnp.zeros((len(merged), self.dim), jnp.float32)
            return grown.at[idx_dev].set(jnp.asarray(table, jnp.float32))

        return dataclasses.replace(
            self,
            table=scatter(self.table),
            keys=merged,
            variances=(
                None if self.variances is None else scatter(self.variances)
            ),
        )

    def score(self, data: GameDataset) -> np.ndarray:
        from photon_tpu.game.data import entity_index_for

        entity_idx = entity_index_for(data.id_columns[self.entity_column], self.keys)
        feats, dense = _shard_feats(data.shard(self.shard_name))
        return to_host(
            _random_margins(self.table, jnp.asarray(entity_idx), feats, dense)
        )

    def margins_device(self, entity_idx: Array, feats, dense: bool) -> Array:
        """Device-resident margins against pre-uploaded shard features and a
        pre-computed per-row entity index — the residual engine's scoring
        path (the gather-join with no host round-trip)."""
        return _random_margins(jnp.asarray(self.table), entity_idx, feats, dense)

    @property
    def serving_capacity(self) -> int:
        """Default row capacity of this coordinate's serving gather table:
        the next power of two past ``num_entities + 1`` (entities + the
        zero row).  Amortized doubling — the headroom is what lets a GROWN
        vocabulary hot-swap into a live scorer in place: as long as the new
        ``num_entities + 1`` still fits the capacity, the table SHAPE (and
        with it every compiled bucket program) is unchanged and only the
        movable zero-row index advances."""
        return pow2_at_least(self.num_entities + 1)

    def serving_table(self, mesh=None, capacity: Optional[int] = None,
                      dtype: Optional[str] = None):
        """Flatten this coordinate's per-entity rows into ONE device-resident
        gather table for the online scoring service: ``[capacity, dim]``
        (default :attr:`serving_capacity` — amortized-doubling headroom),
        rows ``num_entities`` … ``capacity - 1`` all-zero, sharded over the
        mesh rows.

        Unknown entities (entity index -1) are pre-mapped by the scorer to
        the movable zero row at index ``num_entities``, so the serving
        gather yields exactly zero margin — the fixed-effect-only fallback
        — without a per-row output mask (photon_tpu.serving counts them as
        ``serving.cold_entities``).  Rows past ``num_entities`` — the
        capacity headroom AND whatever reshard_to_mesh's padding adds — are
        zero by construction, so any index into the tail stays harmless.

        ``capacity`` pins the table shape explicitly: a live scorer
        hot-swapping a grown model passes its SERVED capacity so the new
        table keeps the compiled programs' shape.  A vocabulary that no
        longer fits is a layout-shape change and is refused loudly — that
        rebuild boundary is the amortized-doubling contract.

        ``dtype`` picks the STORAGE precision tier (ISSUE 17):

        - ``"f32"`` (default) — today's exact table;
        - ``"bf16"`` — the same shape at half the bytes;
        - ``"int8"`` — an ``(q int8 [capacity, dim], scale f32 [capacity])``
          tuple: symmetric per-row absmax quantization, ~4x fewer gather
          bytes.  Headroom/zero rows have absmax 0, so their stored scale
          is 0 and the decoded margin is EXACTLY zero — the cold-entity
          fallback survives quantization bit-for-bit.

        All three forms feed :func:`serving_gather_margins`, which decodes
        on device after the gather and accumulates in f32."""
        from photon_tpu.game.lowp import check_dtype
        from photon_tpu.parallel.mesh import reshard_to_mesh

        dtype = check_dtype(dtype)
        rows = self.num_entities + 1
        capacity = self.serving_capacity if capacity is None else int(capacity)
        if rows > capacity:
            raise ValueError(
                f"serving_table: vocabulary ({self.num_entities} entities "
                f"+ zero row) exceeds the table capacity {capacity}; "
                "capacity growth is a layout-shape change — rebuild the "
                "scorer instead of hot-swapping"
            )
        table = jnp.concatenate(
            [
                jnp.asarray(self.table, jnp.float32),
                jnp.zeros((capacity - self.num_entities, self.dim),
                          jnp.float32),
            ]
        )
        if dtype == "bf16":
            return reshard_to_mesh(table.astype(jnp.bfloat16), mesh)
        if dtype == "int8":
            absmax = jnp.max(jnp.abs(table), axis=-1)
            scale = (absmax / 127.0).astype(jnp.float32)
            divisor = jnp.where(absmax > 0.0, absmax / 127.0, 1.0)
            q = jnp.clip(
                jnp.round(table / divisor[:, None]), -127.0, 127.0
            ).astype(jnp.int8)
            return (reshard_to_mesh(q, mesh), reshard_to_mesh(scale, mesh))
        return reshard_to_mesh(table, mesh)


CoordinateModel = "FixedEffectModel | RandomEffectModel"


class DeviceScoringCache:
    """Device-resident scoring-side data for one (validation) GameDataset.

    Holds everything the on-device validation pipeline needs to re-score a
    coordinate and evaluate metrics without touching host memory: per-shard
    feature blocks, labels and weights, per-id-column integer entity codes
    (for the segment-reduce sharded evaluators), and per-(column,
    vocabulary) row→entity indices.  Rows are padded to a multiple of the
    mesh size (padded rows carry weight 0 and entity index -1 — invisible
    to margins and metrics) and every per-row array is SHARDED over the
    data axis: one copy of the validation data across the mesh.

    Built once per estimator and shared across sweep configurations and
    descent runs — feature uploads happen once per shard, not once per
    (configuration × iteration) as the host path's ``GameModel.score`` did.
    """

    def __init__(self, data: GameDataset, mesh=None, telemetry=None):
        from photon_tpu.parallel.mesh import mesh_shards, pad_to_multiple
        from photon_tpu.telemetry import NULL_SESSION

        self.data = data
        self.mesh = mesh
        self.telemetry = telemetry or NULL_SESSION
        self.n = data.num_examples
        self.n_pad = pad_to_multiple(self.n, mesh_shards(mesh))
        self.device_bytes = 0
        self._feats: Dict[str, tuple] = {}
        # Sparse shards a fixed effect scores through block tiles: named by
        # the estimator (score_fixed_through_tiles), built on first use.
        self._tiled_shards: set = set()
        self._fixed_tiles: Dict[str, object] = {}
        self._entity_codes: Dict[str, Array] = {}
        self._entity_idx: Dict[str, tuple] = {}
        self.label = self._put(np.asarray(data.label, np.float32))
        self.weight = self._put(np.asarray(data.weight, np.float32))

    def _put(self, host: np.ndarray, pad_value=0) -> Array:
        """Upload one per-row host array padded + sharded, with transfer and
        residency accounting.  Logical rows in, mesh-padded sharded buffer
        out (reshard_to_mesh) — the cache is rebuilt per run against the
        CURRENT mesh, which is what keeps it out of the checkpoint: a
        resumed fit on a different device count pays one fresh upload here
        instead of carrying mesh-shaped state in the snapshot."""
        from photon_tpu.parallel.mesh import reshard_to_mesh

        dev = reshard_to_mesh(host, self.mesh, pad_value=pad_value)
        self._count_upload(dev.nbytes)
        return dev

    def _count_upload(self, nbytes: int) -> None:
        self.telemetry.counter(
            "descent.host_transfer_bytes", direction="h2d", path="validation"
        ).inc(nbytes)
        self.device_bytes += nbytes

    def feats(self, shard_name: str) -> tuple:
        """Shard ``shard_name``'s features as padded, sharded device leaves
        (uploaded on first use): ``(leaves, dense)``."""
        if shard_name not in self._feats:
            leaves, dense = _shard_feats_padded(
                self.data.shard(shard_name), self.n_pad
            )
            if dense:
                dev = self._put(leaves)
            else:
                dev = (self._put(leaves[0]), self._put(leaves[1]))
            self._feats[shard_name] = (dev, dense)
        return self._feats[shard_name]

    def score_fixed_through_tiles(self, shard_name: str) -> None:
        """Told by the cache's owner (the estimator, which owns the
        coordinates' training layouts too) that the fixed effect on
        ``shard_name`` trains on a batch that carries block tiles: the
        selector judged ``blocked`` for this shard, so the validation rows
        are scored through tiles of their own.  The cache asks no selector
        and runs no probe."""
        if shard_name not in self._fixed_tiles:
            self._tiled_shards.add(shard_name)

    def _fixed_feats(self, shard_name: str) -> tuple:
        """``(feats, dense, entries)`` a fixed effect scores ``shard_name``
        from: the shard's block tiles, built and uploaded once IN PLACE of
        its ``(ids, vals)``, where the owner named the shard, it is sparse,
        its rows are one block on one device and their grid can be tiled;
        else :meth:`feats`.  ``entries`` is the shard's padded-COO entry
        count either way (``score.sparse_entries``)."""
        tiles = self._fixed_tiles.get(shard_name)
        if tiles is None and shard_name in self._tiled_shards:
            # Decided once a naming: built, or the name is dropped.
            self._tiled_shards.discard(shard_name)
            tiles = self._build_fixed_tiles(shard_name)
            if tiles is not None:
                self._fixed_tiles[shard_name] = tiles
        if tiles is None:
            return self.feats(shard_name) + (None,)
        return tiles, False, self.n * self.data.shard(shard_name).ids.shape[1]

    def _build_fixed_tiles(self, shard_name: str):
        """The shard's rows as block tiles on the device, or ``None`` where
        :meth:`_fixed_feats` says they are not to be."""
        from photon_tpu.ops.block_tiles import (
            attach_block_tiles,
            block_tile_geometry,
        )

        shard = self.data.shard(shard_name)
        if (self.mesh is not None or not isinstance(shard, SparseShard)
                or block_tile_geometry(
                    self.n, shard.dim, shard.ids.size) is None):
            return None
        tiles = attach_block_tiles(shard.ids, shard.vals, shard.dim)
        self._count_upload(
            sum(leaf.nbytes for leaf in jax.tree.leaves(tiles))
        )
        return tiles

    def entity_index(self, column: str, keys: np.ndarray) -> Array:
        """Per-row entity index of ``column`` against ``keys`` (``[n_pad]``
        int32, -1 = unseen/padding), cached per column for the latest
        vocabulary — identity-checked first, so the common case (a model
        trained on this run's own vocabulary, every iteration) never pays
        the O(n) host key lookup again."""
        cached = self._entity_idx.get(column)
        if cached is not None:
            ref, arr, dev = cached
            # host-sync: key compare runs only for FOREIGN vocabularies
            # (warm starts loaded from disk); same-run models hit the
            # identity check.
            if keys_match(keys, ref, arr):
                return dev
        from photon_tpu.game.data import entity_index_for

        arr = np.asarray(keys)
        idx = entity_index_for(self.data.id_columns[column], arr)
        dev = self._put(idx.astype(np.int32), pad_value=-1)
        if cached is not None:
            # The replaced index buffer is dropped: keep the residency
            # gauge honest (device_bytes tracks LIVE bytes, not uploads).
            self.device_bytes -= cached[2].nbytes
        self._entity_idx[column] = (keys, arr, dev)
        return dev

    def entity_codes(self, column: str) -> tuple:
        """``(codes, num_segments)``: dense integer codes of ``column``'s
        raw entity keys (``[n_pad]`` int32; padding rows get a fresh code so
        they form their own — all weight-0, hence skipped — segment) plus
        the static segment count, for the segment-reduce sharded
        evaluators (``evaluation.metrics.sharded_metric_device``)."""
        if column not in self._entity_codes:
            uniq, codes = np.unique(self.data.id_columns[column],
                                    return_inverse=True)
            self._entity_codes[column] = (
                self._put(codes.astype(np.int32), pad_value=len(uniq)),
                len(uniq) + 1,
            )
        return self._entity_codes[column]

    def score(self, model, coordinate: str = "") -> Array:
        """Device-resident margins of one coordinate model over the cached
        (validation) rows — ``[n_pad]``, sharded, no host round-trip.
        ``coordinate`` is the update-sequence name the model is scored
        under (the label of ``score.sparse_entries``)."""
        if isinstance(model, FixedEffectModel):
            feats, dense, entries = self._fixed_feats(model.shard_name)
            count_sparse_entries(
                self.telemetry, coordinate, feats, dense, entries
            )
            return model.margins_device(feats, dense)
        if isinstance(model, RandomEffectModel):
            entity_idx = self.entity_index(model.entity_column, model.keys)
            feats, dense = self.feats(model.shard_name)
            return model.margins_device(entity_idx, feats, dense)
        raise TypeError(
            f"cannot device-score a {type(model).__name__}; expected "
            "FixedEffectModel or RandomEffectModel"
        )


@dataclasses.dataclass(frozen=True)
class GameModel:
    """Ordered per-coordinate model container (reference: GameModel).

    ``task_type`` fixes the link for prediction; coordinate order is the
    score-accumulation order (it does not affect the sum).
    """

    coordinates: Dict[str, object]  # name -> FixedEffectModel | RandomEffectModel
    task_type: str

    def coordinate(self, name: str):
        return self.coordinates[name]

    def score(self, data: GameDataset) -> np.ndarray:
        """Total raw score: dataset offset + sum of coordinate margins
        (reference: ModelDataScores accumulation, SURVEY.md §3.3)."""
        total = data.offset.astype(np.float64).copy()
        for model in self.coordinates.values():
            total += np.asarray(model.score(data), np.float64)
        return total.astype(np.float32)

    def predict(self, data: GameDataset) -> np.ndarray:
        """Apply the task's mean/inverse-link to the total score (e.g.
        sigmoid for logistic — SURVEY.md §3.3 'sigmoid for logistic')."""
        # get_loss resolves task-type names directly (core/losses.TASK_TO_LOSS).
        loss = get_loss(self.task_type)
        return np.asarray(loss.mean(jnp.asarray(self.score(data))))
