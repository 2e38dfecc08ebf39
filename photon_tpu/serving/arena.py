"""Multi-model serving arena: N tenant models in ONE gather-table
allocation and ONE compiled bucket ladder (ISSUE 18).

No production GAME deployment serves one model — per-market variants and
A/B arms mean a fleet hosts many small models at once.  Pre-arena, each
``GameScorer`` paid its own device allocation and its own compiled
bucket ladder, so compiled-program count and table bytes both scaled
with model count.  The arena collapses that: per random coordinate, ONE
``[arena_rows, dim]`` gather table (stored at the PR 17 precision tier)
holds every hosted model's rows at per-model row OFFSETS, and per fixed
coordinate one ``[model_slots, dim]`` stacked weight table holds every
model's coefficient vector at its slot row.  Model identity is NOT
compiled into anything: every bucket program takes a per-row global
gather index and a per-row model-slot vector as ARGUMENTS, so the
programs are keyed on (bucket shape x coordinate layout x dtype) only —
hosting the 9th model compiles exactly nothing.

Residency/allocation contract:

- onboarding, retiring, or refreshing a model is a SLICE SCATTER
  (``lax.dynamic_update_slice`` at the model's base row, traced base so
  offsets never recompile) — no host re-upload of any untouched model's
  rows, no change to the compiled footprint;
- per-model slots carry amortized-doubling headroom (next pow2 past
  ``entities + 1``, times ``table_capacity_factor``) so a refreshed
  model whose vocabulary grew within its slot republishes in place; a
  model that outgrows its slot MIGRATES to a larger free extent (still
  zero recompiles — only its base offset moves); only when the whole
  arena is out of free rows does capacity double, which rebuilds the
  tables and the ladder (the documented "arena-growth migration"
  boundary, surfaced by a ``layout_version`` bump);
- the hot path keeps the scorer's contract: one compiled dispatch + ONE
  host sync per micro-batch; the entity join AND the model->slot
  resolution run host-side at ingest (the sanctioned edge), so cold
  entities are counted on host for free and the device program has no
  per-model branches at all.

``serving.arena_bytes`` / ``serving.arena_models`` gauge the shared
allocation; the serving bench asserts arena bytes stay within 1.15x the
sum of the hosted models' solo tables.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.game.data import entity_index_for
from photon_tpu.game.model import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
    serving_gather_margins,
)
from photon_tpu.parallel.mesh import (
    abstract_like,
    mesh_shards,
    pad_to_multiple,
    put_replicated,
    put_request,
)
from photon_tpu.serving.scorer import (
    DEFAULT_MAX_BATCH,
    DEFAULT_MIN_BUCKET,
    ScoringRequest,
    ShardSpec,
    _pad_rows,
    bucket_ladder,
    devices_of,
    padded_cost,
    request_spec_for_model,
    slice_request,
)
from photon_tpu.utils import pow2_at_least


@jax.jit
def _scatter_rows(table, update, base):
    """Row-slice scatter at a TRACED base offset: one compile per
    (table shape, update shape) pair, reused for every model/offset."""
    return jax.lax.dynamic_update_slice(
        table, update, (base, jnp.int32(0))
    )


@jax.jit
def _scatter_vec(vec, update, base):
    """1-D twin of :func:`_scatter_rows` (int8 per-row scale vectors)."""
    return jax.lax.dynamic_update_slice(vec, update, (base,))


def _encode_slot_rows(table, slot_rows: int, dim: int, dtype: str):
    """One model's coefficient table as a ``[slot_rows, dim]`` storage-
    form block: vocabulary rows first, then all-zero rows (the movable
    zero row + headroom).  Device-side — mirrors
    :meth:`RandomEffectModel.serving_table`'s encode so the arena slice
    and a solo scorer's table hold byte-identical content."""
    table = jnp.asarray(table, jnp.float32)
    block = jnp.concatenate(
        [table, jnp.zeros((slot_rows - table.shape[0], dim), jnp.float32)]
    )
    if dtype == "bf16":
        return block.astype(jnp.bfloat16)
    if dtype == "int8":
        absmax = jnp.max(jnp.abs(block), axis=-1)
        scale = (absmax / 127.0).astype(jnp.float32)
        divisor = jnp.where(absmax > 0.0, absmax / 127.0, 1.0)
        q = jnp.clip(
            jnp.round(block / divisor[:, None]), -127.0, 127.0
        ).astype(jnp.int8)
        return (q, scale)
    return block


@dataclasses.dataclass(frozen=True)
class _ArenaCoord:
    """Static per-coordinate layout of the arena (the compiled shape)."""

    name: str
    kind: str  # "fixed" | "random"
    shard: str
    dim: int
    column: Optional[str] = None  # random: id column joined on
    rows: int = 0  # random: total arena rows (the table's first axis)


@dataclasses.dataclass(frozen=True)
class _Slot:
    """One hosted model's placement inside the arena.

    ``row`` indexes the fixed-coordinate weight stacks (and the host-side
    per-slot base/zero arrays); ``base``/``size`` give each random
    coordinate's extent; ``zero`` the GLOBAL index of the model's movable
    zero row (``base + num_entities``)."""

    row: int
    base: Dict[str, int]
    size: Dict[str, int]
    zero: Dict[str, int]
    vocab: Dict[str, np.ndarray]
    model: GameModel
    version: int


class _ArenaState:
    """Immutable host-side routing snapshot published alongside the device
    tables: model-id -> slot resolution (sorted ids + searchsorted, the
    same join idiom as the entity vocabulary) and per-slot base/zero
    arrays the ingest staging indexes per row."""

    def __init__(self, slots: Dict[str, _Slot], coords, model_slots: int):
        self.slots = dict(slots)
        ids = sorted(slots)
        # host-sync: ingest routing tables — host numpy by construction
        # (model ids never live on device).
        self.ids_sorted = np.asarray(ids, dtype=object)
        self.row_sorted = np.asarray(
            [slots[i].row for i in ids], np.int32
        )
        self.id_of_row = {s.row: i for i, s in slots.items()}
        self.base: Dict[str, np.ndarray] = {}
        self.zero: Dict[str, np.ndarray] = {}
        for c in coords:
            if c.kind != "random":
                continue
            base = np.zeros(model_slots, np.int32)
            zero = np.zeros(model_slots, np.int32)
            for s in slots.values():
                base[s.row] = s.base[c.name]
                zero[s.row] = s.zero[c.name]
            self.base[c.name] = base
            self.zero[c.name] = zero

    def row_of(self, model_id: str) -> int:
        slot = self.slots.get(model_id)
        if slot is None:
            raise KeyError(
                f"model {model_id!r} is not hosted in this arena "
                f"(hosted: {sorted(self.slots)})"
            )
        return slot.row

    def rows_for(self, model_ids: np.ndarray) -> np.ndarray:
        """Per-row slot rows for a mixed-model batch; unknown ids raise
        (a request for an unhosted model must shed loudly, not gather
        another tenant's rows)."""
        pos = entity_index_for(model_ids, self.ids_sorted)
        if (pos < 0).any():
            # host-sync: error-path formatting over the host id vector.
            bad = sorted(set(np.asarray(model_ids, dtype=object)[pos < 0]))
            raise KeyError(
                f"request routes to unhosted model(s) {bad!r} "
                f"(hosted: {sorted(self.slots)})"
            )
        return self.row_sorted[pos]


class ModelArena:
    """The shared device allocation: per-coordinate arena tables plus the
    extent/slot bookkeeping that makes onboard/retire/refresh a slice
    scatter.  Pure state management — the compiled programs live in
    :class:`MultiModelScorer`, which owns an arena and re-publishes its
    ``(tables, state)`` snapshots."""

    def __init__(
        self,
        models: Dict[str, GameModel],
        mesh=None,
        table_dtype: str = "f32",
        table_capacity_factor: int = 1,
        model_slots: Optional[int] = None,
        reserve_rows: int = 0,
        telemetry=None,
    ):
        from photon_tpu.game.lowp import check_dtype
        from photon_tpu.telemetry import NULL_SESSION

        if not models:
            raise ValueError("ModelArena needs at least one hosted model")
        self.mesh = mesh
        self.table_dtype = check_dtype(table_dtype)
        self.table_capacity_factor = max(1, int(table_capacity_factor))
        self.telemetry = telemetry or NULL_SESSION
        self.layout_version = 0
        self._rebuilds = 0
        self._lock = threading.Lock()
        first = next(iter(models.values()))
        self.default_id = next(iter(models))
        self._coord_template = self._template_of(first)
        for mid, model in models.items():
            self._check_layout(mid, model)

        # Fixed-coordinate stacking: one slot row per hosted model, with
        # pow2 headroom so onboarding stays recompile-free until the slot
        # count itself doubles.
        self.model_slots = int(
            model_slots
            if model_slots is not None
            else pow2_at_least(max(2 * len(models), 4))
        )
        if self.model_slots < len(models):
            raise ValueError(
                f"model_slots={self.model_slots} < {len(models)} models"
            )

        slot_sizes = {
            mid: self._slot_sizes(model) for mid, model in models.items()
        }
        self._capacity: Dict[str, int] = {}
        for name, _, _, _ in self._random_coords():
            need = sum(s[name] for s in slot_sizes.values())
            self._capacity[name] = pad_to_multiple(
                need + int(reserve_rows), max(1, mesh_shards(mesh))
            )
        self._free: Dict[str, List[Tuple[int, int]]] = {
            name: [] for name in self._capacity
        }
        self._free_rows_of_slots = list(range(self.model_slots))

        self.coords = self._build_coords()
        self.tables = self._alloc_tables()
        self.slots: Dict[str, _Slot] = {}
        cursor = {name: 0 for name in self._capacity}
        for mid, model in models.items():
            slot = self._place_slot(mid, model, slot_sizes[mid], cursor)
            self.tables = self._publish_slot(self.tables, slot, model)
        for name, cap in self._capacity.items():
            used = cursor[name]
            if used < cap:
                self._free[name].append((used, cap - used))
        self.state = _ArenaState(self.slots, self.coords, self.model_slots)
        jax.block_until_ready(self.tables)
        self._record_gauges()

    # -- layout helpers ----------------------------------------------------
    @staticmethod
    def _template_of(model: GameModel):
        out = []
        for name, coord in model.coordinates.items():
            if isinstance(coord, FixedEffectModel):
                out.append((name, "fixed", coord.shard_name,
                            int(len(coord.coefficients.means)), None))
            elif isinstance(coord, RandomEffectModel):
                out.append((name, "random", coord.shard_name,
                            int(coord.dim), coord.entity_column))
            else:
                raise TypeError(
                    f"cannot serve a {type(coord).__name__} coordinate"
                )
        return tuple(out)

    def _check_layout(self, model_id: str, model: GameModel) -> None:
        """Every hosted model must share ONE coordinate layout — the arena
        compiles one ladder for all of them, so a model with different
        coordinates/shards/dims cannot share the allocation."""
        got = self._template_of(model)
        if got != self._coord_template:
            raise ValueError(
                f"model {model_id!r} does not match the arena's coordinate "
                f"layout (arena {self._coord_template}, model {got}); "
                "every hosted model must share one coordinate layout"
            )

    def _random_coords(self):
        return [
            (name, shard, dim, column)
            for name, kind, shard, dim, column in self._coord_template
            if kind == "random"
        ]

    def _slot_sizes(self, model: GameModel) -> Dict[str, int]:
        """Per-random-coordinate slot rows for one model: the model's own
        amortized-doubling serving capacity (entities + zero row, next
        pow2, times the pre-provisioning factor) — the same headroom a
        solo scorer would allocate, so arena bytes track the sum of solo
        tables."""
        sizes = {}
        for name, coord in model.coordinates.items():
            if isinstance(coord, RandomEffectModel):
                sizes[name] = pow2_at_least(
                    self.table_capacity_factor * (coord.num_entities + 1)
                )
        return sizes

    def _build_coords(self) -> Tuple[_ArenaCoord, ...]:
        coords = []
        for name, kind, shard, dim, column in self._coord_template:
            coords.append(
                _ArenaCoord(
                    name, kind, shard, dim, column=column,
                    rows=self._capacity.get(name, 0),
                )
            )
        return tuple(coords)

    def _alloc_tables(self) -> tuple:
        """Fresh all-zero arena tables at the current capacities, in
        coordinate order: fixed -> ``[model_slots, dim]`` f32 replicated;
        random -> ``[rows, dim]`` storage-form, row-sharded like a solo
        serving table."""
        from photon_tpu.parallel.mesh import reshard_to_mesh

        tables = []
        for c in self.coords:
            if c.kind == "fixed":
                tables.append(
                    put_replicated(
                        jnp.zeros((self.model_slots, c.dim), jnp.float32),
                        self.mesh,
                    )
                )
            elif self.table_dtype == "int8":
                tables.append((
                    reshard_to_mesh(
                        jnp.zeros((c.rows, c.dim), jnp.int8), self.mesh
                    ),
                    reshard_to_mesh(
                        jnp.zeros((c.rows,), jnp.float32), self.mesh
                    ),
                ))
            else:
                dt = jnp.bfloat16 if self.table_dtype == "bf16" else jnp.float32
                tables.append(
                    reshard_to_mesh(
                        jnp.zeros((c.rows, c.dim), dt), self.mesh
                    )
                )
        return tuple(tables)

    # -- extent allocator --------------------------------------------------
    def _alloc_extent(self, name: str, size: int) -> Optional[int]:
        """Best-fit over the coordinate's free list; splits the remainder
        back.  Returns the base row, or None when no extent fits (the
        caller then grows the arena)."""
        best = None
        for i, (base, extent) in enumerate(self._free[name]):
            if extent >= size and (best is None
                                   or extent < self._free[name][best][1]):
                best = i
        if best is None:
            return None
        base, extent = self._free[name].pop(best)
        if extent > size:
            self._free[name].append((base + size, extent - size))
        return base

    def _free_extent(self, name: str, base: int, size: int) -> None:
        """Return an extent, coalescing adjacent frees so churn (retire +
        onboard cycles) cannot fragment the arena into unusable slivers."""
        extents = sorted(self._free[name] + [(base, size)])
        merged: List[Tuple[int, int]] = []
        for b, s in extents:
            if merged and merged[-1][0] + merged[-1][1] == b:
                merged[-1] = (merged[-1][0], merged[-1][1] + s)
            else:
                merged.append((b, s))
        self._free[name] = merged

    def free_rows(self, name: str) -> int:
        return sum(s for _, s in self._free[name])

    # -- slot placement / publish -----------------------------------------
    def _place_slot(self, model_id: str, model: GameModel,
                    sizes: Dict[str, int], cursor: Dict[str, int]) -> _Slot:
        """Initial-build placement: slots pack densely from row 0."""
        row = self._free_rows_of_slots.pop(0)
        base, zero, vocab = {}, {}, {}
        for name, coord in model.coordinates.items():
            if not isinstance(coord, RandomEffectModel):
                continue
            base[name] = cursor[name]
            zero[name] = cursor[name] + coord.num_entities
            # host-sync: build-time only — entity vocabularies are host
            # numpy by construction (the key join runs at ingest).
            vocab[name] = np.asarray(coord.keys)
            cursor[name] += sizes[name]
        slot = _Slot(row=row, base=base, size=dict(sizes), zero=zero,
                     vocab=vocab, model=model, version=1)
        self.slots[model_id] = slot
        return slot

    def _publish_slot(self, tables: tuple, slot: _Slot,
                      model: GameModel) -> tuple:
        """Scatter one model's rows into its extents: the COPY-ON-WRITE
        slice update (functional ``dynamic_update_slice`` — in-flight
        batches keep reading the tables they captured; the new tuple
        publishes in one assignment upstream).  No host re-upload of any
        other model's rows ever happens here."""
        out = list(tables)
        for i, c in enumerate(self.coords):
            coord = model.coordinates[c.name]
            if c.kind == "fixed":
                w = jnp.asarray(
                    coord.coefficients.means, jnp.float32
                )[None, :]
                out[i] = _scatter_rows(out[i], w, jnp.int32(slot.row))
                continue
            block = _encode_slot_rows(
                coord.table, slot.size[c.name], c.dim, self.table_dtype
            )
            base = jnp.int32(slot.base[c.name])
            if self.table_dtype == "int8":
                q, scale = out[i]
                bq, bscale = block
                out[i] = (
                    _scatter_rows(q, bq, base),
                    _scatter_vec(scale, bscale, base),
                )
            else:
                out[i] = _scatter_rows(out[i], block, base)
        return tuple(out)

    # -- lifecycle ---------------------------------------------------------
    def onboard(self, model_id: str, model: GameModel) -> bool:
        """Host a new model.  Allocates one extent per random coordinate
        plus a fixed slot row and slice-scatters the rows in — zero new
        device allocations and zero recompiles while free extents and
        slot rows last.  Returns True when the LAYOUT changed (arena had
        to grow — the caller must rebuild its compiled ladder)."""
        with self._lock:
            if model_id in self.slots:
                raise ValueError(
                    f"model {model_id!r} is already hosted; use refresh()"
                )
            self._check_layout(model_id, model)
            sizes = self._slot_sizes(model)
            grew = self._ensure_room(sizes, need_slot_row=True)
            row = self._free_rows_of_slots.pop(0)
            base, zero, vocab = {}, {}, {}
            for name, size in sizes.items():
                b = self._alloc_extent(name, size)
                assert b is not None  # _ensure_room guaranteed space
                base[name] = b
                coord = model.coordinates[name]
                zero[name] = b + coord.num_entities
                # host-sync: onboard-time only — vocabulary join tables.
                vocab[name] = np.asarray(coord.keys)
            slot = _Slot(row=row, base=base, size=sizes, zero=zero,
                         vocab=vocab, model=model, version=1)
            self.slots[model_id] = slot
            self.tables = self._publish_slot(self.tables, slot, model)
            self.state = _ArenaState(
                self.slots, self.coords, self.model_slots
            )
            jax.block_until_ready(self.tables)
            self.telemetry.counter("serving.arena_onboards").inc()
            self._record_gauges()
            return grew

    def retire(self, model_id: str) -> None:
        """Un-host a model: its extents and slot row return to the free
        lists.  The rows themselves stay in device memory untouched —
        ingest routing refuses the id, so they are unreachable, and the
        next onboard overwrites them.  Never recompiles."""
        with self._lock:
            if len(self.slots) == 1:
                raise ValueError(
                    "cannot retire the last hosted model; the arena "
                    "always serves at least one"
                )
            slot = self.slots.pop(model_id, None)
            if slot is None:
                raise KeyError(f"model {model_id!r} is not hosted")
            for name, size in slot.size.items():
                self._free_extent(name, slot.base[name], size)
            self._free_rows_of_slots.insert(0, slot.row)
            if model_id == self.default_id:
                self.default_id = next(iter(self.slots))
            self.state = _ArenaState(
                self.slots, self.coords, self.model_slots
            )
            self.telemetry.counter("serving.arena_retires").inc()
            self._record_gauges()

    def refresh(self, model_id: str, model: GameModel) -> bool:
        """Republish one hosted model (the online-refresh publish path).

        In-slot when the grown vocabulary still fits the slot (the common
        case — slots carry pow2 headroom); MIGRATES to a larger free
        extent when it does not (base offset moves, zero recompiles);
        grows the arena only when no extent fits.  Returns True when the
        layout changed."""
        with self._lock:
            slot = self.slots.get(model_id)
            if slot is None:
                raise KeyError(f"model {model_id!r} is not hosted")
            self._check_layout(model_id, model)
            sizes = self._slot_sizes(model)
            grew = False
            moved = {
                name: size for name, size in sizes.items()
                if size > slot.size[name]
            }
            if moved:
                # Free the old extents FIRST so a doubled slot can reuse
                # its own rows when they adjoin free space; the old rows
                # stay readable until the new state publishes (frees are
                # bookkeeping, not writes).
                for name in moved:
                    self._free_extent(name, slot.base[name],
                                      slot.size[name])
                rebuilds = self._rebuilds
                grew = self._ensure_room(moved, need_slot_row=False)
                if self._rebuilds != rebuilds:
                    # The rebuild re-based every slot and reset the free
                    # lists (the pre-rebuild frees with them) — re-fetch
                    # this model's repacked placement and abandon its
                    # about-to-move extents again.
                    slot = self.slots[model_id]
                    for name in moved:
                        self._free_extent(name, slot.base[name],
                                          slot.size[name])
            new_base = dict(slot.base)
            new_size = dict(slot.size)
            if moved:
                for name, size in moved.items():
                    b = self._alloc_extent(name, size)
                    assert b is not None
                    new_base[name] = b
                    new_size[name] = size
            base_zero = {}
            vocab = {}
            for name, coord in model.coordinates.items():
                if not isinstance(coord, RandomEffectModel):
                    continue
                base_zero[name] = new_base[name] + coord.num_entities
                # host-sync: refresh-time only — vocabulary join tables.
                vocab[name] = np.asarray(coord.keys)
            new_slot = _Slot(
                row=slot.row, base=new_base, size=new_size,
                zero=base_zero, vocab=vocab, model=model,
                version=slot.version + 1,
            )
            self.slots[model_id] = new_slot
            self.tables = self._publish_slot(self.tables, new_slot, model)
            self.state = _ArenaState(
                self.slots, self.coords, self.model_slots
            )
            jax.block_until_ready(self.tables)
            self.telemetry.counter("serving.arena_refreshes").inc()
            self._record_gauges()
            return grew

    def _ensure_room(self, sizes: Dict[str, int],
                     need_slot_row: bool) -> bool:
        """Make one free extent of each requested size exist (+ a free
        slot row if asked).  When a coordinate has no fitting extent, the
        arena REBUILDS: every hosted slot repacks densely from row 0, and
        if even the repacked tail cannot hold the request the capacity
        doubles first — the amortized-doubling boundary.  Returns True
        when table SHAPES changed (the scorer must rebuild its ladder); a
        same-shape compaction rebuild returns False (the compiled
        programs take the tables as arguments, so only offsets moved)."""
        new_caps = dict(self._capacity)
        need_rebuild = False
        for name, size in sizes.items():
            if any(extent >= size for _, extent in self._free[name]):
                continue
            used = sum(
                s.size.get(name, 0) for s in self.slots.values()
            )
            cap = new_caps[name]
            while cap - used < size:
                cap *= 2
            new_caps[name] = pad_to_multiple(
                cap, max(1, mesh_shards(self.mesh))
            )
            need_rebuild = True
        new_slots = self.model_slots
        if need_slot_row and not self._free_rows_of_slots:
            new_slots = self.model_slots * 2
            need_rebuild = True
        if not need_rebuild:
            return False
        grew = (
            new_caps != self._capacity or new_slots != self.model_slots
        )
        self._rebuild(new_caps, new_slots)
        return grew

    def _rebuild(self, capacities: Dict[str, int], model_slots: int) -> None:
        """The arena-growth migration: fresh (bigger) tables, every hosted
        model re-placed densely and re-scattered.  The ONLY path that
        allocates device memory after construction; ``layout_version``
        bumps when the shapes changed so the scorer rebuilds its compiled
        ladder before publishing (in-flight batches finish on the old
        tables — the rebuild is double-buffered like any swap)."""
        shapes_changed = (
            capacities != self._capacity
            or model_slots != self.model_slots
        )
        self._capacity = dict(capacities)
        self.model_slots = int(model_slots)
        self.coords = self._build_coords()
        tables = self._alloc_tables()
        cursor = {name: 0 for name in self._capacity}
        used_rows = sorted(self.slots.values(), key=lambda s: s.row)
        self._free_rows_of_slots = [
            r for r in range(self.model_slots)
            if r not in {s.row for s in used_rows}
        ]
        for mid in list(self.slots):
            slot = self.slots[mid]
            sizes = dict(slot.size)
            base = {}
            zero = {}
            for name, size in sizes.items():
                base[name] = cursor[name]
                zero[name] = (
                    cursor[name] + (slot.zero[name] - slot.base[name])
                )
                cursor[name] += size
            new_slot = dataclasses.replace(slot, base=base, zero=zero)
            self.slots[mid] = new_slot
            tables = self._publish_slot(tables, new_slot, slot.model)
        self._free = {
            name: ([(cursor[name], cap - cursor[name])]
                   if cursor[name] < cap else [])
            for name, cap in self._capacity.items()
        }
        self.tables = tables
        self.state = _ArenaState(self.slots, self.coords, self.model_slots)
        self._rebuilds += 1
        if shapes_changed:
            self.layout_version += 1
        self.telemetry.counter("serving.arena_growths").inc()

    # -- observability -----------------------------------------------------
    def arena_bytes(self) -> int:
        return sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(self.tables)
        )

    def _record_gauges(self) -> None:
        self.telemetry.gauge("serving.arena_bytes").set(self.arena_bytes())
        self.telemetry.gauge("serving.arena_models").set(len(self.slots))
        self.telemetry.gauge("serving.arena_layout_version").set(
            self.layout_version
        )
        for name, cap in self._capacity.items():
            self.telemetry.gauge(
                "serving.arena_rows", coordinate=name
            ).set(cap)
            self.telemetry.gauge(
                "serving.arena_free_rows", coordinate=name
            ).set(self.free_rows(name))


class MultiModelScorer:
    """N hosted models behind ONE compiled bucket ladder.

    The :class:`~photon_tpu.serving.scorer.GameScorer` surface (warmup /
    score_batch / swap_model / bucket_for / compilations ...) over a
    :class:`ModelArena`: every bucket program takes the arena tables plus
    per-row ``(global gather index, model slot)`` vectors, so model
    identity is request DATA — the compiled-program count is
    O(log max_batch), independent of model count, and a mixed-model
    micro-batch (the batcher coalescing two tenants' requests) scores in
    one dispatch.

    Requests route by ``ScoringRequest.model`` (a scalar id, or a per-row
    id array after coalescing); a request without a model id scores
    against the arena's default model, which keeps every single-model
    caller (supervisor probes, canary rollouts, benches) working
    unchanged."""

    def __init__(
        self,
        models: Dict[str, GameModel],
        mesh=None,
        request_spec: Optional[Dict[str, ShardSpec]] = None,
        buckets: Optional[Tuple[int, ...]] = None,
        max_batch: int = DEFAULT_MAX_BATCH,
        min_bucket: int = DEFAULT_MIN_BUCKET,
        telemetry=None,
        strict_after_warmup: bool = True,
        table_capacity_factor: int = 1,
        table_dtype: str = "f32",
        model_slots: Optional[int] = None,
        reserve_rows: int = 0,
    ):
        from photon_tpu.telemetry import NULL_SESSION

        self.telemetry = telemetry or NULL_SESSION
        self.mesh = mesh
        self.arena = ModelArena(
            models,
            mesh=mesh,
            table_dtype=table_dtype,
            table_capacity_factor=table_capacity_factor,
            model_slots=model_slots,
            reserve_rows=reserve_rows,
            telemetry=self.telemetry,
        )
        self.table_dtype = self.arena.table_dtype
        first = next(iter(models.values()))
        self.request_spec = request_spec or request_spec_for_model(first)
        for c in self.arena.coords:
            if c.shard not in self.request_spec:
                raise ValueError(
                    f"request spec is missing shard {c.shard!r}"
                )
        self.buckets = bucket_ladder(buckets, max_batch, min_bucket)
        self.max_bucket = self.buckets[-1]
        self.compilations = 0
        self._warm = False
        self.strict_after_warmup = strict_after_warmup
        self._programs: Dict[tuple, object] = {}
        self._swap_lock = threading.Lock()
        # The ONE published (tables, state, programs) triple: score_batch
        # unpacks it once at entry, so an onboard/retire/refresh — even an
        # arena-growth rebuild — can never hand one batch mixed state.
        self._serving = (self.arena.tables, self.arena.state, self._programs)

    # -- GameScorer-compatible surface ------------------------------------
    @property
    def model(self) -> GameModel:
        """The DEFAULT model — what single-model callers (supervisor
        known-answer probes, respawn identity checks) see."""
        return self.model_for(self.arena.default_id)

    @property
    def models(self) -> Dict[str, GameModel]:
        _, state, _ = self._serving
        return {mid: s.model for mid, s in state.slots.items()}

    @property
    def model_ids(self) -> Tuple[str, ...]:
        _, state, _ = self._serving
        return tuple(sorted(state.slots))

    def model_for(self, model_id: str) -> GameModel:
        _, state, _ = self._serving
        slot = state.slots.get(model_id)
        if slot is None:
            raise KeyError(f"model {model_id!r} is not hosted")
        return slot.model

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"batch of {n} rows exceeds max bucket "
                         f"{self.max_bucket}; chunk it (score_batch does)")

    def padded_rows(self, n: int) -> int:
        return padded_cost(n, self.buckets)

    def warmup(self) -> "MultiModelScorer":
        """AOT-compile every ladder bucket ONCE for all hosted models —
        the arena's headline invariant: warmup cost is independent of
        model count, and serving any hosted (or later-onboarded) model
        hits these same executables."""
        with self.telemetry.span(
            "serving.warmup", buckets=len(self.buckets),
            models=len(self.model_ids),
        ):
            tables, _, programs = self._serving
            for b in self.buckets:
                self._compile(b, "request", tables, programs)
        self._warm = True
        return self

    # -- program build -----------------------------------------------------
    def _donate_argnums(self) -> tuple:
        """Donate request buffers (args 1-4: feats/gidx/mslot/offset) on
        accelerators only — same CPU aliasing hazard as GameScorer."""
        if any(d.platform == "cpu" for d in self.table_devices()):
            return ()
        return (1, 2, 3, 4)

    def table_devices(self) -> list:
        """The devices that hold the arena tables, by id (GameScorer's
        contract)."""
        return devices_of(self.arena.tables)

    def _compile(self, bucket: int, layout: str, tables, programs):
        program = programs.get((bucket, layout))
        if program is not None:
            return program
        plan, spec = self.arena.coords, self.request_spec

        def score(tables, feats, gidx, mslot, offset, n_valid):
            valid = jnp.arange(bucket, dtype=jnp.int32) < n_valid
            total = offset
            for c, table in zip(plan, tables):
                dense = spec[c.shard].dense
                if c.kind == "fixed":
                    # Per-row weight gather from the model-slot stack:
                    # the fixed coordinate's "which model" is a data
                    # dependency, never a compiled branch.
                    w = table[mslot]
                    if dense:
                        total = total + jnp.einsum(
                            "nd,nd->n", feats[c.shard], w
                        )
                    else:
                        ids, vals = feats[c.shard]
                        total = total + jnp.sum(
                            jnp.take_along_axis(w, ids, axis=1) * vals,
                            axis=-1,
                        )
                else:
                    # gidx is already GLOBAL and already safe: ingest
                    # resolved model base + local entity index, mapped
                    # unknown entities to the model's own zero row, and
                    # padded rows to 0 (masked below).
                    total = total + serving_gather_margins(
                        table, gidx[c.name], feats[c.shard], dense
                    )
            return jnp.where(valid, total, 0.0)

        jitted = jax.jit(score, donate_argnums=self._donate_argnums())
        sample = self._place(*self._zero_request(bucket))
        import warnings

        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            program = jitted.lower(
                tables, *abstract_like(sample)
            ).compile()
        programs[(bucket, layout)] = program
        self.compilations += 1
        self.telemetry.counter("serving.compilations").inc()
        return program

    def _program(self, bucket: int, layout: str, tables, programs):
        program = programs.get((bucket, layout))
        if program is not None:
            return program
        if self._warm and self.strict_after_warmup and layout == "request":
            raise RuntimeError(
                f"no pre-compiled program for bucket {bucket} after warmup "
                f"(compiled: {sorted(b for b, l in programs if l == 'request')}); "
                "widen `buckets` or chunk the batch — serving must never "
                "recompile"
            )
        return self._compile(bucket, layout, tables, programs)

    def _zero_request(self, bucket: int):
        feats: Dict[str, object] = {}
        gidx: Dict[str, np.ndarray] = {}
        for c in self.arena.coords:
            s = self.request_spec[c.shard]
            if c.shard not in feats:
                if s.dense:
                    feats[c.shard] = np.zeros((bucket, s.dim), np.float32)
                else:
                    feats[c.shard] = (
                        np.zeros((bucket, s.nnz), np.int32),
                        np.zeros((bucket, s.nnz), np.float32),
                    )
            if c.kind == "random":
                gidx[c.name] = np.zeros(bucket, np.int32)
        mslot = np.zeros(bucket, np.int32)
        offset = np.zeros(bucket, np.float32)
        return feats, gidx, mslot, offset, np.int32(0)

    def _place(self, feats, gidx, mslot, offset, n_valid):
        return put_request(
            (feats, gidx, mslot, offset, jnp.int32(n_valid)), self.mesh
        )

    # -- ingest (host side, the sanctioned edge) ---------------------------
    def _resolve_rows(self, request: ScoringRequest, n: int,
                      state: _ArenaState):
        """Per-row model-slot rows for one request — the model->slot join.
        ``model`` may be a scalar id (whole request one tenant), a per-row
        id array (a coalesced mixed batch), or None (default model)."""
        model = getattr(request, "model", None)
        if model is None:
            return np.full(n, state.row_of(self.arena.default_id),
                           np.int32), None
        if isinstance(model, str):
            return np.full(n, state.row_of(model), np.int32), model
        # host-sync: ingest routing — caller-owned host id array.
        ids = np.asarray(model, dtype=object)
        if len(ids) != n:
            raise ValueError(
                f"request.model has {len(ids)} rows, request has {n}"
            )
        # Rows whose request carried no model id (a mixed coalesced batch
        # of routed and unrouted requests) score the default model.
        none_mask = np.frompyfunc(lambda v: v is None, 1, 1)(ids)
        if none_mask.any():
            ids = ids.copy()
            ids[none_mask.astype(bool)] = self.arena.default_id
        return state.rows_for(ids), None

    def _stage(self, request: ScoringRequest, bucket: int, n: int,
               state: _ArenaState):
        """Validate + pad features, resolve model slots, and join entity
        keys per tenant into GLOBAL arena indices.  Unknown entities map
        to the owning model's zero row (counted host-side as
        ``serving.cold_entities`` — the arena staging already walks the
        keys, so the count is free and the device program carries no cold
        logic at all)."""
        feats: Dict[str, object] = {}
        for c in self.arena.coords:
            if c.shard in feats:
                continue
            s = self.request_spec[c.shard]
            leaf = request.features.get(c.shard)
            if leaf is None:
                raise ValueError(f"request is missing shard {c.shard!r}")
            if s.dense:
                # host-sync: request ingest — coercing caller-owned rows
                # to upload-ready numpy (no device data involved).
                x = np.asarray(leaf, np.float32)
                if x.shape != (n, s.dim):
                    raise ValueError(
                        f"shard {c.shard!r}: got {x.shape}, want {(n, s.dim)}"
                    )
                feats[c.shard] = _pad_rows(x, bucket)
            else:
                ids, vals = leaf
                # host-sync: request ingest — same coercion, sparse leaves.
                ids = np.asarray(ids, np.int32)
                vals = np.asarray(vals, np.float32)
                if ids.shape != (n, s.nnz) or vals.shape != (n, s.nnz):
                    raise ValueError(
                        f"shard {c.shard!r}: got {ids.shape}/{vals.shape}, "
                        f"want {(n, s.nnz)}"
                    )
                feats[c.shard] = (
                    _pad_rows(ids, bucket), _pad_rows(vals, bucket)
                )
        rows, scalar_id = self._resolve_rows(request, n, state)
        gidx: Dict[str, np.ndarray] = {}
        cold: Dict[str, int] = {}
        for c in self.arena.coords:
            if c.kind != "random":
                continue
            keys = request.entity_ids.get(c.column)
            if keys is None:
                raise ValueError(
                    f"request is missing id column {c.column!r}"
                )
            # host-sync: request ingest — the key->row join against each
            # tenant's vocabulary (host searchsorted), then base offsets.
            keys = np.asarray(keys)
            local = np.empty(n, np.int32)
            if scalar_id is not None or len(state.slots) == 1:
                mid = scalar_id or next(iter(state.slots))
                local[:] = entity_index_for(
                    keys, state.slots[mid].vocab[c.name]
                )
            else:
                for r in np.unique(rows):
                    mask = rows == r
                    vocab = state.slots[state.id_of_row[int(r)]].vocab
                    local[mask] = entity_index_for(keys[mask],
                                                   vocab[c.name])
            base = state.base[c.name][rows]
            zero = state.zero[c.name][rows]
            cold_mask = local < 0
            cold[c.name] = int(cold_mask.sum())
            g = np.where(cold_mask, zero, base + local).astype(np.int32)
            gidx[c.name] = _pad_rows(g, bucket)
        offset = (
            np.zeros(bucket, np.float32) if request.offset is None
            else _pad_rows(
                # host-sync: request ingest — offset coercion, host data.
                np.asarray(request.offset, np.float32), bucket
            )
        )
        return feats, gidx, _pad_rows(rows, bucket), offset, cold

    # -- scoring -----------------------------------------------------------
    def score_batch(self, request: ScoringRequest) -> np.ndarray:
        """One compiled dispatch + ONE host sync, any mix of hosted
        models in the batch; oversize requests chunk like GameScorer."""
        n = request.num_rows
        if n == 0:
            return np.zeros(0, np.float32)
        if n > self.max_bucket:
            return np.concatenate([
                self.score_batch(slice_request(request, lo,
                                               min(lo + self.max_bucket, n)))
                for lo in range(0, n, self.max_bucket)
            ])
        return self._score_padded(request, self.bucket_for(n), n)

    def _score_padded(self, request: ScoringRequest, bucket: int,
                      n: int) -> np.ndarray:
        t0 = time.monotonic()
        # ONE read of the published triple (see __init__).
        tables, state, programs = self._serving
        program = self._program(bucket, "request", tables, programs)
        feats, gidx, mslot, offset, cold = self._stage(
            request, bucket, n, state
        )
        placed = self._place(feats, gidx, mslot, offset, n)
        out = program(tables, *placed)
        # host-sync: response egress — THE one per-batch fetch (cold
        # counts came free at ingest, so only scores ride it).
        fetched = jax.device_get(out)
        scores = np.array(fetched, copy=True)
        t = self.telemetry
        t.counter("serving.host_syncs").inc()
        t.counter("serving.batches", bucket=bucket).inc()
        t.counter("serving.rows").inc(n)
        t.histogram("serving.batch_rows").observe(n)
        t.histogram("serving.bucket_occupancy", bucket=bucket).observe(
            n / bucket
        )
        t.histogram("serving.padded_fraction").observe((bucket - n) / bucket)
        t.histogram("serving.score_seconds").observe(time.monotonic() - t0)
        for name, count in cold.items():
            if count:
                t.counter("serving.cold_entities", coordinate=name).inc(
                    count
                )
        return scores[:n]

    # -- model lifecycle ---------------------------------------------------
    def _republish(self, grew: bool) -> None:
        """Publish the arena's new (tables, state) — and, after a growth
        rebuild, a freshly compiled ladder — in one assignment."""
        programs = self._programs
        if grew:
            programs = {}
            if self._warm:
                for b in self.buckets:
                    self._compile(b, "request", self.arena.tables, programs)
            self._programs = programs
        self._serving = (self.arena.tables, self.arena.state, programs)

    def add_model(self, model_id: str, model: GameModel) -> None:
        """Onboard a tenant under live traffic: slice scatter + one
        published snapshot; in-flight batches finish on the tables they
        captured — zero requests dropped, zero recompiles unless the
        arena itself had to grow."""
        with self._swap_lock:
            grew = self.arena.onboard(model_id, model)
            self._republish(grew)

    def retire_model(self, model_id: str) -> None:
        with self._swap_lock:
            self.arena.retire(model_id)
            self._republish(False)

    def swap_model(self, model: GameModel, model_id: Optional[str] = None,
                   table_dtype: Optional[str] = None) -> None:
        """Hot-swap ONE tenant's slice (the GameScorer signature plus
        ``model_id``; None targets the default model, which is what the
        single-model rollout/canary machinery passes).  A dtype-mismatched
        publish refuses exactly like GameScorer's gate — the decode is
        baked into the shared ladder, so one tenant cannot change it."""
        if table_dtype is not None and table_dtype != self.table_dtype:
            raise ValueError(
                f"swap_model: model published at table dtype "
                f"{table_dtype!r} but this arena's warmed programs decode "
                f"{self.table_dtype!r}; the storage tier is baked into the "
                "compiled bucket ladder — rebuild the arena to change it"
            )
        with self._swap_lock:
            mid = model_id or self.arena.default_id
            grew = self.arena.refresh(mid, model)
            self._republish(grew)
            self.telemetry.counter("serving.swaps").inc()

    def sync_models(self, models: Dict[str, GameModel]) -> None:
        """Converge the hosted set onto ``models`` (respawn/rejoin): new
        ids onboard, known ids refresh, absent ids retire."""
        with self._swap_lock:
            grew = False
            for mid, model in models.items():
                if mid in self.arena.slots:
                    grew |= self.arena.refresh(mid, model)
                else:
                    grew |= self.arena.onboard(mid, model)
            for mid in list(self.arena.slots):
                if mid not in models and len(self.arena.slots) > 1:
                    self.arena.retire(mid)
            self._republish(grew)
