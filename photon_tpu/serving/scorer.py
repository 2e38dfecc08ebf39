"""Device-resident GAME scorer with recompile-free bucketed micro-batching.

The hot path's residency contract (enforced by ``tools/check_host_sync.py``):
model tables live on device for the scorer's whole lifetime; each scored
batch pays exactly ONE host round-trip — the request buffers go up in one
``put_request`` placement and the ``(scores, cold_counts)`` pair comes back
in one ``jax.device_get`` (``serving.host_syncs`` == 1 per batch, pinned by
tests).  Everything between those two edges is a single pre-compiled XLA
program per bucket shape, with the request buffers DONATED on accelerators
so XLA recycles them for outputs (not on CPU, where placed buffers can
alias host memory — see ``_donate_argnums``).

Bucketing: batch sizes are padded to a small power-of-two ladder
(``buckets``, default 8 … ``max_batch``), so arrival patterns map onto
O(log max_batch) compiled programs.  :meth:`GameScorer.warmup` AOT-compiles
the whole ladder up front (``jax.jit(...).lower(...).compile()``); after
warmup a request can never trigger a compile — an off-ladder shape raises
instead of silently recompiling.  Padded rows carry entity index -1 and are
masked out of the cold-entity counts by the device-side ``n_valid`` bound;
their scores are sliced off before anything leaves the scorer.

Unknown entities: each random coordinate's table is the model's
:meth:`~photon_tpu.game.model.RandomEffectModel.serving_table` —
``[capacity, dim]`` with every row past the vocabulary all-zero — and
request rows whose entity key is outside the vocabulary gather the zero
row at index ``num_entities``, falling back to a fixed-effect-only score.
They are counted on device and surface as
``serving.cold_entities{coordinate=...}``.

Capacity headroom: tables allocate at the model's amortized-doubling
:attr:`~photon_tpu.game.model.RandomEffectModel.serving_capacity` (next
pow2 past entities + 1), and the zero-row index rides the published
serving state as a DEVICE argument — not a constant baked into the
compiled programs.  A retrained model whose grown vocabulary still fits
the served capacity therefore hot-swaps in place with zero recompiles
(the zero row just moves); only a capacity/dim change — a real
layout-shape change — still refuses and requires a new scorer.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.game.data import (
    DenseShard,
    GameDataset,
    entity_index_for,
)
from photon_tpu.game.model import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
    _fixed_margins,
    serving_gather_margins,
)
from photon_tpu.parallel.mesh import abstract_like, put_request
from photon_tpu.utils import pow2_at_least

DEFAULT_MAX_BATCH = 256
DEFAULT_MIN_BUCKET = 8


def bucket_ladder(
    buckets: Optional[Tuple[int, ...]], max_batch: int, min_bucket: int
) -> Tuple[int, ...]:
    """The ONE bucket-ladder construction: an explicit ladder is
    deduped/sorted, a default one is the powers of two from
    ``min_bucket`` through ``pow2(max_batch)``.  Shared by
    :class:`GameScorer` and the subprocess replica's parent-side mirror so
    the two can never pad differently."""
    if buckets is None:
        b, ladder = max(1, pow2_at_least(min_bucket)), []
        max_bucket = pow2_at_least(max_batch)
        while b < max_bucket:
            ladder.append(b)
            b *= 2
        ladder.append(max_bucket)
        buckets = tuple(ladder)
    return tuple(sorted(set(int(b) for b in buckets)))


def devices_of(tables) -> list:
    """The devices holding any leaf of ``tables``, sorted by id."""
    devices = set()
    for leaf in jax.tree_util.tree_leaves(tables):
        devices |= leaf.devices()
    return sorted(devices, key=lambda d: d.id)


def padded_cost(n: int, buckets: Tuple[int, ...]) -> int:
    """Device rows an ``n``-row request actually COSTS through the bucket
    ladder: the smallest holding bucket, with oversize requests chunked
    into max-bucket slabs first (exactly what ``score_batch`` does).  The
    admission projection charges queue wait in these padded rows — padding
    costs compute too, so a raw-rows projection systematically under-
    estimates the wait and over-admits near saturation."""
    n = int(n)
    if n <= 0:
        return 0
    max_bucket = buckets[-1]
    full, rem = divmod(n, max_bucket)
    cost = full * max_bucket
    if rem:
        cost += next(b for b in buckets if rem <= b)
    return cost


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Fixed request layout of one feature shard: serving programs compile
    against ONE shape per shard, so the spec — dense width, or the sparse
    padded-COO nonzero width — is part of the scorer's identity."""

    kind: str  # "dense" | "sparse"
    dim: int
    nnz: int = 0  # padded-COO width (sparse only)

    @property
    def dense(self) -> bool:
        return self.kind == "dense"


@dataclasses.dataclass(frozen=True)
class ScoringRequest:
    """One scoring request: per-shard feature rows, raw per-row entity keys
    for each id column a random coordinate joins on, and an optional
    per-row offset — a :class:`~photon_tpu.game.data.GameDataset` minus
    labels/weights.  All arrays are host-side; the scorer owns placement.

    ``model`` routes the request in a MULTI-MODEL fleet (ISSUE 18): a
    scalar model id (the whole request scores against one tenant), or —
    after the batcher coalesces requests from different tenants — a
    per-row id array.  ``None`` means the default model, which keeps every
    single-model caller unchanged; a single-model :class:`GameScorer`
    ignores the field entirely."""

    features: Dict[str, object]  # shard -> [n, d] dense | (ids, vals) sparse
    entity_ids: Dict[str, np.ndarray]  # id column -> [n] raw keys
    offset: Optional[np.ndarray] = None  # [n] float32
    model: Optional[object] = None  # None | str | [n] object array

    @property
    def num_rows(self) -> int:
        for leaf in self.features.values():
            arr = leaf[0] if isinstance(leaf, tuple) else leaf
            return int(arr.shape[0])
        for col in self.entity_ids.values():
            return int(len(col))
        return 0


def request_spec_for_model(model: GameModel) -> Dict[str, ShardSpec]:
    """Dense request layout straight from the model's own dimensions — the
    default for request sources that send dense feature vectors."""
    spec: Dict[str, ShardSpec] = {}
    for coord in model.coordinates.values():
        if isinstance(coord, FixedEffectModel):
            spec[coord.shard_name] = ShardSpec(
                "dense", int(len(coord.coefficients.means))
            )
        else:
            spec[coord.shard_name] = ShardSpec("dense", int(coord.dim))
    return spec


def request_spec_for_dataset(
    model: GameModel, data: GameDataset
) -> Dict[str, ShardSpec]:
    """Request layout matching a concrete dataset's shard storage (the
    batch ``score_game`` route: Avro input arrives as padded-COO sparse
    shards, whose nonzero width fixes the compiled program's shape)."""
    spec: Dict[str, ShardSpec] = {}
    for coord in model.coordinates.values():
        shard = data.shard(coord.shard_name)
        if isinstance(shard, DenseShard):
            spec[coord.shard_name] = ShardSpec("dense", int(shard.dim))
        else:
            spec[coord.shard_name] = ShardSpec(
                "sparse", int(shard.dim), nnz=int(shard.ids.shape[1])
            )
    return spec


def request_from_dataset(data: GameDataset, model: GameModel) -> ScoringRequest:
    """The whole dataset as one request (batch scoring through the serving
    tables); only the shards/id-columns the model actually joins ride."""
    features: Dict[str, object] = {}
    entity_ids: Dict[str, np.ndarray] = {}
    for coord in model.coordinates.values():
        shard = data.shard(coord.shard_name)
        features[coord.shard_name] = (
            shard.x if isinstance(shard, DenseShard) else (shard.ids, shard.vals)
        )
        if isinstance(coord, RandomEffectModel):
            entity_ids[coord.entity_column] = data.id_columns[coord.entity_column]
    return ScoringRequest(
        features=features, entity_ids=entity_ids, offset=data.offset
    )


def request_model_rows(model, n: int):
    """One request's model-id routing as per-row values: ``None``/scalar
    ids broadcast over the rows; a per-row array passes through.  The ONE
    widening rule :func:`concat_requests` and the wire transport share."""
    if model is None or isinstance(model, str):
        return np.full(n, model, dtype=object)
    # host-sync: ingest routing — caller-owned host id array.
    return np.asarray(model, dtype=object)


def slice_request(req: ScoringRequest, lo: int, hi: int) -> ScoringRequest:
    """Row window ``[lo, hi)`` of a request (oversize-batch chunking)."""
    def cut(leaf):
        if isinstance(leaf, tuple):
            return tuple(a[lo:hi] for a in leaf)
        return leaf[lo:hi]

    model = req.model
    if model is not None and not isinstance(model, str):
        # host-sync: request model-id routing vectors are host object
        # arrays end to end — never device data.
        model = np.asarray(model, dtype=object)[lo:hi]
    return ScoringRequest(
        features={k: cut(v) for k, v in req.features.items()},
        entity_ids={k: v[lo:hi] for k, v in req.entity_ids.items()},
        offset=None if req.offset is None else req.offset[lo:hi],
        model=model,
    )


def concat_requests(requests: List[ScoringRequest]) -> ScoringRequest:
    """Coalesce requests into one micro-batch (the batcher's merge step).
    Every request must carry the same shards/id-columns; offsets default to
    zero rows so requests with and without offsets can share a batch."""
    if len(requests) == 1:
        return requests[0]
    first = requests[0]

    def cat(key):
        leaves = [r.features[key] for r in requests]
        if isinstance(leaves[0], tuple):
            return tuple(
                np.concatenate([leaf[i] for leaf in leaves])
                for i in range(len(leaves[0]))
            )
        return np.concatenate(leaves)

    offsets = []
    for r in requests:
        offsets.append(
            np.zeros(r.num_rows, np.float32) if r.offset is None
            # host-sync: request ingest — caller-owned host offsets.
            else np.asarray(r.offset, np.float32)
        )
    # Model-id routing must survive coalescing: all-same scalars (the
    # common single-tenant batch) stay scalar; any mix widens to a
    # per-row id array the multi-model scorer resolves per row.
    model = None
    scalars = set()
    for r in requests:
        m = r.model
        scalars.add(m if (m is None or isinstance(m, str)) else False)
    if scalars != {None}:
        if len(scalars) == 1 and False not in scalars:
            model = next(iter(scalars))
        else:
            model = np.concatenate([
                request_model_rows(r.model, r.num_rows) for r in requests
            ])
    return ScoringRequest(
        features={k: cat(k) for k in first.features},
        entity_ids={
            k: np.concatenate([r.entity_ids[k] for r in requests])
            for k in first.entity_ids
        },
        offset=np.concatenate(offsets),
        model=model,
    )


def request_windows(n_rows: int, sizes, start: int = 0) -> List[np.ndarray]:
    """Consecutive row windows of the given sizes, wrapping modulo the
    dataset.  The ONE definition of the request-stream cut: the serving
    bench's host baseline scores the same windows the served requests were
    built from, so the parity comparison can never drift onto misaligned
    rows."""
    out: List[np.ndarray] = []
    pos = start
    for size in sizes:
        out.append(np.arange(pos, pos + int(size)) % n_rows)
        pos = (pos + int(size)) % n_rows
    return out


def build_requests(
    data: GameDataset, model: GameModel, sizes, start: int = 0
) -> List[ScoringRequest]:
    """Cut a dataset into a request stream over :func:`request_windows`.
    Shared by the serve_game driver, the serving bench, and the tests —
    one request shape everywhere."""
    whole = request_from_dataset(data, model)
    out: List[ScoringRequest] = []
    for rows in request_windows(data.num_examples, sizes, start=start):

        def take(leaf):
            if isinstance(leaf, tuple):
                return tuple(a[rows] for a in leaf)
            return leaf[rows]

        out.append(
            ScoringRequest(
                features={k: take(v) for k, v in whole.features.items()},
                entity_ids={k: v[rows] for k, v in whole.entity_ids.items()},
                offset=None if whole.offset is None else whole.offset[rows],
            )
        )
    return out


@dataclasses.dataclass(frozen=True)
class _CoordPlan:
    """Static per-coordinate scoring plan baked into every bucket program.

    Deliberately carries the table CAPACITY (the compiled shape) and not
    the entity count: the zero-row index is dynamic published state, so a
    swap that only grows the vocabulary within capacity compares equal.
    The storage ``dtype`` IS part of the plan: the decode is baked into
    every bucket program, so a dtype-mismatched swap must refuse through
    the same plan-equality gate as a capacity change."""

    name: str
    kind: str  # "fixed" | "random"
    shard: str
    column: Optional[str] = None  # random: id column joined on
    capacity: int = 0  # random: table rows (vocabulary + zero-row headroom)
    dtype: str = "f32"  # random: gather-table storage tier (f32|bf16|int8)


class GameScorer:
    """Device-resident GAME model + per-bucket pre-compiled scoring programs.

    Built once per served model; :meth:`score_batch` is the request hot
    path (one compiled dispatch + one host sync per micro-batch) and
    :meth:`score_dataset` the batch route sharing the same tables and
    kernels.  ``buckets`` is the padded-batch ladder; ``max_batch`` caps it
    (a bigger request is chunked).  ``strict_after_warmup`` (default True)
    makes any shape outside the compiled set an error instead of a compile.
    """

    def __init__(
        self,
        model: GameModel,
        mesh=None,
        request_spec: Optional[Dict[str, ShardSpec]] = None,
        buckets: Optional[Tuple[int, ...]] = None,
        max_batch: int = DEFAULT_MAX_BATCH,
        min_bucket: int = DEFAULT_MIN_BUCKET,
        telemetry=None,
        strict_after_warmup: bool = True,
        table_capacity_factor: int = 1,
        table_dtype: str = "f32",
    ):
        from photon_tpu.game.lowp import check_dtype
        from photon_tpu.telemetry import NULL_SESSION

        self.model = model
        self.mesh = mesh
        # Gather-table storage tier (ISSUE 17): f32 | bf16 | int8.  Baked
        # into every bucket program's decode (and into the plan, so a
        # mismatched swap refuses); accumulation stays f32 regardless.
        self.table_dtype = check_dtype(table_dtype)
        self.telemetry = telemetry or NULL_SESSION
        self.request_spec = request_spec or request_spec_for_model(model)
        self.buckets = bucket_ladder(buckets, max_batch, min_bucket)
        self.max_bucket = self.buckets[-1]
        self.compilations = 0
        self._warm = False
        self.strict_after_warmup = strict_after_warmup
        self._programs: Dict[int, object] = {}

        # -- device-resident model tables (loaded once; replaceable by
        # swap_model without recompiling — the programs take them as
        # arguments).  ``table_capacity_factor`` > 1 PRE-PROVISIONS gather-
        # table headroom past the default next-power-of-two: an online-
        # learning deployment expecting vocabulary growth provisions 2x/4x
        # so refresh after refresh hot-swaps in place before hitting the
        # capacity rebuild boundary. ------------------------------------------
        capacities = None
        if int(table_capacity_factor) > 1:
            from photon_tpu.utils import pow2_at_least

            capacities = {
                name: pow2_at_least(
                    int(table_capacity_factor) * (coord.num_entities + 1)
                )
                for name, coord in model.coordinates.items()
                if isinstance(coord, RandomEffectModel)
            }
        plan, tables, zero_rows, vocab = self._build_tables(
            model, capacities=capacities
        )
        self._plan = tuple(plan)
        self._tables = tuple(tables)
        self._zero_rows = zero_rows
        self._vocab = vocab
        # The ONE published (tables, zero_rows, vocab) triple: score_batch
        # unpacks it once at entry, so a swap can never hand one batch a
        # mixed state.
        self._serving = (self._tables, self._zero_rows, self._vocab)
        self._record_model_gauges(model, self._tables)

    def _build_tables(self, model: GameModel,
                      capacities: Optional[Dict[str, int]] = None):
        """Device placement of one model's serving state: the static
        per-coordinate plan, the device table tuple, the movable zero-row
        index vector (one int32 per random coordinate, in plan order —
        published state, never baked into a program), and the host
        vocabularies the ingest join runs against.  Shared by ``__init__``
        and :meth:`swap_model` so the two can never build differently;
        the swap passes its SERVED ``capacities`` so a grown vocabulary
        builds at the compiled shape (and refuses past it).
        Sets NO gauges — :meth:`_record_model_gauges` publishes telemetry
        only for a model that actually serves (a refused swap must not
        leave gauges describing the rejected model)."""
        plan: List[_CoordPlan] = []
        tables: List[jax.Array] = []
        zero_rows: List[int] = []
        vocab: Dict[str, np.ndarray] = {}
        for name, coord in model.coordinates.items():
            if isinstance(coord, FixedEffectModel):
                plan.append(_CoordPlan(name, "fixed", coord.shard_name))
                tables.append(coord.serving_weights(self.mesh))
            elif isinstance(coord, RandomEffectModel):
                capacity = (capacities or {}).get(
                    name, coord.serving_capacity
                )
                plan.append(
                    _CoordPlan(
                        name, "random", coord.shard_name,
                        column=coord.entity_column,
                        capacity=int(capacity),
                        dtype=self.table_dtype,
                    )
                )
                tables.append(
                    coord.serving_table(
                        self.mesh, capacity=capacity,
                        dtype=self.table_dtype,
                    )
                )
                zero_rows.append(coord.num_entities)
                # host-sync: build/swap-time only — entity vocabularies are
                # host numpy by construction (the key join runs at ingest).
                vocab[name] = np.asarray(coord.keys)
            else:
                raise TypeError(
                    f"cannot serve a {type(coord).__name__} coordinate"
                )
            if coord.shard_name not in self.request_spec:
                raise ValueError(
                    f"request spec is missing shard {coord.shard_name!r}"
                )
        # host-sync: build/swap-time only — the movable zero-row vector is
        # assembled on host and uploaded once per published model.
        zero_dev = put_request(
            jnp.asarray(np.asarray(zero_rows, np.int32)), self.mesh
        )
        return plan, tables, zero_dev, vocab

    def _record_model_gauges(self, model: GameModel, tables) -> None:
        """Publish the SERVED model's residency/entity gauges (called only
        after a model is actually installed)."""
        for name, coord in model.coordinates.items():
            if isinstance(coord, RandomEffectModel):
                self.telemetry.gauge(
                    "serving.entities", coordinate=name
                ).set(coord.num_entities)
                self.telemetry.gauge(
                    "serving.table_capacity", coordinate=name
                ).set(next(
                    c.capacity for c in self._plan if c.name == name
                ))
        # Leaf-wise: an int8 table is a (q, scale) tuple — count both.
        total_bytes = sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(tables)
        )
        self.telemetry.gauge("serving.model_bytes").set(total_bytes)
        # The precision tier's headline gauge: gather-table bytes under
        # the SERVED storage dtype (bf16 >= 1.9x, int8 >= 3.5x smaller
        # than f32 at equal entity count — asserted by the serving bench).
        self.telemetry.gauge(
            "serving.table_bytes", dtype=self.table_dtype
        ).set(total_bytes)

    def swap_model(self, model: GameModel,
                   table_dtype: Optional[str] = None) -> None:
        """HOT-SWAP a retrained model under live traffic: the new device
        table tuple is built (uploaded) FIRST — double-buffered next to the
        serving tables — then published in one reference assignment, so no
        request is dropped and nothing recompiles (every bucket program
        takes the tables AND the zero-row index vector as arguments; the
        per-coordinate plan, which IS baked into the programs, must match
        the served model's — same coordinate names/kinds/shards/table
        capacities.  A GROWN vocabulary that still fits the served
        capacity swaps in place: the new entities' rows upload into the
        headroom and the zero-row index advances — ROADMAP continual-
        training blocker (b) cleared.  Growth PAST capacity, or a changed
        dim/coordinate set, is a layout-shape change and refuses).

        In-flight requests complete against whichever triple they captured
        at dispatch: the old tables stay alive until their last dispatch
        retires (the runtime holds the references), then free.  Counted as
        ``serving.swaps``.

        ``table_dtype``, when given, asserts the caller's expected storage
        tier: the decode is baked into the warmed bucket programs, so an
        artifact published at a DIFFERENT dtype must refuse here instead
        of silently re-encoding (serving it would change the fleet's
        parity bound under live traffic)."""
        if table_dtype is not None and table_dtype != self.table_dtype:
            raise ValueError(
                f"swap_model: model published at table dtype "
                f"{table_dtype!r} but this scorer's warmed programs decode "
                f"{self.table_dtype!r}; the storage tier is baked into the "
                "compiled bucket ladder — rebuild the scorer to change it"
            )
        capacities = {
            c.name: c.capacity for c in self._plan if c.kind == "random"
        }
        plan, tables, zero_rows, vocab = self._build_tables(
            model, capacities=capacities
        )
        if tuple(plan) != self._plan:
            raise ValueError(
                "swap_model: the new model's serving plan does not match "
                f"the compiled programs (served {self._plan}, new "
                f"{tuple(plan)}); a changed coordinate layout, table "
                "capacity, or storage dtype requires a new GameScorer"
            )
        # Leaf-wise: an int8 table is a (q, scale) tuple; its structure,
        # every leaf shape, AND every leaf dtype must match the compiled
        # programs exactly or nothing recompile-free can serve it.
        new_leaves, new_treedef = jax.tree_util.tree_flatten(tuple(tables))
        old_leaves, old_treedef = jax.tree_util.tree_flatten(self._tables)
        if new_treedef != old_treedef:
            raise ValueError(
                "swap_model: table pytree structure changed "
                f"({old_treedef} -> {new_treedef}); a changed table "
                "layout requires a new GameScorer"
            )
        for new, old in zip(new_leaves, old_leaves):
            if new.shape != old.shape or new.dtype != old.dtype:
                raise ValueError(
                    "swap_model: table shape/dtype changed "
                    f"({old.shape}/{old.dtype} -> {new.shape}/{new.dtype}); "
                    "a changed table layout requires a new GameScorer"
                )
        import jax as _jax

        # The upload completes BEFORE publication: a request arriving the
        # instant after the swap reads fully-materialized tables.
        _jax.block_until_ready((tables, zero_rows))
        # One-assignment publication: score_batch reads ``self._serving``
        # exactly once at entry, so every batch scores against ONE model's
        # tables + zero rows + vocabulary — never a mix of old and new.
        self._tables = tuple(tables)
        self._zero_rows = zero_rows
        self._vocab = vocab
        self._serving = (self._tables, self._zero_rows, self._vocab)
        self.model = model
        self._record_model_gauges(model, self._tables)
        self.telemetry.counter("serving.swaps").inc()

    # -- bucket policy -------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        """Smallest ladder bucket holding ``n`` rows (n <= max_bucket)."""
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"batch of {n} rows exceeds max bucket "
                         f"{self.max_bucket}; chunk it (score_batch does)")

    def padded_rows(self, n: int) -> int:
        """Padded device rows ``n`` request rows cost through this ladder
        (the admission projection's cost unit)."""
        return padded_cost(n, self.buckets)

    def warmup(self) -> "GameScorer":
        """AOT-compile every ladder bucket's program.  After this, serving
        arrival patterns can never compile: each micro-batch maps onto one
        of these executables, and (under ``strict_after_warmup``) an
        off-ladder shape raises instead of silently compiling."""
        with self.telemetry.span("serving.warmup", buckets=len(self.buckets)):
            for b in self.buckets:
                self._program(b)
        self._warm = True
        return self

    def _donate_argnums(self) -> tuple:
        """Donate request buffers (args 2–4: feats/idx/offset) on
        accelerators only.  See the comment at the jit site: on CPU the
        placed buffers can alias the staged host memory and each other
        across replicas, and donating an aliased buffer corrupts scores."""
        if any(d.platform == "cpu" for d in self.table_devices()):
            return ()
        return (2, 3, 4)

    def table_devices(self) -> list:
        """The devices that hold this scorer's serving tables, by id — what
        a summary reports so "each replica on its own chip" is read off the
        arrays, not off the mesh the replica was handed."""
        return devices_of(self._tables)

    # -- program build -------------------------------------------------------
    def _program(self, bucket: int, layout: str = "request"):
        program = self._programs.get((bucket, layout))
        if program is not None:
            return program
        if self._warm and self.strict_after_warmup and layout == "request":
            raise RuntimeError(
                f"no pre-compiled program for bucket {bucket} after warmup "
                f"(compiled: {sorted(b for b, l in self._programs if l == 'request')}); "
                "widen `buckets` or chunk the batch — serving must never "
                "recompile"
            )
        plan, spec = self._plan, self.request_spec

        def score(tables, zero_rows, feats, idx, offset, n_valid):
            valid = jnp.arange(bucket, dtype=jnp.int32) < n_valid
            total = offset
            colds = []
            random_pos = 0
            for c, table in zip(plan, tables):
                dense = spec[c.shard].dense
                if c.kind == "fixed":
                    total = total + _fixed_margins(table, feats[c.shard], dense)
                else:
                    raw = idx[c.name]
                    # The zero row is DYNAMIC published state (it moves when
                    # a grown vocabulary hot-swaps in), never a baked
                    # constant — otherwise growth would mean recompiles.
                    safe = jnp.where(raw >= 0, raw, zero_rows[random_pos])
                    random_pos += 1
                    total = total + serving_gather_margins(
                        table, safe, feats[c.shard], dense
                    )
                    colds.append(
                        jnp.sum((raw < 0) & valid, dtype=jnp.int32)
                    )
            cold = (
                jnp.stack(colds) if colds else jnp.zeros((0,), jnp.int32)
            )
            return jnp.where(valid, total, 0.0), cold

        # Request buffers (feats/idx/offset) are DONATED on accelerators:
        # XLA recycles the uploaded buffers for outputs, so steady-state
        # serving allocates nothing per batch beyond the h2d staging
        # itself.  NOT on CPU — there "device" buffers can zero-copy alias
        # the staged host numpy AND each other across a replicated mesh
        # placement, and a donated alias lets one replica's output write
        # clobber a buffer another replica still reads (observed as
        # intermittent whole-batch garbage; the only CPU-donatable buffer
        # was the offset, whose shape/dtype matches the scores output).
        # On TPU/GPU every h2d is a real copy into device memory, so
        # donation is both safe and the allocation win it exists for.
        jitted = jax.jit(score, donate_argnums=self._donate_argnums())
        sample = self._place(*self._zero_request(bucket), layout=layout)
        import warnings

        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            program = jitted.lower(
                self._tables, self._zero_rows, *abstract_like(sample)
            ).compile()
        self._programs[(bucket, layout)] = program
        self.compilations += 1
        self.telemetry.counter("serving.compilations").inc()
        return program

    def _zero_request(self, bucket: int):
        """Host-side zero request buffers at a bucket's exact layout."""
        feats: Dict[str, object] = {}
        for c in self._plan:
            s = self.request_spec[c.shard]
            if s.dense:
                feats[c.shard] = np.zeros((bucket, s.dim), np.float32)
            else:
                feats[c.shard] = (
                    np.zeros((bucket, s.nnz), np.int32),
                    np.zeros((bucket, s.nnz), np.float32),
                )
        idx = {
            c.name: np.full(bucket, -1, np.int32)
            for c in self._plan if c.kind == "random"
        }
        offset = np.zeros(bucket, np.float32)
        return feats, idx, offset, np.int32(0)

    def _place(self, feats, idx, offset, n_valid, layout: str = "request"):
        """One h2d placement of a staged request, matching the layout the
        bucket program was lowered against.  ``"request"`` replicates the
        micro-batch (put_request — tiny next to the tables); ``"dataset"``
        SHARDS the per-row buffers over the mesh rows: a whole-dataset
        batch replicated would cost one full dataset copy PER DEVICE,
        inverting the micro-batch rationale."""
        if layout == "dataset" and self.mesh is not None:
            from photon_tpu.parallel.mesh import put_replicated, put_sharded

            return (
                *put_sharded((feats, idx, offset), self.mesh),
                put_replicated(jnp.int32(n_valid), self.mesh),
            )
        return put_request((feats, idx, offset, jnp.int32(n_valid)), self.mesh)

    # -- request staging (host side, the sanctioned ingest edge) -------------
    def _stage(self, request: ScoringRequest, bucket: int, n: int,
               vocab: Optional[Dict[str, np.ndarray]] = None):
        """Validate + pad one request to its bucket, join entity keys
        against each coordinate's vocabulary, and coerce dtypes — the
        request-ingest host work.  Padding rows carry zero features, entity
        index -1 (masked from cold counts by ``n_valid``), zero offset."""
        if vocab is None:
            vocab = self._vocab
        feats: Dict[str, object] = {}
        for c in self._plan:
            if c.shard in feats:
                continue
            s = self.request_spec[c.shard]
            leaf = request.features.get(c.shard)
            if leaf is None:
                raise ValueError(f"request is missing shard {c.shard!r}")
            if s.dense:
                # host-sync: request ingest — coercing caller-owned feature
                # rows to upload-ready numpy (no device data involved).
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
        idx: Dict[str, np.ndarray] = {}
        for c in self._plan:
            if c.kind != "random":
                continue
            keys = request.entity_ids.get(c.column)
            if keys is None:
                raise ValueError(
                    f"request is missing id column {c.column!r}"
                )
            # The key->row join (host searchsorted against the sorted
            # vocabulary) is the serving-time shape of the reference's
            # scoring shuffle-join; unknown keys become -1 -> zero row.
            rows = entity_index_for(keys, vocab[c.name])
            idx[c.name] = _pad_rows(rows, bucket, fill=-1)
        offset = (
            np.zeros(bucket, np.float32) if request.offset is None
            else _pad_rows(
                # host-sync: request ingest — offset coercion, host data.
                np.asarray(request.offset, np.float32), bucket
            )
        )
        return feats, idx, offset

    # -- scoring -------------------------------------------------------------
    def score_batch(self, request: ScoringRequest) -> np.ndarray:
        """Score one request micro-batch; returns ``[n]`` float32 raw
        scores (offset + every coordinate's margin; unknown entities get
        the fixed-effect-only fallback).  ONE compiled dispatch + ONE host
        sync; requests wider than the bucket ladder are chunked."""
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

    def score_dataset(self, data: GameDataset) -> np.ndarray:
        """Batch scoring through the SAME device tables and kernels: the
        dataset is one request padded to the next power of two (its own
        bucket, compiled once per dataset shape — the ``score_game``
        non-streamed route), so the batch and online paths cannot drift.
        Unlike request micro-batches, the per-row buffers are SHARDED over
        the mesh (one dataset copy across devices, not one per device)."""
        from photon_tpu.parallel.mesh import mesh_shards, pad_to_multiple

        req = request_from_dataset(data, self.model)
        n = req.num_rows
        if n == 0:
            return np.zeros(0, np.float32)
        # pow2 for shape bucketing, then up to a mesh multiple so the row
        # sharding divides (a no-op on power-of-two meshes).
        bucket = pad_to_multiple(pow2_at_least(n), mesh_shards(self.mesh))
        return self._score_padded(req, bucket, n, layout="dataset")

    def _score_padded(self, request: ScoringRequest, bucket: int,
                      n: int, layout: str = "request") -> np.ndarray:
        t0 = time.monotonic()
        # ONE read of the published (tables, zero_rows, vocab) triple: a
        # concurrent swap_model cannot hand this batch old tables + a new
        # vocabulary (or a moved zero row).
        tables, zero_rows, vocab = self._serving
        program = self._program(bucket, layout=layout)
        feats, idx, offset = self._stage(request, bucket, n, vocab)
        placed = self._place(feats, idx, offset, n, layout=layout)
        out, cold_dev = program(tables, zero_rows, *placed)
        # The response must OWN its memory (the copy below): on CPU the
        # fetch can alias the device output buffer, and with donated inputs
        # that buffer is recycled by the very next batch — a zero-copy view
        # would read the next request's scores (the egress twin of
        # _pad_rows' ingest copy).
        # host-sync: response egress — THE one per-batch fetch; scores and
        # the per-coordinate cold-entity counts ride one device_get.
        fetched_scores, cold = jax.device_get((out, cold_dev))
        scores = np.array(fetched_scores, copy=True)
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
        cold_plan = [c for c in self._plan if c.kind == "random"]
        for c, count in zip(cold_plan, cold):
            if count:
                t.counter("serving.cold_entities", coordinate=c.name).inc(
                    int(count)
                )
        return scores[:n]


def _pad_rows(a: np.ndarray, target: int, fill=0) -> np.ndarray:
    """Pad rows to the bucket — ALWAYS returning memory this module owns.

    The staged buffers are DONATED to the bucket programs, and on CPU
    ``device_put`` can alias suitably-aligned host numpy zero-copy: donating
    an aliased view of the caller's dataset would let XLA write outputs
    into the caller's own arrays (the exact corruption class PR 3's
    XLA-born-donation rule exists for).  ``np.pad`` copies when padding is
    needed; the exact-size case must copy explicitly."""
    short = target - a.shape[0]
    if short <= 0:
        return np.array(a, copy=True)
    widths = [(0, short)] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, widths, constant_values=fill)
