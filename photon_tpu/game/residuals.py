"""Device-resident score engines for GAME coordinate descent.

The reference's CoordinateDescent passes residuals between coordinates via
RDD shuffles; the seed rebuilt that as HOST float64 accumulation — every
coordinate of every outer iteration summed the other coordinates' score
vectors in numpy, uploaded the result, and fetched the fresh scores back to
host after rescoring.  That is an O(n · coordinates · iterations) host
round-trip on the hottest loop of GAME training (Snap ML's hierarchy
argument, PAPERS.md: keep hot state at the fastest tier).

Two engines keep score state on device, both built on one stacked table:

- :class:`ResidualEngine` — training-side residual passing.  Training
  offsets for coordinate ``c`` are ``base + (total - scores[c]) + comp`` —
  one O(n) jitted kernel per coordinate instead of a host O(C·n) float64
  accumulate + upload.
- :class:`ValidationEngine` — validation-side incremental scoring.  The
  same table over the validation rows; only the coordinate that just
  trained is re-scored each outer iteration, and the composite margin is
  ``base + total + comp`` from the same compensated-total kernel.  The
  descent loop's one remaining host sync per iteration is the per-metric
  scalars (see ``game.descent``).

Shared table mechanics:

- ``scores`` — ONE stacked ``[C, n_pad]`` float32 table, row ``c`` holding
  coordinate ``c``'s current score vector.  Under a mesh the row length is
  padded to a multiple of the mesh size and SHARDED over the data axis
  (``PartitionSpec(None, "data")``) — each device holds only its column
  slice, one copy of the score state across the mesh instead of the
  replicated copy per device earlier rounds paid for.
- ``total``/``comp`` — a Neumaier-compensated sum of the score rows,
  refreshed by the same jitted kernel that writes an updated row.  The
  compensation term holds the summation parity the host float64 path
  provided.  The scan over rows is element-wise per column, so the sharded
  table needs NO collectives for updates or offsets; reductions that do
  cross shards (validation metrics) get their psums from GSPMD inside the
  jitted metric kernels — the DrJAX shape (arXiv:2403.07128): express the
  map-reduce as sharded collectives and let the partitioner place them.
  Because every rank of a multi-process run executes the same jitted
  programs over globally-sharded arrays, the engine is multi-controller
  safe: ``--residuals device`` is legal under ``jax.process_count() > 1``
  (the PR-2 engine was single-controller and fell back to host).
- Row updates **donate** the score table (and the total/comp pair), so
  rescoring a coordinate recycles its row's buffer instead of allocating a
  second ``[C, n_pad]`` table per update.

Hosts see score data only where the algorithm genuinely needs host values:
per-metric validation scalars once per outer iteration, and model export at
the end.

``PHOTON_RESIDUALS=host`` (or the GAME driver's ``--residuals host``)
restores the seed's host-resident float64 path end to end — the escape
hatch if a backend misbehaves under donation or long async dispatch chains.
``PHOTON_VALIDATION=host`` (``--validation-pipeline host``) does the same
for the validation side alone.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.parallel.mesh import (
    DATA_AXIS,
    axis_sharding,
    mesh_shards,
    pad_to_multiple,
    reshard,
    reshard_to_mesh,
    to_host,
)
from photon_tpu.telemetry import NULL_SESSION
from photon_tpu.utils.device import named_jit

Array = jax.Array


def resolve_residual_mode(mode: Optional[str] = None) -> str:
    """Resolve the operative residual mode: ``device`` | ``host``.

    Precedence: explicit ``mode`` argument (driver flag) over the
    ``PHOTON_RESIDUALS`` env var over the default (``auto`` == device).
    The device engine runs as sharded SPMD programs over globally-sharded
    score rows, so ``auto`` resolves to ``device`` under multi-process runs
    too (the PR-2 single-controller engine used to fall back to host
    there); ``host`` remains the explicit escape hatch.
    """
    resolved = mode or os.environ.get("PHOTON_RESIDUALS", "").strip().lower() \
        or "auto"
    if resolved not in ("auto", "device", "host"):
        raise ValueError(
            f"residual mode must be 'auto', 'device' or 'host', got {resolved!r}"
        )
    return "device" if resolved == "auto" else resolved


def resolve_validation_mode(
    mode: Optional[str] = None, residual_mode: str = "device"
) -> str:
    """Resolve the validation-pipeline mode: ``device`` | ``host``.

    ``auto`` (default) follows the residual mode: a device-resident descent
    run scores and evaluates validation on device too; a host-mode run
    (escape hatch) keeps the seed's host evaluation end to end.  Explicit
    ``device``/``host`` (driver flag or ``PHOTON_VALIDATION``) overrides.
    """
    resolved = mode or os.environ.get("PHOTON_VALIDATION", "").strip().lower() \
        or "auto"
    if resolved not in ("auto", "device", "host"):
        raise ValueError(
            f"validation mode must be 'auto', 'device' or 'host', "
            f"got {resolved!r}"
        )
    if resolved == "auto":
        return "device" if residual_mode == "device" else "host"
    return resolved


def _neumaier_rows(scores: Array) -> tuple[Array, Array]:
    """Compensated column-wise sum of the ``[C, n]`` table -> (total, comp).

    Neumaier's variant of Kahan summation: ``total + comp`` carries the row
    sum to roughly twice f32 precision, which is what lets the f32 engine
    match the host float64 accumulate within validation-metric tolerance.
    Element-wise per column — sharded tables sum shard-locally.
    """
    zero = jnp.zeros_like(scores[0])

    def step(carry, row):
        total, comp = carry
        t = total + row
        lost = jnp.where(
            jnp.abs(total) >= jnp.abs(row),
            (total - t) + row,
            (row - t) + total,
        )
        return (t, comp + lost), None

    (total, comp), _ = jax.lax.scan(step, (zero, zero), scores)
    return total, comp


@functools.partial(
    named_jit, "score_table_update", donate_argnums=(0, 1, 2)
)
def _set_row_and_resum(
    scores: Array, total: Array, comp: Array, c, new_row: Array
) -> tuple[Array, Array, Array, Array]:
    """Write row ``c`` and refresh the compensated total in one program.

    The table and the old total/comp are donated: the update recycles their
    buffers (XLA aliases the output table onto the input) instead of holding
    two ``[C, n]`` tables live.  ``total``/``comp`` are recomputed from the
    full table — never incrementally drifted — so compensation error cannot
    accumulate across descent iterations.

    Non-finite guard: a row containing any NaN/Inf is REJECTED on device —
    the previous row is kept, so one poisoned solve cannot contaminate the
    compensated total (NaN + anything = NaN forever).  The returned ``ok``
    scalar stays on device; the descent loop drains the flags once per
    outer iteration and quarantines the offending coordinate.
    """
    del total, comp  # recomputed below; parameters exist to donate buffers
    with jax.named_scope("residuals/update"):
        ok = jnp.all(jnp.isfinite(new_row))
        scores = scores.at[c].set(jnp.where(ok, new_row, scores[c]))
        new_total, new_comp = _neumaier_rows(scores)
    return scores, new_total, new_comp, ok


@functools.partial(named_jit, "score_table_resum")
def _resum_rows(scores: Array) -> tuple[Array, Array]:
    """Fresh compensated total of a (non-donated) table — the table-growth
    path rebuilds total/comp after appending rows."""
    return _neumaier_rows(scores)


@functools.partial(named_jit, "residuals_offsets")
def _offsets_kernel(base: Array, total: Array, comp: Array,
                    scores: Array, c) -> Array:
    """Training offsets for coordinate ``c``: ``base + Σ_{k≠c} scores[k]``
    as ``base + (total - scores[c]) + comp`` — one fused O(n) program."""
    return base + ((total - scores[c]) + comp)


@functools.partial(named_jit, "validation_composite")
def _composite_kernel(base: Array, total: Array, comp: Array) -> Array:
    """Composite margin over ALL coordinates: ``base + Σ_k scores[k]`` as
    ``base + (total + comp)`` — the validation engine's scoring output."""
    return base + (total + comp)


class _DeviceScoreTable:
    """Shared table state of the residual and validation engines: a stacked
    ``[C, n_pad]`` score table with a maintained Neumaier-compensated total,
    sharded over the mesh data axis (see module docstring).

    ``names`` fixes the row order; ``base_offset`` is the dataset offset
    (``[n]``, uploaded once, zero-padded to ``n_pad``).  ``path`` labels the
    telemetry transfer counters (``residuals`` / ``validation``).
    """

    _PATH = "table"
    _BYTES_GAUGE: Optional[str] = None

    def __init__(
        self,
        base_offset: np.ndarray,
        names: Sequence[str],
        mesh=None,
        telemetry=None,
    ):
        if not names:
            raise ValueError(
                f"{type(self).__name__} needs at least one coordinate"
            )
        self.names = list(names)
        self._row = {name: i for i, name in enumerate(self.names)}
        if len(self._row) != len(self.names):
            raise ValueError(f"duplicate coordinate names in {self.names}")
        self.mesh = mesh
        self.telemetry = telemetry or NULL_SESSION
        # Device-resident ok-flags of recent row updates, drained (ONE tiny
        # host sync) by poll_quarantined once per outer iteration.
        self._pending_guard: list = []
        self.n = int(len(base_offset))
        self.n_pad = pad_to_multiple(self.n, mesh_shards(mesh))
        base = np.zeros(self.n_pad, np.float32)
        # host-sync: one-time base-offset staging (host numpy in; the upload
        # below is the table's entire steady-state h2d cost).
        base[: self.n] = np.asarray(base_offset, np.float32)
        self.base = self._put(base)
        # The table and its running total are the DONATED buffers
        # (_set_row_and_resum recycles them): build them XLA-born via
        # jnp.zeros, never from host numpy memory — a zero-copy host upload
        # entering a donating kernel would be freed out from under numpy.
        self.scores = self._device(
            jnp.zeros((len(self.names), self.n_pad), jnp.float32), axis=1
        )
        self.total = self._device(jnp.zeros(self.n_pad, jnp.float32))
        self.comp = self._device(jnp.zeros(self.n_pad, jnp.float32))
        # The one-time upload is the device path's entire steady-state h2d
        # cost for this table; the host path pays ~2 vectors per coordinate
        # per iteration (see game.descent counters).
        self.telemetry.counter(
            "descent.host_transfer_bytes", direction="h2d", path=self._PATH
        ).inc(self.base.nbytes)
        if self._BYTES_GAUGE:
            self.telemetry.gauge(self._BYTES_GAUGE).set(self.device_bytes)

    def _put(self, host: np.ndarray, axis: int = 0) -> Array:
        if self.mesh is None:
            return jnp.asarray(host)
        return jax.device_put(
            host, axis_sharding(self.mesh, host.ndim, axis, DATA_AXIS)
        )

    def _device(self, dev: Array, axis: int = 0) -> Array:
        """Place an already-device array onto the table's row sharding."""
        if self.mesh is None:
            return dev
        return reshard(dev, axis_sharding(self.mesh, dev.ndim, axis, DATA_AXIS))

    @property
    def device_bytes(self) -> int:
        """Global bytes of the table state (per-device residency is this
        divided by the mesh size — the rows are sharded, not replicated)."""
        return (
            self.scores.nbytes + self.base.nbytes
            + self.total.nbytes + self.comp.nbytes
        )

    def row(self, name: str) -> int:
        return self._row[name]

    def update(self, name: str, new_scores) -> None:
        """Replace ``name``'s score row and refresh the compensated total.
        Donates the previous table buffers.

        Accepts a device row of length ``n_pad`` (the device scoring paths
        emit padded, sharded rows) or a host/device vector of length ``n``
        (host-scored fallbacks; padded and counted as an h2d transfer).
        """
        if isinstance(new_scores, np.ndarray):
            # A host score vector entering the device table is a real h2d
            # transfer (warm-start models scored on host, or a coordinate
            # without a device scoring path) — count it.
            self.telemetry.counter(
                "descent.host_transfer_bytes", direction="h2d", path=self._PATH
            ).inc(new_scores.size * 4)
        new_row = jnp.asarray(new_scores, jnp.float32)
        if new_row.shape not in ((self.n,), (self.n_pad,)):
            raise ValueError(
                f"score vector for {name!r} has shape {new_row.shape}, "
                f"want ({self.n},) or padded ({self.n_pad},)"
            )
        # Logical [n] rows — host fallbacks AND checkpointed rows written
        # under any other mesh shape — are re-padded and re-sharded onto
        # THIS table's mesh here (the elastic-resume placement path);
        # already-padded device rows just re-place (a sharding no-op in
        # the steady state).
        new_row = reshard_to_mesh(new_row, self.mesh)
        with self.telemetry.span(f"{self._PATH}.update", coordinate=name):
            self.scores, self.total, self.comp, ok = _set_row_and_resum(
                self.scores, self.total, self.comp, self._row[name], new_row
            )
        # The ok flag stays a device scalar here (no sync in the hot loop);
        # descent drains it via poll_quarantined at the iteration boundary.
        # Bounded: callers that never poll (benches, direct engine use) cap
        # the backlog instead of growing it per update.
        self._pending_guard.append((name, ok))
        if len(self._pending_guard) > 4096:
            del self._pending_guard[:-4096]
        self.telemetry.counter(
            f"{self._PATH}.updates", coordinate=name
        ).inc()

    def scores_for(self, name: str) -> Array:
        """Coordinate ``name``'s current score row (device view, ``[n]`` —
        padding trimmed)."""
        return self.scores[self._row[name], : self.n]

    def drain_guard_flags(self) -> list:
        """Hand the pending ``(name, ok)`` guard flags to the caller and
        clear them — NO host access: the ok values are device bool scalars
        the descent loop batches into its single per-iteration stats/
        quarantine drain (``jax.device_get`` over everything at once)
        instead of one blocking ``bool()`` per flag."""
        pending, self._pending_guard = self._pending_guard, []
        return pending

    def record_rejected(self, bad: Sequence[str]) -> None:
        """Count rejected row updates (called by whoever drained the
        flags — poll_quarantined below, or the descent boundary drain)."""
        for name in bad:
            self.telemetry.counter(
                f"{self._PATH}.nonfinite_rows", coordinate=name
            ).inc()

    def poll_quarantined(self) -> list:
        """Names whose row updates were rejected (non-finite) since the
        last poll — the standalone-caller form of the guard drain (the
        descent loop batches drain_guard_flags into its one boundary
        sync instead)."""
        pending = self.drain_guard_flags()
        # host-sync: draining the per-update ok flags — bool scalars, the
        # sanctioned quarantine-accounting sync for direct callers.
        bad = [name for name, ok in pending if not bool(ok)]
        self.record_rejected(bad)
        return bad

    def snapshot_rows_async(self) -> dict:
        """Device row handles ``{name: [n]}`` for the ASYNC checkpoint
        staging path: the checkpointer starts ``copy_to_host_async`` on
        them together with the model tables and gathers once — no blocking
        per-row fetch here.  The handles must be materialized before the
        next ``update`` donates the table (the checkpointer stages them
        synchronously inside ``save``, before the loop resumes)."""
        return {
            name: self.scores[self._row[name], : self.n] for name in self.names
        }

    def snapshot_rows(self) -> dict:
        """All score rows as host float32 arrays ``{name: [n]}`` — the
        checkpoint snapshot, fetched ONCE per outer iteration off the hot
        path (to_host gathers across processes under multi-controller)."""
        # host-sync: checkpoint snapshot — the sanctioned off-hot-path
        # fetch of the score table.
        table = to_host(self.scores)
        self.telemetry.counter(
            "descent.host_transfer_bytes", direction="d2h", path="checkpoint"
        ).inc(table.nbytes)
        return {
            name: np.array(table[self._row[name], : self.n])
            for name in self.names
        }

    def load_rows(self, rows: dict) -> None:
        """Rebuild the device table from checkpointed rows (resume path):
        one guarded update per coordinate, exactly the state an
        uninterrupted run would hold after the same iterations.

        Checkpointed rows are LOGICAL (unpadded, length ``n``): update()
        re-pads them to THIS run's mesh multiple and re-shards — so a
        checkpoint written under any device/process count restores onto
        whatever mesh this engine was built with (elastic resume)."""
        for name, row in rows.items():
            if name in self._row:
                # host-sync: resume-path upload of checkpointed HOST rows
                # (asarray normalizes dtype; no device fetch happens here).
                self.update(name, np.asarray(row, np.float32))

    def grow(self, base_offset: np.ndarray) -> None:
        """Extend the table to cover APPENDED training rows (incremental
        entity onboarding — ISSUE 8): existing score rows keep their values
        on device (one pad + re-shard, no d2h round-trip), appended rows
        start at zero until the next update()/re-score fills them, and the
        base offset is replaced by the grown vector.  The compensated
        total rebuilds from the grown table, so compensation error cannot
        leak across the growth."""
        new_n = int(len(base_offset))
        if new_n < self.n:
            raise ValueError(
                f"grow() only appends rows: table holds {self.n}, got {new_n}"
            )
        old_scores, old_n = self.scores, self.n
        self.n = new_n
        self.n_pad = pad_to_multiple(new_n, mesh_shards(self.mesh))
        base = np.zeros(self.n_pad, np.float32)
        # host-sync: one-time base-offset staging of the grown vector (an
        # upload, same as __init__ — no device fetch happens here).
        base[: self.n] = np.asarray(base_offset, np.float32)
        self.base = self._put(base)
        self.telemetry.counter(
            "descent.host_transfer_bytes", direction="h2d", path=self._PATH
        ).inc(self.base.nbytes)
        grown = jnp.pad(
            old_scores[:, :old_n], ((0, 0), (0, self.n_pad - old_n))
        )
        self.scores = self._device(grown, axis=1)
        total, comp = _resum_rows(self.scores)
        self.total = self._device(total)
        self.comp = self._device(comp)
        if self._BYTES_GAUGE:
            self.telemetry.gauge(self._BYTES_GAUGE).set(self.device_bytes)


class ResidualEngine(_DeviceScoreTable):
    """Training-side per-coordinate score vectors resident on device with a
    maintained compensated total (see module docstring).

    The fixed effect re-shards the emitted offsets over the data axis (a
    no-op: they already are) and the random-effect bucket gathers pull the
    rows they need across shards — GSPMD inserts the gather.
    """

    _PATH = "residuals"
    _BYTES_GAUGE = "residuals.device_bytes"

    def offsets_for(self, name: str) -> Array:
        """Training offsets for ``name``: ``base + Σ_{other} scores`` as one
        jitted device kernel; float32, shape ``[n_pad]``, sharded over the
        data axis (padding rows carry whatever the base padding holds —
        weight-0 rows never read them)."""
        return _offsets_kernel(
            self.base, self.total, self.comp, self.scores, self._row[name]
        )


class ValidationEngine(_DeviceScoreTable):
    """Validation-side score table: incremental per-coordinate re-scoring
    with a composite margin from the same compensated-total kernel.

    The descent loop updates only the rows whose coordinate just retrained
    (``validation.score_reuse`` counts the rows it did NOT have to touch)
    and evaluates metrics on :meth:`composite` without fetching scores to
    host — see ``game.descent``.
    """

    _PATH = "validation"
    _BYTES_GAUGE = "validation.device_bytes"

    def composite(self) -> Array:
        """Composite validation margin ``base + Σ_k scores[k]`` — float32,
        ``[n_pad]``, sharded; padded rows carry weight 0 for every metric."""
        return _composite_kernel(self.base, self.total, self.comp)


class HostResiduals:
    """The seed's host-resident float64 residual path — the escape hatch.

    Scores live on host as float64 numpy vectors; offsets for a coordinate
    are accumulated in float64 and cast to float32, bit-for-bit the
    pre-engine behavior.  Every coordinate of every outer iteration pays one
    O(C·n) host accumulate, one h2d offsets upload, and one d2h score fetch;
    the same telemetry counters the device engine emits make that recurring
    cost visible next to the engine's one-time upload.
    """

    def __init__(
        self,
        base_offset: np.ndarray,
        names: Sequence[str] = (),
        mesh=None,
        telemetry=None,
    ):
        del names, mesh  # same signature as ResidualEngine; state is host-only
        # host-sync: the escape hatch keeps ALL residual state on host.
        self.base = np.asarray(base_offset, np.float64)
        self.scores: dict = {}
        self._pending_guard: list = []
        self.telemetry = telemetry or NULL_SESSION

    def update(self, name: str, new_scores) -> None:
        """Store ``name``'s score vector on host (fetching it if needed).
        Non-finite vectors are rejected — the previous iterate is kept and
        the coordinate reported via :meth:`poll_quarantined`, mirroring the
        device engine's guarded row writes."""
        # host-sync: the host escape hatch IS the host path — every update
        # fetches one score vector, counted below.
        host = np.asarray(new_scores, np.float64)
        if host.shape != self.base.shape:
            raise ValueError(
                f"score vector for {name!r} has shape {host.shape}, "
                f"want {self.base.shape}"
            )
        if not np.isfinite(host).all():
            self._pending_guard.append(name)
        else:
            self.scores[name] = host
        # The fetch moved one f32 score vector device→host.
        self.telemetry.counter(
            "descent.host_transfer_bytes", direction="d2h", path="residuals"
        ).inc(host.size * 4)
        self.telemetry.counter("residuals.updates", coordinate=name).inc()

    def offsets_for(self, name: str) -> np.ndarray:
        """float32 host offsets; the coordinate's train() uploads them."""
        offsets = self.base.copy()
        for other, s in self.scores.items():
            if other != name:
                offsets += s
        out = offsets.astype(np.float32)
        self.telemetry.counter(
            "descent.host_transfer_bytes", direction="h2d", path="residuals"
        ).inc(out.nbytes)
        return out

    def drain_guard_flags(self) -> list:
        """Pending ``(name, ok)`` flags (host bools here — the escape hatch
        rejected on host at update time); same batching contract as the
        device engines'."""
        bad, self._pending_guard = self._pending_guard, []
        return [(name, False) for name in bad]

    def record_rejected(self, bad) -> None:
        for name in bad:
            self.telemetry.counter(
                "residuals.nonfinite_rows", coordinate=name
            ).inc()

    def poll_quarantined(self) -> list:
        """Names whose updates were rejected (non-finite) since last poll —
        same contract as the device engines' guarded rows."""
        bad = [name for name, _ok in self.drain_guard_flags()]
        self.record_rejected(bad)
        return bad

    def snapshot_rows(self) -> dict:
        """All score rows (host float64 copies) — the checkpoint snapshot.
        Saved at the path's native dtype so a resumed host-mode fit is
        bit-identical to an uninterrupted one."""
        return {name: s.copy() for name, s in self.scores.items()}

    def snapshot_rows_async(self) -> dict:
        """Host engine: rows already live on host — staging is a copy."""
        return self.snapshot_rows()

    def load_rows(self, rows: dict) -> None:
        """Restore checkpointed rows (resume path).  Stored directly —
        checkpointed rows never crossed the device boundary, so routing
        them through update() would count phantom d2h transfer bytes."""
        for name, row in rows.items():
            # host-sync: the host engine restores HOST float64 rows.
            host = np.asarray(row, np.float64)
            if host.shape != self.base.shape:
                raise ValueError(
                    f"checkpointed row for {name!r} has shape {host.shape}, "
                    f"want {self.base.shape}"
                )
            self.scores[name] = host

    def grow(self, base_offset: np.ndarray) -> None:
        """Append-rows growth (entity onboarding), mirroring the device
        engines: existing rows keep their values, appended rows are zero
        until re-scored."""
        # host-sync: the escape hatch keeps ALL residual state on host.
        new_base = np.asarray(base_offset, np.float64)
        old_n = len(self.base)
        if len(new_base) < old_n:
            raise ValueError(
                f"grow() only appends rows: table holds {old_n}, got "
                f"{len(new_base)}"
            )
        self.base = new_base
        self.scores = {
            name: np.pad(s, (0, len(new_base) - old_n))
            for name, s in self.scores.items()
        }
