"""Preemption-safe checkpoint/resume for GAME coordinate descent.

The reference's recovery story is Spark lineage plus per-iteration HDFS
model dumps; a preempted TPU slice has neither.  This module snapshots the
full descent state after every outer iteration — per-coordinate models,
the residual engine's score rows (fetched once, off the hot path), the
best-model-so-far, validation-metric history, and the iteration/quarantine
counters — into a versioned on-disk checkpoint published with the atomic
protocol of :mod:`photon_tpu.fault.atomic`:

    <dir>/ckpt-000002/
        state.json      # iteration, history, best metrics, fingerprint
        arrays.npz      # model tables + residual score rows (exact dtypes)
        manifest.json   # content hashes, written last
    <dir>/LATEST        # pointer file, replaced atomically

Resume rebuilds the device score tables from the snapshot rows and warm
starts every coordinate from its checkpointed model, so a resumed fit is
numerically identical to an uninterrupted one (score rows round-trip at
their native dtype: f32 for the device engine, f64 for the host escape
hatch).  Under multi-controller runs every rank LOADS the checkpoint (the
directory must be on storage all ranks can read) but only rank 0 WRITES —
the same primary-writes rule the drivers use for models and reports.

Async publishing (``PHOTON_CHECKPOINT_ASYNC`` / ``--checkpoint-async``,
default on): the per-iteration snapshot is split into a cheap STAGING step
on the descent thread — ``copy_to_host_async()`` starts the d2h copies of
every score row and model table together, then gathers them (the transfers
overlap in flight instead of fetching serially) — and the expensive
serialize + fsync + atomic-rename publish, which runs on a dedicated
publisher thread with bounded depth 1.  The training loop blocks only when
the PREVIOUS publish is still in flight (``checkpoint.blocked_s``); a
publish failure is re-raised at the next save (or the final drain) — never
swallowed; and the final iteration drains the publisher before the fit
returns, so a completed run always ends with its last checkpoint published.
Durability window: under async publishing ``LATEST`` may lag the training
loop by one iteration — a kill can lose at most the single snapshot that
was still in flight (the previous published checkpoint stays intact; the
same atomic temp+fsync+rename protocol runs on the publisher thread, and
the ``checkpoint:stage`` / ``checkpoint:write`` fault sites keep firing
inside its staging and torn-write windows).

Mesh-shape portability (elastic resume): a checkpoint records only the
LOGICAL layout of the fit — unpadded row counts, per-coordinate entity
vocabularies and dimensions (the ``layout`` payload section, digested into
the manifest) — never the mesh shape that wrote it.  Score rows are
snapshotted trimmed to the logical length, model tables at their logical
``[entities, dim]`` shape, and every padded/sharded device buffer is
rebuilt at load time against the RESUMING run's mesh
(:func:`photon_tpu.parallel.mesh.reshard_to_mesh` and the engines'
``load_rows``).  The compatibility fingerprint pins the logical layout and
deliberately contains NO device-, process-, or mesh-shape component — so a
fit written on N processes/devices resumes on M (preemptible capacity,
mid-sweep mesh resizes), and the resumed state is bit-identical to the
saved one.

Host-side RSS bound: the async publisher holds one in-flight snapshot's
staged host copies.  ``checkpoint.staged_bytes`` gauges that residency,
and ``max_staged_mb`` (``--checkpoint-max-staged-mb``;
``PHOTON_CHECKPOINT_MAX_STAGED_MB``) caps it — a snapshot over the cap
publishes BLOCKING on the loop thread (``checkpoint.staged_fallback_sync``
counts the fallbacks) instead of holding a second GB-scale snapshot while
the loop runs ahead.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import threading
import time
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from photon_tpu.fault.atomic import (
    atomic_dir,
    atomic_write_bytes,
    verify_manifest,
    write_manifest,
)
from photon_tpu.fault.injection import fault_point
from photon_tpu.fault.retry import retry_call
from photon_tpu.telemetry import NULL_SESSION

STATE_VERSION = 1
LATEST_NAME = "LATEST"


class CheckpointError(RuntimeError):
    """A checkpoint could not be loaded (missing, corrupt, or mismatched)."""


def resolve_checkpoint_async(mode=None) -> bool:
    """Resolve the checkpoint-publishing mode: True = async publisher.

    Precedence: explicit ``mode`` (driver flag / bool) over the
    ``PHOTON_CHECKPOINT_ASYNC`` env var over the default (``on``): the
    async publisher is the steady state, synchronous publishing is the
    escape hatch (``--checkpoint-async off``) for storage that misbehaves
    under concurrent writers."""
    if isinstance(mode, bool):
        return mode
    resolved = (
        (mode or "").strip().lower()
        or os.environ.get("PHOTON_CHECKPOINT_ASYNC", "").strip().lower()
        or "on"
    )
    if resolved not in ("on", "off"):
        raise ValueError(
            f"checkpoint-async must be 'on' or 'off', got {resolved!r}"
        )
    return resolved == "on"


def has_published_checkpoint(checkpoint_dir: Optional[str]) -> bool:
    """True when any checkpoint chain under ``checkpoint_dir`` has a
    PUBLISHED version (a LATEST pointer exists) — .tmp-* debris from a run
    killed before its first publish does not count."""
    if not checkpoint_dir or not os.path.isdir(checkpoint_dir):
        return False
    for _dirpath, _dirnames, filenames in os.walk(checkpoint_dir):
        if LATEST_NAME in filenames:
            return True
    return False


def stage_to_host(arrays: Dict[str, object], telemetry=None) -> Dict[str, np.ndarray]:
    """Two-pass d2h staging of a checkpoint's array dict.

    First pass starts ``copy_to_host_async()`` on every device leaf — all
    the transfers go in flight together; second pass gathers them into
    numpy (each gather blocks only on a copy that is already running).
    Host leaves pass straight through.  The gathered bytes are counted as
    ``descent.host_transfer_bytes{path=checkpoint}`` — the sanctioned
    off-hot-path fetch."""
    import jax

    for value in arrays.values():
        if isinstance(value, jax.Array) and value.is_fully_addressable:
            try:
                value.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass  # backends without async d2h fall back to the gather
    staged: Dict[str, np.ndarray] = {}
    d2h_bytes = 0
    for key, value in arrays.items():
        if isinstance(value, jax.Array):
            from photon_tpu.parallel.mesh import to_host

            # host-sync: checkpoint staging — the async copies above put
            # these transfers in flight; this gather is the sanctioned
            # once-per-iteration off-hot-path fetch.
            host = to_host(value)
            d2h_bytes += host.nbytes
        else:
            # host-sync: host leaves (host-engine rows, key vocabularies)
            # normalize through numpy without touching a device.
            host = np.asarray(value)
        staged[key] = host
    if telemetry is not None and d2h_bytes:
        telemetry.counter(
            "descent.host_transfer_bytes", direction="d2h", path="checkpoint"
        ).inc(d2h_bytes)
    return staged


class AsyncPublisher:
    """Dedicated checkpoint-publisher thread with bounded depth 1.

    ``submit(fn)`` first waits out any in-flight publish (the wait is the
    ONLY place the training loop can block on checkpoint IO —
    ``checkpoint.blocked_s`` observes it) and re-raises a previous publish
    failure at the submission site: a failed publish surfaces on the next
    iteration, never silently.  ``drain()`` is the final-iteration barrier —
    it waits for the in-flight publish, stops the thread, and raises any
    pending failure.  ``checkpoint.publish_lag_s`` observes enqueue→landed
    latency per publish."""

    def __init__(self, telemetry=None, name: str = "checkpoint-publisher"):
        self.telemetry = telemetry or NULL_SESSION
        self._name = name
        self._job = None
        self._job_ready = threading.Condition()
        self._idle = threading.Event()
        self._idle.set()
        self._error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = False

    # -- worker --------------------------------------------------------------
    def _run(self) -> None:
        while True:
            with self._job_ready:
                while self._job is None and not self._stop:
                    self._job_ready.wait()
                if self._stop and self._job is None:
                    return
                fn, enqueued = self._job
                self._job = None
            try:
                with self.telemetry.span("checkpoint.publish"):
                    fn()
            except BaseException as e:  # surfaced at the next save/drain
                self._error = e
            finally:
                self.telemetry.histogram("checkpoint.publish_lag_s").observe(
                    time.monotonic() - enqueued
                )
                self._idle.set()

    def _wait_idle(self) -> None:
        t0 = time.monotonic()
        self._idle.wait()
        self.telemetry.histogram("checkpoint.blocked_s").observe(
            time.monotonic() - t0
        )

    def _raise_pending(self) -> None:
        err, self._error = self._error, None
        if err is not None:
            raise err

    # -- API -----------------------------------------------------------------
    def submit(self, fn) -> None:
        """Enqueue one publish; blocks while the previous one is in flight
        (bounded depth 1) and re-raises its failure here."""
        self._wait_idle()
        self._raise_pending()
        if self._thread is None or not self._thread.is_alive():
            self._stop = False
            self._thread = threading.Thread(
                target=self._run, name=self._name, daemon=True
            )
            self._thread.start()
        self._idle.clear()
        with self._job_ready:
            self._job = (fn, time.monotonic())
            self._job_ready.notify()

    def wait(self, reraise: bool = True) -> None:
        """Block until the in-flight publish (if any) lands, WITHOUT
        stopping the thread — the blocking-save fallback's barrier (the
        staged-bytes cap publishes synchronously but keeps the publisher
        alive for later, smaller snapshots)."""
        self._idle.wait()
        if reraise:
            self._raise_pending()

    def drain(self, reraise: bool = True) -> None:
        """Wait out the in-flight publish and stop the thread.  With
        ``reraise`` (the final-iteration barrier) a pending publish failure
        propagates; ``reraise=False`` (error paths) preserves the caller's
        original exception while still quiescing the publisher."""
        self._idle.wait()
        thread = self._thread
        if thread is not None and thread.is_alive():
            with self._job_ready:
                self._stop = True
                self._job_ready.notify()
            thread.join()
        self._thread = None
        if reraise:
            self._raise_pending()
        else:
            self._error = None


def logical_layout(num_examples: int, coordinate_kinds=None) -> dict:
    """The MESH-INDEPENDENT layout of a descent run: logical (unpadded)
    training row count plus each coordinate's kind in update order.  This —
    not any padded shape, shard count, or device count — is what a
    checkpoint pins: padding and sharding are derived from whatever mesh
    the resuming run constructs (reshard_to_mesh)."""
    return {
        "rows": int(num_examples),
        "coordinates": {
            str(name): str(kind)
            for name, kind in (coordinate_kinds or {}).items()
        },
    }


def layout_digest(layout: dict) -> str:
    """Stable digest of a logical layout.  Stamped into the checkpoint
    manifest so tools (and operators) can identify a checkpoint's logical
    shape without opening ``arrays.npz``; the descent load path
    cross-checks it against the payload's layout, so the two can never
    silently disagree (mixed-version artifacts, writer bugs)."""
    import hashlib

    return hashlib.sha256(
        json.dumps(layout, sort_keys=True).encode()
    ).hexdigest()[:16]


def descent_fingerprint(
    task_type: str, coordinate_names, num_examples: int, residual_mode: str,
    config_key: Optional[str] = None,
    validation_key: Optional[str] = None,
    locked=(),
    warm_start: bool = False,
    coordinate_kinds=None,
) -> dict:
    """The ONE definition of a descent run's checkpoint-compatibility
    fingerprint (descent and estimator both check against it): a resumed
    run must be the same descent — same task, coordinate update sequence,
    LOGICAL layout (row count + per-coordinate kinds, via
    :func:`logical_layout`), residual mode, optimization configuration
    (when the caller supplies a key), validation setup (primary evaluator,
    or None for an unevaluated fit), lock list, and warm-start-ness — or
    the restored state would silently be another run's model (or crash on
    a best-metrics shape it never tracked).

    Deliberately ABSENT: any device-count-, process-count-, or mesh-shape-
    dependent component.  Mesh shape is an execution choice, not an
    identity of the fit — dropping it from the fingerprint is what makes
    checkpoints elastic (a fit written on N devices resumes on M; the
    padded/sharded buffers are rebuilt for the resuming mesh at load)."""
    fp = {
        "task_type": task_type,
        "coordinates": list(coordinate_names),
        "layout": logical_layout(num_examples, coordinate_kinds),
        "residual_mode": residual_mode,
        "validation": validation_key,
        "locked": sorted(locked),
        "warm_start": bool(warm_start),
    }
    if config_key is not None:
        fp["config"] = config_key
    return fp


def require_fingerprint(state, expected: dict, what: str):
    """The ONE refusal: pass ``state`` through unless its fingerprint
    differs from ``expected``, in which case raise :class:`CheckpointError`
    naming ``what`` the checkpoint failed to match.  ``state`` may be None
    (nothing checkpointed yet — auto resume starts fresh)."""
    if state is not None and state.fingerprint != expected:
        raise CheckpointError(
            f"checkpoint fingerprint {state.fingerprint} does not match "
            f"{what} ({expected}); refusing to resume"
        )
    return state


def configuration_key(coordinate_configs: dict) -> str:
    """Digest of a sweep point's per-coordinate optimization configs
    (regularization weights, solver settings — frozen-dataclass reprs are
    deterministic and content-bearing).  Deliberately EXCLUDES
    ``descent_iterations``: resuming with more iterations is a supported
    continuation, a different regularization is a different model."""
    import hashlib

    return hashlib.sha256(repr(coordinate_configs).encode()).hexdigest()[:16]


@dataclasses.dataclass
class DescentState:
    """One outer iteration's complete restart state (live model objects;
    (de)serialization to arrays happens in the checkpointer).

    ``residual_rows`` may hold host numpy rows (the host engine, or a
    pre-fetched sync snapshot) or DEVICE row handles (the async staging
    path) — the checkpointer's :func:`stage_to_host` gathers either."""

    iteration: int              # last COMPLETED outer iteration
    num_iterations: int         # the run's target iteration count
    task_type: str
    models: Dict[str, object]
    best_models: Dict[str, object]
    best_metrics: Dict[str, float]
    best_iteration: int
    history: List[dict]
    residual_rows: Dict[str, np.ndarray]
    quarantined: int
    fingerprint: dict
    # Streamed (out-of-core) descents only: the mid-epoch restart cursor —
    # {"chunk_rows", "cursor" (coordinates completed in the in-progress
    # iteration; 0 = iteration boundary), "seq" (monotonic checkpoint
    # sequence), "tile_digests" (per-chunk score-tile content digests,
    # verified on resume)}.  None for resident descents.
    stream: Optional[dict] = None

    @property
    def completed(self) -> bool:
        return self.iteration + 1 >= self.num_iterations and not (
            self.stream or {}
        ).get("cursor")


# -- model <-> array serialization ------------------------------------------


def _models_to_arrays(prefix: str, models: Dict[str, object]):
    """(arrays, meta) for one model dict; array keys are
    ``<prefix><i>__<field>`` (npz-safe, order = meta order).  Device arrays
    are returned AS DEVICE HANDLES — :func:`stage_to_host` fetches them in
    one overlapped staging pass, not one blocking fetch per table."""
    from photon_tpu.game.model import FixedEffectModel, RandomEffectModel

    arrays, meta = {}, []
    for i, (name, model) in enumerate(models.items()):
        key = f"{prefix}{i}__"
        if isinstance(model, FixedEffectModel):
            coeff = model.coefficients
            arrays[key + "means"] = coeff.means
            if coeff.variances is not None:
                arrays[key + "variances"] = coeff.variances
            meta.append({
                "name": name, "kind": "fixed", "shard_name": model.shard_name,
                "has_variances": coeff.variances is not None,
            })
        elif isinstance(model, RandomEffectModel):
            arrays[key + "table"] = model.table
            # host-sync: entity-key vocabularies already live on host.
            arrays[key + "keys"] = np.asarray(model.keys)
            if model.variances is not None:
                arrays[key + "variances"] = model.variances
            meta.append({
                "name": name, "kind": "random", "shard_name": model.shard_name,
                "entity_column": model.entity_column,
                "has_variances": model.variances is not None,
            })
        else:
            raise TypeError(f"cannot checkpoint coordinate model {type(model)!r}")
    return arrays, meta


def _models_from_arrays(prefix: str, meta: List[dict], arrays, task_type: str,
                        mesh=None):
    """Rebuild coordinate models from checkpointed host arrays.

    Tables come back at their LOGICAL ``[entities, dim]`` shapes; with a
    ``mesh`` they are placed replicated over it (the SPMD-correct placement
    for model state every shard reads whole — the elastic-resume leg: the
    mesh here is the RESUMING run's, any shape), single-device otherwise.
    Bulk per-row state (score rows) is re-padded/re-sharded separately by
    the engines' ``load_rows``."""
    from photon_tpu.game.model import FixedEffectModel, RandomEffectModel
    from photon_tpu.models.glm import Coefficients, model_for_task
    from photon_tpu.parallel.mesh import put_replicated

    def place(host):
        return put_replicated(jnp.asarray(host), mesh)

    models = {}
    for i, m in enumerate(meta):
        key = f"{prefix}{i}__"
        variances = (
            place(arrays[key + "variances"]) if m["has_variances"] else None
        )
        if m["kind"] == "fixed":
            glm = model_for_task(
                task_type,
                Coefficients(place(arrays[key + "means"]), variances),
            )
            models[m["name"]] = FixedEffectModel(
                model=glm, shard_name=m["shard_name"]
            )
        else:
            models[m["name"]] = RandomEffectModel(
                table=place(arrays[key + "table"]),
                # host-sync: checkpointed key vocabularies are host data.
                keys=np.asarray(arrays[key + "keys"]),
                entity_column=m["entity_column"],
                shard_name=m["shard_name"],
                task_type=task_type,
                variances=variances,
            )
    return models


class CheckpointPublisherBase:
    """Shared checkpoint publication machinery: versioned directories under
    one root, the atomic temp+fsync+rename protocol with a manifest written
    last, a LATEST pointer, keep-N pruning, rank-0-writes — and the sync or
    async publish path.  :class:`DescentCheckpointer` (GAME descent state)
    and :class:`StreamCheckpointer` (streamed-GLM L-BFGS state) both
    publish through it.

    ``write`` defaults to ``jax.process_index() == 0`` at save time
    (rank-0-writes); every rank may load.  ``keep`` bounds on-disk versions
    (older checkpoints are pruned after a successful publish).
    ``async_publish`` (default: :func:`resolve_checkpoint_async`) routes
    publishes through a dedicated :class:`AsyncPublisher` thread.
    ``max_staged_mb`` (default ``PHOTON_CHECKPOINT_MAX_STAGED_MB``, else
    unbounded) caps the host RSS the async path may hold in staged
    snapshot copies: a snapshot over the cap publishes BLOCKING instead.
    """

    def __init__(self, directory: str, telemetry=None, logger=None,
                 keep: int = 2, write: Optional[bool] = None,
                 async_publish=None, max_staged_mb: Optional[float] = None):
        self.directory = directory
        self.telemetry = telemetry or NULL_SESSION
        self.logger = logger
        self.keep = max(1, keep)
        self._write = write
        self.async_publish = resolve_checkpoint_async(async_publish)
        self._publisher = (
            AsyncPublisher(self.telemetry) if self.async_publish else None
        )
        if max_staged_mb is None:
            raw = os.environ.get(
                "PHOTON_CHECKPOINT_MAX_STAGED_MB", ""
            ).strip()
            try:
                max_staged_mb = float(raw) if raw else None
            except ValueError:
                max_staged_mb = None
        self.max_staged_bytes = (
            None if max_staged_mb is None or max_staged_mb < 0
            else int(max_staged_mb * (1 << 20))
        )

    # -- helpers -------------------------------------------------------------
    def _should_write(self) -> bool:
        if self._write is not None:
            return self._write
        import jax

        return jax.process_index() == 0

    def _ckpt_name(self, iteration: int) -> str:
        return f"ckpt-{iteration:06d}"

    def latest_path(self) -> Optional[str]:
        """The checkpoint directory LATEST points to, or None."""
        pointer = os.path.join(self.directory, LATEST_NAME)
        if not os.path.isfile(pointer):
            return None
        with open(pointer) as f:
            name = f.read().strip()
        path = os.path.join(self.directory, name)
        return path if os.path.isdir(path) else None

    # -- save ----------------------------------------------------------------
    def save_arrays(self, iteration: int, arrays: Dict[str, object],
                    payload: dict) -> Optional[str]:
        """Stage + publish one checkpoint version; returns its final path
        (None on non-writing ranks).

        Staging (the overlapped d2h gather) always happens HERE, on the
        calling thread — device buffers may be donated or mutated the
        moment the training loop resumes, so the host copies must exist
        before this returns.  The publish (serialize + fsync + rename +
        prune) runs synchronously, or on the publisher thread when async:
        the call then blocks only if the PREVIOUS publish is still in
        flight, and a publish failure surfaces at the next save or the
        final :meth:`drain` — never silently.  Checkpoint IO retries like
        any other guarded write; an exhausted retry raises — a run that
        cannot checkpoint is a failed run, not a silently unprotected one."""
        if not self._should_write():
            # A globally-sharded array is fetched by a COLLECTIVE
            # (parallel/mesh.to_host -> process_allgather): every rank must
            # take part, in the writer's order, or the writer hangs in it.
            import jax

            from photon_tpu.parallel.mesh import to_host

            for value in arrays.values():
                if (isinstance(value, jax.Array)
                        and not value.is_fully_addressable):
                    # host-sync: the non-writer's half of the writer's
                    # checkpoint-staging gather (same off-hot-path fetch).
                    to_host(value)
            return None
        t0 = time.monotonic()
        # The d2h-staging fault window: a kill here (or anywhere before the
        # publish rename) leaves the previously published chain untouched.
        fault_point("checkpoint:stage", iteration=iteration)
        staged = stage_to_host(arrays, telemetry=self.telemetry)
        staged_bytes = sum(a.nbytes for a in staged.values())
        # The async publisher's extra host residency is exactly one staged
        # snapshot (bounded depth 1): make it visible, and bound it.
        self.telemetry.gauge("checkpoint.staged_bytes").set(staged_bytes)
        final = os.path.join(self.directory, self._ckpt_name(iteration))

        def publish() -> str:
            return retry_call(
                lambda: self._publish_once(final, staged, payload),
                site="checkpoint:io",
                telemetry=self.telemetry, logger=self.logger,
            )

        if self._publisher is None:
            publish()
        elif (self.max_staged_bytes is not None
                and staged_bytes > self.max_staged_bytes):
            # Over the staged-RSS cap: publish BLOCKING on the loop thread
            # (after surfacing any previous in-flight failure) — the loop
            # pays the serialize+fsync wall clock, and the process never
            # holds this snapshot's host copies while running ahead.
            self._publisher.wait()
            self.telemetry.counter("checkpoint.staged_fallback_sync").inc()
            if self.logger is not None:
                self.logger.info(
                    "checkpoint: staged snapshot %.1f MB over the "
                    "--checkpoint-max-staged-mb cap (%.1f MB); publishing "
                    "blocking", staged_bytes / (1 << 20),
                    self.max_staged_bytes / (1 << 20),
                )
            publish()
        else:
            self._publisher.submit(publish)
        # In async mode this histogram observes the LOOP-SIDE cost (staging
        # + any wait on the previous publish) — the per-iteration premium
        # the descent actually pays; the publisher's own wall clock is
        # checkpoint.publish_lag_s.
        self.telemetry.histogram("checkpoint.write_seconds").observe(
            time.monotonic() - t0
        )
        self.telemetry.counter("checkpoint.saves").inc()
        if self.logger is not None:
            self.logger.info(
                "checkpoint: iteration %d -> %s%s", iteration, final,
                " (async publish)" if self._publisher is not None else "",
            )
        return final

    def drain(self, reraise: bool = True) -> None:
        """Final-iteration barrier: wait for the in-flight async publish
        (no-op in sync mode) and surface its failure.  ``reraise=False``
        quiesces the publisher on error paths without masking the original
        exception."""
        if self._publisher is not None:
            self._publisher.drain(reraise=reraise)

    def _publish_once(self, final: str, arrays: Dict[str, np.ndarray],
                      payload: dict) -> str:
        iteration = int(payload.get("iteration", 0))
        manifest_extra = {"iteration": iteration}
        if "layout" in payload:
            # The logical-layout digest rides the manifest: a resuming run
            # can check layout compatibility before touching arrays.npz.
            manifest_extra["layout_digest"] = layout_digest(payload["layout"])
        with atomic_dir(final) as tmp:
            with open(os.path.join(tmp, "arrays.npz"), "wb") as f:
                np.savez(f, **arrays)
            with open(os.path.join(tmp, "state.json"), "w") as f:
                json.dump(payload, f, indent=1)
            # The torn-write window fault injection aims at: payload files
            # exist, manifest/publish has not happened.  A kill here leaves
            # only an invisible .tmp dir — LATEST still names the previous
            # complete checkpoint.  The site fires on the publisher thread
            # in async mode, so the atomicity tests exercise the real
            # concurrent window.
            fault_point("checkpoint:write", iteration=iteration)
            write_manifest(tmp, extra=manifest_extra)
        atomic_write_bytes(
            os.path.join(self.directory, LATEST_NAME),
            os.path.basename(final).encode(),
        )
        self._prune(keep_name=os.path.basename(final))
        return final

    def _prune(self, keep_name: str) -> None:
        """Drop all but the newest ``keep`` published checkpoints (the one
        just written always survives), plus any ``.tmp-*``/``.old-*``
        debris a hard kill left behind — saves are sequential within the
        writing rank, so anything with those prefixes is stale by the time
        a later save prunes."""
        try:
            entries = os.listdir(self.directory)
        except OSError:
            return
        names = sorted(
            n for n in entries
            if n.startswith("ckpt-")
            and os.path.isdir(os.path.join(self.directory, n))
        )
        stale = [n for n in entries if n.startswith((".tmp-", ".old-"))]
        for name in stale + names[:-self.keep]:
            if name != keep_name:
                shutil.rmtree(
                    os.path.join(self.directory, name), ignore_errors=True
                )

    # -- load ----------------------------------------------------------------
    @staticmethod
    def read_payload(path: str) -> tuple:
        """(payload, arrays) of one checkpoint-version directory, manifest
        verified first and the read retried like any guarded IO."""
        if not os.path.isdir(path):
            raise CheckpointError(f"no checkpoint directory at {path!r}")
        verify_manifest(path)

        def _read():
            fault_point("checkpoint:read", path=path)
            with open(os.path.join(path, "state.json")) as f:
                payload = json.load(f)
            with np.load(os.path.join(path, "arrays.npz")) as arrays:
                return payload, {k: arrays[k] for k in arrays.files}

        payload, arrays = retry_call(_read, site="checkpoint:io")
        if payload.get("version") != STATE_VERSION:
            raise CheckpointError(
                f"{path}: checkpoint version {payload.get('version')!r} "
                f"!= supported {STATE_VERSION}"
            )
        return payload, arrays

    def resolve_resume(self, resume: str) -> Optional[str]:
        """Resolve a ``resume`` spec to a checkpoint-version path: ``auto``
        returns None when nothing is checkpointed yet, ``latest`` requires a
        published checkpoint, anything else is an explicit path."""
        if resume in ("auto", "latest"):
            path = self.latest_path()
            if path is None and resume == "latest":
                raise CheckpointError(
                    f"--resume latest: no checkpoint under {self.directory}"
                )
            return path
        return resume


def _state_layout(state: "DescentState") -> dict:
    """The snapshot's logical (mesh-independent) layout, recorded in the
    payload and digested into the manifest: unpadded score-row lengths plus
    each coordinate model's entity-vocabulary size and dimension.  Padded
    and sharded shapes are deliberately ABSENT — they belong to the mesh
    that happens to execute the fit, and the resuming run derives its own
    (reshard_to_mesh / the engines' load_rows)."""
    from photon_tpu.game.model import RandomEffectModel

    coords = {}
    for name, model in state.models.items():
        if isinstance(model, RandomEffectModel):
            coords[name] = {
                "kind": "random",
                "entities": int(model.num_entities),
                "dim": int(model.dim),
            }
        else:
            coords[name] = {
                "kind": "fixed",
                "dim": int(model.coefficients.means.shape[0]),
            }
    return {
        "rows": {
            name: int(row.shape[0])
            for name, row in state.residual_rows.items()
        },
        "coordinates": coords,
    }


class DescentCheckpointer(CheckpointPublisherBase):
    """Versioned GAME-descent checkpoints (see module docstring): the
    descent's full restart state serialized through the shared publisher."""

    # -- save ----------------------------------------------------------------
    def save(self, state: DescentState) -> Optional[str]:
        """Stage + publish ``state``; returns the checkpoint path (None on
        non-writing ranks).  See :meth:`CheckpointPublisherBase.save_arrays`
        for the sync/async semantics."""
        arrays, models_meta = _models_to_arrays("m", state.models)
        # When the best model IS the current iterate (the common improving-
        # run case), its coordinate models are the same objects as
        # state.models' — store name references instead of fetching and
        # hashing every table twice.
        best_shared = sorted(
            name for name, model in state.best_models.items()
            if state.models.get(name) is model
        )
        best_arrays, best_meta = _models_to_arrays(
            "b",
            {
                name: model for name, model in state.best_models.items()
                if name not in set(best_shared)
            },
        )
        arrays.update(best_arrays)
        for j, (name, row) in enumerate(state.residual_rows.items()):
            arrays[f"r{j}__row"] = row
        payload = {
            "version": STATE_VERSION,
            "iteration": state.iteration,
            "num_iterations": state.num_iterations,
            "task_type": state.task_type,
            "models": models_meta,
            "best_models": best_meta,
            "best_shared": best_shared,
            "best_metrics": state.best_metrics,
            "best_iteration": state.best_iteration,
            "history": state.history,
            "residual_rows": list(state.residual_rows),
            "quarantined": state.quarantined,
            "fingerprint": state.fingerprint,
            "layout": _state_layout(state),
            "stream": state.stream,
        }
        # Streamed descents checkpoint MID-EPOCH (after every coordinate):
        # the version name follows the monotonic stream sequence so two
        # snapshots of one iteration never collide; resident descents keep
        # the one-version-per-iteration naming.
        seq = state.iteration
        if state.stream:
            seq = int(state.stream.get("seq", state.iteration))
        return self.save_arrays(seq, arrays, payload)

    # -- load ----------------------------------------------------------------
    def load(self, resume: str, mesh=None) -> Optional[DescentState]:
        """Resolve ``resume`` and load: ``auto`` returns None when nothing
        is checkpointed yet, ``latest`` requires a checkpoint, anything else
        is an explicit checkpoint-version directory path.  ``mesh`` is the
        RESUMING run's mesh (any shape — checkpoints are mesh-portable):
        restored model state is placed for it."""
        path = self.resolve_resume(resume)
        if path is None:
            return None
        return self.load_path(path, mesh=mesh)

    @staticmethod
    def load_path(path: str, mesh=None) -> DescentState:
        """Load one checkpoint-version directory, verifying its manifest.
        Model tables come back at their logical shapes, placed for ``mesh``
        (the resuming run's — NOT necessarily the writing run's)."""
        payload, arrays = CheckpointPublisherBase.read_payload(path)
        layout = payload.get("layout")
        if layout is not None:
            # Cross-check the manifest's advertised layout digest against
            # the payload it actually shipped: the manifest hash catches
            # corruption, this catches a writer bug / mixed-version
            # artifact where the two were written inconsistently.  The
            # re-read is guarded IO like every other checkpoint read.
            def _read_manifest():
                with open(os.path.join(path, "manifest.json")) as f:
                    return json.load(f)

            advertised = retry_call(
                _read_manifest, site="checkpoint:io"
            ).get("extra", {}).get("layout_digest")
            if advertised is not None and advertised != layout_digest(layout):
                raise CheckpointError(
                    f"{path}: manifest layout digest {advertised!r} does "
                    "not match the payload layout — inconsistent checkpoint "
                    "artifact; refusing to resume"
                )
        task = payload["task_type"]
        models = _models_from_arrays(
            "m", payload["models"], arrays, task, mesh=mesh
        )
        best_models = _models_from_arrays(
            "b", payload["best_models"], arrays, task, mesh=mesh
        )
        for name in payload.get("best_shared", []):
            best_models[name] = models[name]
        # Keep the composite's coordinate order (the update sequence) stable
        # across the reference-dedup round trip.
        best_models = {
            name: best_models[name] for name in models if name in best_models
        } | {
            name: model for name, model in best_models.items()
            if name not in models
        }
        return DescentState(
            iteration=payload["iteration"],
            num_iterations=payload["num_iterations"],
            task_type=task,
            models=models,
            best_models=best_models,
            best_metrics=dict(payload["best_metrics"]),
            best_iteration=payload["best_iteration"],
            history=list(payload["history"]),
            residual_rows={
                name: arrays[f"r{j}__row"]
                for j, name in enumerate(payload["residual_rows"])
            },
            quarantined=int(payload.get("quarantined", 0)),
            fingerprint=payload.get("fingerprint", {}),
            stream=payload.get("stream"),
        )


# -- streamed-GLM L-BFGS checkpoints ----------------------------------------


@dataclasses.dataclass
class StreamState:
    """Mid-fit (or completed) streamed L-BFGS state: everything
    :func:`photon_tpu.data.streaming.streaming_lbfgs` needs to continue a
    fit exactly where it left off — iterate, gradient, curvature-pair ring
    buffer, convergence history, and the host-loop scalars.  ``completed``
    marks a final snapshot (the fit converged; resume rebuilds the result
    without streaming a single pass)."""

    iteration: int
    arrays: Dict[str, np.ndarray]   # w, g, S, Y, rho, hv, hg, hvalid
    scalars: dict                   # f, gnorm0, num_pairs, insert_pos, gamma
    completed: bool
    reason: int
    fingerprint: dict


class StreamCheckpointer(CheckpointPublisherBase):
    """Streamed-GLM L-BFGS checkpoints through the same atomic protocol
    and async publisher as the descent checkpoints (the ROADMAP's
    streamed-GLM mid-fit edge).  One instance owns one lambda's chain."""

    KIND = "stream-lbfgs"

    def save(self, state: StreamState) -> Optional[str]:
        payload = {
            "version": STATE_VERSION,
            "kind": self.KIND,
            "iteration": state.iteration,
            "scalars": state.scalars,
            "completed": state.completed,
            "reason": state.reason,
            "arrays": sorted(state.arrays),
            "fingerprint": state.fingerprint,
        }
        return self.save_arrays(state.iteration, dict(state.arrays), payload)

    def load(self, resume: str) -> Optional[StreamState]:
        path = self.resolve_resume(resume)
        if path is None:
            return None
        payload, arrays = self.read_payload(path)
        if payload.get("kind") != self.KIND:
            raise CheckpointError(
                f"{path}: not a streamed-GLM checkpoint "
                f"(kind={payload.get('kind')!r})"
            )
        return StreamState(
            iteration=int(payload["iteration"]),
            arrays={k: arrays[k] for k in payload["arrays"]},
            scalars=dict(payload["scalars"]),
            completed=bool(payload.get("completed", False)),
            reason=int(payload.get("reason", 0)),
            fingerprint=payload.get("fingerprint", {}),
        )
