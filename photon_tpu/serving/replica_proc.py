"""Process-backed serving replicas: own runtime, frame protocol, respawn.

PR 12's replicas are threads sharing one Python runtime — "replica
isolation" there is an honest fiction (one GIL, one jax runtime, one
process to crash).  This module makes it real (the ISSUE 13 tentpole;
Snap ML's hierarchy — node-level processes each owning their device set,
supervised from above — is the shape, PAPERS.md 1803.06333):

- **The child** (``python -m photon_tpu.serving.replica_proc``) is a full
  replica runtime: it loads the shared model ARTIFACT (the wire-format
  model file every replica of a fleet reads), builds its own
  :class:`~photon_tpu.serving.scorer.GameScorer`, AOT-warms the bucket
  ladder, then serves the PR 12 length-prefixed frame protocol on a
  loopback socket — ``score`` frames on the data connection, plus the
  supervision vocabulary on a control connection: ``ping``/``pong``
  (liveness), ``swap`` (hot-swap to a newer model artifact, zero child
  recompiles — the scorer's capacity-headroom swap), ``shutdown``.
  Children run on the HOST platform (``JAX_PLATFORMS=cpu``): a chip
  belongs to one process and the parent, which loaded the model with JAX,
  already holds it — ``ServingFleet(backend="subprocess")`` refuses to
  start under a TPU parent; replicas on chips are thread replicas, one
  device each.
- **The parent side** (:class:`SubprocessReplica`) is a drop-in
  :class:`~photon_tpu.serving.router.ScorerReplica`: the router's
  batcher coalesces requests exactly as for a thread replica, and the
  replica's "scorer" (:class:`_RemoteScorer`) exchanges each micro-batch
  as one frame on the data connection.  A dropped connection mid-batch is
  the crash signal: the batch raises
  :class:`~photon_tpu.serving.router.ReplicaDeadError` and the router
  reroutes it exactly-once — the same path an injected
  ``serve:replica_kill`` takes.
- **Fault surface**: ``replica:spawn`` fires at the top of every (re)spawn
  (retriable — the supervisor backs off and retries); ``replica:crash``
  consumed INSIDE the child hard-exits it (``os._exit``), a real crash
  with a real exit code; ``replica:hang`` consumed in the child wedges the
  handler, a real hang only the supervisor's probe deadline can see.

Residency contract (``tools/check_host_sync.py`` guards this module): the
parent side is pure host IO (frames, numpy); the one sanctioned fetch is
the artifact publish, which serializes the model tables to host once per
published version.
"""

from __future__ import annotations

import itertools
import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from photon_tpu.fault.injection import (
    InjectedKillError,
    consume_hang_injection,
    fault_point,
)
from photon_tpu.serving.netfault import maybe_shim
from photon_tpu.serving.router import (
    ReplicaDeadError,
    ScorerReplica,
)
from photon_tpu.serving.scorer import (
    ShardSpec,
    bucket_ladder,
    padded_cost,
)
from photon_tpu.serving.transport import (
    TransportError,
    pack_control,
    pack_error,
    pack_request,
    pack_scores,
    payload_kind,
    read_frame,
    unpack_control,
    unpack_request_ex,
    unpack_request_hx,
    unpack_response_ex,
    write_frame,
    _decode_response,
    _pack,
    _unpack,
)
from photon_tpu.telemetry.distributed import (
    FlightRecorder,
    MergeableHistogram,
    SpanRecord,
    shift_span_times,
    trace_of,
)

ARTIFACT_VERSION = 1
CRASH_EXIT_CODE = 86  # the child's injected-crash exit status


class ReplicaSpawnError(OSError):
    """Spawning a replica child failed (an ``OSError``: the supervisor's
    backoff-and-retry policy applies to a failed spawn exactly as the
    retry layer's does to failed IO)."""


# -- model wire artifact -------------------------------------------------------
#
# The shared model artifact every child loads (at boot and at swap) is ONE
# frame payload — the same header + array-manifest wire format the scoring
# protocol uses, so a model travels exactly like a request: fixed
# coordinates carry their coefficient vector, random coordinates their
# [entities, dim] table and sorted key vocabulary (string keys ride as
# their <U* buffers like any id column).  Serving needs means only; the
# artifact deliberately drops variances.


def pack_model(model, version: int) -> bytes:
    """One GAME model as a wire payload (the shared serving artifact)."""
    from photon_tpu.game.model import FixedEffectModel, RandomEffectModel

    entries = []
    meta = []
    for name, coord in model.coordinates.items():
        if isinstance(coord, FixedEffectModel):
            meta.append({"name": name, "kind": "fixed",
                         "shard": coord.shard_name,
                         "task": coord.model.task_type})
            entries.append(
                ("coef", name,
                 # host-sync: artifact publish — the coefficient vector is
                 # fetched to host once per published model version.
                 np.asarray(coord.coefficients.means, np.float32))
            )
        elif isinstance(coord, RandomEffectModel):
            meta.append({"name": name, "kind": "random",
                         "shard": coord.shard_name,
                         "column": coord.entity_column,
                         "task": coord.task_type})
            # host-sync: artifact publish — the per-entity table is fetched
            # to host once per published model version.
            entries.append(("table", name, np.asarray(coord.table,
                                                      np.float32)))
            # host-sync: keys are host numpy by construction (publish-time).
            entries.append(("keys", name, np.asarray(coord.keys)))
        else:
            raise TypeError(f"cannot publish a {type(coord).__name__}")
    return _pack({
        "v": ARTIFACT_VERSION, "kind": "model",
        "task": model.task_type, "version": int(version), "coords": meta,
        "_arrays": entries,
    })


def unpack_model(payload: bytes):
    """``(GameModel, version)`` from a model artifact payload."""
    from photon_tpu.game.model import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import Coefficients, model_for_task

    header, arrays = _unpack(payload)
    if header.get("kind") != "model":
        raise TransportError(
            f"unexpected artifact kind {header.get('kind')!r}"
        )
    slots: Dict[Tuple[str, str], np.ndarray] = {}
    for entry, arr in zip(header.get("arrays", []), arrays):
        slots[(entry["slot"], entry["name"])] = arr
    coordinates = {}
    for meta in header["coords"]:
        name = meta["name"]
        if meta["kind"] == "fixed":
            coordinates[name] = FixedEffectModel(
                model_for_task(
                    meta["task"], Coefficients(slots[("coef", name)])
                ),
                meta["shard"],
            )
        else:
            coordinates[name] = RandomEffectModel(
                table=slots[("table", name)],
                keys=slots[("keys", name)],
                entity_column=meta["column"],
                shard_name=meta["shard"],
                task_type=meta["task"],
            )
    model = GameModel(coordinates=coordinates, task_type=header["task"])
    return model, int(header.get("version", 0))


def save_model_artifact(path: str, model, version: int) -> None:
    """Atomic artifact publish: temp + fsync + rename, so a reader (a
    booting child) sees the previous complete artifact or the new one."""
    payload = pack_model(model, version)
    fd, tmp = tempfile.mkstemp(
        prefix=os.path.basename(path) + ".", suffix=".tmp",
        dir=os.path.dirname(path) or ".",
    )
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def load_model_artifact(path: str, telemetry=None):
    """``(GameModel, version)`` from an artifact file (retried like any
    guarded model load)."""
    from photon_tpu.fault.retry import retry_call

    def attempt():
        with open(path, "rb") as f:
            return f.read()

    return unpack_model(
        retry_call(attempt, site="model:load", telemetry=telemetry)
    )


class ModelStore:
    """Versioned shared model artifacts under one fleet workdir.

    ``publish()`` writes the wire-format artifact ONCE per model object
    (cached by identity, with a strong reference so the cache key cannot
    be recycled) and returns its path+version; every child — at boot, at
    swap, at respawn — loads from the same file: the shared-model-artifact
    distribution the fleet tier is built on.

    Only the newest ``keep`` versions stay cached (default 2: the served
    model plus its predecessor, which an in-flight swap/rollback may
    still reference) — a long-running fleet rolling models out
    periodically must not grow host memory and workdir disk by one full
    table set per rollout forever.  Re-publishing an evicted model (a
    deep rollback) simply writes it again under a fresh version."""

    def __init__(self, workdir: str, keep: int = 2):
        self.workdir = workdir
        os.makedirs(workdir, exist_ok=True)
        self.keep = max(1, int(keep))
        self._lock = threading.Lock()
        self._published = []  # [(model, path, version)] — strong refs
        self._next = 0

    def publish(self, model) -> Tuple[str, int]:
        with self._lock:
            for m, path, version in self._published:
                if m is model:
                    return path, version
            version = self._next
            self._next += 1
            path = os.path.join(self.workdir, f"model-v{version:06d}.bin")
            save_model_artifact(path, model, version)
            self._published.append((model, path, version))
            while len(self._published) > self.keep:
                _, old_path, _ = self._published.pop(0)
                try:
                    os.unlink(old_path)
                except OSError:
                    pass
            return path, version


# -- the child runtime ---------------------------------------------------------


class _ChildService:
    """The replica child's state: one scorer (+ artifact version) behind a
    lock so a ``swap`` and a concurrent ``score`` can never interleave a
    half-published model (the scorer's own one-assignment publication does
    the real work; the lock only orders version bookkeeping).

    ``telemetry`` is the child's own in-process registry: the scorer's
    ``serving.*`` counters (host_syncs, batches, cold_entities, ...)
    accrue HERE, in the child — the ``stats`` control frame is how they
    reach the parent's run report (ISSUE 14 satellite; ROADMAP fleet
    edge (e))."""

    def __init__(self, replica_id: str, scorer, version: int,
                 telemetry=None, flight_path: Optional[str] = None,
                 generation: int = 0):
        from collections import deque

        from photon_tpu.telemetry import NULL_SESSION

        self.replica_id = replica_id
        self.scorer = scorer
        self.version = version
        # Membership generation (ISSUE 19): seeded from the spawn config,
        # then ratcheted to the max stamp seen on any inbound frame — the
        # child adopts the parent's view and ECHOES its own on every
        # response, so a zombie (a child whose lease expired while a
        # newer generation took over) keeps answering with a stale stamp
        # the parent's exchange loop fences.
        self.generation = int(generation)
        self.telemetry = telemetry or NULL_SESSION
        self.lock = threading.Lock()
        # Observability: the crash flight recorder (flushed to
        # ``flight_path`` at traced-frame ingress, BEFORE scoring — so a
        # SIGKILL mid-batch still leaves the victim's last accepted work
        # on disk), the mergeable compute-latency histogram the parent
        # aggregates fleet-wide, and the overflow queue for spans whose
        # response frame could not carry them (error paths).
        self.process = f"replica-{replica_id}:{os.getpid()}"
        self.flight_path = flight_path
        self.flight = FlightRecorder(self.process)
        self.latency_hist = MergeableHistogram()
        self._pending_spans: deque = deque(maxlen=256)
        self._spans_lock = threading.Lock()

    def _flush_flight(self) -> None:
        if not self.flight_path:
            return
        try:
            self.flight.dump(self.flight_path)
        except OSError:
            pass  # a full disk must not fail the scoring path

    def _drain_spans(self) -> list:
        with self._spans_lock:
            out = list(self._pending_spans)
            self._pending_spans.clear()
        return out

    def _score_frame(self, payload: bytes) -> bytes:
        """One scoring exchange, with the traced-request hop recorded: a
        request carrying a wire trace context gets a child span (ingress →
        compute → egress) shipped back inline on the response header."""
        self.flight.note_frame("in", "score", len(payload))
        self.maybe_fault()
        request, _, seq, rheader = unpack_request_hx(payload)
        gen = rheader.get("gen")
        if gen is not None:
            self.generation = max(self.generation, int(gen))
        ctx = trace_of(request)
        span = None
        if ctx is not None:
            span = SpanRecord(ctx.trace_id, "replica.score", self.process,
                              parent_id=ctx.span_id)
            span.event("ingress", rows=request.num_rows,
                       nbytes=len(payload))
            self.flight.note_span(span, "open")
            self._flush_flight()
        t0 = time.monotonic()
        try:
            if span is not None:
                span.event("compute_begin")
            scores = self.scorer.score_batch(request)
            if span is not None:
                span.event("compute_end")
        except BaseException as e:
            if span is not None:
                span.finish(status="error")
                self.flight.note_span(span, "close")
                with self._spans_lock:
                    self._pending_spans.append(span.to_dict())
            # Echo ``seq`` on the error frame: the parent's seq-matching
            # exchange loop would FENCE a seq-less reply and resend until
            # its deadline — a scoring failure must settle the exchange
            # that caused it, not starve it (ISSUE 19).
            return pack_error(f"{type(e).__name__}: {e}", seq=seq)
        self.latency_hist.observe(time.monotonic() - t0)
        meta = {"version": self.version, "gen": self.generation}
        if span is not None:
            span.event("egress")
            span.attrs["rows"] = request.num_rows
            span.attrs["version"] = self.version
            span.finish()
            self.flight.note_span(span, "close")
            meta["spans"] = [span.to_dict()] + self._drain_spans()
        return pack_scores(scores, seq=seq, meta=meta)

    def serving_counters(self) -> list:
        """This child's scorer-level ``serving.*`` counters as JSON-ready
        ``{name, labels, value}`` rows — the ``stats`` frame payload.
        Values are CUMULATIVE for the child's lifetime; the parent merges
        deltas, so repeated pulls never double-count."""
        snapshot = self.telemetry.registry.snapshot()
        return [
            {"name": m["name"], "labels": dict(m.get("labels") or {}),
             "value": float(m["value"])}
            for m in snapshot.get("counters", [])
            if m["name"].startswith("serving.")
        ]

    def maybe_fault(self) -> None:
        """The child-side fault surface: an injected ``replica:crash``
        HARD-EXITS the child (a real crash with a real exit code — the
        supervisor sees it via ``poll_exit``/the dropped connection), an
        injected ``replica:hang`` wedges this handler thread (a real hang
        only the probe deadline can see; the supervisor kills the child)."""
        try:
            fault_point("replica:crash", replica=self.replica_id)
        except InjectedKillError:
            os._exit(CRASH_EXIT_CODE)
        if consume_hang_injection(self.replica_id):
            time.sleep(3600.0)

    def handle(self, sock: socket.socket, shutdown) -> None:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        while True:
            try:
                payload = read_frame(sock)
            except (OSError, TransportError):
                return
            kind = payload_kind(payload)
            # Control frames echo the caller's ``seq`` (and the pong its
            # generation): the parent's exchange loops discard stale
            # replies left in the pipe by a timed-out earlier exchange —
            # without the echo, a late pong could satisfy the WRONG ping
            # and poison the clock-offset estimate (ISSUE 19).
            seq = None
            try:
                if kind == "score":
                    out = self._score_frame(payload)
                elif kind == "ping":
                    self.maybe_fault()
                    header = unpack_control(payload)
                    seq = header.get("seq")
                    gen = header.get("gen")
                    if gen is not None:
                        self.generation = max(self.generation, int(gen))
                    out = pack_control(
                        "pong", version=self.version, pid=os.getpid(),
                        compilations=self.scorer.compilations,
                        seq=seq, gen=self.generation,
                        # Clock-offset estimation: the child's wall clock,
                        # sampled mid-exchange — the parent subtracts the
                        # RTT midpoint to estimate this host's skew and
                        # de-skews child span timestamps before merging.
                        child_time=time.time(),
                    )
                elif kind == "stats":
                    # Deliberately NOT behind maybe_fault: a stats pull is
                    # advisory telemetry, not a liveness probe — the
                    # injected crash/hang sites stay on the frames whose
                    # failure semantics the supervisor tests pin.
                    seq = unpack_control(payload).get("seq")
                    out = pack_control(
                        "stats", version=self.version,
                        counters=self.serving_counters(),
                        hist=self.latency_hist.snapshot(),
                        seq=seq,
                    )
                elif kind == "spans":
                    # Drain completed-but-unshipped spans (error paths) —
                    # advisory like stats, so NOT behind maybe_fault.
                    seq = unpack_control(payload).get("seq")
                    out = pack_control("spans", spans=self._drain_spans(),
                                       seq=seq)
                elif kind == "swap":
                    header = unpack_control(payload)
                    seq = header.get("seq")
                    model, version = load_model_artifact(header["path"])
                    model_id = header.get("model_id")
                    with self.lock:
                        if model_id is None:
                            self.scorer.swap_model(model)
                        else:
                            # Multi-model arena child: replace ONE tenant
                            # slice; every other hosted model is untouched.
                            self.scorer.swap_model(model, model_id=model_id)
                        self.version = version
                    out = pack_control("ok", version=version, seq=seq)
                elif kind == "shutdown":
                    seq = unpack_control(payload).get("seq")
                    out = pack_control("ok", seq=seq)
                    try:
                        write_frame(sock, out)
                    except OSError:
                        pass
                    shutdown()
                    return
                else:
                    out = pack_error(f"unknown frame kind {kind!r}")
            except BaseException as e:  # surfaced as a typed frame
                out = pack_error(f"{type(e).__name__}: {e}", seq=seq)
            try:
                write_frame(sock, out)
            except OSError:
                return


def _child_main(argv=None) -> None:
    import argparse

    import socketserver

    p = argparse.ArgumentParser("photon_tpu.serving.replica_proc")
    # Optional when the config carries a multi-model "models" map (each
    # tenant then names its own artifact path).
    p.add_argument("--artifact", default=None)
    p.add_argument("--ready-file", required=True)
    p.add_argument("--config", required=True, help="JSON replica config")
    args = p.parse_args(argv)
    cfg = json.loads(args.config)

    # Parent-death watchdog: the parent holds our stdin pipe open for our
    # whole life and never writes to it — EOF means the parent is GONE
    # (crashed, SIGKILLed, or torn down racing a respawn), and an orphaned
    # replica serving nobody forever is a resource leak, not availability.
    def watch_parent():
        try:
            sys.stdin.buffer.read()
        except Exception:  # noqa: BLE001 — any stdin failure == orphaned
            pass
        os._exit(0)

    threading.Thread(target=watch_parent, name="parent-watch",
                     daemon=True).start()

    from photon_tpu.serving.scorer import GameScorer
    from photon_tpu.telemetry import TelemetrySession

    spec = {
        shard: ShardSpec(kind=s["kind"], dim=int(s["dim"]),
                         nnz=int(s.get("nnz", 0)))
        for shard, s in cfg["spec"].items()
    }
    # The child's own registry: scorer counters accrue in THIS process and
    # travel to the parent via the stats frame — never written to disk
    # here (the parent's run report is the one report of the fleet).
    session = TelemetrySession(f"replica-{cfg['replica_id']}")
    if cfg.get("models"):
        # Multi-model arena child: every hosted tenant loads from its own
        # artifact into ONE shared arena + ONE compiled bucket ladder.
        from photon_tpu.serving.arena import MultiModelScorer

        loaded, version = {}, 0
        for mid, path in cfg["models"].items():
            m, v = load_model_artifact(path)
            loaded[mid] = m
            version = max(version, v)
        scorer = MultiModelScorer(
            loaded,
            request_spec=spec,
            buckets=tuple(cfg["buckets"]) if cfg.get("buckets") else None,
            max_batch=int(cfg["max_batch"]),
            min_bucket=int(cfg["min_bucket"]),
            telemetry=session,
            table_capacity_factor=int(cfg.get("table_capacity_factor", 1)),
            table_dtype=cfg.get("table_dtype", "f32"),
            reserve_rows=int(cfg.get("reserve_rows", 0)),
        ).warmup()
    else:
        model, version = load_model_artifact(args.artifact)
        scorer = GameScorer(
            model,
            request_spec=spec,
            buckets=tuple(cfg["buckets"]) if cfg.get("buckets") else None,
            max_batch=int(cfg["max_batch"]),
            min_bucket=int(cfg["min_bucket"]),
            telemetry=session,
            table_capacity_factor=int(cfg.get("table_capacity_factor", 1)),
            table_dtype=cfg.get("table_dtype", "f32"),
        ).warmup()
    service = _ChildService(cfg["replica_id"], scorer, version,
                            telemetry=session,
                            flight_path=cfg.get("flight_path"),
                            generation=int(cfg.get("generation", 0)))

    class _Handler(socketserver.BaseRequestHandler):
        def handle(self):  # noqa: D102 — per-connection loop
            service.handle(self.request, shutdown)

    class _Server(socketserver.ThreadingTCPServer):
        daemon_threads = True
        allow_reuse_address = True

    server = _Server(("127.0.0.1", 0), _Handler)

    def shutdown():
        threading.Thread(target=server.shutdown, daemon=True).start()

    # Atomic readiness handshake: the parent polls for this file.
    ready = {
        "port": server.server_address[1],
        "pid": os.getpid(),
        "version": version,
        "compilations": scorer.compilations,
    }
    tmp = args.ready_file + ".tmp"
    with open(tmp, "w") as f:
        json.dump(ready, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, args.ready_file)
    server.serve_forever()
    server.server_close()


# -- the parent side -----------------------------------------------------------


class _RemoteScorer:
    """Parent-side facade of a child's scorer: mirrors the GameScorer
    surface the replica/batcher/router layers touch (bucket ladder, model,
    compilations, warmup, swap) while ``score_batch`` is one frame
    exchange on the data connection.  A dropped/reset connection raises
    :class:`ReplicaDeadError` — the crash signal the router reroutes on."""

    def __init__(self, replica_id: str, model, version: int,
                 store: ModelStore, request_spec: Dict[str, ShardSpec],
                 buckets, max_batch: int, min_bucket: int,
                 port: int, compilations: int, telemetry=None,
                 timeout_s: float = 300.0, span_sink=None,
                 table_dtype: str = "f32", models: Optional[Dict] = None,
                 generation: int = 0):
        from photon_tpu.telemetry import NULL_SESSION

        self.replica_id = replica_id
        self.model = model
        # Membership generation (ISSUE 19): stamped on every request and
        # ping; the child echoes the stamp on responses, and a response
        # whose stamp disagrees is FENCED — a zombie child (dead-declared
        # but still answering) cannot satisfy a live exchange.
        self.generation = int(generation)
        # Multi-model arena child: the hosted tenant map (id -> model),
        # mirrored parent-side so a respawn can rebuild the same arena and
        # a per-tenant rollout can read the old slice for rollback.
        self.models: Optional[Dict] = dict(models) if models else None
        self.version = version
        # Estimated child-minus-parent wall-clock offset (EWMA over ping
        # RTT midpoints) — applied to child span timestamps before they
        # merge into the parent's trace tree.
        self.clock_offset_s = 0.0
        # Mirrors the child scorer's storage tier so parent-side parity
        # gates (router canary histogram, fleet defaults) see one surface.
        self.table_dtype = str(table_dtype)
        # Observability: completed child spans piggybacked on response
        # headers (or pulled via the ``spans`` control frame) go here; the
        # last shipped histogram snapshot is what the observer aggregates.
        self.span_sink = span_sink
        self.last_hist_snapshot: Optional[dict] = None
        self.request_spec = request_spec
        self.buckets = bucket_ladder(buckets, max_batch, min_bucket)
        self.max_bucket = self.buckets[-1]
        self.compilations = int(compilations)
        self.telemetry = telemetry or NULL_SESSION
        self._store = store
        self._data_lock = threading.Lock()
        self._ctrl_lock = threading.Lock()
        # Last-seen child counter values per (name, labels) — the delta
        # base for stats pulls.  Lives on the scorer (fresh per spawned
        # child), so a respawned child's counters restarting at zero can
        # never produce negative deltas.  The lock serializes WHOLE pulls
        # (exchange + read-merge-update): a supervisor-thread pull racing
        # a direct pull_stats()/close() must not compute two deltas from
        # one stale base and double-count into the parent registry.
        self._stats_seen: Dict[tuple, float] = {}
        self._stats_lock = threading.Lock()
        # Exchange bookkeeping (ISSUE 19): every request/ping carries a
        # process-unique seq the child echoes; on a per-attempt timeout
        # the exchange RESENDS (the frame may have been black-holed by a
        # partition) until ``resend_deadline_s``, fencing any stale-seq
        # replies a prior timed-out attempt left in the pipe.  A dropped
        # CONNECTION (vs. dropped frame) gets one silent reconnect per
        # exchange — rejoin-within-lease, not death.
        self._seq = itertools.count(1)
        self._port = int(port)
        self._timeout_s = float(timeout_s)
        self._closed = False
        self.exchange_timeout_s = 30.0
        self.resend_deadline_s = float(timeout_s)
        self._data = self._connect(port, timeout_s, "data")
        self._ctrl = self._connect(port, timeout_s, "ctrl")

    def _connect(self, port: int, timeout_s: float, chan: str):
        sock = socket.create_connection(("127.0.0.1", port),
                                        timeout=timeout_s)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        # Chaos seam: an installed NetFaultPlan wraps this socket so every
        # partition/duplicate/reorder scenario is reproducible (ISSUE 19).
        return maybe_shim(sock, f"{self.replica_id}:{chan}")

    def _reconnect(self, chan: str):
        """Silent rejoin within the lease window: a dropped control or
        data connection is NOT death — dial the same child again and let
        the caller resend.  Refused (child actually gone) raises, which
        the exchange surfaces as :class:`ReplicaDeadError`."""
        if self._closed:
            raise ConnectionError(
                f"replica {self.replica_id} scorer is disconnected"
            )
        old = self._data if chan == "data" else self._ctrl
        try:
            old.close()
        except OSError:
            pass
        sock = self._connect(self._port, self._timeout_s, chan)
        if chan == "data":
            self._data = sock
        else:
            self._ctrl = sock
        self.telemetry.counter("serving.replica_reconnects",
                               replica=self.replica_id, chan=chan).inc()
        return sock

    # -- GameScorer surface ---------------------------------------------------
    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"batch of {n} rows exceeds max bucket "
                         f"{self.max_bucket}")

    def padded_rows(self, n: int) -> int:
        return padded_cost(n, self.buckets)

    def warmup(self) -> "_RemoteScorer":
        return self  # the child AOT-warmed its ladder at boot

    def score_batch(self, request) -> np.ndarray:
        seq = next(self._seq)
        payload = pack_request(request, seq=seq, gen=self.generation)
        try:
            with self._data_lock:
                scores, header = self._exchange_scores(payload, seq)
        except (socket.timeout, OSError) as e:
            raise ReplicaDeadError(
                f"replica {self.replica_id} child connection lost: {e}"
            ) from e
        spans = header.get("spans")
        if spans and self.span_sink is not None:
            try:
                self.span_sink(spans)
            except Exception:  # noqa: BLE001 — span delivery is advisory
                pass
        return scores

    def _exchange_scores(self, payload: bytes, seq: int):
        """One at-least-once scoring exchange with fencing (ISSUE 19):
        send, then read until a response matching ``seq`` AND the current
        generation arrives.  A per-attempt ``exchange_timeout_s`` silence
        means the frame (either direction) may be black-holed — resend
        until ``resend_deadline_s``.  Duplicated/stale-seq replies are
        discarded and counted; a matching reply stamped with a STALE
        generation raises :class:`ReplicaDeadError` (the zombie fence —
        the router reroutes, exactly-once preserved).  Duplicate sends
        are safe: the child may score a request twice, but only ONE reply
        per seq ever settles the exchange."""
        deadline = time.monotonic() + self.resend_deadline_s
        reconnected = False
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise ConnectionError(
                    f"no matching response for seq {seq} within "
                    f"{self.resend_deadline_s:g}s"
                )
            try:
                self._data.settimeout(
                    min(self.exchange_timeout_s, max(remaining, 0.05))
                )
                write_frame(self._data, payload)
                while True:
                    rseq, scores, exc, header = _decode_response(
                        read_frame(self._data)
                    )
                    if rseq is None:
                        if exc is not None:
                            raise exc  # seq-less child failure: backstop
                        continue
                    if int(rseq) != seq:
                        self.telemetry.counter(
                            "serving.fenced_responses",
                            replica=self.replica_id, reason="stale_seq",
                        ).inc()
                        continue
                    rgen = header.get("gen")
                    if rgen is not None and int(rgen) != int(self.generation):
                        self.telemetry.counter(
                            "serving.fenced_responses",
                            replica=self.replica_id, reason="stale_gen",
                        ).inc()
                        raise ReplicaDeadError(
                            f"replica {self.replica_id} answered from stale "
                            f"generation {rgen} (current {self.generation}) "
                            f"— response fenced"
                        )
                    if exc is not None:
                        raise exc
                    return scores, header
            except socket.timeout:
                self.telemetry.counter(
                    "serving.exchange_resends", replica=self.replica_id
                ).inc()
                continue
            except OSError:
                if reconnected or self._closed:
                    raise
                reconnected = True
                self._reconnect("data")
                continue

    def model_for(self, model_id: str):
        """The hosted model behind one tenant id (multi-model children):
        what a per-tenant rollout reads for its rollback slice."""
        if self.models is None or model_id not in self.models:
            raise KeyError(f"model {model_id!r} is not hosted on replica "
                           f"{self.replica_id}")
        return self.models[model_id]

    def swap_model(self, model, model_id: Optional[str] = None) -> None:
        """Hot-swap the CHILD to a newer model: publish the shared
        artifact (cached per model object — one file serves every replica
        of the fleet) and instruct the child over the control connection.
        The child's scorer does the capacity-headroom swap — zero child
        recompiles, same refusal semantics as a thread replica.
        ``model_id`` targets one tenant slice of a multi-model child; the
        other hosted models are untouched."""
        path, version = self._store.publish(model)
        frame = {"path": path, "version": version}
        if model_id is not None:
            frame["model_id"] = model_id
        header = self._ctrl_exchange("swap", **frame)
        if header.get("kind") != "ok":
            raise TransportError(
                f"swap refused: unexpected reply {header.get('kind')!r}"
            )
        if model_id is not None and self.models is not None:
            self.models[model_id] = model
        if model_id is None:
            self.model = model
            if self.models is not None and self.models:
                self.models[next(iter(self.models))] = model
        self.version = version

    def _ctrl_exchange(self, kind: str, **fields) -> dict:
        """One seq-tagged control exchange: send, then read until the
        reply echoes our seq (discarding stale replies a timed-out
        earlier exchange left in the pipe — counted as fenced)."""
        seq = next(self._seq)
        with self._ctrl_lock:
            write_frame(self._ctrl, pack_control(kind, seq=seq, **fields))
            while True:
                header = unpack_control(read_frame(self._ctrl))
                if header.get("seq") in (None, seq):
                    return header
                self.telemetry.counter(
                    "serving.fenced_responses",
                    replica=self.replica_id, reason="stale_ctrl",
                ).inc()

    # -- supervision ----------------------------------------------------------
    def ping(self, deadline_s: float, gen: Optional[int] = None) -> dict:
        """Liveness ping — the LEASE RENEWAL exchange (ISSUE 19).  The
        ping carries a ``seq`` (stale pongs from timed-out earlier probes
        are fenced, not mistaken for this renewal) and the membership
        generation stamp the child adopts; the deadline rides the socket
        (so a silent partition surfaces as ``socket.timeout`` promptly
        and RELEASES the control lock — the next probe after heal can
        renew), with the watchdog's ``call_with_timeout`` as the backstop
        for a wedged write.  A dropped control connection gets one silent
        reconnect — rejoin within the lease, not death.

        Each pong doubles as a clock-offset sample: the child echoes its
        wall clock, and ``child_time - (t_send + t_recv)/2`` estimates
        this child's skew (the RTT-midpoint trick — symmetric-path NTP).
        An EWMA smooths jitter; the offset de-skews child span timestamps
        before trace merge, so a skewed host cannot misorder hops.  The
        pong also refreshes ``compilations`` — the fleet-level recompile
        ledger stays honest across swaps without an extra frame."""
        from photon_tpu.fault.watchdog import call_with_timeout

        seq = next(self._seq)
        stamp = self.generation if gen is None else int(gen)

        def exchange():
            with self._ctrl_lock:
                deadline = time.monotonic() + deadline_s
                reconnected = False
                while True:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise socket.timeout(
                            f"ping seq {seq} unanswered within "
                            f"{deadline_s:g}s"
                        )
                    try:
                        self._ctrl.settimeout(max(remaining, 0.05))
                        t_send = time.time()
                        write_frame(
                            self._ctrl,
                            pack_control("ping", seq=seq, gen=stamp),
                        )
                        while True:
                            header = unpack_control(read_frame(self._ctrl))
                            if header.get("seq") in (None, seq):
                                break
                            self.telemetry.counter(
                                "serving.fenced_responses",
                                replica=self.replica_id,
                                reason="stale_pong",
                            ).inc()
                        t_recv = time.time()
                        break
                    except socket.timeout:
                        raise
                    except OSError:
                        if reconnected or self._closed:
                            raise
                        reconnected = True
                        self._reconnect("ctrl")
            child_time = header.get("child_time")
            if isinstance(child_time, (int, float)):
                sample = float(child_time) - (t_send + t_recv) / 2.0
                self.clock_offset_s = (
                    sample if self.clock_offset_s == 0.0
                    else 0.8 * self.clock_offset_s + 0.2 * sample
                )
            comps = header.get("compilations")
            if comps is not None:
                self.compilations = int(comps)
            return header

        return call_with_timeout(
            exchange, deadline_s + 1.0, site=f"replica:{self.replica_id}:ping"
        )

    def stats(self, deadline_s: float = 5.0) -> list:
        """Pull the child's cumulative ``serving.*`` counters over the
        control connection (the ``stats`` frame — ISSUE 14 satellite).
        Deadline-bounded like the ping: a wedged child must not hang the
        supervisor's stats pass."""
        from photon_tpu.fault.watchdog import call_with_timeout

        header = call_with_timeout(
            lambda: self._ctrl_exchange("stats"),
            deadline_s, site=f"replica:{self.replica_id}:stats"
        )
        self.last_hist_snapshot = header.get("hist") or self.last_hist_snapshot
        return header.get("counters", [])

    def pull_spans(self, deadline_s: float = 5.0) -> list:
        """Drain the child's completed-but-unshipped spans (error paths)
        over the control connection — deadline-bounded like every other
        control exchange."""
        from photon_tpu.fault.watchdog import call_with_timeout

        header = call_with_timeout(
            lambda: self._ctrl_exchange("spans"),
            deadline_s, site=f"replica:{self.replica_id}:spans"
        )
        return header.get("spans", [])

    def shutdown(self, deadline_s: float = 5.0) -> None:
        from photon_tpu.fault.watchdog import call_with_timeout

        call_with_timeout(lambda: self._ctrl_exchange("shutdown"),
                          deadline_s,
                          site=f"replica:{self.replica_id}:shutdown")

    def disconnect(self) -> None:
        # Latch first: a batcher thread mid-exchange must NOT dial the
        # (possibly respawned-on-the-same-port) child back after teardown.
        self._closed = True
        for sock in (self._data, self._ctrl):
            try:
                sock.close()
            except OSError:
                pass


class SubprocessReplica(ScorerReplica):
    """A serving replica whose runtime is a CHILD PROCESS — its own Python
    and jax runtime, its own device set (dealt via the spawn environment),
    speaking the frame protocol to the router over loopback sockets.

    Drop-in for :class:`ScorerReplica`: the router dispatches, sheds,
    reroutes, and rolls out against it unchanged.  Crash detection is
    structural (child exit code via :meth:`poll_exit`, dropped data
    connection mid-batch → :class:`ReplicaDeadError`); :meth:`respawn`
    spawns a fresh child from the fleet's CURRENT model artifact."""

    def __init__(
        self,
        replica_id: str,
        model,
        store: ModelStore,
        request_spec: Dict[str, ShardSpec],
        buckets=None,
        max_batch: int = 256,
        min_bucket: int = 8,
        max_delay_s: float = 0.002,
        telemetry=None,
        child_env: Optional[Dict[str, str]] = None,
        spawn_timeout_s: float = 120.0,
        table_capacity_factor: int = 1,
        table_dtype: str = "f32",
        models: Optional[Dict] = None,
        reserve_rows: int = 0,
    ):
        self._models = dict(models) if models else None
        self._reserve_rows = int(reserve_rows)
        self._store = store
        self._request_spec = dict(request_spec)
        self._buckets = buckets
        self._min_bucket = min_bucket
        self._table_capacity_factor = int(table_capacity_factor)
        self._table_dtype = str(table_dtype)
        self._spawn_timeout_s = float(spawn_timeout_s)
        self.child_env = dict(child_env or {})
        self._proc: Optional[subprocess.Popen] = None
        self._replica_id = replica_id
        self._cfg_max_batch = int(max_batch)
        # Observability: where the child flushes its flight-recorder ring
        # (the supervisor's postmortem collector reads it after a kill),
        # and the observer-installed sink completed child spans forward to.
        # The sink lives on the REPLICA (not the per-child scorer) so it
        # survives respawn; _spawn hands each child scorer the bound
        # forwarder.
        self.flight_path = os.path.join(store.workdir,
                                        f"{replica_id}.flight.json")
        self.span_sink = None
        scorer = self._spawn(model, telemetry=telemetry)
        super().__init__(replica_id, scorer, max_batch=max_batch,
                         max_delay_s=max_delay_s, telemetry=telemetry)

    # -- child lifecycle ------------------------------------------------------
    def _spawn(self, model, telemetry=None) -> _RemoteScorer:
        """Spawn one child on the current shared artifact and connect —
        the ``replica:spawn`` fault site (retriable: the supervisor backs
        off and retries a failed spawn)."""
        proc, scorer = self._launch_child(
            model, self._table_capacity_factor, telemetry=telemetry,
            generation=getattr(self, "generation", 0),
        )
        self._proc = proc
        return scorer

    def build_replacement(self, model,
                          table_capacity_factor: int) -> Tuple:
        """Spawn (and warm) a REPLACEMENT child at a new capacity factor
        while the current child keeps serving — the background half of a
        zero-downtime rebuild (ISSUE 19).  Returns ``(proc, scorer)``;
        nothing on this replica changes until :meth:`cutover_to`.  The
        replacement is born into generation+1, the stamp the router's
        cutover publishes — any answer the OLD child still produces after
        cutover carries the stale generation and is fenced."""
        return self._launch_child(
            model, int(table_capacity_factor), telemetry=self.telemetry,
            generation=getattr(self, "generation", 0) + 1,
        )

    def cutover_to(self, scorer, proc=None,
                   table_capacity_factor: Optional[int] = None) -> None:
        """Atomically swap serving to a replacement child: new
        submissions flow to the new scorer immediately, the OLD batcher
        drains its queued work against the old child (zero shed), then
        the old child is retired."""
        old_proc = self._proc
        old_scorer = self.scorer
        if table_capacity_factor is not None:
            self._table_capacity_factor = int(table_capacity_factor)
        if proc is not None:
            self._proc = proc
        super().cutover_to(scorer)  # swaps batcher + drains the old one
        try:
            old_scorer.shutdown(deadline_s=5.0)
        except Exception:  # noqa: BLE001 — retirement is best-effort
            pass
        old_scorer.disconnect()
        if old_proc is not None and old_proc.poll() is None:
            old_proc.kill()
            try:
                old_proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass

    def _launch_child(self, model, table_capacity_factor: int,
                      telemetry=None, generation: int = 0) -> Tuple:
        fault_point("replica:spawn", replica=self._replica_id)
        model_paths = None
        if self._models:
            # The store's eviction horizon must cover every hosted tenant
            # plus an in-flight rollout's predecessor — N live artifacts,
            # not the single-model "current + previous" default.
            self._store.keep = max(self._store.keep, len(self._models) + 2)
            # Multi-model arena child: one shared artifact PER tenant
            # (each cached per model object — untouched tenants re-use
            # their published file across respawns).
            model_paths, version = {}, 0
            for mid, m in self._models.items():
                path, v = self._store.publish(m)
                model_paths[mid] = path
                version = max(version, v)
            artifact = next(iter(model_paths.values()))
        else:
            artifact, version = self._store.publish(model)
        ready_path = os.path.join(
            self._store.workdir,
            f"{self._replica_id}-ready-{os.getpid()}-{time.monotonic_ns()}"
            ".json",
        )
        config = {
            "replica_id": self._replica_id,
            "spec": {
                shard: {"kind": s.kind, "dim": s.dim, "nnz": s.nnz}
                for shard, s in self._request_spec.items()
            },
            "buckets": list(self._buckets) if self._buckets else None,
            "max_batch": self._cfg_max_batch,
            "min_bucket": self._min_bucket,
            "table_capacity_factor": int(table_capacity_factor),
            "table_dtype": self._table_dtype,
            "flight_path": self.flight_path,
            "models": model_paths,
            "reserve_rows": self._reserve_rows,
            "generation": int(generation),
        }
        env = dict(os.environ)
        env.update(self.child_env)
        log_path = os.path.join(self._store.workdir,
                                f"{self._replica_id}.log")
        log = open(log_path, "ab")
        try:
            # stdin is a PIPE the parent never writes: the child's
            # parent-death watchdog reads it and exits on EOF, so a crashed
            # (or respawn-racing) parent can never leak orphan children.
            proc = subprocess.Popen(
                [sys.executable, "-m", "photon_tpu.serving.replica_proc",
                 "--artifact", artifact, "--ready-file", ready_path,
                 "--config", json.dumps(config)],
                env=env, stdin=subprocess.PIPE, stdout=log, stderr=log,
            )
        finally:
            log.close()
        deadline = time.monotonic() + self._spawn_timeout_s
        ready = None
        while time.monotonic() < deadline:
            code = proc.poll()
            if code is not None:
                raise ReplicaSpawnError(
                    f"replica {self._replica_id} child exited {code} during "
                    f"startup (log: {log_path})"
                )
            if os.path.exists(ready_path):
                with open(ready_path) as f:
                    ready = json.load(f)
                break
            time.sleep(0.02)
        if ready is None:
            proc.kill()
            raise ReplicaSpawnError(
                f"replica {self._replica_id} child not ready within "
                f"{self._spawn_timeout_s:g}s (log: {log_path})"
            )
        try:
            os.unlink(ready_path)
        except OSError:
            pass
        return proc, _RemoteScorer(
            self._replica_id, model, version, self._store,
            self._request_spec, self._buckets, self._cfg_max_batch,
            self._min_bucket, port=int(ready["port"]),
            compilations=int(ready.get("compilations", 0)),
            telemetry=telemetry, span_sink=self._deliver_spans,
            table_dtype=self._table_dtype, models=self._models,
            generation=int(generation),
        )

    def _deliver_spans(self, spans: list) -> None:
        sink = self.span_sink
        if sink is not None:
            # De-skew the child's wall-clock timestamps onto the parent's
            # clock before they merge into the trace tree (the ping-RTT
            # offset estimate — ROADMAP observability edge (a)).
            offset = getattr(self.scorer, "clock_offset_s", 0.0)
            sink(shift_span_times(spans, offset))

    def poll_exit(self) -> Optional[int]:
        return None if self._proc is None else self._proc.poll()

    @property
    def child_pid(self) -> Optional[int]:
        return None if self._proc is None else self._proc.pid

    def kill_backend(self) -> None:
        """Tear the child down hard (the unhealthy-replica reaper): close
        the sockets — which unwedges a batcher thread blocked on a hung
        exchange — then SIGKILL the process."""
        self.scorer.disconnect()
        if self._proc is not None and self._proc.poll() is None:
            self._proc.kill()
        if self._proc is not None:
            try:
                self._proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass

    def respawn(self, model=None) -> None:
        """Real resurrection: abandon whatever the old batcher held (the
        router reroutes it), reap the dead child, spawn a FRESH child from
        the fleet's current model artifact (re-warmed at boot), and attach
        a fresh batcher.  Dispatch resumes only after ``router.revive()``
        — the canary-gated rejoin.  A multi-model replica respawns its
        whole hosted set (``self._models`` tracks per-tenant swaps)."""
        self.abandon_for_respawn()
        self.kill_backend()
        if self._models:
            # Carry per-tenant swaps that landed on the old child forward.
            old = getattr(self.scorer, "models", None)
            if old:
                self._models = dict(old)
        model = model if model is not None else self.scorer.model
        self.scorer = self._spawn(model, telemetry=self.telemetry)
        self.attach_fresh_batcher()

    def ping(self, deadline_s: float, **kw) -> dict:
        return self.scorer.ping(deadline_s, **kw)

    def pull_spans(self, deadline_s: float = 5.0) -> list:
        spans = self.scorer.pull_spans(deadline_s)
        return shift_span_times(
            spans, getattr(self.scorer, "clock_offset_s", 0.0)
        )

    def pull_stats(self, deadline_s: float = 5.0) -> dict:
        """Pull the child's scorer-level ``serving.*`` counters and merge
        the DELTA since the last pull into the parent's telemetry registry
        under the same metric names plus a ``replica`` label (ISSUE 14
        satellite / ROADMAP fleet edge (e)) — so a subprocess fleet's
        host_syncs/batches/cold_entities land in the parent's run report
        exactly like a thread replica's do.  Idempotent across repeated
        pulls (cumulative child values, delta merge); the seen-state lives
        on the per-child scorer, so a respawned child restarts the base at
        zero.  Returns the merged deltas keyed by (name, labels)."""
        scorer = self.scorer
        seen = getattr(scorer, "_stats_seen", None)
        stats = getattr(scorer, "stats", None)
        lock = getattr(scorer, "_stats_lock", None)
        if seen is None or stats is None or lock is None:
            return {}
        with lock:
            merged = {}
            for m in stats(deadline_s):
                name = m.get("name")
                labels = {
                    str(k): str(v) for k, v in (m.get("labels") or {}).items()
                }
                value = float(m.get("value", 0.0))
                key = (name, tuple(sorted(labels.items())))
                delta = value - seen.get(key, 0.0)
                if delta <= 0.0:
                    continue
                seen[key] = value
                self.telemetry.counter(
                    name, replica=self.replica_id, **labels
                ).inc(delta)
                merged[key] = delta
            return merged

    def close(self) -> None:
        # Drain FIRST: close()'s contract (queued requests still get
        # scored) needs the child alive while the batcher empties; tearing
        # the child down first would fail every drained request with
        # ReplicaDeadError.  A dead/hung child makes the drain fail fast
        # (socket errors) inside the batcher's bounded join.
        super().close()
        if self._proc is not None and self._proc.poll() is None:
            # Final stats pull AFTER the drain (so the drained batches are
            # counted) and BEFORE teardown — a fleet that never ran a
            # supervisor still gets its children's counters in the report.
            try:
                self.pull_stats(deadline_s=5.0)
            except Exception:  # noqa: BLE001 — stats are advisory
                pass
            try:
                self.scorer.shutdown()
            except (OSError, TransportError):
                pass
        self.kill_backend()


if __name__ == "__main__":
    _child_main()
