"""Large-scale input pipeline: sharded files, chunked batches, streaming.

Rebuild of the reference's billion-row story (SURVEY.md §7 step 7).  The
reference leans on Spark: executors each own partitions, ``treeAggregate``
folds them, and the "pipeline" is the cluster.  The TPU equivalents, by
dataset size:

1. **Fits in HBM** — one :class:`photon_tpu.data.batch.SparseBatch` (the
   default path everywhere else in the framework).
2. **Fits in HBM, but intermediates don't** — :class:`ChunkedBatch`: the
   batch stacked as ``[num_chunks, rows_per_chunk, ...]``; the objective
   folds chunks with ``lax.scan``, bounding peak activation memory while
   remaining ONE jittable function — it slots into the existing jitted
   optimizers unchanged (chunk loop ≙ the reference's per-partition fold).
3. **Host RAM only** — :func:`stream_chunks` + :func:`streaming_lbfgs`:
   per-file host parsing sharded across processes, double-buffered
   host→device transfer, and a host-loop L-BFGS whose every objective
   evaluation is one streamed pass (what a Spark scan of a disk-persisted
   RDD does, minus the JVM).

Multi-host: :func:`shard_files_for_process` gives each host its file slice
and :func:`make_global_batch` assembles per-process arrays into one global
sharded array (``jax.make_array_from_process_local_data``) over the mesh.
"""

from __future__ import annotations

import dataclasses
import functools
import queue
import threading
from typing import Callable, Iterable, Iterator, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from photon_tpu.core.optimizers.base import (
    ConvergenceReason,
    OptimizerConfig,
    OptimizerResult,
    init_history,
)
from photon_tpu.core.optimizers.lbfgs import _two_loop_direction
from photon_tpu.data.batch import LAYOUT_FIELDS, SparseBatch
from photon_tpu.fault.injection import fault_point

# Module-level jit: a per-call `jax.jit(...)` wrapper would carry a fresh
# trace cache, re-tracing the two-loop recursion for every lambda in a
# streamed sweep (same discipline as core/problem.cached_solver).
_jitted_direction = jax.jit(_two_loop_direction, static_argnames=("m",))

Array = jax.Array


# ---------------------------------------------------------------------------
# Tier 2: device-resident chunked batch (lax.scan fold inside jit)
# ---------------------------------------------------------------------------


class ChunkedBatch(NamedTuple):
    """A sparse batch stacked into fixed-size chunks.

    Shapes: ids/vals ``[C, R, k]``; label/offset/weight ``[C, R]``.  Padding
    rows carry zero weight.  The per-chunk fold bounds peak memory for the
    gather intermediates at one chunk's worth (the reference's
    per-partition aggregator fold — SURVEY.md §3.4).
    """

    ids: Array
    vals: Array
    label: Array
    offset: Array
    weight: Array

    @property
    def num_chunks(self) -> int:
        return self.ids.shape[0]

    @property
    def num_examples(self) -> int:
        # Physical rows incl. padding; objectives ignore zero-weight rows.
        return self.ids.shape[0] * self.ids.shape[1]

    def chunk(self, c: int) -> SparseBatch:
        return SparseBatch(
            self.ids[c], self.vals[c], self.label[c],
            self.offset[c], self.weight[c],
        )


def chunk_batch(batch: SparseBatch, rows_per_chunk: int) -> ChunkedBatch:
    """Stack a flat SparseBatch into ``[C, rows_per_chunk, ...]`` chunks."""
    n, k = batch.ids.shape
    c = max(1, -(-n // rows_per_chunk))
    pad = c * rows_per_chunk - n

    def pad_rows(a):
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, widths)

    return ChunkedBatch(
        ids=pad_rows(batch.ids).reshape(c, rows_per_chunk, k),
        vals=pad_rows(batch.vals).reshape(c, rows_per_chunk, k),
        label=pad_rows(batch.label).reshape(c, rows_per_chunk),
        offset=pad_rows(batch.offset).reshape(c, rows_per_chunk),
        weight=pad_rows(batch.weight).reshape(c, rows_per_chunk),
    )


@dataclasses.dataclass(frozen=True)
class ChunkedGlmObjective:
    """GlmObjective adapter folding a ChunkedBatch with ``lax.scan``.

    Exposes the same (value / value_and_grad / hessian_vector) surface the
    optimization problems use, so the existing jitted optimizers run
    unchanged on chunked data.
    """

    objective: object  # GlmObjective

    @property
    def l1_weight(self) -> float:
        return self.objective.l1_weight

    @property
    def l2_weight(self) -> float:
        return self.objective.l2_weight

    def _fold(self, fn, w: Array, chunks: ChunkedBatch, init):
        def step(acc, chunk_leaves):
            chunk = SparseBatch(*chunk_leaves)
            out = fn(w, chunk)
            return jax.tree.map(jnp.add, acc, out), None

        acc, _ = lax.scan(step, init, tuple(chunks))
        return acc

    def value(self, w: Array, chunks: ChunkedBatch) -> Array:
        data = self._fold(self.objective.data_value, w, chunks, jnp.zeros(()))
        if self.objective.l2_weight:
            data = data + 0.5 * self.objective.l2_weight * jnp.dot(w, w)
        return data

    def value_and_grad(self, w: Array, chunks: ChunkedBatch) -> tuple[Array, Array]:
        value, grad = self._fold(
            lambda w_, c: jax.value_and_grad(self.objective.data_value)(w_, c),
            w, chunks, (jnp.zeros(()), jnp.zeros_like(w)),
        )
        l2 = self.objective.l2_weight
        if l2:
            value = value + 0.5 * l2 * jnp.dot(w, w)
            grad = grad + l2 * w
        return value, grad

    def grad(self, w: Array, chunks: ChunkedBatch) -> Array:
        return self.value_and_grad(w, chunks)[1]

    def hessian_vector(self, w: Array, v: Array, chunks: ChunkedBatch) -> Array:
        hv = self._fold(
            lambda w_, c: jax.jvp(
                lambda u: jax.grad(self.objective.data_value)(u, c), (w,), (v,)
            )[1],
            w, chunks, jnp.zeros_like(w),
        )
        return hv + self.objective.l2_weight * v

    def hessian_diagonal(self, w: Array, chunks: ChunkedBatch) -> Array:
        diag = self._fold(
            # data-only diagonal: subtract the per-chunk l2 the underlying
            # objective adds, then add it back once.
            lambda w_, c: self.objective.hessian_diagonal(w_, c)
            - self.objective.l2_weight,
            w, chunks, jnp.zeros_like(w),
        )
        return diag + self.objective.l2_weight

    def hessian_matrix(self, w: Array, chunks: ChunkedBatch) -> Array:
        d = w.shape[0]
        eye = jnp.eye(d, dtype=w.dtype)
        h = self._fold(
            lambda w_, c: self.objective.hessian_matrix(w_, c)
            - self.objective.l2_weight * eye,
            w, chunks, jnp.zeros((d, d), w.dtype),
        )
        return h + self.objective.l2_weight * eye


# ---------------------------------------------------------------------------
# Tier 3: host streaming
# ---------------------------------------------------------------------------


def shard_files_for_process(
    files: Sequence[str],
    process_index: Optional[int] = None,
    process_count: Optional[int] = None,
) -> List[str]:
    """This host's slice of the input file list (round-robin by index) —
    the multi-host replacement for Spark's partition assignment."""
    pi = jax.process_index() if process_index is None else process_index
    pc = jax.process_count() if process_count is None else process_count
    return [f for i, f in enumerate(sorted(files)) if i % pc == pi]


def stream_chunks(
    load_chunk: Callable[[int], Optional[SparseBatch]],
    num_chunks: int,
    prefetch: int = 2,
) -> Iterator[SparseBatch]:
    """Iterate device-ready chunks with background prefetch.

    ``load_chunk(i)`` runs on a worker thread (parse + device_put); the
    consumer overlaps device compute with the next chunk's host work —
    the double-buffering SURVEY.md §7 calls for.  Abandoning the generator
    mid-pass (e.g. an exception in the consumer) stops the worker and
    releases its prefetched device batches instead of pinning them.

    With ``PHOTON_IO_THREADS > 1`` (multi-core hosts) chunks load
    CONCURRENTLY on the host-IO pool — the measured 10M-row streaming pass
    is parse-dominated on one core (BASELINE.md row 5s).  Delivery stays
    strictly ordered, and the in-flight window keeps the SAME device-memory
    bound as the single-worker queue (``prefetch`` chunks plus the one
    being consumed) — concurrency beyond that requires the operator to
    raise ``prefetch``, because each in-flight chunk is device-resident.
    """
    from photon_tpu.utils.io_pool import io_threads, map_ordered

    workers = io_threads()
    # The pooled path needs prefetch >= 2 to beat the single-worker queue
    # (with a window of 1 it would serialize load and compute, losing even
    # the overlap the queue below provides).
    if workers > 1 and num_chunks > 1 and prefetch >= 2:
        yield from (
            c for c in map_ordered(
                load_chunk, range(num_chunks),
                workers=min(workers, prefetch), window=prefetch,
            ) if c is not None
        )
        return
    q: "queue.Queue" = queue.Queue(maxsize=max(1, prefetch))
    sentinel = object()
    stop = threading.Event()

    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for i in range(num_chunks):
                if stop.is_set() or not put(load_chunk(i)):
                    return
        except BaseException as e:  # surface worker errors to the consumer
            put(e)
        finally:
            put(sentinel)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    try:
        while True:
            item = q.get()
            if item is sentinel:
                return
            if isinstance(item, BaseException):
                raise item
            if item is not None:
                yield item
    finally:
        stop.set()
        # Drain so a blocked worker can observe the stop event and exit.
        while True:
            try:
                q.get_nowait()
            except queue.Empty:
                break


@functools.partial(jax.jit, static_argnames=("objective", "kernel"))
def _chunk_value_and_grad(objective, kernel, w: Array, chunk: SparseBatch):
    """Shared jitted per-chunk kernel: module-level with the (hashable)
    objective AND the resolved kernel static, so a lambda sweep reuses
    one compilation per chunk shape — and a mid-process kernel flip
    (env change, kernel-comparison sweep) gets a NEW program instead of
    silently reusing the old kernel's under an identical treedef.

    ``kernel`` is resolved EAGERLY by the caller (the caller strips the
    reg weights, so this is the data term): chunks whose carried aux
    wins the measured selection run that fast kernel; everything else —
    bare chunks, and aux-carrying chunks whose selection says autodiff —
    takes the literal pre-round-5 autodiff path."""
    if kernel is None:
        return jax.value_and_grad(objective.data_value)(w, chunk)
    return objective._fast_data_value_and_grad(w, chunk, kernel)


@dataclasses.dataclass
class StreamingObjective:
    """Objective whose every evaluation is one streamed pass over chunks.

    ``chunk_iter_factory`` yields device SparseBatches (typically via
    :func:`stream_chunks`); evaluation accumulates a jitted per-chunk
    value+grad.  In multi-process runs each process streams its own file
    shard and ``all_reduce`` sums across hosts (psum over DCN).
    """

    objective: object  # GlmObjective
    chunk_iter_factory: Callable[[], Iterable[SparseBatch]]
    all_reduce: Optional[Callable[[Array], Array]] = None
    # The kernel the LAST streamed pass actually ran (first chunk's
    # measured selection; "autodiff" when no fast layout won) — bench
    # attribution must report what ran, not the attach-time intent.
    last_kernel: Optional[str] = None

    def value_and_grad(self, w: Array) -> tuple[Array, Array]:
        # Strip the reg weights from the static jit key: data_value ignores
        # them, so every lambda in a sweep shares one compilation.
        data_obj = dataclasses.replace(
            self.objective, l2_weight=0.0, l1_weight=0.0
        )
        total_v = jnp.zeros(())
        total_g = jnp.zeros_like(w)
        first = True
        for chunk in self.chunk_iter_factory():
            # Resolve the kernel eagerly per chunk (host-side; the
            # selection probe caches per shape bucket) and pass it as a
            # STATIC jit argument — see _chunk_value_and_grad.
            kernel = data_obj._sparse_kernel(chunk, int(w.shape[0]))
            if first:
                first = False
                self.last_kernel = kernel or "autodiff"
            v, g = _chunk_value_and_grad(data_obj, kernel, w, chunk)
            total_v = total_v + v
            total_g = total_g + g
        if self.all_reduce is not None:
            total_v = self.all_reduce(total_v)
            total_g = self.all_reduce(total_g)
        l2 = self.objective.l2_weight
        if l2:
            total_v = total_v + 0.5 * l2 * jnp.dot(w, w)
            total_g = total_g + l2 * w
        return total_v, total_g


def streaming_lbfgs(
    objective: StreamingObjective,
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
    checkpointer=None,
    checkpoint_every: int = 1,
    resume_state=None,
    fingerprint: Optional[dict] = None,
) -> OptimizerResult:
    """Host-loop L-BFGS for datasets that only fit on the host.

    Same math as :func:`photon_tpu.core.optimizers.lbfgs` (shared two-loop
    recursion, Armijo backtracking, cautious pair updates) but each function
    evaluation is a streamed pass, so the outer loop lives in Python — the
    shape of the reference's driver loop, where every evaluation is a
    cluster scan (SURVEY.md §3.4).

    ``checkpointer`` (a :class:`photon_tpu.fault.checkpoint.
    StreamCheckpointer`) snapshots the COMPLETE loop state — iterate,
    gradient, curvature-pair ring buffer, convergence history, and the
    host scalars — every ``checkpoint_every`` iterations plus a final
    ``completed`` snapshot, published through the same atomic protocol and
    async publisher as the GAME descent checkpoints.  ``resume_state``
    restores a snapshot: a resumed fit continues EXACTLY where the
    interrupted one stopped (every streamed pass already run is skipped,
    including the initial evaluation), and a completed snapshot rebuilds
    the result without streaming a single pass.  ``fingerprint`` is
    stamped into each snapshot; compatibility checks are the caller's.
    """
    m = config.history_length
    d = w0.shape[0]
    dtype = w0.dtype
    direction = _jitted_direction

    if resume_state is not None and resume_state.completed:
        if (_stream_converged(resume_state.reason)
                or resume_state.reason == ConvergenceReason.OBJECTIVE_NOT_IMPROVING
                or resume_state.iteration >= config.max_iterations):
            # The fit genuinely finished (converged, line search dead, or
            # this run's budget already spent): rebuild the result from the
            # final snapshot — zero streamed passes.  A fit that stopped on
            # MAX_ITERATIONS resumed with a LARGER budget falls through and
            # continues — same rule as descent checkpoints (the iteration
            # budget is deliberately outside the fingerprint).
            return _result_from_stream_state(resume_state)

    if resume_state is not None:
        arrays, scalars = resume_state.arrays, resume_state.scalars
        w = jnp.asarray(arrays["w"], dtype)
        g = jnp.asarray(arrays["g"], dtype)
        S = jnp.asarray(arrays["S"], dtype)
        Y = jnp.asarray(arrays["Y"], dtype)
        rho = jnp.asarray(arrays["rho"], dtype)
        hv, hg, hvalid = (
            np.array(arrays["hv"]), np.array(arrays["hg"]),
            np.array(arrays["hvalid"]),
        )
        f, gnorm0 = float(scalars["f"]), float(scalars["gnorm0"])
        num_pairs = int(scalars["num_pairs"])
        insert_pos = int(scalars["insert_pos"])
        gamma = float(scalars["gamma"])
        it = resume_state.iteration
        reason = ConvergenceReason.NOT_CONVERGED
    else:
        w = w0
        f, g = objective.value_and_grad(w)
        f, gnorm0 = float(f), float(jnp.linalg.norm(g))
        hv, hg, hvalid = init_history(
            config.max_iterations, jnp.asarray(f), jnp.asarray(gnorm0)
        )
        # np.array (copy): asarray of a jax array is a read-only view.
        hv, hg, hvalid = np.array(hv), np.array(hg), np.array(hvalid)

        S = jnp.zeros((m, d), dtype)
        Y = jnp.zeros((m, d), dtype)
        rho = jnp.zeros(m, dtype)
        num_pairs, insert_pos, gamma = 0, 0, 1.0
        reason = ConvergenceReason.NOT_CONVERGED
        it = 0

        if gnorm0 == 0.0:
            reason = ConvergenceReason.GRADIENT_TOLERANCE

    def snapshot(completed: bool):
        from photon_tpu.fault.checkpoint import StreamState

        return StreamState(
            iteration=it,
            # The history buffers are the loop's MUTABLE scratch — copy at
            # snapshot time so the async publisher serializes a frozen
            # view, not whatever the next iteration wrote into them.
            arrays={
                "w": w, "g": g, "S": S, "Y": Y, "rho": rho,
                "hv": hv.copy(), "hg": hg.copy(), "hvalid": hvalid.copy(),
            },
            scalars={
                "f": f, "gnorm0": gnorm0, "num_pairs": num_pairs,
                "insert_pos": insert_pos, "gamma": gamma,
            },
            completed=completed,
            reason=int(reason),
            fingerprint=fingerprint or {},
        )

    from photon_tpu.fault.preemption import (
        PreemptedError,
        consume_preempt_injection,
        preemption_requested,
        preemption_reason,
    )
    from photon_tpu.fault.watchdog import heartbeat

    try:
        while reason == ConvergenceReason.NOT_CONVERGED:
            # The streamed-GLM preemption site: a killed fit restarts from
            # the last published mid-fit snapshot (the descent:kill analog).
            fault_point("stream:kill", iteration=it)
            # Preemption-aware shutdown (SIGTERM, or the injected `preempt`
            # site): the loop state is consistent here, so snapshot it NOW
            # — off the checkpoint_every cadence if need be — drain the
            # publisher so the save is durably published, and exit with
            # the distinct preemption error the driver maps to exit 75.
            consume_preempt_injection(it)
            if preemption_requested():
                if checkpointer is not None:
                    checkpointer.save(snapshot(completed=False))
                    checkpointer.drain()
                    hint = "resume with --resume auto"
                else:
                    hint = ("no checkpointer configured — a restart begins "
                            "from scratch (set --checkpoint-dir)")
                raise PreemptedError(
                    f"preempted ({preemption_reason()}) before streamed "
                    f"L-BFGS iteration {it}; {hint}"
                )
            heartbeat("stream.iteration")
            reason, w, f, g, S, Y, rho, num_pairs, insert_pos, gamma, it = (
                _stream_lbfgs_step(
                    objective, config, direction, m, dtype, reason, w, f, g,
                    gnorm0, S, Y, rho, num_pairs, insert_pos, gamma, it,
                    hv, hg, hvalid,
                )
            )
            if (checkpointer is not None and checkpoint_every
                    and reason == ConvergenceReason.NOT_CONVERGED
                    and it % checkpoint_every == 0):
                checkpointer.save(snapshot(completed=False))
    except BaseException:
        if checkpointer is not None:
            checkpointer.drain(reraise=False)
        raise
    finally:
        # Retire the iteration heartbeat: a finished (or dead) fit going
        # quiet is not a stall the watchdog should flag.
        from photon_tpu.fault.watchdog import complete

        complete("stream.iteration")
    if checkpointer is not None:
        # Final snapshot: resume rebuilds the finished result without a
        # single streamed pass; the drain is the final-iteration barrier.
        checkpointer.save(snapshot(completed=True))
        checkpointer.drain()

    return OptimizerResult(
        w=w,
        value=jnp.asarray(f),
        grad_norm=jnp.linalg.norm(g),
        iterations=jnp.asarray(it, jnp.int32),
        converged=jnp.asarray(_stream_converged(reason)),
        reason=jnp.asarray(reason, jnp.int32),
        history_value=jnp.asarray(hv),
        history_grad_norm=jnp.asarray(hg),
        history_valid=jnp.asarray(hvalid),
    )


def _stream_converged(reason) -> bool:
    """The ONE definition of 'this streamed fit converged' — shared by the
    live loop's result and the completed-checkpoint rebuild, so the two can
    never drift apart on what counts as converged."""
    return reason in (
        ConvergenceReason.GRADIENT_TOLERANCE,
        ConvergenceReason.FUNCTION_VALUES_TOLERANCE,
    )


def _result_from_stream_state(state) -> OptimizerResult:
    """OptimizerResult rebuilt from a ``completed`` stream snapshot."""
    reason = int(state.reason)
    g = np.asarray(state.arrays["g"])
    return OptimizerResult(
        w=jnp.asarray(state.arrays["w"]),
        value=jnp.asarray(float(state.scalars["f"])),
        grad_norm=jnp.asarray(float(np.linalg.norm(g))),
        iterations=jnp.asarray(state.iteration, jnp.int32),
        converged=jnp.asarray(_stream_converged(reason)),
        reason=jnp.asarray(reason, jnp.int32),
        history_value=jnp.asarray(state.arrays["hv"]),
        history_grad_norm=jnp.asarray(state.arrays["hg"]),
        history_valid=jnp.asarray(state.arrays["hvalid"]),
    )


def _stream_lbfgs_step(
    objective, config, direction, m, dtype, reason, w, f, g, gnorm0,
    S, Y, rho, num_pairs, insert_pos, gamma, it, hv, hg, hvalid,
):
    """One host-loop L-BFGS iteration (direction, line search, pair
    update, convergence check); history buffers mutate in place."""
    while True:  # single pass; structured as a loop for early breaks
        dvec = direction(
            g, S, Y, rho,
            jnp.asarray(num_pairs, jnp.int32),
            jnp.asarray(insert_pos, jnp.int32),
            jnp.asarray(gamma, dtype), m,
        )
        dir_deriv = float(jnp.dot(g, dvec))
        if dir_deriv >= 0.0:
            dvec = -g
            dir_deriv = -float(jnp.dot(g, g))
        t = 1.0 if num_pairs else 1.0 / max(float(jnp.linalg.norm(g)), 1.0)

        ls_ok = False
        for _ in range(config.max_line_search):
            w_try = w + t * dvec
            f_try, g_try = objective.value_and_grad(w_try)
            f_try = float(f_try)
            if np.isfinite(f_try) and f_try <= f + 1e-4 * t * dir_deriv:
                ls_ok = True
                break
            t *= 0.5
        if not ls_ok:
            reason = ConvergenceReason.OBJECTIVE_NOT_IMPROVING
            break

        svec = w_try - w
        yvec = g_try - g
        sy = float(jnp.dot(svec, yvec))
        if sy > 1e-10:
            S = S.at[insert_pos].set(svec)
            Y = Y.at[insert_pos].set(yvec)
            rho = rho.at[insert_pos].set(1.0 / sy)
            num_pairs = min(num_pairs + 1, m)
            insert_pos = (insert_pos + 1) % m
            gamma = sy / max(float(jnp.dot(yvec, yvec)), 1e-30)

        gnorm_new = float(jnp.linalg.norm(g_try))
        it += 1
        if it < hv.shape[0]:
            hv[it], hg[it], hvalid[it] = f_try, gnorm_new, True
        # Same tolerance semantics as base.check_convergence.
        if gnorm_new <= config.gradient_tolerance * max(gnorm0, 1.0):
            reason = ConvergenceReason.GRADIENT_TOLERANCE
        elif abs(f - f_try) / max(abs(f), 1e-12) <= config.tolerance:
            reason = ConvergenceReason.FUNCTION_VALUES_TOLERANCE
        elif it >= config.max_iterations:
            reason = ConvergenceReason.MAX_ITERATIONS
        w, f, g = w_try, f_try, g_try
        break

    return reason, w, f, g, S, Y, rho, num_pairs, insert_pos, gamma, it


def _scan_rows_nnz(path: str) -> tuple[int, int]:
    """(row count, max nnz per row) without materializing values — the
    metadata-only pass used when the feature dimension is already known.
    Uses the native line indexer when available (the Python fallback is
    the measurable cost of the metadata phase at 10M-row scale)."""
    try:
        from photon_tpu.native import libsvm_native

        meta = libsvm_native.scan_meta(path)
        if meta is not None:
            return meta
    except Exception:  # noqa: BLE001 — metadata must not depend on the .so
        pass
    rows, max_nnz = 0, 0
    with open(path, "rb") as f:
        for raw in f:
            line = raw.split(b"#", 1)[0].strip()
            if not line:
                continue
            rows += 1
            max_nnz = max(max_nnz, line.count(b":"))
    return rows, max_nnz


class LibsvmFileSource:
    """Streamed LIBSVM input: one chunk per file, re-parsed each pass.

    A cheap metadata scan (native parser) fixes the global feature
    dimension and nonzero capacity up front so every chunk shares one
    padded layout (one XLA program).  Each objective evaluation then
    re-streams the files — the disk-persisted-RDD behavior of the
    reference's scans, with parse/transfer overlapped via
    :func:`stream_chunks`.
    """

    def __init__(
        self,
        files: Sequence[str],
        intercept: bool = True,
        binary_labels: bool = True,
        feature_dim: Optional[int] = None,
        telemetry=None,
    ):
        """Metadata must cover the GLOBAL file list (multi-process runs
        shard files AFTER construction via :meth:`with_files` — scanning a
        local shard would give hosts divergent coefficient dimensions).

        With ``feature_dim`` given (e.g. from a feature-indexing job's index
        map), only a cheap row/nnz line scan runs; otherwise each file is
        parsed once to discover the max feature id.  ``telemetry`` receives
        the per-part ``io.retries`` counter of the retried chunk loads.
        """
        if not files:
            raise ValueError("LibsvmFileSource needs at least one file")
        self.files = list(files)
        self.intercept = intercept
        self.binary_labels = binary_labels
        self.telemetry = telemetry
        dim, capacity, total = feature_dim or 0, 1, 0
        if feature_dim is None:
            from photon_tpu.data.libsvm import parse_libsvm
            from photon_tpu.utils.io_pool import io_threads, map_ordered

            def _meta(f):
                # Reduce INSIDE the worker: the pool's result window then
                # holds 3-int tuples, not whole parsed files.
                from photon_tpu.data.libsvm import parse_csr_or_none

                csr = parse_csr_or_none(f)
                if csr is not None:
                    _, row_ptr, _, _, fdim = csr
                    counts = np.diff(row_ptr)
                    cap = int(counts.max()) if counts.size else 1
                    return fdim, max(cap, 1), int(row_ptr.shape[0]) - 1
                data = parse_libsvm(f)
                cap = max((len(r[0]) for r in data.rows), default=1)
                return data.dim, cap, data.num_examples

            # Each in-progress parse holds a whole file transiently: cap
            # the concurrency (same rationale as the validate-data pass).
            for fdim, fcap, fn_rows in map_ordered(
                _meta, self.files, workers=min(io_threads(), 4)
            ):
                dim = max(dim, fdim)
                capacity = max(capacity, fcap)
                total += fn_rows
        else:
            for f in self.files:
                rows, max_nnz = _scan_rows_nnz(f)
                capacity = max(capacity, max_nnz)
                total += rows
        self.feature_dim = dim
        self.capacity = capacity + (1 if intercept else 0)
        self.num_examples = total
        self.dim = dim + (1 if intercept else 0)

    def with_files(self, files: Sequence[str]) -> "LibsvmFileSource":
        """Same (global) metadata, restricted stream list — each process
        calls this with its shard from :func:`shard_files_for_process`."""
        import copy

        out = copy.copy(self)
        out.files = list(files)
        return out

    def _load_chunk(self, i: int) -> SparseBatch:
        from photon_tpu.data.libsvm import load_sparse_batch
        from photon_tpu.fault.injection import fault_point
        from photon_tpu.fault.retry import retry_call

        def _load():
            # Flat-CSR fast path inside (skips per-row numpy views, which
            # cost more than the C++ parse at streaming scale);
            # self.capacity already counts the appended intercept column.
            fault_point("io:read", path=self.files[i])
            return load_sparse_batch(
                self.files[i],
                dim=self.feature_dim,
                intercept=self.intercept,
                capacity=self.capacity,
                binary_labels=self.binary_labels,
            )

        # Part-file re-parses happen once per objective pass: a transient
        # storage error mid-pass must cost a backoff, not the whole
        # streamed fit (io.retries counts recoveries).
        batch, _, _ = retry_call(
            _load, site="libsvm:read", telemetry=self.telemetry
        )
        from photon_tpu.data.stream_layouts import (
            attach_stream_aux,
            stream_kernel,
        )

        if stream_kernel() != "autodiff":
            # Fast-kernel layouts for streamed chunks (VERDICT r5 item
            # 3): built once per file on first touch, cached, then
            # re-attached per pass at stat+load cost.
            batch = attach_stream_aux(batch, self.dim, self.files[i])
        return batch

    def chunk_iter_factory(self) -> Iterable[SparseBatch]:
        # PHOTON_STREAM_PREFETCH raises the in-flight chunk window (each
        # chunk is device-resident, so this trades device memory for host
        # parse parallelism on multi-core hosts — see stream_chunks).
        from photon_tpu.utils.env import env_int

        return stream_chunks(
            self._load_chunk, len(self.files),
            prefetch=env_int("PHOTON_STREAM_PREFETCH", 2, minimum=1),
        )


# ---------------------------------------------------------------------------
# Multi-host assembly
# ---------------------------------------------------------------------------


def make_global_batch(local_batch: SparseBatch, mesh, axis: str = "data",
                      aligned_dim: Optional[int] = None):
    """Assemble per-process local rows into one globally-sharded batch
    (``jax.make_array_from_process_local_data`` over the mesh's data axis —
    the multi-host path SURVEY.md §7 names).  Single-process meshes reduce
    to a plain shard placement.

    With ``aligned_dim`` (and the kernel selector wanting them, asked on
    the GLOBAL entry count), each process builds the aligned layouts for
    ITS local row blocks, with the padded geometry agreed GLOBALLY via a
    process allgather — so the per-process stacked aux leaves concatenate
    into one uniformly-shaped global array and the fast kernels run per
    shard on every host (VERDICT r5 item 2, multi-process leg).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    def build(leaf):
        sharding = NamedSharding(
            mesh, P(axis, *([None] * (leaf.ndim - 1)))
        )
        return jax.make_array_from_process_local_data(
            sharding, np.asarray(leaf)
        )

    def build_tree(aux):
        return jax.tree.map(build, aux)

    core = SparseBatch(*(build(leaf) for leaf in local_batch[:5]))
    local_shards = int(mesh.local_mesh.shape[axis])

    def gather_geometry(local_arr: np.ndarray) -> np.ndarray:
        if jax.process_count() == 1:
            return local_arr
        from jax.experimental import multihost_utils

        return multihost_utils.process_allgather(local_arr, tiled=True)

    wants_aligned = False
    global_entries = None
    if aligned_dim is not None and local_batch.ids.ndim == 2:
        from photon_tpu.ops.sparse_grad_select import (
            aligned_layout_wanted,
            pinned_kernel,
        )

        # Collective-agreement discipline: every decision that gates a
        # collective must itself be computed from GLOBALLY-agreed
        # inputs.  ``aligned_dim`` must be passed uniformly by every
        # process (caller contract, like the mesh itself); the entry
        # count is allgathered so the branch below is identical on
        # every host.
        shapes = np.asarray(gather_geometry(
            np.asarray([list(local_batch.ids.shape)], np.int64)
        ), np.int64)
        if len({tuple(row) for row in shapes.tolist()}) != 1:
            # make_array_from_process_local_data requires uniform
            # per-process contributions for P(axis) row sharding; with
            # unequal [n, k] SHAPES (entry counts alone could
            # coincide, e.g. 100x2 vs 50x4) the per-process aux (and
            # core) leaves would diverge into a cross-host hang.  The
            # gathered shapes are identical on every host, so every
            # process raises this SAME error — loud, not a deadlock.
            raise ValueError(
                f"make_global_batch requires equal local batch shapes "
                f"across processes (got {shapes.tolist()}); pad local "
                "batches first"
            )
        global_entries = int(shapes.prod(axis=1).sum())
        # Mirror DistributedGlmObjective._sparse_kernel's multi-process
        # auto pin: the objective will run autodiff, so building (and
        # shipping to HBM) aux it will never touch is pure waste — AND
        # this pin is what makes the gate host-uniform: a forced mode
        # resolves aligned_layout_wanted from the env alone (no per-host
        # probes), so no host can diverge around the geometry
        # collectives.  PHOTON_SPARSE_GRAD must be set uniformly across
        # processes (caller contract, like the mesh).
        wants_aligned = not (
            jax.process_count() > 1 and pinned_kernel() is None
        ) and aligned_layout_wanted(global_entries)
    rebuilt = False
    if wants_aligned or (
        local_batch.fm is not None
        and int(local_batch.fm.ids.shape[0]) != local_shards
    ):
        # Rebuild the aux at the right granularity (one block per local
        # device) — and, when eligible, with the aligned layouts.
        from photon_tpu.data.batch import attach_feature_major

        local_batch = attach_feature_major(
            local_batch._replace(**dict.fromkeys(LAYOUT_FIELDS)),
            shards=local_shards,
            aligned_dim=aligned_dim if wants_aligned else None,
            geometry_gather=gather_geometry,
        )
        rebuilt = True
    # Beside the per-block fm, forward ONLY aux this assembly built
    # (stacked, with globally agreed geometry).  Caller-attached
    # single-block aux cannot be row-sharded — it is dropped here,
    # exactly as before round 5.
    for aux_name in LAYOUT_FIELDS if rebuilt else ("fm",):
        aux = getattr(local_batch, aux_name)
        if aux is not None:
            core = core._replace(**{aux_name: build_tree(aux)})
    return core
