"""Large-scale input pipeline tests (SURVEY.md §7 step 7): chunked in-HBM
folds, host streaming with prefetch, file sharding, multi-host assembly."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.core.objective import GlmObjective, RegularizationContext
from photon_tpu.core.optimizers import OptimizerConfig
from photon_tpu.core.optimizers.lbfgs import lbfgs
from photon_tpu.data.batch import SparseBatch
from photon_tpu.data.streaming import (
    ChunkedGlmObjective,
    LibsvmFileSource,
    StreamingObjective,
    chunk_batch,
    make_global_batch,
    shard_files_for_process,
    stream_chunks,
    streaming_lbfgs,
)


def _sparse_data(n=900, k=5, d=64, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, d, size=(n, k)).astype(np.int32)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    w_true = (rng.standard_normal(d) * 0.4).astype(np.float32)
    m = (w_true[ids] * vals).sum(1)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-m))).astype(np.float32)
    return SparseBatch(
        jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(y),
        jnp.zeros(n, jnp.float32), jnp.ones(n, jnp.float32),
    )


def test_chunked_objective_matches_flat():
    batch = _sparse_data()
    chunks = chunk_batch(batch, rows_per_chunk=128)
    assert chunks.num_chunks == 8  # ceil(900/128), padded
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 0.5))
    cobj = ChunkedGlmObjective(obj)
    w = jnp.asarray(np.random.default_rng(1).standard_normal(64), jnp.float32)
    v1, g1 = obj.value_and_grad(w, batch)
    v2, g2 = cobj.value_and_grad(w, chunks)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(obj.value(w, batch)), float(cobj.value(w, chunks)), rtol=1e-5)
    v = jnp.ones(64, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(obj.hessian_vector(w, v, batch)),
        np.asarray(cobj.hessian_vector(w, v, chunks)),
        rtol=1e-4, atol=1e-4,
    )
    np.testing.assert_allclose(
        np.asarray(obj.hessian_diagonal(w, batch)),
        np.asarray(cobj.hessian_diagonal(w, chunks)),
        rtol=1e-4, atol=1e-4,
    )


def test_chunked_objective_full_fit_matches():
    """The chunked objective slots into the jitted L-BFGS unchanged."""
    batch = _sparse_data(seed=2)
    chunks = chunk_batch(batch, rows_per_chunk=256)
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 1.0))
    cobj = ChunkedGlmObjective(obj)
    config = OptimizerConfig(max_iterations=40)
    w0 = jnp.zeros(64, jnp.float32)
    r1 = lbfgs(lambda w: obj.value_and_grad(w, batch), w0, config)
    r2 = lbfgs(lambda w: cobj.value_and_grad(w, chunks), w0, config)
    np.testing.assert_allclose(float(r1.value), float(r2.value), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(r1.w), np.asarray(r2.w), rtol=1e-2, atol=1e-3)


def test_stream_chunks_order_and_prefetch(monkeypatch):
    # Pin to the single-worker prefetch path: its contract includes strict
    # LOAD order (pooled delivery order is covered by test_io_pool).
    monkeypatch.setenv("PHOTON_IO_THREADS", "1")
    seen = []

    def load(i):
        seen.append(i)
        return jnp.full((2,), float(i))

    out = list(stream_chunks(load, 5, prefetch=2))
    assert [int(o[0]) for o in out] == [0, 1, 2, 3, 4]
    assert seen == [0, 1, 2, 3, 4]


def test_stream_chunks_pooled_delivery_order(monkeypatch):
    # Pooled path (multi-core hosts): DELIVERY stays strictly ordered even
    # when loads finish out of order; chunk residency stays bounded by
    # prefetch — loads STARTED may never exceed chunks consumed + prefetch,
    # even with a slow consumer (unbounded submission would race ahead).
    monkeypatch.setenv("PHOTON_IO_THREADS", "4")
    import time as _time

    started = []

    def load(i):
        started.append(i)
        _time.sleep(0.002 * ((i * 3) % 4))
        return jnp.full((2,), float(i))

    out = []
    for c in stream_chunks(load, 8, prefetch=2):
        out.append(c)
        _time.sleep(0.005)
        assert len(started) <= len(out) + 2, (
            f"{len(started)} loads started, {len(out)} consumed"
        )
    assert [int(o[0]) for o in out] == list(range(8))


def _run_stream_scale_bench(tmp_path, flag, rows):
    """Run ``bench.py <flag>`` in a subprocess at toy size and return
    ``(returncode, parsed final JSON line)``.  The subprocess runs under
    the device policy's EXPLICIT cpu (``JAX_PLATFORMS=cpu``) and inherits
    the suite's compile cache through ``JAX_COMPILATION_CACHE_DIR``."""
    import json
    import subprocess
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PHOTON_STREAM_SCALE_ROWS=str(rows),
        PHOTON_STREAM_SCALE_DIR=str(tmp_path / "data"),
    )
    out = subprocess.run(
        [_sys.executable, os.path.join(repo, "bench.py"), flag],
        capture_output=True, text=True, timeout=500, env=env, cwd=repo,
    )
    lines = out.stdout.strip().splitlines()
    assert lines, out.stderr[-2000:]
    return out.returncode, json.loads(lines[-1]), out.stderr


def test_stream_scale_mp_bench_mode(tmp_path):
    """bench.py --stream-scale-mp at toy size: the 2-process distributed
    pass runs, the JSON line parses, and the (value, |grad|) cross-check
    against the single-process pass holds (both CPU-pinned workers).  A
    mode that raises prints its bench_error line AND exits non-zero."""
    rc, line, stderr = _run_stream_scale_bench(
        tmp_path, "--stream-scale-mp", 2000
    )
    if line["metric"] == "bench_error":
        assert rc != 0, "a failed bench mode must exit non-zero"
        # Some jaxlibs cannot run cross-process collectives on the CPU
        # backend at all; that is a platform limitation, not a bench bug
        # (same signatures test_multiprocess skips on).
        from bench import MP_UNSUPPORTED_MARKERS

        err = str(line["detail"].get("error", ""))
        if any(marker in err for marker in MP_UNSUPPORTED_MARKERS):
            pytest.skip(f"platform cannot run multi-process JAX: {err[:200]}")
    assert rc == 0, stderr[-2000:]
    assert line["metric"] == "config5_stream_mp_rows_per_sec"
    assert line["detail"]["processes"] == 2
    assert line["detail"]["rows"] == 2000
    assert line["detail"]["value_match"] is True
    assert line["detail"]["grad_l1_match"] is True
    # Every emitted line names the device the policy resolved.
    assert line["detail"]["device_kind"] == "cpu"
    assert line["detail"]["device_count"] >= 1


def test_csr_chunk_path_matches_rows_path(tmp_path):
    """The flat-CSR fast chunk loader must produce byte-identical batches
    to the rows-based builder (same padding, intercept column, label
    normalization), and reject malformed input with the same error."""
    import numpy as np

    from photon_tpu.data.libsvm import (
        csr_to_sparse_batch,
        parse_libsvm,
        to_sparse_batch,
    )
    from photon_tpu.native import libsvm_native

    p = str(tmp_path / "part.libsvm")
    with open(p, "w") as f:
        f.write("1 3:0.5 7:-1.25\n")
        f.write("-1 1:2.0\n")
        f.write("0\n")  # label-only row: only the intercept column
        f.write("1 2:1.0 4:4.0 9:0.125\n")

    csr = libsvm_native.parse_file_csr(p)
    if csr is None:
        pytest.skip("native library unavailable (source-only checkout)")
    labels, row_ptr, ids, vals, dim = csr
    b_csr, d_csr = csr_to_sparse_batch(
        labels, row_ptr, ids, vals, dim=dim, intercept=True, capacity=8
    )
    b_rows, d_rows = to_sparse_batch(
        parse_libsvm(p), dim=dim, intercept=True, capacity=8
    )
    assert d_csr == d_rows
    np.testing.assert_array_equal(b_csr.ids, b_rows.ids)
    np.testing.assert_array_equal(b_csr.vals, b_rows.vals)
    np.testing.assert_array_equal(b_csr.label, b_rows.label)
    np.testing.assert_array_equal(b_csr.weight, b_rows.weight)

    bad = str(tmp_path / "bad.libsvm")
    with open(bad, "w") as f:
        f.write("1 3:\n")
    with pytest.raises(ValueError):
        libsvm_native.parse_file_csr(bad)


def test_stream_chunks_propagates_worker_error():
    def load(i):
        if i == 2:
            raise RuntimeError("disk error")
        return jnp.zeros(1)

    with pytest.raises(RuntimeError, match="disk error"):
        list(stream_chunks(load, 4))


def test_shard_files_for_process():
    files = [f"part-{i:03d}" for i in range(10)]
    shards = [shard_files_for_process(files, p, 3) for p in range(3)]
    assert sorted(sum(shards, [])) == files
    assert abs(len(shards[0]) - len(shards[2])) <= 1
    assert shard_files_for_process(files, 0, 1) == files


def _write_files(tmp_path, n_files=3, rows=120, d=40, seed=0):
    from photon_tpu.data.synthetic import make_glm_data, write_libsvm

    paths = []
    full_x, full_y = [], []
    for i in range(n_files):
        b, _ = make_glm_data(rows, d, seed=seed + i, weight_seed=7)
        x = np.asarray(b.x)[:, :-1]
        y = np.asarray(b.label)
        p = str(tmp_path / f"part-{i}.libsvm")
        write_libsvm(p, x, y)
        paths.append(p)
        full_x.append(x)
        full_y.append(y)
    return paths, np.concatenate(full_x), np.concatenate(full_y)


def test_streaming_lbfgs_matches_in_memory(tmp_path):
    paths, x, y = _write_files(tmp_path)
    source = LibsvmFileSource(paths)
    assert source.num_examples == len(y)
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 1.0))
    sobj = StreamingObjective(obj, source.chunk_iter_factory)
    config = OptimizerConfig(max_iterations=40)
    result = streaming_lbfgs(sobj, jnp.zeros(source.dim, jnp.float32), config)
    assert bool(result.converged)

    # In-memory reference on the concatenated data.
    from photon_tpu.data.libsvm import parse_libsvm, to_sparse_batch

    batches = [parse_libsvm(p) for p in paths]
    rows = [r for b in batches for r in b.rows]
    labels = np.concatenate([b.labels for b in batches])
    from photon_tpu.data.libsvm import LibsvmData

    flat, dim = to_sparse_batch(
        LibsvmData(rows, labels, max(b.dim for b in batches)),
        capacity=source.capacity,
    )
    r_ref = lbfgs(lambda w: obj.value_and_grad(w, flat),
                  jnp.zeros(dim, jnp.float32), config)
    np.testing.assert_allclose(float(result.value), float(r_ref.value), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(result.w), np.asarray(r_ref.w), rtol=5e-2, atol=5e-3
    )


def test_streaming_lbfgs_kill_and_resume_exact(tmp_path):
    """A streamed fit killed mid-loop and resumed from its mid-fit L-BFGS
    snapshot matches the uninterrupted fit EXACTLY (ISSUE 5 satellite: the
    ROADMAP's streamed-GLM checkpoint edge)."""
    from photon_tpu.fault.checkpoint import StreamCheckpointer
    from photon_tpu.fault.injection import (
        FaultPlan,
        InjectedKillError,
        set_plan,
    )

    paths, _, _ = _write_files(tmp_path)
    source = LibsvmFileSource(paths)
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 1.0))
    config = OptimizerConfig(max_iterations=25)

    def objective():
        return StreamingObjective(obj, source.chunk_iter_factory)

    w0 = jnp.zeros(source.dim, jnp.float32)
    baseline = streaming_lbfgs(objective(), w0, config)

    ckpt = StreamCheckpointer(str(tmp_path / "ckpt"))
    set_plan(FaultPlan.parse("stream:kill:iter=3"))
    try:
        with pytest.raises(InjectedKillError):
            streaming_lbfgs(objective(), w0, config, checkpointer=ckpt)
    finally:
        set_plan(None)

    state = ckpt.load("latest")
    assert state is not None and not state.completed
    assert state.iteration <= 3
    resumed = streaming_lbfgs(
        objective(), w0, config, checkpointer=ckpt, resume_state=state
    )
    np.testing.assert_array_equal(np.asarray(baseline.w), np.asarray(resumed.w))
    assert int(baseline.iterations) == int(resumed.iterations)
    assert int(baseline.reason) == int(resumed.reason)
    np.testing.assert_array_equal(
        np.asarray(baseline.history_value), np.asarray(resumed.history_value)
    )


def test_streaming_completed_checkpoint_rebuilds_without_passes(tmp_path):
    """Resuming a COMPLETED streamed fit rebuilds the result from the final
    snapshot with zero streamed passes."""
    from photon_tpu.fault.checkpoint import StreamCheckpointer

    paths, _, _ = _write_files(tmp_path)
    source = LibsvmFileSource(paths)
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 1.0))
    config = OptimizerConfig(max_iterations=25)
    passes = {"n": 0}

    def counting_factory():
        passes["n"] += 1
        return source.chunk_iter_factory()

    ckpt = StreamCheckpointer(str(tmp_path / "ckpt"))
    w0 = jnp.zeros(source.dim, jnp.float32)
    fitted = streaming_lbfgs(
        StreamingObjective(obj, counting_factory), w0, config,
        checkpointer=ckpt,
    )
    state = ckpt.load("latest")
    assert state is not None and state.completed

    passes["n"] = 0
    rebuilt = streaming_lbfgs(
        StreamingObjective(obj, counting_factory), w0, config,
        checkpointer=ckpt, resume_state=state,
    )
    assert passes["n"] == 0  # not a single streamed pass
    np.testing.assert_array_equal(np.asarray(fitted.w), np.asarray(rebuilt.w))
    assert float(fitted.value) == float(rebuilt.value)
    assert bool(fitted.converged) == bool(rebuilt.converged)


def test_streaming_max_iterations_checkpoint_continues_with_larger_budget(
    tmp_path,
):
    """A streamed fit that stopped on MAX_ITERATIONS is 'completed' for its
    own budget, but resuming with a LARGER budget continues the loop (same
    rule as descent checkpoints) instead of short-circuiting stale."""
    from photon_tpu.fault.checkpoint import StreamCheckpointer

    paths, _, _ = _write_files(tmp_path)
    source = LibsvmFileSource(paths)
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 1.0))

    def objective():
        return StreamingObjective(obj, source.chunk_iter_factory)

    w0 = jnp.zeros(source.dim, jnp.float32)
    small = OptimizerConfig(max_iterations=3)
    ckpt = StreamCheckpointer(str(tmp_path / "ckpt"))
    capped = streaming_lbfgs(objective(), w0, small, checkpointer=ckpt)
    assert int(capped.iterations) == 3 and not bool(capped.converged)

    state = ckpt.load("latest")
    assert state is not None and state.completed

    # Same budget: rebuilt without passes (stale short-circuit is correct).
    same = streaming_lbfgs(
        objective(), w0, small, checkpointer=ckpt, resume_state=state
    )
    assert int(same.iterations) == 3

    # Larger budget: the loop CONTINUES past the snapshot.
    grown = streaming_lbfgs(
        objective(), w0, OptimizerConfig(max_iterations=25),
        checkpointer=ckpt, resume_state=state,
    )
    assert int(grown.iterations) > 3
    assert float(grown.value) < float(capped.value)  # it kept optimizing


def test_source_with_files_and_known_dim(tmp_path):
    """Global metadata + per-process file restriction; known feature_dim
    skips the full parse but yields identical layout."""
    paths, _, _ = _write_files(tmp_path)
    full = LibsvmFileSource(paths)
    fast = LibsvmFileSource(paths, feature_dim=full.feature_dim)
    assert fast.dim == full.dim
    assert fast.capacity == full.capacity
    assert fast.num_examples == full.num_examples
    shard = full.with_files(paths[:1])
    assert shard.dim == full.dim  # metadata survives restriction
    chunks = list(shard.chunk_iter_factory())
    assert len(chunks) == 1
    assert chunks[0].ids.shape[1] == full.capacity


def test_streaming_train_driver(tmp_path):
    paths, _, _ = _write_files(tmp_path, n_files=2, rows=150)
    from photon_tpu.drivers import train

    out = str(tmp_path / "out")
    summary = train.run(train.build_parser().parse_args([
        "--backend", "cpu",
        "--input", str(tmp_path / "part-*.libsvm"),
        "--stream",
        "--validation-input", "synthetic:logistic_regression:200:40:5:7",
        "--max-iterations", "30",
        "--output-dir", out,
    ]))
    assert summary["streaming"] is True
    assert os.path.exists(os.path.join(out, "best_model.avro"))
    assert summary["sweep"][0]["metrics"]["AUC"] > 0.6


def test_make_global_batch_single_process():
    from photon_tpu.parallel import create_mesh

    batch = _sparse_data(n=64)
    mesh = create_mesh()
    global_batch = make_global_batch(batch, mesh)
    np.testing.assert_array_equal(np.asarray(global_batch.ids), np.asarray(batch.ids))
    obj = GlmObjective.create("logistic")
    w = jnp.zeros(64, jnp.float32)
    v1, _ = obj.value_and_grad(w, batch)
    v2, _ = obj.value_and_grad(w, global_batch)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-6)


def test_make_global_batch_aligned_single_process(monkeypatch):
    """make_global_batch(aligned_dim=...) attaches per-local-shard
    aligned layouts (8 local devices here) and the sharded objective
    matches single-device autodiff — the single-process degenerate of
    the multi-process leg (tests/test_multiprocess.py part 1b)."""
    from photon_tpu.parallel import DistributedGlmObjective, create_mesh

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    monkeypatch.setenv("PHOTON_ROUTE_CACHE", "0")
    batch = _sparse_data(n=64)
    mesh = create_mesh()
    global_batch = make_global_batch(batch, mesh, aligned_dim=64)
    assert global_batch.al is not None
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 0.4))
    w = jnp.asarray(
        np.random.default_rng(3).standard_normal(64).astype(np.float32) * 0.1
    )
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "autodiff")
    v_ref, g_ref = obj.value_and_grad(w, batch)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    dist = DistributedGlmObjective(obj, mesh)
    assert dist._sparse_kernel(w, global_batch) == "pallas"
    v, g = dist.value_and_grad(w, global_batch)
    np.testing.assert_allclose(float(v), float(v_ref), rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(g_ref), rtol=2e-4, atol=1e-4
    )


def test_streaming_path_validates_data(tmp_path):
    # ADVICE r1: --stream used to skip data validation entirely.
    import pytest

    from photon_tpu.data.validation import DataValidationError
    from photon_tpu.drivers import train

    bad = tmp_path / "bad.libsvm"
    bad.write_text("nan 1:1.0\n1 2:1.0\n-1 1:0.5\n")
    args = [
        "--input", str(bad), "--task", "logistic_regression",
        "--stream", "--max-iterations", "3",
        "--output-dir", str(tmp_path / "out"),
    ]
    with pytest.raises(DataValidationError):
        train.run(train.build_parser().parse_args(
            args + ["--data-validation", "error"]))
    # off -> trains (NaN label flows into the data; run must still finish)
    summary = train.run(train.build_parser().parse_args(
        args + ["--data-validation", "off"]))
    assert summary is not None


def test_stream_scale_bench_mode(tmp_path):
    """bench.py --stream-scale at toy size: generated part files stream
    through the production path, the JSON line parses, RSS bound holds, and
    the generator's manifest cache skips regeneration (VERDICT r3 item 3;
    full-scale 10M-row runs are recorded in BASELINE.md)."""
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rc, line, stderr = _run_stream_scale_bench(
        tmp_path, "--stream-scale", 3000
    )
    assert rc == 0, stderr[-2000:]
    assert line["metric"] == "config5_stream_rows_per_sec"
    assert line["detail"]["rows"] == 3000
    assert line["detail"]["rss_bounded"] is True
    assert line["detail"]["kernel"] == "fm"

    # Manifest cache: a repeat call with the same spec returns the same
    # files without rewriting; a changed spec regenerates (in-process — the
    # generator is pure numpy).
    _sys.path.insert(0, repo)
    import bench

    files = sorted(os.listdir(tmp_path / "data"))
    mtimes = [os.path.getmtime(tmp_path / "data" / f) for f in files]
    again = bench._generate_stream_files(str(tmp_path / "data"), 3000, 64, 16, 1 << 17)
    assert len(again) == 64
    assert [os.path.getmtime(tmp_path / "data" / f) for f in files] == mtimes
    smaller = bench._generate_stream_files(str(tmp_path / "data"), 640, 4, 8, 1 << 10)
    assert len(smaller) == 4
