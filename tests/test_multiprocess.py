"""Multi-process runtime: 2 local CPU processes must compute the same
distributed objective as one process (VERDICT r2 item 4; SURVEY.md §2.6).

Each subprocess joins via ``jax.distributed.initialize`` (the drivers'
``--coordinator/--process-id/--num-processes`` path), contributes its local
rows through ``make_global_batch``, and evaluates the sharded
value+gradient over the 2-device global mesh; both the psum-ed value and
gradient must match a single-process evaluation over the full batch.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One worker covers BOTH the sharded-objective check and the row-split
# entity-solve check: jax import + distributed init dominate worker wall
# time on this box, so the two checks share one process pair (suite-time
# budget, VERDICT r3 item 4).
WORKER = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
sys.path.insert(0, sys.argv[1])
coordinator, pid, out_path = sys.argv[2], int(sys.argv[3]), sys.argv[4]
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=coordinator, num_processes=2, process_id=pid
)
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_tpu.core.objective import GlmObjective, RegularizationContext
from photon_tpu.data.batch import SparseBatch, attach_feature_major
from photon_tpu.data.streaming import make_global_batch
from photon_tpu.parallel.distributed import DistributedGlmObjective

# Part 1: sharded objective. Deterministic dataset; each process
# contributes its half as local rows.
n, k, d = 256, 6, 48
rng = np.random.default_rng(0)
ids = rng.integers(0, d, size=(n, k), dtype=np.int32)
vals = rng.standard_normal((n, k)).astype(np.float32)
label = (rng.random(n) < 0.5).astype(np.float32)
weight = rng.uniform(0.5, 2.0, n).astype(np.float32)
lo, hi = pid * (n // 2), (pid + 1) * (n // 2)
local = SparseBatch(
    jnp.asarray(ids[lo:hi]), jnp.asarray(vals[lo:hi]),
    jnp.asarray(label[lo:hi]), jnp.zeros(n // 2, jnp.float32),
    jnp.asarray(weight[lo:hi]),
)
local = attach_feature_major(local)

assert jax.process_count() == 2 and len(jax.devices()) == 2
mesh = Mesh(np.asarray(jax.devices()), ("data",))
batch = make_global_batch(local, mesh)
assert batch.fm is not None

obj = GlmObjective.create("logistic", RegularizationContext("l2", 0.7))
dist = DistributedGlmObjective(obj, mesh)
w = jnp.asarray(np.random.default_rng(1).standard_normal(d), jnp.float32) * 0.1
v, g = dist.value_and_grad(w, batch)
hv = dist.hessian_vector(
    w, jnp.asarray(np.random.default_rng(2).standard_normal(d), jnp.float32),
    batch,
)

# Part 1b (round 5): multi-process SHARDED FAST KERNELS — each process
# builds the aligned layout for its local block with globally-agreed geometry
# (the allgather inside make_global_batch), and the sharded objective
# must produce the same numbers the fm path above did.
_prev_env = {
    k: os.environ.get(k)
    for k in ("PHOTON_SPARSE_GRAD", "PHOTON_ROUTE_CACHE")
}
os.environ["PHOTON_SPARSE_GRAD"] = "pallas"
os.environ["PHOTON_ROUTE_CACHE"] = "0"
local_x = SparseBatch(
    jnp.asarray(ids[lo:hi]), jnp.asarray(vals[lo:hi]),
    jnp.asarray(label[lo:hi]), jnp.zeros(n // 2, jnp.float32),
    jnp.asarray(weight[lo:hi]),
)
batch_x = make_global_batch(local_x, mesh, aligned_dim=d)
assert batch_x.al is not None, "multi-process aligned layout missing"
assert dist._sparse_kernel(w, batch_x) == "pallas"
v_x, g_x = dist.value_and_grad(w, batch_x)
# Restore the pre-part-1b environment so part 2 exercises the same
# (auto, cached-layouts) dispatch it did before round 5.
for _k, _v in _prev_env.items():
    if _v is None:
        os.environ.pop(_k, None)
    else:
        os.environ[_k] = _v

# Part 2: row-split entity solves. THIS process holds rows
# [pid*R/2, (pid+1)*R/2) of EVERY entity — the row-split multi-host
# placement (no shuffle).
from photon_tpu.core.optimizers import OptimizerConfig
from photon_tpu.core.problem import ProblemConfig
from photon_tpu.parallel.distributed import solve_entities_row_split
from photon_tpu.parallel.mesh import to_host

E, R, rk, rd = 5, 16, 3, 10
rng = np.random.default_rng(0)
rids = rng.integers(1, rd, (E, R, rk)).astype(np.int32)
rvals = rng.standard_normal((E, R, rk)).astype(np.float32)
rlabel = (rng.random((E, R)) < 0.5).astype(np.float32)
rweight = rng.uniform(0.5, 2.0, (E, R)).astype(np.float32)
rlo, rhi = pid * R // 2, (pid + 1) * R // 2

def row_sharded(a):
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(None, "data", *([None] * (a.ndim - 2)))),
        a[:, rlo:rhi],
    )
rbatch = SparseBatch(
    row_sharded(rids), row_sharded(rvals), row_sharded(rlabel),
    row_sharded(np.zeros((E, R), np.float32)), row_sharded(rweight),
)
reg = RegularizationContext("l2", 0.8)
cfg = ProblemConfig(optimizer="lbfgs", regularization=reg,
                    optimizer_config=OptimizerConfig(max_iterations=12))
robj = GlmObjective.create("logistic", reg)
coeffs, res = solve_entities_row_split(
    robj, cfg, rbatch, jnp.zeros((E, rd), jnp.float32), mesh
)
with open(out_path, "w") as f:
    json.dump({
        "value": float(v),
        "grad": np.asarray(g).tolist(),
        "hv": np.asarray(hv).tolist(),
        "pallas_value": float(v_x),
        "pallas_grad": np.asarray(g_x).tolist(),
        "rs_means": to_host(coeffs.means).tolist(),
        "rs_value": to_host(res.value).tolist(),
    }, f)
"""


def _worker_env() -> dict:
    """Worker subprocess environment: strip the parent's XLA_/JAX_ device
    forcing (each worker sets its own) but keep the shared compilation
    cache so workers load, not recompile."""
    return {
        k: v for k, v in os.environ.items()
        if not k.startswith(("XLA_", "JAX_"))
        or k.startswith("JAX_PERSISTENT_CACHE")
        or k == "JAX_COMPILATION_CACHE_DIR"
    }


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# Error signatures of a platform that cannot run 2-process JAX at all (e.g.
# a jaxlib whose CPU client lacks cross-process collectives): the suite must
# SKIP these tests with a reason, not report code failures.  The canonical
# tuple lives in bench.py (its worker runner re-raises on the same
# signatures) so the skip logic and the bench stay in lockstep.
from bench import MP_UNSUPPORTED_MARKERS  # noqa: E402

# A coordinator port lost to the free-port race (another process bound it
# between _free_port() and the workers' bind): retry with a fresh port.
_PORT_COLLISION_MARKERS = ("Address already in use", "address in use")


def skip_if_mp_unsupported(err: str) -> None:
    """Skip (with the signature as reason) when worker output shows this
    platform cannot spawn multi-process JAX."""
    for marker in MP_UNSUPPORTED_MARKERS:
        if marker in err:
            pytest.skip(
                f"platform cannot run multi-process JAX: {marker!r}"
            )


def run_worker_pair(cmds_for, timeout=300, what="multi-process worker"):
    """Launch the 2-process worker pair ``cmds_for(coordinator)``; on a
    coordinator-port collision retry once with a freshly allocated port,
    and on the no-multi-process-JAX signatures skip instead of failing."""
    for attempt in (0, 1):
        coordinator = f"127.0.0.1:{_free_port()}"
        env = _worker_env()
        procs = [
            subprocess.Popen(
                cmd, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
            )
            for cmd in cmds_for(coordinator)
        ]
        errs = []
        try:
            for p in procs:
                _, err = p.communicate(timeout=timeout)
                errs.append(err)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
                q.wait()
            pytest.fail(f"{what} timed out (distributed hang)")
        if all(p.returncode == 0 for p in procs):
            return
        joined = "\n".join(errs)
        skip_if_mp_unsupported(joined)
        if attempt == 0 and any(m in joined for m in _PORT_COLLISION_MARKERS):
            continue
        for p, err in zip(procs, errs):
            assert p.returncode == 0, f"{what} failed:\n{err[-2000:]}"


@pytest.fixture(scope="module")
def merged_worker_results(tmp_path_factory):
    """Run the merged 2-process worker pair once for the module; both the
    objective test and the row-split test assert against its outputs."""
    tmp_path = tmp_path_factory.mktemp("mp_worker")
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)
    outs = [str(tmp_path / f"out{i}.json") for i in range(2)]
    run_worker_pair(lambda coordinator: [
        [sys.executable, str(worker), REPO, coordinator, str(i), outs[i]]
        for i in range(2)
    ])
    return [json.load(open(o)) for o in outs]


def test_two_process_objective_matches_single(merged_worker_results):
    results = merged_worker_results
    # Both processes see the identical replicated (value, grad).
    assert results[0]["value"] == pytest.approx(results[1]["value"], rel=1e-6)
    np.testing.assert_allclose(results[0]["grad"], results[1]["grad"], rtol=1e-5)

    # Single-process reference over the full batch.
    import jax
    import jax.numpy as jnp

    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.data.batch import SparseBatch

    n, k, d = 256, 6, 48
    rng = np.random.default_rng(0)
    ids = rng.integers(0, d, size=(n, k), dtype=np.int32)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    label = (rng.random(n) < 0.5).astype(np.float32)
    weight = rng.uniform(0.5, 2.0, n).astype(np.float32)
    batch = SparseBatch(
        jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(label),
        jnp.zeros(n, jnp.float32), jnp.asarray(weight),
    )
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 0.7))
    w = jnp.asarray(np.random.default_rng(1).standard_normal(d), jnp.float32) * 0.1
    v_ref, g_ref = jax.value_and_grad(obj.value)(w, batch)
    hv_ref = jax.jvp(
        lambda u: jax.grad(obj.value)(u, batch),
        (w,),
        (jnp.asarray(np.random.default_rng(2).standard_normal(d), jnp.float32),),
    )[1]
    assert results[0]["value"] == pytest.approx(float(v_ref), rel=1e-5)
    np.testing.assert_allclose(results[0]["grad"], np.asarray(g_ref),
                               rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(results[0]["hv"], np.asarray(hv_ref),
                               rtol=2e-4, atol=1e-5)
    # Round 5: the multi-process SHARDED PALLAS path (per-process aux with
    # globally-agreed geometry) must match the same reference.
    assert results[0]["pallas_value"] == pytest.approx(float(v_ref), rel=1e-5)
    np.testing.assert_allclose(results[0]["pallas_grad"], np.asarray(g_ref),
                               rtol=2e-4, atol=1e-4)
    assert results[0]["pallas_value"] == pytest.approx(
        results[1]["pallas_value"], rel=1e-6
    )


STREAM_WORKER = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
# Simulate an operator who left kernel selection on auto: the driver's
# distributed init must pin it (to fm) identically on every rank.
os.environ["PHOTON_SPARSE_GRAD"] = "auto"
sys.path.insert(0, sys.argv[1])
coordinator, pid, input_dir, out_dir = (
    sys.argv[2], int(sys.argv[3]), sys.argv[4], sys.argv[5]
)
from photon_tpu.drivers import train

train.run(train.build_parser().parse_args([
    "--backend", "cpu",
    "--coordinator", coordinator, "--process-id", str(pid),
    "--num-processes", "2",
    "--input", input_dir, "--task", "logistic_regression",
    "--stream", "--reg-weights", "1.0", "--max-iterations", "6",
    "--output-dir", out_dir,
]))
# Every rank (not just the writing rank 0) records the kernel it resolved:
# maybe_init_distributed must have pinned auto -> fm so shards never mix
# reduction orders (VERDICT r3 weak 2).
os.makedirs(out_dir, exist_ok=True)
with open(os.path.join(out_dir, "kernel.json"), "w") as f:
    json.dump({"kernel": os.environ.get("PHOTON_SPARSE_GRAD", "auto")}, f)
"""


def test_two_process_streaming_driver_matches_single(tmp_path):
    """The --stream driver under --coordinator: per-shard streamed gradients
    all-reduce across processes, so the fitted model must match a
    single-process run over all files (the treeAggregate-across-hosts
    analog)."""
    rng = np.random.default_rng(3)
    n_per, k, d = 60, 5, 30
    input_dir = tmp_path / "data"
    input_dir.mkdir()
    w_true = rng.standard_normal(d)
    for fi in range(4):
        with open(input_dir / f"part-{fi}.libsvm", "w") as f:
            for _ in range(n_per):
                fid = np.sort(
                    rng.choice(np.arange(1, d + 1), size=k, replace=False)
                )
                xv = rng.standard_normal(k)
                m = float(w_true[fid - 1] @ xv)
                y = 1 if rng.random() < 1 / (1 + np.exp(-m)) else -1
                f.write(f"{y} " + " ".join(
                    f"{j}:{v:.5f}" for j, v in zip(fid, xv)) + "\n")

    from photon_tpu.drivers import train

    single_out = str(tmp_path / "single")
    train.run(train.build_parser().parse_args([
        "--backend", "cpu", "--input", str(input_dir),
        "--task", "logistic_regression", "--stream",
        "--reg-weights", "1.0", "--max-iterations", "6",
        "--output-dir", single_out,
    ]))

    worker = tmp_path / "stream_worker.py"
    worker.write_text(STREAM_WORKER)
    outs = [str(tmp_path / f"mp{i}") for i in range(2)]
    run_worker_pair(lambda coordinator: [
        [sys.executable, str(worker), REPO, coordinator, str(i),
         str(input_dir), outs[i]]
        for i in range(2)
    ], timeout=240, what="streaming worker")

    def final_value(out):
        with open(os.path.join(out, "training_summary.json")) as f:
            return json.load(f)["sweep"][0]["final_value"]

    # Identical global objective -> identical optimum (up to solver noise).
    # Only rank 0 writes outputs (the reference's driver-writes semantics);
    # rank 1 exiting cleanly above is its assertion.
    assert final_value(outs[0]) == pytest.approx(
        final_value(single_out), rel=1e-4
    )
    assert not os.path.exists(os.path.join(outs[1], "training_summary.json"))

    # Kernel pinning (VERDICT r3 weak 2): both ranks started on "auto" and
    # must have resolved the SAME pinned kernel (the autodiff default —
    # measured fastest on real TPU, KERNEL_NOTES.md round-4 table) — never
    # a per-rank measurement that could mix reduction orders across shards.
    kernels = [
        json.load(open(os.path.join(o, "kernel.json")))["kernel"] for o in outs
    ]
    assert kernels == ["autodiff", "autodiff"], kernels


GAME_WORKER = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
sys.path.insert(0, sys.argv[1])
coordinator, pid, out_dir = sys.argv[2], int(sys.argv[3]), sys.argv[4]
extra = sys.argv[5:]
from photon_tpu.drivers import train_game

summary = train_game.run(train_game.build_parser().parse_args([
    "--backend", "cpu",
    "--coordinator", coordinator, "--process-id", str(pid),
    "--num-processes", "2",
    "--input", "synthetic-game:32:4:8:4:1:7",
    "--coordinate", "fixed:type=fixed,shard=global,max_iters=6",
    "--coordinate", "per_user:type=random,shard=re0,entity=re0,max_iters=5",
    "--descent-iterations", "1",
    "--validation-split", "0.25",
    "--output-dir", out_dir,
] + extra))
if pid == 0:
    with open(os.path.join(out_dir, "mp_metrics.json"), "w") as f:
        json.dump(summary["best_metrics"], f)
"""


def test_two_process_game_driver_matches_single(tmp_path):
    """Full GAME training over a 2-process global mesh: fixed effect
    data-sharded with psum, random effect entity-sharded, rank-0-only
    writes — must reproduce the single-process metrics.  (Row-split across
    real processes is covered by test_two_process_row_split_matches_single;
    carrying it here too tripled this test's compile load.)"""
    from photon_tpu.drivers import train_game

    argv = [
        "--backend", "cpu",
        "--input", "synthetic-game:32:4:8:4:1:7",
        "--coordinate", "fixed:type=fixed,shard=global,max_iters=6",
        "--coordinate", "per_user:type=random,shard=re0,entity=re0,max_iters=5",
        "--descent-iterations", "1",
        "--validation-split", "0.25",
    ]
    single = train_game.run(train_game.build_parser().parse_args(
        argv + ["--output-dir", str(tmp_path / "single")]))

    worker = tmp_path / "game_worker.py"
    worker.write_text(GAME_WORKER)
    outs = [str(tmp_path / f"mp{i}") for i in range(2)]
    run_worker_pair(lambda coordinator: [
        [sys.executable, str(worker), REPO, coordinator, str(i), outs[i]]
        for i in range(2)
    ], what="GAME worker")

    mp_metrics = json.load(open(os.path.join(outs[0], "mp_metrics.json")))
    assert os.path.isdir(os.path.join(outs[0], "best_model"))
    for name, value in single["best_metrics"].items():
        assert mp_metrics[name] == pytest.approx(value, rel=2e-3), (
            name, mp_metrics[name], value
        )


def test_two_process_device_residuals_match_single(tmp_path):
    """EXPLICIT ``--residuals device --validation-pipeline device`` under a
    2-process global mesh: the sharded score tables (training residuals AND
    validation) run as SPMD programs over globally-sharded rows, so the
    device engine no longer falls back to host multi-process — metrics must
    reproduce a single-process device-mode run."""
    from photon_tpu.drivers import train_game

    flags = ["--residuals", "device", "--validation-pipeline", "device"]
    argv = [
        "--backend", "cpu",
        "--input", "synthetic-game:32:4:8:4:1:7",
        "--coordinate", "fixed:type=fixed,shard=global,max_iters=6",
        "--coordinate", "per_user:type=random,shard=re0,entity=re0,max_iters=5",
        "--descent-iterations", "1",
        "--validation-split", "0.25",
    ] + flags
    single = train_game.run(train_game.build_parser().parse_args(
        argv + ["--output-dir", str(tmp_path / "single")]))

    worker = tmp_path / "game_worker.py"
    worker.write_text(GAME_WORKER)
    outs = [str(tmp_path / f"mp{i}") for i in range(2)]
    run_worker_pair(lambda coordinator: [
        [sys.executable, str(worker), REPO, coordinator, str(i), outs[i]]
        + flags
        for i in range(2)
    ], what="GAME device-residual worker")

    mp_metrics = json.load(open(os.path.join(outs[0], "mp_metrics.json")))
    for name, value in single["best_metrics"].items():
        assert mp_metrics[name] == pytest.approx(value, rel=2e-3), (
            name, mp_metrics[name], value
        )




def test_two_process_checkpoint_resumes_on_one_process(tmp_path):
    """Elastic resume, the real multi-controller leg: a checkpoint WRITTEN
    by a 2-process run (rank 0 writes, globally-sharded score tables)
    resumes on ONE process — a different process AND device count — and
    continues training to the single-process run's metrics.  Skips with a
    reason on jaxlibs without cross-process CPU collectives
    (MP_UNSUPPORTED_MARKERS), like every multi-process test."""
    from photon_tpu.drivers import train_game

    ckpt = str(tmp_path / "ckpt")
    worker = tmp_path / "game_worker.py"
    worker.write_text(GAME_WORKER)
    outs = [str(tmp_path / f"mp{i}") for i in range(2)]
    # The 2-proc pair trains ONE outer iteration with checkpointing on.
    run_worker_pair(lambda coordinator: [
        [sys.executable, str(worker), REPO, coordinator, str(i), outs[i],
         "--checkpoint-dir", ckpt]
        for i in range(2)
    ], what="GAME checkpoint worker")
    from photon_tpu.fault.checkpoint import has_published_checkpoint

    assert has_published_checkpoint(ckpt)

    argv = [
        "--backend", "cpu",
        "--input", "synthetic-game:32:4:8:4:1:7",
        "--coordinate", "fixed:type=fixed,shard=global,max_iters=6",
        "--coordinate", "per_user:type=random,shard=re0,entity=re0,max_iters=5",
        "--validation-split", "0.25",
    ]
    # Resume single-process with a RAISED iteration budget: iteration 0 is
    # restored from the 2-proc snapshot, iteration 1 trains locally.
    resumed = train_game.run(train_game.build_parser().parse_args(
        argv + ["--descent-iterations", "2",
                "--checkpoint-dir", ckpt, "--resume", "latest",
                "--output-dir", str(tmp_path / "resumed")]))
    single = train_game.run(train_game.build_parser().parse_args(
        argv + ["--descent-iterations", "2",
                "--output-dir", str(tmp_path / "single")]))
    for name, value in single["best_metrics"].items():
        assert resumed["best_metrics"][name] == pytest.approx(
            value, rel=2e-3
        ), (name, resumed["best_metrics"][name], value)
    history = resumed["sweep"][0]["history"]
    assert [h["iteration"] for h in history] == [0, 1]


def test_two_process_row_split_matches_single(merged_worker_results):
    """Row-split entity solves across 2 REAL processes (each holding half of
    every entity's rows) must match a single-process co-located solve — the
    multi-host shuffle-free random-effect path end-to-end.  (Runs inside the
    shared merged worker pair; see merged_worker_results.)"""
    results = merged_worker_results
    np.testing.assert_allclose(results[0]["rs_means"], results[1]["rs_means"],
                               rtol=1e-6)
    np.testing.assert_allclose(results[0]["rs_value"], results[1]["rs_value"],
                               rtol=1e-6)

    # Single-process co-located reference on the same data.
    import jax
    import jax.numpy as jnp

    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig
    from photon_tpu.data.batch import SparseBatch

    E, R, k, d = 5, 16, 3, 10  # must match the worker's Part-2 shapes
    rng = np.random.default_rng(0)
    batch = SparseBatch(
        jnp.asarray(rng.integers(1, d, (E, R, k)).astype(np.int32)),
        jnp.asarray(rng.standard_normal((E, R, k)).astype(np.float32)),
        jnp.asarray((rng.random((E, R)) < 0.5).astype(np.float32)),
        jnp.zeros((E, R), jnp.float32),
        jnp.asarray(rng.uniform(0.5, 2.0, (E, R)).astype(np.float32)),
    )
    reg = RegularizationContext("l2", 0.8)
    cfg = ProblemConfig(optimizer="lbfgs", regularization=reg,
                        optimizer_config=OptimizerConfig(max_iterations=12))
    obj = GlmObjective.create("logistic", reg)
    ref_coeffs, _ = GlmOptimizationProblem(obj, cfg).solver(vmapped=True)(
        obj, batch, jnp.zeros((E, d), jnp.float32)
    )
    np.testing.assert_allclose(
        results[0]["rs_means"], np.asarray(ref_coeffs.means),
        rtol=2e-2, atol=2e-3,
    )
