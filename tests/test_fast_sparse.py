"""The static-sparsity fast gradient path (FeatureMajorAux) must match the
autodiff reference exactly (up to float32 reduction order).

The fast path replaces XLA's unsorted scatter-add (sort + segmented reduce
per evaluation) with a host-pre-sorted ``segment_sum(indices_are_sorted=
True)`` — VERDICT r2 item 1; the reference's ValueAndGradientAggregator /
HessianVectorAggregator hot loop (SURVEY.md §3.4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.core.objective import GlmObjective, RegularizationContext
from photon_tpu.data.batch import SparseBatch, attach_feature_major


def _random_batch(n, k, d, seed=0, zipf=False, with_pads=True):
    rng = np.random.default_rng(seed)
    if zipf:
        # Power-law feature frequencies — the realistic sparse-GLM regime.
        ids = (rng.zipf(1.3, size=(n, k)) - 1) % d
        ids = ids.astype(np.int32)
    else:
        ids = rng.integers(0, d, size=(n, k), dtype=np.int32)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    if with_pads:
        # Zero out a random suffix of some rows (the padding convention).
        cut = rng.integers(1, k + 1, size=n)
        mask = np.arange(k)[None, :] < cut[:, None]
        vals = np.where(mask, vals, 0.0).astype(np.float32)
        ids = np.where(mask, ids, 0).astype(np.int32)
    label = (rng.random(n) < 0.5).astype(np.float32)
    offset = rng.standard_normal(n).astype(np.float32) * 0.1
    weight = rng.uniform(0.5, 2.0, n).astype(np.float32)
    return SparseBatch(
        ids=jnp.asarray(ids), vals=jnp.asarray(vals), label=jnp.asarray(label),
        offset=jnp.asarray(offset), weight=jnp.asarray(weight),
    )


@pytest.mark.parametrize("loss", ["logistic", "squared", "poisson"])
@pytest.mark.parametrize("zipf", [False, True])
def test_fast_value_and_grad_matches_autodiff(loss, zipf):
    n, k, d = 512, 8, 64
    batch = _random_batch(n, k, d, seed=1, zipf=zipf)
    fast = attach_feature_major(batch)
    obj = GlmObjective.create(loss, RegularizationContext("l2", 0.7))
    w = jnp.asarray(np.random.default_rng(2).standard_normal(d), jnp.float32) * 0.1

    v_ref, g_ref = jax.value_and_grad(obj.value)(w, batch)
    v_fast, g_fast = obj.value_and_grad(w, fast)
    np.testing.assert_allclose(v_fast, v_ref, rtol=1e-5)
    np.testing.assert_allclose(g_fast, g_ref, rtol=2e-4, atol=1e-5)
    # And under jit (the optimizer always calls it jitted).
    v_j, g_j = jax.jit(obj.value_and_grad)(w, fast)
    np.testing.assert_allclose(g_j, g_ref, rtol=2e-4, atol=1e-5)


def test_fast_hessian_vector_matches_jvp():
    n, k, d = 256, 6, 48
    batch = _random_batch(n, k, d, seed=3)
    fast = attach_feature_major(batch)
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 0.3))
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.standard_normal(d), jnp.float32) * 0.1
    v = jnp.asarray(rng.standard_normal(d), jnp.float32)

    hv_ref = jax.jvp(lambda u: jax.grad(obj.value)(u, batch), (w,), (v,))[1]
    hv_fast = obj.hessian_vector(w, v, fast)
    np.testing.assert_allclose(hv_fast, hv_ref, rtol=2e-4, atol=1e-5)


def test_multi_block_single_device():
    """S > 1 on one device: block-local rows offset to global rows."""
    n, k, d = 256, 4, 32
    batch = _random_batch(n, k, d, seed=5)
    obj = GlmObjective.create("logistic")
    w = jnp.asarray(np.random.default_rng(6).standard_normal(d), jnp.float32) * 0.1
    _, g_ref = jax.value_and_grad(obj.value)(w, batch)
    for shards in (1, 4):
        fast = attach_feature_major(batch, shards=shards)
        assert fast.fm.ids.shape[0] == shards
        _, g = obj.value_and_grad(w, fast)
        np.testing.assert_allclose(g, g_ref, rtol=2e-4, atol=1e-5)


def test_fm_ids_sorted_and_pads_harmless():
    batch = _random_batch(64, 4, 16, seed=7)
    fast = attach_feature_major(batch, shards=2)
    ids = np.asarray(fast.fm.ids)
    assert (np.diff(ids, axis=1) >= 0).all(), "ids must be sorted within blocks"
    # Pad entries carry val 0 -> removing them changes nothing.
    obj = GlmObjective.create("squared")
    w = jnp.ones(16, jnp.float32)
    _, g = obj.value_and_grad(w, fast)
    assert np.isfinite(np.asarray(g)).all()


def test_attach_feature_major_validation():
    batch = _random_batch(10, 3, 8, seed=8)
    with pytest.raises(ValueError, match="divisible"):
        attach_feature_major(batch, shards=3)


def test_distributed_fast_path_matches_single_device():
    from jax.sharding import Mesh
    from photon_tpu.parallel.distributed import DistributedGlmObjective
    from photon_tpu.parallel.mesh import create_mesh, shard_batch

    n, k, d = 512, 8, 64
    batch = _random_batch(n, k, d, seed=9)
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 0.5))
    w = jnp.asarray(np.random.default_rng(10).standard_normal(d), jnp.float32) * 0.1
    v_ref, g_ref = jax.value_and_grad(obj.value)(w, batch)

    mesh = create_mesh(8)
    sharded = shard_batch(batch, mesh)  # attaches per-shard fm
    assert sharded.fm is not None and sharded.fm.ids.shape[0] == 8
    dist = DistributedGlmObjective(obj, mesh)
    v, g = dist.value_and_grad(w, sharded)
    np.testing.assert_allclose(v, v_ref, rtol=1e-5)
    np.testing.assert_allclose(g, g_ref, rtol=2e-4, atol=1e-5)

    rng = np.random.default_rng(11)
    vec = jnp.asarray(rng.standard_normal(d), jnp.float32)
    hv_ref = jax.jvp(lambda u: jax.grad(obj.value)(u, batch), (w,), (vec,))[1]
    hv = dist.hessian_vector(w, vec, sharded)
    np.testing.assert_allclose(hv, hv_ref, rtol=2e-4, atol=1e-5)


def test_sparse_grad_kernel_selection(monkeypatch):
    """ops/sparse_grad_select: env overrides force the path; auto measures
    once per (backend, size bucket) and caches."""
    import photon_tpu.core.objective as obj_mod
    import photon_tpu.ops.sparse_grad_select as sel

    # Drop the probe floor so this tiny problem exercises the measured
    # path (production small shapes short-circuit to autodiff).
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_FLOOR", "0")
    n, k, d = 256, 4, 64
    batch = attach_feature_major(_random_batch(n, k, d, seed=20))
    obj = GlmObjective.create("logistic")
    w = jnp.zeros(d, jnp.float32)

    calls = []
    real = obj_mod._fm_segment_grad

    def spy(per_row, fm, dim):
        calls.append(dim)
        return real(per_row, fm, dim)

    monkeypatch.setattr(obj_mod, "_fm_segment_grad", spy)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "autodiff")
    obj.value_and_grad(w, batch)
    assert not calls, "autodiff override must bypass the fm kernel"
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "fm")
    obj.value_and_grad(w, batch)
    assert calls, "fm override must route through the fm kernel"

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "auto")
    sel._CACHE.clear()
    # On the CPU auto never measures a Mosaic kernel, whatever the batch
    # carries (the eligibility gate): the aligned layout is no candidate.
    with_al = batch._replace(al=object())
    assert sel.select_kernel(with_al, d) in ("fm", "autodiff")
    assert sel._CACHE, "auto mode must cache the measurement"
    (key,) = sel._CACHE
    assert key[3] == ("autodiff", "fm")
    # Same bucket -> no re-measure (cache key count stable).
    before = dict(sel._CACHE)
    sel.select_kernel(batch, d)
    assert sel._CACHE == before
    # Which layouts a builder should pay for: a pinned kernel's; on the
    # CPU, none in auto.
    assert not sel.aligned_layout_wanted()
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    assert sel.aligned_layout_wanted()
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "fm")
    assert not sel.aligned_layout_wanted()


def test_fast_path_under_normalization_matches_autodiff():
    """g = F (X^T dz - s * sum(dz)): the fm path must stay exact under
    in-objective normalization (it used to fall back to autodiff)."""
    from photon_tpu.core.normalization import NormalizationContext
    from photon_tpu.core.stats import BasicStatisticalSummary

    n, k, d = 384, 6, 40
    batch = _random_batch(n, k, d, seed=31)
    fast = attach_feature_major(batch)
    summary = BasicStatisticalSummary.from_batch(batch, d)
    for kind in ("scale_with_standard_deviation", "standardization"):
        norm = NormalizationContext.build(kind, summary, intercept_id=0)
        obj = GlmObjective.create(
            "logistic", RegularizationContext("l2", 0.4), normalization=norm
        )
        w = jnp.asarray(
            np.random.default_rng(32).standard_normal(d), jnp.float32) * 0.1
        v_ref, g_ref = jax.value_and_grad(obj.value)(w, batch)
        v_fast, g_fast = obj.value_and_grad(w, fast)
        np.testing.assert_allclose(v_fast, v_ref, rtol=1e-5)
        np.testing.assert_allclose(g_fast, g_ref, rtol=2e-4, atol=1e-5)
        hv_ref = jax.jvp(
            lambda u: jax.grad(obj.value)(u, batch), (w,),
            (jnp.asarray(np.random.default_rng(33).standard_normal(d),
                         jnp.float32),),
        )[1]
        hv = obj.hessian_vector(
            w, jnp.asarray(np.random.default_rng(33).standard_normal(d),
                           jnp.float32), fast)
        np.testing.assert_allclose(hv, hv_ref, rtol=2e-4, atol=1e-5)


def test_distributed_fast_path_under_normalization():
    """Per-shard normalization correction (shifts * local sum(dz)) must psum
    to the global correction — 8-device mesh vs single-device, normalized."""
    from photon_tpu.core.normalization import NormalizationContext
    from photon_tpu.core.stats import BasicStatisticalSummary
    from photon_tpu.parallel.distributed import DistributedGlmObjective
    from photon_tpu.parallel.mesh import create_mesh, shard_batch

    n, k, d = 512, 8, 64
    batch = _random_batch(n, k, d, seed=41)
    summary = BasicStatisticalSummary.from_batch(batch, d)
    norm = NormalizationContext.build("standardization", summary, intercept_id=0)
    obj = GlmObjective.create(
        "logistic", RegularizationContext("l2", 0.5), normalization=norm
    )
    w = jnp.asarray(np.random.default_rng(42).standard_normal(d), jnp.float32) * 0.1
    v_ref, g_ref = jax.value_and_grad(obj.value)(w, batch)

    mesh = create_mesh(8)
    dist = DistributedGlmObjective(obj, mesh)
    v, g = dist.value_and_grad(w, shard_batch(batch, mesh))
    np.testing.assert_allclose(v, v_ref, rtol=1e-5)
    np.testing.assert_allclose(g, g_ref, rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("loss", ["logistic", "poisson"])
@pytest.mark.parametrize("zipf", [False, True])
def test_pallas_kernel_matches_autodiff(monkeypatch, loss, zipf):
    """PHOTON_SPARSE_GRAD=pallas routes value+grad AND Hv through the
    slab-aligned Mosaic kernel (interpret mode on CPU) — must match the
    autodiff reference like the fm path does (VERDICT r3 item 2)."""
    n, k, d = 256, 6, 48
    batch = _random_batch(n, k, d, seed=50, zipf=zipf)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    fast = attach_feature_major(batch, aligned_dim=d)
    assert fast.al is not None
    obj = GlmObjective.create(loss, RegularizationContext("l2", 0.6))
    rng = np.random.default_rng(51)
    w = jnp.asarray(rng.standard_normal(d), jnp.float32) * 0.1

    assert obj._sparse_kernel(fast, d) == "pallas"
    v_ref, g_ref = jax.value_and_grad(obj.value)(w, batch)
    v_p, g_p = obj.value_and_grad(w, fast)
    np.testing.assert_allclose(v_p, v_ref, rtol=1e-5)
    np.testing.assert_allclose(g_p, g_ref, rtol=2e-4, atol=1e-5)
    # Under jit (optimizers always call it jitted).
    v_j, g_j = jax.jit(obj.value_and_grad)(w, fast)
    np.testing.assert_allclose(g_j, g_ref, rtol=2e-4, atol=1e-5)

    vec = jnp.asarray(rng.standard_normal(d), jnp.float32)
    hv_ref = jax.jvp(lambda u: jax.grad(obj.value)(u, batch), (w,), (vec,))[1]
    hv = obj.hessian_vector(w, vec, fast)
    np.testing.assert_allclose(hv, hv_ref, rtol=2e-4, atol=1e-5)


def test_pallas_kernel_under_normalization(monkeypatch):
    """The normalization algebra (g = F (X^T dz - s Σ dz)) is shared with
    the fm path, so the pallas kernel must stay exact under it too."""
    from photon_tpu.core.normalization import NormalizationContext
    from photon_tpu.core.stats import BasicStatisticalSummary

    n, k, d = 192, 5, 40
    batch = _random_batch(n, k, d, seed=60)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    fast = attach_feature_major(batch, aligned_dim=d)
    summary = BasicStatisticalSummary.from_batch(batch, d)
    norm = NormalizationContext.build("standardization", summary, intercept_id=0)
    obj = GlmObjective.create(
        "logistic", RegularizationContext("l2", 0.4), normalization=norm
    )
    w = jnp.asarray(np.random.default_rng(61).standard_normal(d), jnp.float32) * 0.1
    v_ref, g_ref = jax.value_and_grad(obj.value)(w, batch)
    v_p, g_p = obj.value_and_grad(w, fast)
    np.testing.assert_allclose(v_p, v_ref, rtol=1e-5)
    np.testing.assert_allclose(g_p, g_ref, rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("zipf", [False, True])
def test_pallas_forward_margins_via_transposed_layout(monkeypatch, zipf):
    """aligned_forward=True builds the row-dictionary layout; the pallas
    path then computes margins AND Hv products through the same
    position-reduce kernel (KERNEL_NOTES option (a)) — must match the
    autodiff reference, incl. under normalization."""
    from photon_tpu.core.normalization import NormalizationContext
    from photon_tpu.core.stats import BasicStatisticalSummary
    from photon_tpu.ops.pallas_gather import aligned_segment_grad

    n, k, d = 320, 7, 56
    batch = _random_batch(n, k, d, seed=80, zipf=zipf)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    fast = attach_feature_major(batch, aligned_dim=d, aligned_forward=True)
    assert fast.al is not None and fast.al_t is not None
    rng = np.random.default_rng(81)
    w = jnp.asarray(rng.standard_normal(d), jnp.float32) * 0.1

    # Raw margins through the transposed layout == row-major gather.
    from photon_tpu.data.batch import margins as rowmajor_margins

    z_t = aligned_segment_grad(w, fast.al_t, n, interpret=True) + batch.offset
    np.testing.assert_allclose(
        np.asarray(z_t), np.asarray(rowmajor_margins(w, batch)),
        rtol=2e-4, atol=1e-5,
    )

    obj = GlmObjective.create("logistic", RegularizationContext("l2", 0.5))
    v_ref, g_ref = jax.value_and_grad(obj.value)(w, batch)
    v_p, g_p = obj.value_and_grad(w, fast)
    np.testing.assert_allclose(v_p, v_ref, rtol=1e-5)
    np.testing.assert_allclose(g_p, g_ref, rtol=2e-4, atol=1e-5)
    vec = jnp.asarray(rng.standard_normal(d), jnp.float32)
    hv_ref = jax.jvp(lambda u: jax.grad(obj.value)(u, batch), (w,), (vec,))[1]
    np.testing.assert_allclose(
        obj.hessian_vector(w, vec, fast), hv_ref, rtol=2e-4, atol=1e-5
    )

    # Under normalization (the shifted-margin correction rides along).
    summary = BasicStatisticalSummary.from_batch(batch, d)
    norm = NormalizationContext.build("standardization", summary, intercept_id=0)
    obj_n = GlmObjective.create(
        "logistic", RegularizationContext("l2", 0.5), normalization=norm
    )
    v_ref, g_ref = jax.value_and_grad(obj_n.value)(w, batch)
    v_p, g_p = obj_n.value_and_grad(w, fast)
    np.testing.assert_allclose(v_p, v_ref, rtol=1e-5)
    np.testing.assert_allclose(g_p, g_ref, rtol=2e-4, atol=1e-5)


def test_pallas_kernel_normalized_hessian_vector(monkeypatch):
    """Normalized Hv falls back to jvp-of-grad; pallas_call has no JVP
    rule, so the inner grad must re-route to the (differentiable) fm
    layout — TRON + normalization + pallas used to crash at trace time."""
    from photon_tpu.core.normalization import NormalizationContext
    from photon_tpu.core.stats import BasicStatisticalSummary

    n, k, d = 128, 4, 24
    batch = _random_batch(n, k, d, seed=65)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    fast = attach_feature_major(batch, aligned_dim=d)
    summary = BasicStatisticalSummary.from_batch(batch, d)
    norm = NormalizationContext.build("standardization", summary, intercept_id=0)
    obj = GlmObjective.create(
        "logistic", RegularizationContext("l2", 0.3), normalization=norm
    )
    rng = np.random.default_rng(66)
    w = jnp.asarray(rng.standard_normal(d), jnp.float32) * 0.1
    vec = jnp.asarray(rng.standard_normal(d), jnp.float32)
    hv = obj.hessian_vector(w, vec, fast)
    hv_ref = jax.jvp(lambda u: jax.grad(obj.value)(u, batch), (w,), (vec,))[1]
    np.testing.assert_allclose(hv, hv_ref, rtol=2e-4, atol=1e-5)


def _refused() -> dict:
    """``{kernel: count}`` of the process registry's ``kernels.refused``."""
    from photon_tpu.utils import device

    return {
        row["labels"]["kernel"]: row["value"]
        for row in device.kernel_metrics()
        if row["name"] == "kernels.refused"
    }


def test_measure_correctness_gate_excludes_bad_pallas(monkeypatch, caplog):
    """A Mosaic kernel that miscompiles — or that the compiler refuses —
    on the live backend must be DISQUALIFIED by the probe's on-device
    check, never timed into production eligibility, and never quietly: the
    refusal is counted (``kernels.refused{kernel=pallas}``) and carried by
    every run report of the process.  A correct kernel passes."""
    import photon_tpu.ops.pallas_gather as pg
    import photon_tpu.ops.sparse_grad_select as sel
    from photon_tpu.telemetry import TelemetrySession, process_registry

    import logging

    # Straight onto the kernels' logger: an earlier PhotonLogger("photon_tpu")
    # in this process stops propagation to the root handler caplog owns.
    klog = logging.getLogger("photon_tpu.kernels")
    klog.addHandler(caplog.handler)
    monkeypatch.setattr(klog, "propagate", False)
    real = pg.aligned_segment_grad

    def garbage(per_row, al, dim, interpret=None):
        return real(per_row, al, dim, interpret=True) + 1.0  # wrong output

    def refused(per_row, al, dim, interpret=None):
        raise RuntimeError(
            "Mosaic failed to compile TPU kernel: Not implemented\nmore"
        )

    def correct(per_row, al, dim, interpret=None):
        return real(per_row, al, dim, interpret=True)  # CPU-safe, right math

    process_registry().clear()
    monkeypatch.setattr(pg, "aligned_segment_grad", garbage)
    with caplog.at_level("WARNING", logger="photon_tpu.kernels"):
        choice = sel._measure(1 << 12, 256, 256, ("autodiff", "fm", "pallas"))
    assert choice in ("fm", "autodiff"), "garbage pallas must be excluded"
    assert _refused() == {"pallas": 1.0}
    assert "kernel pallas refused on this device: parity failed" in caplog.text

    process_registry().clear()
    caplog.clear()
    monkeypatch.setattr(pg, "aligned_segment_grad", refused)
    with caplog.at_level("WARNING", logger="photon_tpu.kernels"):
        choice = sel._measure(1 << 12, 256, 256, ("autodiff", "fm", "pallas"))
    assert choice in ("fm", "autodiff"), "a refused pallas must be excluded"
    assert _refused() == {"pallas": 1.0}
    assert caplog.text.rstrip().endswith(
        "kernel pallas refused on this device: "
        "Mosaic failed to compile TPU kernel: Not implemented"
    )
    report = TelemetrySession("t").build_report()
    assert {
        "name": "kernels.refused", "labels": {"kernel": "pallas"},
        "value": 1.0,
    } in report["metrics"]["counters"]
    status = sel.kernel_report(1 << 12, 256, 256, kernels=("fm", "pallas"))
    assert status == {
        "fm": "compiled+parity ok",
        "pallas": "refused: Mosaic failed to compile TPU kernel: "
                  "Not implemented",
    }

    process_registry().clear()
    monkeypatch.setattr(pg, "aligned_segment_grad", correct)
    choice2 = sel._measure(1 << 12, 256, 256, ("autodiff", "fm", "pallas"))
    assert choice2 in ("fm", "autodiff", "pallas")  # gate passed; timing decides
    assert not _refused()
    klog.removeHandler(caplog.handler)


def test_probe_cap_env_override(monkeypatch):
    """The selection probe's size cap is env-tunable (bench.py raises it to
    probe at the true headline shape); garbage values fall back to the
    default instead of crashing training."""
    import photon_tpu.ops.sparse_grad_select as sel

    monkeypatch.delenv("PHOTON_SPARSE_PROBE_MAX_ENTRIES", raising=False)
    assert sel._probe_cap() == sel._PROBE_MAX_ENTRIES
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_MAX_ENTRIES", "4096")
    assert sel._probe_cap() == 4096
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_MAX_ENTRIES", "not-a-number")
    assert sel._probe_cap() == sel._PROBE_MAX_ENTRIES
    # 0 would divide-by-zero in the ceil; negatives would uncap the probe.
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_MAX_ENTRIES", "0")
    assert sel._probe_cap() == sel._PROBE_MAX_ENTRIES
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_MAX_ENTRIES", "-5")
    assert sel._probe_cap() == sel._PROBE_MAX_ENTRIES


def test_probe_floor_skips_measurement_for_small_problems(monkeypatch):
    """Below the probe floor auto mode returns autodiff WITHOUT running the
    eager measurement (GAME runs hit many small shape buckets; a probe per
    bucket costs more than any kernel difference repays)."""
    import photon_tpu.ops.sparse_grad_select as sel

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "auto")

    def boom(*a, **k):
        raise AssertionError("probe must not run below the floor")

    monkeypatch.setattr(sel, "_measure", boom)
    sel._CACHE.clear()
    batch = attach_feature_major(_random_batch(256, 4, 64, seed=72))
    assert sel.select_kernel(batch, 64) == "autodiff"
    assert not sel._CACHE, "below the floor the probe path must not engage"
    # At/above the floor the measurement DOES run — and a probe that fails
    # outright propagates: there is no quiet pin to a default kernel.
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_FLOOR", "512")
    with pytest.raises(AssertionError, match="probe must not run"):
        sel.select_kernel(batch, 64)
    assert not sel._CACHE, "a failed probe must not cache a verdict"


@pytest.mark.parametrize("winner", ["autodiff", "fm"])
def test_cpu_attach_above_the_floor_probes_before_it_sorts(monkeypatch, winner):
    """Off the TPU the candidates are autodiff and fm.  Above the probe floor
    a single-block ``attach_feature_major(batch, aligned_dim=d)`` takes the
    probe's verdict before the sort: the batch carries ``fm`` only if ``fm``
    won, the spared sort is counted, and the selection at trace time finds
    the verdict (one measurement, under its span)."""
    import photon_tpu.ops.sparse_grad_select as sel
    from photon_tpu.telemetry import process_registry

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "auto")
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_FLOOR", "512")
    seen = []

    def measure(e, d, n, names):
        seen.append(names)
        return winner

    monkeypatch.setattr(sel, "_measure", measure)
    monkeypatch.setattr(sel, "_CACHE", {})
    process_registry().clear()
    n, k, d = 256, 4, 64
    batch = _random_batch(n, k, d, seed=76)
    fast = attach_feature_major(batch, aligned_dim=d)
    assert seen == [("autodiff", "fm")]
    assert (fast.fm is not None) == (winner == "fm")
    assert fast.al is None and fast.bt is None
    rows = {
        (r["name"], tuple(r["labels"].values())): r["value"]
        for r in process_registry().snapshot()["counters"]
    }
    assert rows.get(("layout.skipped", ("fm",))) == (
        None if winner == "fm" else 1
    )
    assert rows[("span.count", ("kernels.probe",))] == 1
    assert (("span.count", ("layout.feature_major",)) in rows) == (
        winner == "fm"
    )
    obj = GlmObjective.create("logistic")
    assert obj._sparse_kernel(fast, d) == (None if winner == "autodiff" else "fm")
    assert seen == [("autodiff", "fm")]
    w = jnp.asarray(np.random.default_rng(77).standard_normal(d), jnp.float32)
    v_ref, g_ref = jax.value_and_grad(obj.value)(w, batch)
    v, g = obj.value_and_grad(w, fast)
    np.testing.assert_allclose(float(v), float(v_ref), rtol=2e-5)
    np.testing.assert_allclose(g, g_ref, rtol=2e-4, atol=2e-5)


def test_aligned_layout_survives_astype_and_pad_strip(monkeypatch):
    """batch_astype converts al.vals in place; pad_batch strips al (it is
    row-structure-dependent) so shard_batch rebuilds per-shard fm only."""
    from photon_tpu.data.batch import batch_astype, pad_batch

    batch = _random_batch(64, 4, 32, seed=70)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    fast = attach_feature_major(batch, aligned_dim=32)
    bf16 = batch_astype(fast, jnp.bfloat16)
    assert bf16.al is not None and bf16.al.vals.dtype == jnp.bfloat16
    obj = GlmObjective.create("logistic")
    w = jnp.asarray(np.random.default_rng(71).standard_normal(32), jnp.float32) * 0.1
    _, g_ref = jax.value_and_grad(obj.value)(w, batch)
    _, g_bf = obj.value_and_grad(w, bf16)
    np.testing.assert_allclose(g_bf, g_ref, rtol=0.02, atol=0.02)
    padded = pad_batch(fast, 80)
    assert padded.al is None and padded.fm is None


def test_fast_path_matches_autodiff_across_random_configs():
    """Property-style sweep over (n, k, d) configs — incl. degenerate k=1,
    tiny d, n=1 — with round-robin losses, random l2, and a multi-block
    feature-major layout (shards=2) whenever n is even: the fm fast path
    must agree with the autodiff reference at several random points."""
    rng = np.random.default_rng(2024)
    # Each (n, k, d) is a distinct compile; the fixed list carries the edge
    # cases, so two random draws suffice (suite-time budget, VERDICT r3
    # item 4).
    configs = [(1, 1, 2), (3, 1, 2), (2, 5, 3), (17, 3, 9)] + [
        (int(rng.integers(2, 200)), int(rng.integers(1, 9)),
         int(rng.integers(2, 64)))
        for _ in range(2)
    ]
    for i, (n, k, d) in enumerate(configs):
        loss = ("logistic", "squared", "poisson")[i % 3]
        l2 = float(rng.uniform(0, 2))
        batch = _random_batch(n, k, d, seed=i, zipf=bool(i % 2))
        fast = attach_feature_major(batch, shards=2 if n % 2 == 0 else 1)
        obj = GlmObjective.create(loss, RegularizationContext("l2", l2))
        for trial in range(2):
            w = jnp.asarray(
                rng.standard_normal(d).astype(np.float32) * 0.5
            )
            v_ref, g_ref = jax.value_and_grad(obj.value)(w, batch)
            v_fm, g_fm = obj.value_and_grad(w, fast)
            np.testing.assert_allclose(
                float(v_fm), float(v_ref), rtol=2e-5,
                err_msg=f"cfg {n},{k},{d} {loss} l2={l2}",
            )
            np.testing.assert_allclose(
                np.asarray(g_fm), np.asarray(g_ref), rtol=2e-4, atol=2e-5,
                err_msg=f"cfg {n},{k},{d} {loss} l2={l2}",
            )


def test_selection_probe_measures_under_enclosing_trace(monkeypatch):
    """The auto-selection probe usually first fires while an ENCLOSING
    jit (optimizer while_loop, streamed chunk program) is being traced;
    under omnistaging its host synchronizations would raise.  The
    eval-context escape hatch must let the real measurement complete
    there — INCLUDING the Pallas candidate, whose kernel body (it reads
    ``program_id``) cannot be traced under ensure_compile_time_eval on
    jax 0.9: that refusal was silent until the first chip run."""
    import jax
    import jax.numpy as jnp

    import photon_tpu.ops.sparse_grad_select as sg
    from photon_tpu.telemetry import process_registry

    process_registry().clear()
    # The interpreter stands in for Mosaic: same kernel-body trace.
    monkeypatch.setattr(sg, "_pallas_eligible", lambda: True)
    saved = dict(sg._CACHE)
    sg._CACHE.clear()
    calls = []
    real = sg._measure

    def spy(*args, **kw):
        out = real(*args, **kw)
        calls.append(out)
        return out

    monkeypatch.setattr(sg, "_measure", spy)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "auto")
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_FLOOR", "1")
    # What the batch carries names the candidates; the probe measures them
    # on a problem of its own.
    batch = attach_feature_major(
        _random_batch(256, 16, 512, seed=74)
    )._replace(al=object())
    try:
        def f(x):
            choice = sg.select_kernel(batch, 512)
            assert choice in ("fm", "autodiff", "pallas")
            return x * 2.0

        jax.jit(f)(jnp.ones(2))
        assert calls, "the probe must have measured under the trace"
        assert not _refused(), _refused()
        # The probe ran under its span (process registry: no session here).
        assert any(
            row["name"] == "span.count"
            and row["labels"] == {"span": "kernels.probe"}
            for row in process_registry().snapshot()["counters"]
        )
    finally:
        sg._CACHE.clear()
        sg._CACHE.update(saved)
