"""Sharded fast-kernel equivalence (VERDICT r5 item 2): the pallas
gradient kernel must produce the SAME numbers under the sharded
objective (8-virtual-device mesh, per-shard layouts + psum) as plain
single-device autodiff.

This is the reference's distributed-vs-local cross-check (SURVEY.md §4)
applied to the round-4/5 hardware kernels: before this round the fast
kernels required ``shards == 1`` and silently fell back on any mesh, so
no kernel win could reach the multi-chip north star.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_tpu.core.objective import GlmObjective, RegularizationContext
from photon_tpu.data.batch import SparseBatch, attach_feature_major
from photon_tpu.parallel import DistributedGlmObjective, create_mesh, shard_batch

N, K, D = 160, 5, 64  # N not a multiple of 8 after padding? 160 = 8*20


def _batch(seed=0, n=N):
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, D, size=(n, K)).astype(np.int32)
    vals = rng.standard_normal((n, K)).astype(np.float32)
    vals[rng.random((n, K)) < 0.1] = 0.0
    label = (rng.random(n) < 0.5).astype(np.float32)
    offset = (rng.standard_normal(n) * 0.1).astype(np.float32)
    weight = rng.uniform(0.5, 2.0, n).astype(np.float32)
    return SparseBatch(
        ids=jnp.asarray(ids), vals=jnp.asarray(vals),
        label=jnp.asarray(label), offset=jnp.asarray(offset),
        weight=jnp.asarray(weight),
    )


def _autodiff_reference(obj, w, batch, monkeypatch):
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "autodiff")
    v, g = obj.value_and_grad(w, batch)
    return np.asarray(v), np.asarray(g)


def _check_sharded(monkeypatch, kernel, loss="logistic", reg=None, n=N,
                   check_hv=True):
    monkeypatch.setenv("PHOTON_ROUTE_CACHE", "0")
    batch = _batch(n=n)
    obj = GlmObjective.create(
        loss, reg or RegularizationContext("l2", 0.3)
    )
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal(D).astype(np.float32) * 0.1)
    v_ref, g_ref = _autodiff_reference(obj, w, batch, monkeypatch)

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", kernel)
    mesh = create_mesh()
    sharded = shard_batch(batch, mesh, aligned_dim=D)
    assert sharded.al is not None
    dist = DistributedGlmObjective(obj, mesh)
    assert dist._sparse_kernel(w, sharded) == kernel
    v_d, g_d = dist.value_and_grad(w, sharded)
    np.testing.assert_allclose(v_d, v_ref, rtol=2e-5)
    scale = max(float(np.abs(g_ref).max()), 1.0)
    np.testing.assert_allclose(
        np.asarray(g_d), g_ref, rtol=2e-4, atol=2e-4 * scale
    )
    # Hv through the same sharded kernel vs autodiff jvp.
    if not check_hv:
        return
    u = jnp.asarray(rng.standard_normal(D).astype(np.float32))
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "autodiff")
    hv_ref = np.asarray(obj.hessian_vector(w, u, batch))
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", kernel)
    hv_d = np.asarray(dist.hessian_vector(w, u, sharded))
    hs = max(float(np.abs(hv_ref).max()), 1.0)
    np.testing.assert_allclose(hv_d, hv_ref, rtol=2e-4, atol=2e-4 * hs)


def test_sharded_pallas_grad_matches_autodiff(monkeypatch):
    _check_sharded(monkeypatch, "pallas")


def test_sharded_pallas_poisson_unpadded_rows(monkeypatch):
    """Different loss + a row count that needs zero-weight padding (101
    rows over 8 shards): the pad rows must contribute exactly nothing
    through the per-shard layouts.  (Hv covered by the logistic test.)"""
    _check_sharded(
        monkeypatch, "pallas", loss="poisson", n=101, check_hv=False
    )


def test_sharded_pallas_normalized_grad(monkeypatch):
    """Normalization algebra through the sharded pallas kernel, and the
    normalized Hv fallback (jvp through the fm layout — pallas_call has
    no JVP rule)."""
    from photon_tpu.core.normalization import NormalizationContext

    monkeypatch.setenv("PHOTON_ROUTE_CACHE", "0")
    batch = _batch(seed=7)
    rng = np.random.default_rng(8)
    factors = rng.uniform(0.5, 2.0, D).astype(np.float32)
    shifts = (rng.standard_normal(D) * 0.01).astype(np.float32)
    norm = NormalizationContext(factors=jnp.asarray(factors),
                                shifts=jnp.asarray(shifts))
    obj = GlmObjective.create(
        "logistic", RegularizationContext("l2", 0.2), normalization=norm
    )
    w = jnp.asarray(rng.standard_normal(D).astype(np.float32) * 0.1)
    v_ref, g_ref = _autodiff_reference(obj, w, batch, monkeypatch)

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    mesh = create_mesh()
    sharded = shard_batch(batch, mesh, aligned_dim=D)
    dist = DistributedGlmObjective(obj, mesh)
    v_d, g_d = dist.value_and_grad(w, sharded)
    np.testing.assert_allclose(v_d, v_ref, rtol=2e-5)
    scale = max(float(np.abs(g_ref).max()), 1.0)
    np.testing.assert_allclose(
        np.asarray(g_d), g_ref, rtol=2e-4, atol=2e-4 * scale
    )
    u = jnp.asarray(rng.standard_normal(D).astype(np.float32))
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "autodiff")
    hv_ref = np.asarray(obj.hessian_vector(w, u, batch))
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    hv_d = np.asarray(dist.hessian_vector(w, u, sharded))
    hs = max(float(np.abs(hv_ref).max()), 1.0)
    np.testing.assert_allclose(hv_d, hv_ref, rtol=2e-4, atol=2e-4 * hs)


def test_sharded_attach_stacks_uniform_geometry(monkeypatch):
    """The per-shard aux must stack: the aligned layouts (and, asked for,
    the transposed ones) share one padded geometry, so every leaf carries
    the shard axis and the batch is ONE pytree."""
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    monkeypatch.setenv("PHOTON_ROUTE_CACHE", "0")
    # Skewed ids so per-shard slab and tile counts genuinely differ.
    rng = np.random.default_rng(3)
    n = 8 * 24
    ids = (1 + (rng.zipf(1.5, size=(n, K)) - 1) % (D - 1)).astype(np.int32)
    batch = SparseBatch(
        ids=jnp.asarray(ids),
        vals=jnp.asarray(rng.standard_normal((n, K)).astype(np.float32)),
        label=jnp.asarray((rng.random(n) < 0.5).astype(np.float32)),
        offset=jnp.zeros(n, jnp.float32),
        weight=jnp.ones(n, jnp.float32),
    )
    out = attach_feature_major(
        batch, shards=8, aligned_dim=D, aligned_forward=True
    )
    assert out.al is not None and out.al_t is not None and out.bt is None
    assert int(out.al.lo.shape[0]) == 8
    assert int(out.al.dup_map.shape[0]) == 8
    for aux in (out.al, out.al_t):
        assert all(int(leaf.shape[0]) == 8 for leaf in jax.tree.leaves(aux))
    # Under a pin that reads no layout the same call builds fm alone.
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "fm")
    plain = attach_feature_major(batch, shards=8, aligned_dim=D)
    assert plain.fm is not None and plain.al is None


def test_sharded_lbfgs_convergence_pallas(monkeypatch):
    """A full sharded L-BFGS fit with the pallas kernel forced converges
    to the same optimum as single-device autodiff.  Iteration cap keeps
    the interpret-mode run inside the suite's wall-clock bar (converges in
    ~15 iterations at this shape)."""
    from photon_tpu.core.optimizers import OptimizerConfig, lbfgs

    cfg = OptimizerConfig(max_iterations=30)
    monkeypatch.setenv("PHOTON_ROUTE_CACHE", "0")
    batch = _batch(seed=11)
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 1.0))
    w0 = jnp.zeros(D, jnp.float32)

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "autodiff")
    res_ref = lbfgs(lambda w: obj.value_and_grad(w, batch), w0, cfg)

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    mesh = create_mesh()
    sharded = shard_batch(batch, mesh, aligned_dim=D)
    dist = DistributedGlmObjective(obj, mesh)
    res_d = lbfgs(lambda w: dist.value_and_grad(w, sharded), w0, cfg)
    assert bool(res_d.converged)
    np.testing.assert_allclose(
        float(res_d.value), float(res_ref.value), rtol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(res_d.w), np.asarray(res_ref.w), atol=5e-2
    )


def test_bf16_storage_keeps_pallas_grad_consistent(monkeypatch):
    """batch_astype(bf16) after a pallas attach must keep the gradient
    consistent with the (converted) values the margins read: the layout's
    own value stream converts with the row-major one, so both directions
    see one value stream.  Checked sharded AND single-device against
    autodiff on the SAME converted batch (tight tolerance — same
    values, different reduction order)."""
    from photon_tpu.data.batch import batch_astype

    monkeypatch.setenv("PHOTON_ROUTE_CACHE", "0")
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    batch = _batch(seed=17)
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 0.3))
    rng = np.random.default_rng(18)
    w = jnp.asarray(rng.standard_normal(D).astype(np.float32) * 0.1)

    fast16 = batch_astype(
        attach_feature_major(batch, aligned_dim=D), jnp.bfloat16
    )
    assert fast16.al.vals.dtype == jnp.bfloat16
    v_x, g_x = obj.value_and_grad(w, fast16)

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "autodiff")
    b16 = batch_astype(batch, jnp.bfloat16)
    v_a, g_a = obj.value_and_grad(w, b16)
    np.testing.assert_allclose(float(v_x), float(v_a), rtol=2e-5)
    scale = max(float(np.abs(np.asarray(g_a)).max()), 1.0)
    np.testing.assert_allclose(
        np.asarray(g_x), np.asarray(g_a), rtol=2e-4, atol=2e-4 * scale
    )

    # Sharded: the STACKED value stream converts the same way, and pallas
    # is what dispatches.
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    mesh = create_mesh()
    sharded16 = batch_astype(
        shard_batch(batch, mesh, aligned_dim=D), jnp.bfloat16
    )
    assert sharded16.al.vals.dtype == jnp.bfloat16
    dist = DistributedGlmObjective(obj, mesh)
    assert dist._sparse_kernel(w, sharded16) == "pallas"
    v_d, g_d = dist.value_and_grad(w, sharded16)
    np.testing.assert_allclose(float(v_d), float(v_a), rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(g_d), np.asarray(g_a), rtol=2e-4, atol=2e-4 * scale
    )
