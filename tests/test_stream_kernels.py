"""Streamed fast-kernel layouts (VERDICT r5 item 3): chunks re-parsed
per pass carry cached aligned layouts, route to the fast kernels, and
produce the same numbers as the plain autodiff streamed pass."""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from photon_tpu.core.objective import GlmObjective, RegularizationContext
from photon_tpu.data.streaming import LibsvmFileSource, StreamingObjective

D_RAW = 96  # feature dim before the intercept column


def _write_files(tmp_path, n_files=3, rows=64, k=6, seed=0):
    rng = np.random.default_rng(seed)
    files = []
    for fi in range(n_files):
        path = tmp_path / f"part-{fi:03d}.libsvm"
        with open(path, "w") as f:
            # Last file shorter: exercises the unequal-chunk geometry.
            n = rows if fi < n_files - 1 else rows // 2
            for _ in range(n):
                ids = np.sort(rng.choice(
                    np.arange(1, D_RAW + 1), size=k, replace=False
                ))
                vals = rng.standard_normal(k)
                y = 1 if rng.random() < 0.5 else -1
                f.write(f"{y} " + " ".join(
                    f"{j}:{v:.5f}" for j, v in zip(ids, vals)
                ) + "\n")
        files.append(str(path))
    return files


def _streamed_vg(files, w):
    source = LibsvmFileSource(files, intercept=True)
    obj = StreamingObjective(
        GlmObjective.create("logistic", RegularizationContext("l2", 0.5)),
        source.chunk_iter_factory,
    )
    v, g = obj.value_and_grad(w)
    return float(v), np.asarray(g), source.dim


@pytest.mark.parametrize("kernel", ["fm", "pallas"])
def test_streamed_kernel_matches_autodiff(tmp_path, monkeypatch, kernel):
    files = _write_files(tmp_path)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "autodiff")
    dim_probe = LibsvmFileSource(files, intercept=True).dim
    w = jnp.asarray(
        np.random.default_rng(1).standard_normal(dim_probe)
        .astype(np.float32) * 0.1
    )
    v_ref, g_ref, _ = _streamed_vg(files, w)

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", kernel)
    monkeypatch.setenv(
        "PHOTON_STREAM_LAYOUT_CACHE", str(tmp_path / "cache")
    )
    v, g, _ = _streamed_vg(files, w)
    np.testing.assert_allclose(v, v_ref, rtol=2e-5)
    scale = max(float(np.abs(g_ref).max()), 1.0)
    np.testing.assert_allclose(g, g_ref, rtol=2e-4, atol=2e-4 * scale)


def test_stream_layout_cache_hit_skips_build(tmp_path, monkeypatch):
    """Second pass (and a fresh source, as after a restart) must load
    the cached aux instead of rebuilding."""
    import photon_tpu.data.stream_layouts as sl

    files = _write_files(tmp_path)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    monkeypatch.setenv(
        "PHOTON_STREAM_LAYOUT_CACHE", str(tmp_path / "cache")
    )
    dim_probe = LibsvmFileSource(files, intercept=True).dim
    w = jnp.zeros(dim_probe, jnp.float32)
    builds = []
    real_build = sl._build_padded_layout

    def counting_build(*args, **kw):
        builds.append(1)
        return real_build(*args, **kw)

    monkeypatch.setattr(sl, "_build_padded_layout", counting_build)
    v1, g1, _ = _streamed_vg(files, w)
    assert len(builds) == len(files)  # one build per file, first pass
    v2, g2, _ = _streamed_vg(files, w)  # fresh source = restart
    assert len(builds) == len(files)  # all cache hits
    assert v1 == v2
    np.testing.assert_array_equal(g1, g2)


def test_stream_kernel_follows_forced_sparse_grad(monkeypatch):
    from photon_tpu.data.stream_layouts import stream_kernel

    monkeypatch.delenv("PHOTON_STREAM_KERNEL", raising=False)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    assert stream_kernel() == "pallas"
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "auto")
    assert stream_kernel() == "autodiff"
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")  # no stream layout
    assert stream_kernel() == "autodiff"
    monkeypatch.setenv("PHOTON_STREAM_KERNEL", "fm")
    assert stream_kernel() == "fm"


def test_stream_cache_invalidated_by_file_change(tmp_path, monkeypatch):
    """Rewriting a part file (new size/mtime) must miss the cache and
    rebuild, not serve the stale aux."""
    import photon_tpu.data.stream_layouts as sl

    files = _write_files(tmp_path, n_files=1, rows=32)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    monkeypatch.setenv(
        "PHOTON_STREAM_LAYOUT_CACHE", str(tmp_path / "cache")
    )
    dim_probe = LibsvmFileSource(files, intercept=True).dim
    w = jnp.zeros(dim_probe, jnp.float32)
    builds = []
    real_build = sl._build_padded_layout

    def counting_build(*args, **kw):
        builds.append(1)
        return real_build(*args, **kw)

    monkeypatch.setattr(sl, "_build_padded_layout", counting_build)
    _streamed_vg(files, w)
    assert len(builds) == 1
    # Rewrite with different content (more rows -> different size).
    _write_files(tmp_path, n_files=1, rows=48, seed=9)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "autodiff")
    dim2 = LibsvmFileSource(files, intercept=True).dim
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    v_new, g_new, _ = _streamed_vg(files, jnp.zeros(dim2, jnp.float32))
    assert len(builds) == 2  # rebuilt for the new file identity
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "autodiff")
    v_ref, g_ref, _ = _streamed_vg(files, jnp.zeros(dim2, jnp.float32))
    np.testing.assert_allclose(v_new, v_ref, rtol=2e-5)
    np.testing.assert_allclose(g_new, g_ref, rtol=2e-4, atol=1e-4)
