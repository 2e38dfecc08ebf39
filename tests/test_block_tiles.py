"""The ``blocked`` sparse kernel (ops/block_tiles.py): layout invariants, both
directions and the objective's value+grad / Hv against autodiff (interpret
mode, small shapes), the exact three-term bfloat16 split, and selection."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_tpu.core.objective import GlmObjective, RegularizationContext
from photon_tpu.data.batch import (
    SparseBatch,
    attach_feature_major,
    batch_astype,
    pad_batch,
)
from photon_tpu.ops import block_tiles as bt_mod
from photon_tpu.ops.block_tiles import (
    TILE_SLOTS,
    block_tile_geometry,
    block_tiles_product,
    build_block_tiles,
    device_block_tiles,
    split_bf16x3,
)

# Neither a multiple of the 2,048 block that these shapes derive: two blocks
# a side, the last ragged.
N, D, K = 2500, 2300, 3
GEOMETRY = (2048, 2048)
PATTERNS = ("uniform", "zipf", "duplicates", "ragged")


def _entries(pattern: str, seed: int = 0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, D, size=(N, K), dtype=np.int32)
    vals = rng.standard_normal((N, K)).astype(np.float32)
    if pattern == "zipf":
        ids = np.minimum(rng.zipf(1.3, size=(N, K)) - 1, D - 1).astype(np.int32)
    elif pattern == "duplicates":
        ids[:, 1] = ids[:, 0]
    elif pattern == "ragged":
        pad = np.arange(K)[None, :] >= rng.integers(0, K + 1, size=(N, 1))
        ids[pad], vals[pad] = 0, 0.0
    return ids, vals


def _batch(pattern: str, loss: str, seed: int = 0) -> SparseBatch:
    ids, vals = _entries(pattern, seed)
    rng = np.random.default_rng(seed + 1)
    label = (
        rng.poisson(1.0, N) if loss == "poisson" else rng.random(N) < 0.5
    ).astype(np.float32)
    return SparseBatch(
        ids=jnp.asarray(ids), vals=jnp.asarray(vals),
        label=jnp.asarray(label),
        offset=jnp.asarray(rng.standard_normal(N).astype(np.float32) * 0.1),
        weight=jnp.asarray(rng.random(N).astype(np.float32) + 0.5),
    )


def _with_tiles(batch: SparseBatch) -> SparseBatch:
    layout = build_block_tiles(np.asarray(batch.ids), np.asarray(batch.vals), D)
    return batch._replace(bt=device_block_tiles(layout))


def _close(got, want, rel=1e-6):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= rel * np.abs(want).max()


def _rel(pattern: str) -> float:
    """1e-6 of the largest element against autodiff; zipf's hottest
    coefficient sums ~2,000 float32 terms in two different orders, and the
    two float32 results stand that far from each other."""
    return 4e-6 if pattern == "zipf" else 1e-6


# -- the layout ----------------------------------------------------------------


@pytest.mark.parametrize("pattern", PATTERNS)
def test_layout_invariants(pattern):
    ids, vals = _entries(pattern)
    lay = build_block_tiles(ids, vals, D)
    assert (lay.kr, lay.kf) == GEOMETRY
    kr, kf = GEOMETRY
    n_rb, n_fb = lay.n_rb, lay.n_fb
    rows = TILE_SLOTS // 128
    # The storage closes with GROUP - 1 empty tiles (a copy of GROUP tiles
    # from any tile stays inside it).
    assert lay.slots.shape[0] == lay.n_tiles + bt_mod.GROUP - 1
    assert not lay.slots[lay.n_tiles:].any()
    tiles = lay.slots[:lay.n_tiles]
    idx = tiles[:, :rows].reshape(lay.n_tiles, TILE_SLOTS)
    val = tiles[:, rows:].reshape(lay.n_tiles, TILE_SLOTS).view(np.float32)
    start = lay.start.astype(np.int64)
    assert start[0] == 0 and start[-1] == lay.n_tiles
    assert (np.diff(start) >= 0).all() and start.shape == (n_rb * n_fb + 1,)
    # Every slot back in global coordinates through its cell.
    cell = np.repeat(np.arange(n_rb * n_fb), np.diff(start))[:, None]
    row = (cell // n_fb) * kr + (idx >> 16)
    col = (cell % n_fb) * kf + (idx & 0xFFFF)
    live = val != 0.0
    # Pads are (0, 0.0): index word and value word both zero.
    assert not idx[~live].any()
    # Every real entry in exactly one slot: the multiset of (row, col, value).
    want = vals != 0.0
    got = np.stack([row[live], col[live], val[live].view(np.int32)])
    ref = np.stack([
        np.broadcast_to(np.arange(N)[:, None], ids.shape)[want],
        ids[want], vals[want].view(np.int32),
    ])
    order = lambda a: a[:, np.lexsort(a[::-1])]  # noqa: E731
    np.testing.assert_array_equal(order(got), order(ref))
    # Every leaf has a leading axis: a mesh of one device places them by it.
    assert all(leaf.ndim >= 1 for leaf in jax.tree.leaves(lay))
    # Both walk orders read the same table: row-block-major visits cell
    # (o, j) = o * n_fb + j, feature-block-major (o, j) = j * n_fb + o, so
    # each covers every cell, hence every tile, once; and the grid is
    # dense, so every output block is visited whatever the ids are.
    xw = {o * n_fb + j for o in range(n_rb) for j in range(n_fb)}
    xt = {j * n_fb + o for o in range(n_fb) for j in range(n_rb)}
    assert xw == xt == set(range(n_rb * n_fb))


def test_padded_fraction_on_uniform_ids_at_the_derived_geometry():
    n, d, k = 16384, 8192, 8
    rng = np.random.default_rng(3)
    ids = rng.integers(0, d, size=(n, k), dtype=np.int32)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    lay = build_block_tiles(ids, vals, d)
    # The grid cell aims at CELL_TILES tiles of entries.
    assert lay.kr * lay.kf * k / d == bt_mod.CELL_TILES * TILE_SLOTS
    assert 0.0 <= lay.padded_fraction(n * k) <= 0.5
    assert lay.padded_fraction(n * k) < 0.3  # ~ 1 / (2 * CELL_TILES)


@pytest.mark.parametrize("n,d,e,want", [
    (4194304, 262144, 4194304 * 32, (8192, 4096)),  # the benchmark's cell
    (65536, 262144, 65536 * 32, (8192, 4096)),  # its probe: same density
    (64, 50, 640, (2048, 2048)),  # tiny: one window a side
    (1 << 20, 1 << 24, 1 << 25, (16384, 16384)),  # very sparse: clamped
])
def test_geometry_from_shapes(n, d, e, want):
    assert block_tile_geometry(n, d, e) == want


@pytest.mark.parametrize("n,d,e", [
    (8388608, 8388608, 8388608 * 16),  # 512 x 512 cells of the largest blocks
    (1 << 20, 100_000_000, 1 << 25),  # 64 x 6,104
])
def test_geometry_refuses_a_grid_over_the_tile_table(n, d, e):
    """The tile-start table is scalar-prefetched; a grid of ``MAX_CELLS``
    cells or more cannot be tiled, and the shapes alone say so."""
    assert block_tile_geometry(n, d, e) is None
    assert str(bt_mod.MAX_CELLS) in bt_mod.untileable(n, d)


def test_empty_batch_keeps_one_unread_tile_and_gives_zeros():
    lay = build_block_tiles(
        np.zeros((5, 2), np.int32), np.zeros((5, 2), np.float32), 7
    )
    assert lay.n_tiles == 1 and not lay.slots.any()
    assert not lay.start.any()  # no cell owns it
    out = block_tiles_product(
        jnp.ones(7, jnp.float32), device_block_tiles(lay), 5
    )
    np.testing.assert_array_equal(np.asarray(out), np.zeros(5, np.float32))


# -- the split -----------------------------------------------------------------


@pytest.mark.parametrize("kind", ["random", "extreme"])
def test_three_term_split_is_exact(kind):
    rng = np.random.default_rng(5)
    x = rng.standard_normal(20000).astype(np.float32)
    if kind == "extreme":
        # Exponents whose third term stays normal, both signs, and the
        # values a bfloat16 rounding carries upward.
        x = x * np.exp2(rng.integers(-60, 120, x.size)).astype(np.float32)
        x[:4] = [np.float32(1.0) - np.float32(2.0 ** -24), -3.0e38, 0.0,
                 np.float32(1.00390625) + np.float32(2.0 ** -23)]
    terms = split_bf16x3(jnp.asarray(x))
    for term in terms:  # float32 lanes, each exactly a bfloat16
        assert term.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(term.astype(jnp.bfloat16).astype(jnp.float32)),
            np.asarray(term),
        )
    x1, x2, x3 = (np.asarray(term) for term in terms)
    total = (x1 + x2) + x3
    np.testing.assert_array_equal(total.view(np.int32), x.view(np.int32))


# -- the two directions --------------------------------------------------------


@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("pattern", PATTERNS)
def test_products_match_float64(pattern, transpose):
    ids, vals = _entries(pattern, seed=7)
    bt = device_block_tiles(build_block_tiles(ids, vals, D))
    rng = np.random.default_rng(8)
    if transpose:
        u = rng.standard_normal(N).astype(np.float32)
        want = np.zeros(D)
        np.add.at(want, ids.reshape(-1),
                  (u[:, None].astype(np.float64) * vals).reshape(-1))
        got = block_tiles_product(jnp.asarray(u), bt, D, transpose=True)
    else:
        u = rng.standard_normal(D).astype(np.float32)
        want = (u[ids].astype(np.float64) * vals).sum(axis=1)
        got = block_tiles_product(jnp.asarray(u), bt, N)
    assert got.shape == want.shape and got.dtype == jnp.float32
    _close(got, want)


def _objective(loss: str, normalized: bool, batch: SparseBatch):
    norm = None
    if normalized:
        from photon_tpu.core.normalization import NormalizationContext
        from photon_tpu.core.stats import BasicStatisticalSummary

        norm = NormalizationContext.build(
            "standardization", BasicStatisticalSummary.from_batch(batch, D),
            intercept_id=0,
        )
    return GlmObjective.create(
        loss, RegularizationContext("l2", 0.5), normalization=norm
    )


@pytest.mark.parametrize("normalized", [False, True])
@pytest.mark.parametrize("loss", ["logistic", "poisson"])
@pytest.mark.parametrize("pattern", PATTERNS)
def test_value_and_grad_match_autodiff(monkeypatch, pattern, loss, normalized):
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")
    batch = _batch(pattern, loss, seed=11)
    obj = _objective(loss, normalized, batch)
    w = jnp.asarray(
        np.random.default_rng(12).standard_normal(D).astype(np.float32) * 0.1
    )
    v_ref, g_ref = jax.value_and_grad(obj.value)(w, batch)
    fast = _with_tiles(batch)
    assert obj._sparse_kernel(fast, D) == "blocked"
    v, g = obj.value_and_grad(w, fast)
    np.testing.assert_allclose(float(v), float(v_ref), rtol=1e-6)
    _close(g, g_ref, _rel(pattern))


@pytest.mark.parametrize("loss", ["logistic", "poisson"])
@pytest.mark.parametrize("pattern", PATTERNS)
def test_tron_hessian_vector_matches_autodiff(monkeypatch, pattern, loss):
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")
    batch = _batch(pattern, loss, seed=21)
    obj = _objective(loss, False, batch)
    rng = np.random.default_rng(22)
    w = jnp.asarray(rng.standard_normal(D).astype(np.float32) * 0.1)
    v = jnp.asarray(rng.standard_normal(D).astype(np.float32))
    hv_ref = jax.jvp(lambda u: jax.grad(obj.value)(u, batch), (w,), (v,))[1]
    fast = _with_tiles(batch)
    _close(obj.hessian_vector(w, v, fast), hv_ref, _rel(pattern))
    _close(obj.hvp_operator(w, fast)(v), hv_ref, _rel(pattern))


def test_normalized_hessian_vector_differentiates_around_the_kernel(monkeypatch):
    """``pallas_call`` has no JVP rule: the normalized Hv re-differentiates
    the gradient through autodiff, not through the tiles.  The batch carries
    ``bt`` and no ``fm``: what an attach builds when the probe picks
    ``blocked`` (the verdict comes first, no other layout is built)."""
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")
    batch = _batch("uniform", "logistic", seed=31)
    obj = _objective("logistic", True, batch)
    rng = np.random.default_rng(32)
    w = jnp.asarray(rng.standard_normal(D).astype(np.float32) * 0.1)
    v = jnp.asarray(rng.standard_normal(D).astype(np.float32))
    hv_ref = jax.jvp(lambda u: jax.grad(obj.value)(u, batch), (w,), (v,))[1]
    fast = _with_tiles(batch)
    assert fast.fm is None and obj._sparse_kernel(fast, D) == "blocked"
    _close(obj.hessian_vector(w, v, fast), hv_ref, rel=1e-5)
    _close(obj.hvp_operator(w, fast)(v), hv_ref, rel=1e-5)


def test_normalized_tron_fit_on_a_tiles_only_batch(monkeypatch):
    """TRON under a normalization on a batch that carries the tiles and no
    ``fm``: value and gradient run ``blocked``, every Hv differentiates the
    row-major objective, and the fit is the plain row-major fit.  Run to
    convergence: where a truncated CG stops moves with the last bits of the
    gradient (the ``fm`` route stands as far from ``autodiff`` after 2 or 3
    iterations), the optimum does not."""
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig

    batch = _batch("uniform", "logistic", seed=33)
    context = RegularizationContext("l2", 0.5)
    problem = GlmOptimizationProblem(
        _objective("logistic", True, batch),
        ProblemConfig(
            optimizer="tron", regularization=context,
            optimizer_config=OptimizerConfig(max_iterations=15),
        ),
    )
    w0 = jnp.zeros(D, jnp.float32)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "autodiff")
    want, ref = problem.run(batch, w0)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")
    jax.clear_caches()  # the pin is read when the loop is traced
    fast = _with_tiles(batch)
    assert fast.fm is None
    got, result = problem.run(fast, w0)
    assert int(result.iterations) == int(ref.iterations) < 15
    np.testing.assert_allclose(float(result.value), float(ref.value), rtol=1e-5)
    _close(got.means, want.means, rel=1e-4)


# -- attach, storage dtype, padding -------------------------------------------


def test_attach_builds_the_tiles_only_when_the_kernel_can_be_selected(monkeypatch):
    import photon_tpu.ops.sparse_grad_select as sel
    from photon_tpu import telemetry

    batch = _batch("uniform", "logistic")
    # auto on the CPU: Mosaic is not eligible, nothing is built.
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "auto")
    assert sel.layouts_wanted() == (False, False)
    assert attach_feature_major(batch, aligned_dim=D).bt is None
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    assert sel.layouts_wanted() == (True, False)
    # Pinned: built under its span, no other kernel's layout beside it.
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")
    assert sel.layouts_wanted() == (False, True)
    assert sel.aligned_layout_wanted()  # callers pass the dimension
    telemetry.process_registry().clear()
    fast = attach_feature_major(batch, aligned_dim=D)
    assert fast.bt is not None and fast.fm is not None and fast.al is None
    snap = telemetry.process_registry().snapshot()
    counters = {
        (r["name"], tuple(sorted(r["labels"].items()))): r["value"]
        for r in snap["counters"]
    }
    assert counters[("span.count", (("span", "layout.block_tiles"),))] == 1
    assert counters[("layout.h2d_bytes", (("what", "block_tiles"),))] == sum(
        leaf.nbytes for leaf in jax.tree.leaves(fast.bt)
    )
    (gauge,) = [g for g in snap["gauges"]
                if g["name"] == "valuegrad.tile_padded_fraction"]
    assert gauge["value"] == pytest.approx(fast.bt.padded_fraction(N * K))
    # No aligned_dim, or a sharded attach: no tiles.
    assert attach_feature_major(batch).bt is None
    assert attach_feature_major(
        pad_batch(batch, 2504), shards=2, aligned_dim=D
    ).bt is None
    # With auto selection eligible (a TPU) either layout could be wanted,
    # above the probe floor (under it auto mode runs autodiff whatever is
    # built): the attach takes the probe's verdict first and builds the
    # winner's layout alone.
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "auto")
    monkeypatch.setattr(sel, "_pallas_eligible", lambda: True)
    assert sel.layouts_wanted(N * K) == (False, False)
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_FLOOR", "0")
    assert sel.layouts_wanted(N * K) == (True, True)
    monkeypatch.setattr(sel, "_measure", lambda e, d, n, names: "blocked")
    monkeypatch.setattr(sel, "_CACHE", {})
    telemetry.process_registry().clear()
    only = attach_feature_major(batch, aligned_dim=D)
    assert only.bt is not None
    assert only.fm is None and only.al is None and only.al_t is None
    assert _skipped() == {"fm": 1.0, "al": 1.0}
    spans = {
        row["labels"]["span"]
        for row in telemetry.process_registry().snapshot()["counters"]
        if row["name"] == "span.count"
    }
    assert spans == {"kernels.probe", "layout.block_tiles"}


def _counts(name: str, label: str) -> dict:
    from photon_tpu.telemetry import process_registry

    return {
        row["labels"][label]: row["value"]
        for row in process_registry().snapshot()["counters"]
        if row["name"] == name
    }


def _refusals():
    return _counts("kernels.refused", "kernel")


def _skipped():
    return _counts("layout.skipped", "layout")


@pytest.mark.parametrize("mode", ["blocked", "auto"])
def test_a_batch_over_the_tile_table_goes_on_without_the_tiles(monkeypatch, mode):
    """A grid of ``MAX_CELLS`` cells or more (here: the limit lowered to this
    batch's 2 x 2) is refused from the shapes, before anything is built,
    loudly; the attach returns, and the fit runs on another kernel."""
    import photon_tpu.ops.sparse_grad_select as sel
    from photon_tpu import telemetry
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig

    batch = _batch("uniform", "logistic", seed=81)
    context = RegularizationContext("l2", 1.0)
    problem = GlmOptimizationProblem(
        GlmObjective.create("logistic_regression", context),
        ProblemConfig(
            optimizer="lbfgs", regularization=context,
            optimizer_config=OptimizerConfig(max_iterations=3),
        ),
    )
    w0 = jnp.zeros(D, jnp.float32)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "autodiff")
    want, ref = problem.run(batch, w0)

    monkeypatch.setattr(bt_mod, "MAX_CELLS", 4)
    monkeypatch.setattr(
        bt_mod, "build_block_tiles",
        lambda *a, **k: pytest.fail("refused from the shapes: nothing built"),
    )
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", mode)
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_FLOOR", "0")
    seen = []
    if mode == "auto":  # as on a TPU: both layouts wanted, the probe stubbed
        monkeypatch.setattr(sel, "_pallas_eligible", lambda: True)

        def measure(e, d, n, names):
            seen.append(names)
            return "fm"

        monkeypatch.setattr(sel, "_measure", measure)
    monkeypatch.setattr(sel, "_CACHE", {})
    telemetry.process_registry().clear()
    fast = attach_feature_major(batch, aligned_dim=D)
    assert fast.bt is None and fast.fm is not None and fast.al is None
    assert _refusals() == {"blocked": 1.0}
    # auto: the probe ran in the attach, over the kernels that could still
    # be built, and its verdict (fm) spared the aligned layout; the tiles
    # were refused, not spared.
    assert _skipped() == ({"al": 1.0} if mode == "auto" else {})
    jax.clear_caches()  # the pin is read when the loop is traced
    got, result = problem.run(fast, w0)
    # ... and the traced fit found that verdict: one measurement in all.
    assert seen == ([("autodiff", "fm", "pallas")] if mode == "auto" else [])
    assert int(result.iterations) == int(ref.iterations)
    _close(got.means, want.means, rel=1e-4)


VERDICT_LAYOUT = {"blocked": "bt", "pallas": "al", "fm": "fm", "autodiff": None}


@pytest.mark.parametrize("verdict", list(VERDICT_LAYOUT))
def test_attach_takes_the_verdict_first_and_builds_only_its_layout(
    monkeypatch, verdict
):
    """As on a TPU in auto mode (Mosaic made eligible, the floor at 0, the
    probe's timing stubbed): the attach asks for the verdict before it
    builds, the batch carries exactly the layout the winner reads, every
    other build is counted as spared, the traced fit asks again and is
    answered from the cache (one measurement in all), and it is the
    ``autodiff`` fit."""
    import photon_tpu.ops.sparse_grad_select as sel
    from photon_tpu import telemetry
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig
    from photon_tpu.data.batch import LAYOUT_FIELDS
    from photon_tpu.utils.device import kernel_metrics

    batch = _batch("uniform", "logistic", seed=101)
    context = RegularizationContext("l2", 1.0)
    problem = GlmOptimizationProblem(
        GlmObjective.create("logistic_regression", context),
        ProblemConfig(
            optimizer="lbfgs", regularization=context,
            optimizer_config=OptimizerConfig(max_iterations=3),
        ),
    )
    w0 = jnp.zeros(D, jnp.float32)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "autodiff")
    want, ref = problem.run(batch, w0)

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "auto")
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_FLOOR", "0")
    monkeypatch.setattr(sel, "_pallas_eligible", lambda: True)
    seen = []

    def measure(e, d, n, names):
        seen.append(names)
        return verdict

    monkeypatch.setattr(sel, "_measure", measure)
    monkeypatch.setattr(sel, "_CACHE", {})
    telemetry.process_registry().clear()
    fast = attach_feature_major(batch, aligned_dim=D)
    assert seen == [sel.KERNELS]  # every kernel that could be built
    carried = {f for f in LAYOUT_FIELDS if getattr(fast, f) is not None}
    kept = VERDICT_LAYOUT[verdict]
    assert carried == ({kept} if kept else set())
    assert _skipped() == {f: 1.0 for f in ("fm", "al", "bt") if f != kept}
    jax.clear_caches()  # the selection is made when the loop is traced
    got, result = problem.run(fast, w0)
    assert seen == [sel.KERNELS]  # the verdict was found, not measured again
    assert set(_counts("kernels.selected", "kernel")) == (
        {verdict} if kept else set()
    )
    assert any(row["name"] == "span.count"
               and row["labels"] == {"span": "kernels.probe"}
               and row["value"] == 1 for row in kernel_metrics())
    assert int(result.iterations) == int(ref.iterations)
    np.testing.assert_allclose(float(result.value), float(ref.value), rtol=1e-5)
    _close(got.means, want.means, rel=1e-4)


@pytest.mark.parametrize("case", ["pin", "floor", "shards", "no_dim"])
def test_attach_without_a_measurement_builds_what_it_built(monkeypatch, case):
    """No probe decides under a pin, under the floor, for a sharded attach or
    for ``attach_feature_major(batch)`` (an explicit request for ``fm``):
    nothing is measured, nothing is spared, and the batch carries ``fm`` and
    what ``layouts_wanted`` names."""
    import photon_tpu.ops.sparse_grad_select as sel
    from photon_tpu import telemetry
    from photon_tpu.data.batch import LAYOUT_FIELDS

    monkeypatch.setattr(sel, "_pallas_eligible", lambda: True)  # as on a TPU
    monkeypatch.setattr(
        sel, "_measure", lambda *a, **kw: pytest.fail("nothing to measure")
    )
    monkeypatch.setattr(sel, "_CACHE", {})
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "auto")
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_FLOOR", "0")
    batch = _batch("uniform", "logistic", seed=111)
    kwargs, want = {"aligned_dim": D}, {"fm"}
    if case == "pin":
        monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
        monkeypatch.setenv("PHOTON_SPARSE_MARGIN", "pallas")
        want = {"fm", "al", "al_t"}
    elif case == "floor":
        monkeypatch.setenv("PHOTON_SPARSE_PROBE_FLOOR", str(N * K + 1))
    elif case == "shards":
        batch, kwargs = pad_batch(batch, 2504), {"aligned_dim": D, "shards": 2}
        want = {"fm", "al"}
    else:
        kwargs = {}
    telemetry.process_registry().clear()
    fast = attach_feature_major(batch, **kwargs)
    assert {f for f in LAYOUT_FIELDS if getattr(fast, f) is not None} == want
    shards = kwargs.get("shards", 1)
    assert fast.fm.ids.shape == (shards, batch.ids.size // shards)
    assert not _skipped() and not sel._CACHE


def test_a_mesh_of_one_device_runs_the_tiles(monkeypatch):
    """``shard_batch`` on a one-device mesh is a single-block attach: the
    batch carries the tiles, every leaf is placed by its leading axis, and
    the sharded objective runs the kernel (the normalized Hv differentiates
    around it)."""
    from photon_tpu.parallel.distributed import DistributedGlmObjective
    from photon_tpu.parallel.mesh import create_mesh, shard_batch

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")
    batch = _batch("uniform", "logistic", seed=91)
    rng = np.random.default_rng(92)
    w = jnp.asarray(rng.standard_normal(D).astype(np.float32) * 0.1)
    v = jnp.asarray(rng.standard_normal(D).astype(np.float32))
    mesh = create_mesh(1)
    # A batch that already carries tiles gets them rebuilt, not passed on.
    sharded = shard_batch(_with_tiles(batch), mesh, aligned_dim=D)
    assert sharded.bt is not None and sharded.al is None
    for normalized in (False, True):
        obj = _objective("logistic", normalized, batch)
        dist = DistributedGlmObjective(obj, mesh)
        assert dist._sparse_kernel(w, sharded) == "blocked"
        v_ref, g_ref = jax.value_and_grad(obj.value)(w, batch)
        value, grad = dist.value_and_grad(w, sharded)
        np.testing.assert_allclose(float(value), float(v_ref), rtol=1e-6)
        _close(grad, g_ref)
        hv_ref = jax.jvp(lambda u: jax.grad(obj.value)(u, batch), (w,), (v,))[1]
        _close(dist.hessian_vector(w, v, sharded), hv_ref, rel=1e-5)


def test_storage_dtype_and_row_padding(monkeypatch):
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")
    batch = _batch("uniform", "logistic", seed=41)
    fast = _with_tiles(batch)
    # bfloat16 storage: the tiles hold the rounded values, as float32 words.
    low = batch_astype(fast, jnp.bfloat16)
    w = jnp.asarray(
        np.random.default_rng(42).standard_normal(D).astype(np.float32)
    )
    want = jnp.sum(
        jnp.take(w, low.ids, axis=0) * low.vals.astype(jnp.float32), axis=-1
    )
    _close(block_tiles_product(w, low.bt, N), want)
    # Row padding strips the layout (it is rebuilt at the final row count).
    assert pad_batch(fast, N + 4).bt is None and pad_batch(fast, N).bt is not None


# -- selection -----------------------------------------------------------------


def test_blocked_runs_both_directions_and_is_probed_where_mosaic_compiles(
    monkeypatch,
):
    """Pinned and spied on, as ``test_sparse_grad_kernel_selection`` does for
    ``fm`` (which kernel a pin runs over which layouts:
    tests/test_sparse_kernel_owner.py)."""
    import photon_tpu.ops.sparse_grad_select as sel

    batch = _batch("uniform", "logistic", seed=51)
    obj = GlmObjective.create("logistic")
    w = jnp.zeros(D, jnp.float32)
    calls = []
    real = bt_mod.block_tiles_product

    def spy(u, bt, out_len, transpose=False):
        calls.append((out_len, transpose))
        return real(u, bt, out_len, transpose=transpose)

    monkeypatch.setattr(bt_mod, "block_tiles_product", spy)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")
    with_fm = attach_feature_major(batch)
    assert obj._sparse_kernel(with_fm, D) == "fm"  # no tiles: next best
    obj.value_and_grad(w, with_fm)
    assert not calls
    fast = _with_tiles(batch)
    obj.value_and_grad(w, fast)
    assert calls == [(N, False), (D, True)]
    # auto: a candidate under pallas's conditions (compiled Mosaic, entries
    # above the floor), and the probe is told so.
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "auto")
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_FLOOR", "0")
    seen = []

    def measure(e, d, n, names):
        seen.append(names)
        return "blocked" if "blocked" in names else "autodiff"

    monkeypatch.setattr(sel, "_measure", measure)
    monkeypatch.setattr(sel, "_CACHE", {})
    pick = lambda b: sel.select_kernel(b, D)  # noqa: E731
    assert pick(fast) == "autodiff"  # CPU
    assert not seen
    monkeypatch.setattr(sel, "_pallas_eligible", lambda: True)
    # The attach's question, before anything is built: every kernel whose
    # layout could be built for this shape, measured once.
    assert sel.kernel_for_shape(N, K, D) == ("blocked", True)
    assert seen == [sel.KERNELS]
    # The trace-time question is over what the batch carries: that verdict
    # answers it when its winner is carried ...
    assert pick(fast) == "blocked"
    assert pick(fast._replace(fm=with_fm.fm)) == "blocked"
    assert seen == [sel.KERNELS]
    # ... and a batch that does not carry the winner is measured among what
    # it does carry, as before.
    assert pick(with_fm) == "autodiff"
    assert seen == [sel.KERNELS, ("autodiff", "fm")]
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_FLOOR", str(1 << 20))
    assert pick(fast._replace(fm=with_fm.fm)) == "autodiff"  # the floor
    assert sel.kernel_for_shape(N, K, D) == ("autodiff", False)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "fm")
    assert sel.kernel_for_shape(N, K, D) == ("fm", False)  # the pin
    assert len(seen) == 2


def test_probe_refuses_a_wrong_blocked_kernel_loudly(monkeypatch):
    import photon_tpu.ops.sparse_grad_select as sel
    from photon_tpu.telemetry import process_registry

    refused = _refusals
    real = bt_mod.block_tiles_product

    def garbage(u, bt, out_len, transpose=False):
        out = real(u, bt, out_len, transpose=transpose)
        return out + 1.0 if transpose else out

    def garbage_forward(u, bt, out_len, transpose=False):
        out = real(u, bt, out_len, transpose=transpose)
        return out if transpose else out + 1.0

    for wrong in (garbage, garbage_forward):
        process_registry().clear()
        monkeypatch.setattr(bt_mod, "block_tiles_product", wrong)
        choice = sel._measure(1 << 12, 256, 256, ("autodiff", "fm", "blocked"))
        assert choice in ("fm", "autodiff")
        assert refused() == {"blocked": 1.0}
    process_registry().clear()
    monkeypatch.setattr(bt_mod, "block_tiles_product", real)
    assert sel.kernel_report(1 << 12, 256, 256, kernels=("blocked",)) == {
        "blocked": "compiled+parity ok"
    }
    assert not refused()


def test_probe_times_an_evaluation_margins_and_gradient(monkeypatch):
    """Selecting ``blocked`` replaces the forward too, so the probe ranks
    every candidate on ``Xw`` + ``Xᵀdz``: the timed program holds the
    candidate's own forward, or the row-major gather where it has none."""
    import photon_tpu.ops.sparse_grad_select as sel

    traced, programs = [], []
    real, real_jit = bt_mod.block_tiles_product, jax.jit

    def spy(u, bt, out_len, transpose=False):
        if isinstance(u, jax.core.Tracer):  # inside a timed program
            traced.append(transpose)
        return real(u, bt, out_len, transpose=transpose)

    def jit_spy(fn, *args, **kwargs):
        programs.append(fn)
        return real_jit(fn, *args, **kwargs)

    monkeypatch.setattr(bt_mod, "block_tiles_product", spy)
    monkeypatch.setattr(jax, "jit", jit_spy)
    sel._measure(1 << 12, 256, 256, ("autodiff", "fm", "blocked"))
    monkeypatch.setattr(jax, "jit", real_jit)
    assert sorted(traced) == [False, True]
    p = sel._probe_problem(1 << 12, 256, 256)
    args = [jnp.asarray(a) for a in (p.w, p.dz, p.ids, p.vals)]
    want = p.ref_xw.sum() + p.ref.sum()
    assert len(programs) == 3  # autodiff, fm, blocked: each both directions
    for fn in programs:
        np.testing.assert_allclose(float(fn(*args)), want, rtol=1e-4, atol=1e-2)


def test_layer_metric_reader_reads_the_span_or_nothing():
    """``layout.block_tiles_s`` (BENCHMARK.json): the span's seconds, and
    nothing, not 0, from a program that has no such span (the parent)."""
    import importlib.util
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        spec = importlib.util.spec_from_file_location(
            "bench_layout_block_tiles_s", os.path.join(
                root, "benchmarks", "layer_metrics", "layout.block_tiles_s.py"
            ),
        )
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
    finally:
        sys.path.remove(root)
    rows = [
        {"name": "span.seconds", "labels": {"span": "layout.block_tiles"},
         "value": 7.5},
        {"name": "span.seconds", "labels": {"span": "layout.feature_major"},
         "value": 36.0},
    ]
    assert reader.read({"counters": {"counters": rows}}) == 7.5
    assert reader.read({"counters": {"counters": rows[1:]}}) is None
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == "layout.block_tiles_s"]
    assert entry["moves"] == "setup_s" and entry["workloads"] == ["glm_sparse_fit"]


def test_the_two_directions_carry_their_scopes(monkeypatch):
    """``blocked/xw`` under ``valuegrad/margins`` and ``blocked/xtdz`` under
    ``valuegrad/grad``: the names a trace is read by."""
    import re

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")
    batch = _with_tiles(_batch("uniform", "logistic", seed=61))
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 1.0))
    text = jax.jit(obj.value_and_grad).lower(
        jnp.zeros(D, jnp.float32), batch
    ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    assert any("valuegrad/margins/blocked/xw" in name for name in names)
    assert any("valuegrad/grad/blocked/xtdz" in name for name in names)


@pytest.mark.parametrize("optimizer,reg", [
    ("lbfgs", "l2"), ("owlqn", "l1"), ("tron", "l2"),
])
def test_whole_fits_match_autodiff(monkeypatch, optimizer, reg):
    """What ``drivers/train._run_resident`` runs: the jitted optimizer loop
    over a batch that carries the tiles, each optimizer against its own
    autodiff fit."""
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig

    batch = _batch("uniform", "logistic", seed=71)
    context = RegularizationContext(reg, 1.0)
    problem = GlmOptimizationProblem(
        GlmObjective.create("logistic_regression", context),
        ProblemConfig(
            optimizer=optimizer, regularization=context,
            optimizer_config=OptimizerConfig(max_iterations=3),
        ),
    )
    w0 = jnp.zeros(D, jnp.float32)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "autodiff")
    want, ref = problem.run(batch, w0)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")
    jax.clear_caches()  # the pin is read when the loop is traced
    got, result = problem.run(_with_tiles(batch), w0)
    assert int(result.iterations) == int(ref.iterations)
    np.testing.assert_allclose(float(result.value), float(ref.value), rtol=1e-5)
    _close(got.means, want.means, rel=1e-4)
