"""The sparse-fixed-effect GAME cell (``game_sparse_fit``) at its rehearsal
sizes on the host: the fit through ``GameEstimator`` with a ``SparseShard``
for ``global`` against the plain reference, the control and the planted
fault against it, the sparse reference against the dense one, the
generator's seed rule, what the program counts for the cell's readers, and
the readers on a recorded reduction.  No number here is a device number."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402

SEED = 2 ** 31 + 36
CELL = "game_sparse_fit"
READERS = ("fixed.fit_device_s", "fixed.score_device_s",
           "fixed.valuegrad_roofline", "fixed.score_roofline")

# The limits `correct` is decided on (benchmarks/traffic/descent2_fits_sparse
# .json), each between its two readings on the chip at the cell's size
# (PERF.md section 2; my chip run, PR 36: 6 seeds of the program, 3 of the
# bfloat16 control and of the half batch).
LIMITS = {
    # validation logistic loss after each descent iteration, relative: the
    # mean in float64 over the same rows on both sides; what is left is the
    # distance between 15 Newton steps at default matmul precision and the
    # optimum (read 1.17e-5 on every seed; control 1.93e-5; half 0.40)
    "val_loss_gap": 1e-4,
    # validation AUC, absolute: a rank statistic, moved only by scores that
    # swap order (read 1.47e-6; control 4.70e-6; half 0.059)
    "val_auc_gap": 2e-5,
    # the fixed effect's training objective at the end of its last fit,
    # relative: its offsets are the random effects' scores, so it carries
    # their distance (read 1.58e-5; control 2.6e-6; half 0.47)
    "fixed_loss_gap": 1e-4,
    # gap of norms, worst of the three leaves: insensitive to direction
    # (read 4.61e-5; control 4.0e-6; half 0.14)
    "coef_norm_gap": 3e-4,
    # norm of the difference, worst leaf: the one number the control fails
    # (read 1.675e-4 on every seed, the random leaves' as in game_fit;
    # control 2.205e-3; half 0.76): the geometric middle of the two
    "coef_diff": 6e-4,
}
# A dense matrix written sparsely (ids 0..d-1 in every row): one evaluation
# of the fixed effect is the same sums in another order ...
SPARSE_AGAINST_DENSE = 1e-6
# ... and a whole fit on top of it is two float32 L-BFGS runs that stop on a
# 1e-7 change of the objective and Newton solves that stop where float32 no
# longer tells the objective's values apart: as for the blocked reference
# (tests/test_game_mesh_cell.py) a fraction of each of the cell's limits: half,
# because the AUC of 780 validation rows moves in steps of one swapped pair,
# 6.5e-6 (read: 5.7e-5, 3.6e-6, 1.6e-6, 6.5e-6 and under 1e-5).
SPARSE_FIT_AGAINST_DENSE_FIT = 1 / 2


@pytest.fixture(scope="module")
def cell():
    spec = harness.load_cell(CELL)
    config = dict(spec["config"], sizes=dict(
        spec["config"]["sizes"], **spec["config"]["rehearsal_sizes"]))
    runner = harness.load_module(spec["runner_dir"], spec["traffic"]["runner"])
    return spec, config, runner


@pytest.fixture(scope="module")
def fits(cell):
    """Two fits of the program on one estimator, the second's outputs and
    counts, then the reference, its bfloat16 control and the half batch."""
    spec, config, runner = cell
    assert spec["traffic"]["limits"] == LIMITS
    with pytest.MonkeyPatch.context() as patch:
        # The cell sets no PHOTON_* variable; the suite pins `fm`.
        patch.delenv("PHOTON_SPARSE_GRAD", raising=False)
        state = runner.setup(config, spec["traffic"], SEED, harness.Clock())
        steps = [runner.step(state), runner.step(state)]
    out = {
        "steps": steps, "produced": runner.produced(state),
        "counters": runner.counters(state),
        "work": runner.work(state, steps), "data": state.data,
        "floor": runner.floor(state, steps, {"flops_per_s": 197e12,
                                             "hbm_bytes_per_s": 819e9}),
    }
    runner.release(state)
    out["reference"] = runner.reference(state)
    out["control"] = runner.reference(state, lowp=True)
    n = state.data.fit_rows
    out["half"] = runner.reference(
        state, weight=np.where(np.arange(n) % 2 == 0, 2.0, 0.0).astype(
            np.float32))
    return out


@pytest.mark.parametrize("number", sorted(LIMITS))
def test_program_is_within_the_cells_limits_of_the_reference(
        cell, fits, number):
    numbers = cell[2].compare(fits["produced"], fits["reference"])
    assert np.isfinite(numbers[number])
    assert numbers[number] <= LIMITS[number], numbers


@pytest.mark.parametrize("fault", ["control", "half"])
def test_control_and_half_batch_fail_a_limit(cell, fits, fault):
    """The reference in bfloat16 and the reference on half the rows, put in
    the program's place, are each over at least one limit."""
    numbers = cell[2].compare(fits[fault], fits["reference"])
    assert any(numbers[k] > LIMITS[k] for k in LIMITS), numbers


@pytest.fixture(scope="module")
def dense_written_sparsely():
    """``game_fit``'s rehearsal data set, and the same with its dense fixed
    shard as padded-COO rows (ids 0..d-1 in every row)."""
    from benchmarks import generate, generate_game_sparse

    spec = harness.load_cell("game_fit")
    sizes = dict(spec["config"]["sizes"], **spec["config"]["rehearsal_sizes"])
    data = generate.game(sizes, SEED)
    dim = int(sizes["fixed_dim"])

    def sparsely(split):
        return generate_game_sparse.SparseGameSplit(
            ids_fixed=np.tile(np.arange(dim, dtype=np.int32),
                              (split.rows, 1)),
            vals_fixed=split.x_fixed, x_random=split.x_random,
            entity_ids=split.entity_ids, label=split.label,
        )

    sparse = generate_game_sparse.SparseGameData(
        train=sparsely(data.train), validation=sparsely(data.validation),
        n_entities=data.n_entities, coordinates=data.coordinates,
        fixed_dim=dim,
    )
    fit = spec["traffic"]["fit"]
    return data, sparse, {
        "l2": float(fit["reg_weight"]),
        "descent_iterations": int(fit["descent_iterations"]),
        "fixed_max_iterations": int(fit["fixed_max_iterations"]),
        "tolerance": float(fit["tolerance"]),
        "gradient_tolerance": float(fit["gradient_tolerance"]),
    }


@pytest.mark.parametrize("lowp", [False, True])
def test_sparse_evaluation_equals_the_dense_one_on_a_dense_matrix(
        dense_written_sparsely, lowp):
    """What the fixed effect hands the descent, one evaluation: objective,
    gradient and scores of ``game_sparse`` (two row blocks) against
    ``game._fixed_value_grad`` / ``x @ w``, the control's rounding too."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import game, game_sparse
    from benchmarks.reference.common import round_to

    data, sparse, _ = dense_written_sparsely
    rng = np.random.default_rng(SEED)
    n, dim = data.train.x_fixed.shape
    w = jnp.asarray(rng.standard_normal(dim) * 0.3, jnp.float32)
    offset = jnp.asarray(rng.standard_normal(n), jnp.float32)
    weight = jnp.asarray(rng.integers(0, 3, n), jnp.float32)
    y, l2 = jnp.asarray(data.train.label), jnp.float32(1.0)
    with jax.default_matmul_precision("highest"):
        x = round_to(jnp.asarray(data.train.x_fixed), lowp)
        value, grad = game._fixed_value_grad(
            w, x, y, offset, weight, l2, lowp=lowp)
        scores = x @ round_to(w, lowp)
        half = n // 2
        got_value, got_grad = 0.5 * l2 * jnp.dot(w, w), l2 * w
        got_scores = []
        for cut in (slice(0, half), slice(half, n)):
            ids = jnp.asarray(sparse.train.ids_fixed[cut])
            vals = round_to(jnp.asarray(sparse.train.vals_fixed[cut]), lowp)
            v, g = game_sparse._block_value_grad(
                w, ids, vals, y[cut], offset[cut], weight[cut], lowp=lowp)
            got_value, got_grad = got_value + v, got_grad + g
            got_scores.append(
                game_sparse._block_margins(w, ids, vals, lowp=lowp))
    norm = np.linalg.norm
    assert abs(float(got_value) - float(value)) <= (
        SPARSE_AGAINST_DENSE * abs(float(value)))
    assert norm(got_grad - grad) <= SPARSE_AGAINST_DENSE * norm(grad)
    assert norm(jnp.concatenate(got_scores) - scores) <= (
        SPARSE_AGAINST_DENSE * norm(scores))


@pytest.fixture(scope="module")
def sparse_fit_against_dense_fit(cell, dense_written_sparsely):
    from benchmarks.reference import game, game_sparse

    data, sparse, ref_spec = dense_written_sparsely
    return cell[2].compare(game_sparse.fit(sparse, ref_spec, row_block=1000),
                           game.fit(data, ref_spec))


@pytest.mark.parametrize("number", sorted(LIMITS))
def test_sparse_reference_fit_equals_the_dense_one_on_a_dense_matrix(
        sparse_fit_against_dense_fit, number):
    numbers = sparse_fit_against_dense_fit
    assert numbers[number] <= (
        SPARSE_FIT_AGAINST_DENSE_FIT * LIMITS[number]), numbers


def test_generator_gives_one_data_set_whatever_the_seed(cell):
    """``--seed`` renames the entities of every coordinate and reorders the
    validation rows; the training rows, every value and every label are the
    configuration's (``structure_seed``)."""
    from benchmarks import generate_game_sparse

    _, config, _ = cell
    a = generate_game_sparse.game_sparse(config["sizes"], SEED)
    b = generate_game_sparse.game_sparse(config["sizes"], SEED + 1)
    sizes = config["sizes"]
    stride = sizes["fixed_dim"] // sizes["fixed_nnz_per_row"]
    for data in (a, b):
        ids = data.train.ids_fixed
        assert ids.shape[1] == sizes["fixed_nnz_per_row"]
        assert (ids // stride == np.arange(ids.shape[1])).all()
        assert np.abs(data.train.vals_fixed).max() <= 9.0
    for name in ("ids_fixed", "vals_fixed", "label"):
        np.testing.assert_array_equal(
            getattr(a.train, name), getattr(b.train, name))
    renamed = False
    for name in a.coordinates:
        np.testing.assert_array_equal(
            a.train.x_random[name], b.train.x_random[name])
        ids_a, ids_b = a.train.entity_ids[name], b.train.entity_ids[name]
        renamed |= bool((ids_a != ids_b).any())
        # one name of b for every name of a, and the other way round
        pairs = np.unique(np.stack([ids_a, ids_b]), axis=1)
        assert len(np.unique(pairs[0])) == len(np.unique(pairs[1])) \
            == pairs.shape[1]
    assert renamed

    def rows(split):
        """The validation rows as a sorted table (names left out)."""
        table = np.concatenate(
            [split.ids_fixed.astype(np.float64), split.vals_fixed,
             *(split.x_random[n] for n in a.coordinates),
             split.label[:, None]], axis=1)
        return table[np.lexsort(table.T[::-1])]

    assert a.validation.rows == b.validation.rows
    assert (a.validation.vals_fixed != b.validation.vals_fixed).any()
    np.testing.assert_array_equal(rows(a.validation), rows(b.validation))


def test_load_cell_resolves_the_new_entries():
    spec = harness.load_cell(CELL)
    assert spec["cell"] == dict(
        spec["cell"], config="game_config5_sparse_share",
        traffic="descent2_fits_sparse", chips=1)
    assert spec["config"]["reduced"] == ["entities_per_coordinate"]
    assert spec["config"]["sizes"]["fixed_dim"] == 262144
    assert spec["config"]["sizes"]["fixed_nnz_per_row"] == 32
    assert spec["traffic"]["runner"] == "game_sparse_fit"
    reported = [m["name"] for m in spec["per_layer"]]
    assert [n for n in reported if n in READERS] == list(READERS)
    assert {"setup.data_s", "setup.layout_s", "setup.compiles", "fit.mfu_pct",
            "device.idle_pct", "device.peak_hbm_gib"} <= set(reported)
    assert [m["name"] for m in spec["end_to_end"]] == ["fit_s", "setup_s"]
    for name in READERS:  # the other cells do not report them
        assert name not in [
            m["name"] for m in harness.load_cell("game_fit")["per_layer"]]
    dense = harness.load_cell("game_fit")["traffic"]
    assert spec["traffic"]["fit"] == dense["fit"]


def test_program_counts_what_the_readers_read(fits):
    """``fixed_effect.layout`` once a layout, ``score.sparse_entries`` a
    score from the shapes: after each of the fixed effect's two updates the
    training and the validation rows, ``nnz`` entries each."""
    data = fits["data"]
    nnz = data.train.ids_fixed.shape[1]
    rows = {
        (c["name"], tuple(sorted(c["labels"].items()))): c["value"]
        for c in fits["counters"]["counters"]
    }
    assert rows["fixed_effect.layout", (
        ("coordinate", "fixed"), ("kernel", "autodiff"), ("kind", "sparse"),
    )] == 1  # two fits, one layout; under the probe floor: autodiff
    a_fit = 2 * (data.train.rows + data.validation.rows) * nnz
    assert [s["sparse_entries"] for s in fits["steps"]] == [a_fit, a_fit]
    assert rows["score.sparse_entries", (("coordinate", "fixed"),)] == 2 * a_fit
    assert fits["work"]["sparse_entries"] == a_fit
    assert fits["work"]["fixed_evaluations"] == np.mean(
        [s["fixed_evaluations"] for s in fits["steps"]])
    assert fits["work"]["fixed_evaluations"] >= (
        fits["work"]["fixed_iterations"] + fits["work"]["fixed_fits"])


def test_carried_kernel_tells_the_verdict_without_measuring(monkeypatch):
    """The label of ``fixed_effect.layout``: a pin (its nearest carried
    layout), the probe floor, a verdict the probe has cached, and
    ``unprobed`` where only a probe not yet run could say; never a probe."""
    from photon_tpu.data.batch import attach_feature_major, sparse_batch_from_rows
    from photon_tpu.ops import sparse_grad_select as sel

    rng = np.random.default_rng(0)
    rows = [(rng.choice(64, 4, replace=False), rng.standard_normal(4))
            for _ in range(32)]
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "fm")
    batch = attach_feature_major(sparse_batch_from_rows(
        rows, rng.integers(0, 2, 32).astype(np.float32)))
    monkeypatch.setattr(
        sel, "_measure", lambda *a: pytest.fail("carried_kernel measured"))
    assert sel.carried_kernel(batch, 64) == "fm"
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "blocked")  # carries no tiles
    assert sel.carried_kernel(batch, 64) == "fm"
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "auto")
    assert sel.carried_kernel(batch, 64) == "autodiff"  # under the floor
    monkeypatch.setenv("PHOTON_SPARSE_PROBE_FLOOR", "0")
    monkeypatch.setattr(sel, "_CACHE", {})
    assert sel.carried_kernel(batch, 64) == "unprobed"
    assert sel._CACHE == {}
    import jax

    where = (jax.default_backend(), sel._bucket(32 * 4), sel._bucket(64))
    sel._CACHE[where + (("autodiff", "fm"),)] = "fm"
    assert sel.carried_kernel(batch, 64) == "fm"


def test_floor_swaps_the_fixed_effects_terms(fits):
    from benchmarks import rooflines, rooflines_game_sparse

    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    work, floor = fits["work"], fits["floor"]
    assert set(floor["phases"]) == {
        "fixed_valuegrad", "fixed_scoring", "entity_solves", "scoring"}
    entries = work["rows"] * work["fixed_nnz"]
    assert floor["fixed_valuegrad_seconds"] == pytest.approx(
        work["fixed_evaluations"] * rooflines.bytes_valuegrad(
            entries, work["fixed_dim"], work["rows"]) / 819e9)
    scored = work["sparse_entries"]
    assert floor["fixed_scoring_seconds"] == pytest.approx(
        rooflines_game_sparse.bytes_score(scored, scored / work["fixed_nnz"])
        / 819e9)
    rest = rooflines.game_fit_floor(
        dict(work, fixed_dim=0, fixed_iterations=0, fixed_fits=0), peak)
    assert floor["seconds"] == pytest.approx(
        rest["seconds"] + floor["fixed_valuegrad_seconds"]
        + floor["fixed_scoring_seconds"])


# -- the readers, on the reduction of the cell's traced run on the chip ---------

# The traced fit's reduction on the chip (my chip run, PR 36, seed
# 2147486601): `by_module` as `trace_reduce` gave it, the runner's `work`
# and the floor's two keys the readers take; the values the result line
# of that run carried are what each reader has to give back.
RECORDED = {
    "counters": {"counters": [], "gauges": []},
    "steps": [{}, {}, {}],
    "traced_steps": 1,
    "trace": {"busy_s": 6.107476979, "window_s": 6.137411915, "by_module": [
        ["jit_glm_fit_lbfgs(6463641400443048546)", 2.889216917],
        ["jit_score_fixed(16230584329023609488)", 1.577125908],
        ["jit_entity_solve_newton(6202485998680763925)", 0.401976657],
        ["jit_score_fixed(16675127394422426437)", 0.393908269],
        ["jit_entity_solve_newton(13098442124675224629)", 0.390380458],
        ["jit_gather_bucket_offsets(10239309709549368143)", 0.085968175],
        ["jit_gather_bucket_offsets(4674104613454381822)", 0.085930143],
        ["jit_entity_solve_newton(12822191343392061834)", 0.049454998],
        ["jit_entity_solve_newton(10660404179094771883)", 0.04751157],
        ["jit_entity_solve_newton(3083334432458765886)", 0.031120394],
    ]},
    "work": {"rows": 3192843, "validation_rows": 798211, "fixed_nnz": 32,
             "sparse_entries": 255427456.0},
    "floor": {"fixed_valuegrad_seconds": 31 * 1700689628 / 819e9,
              "hbm_bytes_per_s": 819e9},
}


def _read(metric, run):
    layer_dir = os.path.join(ROOT, "benchmarks", "layer_metrics")
    return harness.load_module(layer_dir, metric).read(run)


@pytest.mark.parametrize("metric, wanted", [
    ("fixed.fit_device_s", 2.889216917),
    ("fixed.score_device_s", 1.971034177),
    ("fixed.valuegrad_roofline", 2.2280385769420397),
    ("fixed.score_roofline", 0.12856208021412405),
])
def test_reader_on_the_recorded_reduction(metric, wanted):
    assert _read(metric, RECORDED) == pytest.approx(wanted, rel=1e-9)
    # A shape of the score cut from by_module's ten, or a fit with no
    # trace: absent, not smaller.
    cut = dict(RECORDED, trace=dict(
        RECORDED["trace"], by_module=RECORDED["trace"]["by_module"][:2]))
    if "score" in metric:
        assert _read(metric, cut) is None
    assert _read(metric, dict(RECORDED, trace=None)) is None


def test_score_roofline_reads_nothing_without_the_programs_counter():
    """The parent of the PR that added ``score.sparse_entries``: the runner
    leaves the count out of ``work`` and the reader returns nothing; the
    other three read what the parent publishes too."""
    work = {k: v for k, v in RECORDED["work"].items()
            if k != "sparse_entries"}
    parent = dict(RECORDED, work=work)
    assert _read("fixed.score_roofline", parent) is None
    for metric in READERS[:3]:
        assert _read(metric, parent) is not None
