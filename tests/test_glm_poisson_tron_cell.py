"""The Poisson + TRON cell (``glm_poisson_tron_fit``) at its rehearsal sizes
on the host: the fit through ``GlmOptimizationProblem.run`` with
``optimizer="tron"`` against the plain reference (``blocked`` in interpret
mode and autodiff), the control and the half batch against it, the
reference against a dense Newton solve, the generator's seed rule, what the
program counts for the cell's readers, and the readers on a recorded
reduction.  No number here is a device number."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402

SEED = 2 ** 31 + 40
CELL = "glm_poisson_tron_fit"
READERS = ("tron.cg_iterations_per_fit", "tron.passes_roofline")
KERNELS = ("blocked", "autodiff")
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cell():
    spec = harness.load_cell(CELL)
    config = dict(spec["config"], sizes=dict(
        spec["config"]["sizes"], **spec["config"]["rehearsal_sizes"]))
    runner = harness.load_module(spec["runner_dir"], spec["traffic"]["runner"])
    return spec, config, runner


@pytest.fixture(scope="module", params=KERNELS)
def fits(cell, request):
    """Two fits of the program with the kernel pinned, the second's outputs,
    the process registry's counts of both, and the reference."""
    from photon_tpu import telemetry

    spec, config, runner = cell
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("PHOTON_SPARSE_GRAD", request.param)
        state = runner.setup(config, spec["traffic"], SEED, harness.Clock())
        telemetry.process_registry().clear()
        steps = [runner.step(state), runner.step(state)]
        out = {
            "kernel": request.param, "steps": steps,
            "produced": runner.produced(state),
            "counters": runner.counters(state),
            "floor": runner.floor(state, steps, PEAK),
            "work": runner.work(state, steps),
        }
        runner.release(state)
    out["reference"] = runner.reference(state)
    out["state"] = state
    return out


@pytest.fixture(scope="module")
def faults(cell, fits):
    """The reference in bfloat16 and on every other row (the rest weighted
    double), each put in the program's place."""
    runner, state = cell[2], fits["state"]
    n = state.data.fit_rows
    return {
        "control": runner.reference(state, lowp=True),
        "half": runner.reference(state, weight=np.where(
            np.arange(n) % 2 == 0, 2.0, 0.0).astype(np.float32)),
    }


@pytest.mark.parametrize("number", ["loss_gap", "grad0_gap", "dw_norm_gap",
                                    "dw_diff", "cg_gap"])
def test_program_is_within_the_cells_limits_of_the_reference(
        cell, fits, number):
    limits = cell[0]["traffic"]["limits"]
    numbers = cell[2].compare(fits["produced"], fits["reference"])
    assert np.isfinite(numbers[number])
    assert numbers[number] <= limits[number], (fits["kernel"], numbers)


@pytest.mark.parametrize("fault", ["control", "half"])
def test_control_and_half_batch_fail_a_limit(cell, fits, faults, fault):
    limits = cell[0]["traffic"]["limits"]
    numbers = cell[2].compare(faults[fault], fits["reference"])
    assert any(numbers[k] > limits[k] for k in limits), numbers


def test_program_counts_cg_steps_and_rejections(fits):
    """Each fit reports the reference's CG count and no rejected trial; the
    process registry holds the two fits' sums (deferred, exact)."""
    want = fits["reference"]
    assert want["rejections"] == 0 and want["iterations"] == 3
    for step in fits["steps"]:
        assert step == {"iterations": 3, "evaluations": 4,
                        "cg_iterations": want["cg_iterations"],
                        "rejections": 0}
    assert want["cg_iterations"] == 24  # every solve runs to the cap
    rows = {
        (c["name"], tuple(sorted(c["labels"].items()))): c["value"]
        for c in fits["counters"]["counters"]
    }
    assert rows["optimizer.cg_iterations", ()] == 2 * 24
    assert rows["optimizer.trust_region_rejections", ()] == 0
    assert rows["optimizer.evaluations", ()] == 2 * 4
    assert rows["kernels.selected", (("kernel", fits["kernel"]),)] >= 1


def test_floor_counts_evaluations_and_cg_steps(fits):
    from benchmarks import rooflines

    work, floor = fits["work"], fits["floor"]
    assert (work["evaluations"], work["cg_iterations"]) == (4, 24)
    # RECORDED's floor below: the cell's own shapes.
    assert rooflines.bytes_valuegrad(1 << 28, 1 << 18, 1 << 23) == 4464836608
    e, d, n = work["entries"], work["dim"], work["rows"]
    want = (4 * rooflines.bytes_valuegrad(e, d, n)
            + 24 * (rooflines.bytes_valuegrad(e, d, n) + 4 * n)) / 819e9
    assert floor["passes_seconds"] == pytest.approx(want, rel=1e-12)
    assert floor["seconds"] == floor["passes_seconds"]
    assert floor["phases"] == {"valuegrad": "hbm", "hessian_vector": "hbm"}


def test_reference_against_a_dense_newton_solve():
    """A dense matrix written sparsely (ids 0..d-1 in every row): one
    Hessian-vector product against ``Xᵀ diag(exp(Xw)) X v + v`` in float64,
    and TRON run to convergence against Newton's method in float64."""
    import jax.numpy as jnp

    from benchmarks.generate import SparseGlmData
    from benchmarks.reference import glm_tron

    rng = np.random.default_rng(SEED)
    n, d = 512, 12
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.poisson(np.exp(x @ (0.2 * rng.standard_normal(d)))).astype(
        np.float32)
    data = SparseGlmData(ids=np.tile(np.arange(d, dtype=np.int32), (n, 1)),
                         vals=x, label=y, dim=d)
    x64, y64 = x.astype(np.float64), y.astype(np.float64)

    def grad_hess(w):
        rate = np.exp(x64 @ w)
        return (x64.T @ (rate - y64) + w,
                x64.T @ (rate[:, None] * x64) + np.eye(d))

    w_star = np.zeros(d)
    for _ in range(30):
        g, h = grad_hess(w_star)
        w_star -= np.linalg.solve(h, g)
    # CG to 1e-6 is Newton's step; the gradient tolerance stops it before
    # float32 stalls the objective (|g| / |g0| 1.7e-4 after 4 steps, when
    # the next reduction is under the objective's last digit), which leaves
    # w about 1e-4 from the optimum.
    out = glm_tron.fit(data, 1.0, 20, d, 1e-6, 1e-12, 1e-3)
    assert out["grad_norms"][-1] <= 1e-3 * out["grad_norms"][0]
    assert out["rejections"] == 0 and out["cg_iterations"] >= out["iterations"]
    assert np.linalg.norm(out["w"] - w_star) <= 1e-3 * np.linalg.norm(w_star)
    w = 0.3 * rng.standard_normal(d).astype(np.float32)
    v = rng.standard_normal(d).astype(np.float32)
    curvature = glm_tron._block_curvature(
        jnp.asarray(w), data.ids, data.vals, jnp.ones(n, jnp.float32))
    got = 1.0 * v + glm_tron._block_hv(
        jnp.asarray(v), data.ids, data.vals, curvature)
    want = grad_hess(w.astype(np.float64))[1] @ v
    assert np.linalg.norm(got - want) <= 1e-5 * np.linalg.norm(want)


def test_generator_gives_one_data_set_whatever_the_seed(cell):
    """``--seed`` reorders the rows; the rows themselves (ids, values, count
    labels) are the configuration's (``structure_seed``), and the feature
    matrix is ``glm_sparse_1b_share``'s draw."""
    from benchmarks import generate, generate_glm_poisson

    sizes = cell[1]["sizes"]
    a = generate_glm_poisson.glm_poisson(sizes, SEED)
    b = generate_glm_poisson.glm_poisson(sizes, SEED + 1)
    stride = sizes["dim"] // sizes["nnz_per_row"]
    assert (a.ids // stride == np.arange(sizes["nnz_per_row"])).all()
    assert (a.label == np.round(a.label)).all() and a.label.min() >= 0
    assert 1.0 < a.label.mean() < 1.4  # exp(Var(z) / 2) with Var(z) ~ 0.32
    assert (a.ids != b.ids).any()

    def rows(data):
        table = np.concatenate([data.ids.astype(np.float64), data.vals,
                                data.label[:, None]], axis=1)
        return table[np.lexsort(table.T[::-1])]

    np.testing.assert_array_equal(rows(a), rows(b))
    logistic = generate.sparse_glm(sizes, SEED)
    np.testing.assert_array_equal(logistic.ids, a.ids)
    np.testing.assert_array_equal(logistic.vals, a.vals)


def test_load_cell_resolves_the_new_entries():
    spec = harness.load_cell(CELL)
    assert spec["cell"] == dict(
        spec["cell"], config="glm_poisson_tron_share",
        traffic="tron3_fits", chips=1)
    config, fit = spec["config"], spec["traffic"]["fit"]
    assert config["reduced"] == ["rows"] and config["task"] == (
        "poisson_regression")
    assert config["sizes"] == dict(config["sizes"], rows=1 << 23,
                                   dim=262144, nnz_per_row=32)
    assert fit == dict(fit, optimizer="tron", max_iterations=3,
                       cg_max_iterations=8, cg_tolerance=1e-12,
                       tolerance=1e-12, gradient_tolerance=1e-12)
    reported = [m["name"] for m in spec["per_layer"]]
    assert [n for n in reported if n in READERS] == list(READERS)
    assert {"setup.data_s", "setup.layout_s", "setup.compiles", "fit.mfu_pct",
            "device.idle_pct", "device.peak_hbm_gib"} <= set(reported)
    assert [m["name"] for m in spec["end_to_end"]] == ["fit_s", "setup_s"]
    for other in ("glm_sparse_fit", "game_sparse_fit"):
        assert not set(READERS) & {
            m["name"] for m in harness.load_cell(other)["per_layer"]}


def test_runner_refuses_a_program_that_does_not_count_cg_steps(
        cell, monkeypatch):
    """The parent of this cell's PR: no ``trust_region_rejections`` on the
    result, so no CG count to compare; set-up exits before any data."""
    import collections

    from photon_tpu.core import optimizers

    spec, config, runner = cell
    monkeypatch.setattr(optimizers, "OptimizerResult", collections.namedtuple(
        "OptimizerResult", ["w", "cg_iterations"]))
    clock = harness.Clock()
    with pytest.raises(SystemExit, match="reports no CG count"):
        runner.setup(config, spec["traffic"], SEED, clock)
    assert clock.seconds == {}


# -- the readers ------------------------------------------------------------------

# The cell's traced run on the chip (my chip run, PR 40, seed 2147488001):
# the process counter after the warm-up fit and three timed fits, the trace
# reduction's busy time, and the floor of the program's counts (4
# evaluations and 24 CG steps a fit at 2^23 rows x 32 nnz, d 262,144); the
# values its result line carried are what each reader has to give back.
RECORDED = {
    "counters": {"counters": [
        {"name": "optimizer.cg_iterations", "labels": {}, "value": 96.0},
        {"name": "optimizer.cg_iterations",
         "labels": {"coordinate": "fixed"}, "value": 7.0},
        {"name": "optimizer.evaluations", "labels": {}, "value": 16.0},
    ], "gauges": []},
    "steps": [{}, {}, {}],
    "traced_steps": 1,
    "trace": {"busy_s": 7.251826412, "window_s": 7.251828463, "by_module": [
        ["jit_glm_fit_tron(15545399695023498744)", 7.251832798]]},
    "floor": {"passes_seconds": (4 * 4464836608 + 24 * 4498391040) / 819e9},
}


def _read(metric, run):
    layer_dir = os.path.join(ROOT, "benchmarks", "layer_metrics")
    return harness.load_module(layer_dir, metric).read(run)


@pytest.mark.parametrize("metric, wanted", [
    ("tron.cg_iterations_per_fit", 24.0),
    ("tron.passes_roofline", 2.1184631005877788),
])
def test_reader_on_a_recorded_reduction(metric, wanted):
    assert _read(metric, RECORDED) == pytest.approx(wanted, rel=1e-12)


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_nothing_without_its_counter(metric):
    """A program that does not count CG steps leaves no counter row and no
    floor of passes; a fit with no trace leaves no busy time."""
    parent = dict(RECORDED, floor={"seconds": 0.153627},
                  counters={"counters": [
                      row for row in RECORDED["counters"]["counters"]
                      if row["name"] != "optimizer.cg_iterations"],
                      "gauges": []})
    assert _read(metric, parent) is None
    if metric == "tron.passes_roofline":
        assert _read(metric, dict(RECORDED, trace=None)) is None
