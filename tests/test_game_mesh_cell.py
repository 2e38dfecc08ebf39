"""The four-chip GAME cell (``game_fit_4chip``) at its rehearsal sizes on a
forced 4-device CPU mesh: the fit through ``GameEstimator(mesh=...)``
against the plain reference, against the same fit on one device, what
``placement.live_rows`` publishes, and the blocked reference against the
whole one.  No number here is a device number."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402

SEED = 2 ** 31 + 34
COORDINATES = ("fixed", "per_user", "per_item")

# The limits `correct` is decided on (benchmarks/traffic/descent2_fits_mesh
# .json, PERF.md section 2), and why each holds at this size on the host.
LIMITS = {
    # validation logistic loss after each descent iteration, relative: both
    # sides take the mean in float64 over the same rows; what is left is the
    # distance between 15 Newton steps and the optimum
    "val_loss_gap": 1e-4,
    # validation AUC, absolute: a rank statistic, moved only by scores that
    # swap order
    "val_auc_gap": 2e-5,
    # the fixed effect's training objective at the end of its last fit,
    # relative: one float32 sum over the rows on each side
    "fixed_loss_gap": 1e-4,
    # gap of norms, worst of the three leaves: insensitive to direction, so
    # tighter than coef_diff
    "coef_norm_gap": 3e-4,
    # norm of the difference, worst leaf: the program stops after 15 Newton
    # steps at default matmul precision, the reference runs to the optimum
    "coef_diff": 5e-4,
}
# A 4-device mesh against one device, same program: the fixed effect's
# gradient is a psum of four partial float32 sums instead of one sum, and a
# bin's entities are solved in another batch; nothing else may differ.
MESH_AGAINST_ONE_DEVICE = 2e-5


@pytest.fixture(scope="module")
def cell():
    spec = harness.load_cell("game_fit_4chip")
    config = dict(spec["config"], sizes=dict(
        spec["config"]["sizes"], **spec["config"]["rehearsal_sizes"]))
    runner = harness.load_module(spec["runner_dir"], spec["traffic"]["runner"])
    return spec, config, runner


@pytest.fixture(scope="module")
def fits(cell):
    """One fit over a 4-device mesh and one with ``mesh=None``, each with
    its gauges, and the blocked reference (blocks small enough to cut the
    rehearsal's rows and entities several times)."""
    from benchmarks.reference import game, game_blocked
    from photon_tpu.drivers import common
    from photon_tpu.parallel import create_mesh

    spec, config, runner = cell
    traffic = spec["traffic"]
    assert traffic["limits"] == LIMITS
    out = {}
    for name, mesh, wanted in (("mesh", lambda: create_mesh(4), 4),
                               ("one", lambda: None, 1)):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(common, "maybe_mesh", mesh)
            state = runner.setup(config, dict(traffic, mesh_devices=wanted),
                                 SEED, harness.Clock())
        counts = runner.step(state)
        out[name] = {"produced": runner.produced(state),
                     "gauges": runner.counters(state)["gauges"],
                     "work": runner.work(state, [counts])}
        data = state.data
        runner.release(state)
    fit = traffic["fit"]
    out["data"] = data
    out["spec"] = {
        "l2": float(fit["reg_weight"]),
        "descent_iterations": int(fit["descent_iterations"]),
        "fixed_max_iterations": int(fit["fixed_max_iterations"]),
        "tolerance": float(fit["tolerance"]),
        "gradient_tolerance": float(fit["gradient_tolerance"]),
    }
    out["reference"] = game_blocked.fit(
        data, out["spec"], row_block=1000, block_cells=512)
    out["whole"] = game.fit(data, out["spec"])
    return out


@pytest.mark.parametrize("number", sorted(LIMITS))
def test_mesh_fit_is_within_the_cells_limits_of_the_reference(
        cell, fits, number):
    _, _, runner = cell
    numbers = runner.compare(fits["mesh"]["produced"], fits["reference"])
    assert np.isfinite(numbers[number])
    assert numbers[number] <= LIMITS[number], numbers


@pytest.mark.parametrize("number", sorted(LIMITS))
def test_mesh_fit_equals_the_one_device_fit_to_rounding(cell, fits, number):
    _, _, runner = cell
    numbers = runner.compare(fits["mesh"]["produced"],
                             fits["one"]["produced"])
    assert numbers[number] <= MESH_AGAINST_ONE_DEVICE, numbers


def _live_rows(gauges):
    return {
        (g["labels"]["coordinate"], g["labels"]["device"]): g["value"]
        for g in gauges if g["name"] == "placement.live_rows"
    }


def test_live_rows_one_gauge_a_device_and_coordinate(fits):
    rows = _live_rows(fits["mesh"]["gauges"])
    train_rows = fits["data"].train.rows
    assert sorted({c for c, _ in rows}) == sorted(COORDINATES)
    for coordinate in COORDINATES:
        by_device = {d: v for (c, d), v in rows.items() if c == coordinate}
        assert len(by_device) == 4
        assert sum(by_device.values()) == train_rows
        assert min(by_device.values()) > 0
    placed = {
        (g["name"], g["labels"]["coordinate"]): g["value"]
        for g in fits["mesh"]["gauges"] if g["name"].startswith("placement.")
        and g["name"] != "placement.live_rows"
    }
    for coordinate in COORDINATES:
        assert placed["placement.devices", coordinate] == 4
        assert placed["placement.slices", coordinate] == 4


def test_live_rows_without_a_mesh_is_one_device(fits):
    rows = _live_rows(fits["one"]["gauges"])
    assert len(rows) == len(COORDINATES)
    assert len({d for _, d in rows}) == 1
    assert set(rows.values()) == {fits["data"].train.rows}


def test_live_rows_are_counted_once_a_layout(cell):
    """``_record_placement`` runs at every fit's start; the pass over the
    host's row weights must not: a second fit on the same layout leaves the
    gauges as they are."""
    from photon_tpu.drivers import common
    from photon_tpu.parallel import create_mesh

    spec, config, runner = cell
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(common, "maybe_mesh", lambda: create_mesh(4))
        state = runner.setup(config, spec["traffic"], SEED, harness.Clock())
    estimator = state.estimator
    estimator._build_coordinates(state.configuration)
    counted = dict(estimator._live_rows_counted)
    assert sorted(counted) == sorted(COORDINATES)
    gauge = state.session.gauge(
        "placement.live_rows", coordinate="per_user", device=0)
    assert gauge.value > 0
    gauge.set(-1)  # a second count would write the rows over it
    estimator._build_coordinates(state.configuration)
    assert estimator._live_rows_counted == counted
    assert gauge.value == -1
    runner.release(state)


def test_mesh_floor_is_a_chips_share(fits):
    mesh, one = fits["mesh"]["work"], fits["one"]["work"]
    for key in ("rows", "validation_rows", "entities"):
        assert mesh[key] == pytest.approx(one[key] / 4)
    for key in ("fixed_dim", "random_dim", "descent_iterations"):
        assert mesh[key] == one[key]


def test_blocked_reference_in_one_block_is_the_whole_one(fits):
    from benchmarks.reference import game_blocked

    whole = fits["whole"]
    blocked = game_blocked.fit(fits["data"], fits["spec"])
    for name, want in whole["coefficients"].items():
        np.testing.assert_array_equal(blocked["coefficients"][name], want)
    assert blocked["fixed_values"] == whole["fixed_values"]
    assert blocked["metrics"] == whole["metrics"]


def test_blocked_reference_in_many_blocks_equals_the_whole_one(cell, fits):
    """Same functions in the same order.  What moves: a float32 sum taken
    block by block (the fixed effect's objective and gradient), and an
    entity's Newton solve in another batch, which stops where float32 can
    no longer tell the objective's values apart (a thousandth of a
    coefficient).  Each of the cell's five numbers stays under a fifth of
    its limit."""
    _, _, runner = cell
    whole = fits["whole"]
    numbers = runner.compare(fits["reference"], whole)
    for name, limit in LIMITS.items():
        assert numbers[name] <= limit / 5, numbers
    for name, want in whole["coefficients"].items():
        assert fits["reference"]["coefficients"][name].shape == want.shape
