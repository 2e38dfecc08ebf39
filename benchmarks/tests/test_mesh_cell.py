"""The four-chip cell ``game_fit_4chip``: its files are found by name, its
rehearsal runs over four host devices and prints no result line, the
reduction of a trace over four devices (``trace_collectives``) and the three
readers on hand-made inputs, the floor as one chip's share, and the control
and the planted fault each failing a limit at the rehearsal sizes."""

import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmarks import generate, rooflines, run as harness, trace_collectives
from benchmarks.runners import game_fit as one_chip

CELL = "game_fit_4chip"
SEED = 2 ** 31 + 34
CMD = [sys.executable, os.path.join(harness.ROOT, "benchmarks", "run.py")]
ENV = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
FOUR = "--xla_force_host_platform_device_count=4"


def _reader(name):
    spec = harness.load_cell(CELL)
    return harness.load_module(spec["layer_dir"], name).read


def test_load_cell_finds_every_file():
    spec = harness.load_cell(CELL)
    assert spec["cell"]["chips"] == 4
    assert spec["config"]["sizes"]["entities_per_coordinate"] == 160000
    assert spec["traffic"]["runner"] == "game_fit_mesh"
    assert spec["traffic"]["mesh_devices"] == spec["cell"]["chips"]
    base = harness.load_cell("game_fit")
    assert spec["traffic"]["fit"] == base["traffic"]["fit"]
    assert spec["traffic"]["limits"] == base["traffic"]["limits"]
    for key in ("rows_per_entity_mean", "fixed_dim", "random_dim",
                "random_coordinates", "validation_split", "structure_seed"):
        assert spec["config"]["sizes"][key] == base["config"]["sizes"][key]
    runner = harness.load_module(spec["runner_dir"], spec["traffic"]["runner"])
    for function in ("setup", "step", "counters", "work", "floor", "check",
                     "produced", "release", "reference", "compare"):
        assert callable(getattr(runner, function))
    names = [m["name"] for m in spec["per_layer"]]
    for name in ("collectives.device_s", "mesh.imbalance_pct",
                 "mesh.busy_spread_pct", "fit.mfu_pct", "device.idle_pct",
                 "device.peak_hbm_gib", "setup.data_s", "setup.layout_s",
                 "setup.compiles"):
        assert name in names
        assert callable(harness.load_module(spec["layer_dir"], name).read)
    assert [m["name"] for m in spec["end_to_end"]] == ["fit_s", "setup_s"]


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_on_four_host_devices_prints_no_result_line(trace):
    done = subprocess.run(
        CMD + ["--workload", CELL, "--seed", str(SEED), "--seconds", "0.5",
               "--trace", trace, "--cpu-rehearsal"],
        capture_output=True, text=True, timeout=900,
        env=dict(ENV, XLA_FLAGS=FOUR),
    )
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip() == ""
    last = done.stderr.strip().splitlines()
    assert last[-1].startswith("cpu rehearsal done") and "correct=True" in last[-1]


def test_rehearsal_refuses_fewer_than_four_devices():
    done = subprocess.run(
        CMD + ["--workload", CELL, "--seed", "1", "--seconds", "0.5",
               "--trace", "0", "--cpu-rehearsal"],
        capture_output=True, text=True, timeout=600,
        env=dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=2"),
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""


# -- the trace over four devices ---------------------------------------------------


def _line(name, events):
    return types.SimpleNamespace(name=name, events=[
        types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
        for n, s, d in events
    ])


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


ALL_REDUCE = ("%all-reduce.7 = f32[128]{0} all-reduce(f32[128]{0} %x), "
              "replica_groups={{0,1,2,3}}, to_apply=%add")
START = ("%all-gather-start.2 = (f32[8]{0}, f32[32]{0}) "
         "all-gather-start(f32[8]{0} %y), dimensions={0}")
DONE = "%all-gather-done.2 = f32[32]{0} all-gather-done(%all-gather-start.2)"
FUSION = ("%all-reduce_fusion.1 = f32[8]{0} fusion(f32[8]{0} %z), "
          "kind=kLoop, calls=%fused_computation")


def _four_planes():
    """Device d is busy 0-1000 with one fusion, then inside collectives for
    100 + 40 + 20 (+ 100 more on device 3, whose fusion also runs 400
    longer); the all-gather halves lie inside a ``while``."""
    planes = []
    for d in range(4):
        extra = 400 if d == 3 else 0
        ops = [
            ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 0,
             1000 + extra),
            (ALL_REDUCE, 2000, 100),
            ("%while.3 = (f32[8]{0}) while((f32[8]{0}) %t), body=%b", 3000, 500),
            (START, 3000, 40), (FUSION, 3100, 200), (DONE, 3400, 20),
        ]
        if d == 3:
            ops.append(("collective-permute.4", 5000, 100))
        planes.append(_plane(f"/device:TPU:{d}", [
            _line("XLA Modules", [("jit_fit(1)", 0, 6000)]),
            _line("XLA Ops", ops),
        ]))
    planes.append(_plane("/host:CPU", [_line("python", [
        ("all-reduce", 0, 6000)])]))
    return planes


def test_collective_names():
    for name in (ALL_REDUCE, START, DONE, "all-reduce.3", "%all-to-all.1",
                 "reduce-scatter", "collective-permute-start.9"):
        assert trace_collectives.is_collective(name), name
    for name in (FUSION, "%fusion.4 = f32[] fusion(...)", "all-reduce_fusion",
                 "%copy.1 = f32[8]{0} copy(f32[8]{0} %all-reduce.7)", ""):
        assert not trace_collectives.is_collective(name), name


def test_trace_collectives_on_four_hand_made_planes():
    out = trace_collectives.reduce_planes(_four_planes())
    assert out["devices"] == 4
    assert out["collective_s_by_device"] == pytest.approx(
        [160e-9, 160e-9, 160e-9, 260e-9])
    assert out["collective_s"] == pytest.approx(185e-9)  # mean over devices
    assert out["collective_ops"] == 13
    # busy: the union, so the while counts once with what lies inside it
    assert out["busy_s_by_device"] == pytest.approx(
        [1600e-9, 1600e-9, 1600e-9, 2100e-9])


def test_the_three_readers():
    mesh_trace = trace_collectives.reduce_planes(_four_planes())
    gauges = [
        {"name": "placement.live_rows",
         "labels": {"coordinate": c, "device": str(d)}, "value": v}
        for c, rows in (("fixed", (100, 100, 100, 100)),
                        ("per_user", (130, 90, 90, 90)))
        for d, v in enumerate(rows)
    ]
    run = {"counters": {"counters": [], "gauges": gauges,
                        "mesh_trace": mesh_trace}, "traced_steps": 1}
    assert _reader("collectives.device_s")(run) == pytest.approx(185e-9)
    assert _reader("mesh.busy_spread_pct")(run) == pytest.approx(
        100 * (2100 / 1725 - 1))
    assert _reader("mesh.imbalance_pct")(run) == pytest.approx(
        100 * (230 / 200 - 1))
    # The parent of the PR that added the gauge, a run with no trace, a
    # one-chip runner: nothing to read, and nothing raised.
    bare = {"counters": {"counters": [], "gauges": []}, "traced_steps": 1}
    for name in ("collectives.device_s", "mesh.busy_spread_pct",
                 "mesh.imbalance_pct"):
        assert _reader(name)(bare) is None


# -- the floor, the control, the planted fault -----------------------------------


def _host_state():
    """What ``reference`` and ``work`` read of a state, without a mesh (this
    process has one host device)."""
    spec = harness.load_cell(CELL)
    config = dict(spec["config"], sizes=dict(
        spec["config"]["sizes"], **spec["config"]["rehearsal_sizes"]))
    runner = harness.load_module(spec["runner_dir"], spec["traffic"]["runner"])
    data = generate.make(config, SEED)
    state = runner.State(
        data=data, fit=spec["traffic"]["fit"], mesh_devices=4,
        fixed_dim=data.train.x_fixed.shape[1],
        random_dim=data.train.x_random["re0"].shape[1],
    )
    return spec, runner, state


def test_floor_is_a_quarter_of_the_whole_works():
    _, runner, state = _host_state()
    steps = [{"fixed_iterations": 24.0, "fixed_fits": 2.0,
              "newton_iterations_last": 30.0}]
    peak = rooflines.peaks("TPU v5 lite")
    whole = one_chip.floor(state, steps, peak)
    share = runner.floor(state, steps, peak)
    # Every phase's flops and bytes are linear in rows and entities.
    assert share["flops"] == pytest.approx(whole["flops"] / 4)
    assert share["seconds"] == pytest.approx(whole["seconds"] / 4)
    assert runner.work(state, steps)["mesh_devices"] == 4


def test_the_control_and_the_half_batch_each_fail_a_limit():
    spec, runner, state = _host_state()
    limits = spec["traffic"]["limits"]
    want = runner.reference(state)
    sound = runner.compare(runner.reference(state), want)
    assert all(sound[k] <= limits[k] for k in limits), sound
    control = runner.compare(runner.reference(state, lowp=True), want)
    assert any(control[k] > limits[k] for k in limits), (control, limits)
    n = state.data.fit_rows
    half = np.where(np.arange(n) % 2 == 0, 2.0, 0.0).astype(np.float32)
    fault = runner.compare(runner.reference(state, weight=half), want)
    assert all(fault[k] > limits[k] for k in limits), (fault, limits)
