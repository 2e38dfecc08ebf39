"""The trace reduction: on hand-made planes, and on one small trace
recorded on the chip (``record_small_trace.py``) and kept beside this file."""

import os
import types

import pytest

from benchmarks import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _line(name, events):
    return types.SimpleNamespace(name=name, events=[
        types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
        for n, s, d in events
    ])


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


def test_reduce_planes_by_hand():
    planes = [
        _plane("/device:TPU:0", [
            _line("XLA Modules", [("jit_fit(1)", 0, 500), ("jit_fit(1)", 700, 300)]),
            _line("XLA Ops", [
                ("%while.9 = (f32[]) while(...)", 0, 400),      # holds the two
                ("fusion.1", 0, 200), ("fusion.2", 200, 150),   # 0-350 inside
                ("fusion.1", 700, 300),                         # 700-1000
            ]),
        ]),
        _plane("/host:CPU", [
            _line("python", [("bench.step", 0, 1000), ("bench.sync", 380, 340),
                             ("other", 0, 1000)]),
        ]),
    ]
    out = trace_reduce.reduce_planes(planes)
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"] == pytest.approx(700e-9)  # the union, not the sum
    ops = dict(out["by_op"])
    assert ops["fusion.1"] == pytest.approx(500e-9)
    assert ops["fusion.2"] == pytest.approx(150e-9)
    assert ops["%while.9 while"] == pytest.approx(50e-9)  # self time only
    assert out["by_module"] == [["jit_fit(1)", pytest.approx(800e-9)]]
    # One gap, 400-700, named by the innermost bench.* span over its middle.
    assert out["gaps"] == [["bench.sync", pytest.approx(300e-9)]]
    assert out["annotations"]["bench.step"] == pytest.approx(1000e-9)


def test_reduce_planes_averages_devices_and_finds_common_gaps():
    planes = [
        _plane("/device:TPU:0", [_line("XLA Ops", [("a", 0, 400)])]),
        _plane("/device:TPU:1", [_line("XLA Ops", [("a", 600, 400)])]),
    ]
    out = trace_reduce.reduce_planes(planes)
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(400e-9)
    assert out["gaps"] == [["unannotated", pytest.approx(200e-9)]]


def test_no_device_plane_reads_nothing():
    out = trace_reduce.reduce_planes([_plane("/host:CPU", [])])
    assert out["devices"] == 0 and out["busy_s"] == 0.0


def test_recorded_chip_trace():
    path = os.path.join(DATA, "small_trace.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace beside the test")
    out = trace_reduce.reduce(path)
    expected = RECORDED
    assert out["devices"] == expected["devices"]
    assert out["window_s"] == pytest.approx(expected["window_s"], rel=1e-9)
    assert out["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert 0 < out["busy_s"] < out["window_s"]
    # Three steps with 20 ms of host sleep after each: the gaps between
    # the three programs are the sleeps.
    assert out["gaps"][0][0] == "bench.sleep"
    assert out["gaps"][0][1] == pytest.approx(expected["sleep_gap_s"], rel=1e-9)
    modules = dict(out["by_module"])
    assert any("small_step" in name for name in modules)


# What reduce() read from data/small_trace.xplane.pb when it was recorded
# (my chip run, PR 26): filled in from record_small_trace.py's output.
RECORDED = {"devices": 1, "window_s": 0.042321036, "busy_s": 3.0376e-05,
            "sleep_gap_s": 0.042290657}
