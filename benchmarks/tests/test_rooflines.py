"""The operation and byte counts against numbers worked by hand."""

import pytest

from benchmarks import rooflines

PEAK = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_bytes_valuegrad_toy_shape():
    # 8 rows x 4 nnz = 32 entries, d = 16: ids+vals twice = 16 * 32 = 512;
    # w read + g written = 8 * 16 = 128; per-row scalars = 20 * 8 = 160.
    assert rooflines.bytes_valuegrad(32, 16, 8) == 512 + 128 + 160
    assert rooflines.flops_valuegrad(32, 8) == 4 * 32 + 80


def test_least_seconds_names_the_bound():
    assert rooflines.least_seconds(200.0, 10.0, PEAK) == (2.0, "flops")
    assert rooflines.least_seconds(100.0, 30.0, PEAK) == (3.0, "hbm")


def test_glm_fit_floor_toy_shape():
    work = {"entries": 32, "dim": 16, "rows": 8, "history_length": 2,
            "iterations": 3}
    floor = rooflines.glm_fit_floor(work, PEAK)
    # 4 evaluations, each max(208 / 100, 800 / 10) = 80 s (HBM-bound);
    # two-loop: bytes 16 * 2 * 16 * 3 = 1536 -> 153.6 s, flops 768 -> 7.68 s.
    assert floor["valuegrad_seconds"] == pytest.approx(320.0)
    assert floor["seconds"] == pytest.approx(320.0 + 153.6)
    assert floor["phases"] == {"valuegrad": "hbm", "two_loop": "hbm"}
    assert floor["flops"] == 4 * 208 + 768


def test_game_fit_floor_toy_shape():
    work = {"rows": 10, "validation_rows": 2, "entities": 3, "fixed_dim": 4,
            "random_dim": 2, "random_coordinates": 2, "descent_iterations": 2,
            "fixed_iterations": 5, "fixed_fits": 2,
            "random_newton_iterations": 6}
    floor = rooflines.game_fit_floor(work, PEAK)
    # fixed: 7 evaluations x (8*10*4 + 200) = 3640 B -> 364 s;
    #        flops 7 * 160 = 1120 -> 11.2 s: HBM-bound.
    # solves: 6 x (10 * (8 + 8) + 3 * 8 / 3) = 6 * 168 = 1008 flop -> 10.08 s;
    #         bytes 6 * (160 + 200) = 2160 -> 216 s: HBM-bound.
    # scoring: 2 * 2 * 12 * (4 + 4) = 384 flop; bytes 2 * 4 * 12 * (4 + 6 + 2)
    #          = 1152 -> 115.2 s: HBM-bound.
    assert floor["seconds"] == pytest.approx(364.0 + 216.0 + 115.2)
    assert floor["flops"] == pytest.approx(1120 + 1008 + 384)
    assert set(floor["phases"].values()) == {"hbm"}


def test_unknown_device_is_an_error():
    assert rooflines.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        rooflines.peaks("TPU v99")
