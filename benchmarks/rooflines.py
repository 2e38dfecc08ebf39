"""The chip's peaks and the least work each fit's algorithm needs.

Every count here is a function of shapes and of the iteration counts a fit
reports, never of what a selected kernel says it moved: a later PR that
swaps kernels is judged against the same floor.  Floats are 4 bytes, ids 4.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and HBM bytes/s of ``device_kind``; an unknown device is
    an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in benchmarks/"
            f"peaks.json (known: {sorted(table)})"
        )
    return table[device_kind]


def least_seconds(flops: float, bytes_: float, peak: dict) -> tuple:
    """``(seconds, bound)``: the larger of compute and memory time, and
    which of the two it is."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "hbm")


# -- sparse fixed-effect value + gradient ---------------------------------------


def bytes_valuegrad(entries: int, dim: int, rows: int) -> int:
    """One value+gradient evaluation over ``entries`` padded-COO nonzeros.

    Counted: the ids and the values read once in each direction (margins,
    then the transposed reduction) = 2 * (4 + 4) * entries; the coefficient
    vector read once and the gradient written once = 8 * dim; per row the
    label, weight and offset read and the loss derivative written then read
    = 20 * rows.  Not counted: any re-read a layout forces (row indices of
    a feature-major copy, slab dictionaries, sort passes)."""
    return 16 * entries + 8 * dim + 20 * rows


def flops_valuegrad(entries: int, rows: int) -> int:
    """A multiply-add per entry in each direction, ~10 per row for the
    loss and its derivative."""
    return 4 * entries + 10 * rows


def glm_fit_floor(work: dict, peak: dict) -> dict:
    """Least seconds of one L-BFGS fit: ``iterations + 1`` value+gradient
    evaluations (one at the start, at least one line-search trial an
    iteration: a floor, the program does not report its evaluation count)
    plus the two-loop recursion's reads of the (s, y) memory."""
    evaluations = work["iterations"] + 1
    t_eval, bound = least_seconds(
        flops_valuegrad(work["entries"], work["rows"]),
        bytes_valuegrad(work["entries"], work["dim"], work["rows"]), peak,
    )
    m, d = work["history_length"], work["dim"]
    t_loop, loop_bound = least_seconds(
        8 * m * d * work["iterations"], 16 * m * d * work["iterations"], peak,
    )
    return {
        "seconds": evaluations * t_eval + t_loop,
        "valuegrad_seconds": evaluations * t_eval,
        "flops": evaluations * flops_valuegrad(work["entries"], work["rows"])
        + 8 * m * d * work["iterations"],
        "phases": {"valuegrad": bound, "two_loop": loop_bound},
    }


# -- GAME fit -------------------------------------------------------------------


def game_fit_floor(work: dict, peak: dict) -> dict:
    """Least seconds of one GAME fit (all descent iterations).

    Fixed effect: ``iterations + fits`` evaluations (one at each fit's
    start), each reading the dense ``[rows, dim]`` block twice (X w, then
    X^T dz) at 4 flops an element.  Random effects, per Newton iteration
    and coordinate: the ``[rows, dim]`` block read twice (gradient, then
    Hessian), 2 dim^2 + 4 dim flops a row, a dim^3 / 3 Cholesky an entity.
    Scoring: every coordinate's block read once over the training rows and
    once over the validation rows, 2 flops an element, after each update.
    Live rows only: bin padding is waste, not work."""
    rows, val_rows = work["rows"], work["validation_rows"]
    fd, rd = work["fixed_dim"], work["random_dim"]
    phases, total, flops = {}, 0.0, 0.0

    def add(name, f, b):
        nonlocal total, flops
        t, bound = least_seconds(f, b, peak)
        phases[name] = bound
        total += t
        flops += f

    evaluations = work["fixed_iterations"] + work["fixed_fits"]
    add("fixed_valuegrad", evaluations * 4 * rows * fd,
        evaluations * (8 * rows * fd + 20 * rows))
    newton = work["random_newton_iterations"]  # summed over coordinates, fits
    add("entity_solves",
        newton * (rows * (2 * rd * rd + 4 * rd)
                  + work["entities"] * rd ** 3 / 3),
        newton * (8 * rows * rd + 20 * rows))
    updates = work["descent_iterations"]
    add("scoring",
        updates * 2 * (rows + val_rows) * (fd + work["random_coordinates"] * rd),
        updates * 4 * (rows + val_rows)
        * (fd + work["random_coordinates"] * (rd + 1) + 2))
    return {"seconds": total, "flops": flops, "phases": phases}
