"""Size-binned batched device linear algebra for random-effect solves.

The seed's ``RandomEffectCoordinate.train`` drove a Python loop over
row-count buckets — O(buckets) host dispatches and one compiled program per
bucket shape — which is what capped entity counts (ROADMAP "Random effects
at millions of entities").  This module is the routing layer that replaces
it:

- **Bin layout** — :func:`bin_layout` consolidates the power-of-two buckets
  into a few padded size bins (``game.data.plan_size_bins`` /
  ``merge_buckets``), so a million-entity coordinate dispatches a handful
  of jitted programs instead of a dozen-plus.  ``PHOTON_SOLVE_BINNING=off``
  restores the one-bucket-per-capacity loop (the escape hatch and the
  bench's bucket-loop baseline).
- **Solver routing** — :func:`solver_route` picks, per bin, between the
  batched damped Newton (``core.optimizers.newton`` vmapped over the
  entity axis: ``[B, dim, dim]`` Hessians, one ``newton.spd_solve`` per
  iteration — an unrolled Cholesky with the entities on the lane axis up
  to ``newton.LANES_MAX_DIM``, XLA's batched ``cho_factor``/``cho_solve``
  above it; ``solves.factorization{kind}`` says which)
  for the common small-``solve_dim`` smooth case, and the existing vmapped
  L-BFGS/OWL-QN/TRON program for everything else (L1 bins, large dims,
  row-split placement) — so every existing ``problem`` config still solves.
  The ``newton`` program takes the objective as a ``newton.MarginForm``:
  the margins ``X w + offset`` ride in the solver's state, so an iteration
  is three passes over a bin's features (``X step``, ``Xᵀ dz``,
  ``Xᵀ D X``) however long the slowest entity's line search backtracks —
  a trial is the loss along ``z + t X step``, not a value and a gradient
  over the features for every entity of the bin.  One bin program of
  ``game_fit`` alone on the v5e, ``[25,238, 256, 16]``: 118.9 ms a call
  before, 44.6 ms after (PERF.md, PR 39).  The three products run over
  ``[B, rows, dim]`` (the compiler puts the rows on the lanes and the
  Hessian on the MXU) unless ``newton.reduction_kind`` says ``lanes``
  (``solves.reductions{kind}``): a dense bin at the lane dims with fewer
  rows an entity than a register has lanes and at least 128 entities a
  device is padded to a multiple of 128 entities a device and its
  features turned to ``[rows, dim, B]`` once a call
  (:func:`_entity_solve_newton`, scope ``newton/to_lanes``), and the
  products are float32 products and sums over the non-lane axes
  (``objective._lane_form``): ``[13,124, 32, 16]`` 23.3 ms before, 19.6 ms
  with the margins carried, 10.3 ms entity-minor.
- **Solver cache** — :func:`cached_newton_solver` mirrors
  ``core.problem.cached_solver``: one traced program per static
  (optimizer-config, variance) pair, module-cached, the objective riding
  along as a pytree argument so reg sweeps share it.

Entity-axis sharding rides the existing ``RandomEffectDeviceData``
placement: bins are padded to the mesh multiple and sharded over the mesh
axis the score tables already use (``parallel.mesh``), composing with
``solve_entities_row_split`` under multi-controller row-split configs.

Above the dense-Newton dim cap, smooth bins now route to the MATRIX-FREE
batched Newton-CG (``core.optimizers.newton_cg`` vmapped over the entity
axis: Hessian-vector products through ``objective.hvp_operator`` — two
sparse matvecs per inner iteration, never a ``[B, d, d]`` block — with a
Jacobi preconditioner from the cheap Hessian diagonal and Eisenstat-Walker
adaptive inner tolerances), lifting the per-entity solve-dimension ceiling
from ``PHOTON_NEWTON_MAX_DIM`` (64) to ``PHOTON_NEWTON_CG_MAX_DIM``
(default 1024) — the ROADMAP "lift the solver ceilings" edge (ISSUE 14).

Knobs (env): ``PHOTON_SOLVE_BINNING`` (``on``/``off``),
``PHOTON_SOLVE_MAX_BINS`` (default 4), ``PHOTON_SOLVE_BIN_WASTE`` (default
2.0 — padded row cells allowed per live row cell before a capacity starts
its own bin), ``PHOTON_SOLVE_NEWTON`` (``on``/``off``),
``PHOTON_NEWTON_MAX_DIM`` (default 64 — above it the dense ``[B, d, d]``
Hessian stops paying and bins route to Newton-CG),
``PHOTON_SOLVE_NEWTON_CG`` (``on``/``off``), ``PHOTON_NEWTON_CG_MAX_DIM``
(default 1024 — above it bins route to the vmapped iterative solvers).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from photon_tpu.core.optimizers import OptimizerConfig
from photon_tpu.core.optimizers.newton import (
    LANES,
    MarginForm,
    factorization_kind,
    newton,
    reduction_kind,
)
from photon_tpu.core.optimizers.newton_cg import newton_cg
from photon_tpu.core.problem import ProblemConfig, _compute_variances, hvp_at_for
from photon_tpu.data.batch import DenseBatch
from photon_tpu.models.glm import Coefficients


def binning_enabled() -> bool:
    return os.environ.get("PHOTON_SOLVE_BINNING", "on").strip().lower() not in (
        "off", "0", "false",
    )


def newton_enabled() -> bool:
    return os.environ.get("PHOTON_SOLVE_NEWTON", "on").strip().lower() not in (
        "off", "0", "false",
    )


def max_bins() -> int:
    return int(os.environ.get("PHOTON_SOLVE_MAX_BINS", "4"))


def bin_waste_cap() -> float:
    return float(os.environ.get("PHOTON_SOLVE_BIN_WASTE", "2.0"))


def newton_max_dim() -> int:
    return int(os.environ.get("PHOTON_NEWTON_MAX_DIM", "64"))


def newton_cg_enabled() -> bool:
    return os.environ.get("PHOTON_SOLVE_NEWTON_CG", "on").strip().lower() not in (
        "off", "0", "false",
    )


def newton_cg_max_dim() -> int:
    return int(os.environ.get("PHOTON_NEWTON_CG_MAX_DIM", "1024"))


def bin_layout(buckets: tuple) -> list:
    """Bucket-index groups for the operative bin policy: the planned size
    bins, or one bucket per bin when binning is off (the seed's loop)."""
    if not binning_enabled() or len(buckets) <= 1:
        return [[i] for i in range(len(buckets))]
    from photon_tpu.game.data import plan_size_bins

    return plan_size_bins(buckets, max_bins=max_bins(),
                          waste_cap=bin_waste_cap())


def solver_route(problem: ProblemConfig, solve_dim: int,
                 row_split: bool = False) -> str:
    """Which solver a bin runs: ``newton`` (batched direct solve) for smooth
    small-dim problems, ``newton_cg`` (matrix-free Hessian-vector CG) for
    smooth bins past the dense-Hessian cap up to ``newton_cg_max_dim``,
    ``row_split`` under row-split placement, else ``vmapped`` (the
    existing L-BFGS/OWL-QN/TRON program — L1 bins and over-cap dims keep
    their iterative solve)."""
    if row_split:
        return "row_split"
    smooth = (
        problem.regularization.l1_weight == 0
        and problem.optimizer.lower() not in ("owlqn", "owl-qn")
    )
    if problem.optimizer.lower() in ("newton_cg", "newton-cg"):
        # An explicitly requested Newton-CG problem routes there at ANY
        # dim — the route label must not silently rename the user's
        # solver choice.
        return "newton_cg"
    if smooth and newton_enabled() and solve_dim <= newton_max_dim():
        return "newton"
    if (
        smooth
        and newton_cg_enabled()
        and newton_max_dim() < solve_dim <= newton_cg_max_dim()
    ):
        return "newton_cg"
    return "vmapped"


def _run_newton_fit(objective, batch, w0, *, cfg: OptimizerConfig,
                    variance: str, lanes: bool = False):
    """One damped-Newton GLM fit, pure in (objective, batch, w0) — the body
    :func:`cached_newton_solver` vmaps and compiles.  Mirrors
    ``core.problem._run_fit``: the objective is a pytree argument, and the
    variance computation is the SAME ``_compute_variances`` formula the
    iterative path runs, so means AND variances agree at convergence.  The
    objective goes in as a ``newton.MarginForm`` (the solver carries the
    margins: three passes over the features an iteration); ``lanes``: its
    dense products in the form that puts a mapped axis on the lanes."""
    form = MarginForm(
        margins=lambda w: objective.margins(w, batch, lanes),
        direction=lambda v: objective.direction_margins(v, batch, lanes),
        value=lambda z, w: objective.value_at_margins(z, w, batch),
        grad=lambda z, w: objective.grad_at_margins(z, w, batch, lanes),
        hess=lambda z, w: objective.hessian_at_margins(z, w, batch, lanes),
    )
    result = newton(None, w0, cfg, form=form)
    coefficients = Coefficients(
        means=result.w,
        variances=_compute_variances(objective, variance, result.w, batch),
    )
    return coefficients, result


def _device_entities(leaf, shards: int, width: int):
    """``[B, ...]`` with each of the ``shards`` contiguous entity blocks
    padded with zeros or cut back to ``width`` entities: no entity changes
    its block, so under a mesh nothing moves between devices."""
    local = leaf.shape[0] // shards
    if local == width:
        return leaf
    leaf = leaf.reshape(shards, local, *leaf.shape[1:])
    if width < local:
        leaf = leaf[:, :width]
    else:
        leaf = jnp.pad(
            leaf, ((0, 0), (0, width - local)) + ((0, 0),) * (leaf.ndim - 2)
        )
    return leaf.reshape(shards * width, *leaf.shape[2:])


def _entity_solve_newton(objective, batch, w0, *, run, entity_shards: int):
    """``run`` over the entity axis of a bin.  Where
    ``newton.reduction_kind`` says ``lanes`` the bin is first turned
    entity-minor under the scope ``newton/to_lanes`` — once a call, outside
    the solver's loops, which take the turned features as a loop-invariant
    operand — each device's entities padded to a multiple of
    ``newton.LANES`` with empty ones (zero rows, ``w0 = 0``: converged at
    the start, cut off again at the end)."""
    entities, dim = w0.shape
    local = entities // entity_shards
    dense = isinstance(batch, DenseBatch)
    if reduction_kind(dense, dim, local, batch.label.shape[1]) != "lanes":
        return jax.vmap(run, in_axes=(None, 0, 0))(objective, batch, w0)
    width = -(-local // LANES) * LANES
    with jax.named_scope("newton/to_lanes"):
        batch, w0 = jax.tree.map(
            lambda leaf: _device_entities(leaf, entity_shards, width),
            (batch, w0),
        )
        batch = batch._replace(x=jnp.moveaxis(batch.x, 0, -1))
    out = jax.vmap(
        functools.partial(run, lanes=True),
        in_axes=(None, DenseBatch(x=-1, label=0, offset=0, weight=0), 0),
    )(objective, batch, w0)
    return jax.tree.map(
        lambda leaf: _device_entities(leaf, entity_shards, local), out
    )


def cached_newton_solver(problem: ProblemConfig):
    """The jit-compiled batched-Newton solver for one static problem
    configuration: ``(objective, batch, w0) -> (Coefficients,
    OptimizerResult)`` mapped over a leading entity axis.  Module-cached
    like ``core.problem.cached_solver`` — every coordinate and sweep config
    with the same static (optimizer config, variance) shares one traced
    program, and jit's own cache keys on bin shapes.  ``entity_shards``
    (static, by keyword; default 1) is the number of devices the entity
    axis is split over, so that the lane form pads each device's block."""
    return _cached_newton_solver(
        problem.optimizer_config, problem.variance_computation
    )


@functools.lru_cache(maxsize=32)
def _cached_newton_solver(cfg: OptimizerConfig, variance: str):
    from photon_tpu.utils.device import named_jit

    run = functools.partial(_run_newton_fit, cfg=cfg, variance=variance)

    def program(objective, batch, w0, entity_shards=1):
        return _entity_solve_newton(
            objective, batch, w0, run=run, entity_shards=entity_shards
        )

    return named_jit(
        "entity_solve_newton", program, static_argnames=("entity_shards",)
    )


def _run_newton_cg_fit(objective, batch, w0, *, cfg: OptimizerConfig,
                       variance: str):
    """One matrix-free Newton-CG GLM fit, pure in (objective, batch, w0) —
    the body :func:`cached_newton_cg_solver` vmaps and compiles.  The
    curvature rides ``objective.hvp_operator`` (per-row ``D(w)`` computed
    once per outer iteration, each CG step two matvecs — never a ``[d, d]``
    block), the Jacobi preconditioner is the cheap Hessian diagonal, and
    the variance computation is the SAME ``_compute_variances`` formula as
    every other route, so means AND variances stay on the existing parity
    contract."""
    fun = lambda w: objective.value_and_grad(w, batch)  # noqa: E731
    result = newton_cg(
        fun, w0, cfg,
        hvp_at=hvp_at_for(objective, batch),
        diag=lambda w: objective.hessian_diagonal(w, batch),
    )
    coefficients = Coefficients(
        means=result.w,
        variances=_compute_variances(objective, variance, result.w, batch),
    )
    return coefficients, result


def cached_newton_cg_solver(problem: ProblemConfig):
    """The jit-compiled batched Newton-CG solver for one static problem
    configuration — same caching contract as :func:`cached_newton_solver`:
    ``(objective, batch, w0) -> (Coefficients, OptimizerResult)`` mapped
    over a leading entity axis, one traced program per static (optimizer
    config, variance) pair."""
    return _cached_newton_cg_solver(
        problem.optimizer_config, problem.variance_computation
    )


@functools.lru_cache(maxsize=32)
def _cached_newton_cg_solver(cfg: OptimizerConfig, variance: str):
    from photon_tpu.utils.device import named_jit

    run = functools.partial(_run_newton_cg_fit, cfg=cfg, variance=variance)
    return named_jit(
        "entity_solve_newton_cg", jax.vmap(run, in_axes=(None, 0, 0))
    )


def record_bin_telemetry(telemetry, coordinate: str, bin_stats: list,
                         routes: list, solve_dims: list, dense: list,
                         entity_shards: int = 1) -> None:
    """Export the bin layout's padding economics as gauges — the ISSUE 8
    observability satellite: ``solves.bin_occupancy`` (LIVE entities per
    bin), ``solves.bin_entities_padded`` (mesh-padding slots), and
    ``solves.padded_fraction`` (padded fraction of the bin's entity×row
    cells — bin merging pads rows, mesh padding pads entities), so the bin
    policy's waste is observable instead of guessed.  Labels carry the
    coordinate, bin index, row capacity, and the routed solver.  The
    ``solves.routed{route}`` counter (ISSUE 14 satellite) counts the LIVE
    entities each route received — a silently-downgraded bin (L1,
    over-cap dim falling back to ``vmapped``) shows up in the run report
    instead of being inferred from timings.  ``solves.factorization{kind}``
    counts the LIVE entities of each ``newton`` bin by the form its
    factor-and-solve takes at the bin's static solve dim
    (``newton.factorization_kind``: ``lanes`` or ``xla``), and
    ``solves.reductions{kind}`` the same entities by the form of the bin's
    three dense products (``newton.reduction_kind``: ``lanes`` or ``rows``,
    from ``dense``, the solve dim, the bin's row capacity and its entities a
    device)."""
    for b, (stats, route, dim, is_dense) in enumerate(
        zip(bin_stats, routes, solve_dims, dense)
    ):
        labels = dict(
            coordinate=coordinate, bin=str(b),
            capacity=str(stats["capacity"]), route=route,
        )
        telemetry.counter(
            "solves.routed", coordinate=coordinate, route=route
        ).inc(stats["live_entities"])
        if route == "newton":
            telemetry.counter(
                "solves.factorization", coordinate=coordinate,
                kind=factorization_kind(dim),
            ).inc(stats["live_entities"])
            telemetry.counter(
                "solves.reductions", coordinate=coordinate,
                kind=reduction_kind(
                    is_dense, dim, stats["total_entities"] // entity_shards,
                    stats["capacity"],
                ),
            ).inc(stats["live_entities"])
        telemetry.gauge("solves.bin_occupancy", **labels).set(
            stats["live_entities"]
        )
        telemetry.gauge("solves.bin_entities_padded", **labels).set(
            stats["total_entities"] - stats["live_entities"]
        )
        cells = stats["total_entities"] * stats["capacity"]
        telemetry.gauge("solves.padded_fraction", **labels).set(
            0.0 if cells == 0 else 1.0 - stats["live_rows"] / cells
        )
