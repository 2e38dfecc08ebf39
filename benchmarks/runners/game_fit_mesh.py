"""Back-to-back whole GAME fits on one estimator built over a device mesh.

``game_fit`` with one difference: the estimator is handed the mesh that
``photon_tpu.drivers.train_game`` builds (``common.maybe_mesh()``: a 1-D data
mesh over every device), so the fixed effect's rows and every random
effect's entities are split over the chips and validation is sharded.  The
traffic file's ``mesh_devices`` says how many chips that has to be; any
other count is refused, on the host too (a rehearsal sets
``XLA_FLAGS=--xla_force_host_platform_device_count=<mesh_devices>``).

What holds of ``game_fit`` is taken from it (``step``, ``produced``,
``release``, ``compare``: a sharded table comes to the host by entity id as
a one-device table does).  What differs:

* ``work`` / ``floor`` give ONE chip's share of the fit (rows, validation
  rows and entities over the mesh size) against one chip's peak, so
  ``fit.mfu_pct`` is a share of the mesh's chips and not of one of them;
* ``counters`` also reduces the profiler's trace, while ``run.py`` still
  has it on disk, to what ``trace_reduce.reduce`` sums away: the seconds
  inside collective operations and each device's busy time
  (``benchmarks/trace_collectives.py``), under ``mesh_trace``;
* the reference is ``benchmarks/reference/game_blocked.py``: the same
  mathematics in row blocks and entity blocks, so that four chips' data
  fits the one device the reference runs on.
"""

from __future__ import annotations

import os

from benchmarks.runners import game_fit as one_chip

State = one_chip.State
step = one_chip.step
produced = one_chip.produced
release = one_chip.release
compare = one_chip.compare


def setup(config: dict, traffic: dict, seed: int, clock) -> State:
    import jax

    from benchmarks import generate
    from photon_tpu.drivers import common, train_game
    from photon_tpu.evaluation.evaluators import (
        MultiEvaluator,
        default_evaluators_for_task,
    )
    from photon_tpu.game.data import DenseShard, GameDataset
    from photon_tpu.game.estimator import (
        GameEstimator,
        GameOptimizationConfiguration,
    )
    from photon_tpu.telemetry import TelemetrySession

    wanted = int(traffic["mesh_devices"])
    mesh = common.maybe_mesh()
    found = 1 if mesh is None else mesh.devices.size
    if found != wanted:
        raise SystemExit(
            f"game_fit_mesh: the mesh is over {found} device(s) "
            f"({len(jax.devices())} visible); {traffic['name']} runs over "
            f"exactly {wanted}"
        )
    state = State()
    state.mesh_devices = wanted
    with clock("data"):
        data = generate.make(config, seed)
    state.data = data
    fit = traffic["fit"]
    task = config["task"]
    with clock("layout"):
        def dataset(split):
            shards = {"global": DenseShard(split.x_fixed)}
            for name in data.coordinates:
                shards[name] = DenseShard(split.x_random[name])
            return GameDataset.create(
                split.label, shards, id_columns=dict(split.entity_ids)
            )

        state.session = TelemetrySession("benchmarks.game_fit_mesh")
        state.estimator = GameEstimator(
            task, dataset(data.train),
            validation_data=dataset(data.validation),
            evaluators=MultiEvaluator(default_evaluators_for_task(task)),
            mesh=mesh, telemetry=state.session,
        )
        specs = [train_game.parse_coordinate_spec(s)
                 for s in fit["coordinates"]]
        ((label, coords, _),) = train_game._build_sweep(specs, task)
        state.configuration = GameOptimizationConfiguration(
            coordinates=coords,
            descent_iterations=int(fit["descent_iterations"]), name=label,
        )
        # Onboarding (entity binning, the sharded h2d) inside set-up's
        # layout phase, not in the first fit.
        for coord_config in coords.values():
            layout = state.estimator.device_layout(coord_config)
            jax.block_until_ready(getattr(layout, "batch", None))
    state.fit = fit
    state.max_quarantined = int(fit["max_quarantined"])
    state.fixed_dim = data.train.x_fixed.shape[1]
    state.random_dim = next(iter(data.train.x_random.values())).shape[1]
    state.last = None
    return state


def counters(state: State) -> dict:
    from benchmarks import run as harness, trace_collectives

    snapshot = one_chip.counters(state)
    if os.path.isdir(harness.TRACE_DIR):
        snapshot["mesh_trace"] = trace_collectives.reduce(harness.TRACE_DIR)
    return snapshot


def work(state: State, steps: list) -> dict:
    """One chip's share: the rows, validation rows and entities a chip
    holds at an even split; widths and iteration counts are the fit's."""
    whole = one_chip.work(state, steps)
    share = {key: whole[key] / state.mesh_devices
             for key in ("rows", "validation_rows", "entities")}
    return dict(whole, **share, mesh_devices=state.mesh_devices)


def floor(state: State, steps: list, peak: dict) -> dict:
    from benchmarks import rooflines

    return rooflines.game_fit_floor(work(state, steps), peak)


def reference(state: State, lowp: bool = False, weight=None) -> dict:
    from benchmarks.reference import game_blocked

    fit = state.fit
    return game_blocked.fit(state.data, {
        "l2": float(fit["reg_weight"]),
        "descent_iterations": int(fit["descent_iterations"]),
        "fixed_max_iterations": int(fit["fixed_max_iterations"]),
        "tolerance": float(fit["tolerance"]),
        "gradient_tolerance": float(fit["gradient_tolerance"]),
    }, lowp=lowp, weight=weight)


def check(state: State) -> dict:
    got = produced(state)
    release(state)
    return compare(got, reference(state))
