"""Back-to-back whole GAME fits on one estimator built once.

The estimator, its coordinate configurations and the configuration fitted
are built as ``photon_tpu.drivers.train_game._run`` builds them (coordinate
specs through ``parse_coordinate_spec`` / ``_build_sweep``), so the data
onboarded to the device is reused across fits as it is across a lambda
sweep.  Every step is one ``GameEstimator.fit([configuration])``, ended by
``block_until_ready`` on the fitted tables.
"""

from __future__ import annotations

import types

import numpy as np


State = types.SimpleNamespace


def setup(config: dict, traffic: dict, seed: int, clock) -> State:
    import jax

    from benchmarks import generate
    from photon_tpu.drivers import train_game
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

    state = State()
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

        state.session = TelemetrySession("benchmarks.game_fit")
        state.estimator = GameEstimator(
            task, dataset(data.train),
            validation_data=dataset(data.validation),
            evaluators=MultiEvaluator(default_evaluators_for_task(task)),
            mesh=None, telemetry=state.session,
        )
        specs = [train_game.parse_coordinate_spec(s)
                 for s in fit["coordinates"]]
        ((label, coords, _),) = train_game._build_sweep(specs, task)
        state.configuration = GameOptimizationConfiguration(
            coordinates=coords,
            descent_iterations=int(fit["descent_iterations"]), name=label,
        )
        # Onboarding (entity binning, h2d) happens on first use: do it here,
        # inside set-up's layout phase, not in the first fit.
        for coord_config in coords.values():
            layout = state.estimator.device_layout(coord_config)
            jax.block_until_ready(getattr(layout, "batch", None))
    state.fit = fit
    state.max_quarantined = int(fit["max_quarantined"])
    state.fixed_dim = data.train.x_fixed.shape[1]
    state.random_dim = next(iter(data.train.x_random.values())).shape[1]
    state.last = None
    return state


def _fit_counts(state: State) -> dict:
    """The program's running totals that a fit moves."""
    session = state.session
    return {
        "fixed_iterations": session.counter(
            "optimizer.iterations", coordinate="fixed").value,
        "fixed_fits": session.counter(
            "optimizer.solves", coordinate="fixed").value,
        "host_syncs": session.counter(
            "descent.host_syncs", kind="stats").value,
    }


def step(state: State) -> dict:
    import jax

    before = _fit_counts(state)
    (result,) = state.estimator.fit(
        [state.configuration], max_quarantined=state.max_quarantined,
    )
    model = result.descent.last_model
    jax.block_until_ready([
        c.table if hasattr(c, "table") else c.coefficients.means
        for c in model.coordinates.values()
    ])
    state.last = result
    counts = {k: v - before[k] for k, v in _fit_counts(state).items()}
    # The gauge holds each coordinate's LAST iteration's lockstep Newton
    # count: a floor on what the earlier, colder iterations ran.
    counts["newton_iterations_last"] = sum(
        m["value"] for m in state.session.registry.snapshot()["gauges"]
        if m["name"] == "re_solver.iterations_max"
    )
    return counts


def counters(state: State) -> dict:
    from photon_tpu.utils.device import kernel_metrics

    snapshot = state.session.registry.snapshot()
    snapshot["counters"] = snapshot["counters"] + kernel_metrics()
    return snapshot


def work(state: State, steps: list) -> dict:
    mean = lambda key: float(np.mean([s[key] for s in steps]))  # noqa: E731
    descent = int(state.fit["descent_iterations"])
    return {
        "rows": state.data.train.rows,
        "validation_rows": state.data.validation.rows,
        "entities": state.data.n_entities,
        "fixed_dim": state.fixed_dim, "random_dim": state.random_dim,
        "random_coordinates": len(state.data.coordinates),
        "descent_iterations": descent,
        "fixed_iterations": mean("fixed_iterations"),
        "fixed_fits": mean("fixed_fits"),
        "random_newton_iterations": mean("newton_iterations_last") * descent,
    }


def floor(state: State, steps: list, peak: dict) -> dict:
    from benchmarks import rooflines

    return rooflines.game_fit_floor(work(state, steps), peak)


def produced(state: State) -> dict:
    """The last timed fit's outputs, on the host: coefficients by entity id,
    the fixed effect's final objective, the validation metrics of every
    descent iteration."""
    result = state.last
    coefficients = {}
    for name, coord in result.descent.last_model.coordinates.items():
        if hasattr(coord, "table"):
            table = np.zeros(
                (state.data.n_entities, coord.table.shape[1]), np.float32
            )
            table[np.asarray(coord.keys)] = np.asarray(coord.table)
            coefficients[coord.entity_column] = table
        else:
            coefficients["fixed"] = np.asarray(coord.coefficients.means)
    final_value = [
        m["value"] for m in state.session.registry.snapshot()["gauges"]
        if m["name"] == "optimizer.final_value"
        and m["labels"].get("coordinate") == "fixed"
    ]
    return {
        "coefficients": coefficients,
        "fixed_final_value": final_value[0],
        "metrics": [dict(h["metrics"]) for h in result.descent.history],
    }


def release(state: State) -> None:
    import gc

    import jax

    state.estimator = state.configuration = state.last = None
    state.session = None
    jax.clear_caches()
    gc.collect()


def reference(state: State, lowp: bool = False, weight=None) -> dict:
    from benchmarks.reference import game

    fit = state.fit
    return game.fit(state.data, {
        "l2": float(fit["reg_weight"]),
        "descent_iterations": int(fit["descent_iterations"]),
        "fixed_max_iterations": int(fit["fixed_max_iterations"]),
        "tolerance": float(fit["tolerance"]),
        "gradient_tolerance": float(fit["gradient_tolerance"]),
    }, lowp=lowp, weight=weight)


def compare(got: dict, want: dict) -> dict:
    """Every descent iteration's validation loss and AUC (all coordinates'
    scores on rows the fit never saw), the fixed effect's training objective
    at the end of its last fit (offsets from both random effects), and each
    coefficient leaf's norm and distance from the reference's, by the worst
    leaf, against the larger of that leaf's norm and the median leaf's."""
    got, want = (
        dict(side, fixed_final_value=side["fixed_values"][-1])
        if "fixed_values" in side else side for side in (got, want)
    )
    steps = min(len(got["metrics"]), len(want["metrics"]))
    numbers = {
        "val_loss_gap": max(
            abs(g["LOGISTIC_LOSS"] - w["LOGISTIC_LOSS"]) / w["LOGISTIC_LOSS"]
            for g, w in zip(got["metrics"][:steps], want["metrics"][:steps])
        ),
        "val_auc_gap": max(
            abs(g["AUC"] - w["AUC"])
            for g, w in zip(got["metrics"][:steps], want["metrics"][:steps])
        ),
        "fixed_loss_gap": abs(
            got["fixed_final_value"] - want["fixed_final_value"]
        ) / abs(want["fixed_final_value"]),
    }
    if len(got["metrics"]) != len(want["metrics"]):
        numbers["val_loss_gap"] = max(numbers["val_loss_gap"], 1.0)
    norms = {k: float(np.linalg.norm(v))
             for k, v in want["coefficients"].items()}
    median = float(np.median(list(norms.values())))
    numbers["coef_norm_gap"] = max(
        abs(float(np.linalg.norm(got["coefficients"][k])) - norms[k])
        / max(norms[k], median) for k in norms
    )
    numbers["coef_diff"] = max(
        float(np.linalg.norm(got["coefficients"][k] - want["coefficients"][k]))
        / max(norms[k], median) for k in norms
    )
    return numbers


def check(state: State) -> dict:
    got = produced(state)
    release(state)
    return compare(got, reference(state))
