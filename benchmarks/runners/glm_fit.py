"""Back-to-back whole fits of a sparse fixed-effect GLM.

The batch is prepared as ``photon_tpu.drivers.train._run_resident`` prepares
it on one device (``attach_feature_major``, with the aligned layout when
``aligned_layout_wanted`` says so) and every step is one
``GlmOptimizationProblem.run(batch, w0)`` from w = 0, ended by
``block_until_ready`` on the fitted coefficients.
"""

from __future__ import annotations

import types

import numpy as np


State = types.SimpleNamespace


def setup(config: dict, traffic: dict, seed: int, clock) -> State:
    import jax
    import jax.numpy as jnp

    from benchmarks import generate
    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig
    from photon_tpu.data.batch import SparseBatch, attach_feature_major
    from photon_tpu.ops.sparse_grad_select import aligned_layout_wanted

    state = State()
    with clock("data"):
        data = generate.make(config, seed)
    state.data = data
    with clock("layout"):
        n = data.rows
        batch = SparseBatch(
            ids=jnp.asarray(data.ids), vals=jnp.asarray(data.vals),
            label=jnp.asarray(data.label),
            offset=jnp.zeros(n, jnp.float32), weight=jnp.ones(n, jnp.float32),
        )
        batch = attach_feature_major(
            batch,
            aligned_dim=data.dim
            if aligned_layout_wanted(int(batch.ids.size)) else None,
        )
        jax.block_until_ready(batch)
    fit = traffic["fit"]
    reg = RegularizationContext(fit["reg_type"], float(fit["reg_weight"]))
    state.problem = GlmOptimizationProblem(
        GlmObjective.create(config["task"], reg),
        ProblemConfig(
            optimizer=fit["optimizer"], regularization=reg,
            optimizer_config=OptimizerConfig(
                max_iterations=int(fit["max_iterations"]),
                tolerance=float(fit["tolerance"]),
                gradient_tolerance=float(fit["gradient_tolerance"]),
            ),
        ),
    )
    state.batch = batch
    state.w0 = jnp.zeros(data.dim, jnp.float32)
    state.fit = fit
    state.history_length = state.problem.config.optimizer_config.history_length
    state.last = None
    return state


def step(state: State) -> dict:
    import jax

    coefficients, result = state.problem.run(state.batch, state.w0)
    jax.block_until_ready(coefficients.means)
    state.last = (coefficients, result)
    return {"iterations": int(result.iterations)}


def counters(state: State) -> dict:
    """What the program counted (kernel selections are process-wide)."""
    from photon_tpu.utils.device import kernel_metrics

    return {"counters": kernel_metrics(), "gauges": []}


def work(state: State, steps: list) -> dict:
    return {
        "entries": state.data.entries, "dim": state.data.dim,
        "rows": state.data.rows, "history_length": state.history_length,
        "iterations": float(np.mean([s["iterations"] for s in steps])),
    }


def floor(state: State, steps: list, peak: dict) -> dict:
    from benchmarks import rooflines

    return rooflines.glm_fit_floor(work(state, steps), peak)


def produced(state: State) -> dict:
    """The last timed fit's outputs, on the host."""
    coefficients, result = state.last
    valid = np.asarray(result.history_valid)
    return {
        "w": np.asarray(coefficients.means),
        "values": [float(v) for v in np.asarray(result.history_value)[valid]]
        + [float(result.value)],
        "grad_norms": [
            float(g) for g in np.asarray(result.history_grad_norm)[valid]
        ],
        "iterations": int(result.iterations),
    }


def release(state: State) -> None:
    import gc

    import jax

    state.batch = state.problem = state.w0 = state.last = None
    jax.clear_caches()
    gc.collect()


def reference(state: State, lowp: bool = False, weight=None) -> dict:
    from benchmarks.reference import glm

    fit = state.fit
    return glm.fit(
        state.data, float(fit["reg_weight"]), int(fit["max_iterations"]),
        float(fit["tolerance"]), float(fit["gradient_tolerance"]),
        lowp=lowp, weight=weight,
    )


def compare(got: dict, want: dict) -> dict:
    """The numbers ``correct`` is decided on: every step's loss (the start,
    each iteration both sides ran, and the end after the polish), the first
    gradient's norm, the norm of the coefficients' change over the fit (the
    start is w = 0) and the distance between the two fitted vectors.  The
    budget is fixed (the tolerances never fire), so both sides run the same
    number of iterations unless a line search fails outright; the end of the
    fit is compared with the end of the fit either way."""
    pairs = list(zip(got["values"][:-1], want["values"][:-1]))
    pairs.append((got["values"][-1], want["values"][-1]))
    norm = float(np.linalg.norm(want["w"]))
    return {
        "loss_gap": max(abs(g - w) / abs(w) for g, w in pairs),
        "grad0_gap": abs(got["grad_norms"][0] - want["grad_norms"][0])
        / want["grad_norms"][0],
        "dw_norm_gap": abs(float(np.linalg.norm(got["w"])) - norm) / norm,
        "dw_diff": float(np.linalg.norm(got["w"] - want["w"])) / norm,
        "iterations_gap": float(abs(got["iterations"] - want["iterations"])),
    }


def check(state: State) -> dict:
    got = produced(state)
    release(state)
    return compare(got, reference(state))
