"""Back-to-back whole TRON fits of a sparse fixed-effect Poisson GLM.

``glm_fit`` with a second-order optimizer: the batch is attached as
``glm_fit`` attaches it (``attach_feature_major``, the probe's verdict
first) and every step is one ``GlmOptimizationProblem.run(batch, w0)`` from
w = 0 with ``optimizer="tron"``, ended by ``block_until_ready`` on the fitted
coefficients.  What differs:

* the data: ``benchmarks/generate_glm_poisson.py`` (count labels);
* the fit: the traffic's CG cap and CG tolerance go to the optimizer;
* ``step`` also returns the CG steps (Hessian-vector products) and the
  rejected trust-region trials the program reported for the fit;
* ``work`` / ``floor``: ``benchmarks/rooflines_glm_tron.py``, from the
  program's own evaluation and CG counts;
* the reference: ``benchmarks/reference/glm_tron.py``, and ``compare`` also
  holds the two CG counts against each other.

A program whose ``OptimizerResult`` carries no ``trust_region_rejections``
(the parent of the PR that added this cell) does not count TRON's CG steps,
so the cell's correctness cannot be decided on it: ``setup`` exits 1 before
it makes any data.
"""

from __future__ import annotations

import numpy as np

from benchmarks.runners import glm_fit

State = glm_fit.State
counters = glm_fit.counters
release = glm_fit.release


def setup(config: dict, traffic: dict, seed: int, clock) -> State:
    import jax
    import jax.numpy as jnp

    from benchmarks import generate_glm_poisson
    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig, OptimizerResult
    from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig
    from photon_tpu.data.batch import SparseBatch, attach_feature_major
    from photon_tpu.ops.sparse_grad_select import aligned_layout_wanted

    if "trust_region_rejections" not in OptimizerResult._fields:
        raise SystemExit(
            "glm_tron_fit: this program's TRON reports no CG count "
            "(OptimizerResult has no trust_region_rejections): the cell "
            "compares the CG count with the reference's and cannot run here")
    state = State()
    with clock("data"):
        data = generate_glm_poisson.glm_poisson(config["sizes"], seed)
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
                cg_max_iterations=int(fit["cg_max_iterations"]),
                cg_tolerance=float(fit["cg_tolerance"]),
            ),
        ),
    )
    state.batch = batch
    state.w0 = jnp.zeros(data.dim, jnp.float32)
    state.fit = fit
    state.last = None
    return state


def step(state: State) -> dict:
    import jax

    coefficients, result = state.problem.run(state.batch, state.w0)
    jax.block_until_ready(coefficients.means)
    state.last = (coefficients, result)
    return {"iterations": int(result.iterations),
            "evaluations": int(result.evaluations),
            "cg_iterations": int(result.cg_iterations),
            "rejections": int(result.trust_region_rejections)}


def work(state: State, steps: list) -> dict:
    def mean(key):
        return float(np.mean([s[key] for s in steps]))

    return {
        "entries": state.data.entries, "dim": state.data.dim,
        "rows": state.data.rows, "iterations": mean("iterations"),
        "evaluations": mean("evaluations"),
        "cg_iterations": mean("cg_iterations"),
    }


def floor(state: State, steps: list, peak: dict) -> dict:
    from benchmarks import rooflines_glm_tron

    return rooflines_glm_tron.glm_tron_fit_floor(work(state, steps), peak)


def produced(state: State) -> dict:
    """The last timed fit's outputs, on the host."""
    out = glm_fit.produced(state)
    out["cg_iterations"] = int(state.last[1].cg_iterations)
    return out


def reference(state: State, lowp: bool = False, weight=None) -> dict:
    from benchmarks.reference import glm_tron

    fit = state.fit
    return glm_tron.fit(
        state.data, float(fit["reg_weight"]), int(fit["max_iterations"]),
        int(fit["cg_max_iterations"]), float(fit["cg_tolerance"]),
        float(fit["tolerance"]), float(fit["gradient_tolerance"]),
        lowp=lowp, weight=weight,
    )


def compare(got: dict, want: dict) -> dict:
    """``glm_fit.compare``'s numbers (every accepted step's objective and the
    end's, the first gradient's norm, the gap of the norms of the change of
    w and the norm of their difference, relative) and the gap of the CG
    counts: the budget is fixed and no tolerance fires, so both sides run 3
    trust-region iterations and a CG solve ends at the cap or at the trust
    boundary; a different count is a different fit."""
    numbers = glm_fit.compare(got, want)
    numbers["cg_gap"] = float(abs(got["cg_iterations"]
                                  - want["cg_iterations"]))
    return numbers


def check(state: State) -> dict:
    got = produced(state)
    release(state)
    return compare(got, reference(state))
