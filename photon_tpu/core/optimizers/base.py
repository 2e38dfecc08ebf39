"""Shared optimizer types: config, result, convergence reasons, states tracker.

Mirrors the reference's ``OptimizerConfig`` and ``OptimizationStatesTracker``
(photon-lib .../optimization — SURVEY.md §2.1, §5 'Tracing'): the tracker's
per-iteration (value, gradient-norm, convergence-reason) history is the main
observable of a training run and part of the public API surface.  Because the
loop runs inside jit, history is recorded into fixed-size device arrays and
materialized host-side afterwards.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


class ConvergenceReason:
    """Integer codes for why optimization stopped (jit-friendly enum).

    Matches the reference's convergence-reason semantics: max iterations,
    function-value tolerance, gradient tolerance.
    """

    NOT_CONVERGED = 0
    MAX_ITERATIONS = 1
    FUNCTION_VALUES_TOLERANCE = 2
    GRADIENT_TOLERANCE = 3
    OBJECTIVE_NOT_IMPROVING = 4  # line search failed to find descent

    NAMES = {
        0: "NOT_CONVERGED",
        1: "MAX_ITERATIONS",
        2: "FUNCTION_VALUES_TOLERANCE",
        3: "GRADIENT_TOLERANCE",
        4: "OBJECTIVE_NOT_IMPROVING",
    }


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Static (trace-time) optimizer configuration.

    ``tolerance`` is the relative function-value tolerance and
    ``gradient_tolerance`` the relative gradient-norm tolerance
    (``||g|| <= gtol * max(1, ||g0||)``), both checked each iteration as in
    the reference.  ``history_length`` is the L-BFGS memory; ``max_line_search``
    bounds the inner line-search loop (static for XLA).
    """

    max_iterations: int = 100
    tolerance: float = 1e-7
    gradient_tolerance: float = 1e-6
    history_length: int = 10
    max_line_search: int = 25
    # Inner-CG bounds (TRON and newton_cg).  0 -> a dimension-capped
    # per-solver default: min(dim, 100) for TRON (LIBLINEAR's constant),
    # min(dim, 256) for newton_cg (whose dims run past 100 by design).
    cg_max_iterations: int = 0
    cg_tolerance: float = 0.1

    def replace(self, **kw) -> "OptimizerConfig":
        return dataclasses.replace(self, **kw)


class OptimizerResult(NamedTuple):
    """Final state plus fixed-size per-iteration history (device arrays).

    ``history_*`` arrays have length ``max_iterations + 1`` (entry 0 is the
    initial point); entries at index > iterations are garbage — mask with
    ``history_valid``.
    """

    w: Array
    value: Array
    grad_norm: Array
    iterations: Array  # int32: number of outer iterations performed
    converged: Array  # bool
    reason: Array  # int32 ConvergenceReason code
    history_value: Array  # [max_iter+1]
    history_grad_norm: Array  # [max_iter+1]
    history_valid: Array  # [max_iter+1] bool
    # int32 total inner-CG iterations (one Hessian-vector product each),
    # set by the solvers with a CG inner loop (tron, newton_cg); None
    # elsewhere — a None leaf is an empty pytree subtree, so the other
    # solvers' jit/vmap programs are unchanged.
    cg_iterations: Array | None = None
    # int32 objective (value+grad) evaluations the fit ran: the initial
    # point, every line-search trial, the final polish steps.  What the
    # rooflines must multiply a pass over the data by; ``iterations + 1`` is
    # a floor on it.  None from solvers that do not count (the streamed
    # host-driven L-BFGS).
    evaluations: Array | None = None
    # int32 line-search trials among them (one per iteration when every
    # first step is accepted; each backtrack adds one).  None from solvers
    # with no line search (TRON).
    line_search_steps: Array | None = None
    # int32 trust-region trials rejected (the step not taken, the radius
    # shrunk); each is still an evaluation and an iteration.  Set by TRON
    # alone.
    trust_region_rejections: Array | None = None
    # int32 forward passes over the features (``X w``) that carried margins
    # made unnecessary: TRON under a ``tron.MarginForm`` spares two an
    # iteration, TRON on ``fun`` none.  None from the other solvers.
    margin_passes_spared: Array | None = None


def _optional_int(count) -> int | None:
    """Host int of an optional device count (summed over a vmapped axis)."""
    return None if count is None else int(np.sum(np.asarray(count)))


class OptimizationStatesTracker:
    """Host-side view of an optimization run's per-iteration history.

    API-parity object for the reference's OptimizationStatesTracker: iterate
    to get (iteration, value, gradient norm), query the convergence reason.
    """

    def __init__(self, result: OptimizerResult, wall_time_s: float | None = None):
        valid = np.asarray(result.history_valid)
        self.values = np.asarray(result.history_value)[valid]
        self.grad_norms = np.asarray(result.history_grad_norm)[valid]
        self.iterations = int(result.iterations)
        self.converged = bool(result.converged)
        self.reason_code = int(result.reason)
        self.wall_time_s = wall_time_s
        self.evaluations = _optional_int(result.evaluations)
        self.line_search_steps = _optional_int(result.line_search_steps)

    @property
    def convergence_reason(self) -> str:
        return ConvergenceReason.NAMES.get(self.reason_code, "UNKNOWN")

    def __iter__(self):
        return iter(zip(range(len(self.values)), self.values, self.grad_norms))

    def states(self) -> list:
        """JSON-ready per-iteration trace ``[[value, |grad|], ...]`` — the
        reference dumps this tracker to logs; drivers keep it in
        training_summary.json so convergence curves survive the run
        (SURVEY.md §5 tracing)."""
        return [[float(v), float(g)] for _, v, g in self]

    def record_to(self, registry, **labels) -> None:
        """Push this run's summary into a telemetry metrics registry
        (photon_tpu.telemetry; duck-typed so ``core/optimizers`` stays
        import-free of it — ``core/problem.py``, one layer up, is what hands
        the process registry its deferred counts): solve counts, iteration totals, a stop-reason
        breakdown, solve-seconds distribution, and the final value."""
        labels = {k: str(v) for k, v in labels.items()}
        registry.counter("optimizer.solves", **labels).inc()
        registry.counter("optimizer.iterations", **labels).inc(self.iterations)
        if self.evaluations is not None:
            registry.counter("optimizer.evaluations", **labels).inc(
                self.evaluations
            )
        if self.line_search_steps is not None:
            registry.counter("optimizer.line_search_steps", **labels).inc(
                self.line_search_steps
            )
        if self.converged:
            registry.counter("optimizer.converged_solves", **labels).inc()
        registry.counter(
            "optimizer.stop_reason", reason=self.convergence_reason, **labels
        ).inc()
        if self.wall_time_s is not None:
            registry.histogram("optimizer.solve_seconds", **labels).observe(
                self.wall_time_s
            )
        if len(self.values):
            registry.gauge("optimizer.final_value", **labels).set(
                float(self.values[-1])
            )

    def summary(self) -> str:
        lines = [
            f"iterations={self.iterations} converged={self.converged} "
            f"reason={self.convergence_reason}"
            + (f" wall={self.wall_time_s:.3f}s" if self.wall_time_s is not None else "")
        ]
        for i, v, g in self:
            lines.append(f"  iter {i:4d}  f={v:.10g}  |g|={g:.6g}")
        return "\n".join(lines)


def init_history(max_iterations: int, f0: Array, gnorm0: Array):
    """History arrays with slot 0 holding the initial point."""
    n = max_iterations + 1
    hv = jnp.zeros(n, dtype=f0.dtype).at[0].set(f0)
    hg = jnp.zeros(n, dtype=gnorm0.dtype).at[0].set(gnorm0)
    valid = jnp.zeros(n, dtype=bool).at[0].set(True)
    return hv, hg, valid


def record_history(hv, hg, valid, idx, f, gnorm, active):
    """Write (f, |g|) into slot ``idx`` when ``active`` (masked for vmap)."""
    hv = hv.at[idx].set(jnp.where(active, f, hv[idx]))
    hg = hg.at[idx].set(jnp.where(active, gnorm, hg[idx]))
    valid = valid.at[idx].set(valid[idx] | active)
    return hv, hg, valid


def reason_is_converged(reason: Array) -> Array:
    """True only for genuine convergence (tolerance met) — not for running
    out of iterations or a failed line search."""
    return (reason == ConvergenceReason.FUNCTION_VALUES_TOLERANCE) | (
        reason == ConvergenceReason.GRADIENT_TOLERANCE
    )


def tree_where(pred: Array, a, b):
    """Elementwise select over a pytree (per-lane freeze for vmapped loops)."""
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def check_convergence(
    f_new: Array,
    f_old: Array,
    gnorm: Array,
    gnorm0: Array,
    config: OptimizerConfig,
):
    """Return (converged, reason) per the reference's tolerance semantics."""
    rel_improve = jnp.abs(f_old - f_new) / jnp.maximum(jnp.abs(f_old), 1e-12)
    f_conv = rel_improve <= config.tolerance
    g_conv = gnorm <= config.gradient_tolerance * jnp.maximum(gnorm0, 1.0)
    reason = jnp.where(
        g_conv,
        ConvergenceReason.GRADIENT_TOLERANCE,
        jnp.where(
            f_conv,
            ConvergenceReason.FUNCTION_VALUES_TOLERANCE,
            ConvergenceReason.NOT_CONVERGED,
        ),
    )
    return f_conv | g_conv, reason
