"""Optimization problems: optimizer + objective + variance, bound together.

Rebuild of the reference's ``DistributedOptimizationProblem`` /
``SingleNodeOptimizationProblem`` (photon-api .../optimization — SURVEY.md
§2.2): a problem owns an objective (local or distributed), an optimizer
choice, regularization, and optional per-coefficient variance computation
(``VarianceComputationType`` NONE/SIMPLE — diagonal-Hessian inverse, the
GLMix posterior approximation).

One class serves both roles: the objective it is built with decides whether
gradients psum over a mesh (DistributedGlmObjective) or stay local
(GlmObjective) — the optimizer code cannot tell the difference.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp

from photon_tpu.core.objective import GlmObjective, RegularizationContext
from photon_tpu.core.optimizers import (
    OptimizerConfig,
    get_optimizer,
    lbfgs,
    newton_cg,
    owlqn,
    tron,
)
from photon_tpu.data.batch import Batch
from photon_tpu.models.glm import Coefficients

Array = jax.Array

VARIANCE_TYPES = ("none", "simple", "full")


@dataclasses.dataclass(frozen=True)
class ProblemConfig:
    """Per-coordinate training configuration (optimizer + regularization +
    tolerances), the analog of the reference's optimization configs."""

    optimizer: str = "lbfgs"
    regularization: RegularizationContext = RegularizationContext()
    optimizer_config: OptimizerConfig = OptimizerConfig()
    variance_computation: str = "none"

    def __post_init__(self):
        get_optimizer(self.optimizer)  # validate early
        if self.variance_computation not in VARIANCE_TYPES:
            raise ValueError(
                f"unknown variance computation {self.variance_computation!r}"
            )
        if self.regularization.l1_weight > 0 and self.optimizer.lower() not in (
            "owlqn",
            "owl-qn",
        ):
            raise ValueError(
                "L1/elastic-net regularization requires the OWL-QN optimizer "
                "(the reference enforces the same pairing)"
            )

    def replace(self, **kw) -> "ProblemConfig":
        return dataclasses.replace(self, **kw)


def hvp_at_for(objective, batch: Batch):
    """Curvature-operator factory for Newton-CG: ``w -> (v -> H(w)·v)``.

    Plain :class:`GlmObjective`s expose ``hvp_operator`` (per-row curvature
    precomputed once per outer iteration — each CG step is two matvecs);
    objectives without it (the distributed/row-split wrappers) fall back
    to a per-call ``hessian_vector``, which is still matrix-free."""
    op = getattr(objective, "hvp_operator", None)
    if op is not None:
        return lambda w: op(w, batch)
    return lambda w: (lambda v: objective.hessian_vector(w, v, batch))


def _run_fit(objective, batch: Batch, w0: Array, *, optimizer: str,
             cfg: OptimizerConfig, variance: str, vmapped: bool = False):
    """One GLM fit, pure in (objective, batch, w0) — the body every cached
    solver compiles.  The objective is a PYTREE ARGUMENT (reg weights and
    normalization arrays are dynamic leaves), so one compiled program serves
    an entire lambda sweep / hyperparameter search; only shapes, the loss,
    the optimizer, and its static config retrace.  ``vmapped``: the body of
    the per-entity bucket solve."""
    fun = lambda w: objective.value_and_grad(w, batch)  # noqa: E731
    if optimizer in ("owlqn", "owl-qn"):
        result = owlqn(fun, w0, cfg, l1_weight=objective.l1_weight)
    elif optimizer == "tron":
        # The objective's margin form where it has one: TRON carries the
        # margins, and an iteration makes no forward pass of its own.  Else
        # the precomputed-curvature operator (hvp_operator): margins/D(w)
        # once per trust-region iteration, two matvecs per CG product
        # (objectives without hvp_operator fall back to per-call
        # hessian_vector inside hvp_at_for, still matrix-free).  Not for
        # the entity lanes: those small fits run on to the objective's
        # float32 resolution, where carried and fresh margins round apart
        # and a trial can go the other way, and the pass spared is one
        # entity's [rows, d] matvec; they keep today's iterates bit for bit.
        make_form = None if vmapped else getattr(objective, "tron_form", None)
        form = None if make_form is None else make_form(
            batch, int(w0.shape[0]))
        if form is None:
            result = tron(fun, w0, cfg, hvp_at=hvp_at_for(objective, batch))
        else:
            result = tron(None, w0, cfg, form=form)
    elif optimizer in ("newton_cg", "newton-cg"):
        result = newton_cg(
            fun, w0, cfg,
            hvp_at=hvp_at_for(objective, batch),
            diag=lambda w: objective.hessian_diagonal(w, batch),
        )
    else:
        result = lbfgs(fun, w0, cfg)
    coefficients = Coefficients(
        means=result.w,
        variances=_compute_variances(objective, variance, result.w, batch),
    )
    return coefficients, result


def cached_solver(optimizer: str, cfg: OptimizerConfig, variance: str,
                  vmapped: bool = False):
    """The jit-compiled solver for one static problem configuration.

    Signature of the returned callable: ``(objective, batch, w0)`` —
    ``vmapped=True`` maps (batch, w0) over a leading entity axis with the
    objective held constant (the GAME random-effect bucket solve).  Cached at
    module level so every coordinate, sweep config, and tuning trial with the
    same static configuration shares one traced program (jit's own cache then
    keys on shapes + objective pytree structure).  The cache is BOUNDED: each
    entry pins its compiled executables for the process lifetime (the hazard
    core/variance.py documents), so a search varying static keys (tolerances,
    max_iterations) evicts old solvers instead of growing without limit —
    eviction only costs a retrace on reuse."""
    # Normalize + reject typos BEFORE the lru_cache key is formed: _run_fit
    # dispatches on exact lowercase names and its else-branch is lbfgs, and
    # lowercasing outside the cache keeps 'TRON'/'tron' from occupying two
    # cache slots.
    optimizer = optimizer.lower()
    get_optimizer(optimizer)
    if variance not in VARIANCE_TYPES:
        raise ValueError(f"unknown variance computation {variance!r}")
    return _cached_solver(optimizer, cfg, variance, vmapped)


@functools.lru_cache(maxsize=32)
def _cached_solver(optimizer: str, cfg: OptimizerConfig, variance: str,
                   vmapped: bool):
    from photon_tpu.utils.device import named_jit

    run = functools.partial(_run_fit, optimizer=optimizer, cfg=cfg,
                            variance=variance, vmapped=vmapped)
    if vmapped:
        run = jax.vmap(run, in_axes=(None, 0, 0))
    # The device program's published name: jit_glm_fit_lbfgs, or
    # jit_entity_fit_lbfgs for the vmapped bucket solve.
    kind = "entity_fit" if vmapped else "glm_fit"
    return named_jit(f"{kind}_{optimizer.replace('-', '_')}", run)


class GlmOptimizationProblem:
    """Runs one GLM fit: ``run(batch, w0) -> (Coefficients, OptimizerResult)``.

    ``objective`` may be a plain :class:`GlmObjective` (single-node path) or a
    :class:`~photon_tpu.parallel.distributed.DistributedGlmObjective`
    (mesh path); both expose the same evaluation methods.
    """

    def __init__(self, objective, config: ProblemConfig):
        self.objective = objective
        self.config = config

    def solver(self, vmapped: bool = False):
        """This problem's shared jitted solver (see :func:`cached_solver`)."""
        return cached_solver(
            self.config.optimizer.lower(),
            self.config.optimizer_config,
            self.config.variance_computation,
            vmapped,
        )

    def run(
        self, batch: Batch, w0: Optional[Array] = None, dim: Optional[int] = None
    ):
        if w0 is None:
            if dim is None:
                raise ValueError("need w0 or dim")
            w0 = jnp.zeros(dim, jnp.float32)
        coefficients, result = self.solver()(self.objective, batch, w0)
        if result.evaluations is not None and not isinstance(
            result.evaluations, jax.core.Tracer
        ):
            # The counts live on the device until the fit ends: handed to
            # the process registry as they are, fetched at its next
            # snapshot, never on this (possibly timed) path.  (Under an
            # enclosing trace there is no value to hand over.)
            from photon_tpu.telemetry import process_registry

            registry = process_registry()
            registry.counter("optimizer.evaluations").inc_deferred(
                result.evaluations
            )
            for name in ("line_search_steps", "cg_iterations",
                         "trust_region_rejections", "margin_passes_spared"):
                count = getattr(result, name)
                if count is not None:
                    registry.counter(f"optimizer.{name}").inc_deferred(count)
        return coefficients, result

    def compute_variances(self, w: Array, batch: Batch) -> Optional[Array]:
        return _compute_variances(
            self.objective, self.config.variance_computation, w, batch
        )


def _compute_variances(objective, kind: str, w: Array, batch: Batch) -> Optional[Array]:
    """Per-coefficient posterior variances at the optimum (SURVEY.md
    §2.2 'L2 + variance'): SIMPLE = 1/diag(H); FULL = diag(H⁻¹) — a
    Cholesky solve of the dense Hessian up to FULL_DENSE_MAX_DIM, a
    matrix-free CG/Hutchinson estimate above it (the dense ``[d, d]``
    materialization is a 256 GB allocation at the bench dimension —
    see core/variance.py)."""
    if kind == "none":
        return None
    if kind == "full":
        from photon_tpu.core.variance import (
            FULL_DENSE_MAX_DIM,
            hutchinson_diag_inverse,
        )

        d = int(w.shape[0])
        if d > FULL_DENSE_MAX_DIM:
            return hutchinson_diag_inverse(
                lambda v: objective.hessian_vector(w, v, batch),
                dim=d,
            )
        h = objective.hessian_matrix(w, batch)
        # Tiny jitter keeps the factorization defined for flat
        # directions (e.g. unreached features with zero curvature).
        chol = jax.scipy.linalg.cho_factor(h + 1e-9 * jnp.eye(d, dtype=h.dtype))
        inv = jax.scipy.linalg.cho_solve(chol, jnp.eye(d, dtype=h.dtype))
        return jnp.maximum(jnp.diagonal(inv), 0.0)
    diag = objective.hessian_diagonal(w, batch)
    return 1.0 / jnp.maximum(diag, 1e-12)
