"""Distributed GLM objective: per-shard evaluation + explicit ICI collectives.

The TPU-native rebuild of the reference's ``DistributedGLMLossFunction``
(photon-api .../function/glm — SURVEY.md §3.4): where the reference broadcasts
coefficients, folds each RDD partition through a ``ValueAndGradientAggregator``
and tree-reduces (gradient, value) pairs to the driver once per optimizer
iteration, here the *loss value* is a ``shard_map`` program — local weighted
loss per shard, ``lax.psum`` over the mesh's data axis — and derivatives come
from differentiating straight through it (``jax.value_and_grad`` /
``jax.jvp``), which transposes the psum correctly under JAX's varying-axes
semantics.  One fused XLA program per optimizer *run*, no host round-trips,
coefficients resident and replicated in device memory.

The optimizer is oblivious: it receives a ``fun(w) -> (value, grad)`` whose
collectives are internal, so the same L-BFGS/OWL-QN/TRON code drives
single-chip and pod-scale training (the reference's Optimizer/ObjectiveFunction
split, kept).
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from photon_tpu.core.objective import GlmObjective, _static_zero
from photon_tpu.data.batch import LAYOUT_FIELDS, Batch
from photon_tpu.ops.sparse_grad_select import differentiable, pinned_kernel
from photon_tpu.parallel.mesh import DATA_AXIS

Array = jax.Array

# One-shot flag for the multi-process auto-pin notice (_sparse_kernel is
# on the per-step hot path).
_MP_AUTO_PIN_LOGGED = False


def _aux_is_stacked(v) -> bool:
    """True when a batch layout carries a leading shard axis: the aligned
    layout's 2-D index plane ``lo`` reads rank 3."""
    from photon_tpu.ops.pallas_gather import AlignedLayoutDev

    return isinstance(v, AlignedLayoutDev) and v.lo.ndim == 3


class DistributedGlmObjective:
    """Binds a :class:`GlmObjective` to a mesh data axis.

    Methods mirror the single-node objective so optimization problems can be
    built against either (SURVEY.md §2.2 Distributed/SingleNode split).
    """

    def __init__(self, obj: GlmObjective, mesh: Mesh, axis_name: str = DATA_AXIS):
        self.obj = obj
        self.mesh = mesh
        self.axis_name = axis_name

    @property
    def l1_weight(self):
        """Mirrors GlmObjective so optimization problems treat both alike."""
        return self.obj.l1_weight

    # -- spec helpers ---------------------------------------------------------
    def _batch_specs(self, batch: Batch):
        return jax.tree.map(
            lambda leaf: P(self.axis_name, *([None] * (leaf.ndim - 1))), batch
        )

    def _squeeze_local_aux(self, local: Batch) -> Batch:
        """Inside shard_map: drop the leading shard axis from STACKED
        layouts so each device hands its block's layout to the kernels in
        their single-block form.  Stacked-ness is a SHAPE property
        (index-plane rank 3 instead of 2) — not a mesh-size inference: a
        1-device-per-process multi-host assembly is stacked at axis length
        1, while a 1-device local mesh with a single-block attach is not.
        The fm aux keeps its (always-present) block axis —
        _fm_segment_grad consumes it directly."""
        for aux in LAYOUT_FIELDS:
            v = getattr(local, aux, None)
            if v is not None and _aux_is_stacked(v):
                local = local._replace(
                    **{aux: jax.tree.map(lambda x: x[0], v)}
                )
        return local

    def _sparse_kernel(self, w: Array, batch: Batch):
        """The measured kernel choice for this batch/backend — any of the
        static-layout kernels now runs per shard (VERDICT r5 item 2).

        MULTI-PROCESS auto mode pins to the generic autodiff path: the
        selection is a per-host wall-clock measurement, and hosts
        measuring different winners would build different shard_map
        programs — mismatched collective sequences hang the job rather
        than falling back.  This mirrors the drivers' determinism pin
        (README determinism note); pin ``PHOTON_SPARSE_GRAD`` explicitly
        to run a fast kernel on a multi-process mesh — a forced choice
        is identical on every host by construction."""
        if pinned_kernel() is None and jax.process_count() > 1:
            global _MP_AUTO_PIN_LOGGED
            if not _MP_AUTO_PIN_LOGGED:
                _MP_AUTO_PIN_LOGGED = True
                import logging

                logging.getLogger("photon_tpu.distributed").info(
                    "multi-process auto mode pins the sharded objective "
                    "to autodiff (per-host probes could disagree); set "
                    "PHOTON_SPARSE_GRAD=fm|pallas to run a fast kernel"
                )
            return None
        return self.obj._sparse_kernel(batch, int(w.shape[0]))

    # -- distributed value (the one shard_map program) ------------------------
    def value(self, w: Array, batch: Batch) -> Array:
        """Global objective: psum of per-shard weighted losses + L2 once."""
        ax = self.axis_name

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(P(), self._batch_specs(batch)),
            out_specs=P(),
        )
        def _v(w, local):
            return lax.psum(self.obj.data_value(w, local), ax)

        v = _v(w, batch)
        if not _static_zero(self.obj.l2_weight):
            v = v + 0.5 * self.obj.l2_weight * jnp.dot(w, w)
        return v

    # -- derivatives: differentiate through the psum --------------------------
    def value_and_grad(self, w: Array, batch: Batch) -> tuple[Array, Array]:
        kernel = self._sparse_kernel(w, batch)
        if kernel is not None:
            # Static-sparsity fast path: per-shard explicit value+gradient
            # over the shard's block-local static layout (fm segment-sum or
            # pallas aligned reduce — whichever the measured selection
            # picked), psum-ed — the direct analog of
            # treeAggregate(ValueAndGradientAggregator) with the
            # per-evaluation sort deleted (see FeatureMajorAux).
            ax = self.axis_name

            @partial(
                shard_map,
                mesh=self.mesh,
                in_specs=(P(), self._batch_specs(batch)),
                out_specs=(P(), P()),
                check_vma=False,  # outputs are psum-replicated by
                # construction; pallas_call cannot annotate vma
            )
            def _vg(w, local):
                local2 = self._squeeze_local_aux(local)
                v, g = self.obj._fast_data_value_and_grad(w, local2, kernel)
                return lax.psum(v, ax), lax.psum(g, ax)

            v, g = _vg(w, batch)
            l2 = self.obj.l2_weight
            if not _static_zero(l2):
                v = v + 0.5 * l2 * jnp.dot(w, w)
                g = g + l2 * w
            return v, g
        return jax.value_and_grad(self.value)(w, batch)

    def grad(self, w: Array, batch: Batch) -> Array:
        if self._sparse_kernel(w, batch) is not None:
            return self.value_and_grad(w, batch)[1]
        return jax.grad(self.value)(w, batch)

    def hessian_vector(self, w: Array, v: Array, batch: Batch) -> Array:
        kernel = (
            self._sparse_kernel(w, batch)
            if self.obj.normalization is None else None
        )
        if kernel is not None:
            ax = self.axis_name

            @partial(
                shard_map,
                mesh=self.mesh,
                in_specs=(P(), P(), self._batch_specs(batch)),
                out_specs=P(),
                check_vma=False,  # as in _vg: psum-replicated outputs
            )
            def _hv(w, v, local):
                local2 = self._squeeze_local_aux(local)
                return lax.psum(
                    self.obj._fast_data_hessian_vector(w, v, local2, kernel),
                    ax,
                )

            hv = _hv(w, v, batch)
            l2 = self.obj.l2_weight
            if not _static_zero(l2):
                hv = hv + l2 * v
            return hv
        return jax.jvp(
            lambda u: self._differentiable_grad(u, batch), (w,), (v,)
        )[1]

    def _differentiable_grad(self, w: Array, batch: Batch) -> Array:
        """Gradient via a kernel jvp can differentiate THROUGH (the
        normalized-Hv path re-differentiates the gradient, and
        ``pallas_call`` has no JVP rule): pallas/blocked route to the fm
        layout where the batch carries one (every sharded attach builds
        it; a one-device mesh's single-block attach only under a pin or
        when fm wins the probe), else to plain autodiff — mirroring
        GlmObjective._differentiable_grad."""
        kernel = self._sparse_kernel(w, batch)
        if kernel is not None and not differentiable(kernel):
            kernel = "fm" if batch.fm is not None else None
        if kernel is None:
            return jax.grad(self.value)(w, batch)
        ax = self.axis_name

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(P(), self._batch_specs(batch)),
            out_specs=P(),
            check_vma=False,  # as in _vg: psum-replicated outputs
        )
        def _g(w, local):
            local2 = self._squeeze_local_aux(local)
            _, g = self.obj._fast_data_value_and_grad(w, local2, kernel)
            return lax.psum(g, ax)

        g = _g(w, batch)
        l2 = self.obj.l2_weight
        if not _static_zero(l2):
            g = g + l2 * w
        return g

    def hessian_diagonal(self, w: Array, batch: Batch) -> Array:
        ax = self.axis_name
        l2 = self.obj.l2_weight

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(P(), self._batch_specs(batch)),
            out_specs=P(),
        )
        def _hd(w, local):
            # Strip the l2 added per shard by the local method; re-add once.
            return lax.psum(self.obj.hessian_diagonal(w, local) - l2, ax)

        return _hd(w, batch) + l2

    def hessian_matrix(self, w: Array, batch: Batch) -> Array:
        """Full Hessian: psum of per-shard ``Xᵀ D X`` blocks + l2·I once
        (the treeAggregate of HessianMatrixAggregator — SURVEY.md §2.2)."""
        ax = self.axis_name
        l2 = self.obj.l2_weight
        d = w.shape[0]

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(P(), self._batch_specs(batch)),
            out_specs=P(),
        )
        def _hm(w, local):
            local_h = self.obj.hessian_matrix(w, local) - l2 * jnp.eye(
                d, dtype=w.dtype
            )
            return lax.psum(local_h, ax)

        return _hm(w, batch) + l2 * jnp.eye(d, dtype=w.dtype)

    # -- optimizer binding ----------------------------------------------------
    def bind(self, batch: Batch) -> Callable[[Array], tuple[Array, Array]]:
        return lambda w: self.value_and_grad(w, batch)

    def bind_hvp(self, batch: Batch) -> Callable[[Array, Array], Array]:
        return lambda w, v: self.hessian_vector(w, v, batch)


# A pytree like GlmObjective (the wrapped objective's reg weights stay
# dynamic); the mesh and axis name are static structure, so solvers cached by
# core/problem.py retrace only when the mesh itself changes.
jax.tree_util.register_pytree_node(
    DistributedGlmObjective,
    lambda o: ((o.obj,), (o.mesh, o.axis_name)),
    lambda aux, children: DistributedGlmObjective(children[0], aux[0], aux[1]),
)


class RowSplitGlmObjective:
    """Per-entity objective whose ROWS are split across a mesh axis.

    The missing leg of the reference's entity-grouping shuffle: when one
    entity's rows span hosts, the reference physically moves rows so each
    entity is co-located.  Here nothing moves — every shard evaluates the
    data terms on its LOCAL rows of every entity and ``lax.psum``s, so the
    (vmapped, replicated) optimizer sees exact global per-entity values.
    The shuffle becomes a collective (README §scale-out data strategy).

    Use INSIDE ``shard_map`` over ``axis_name`` (see
    :func:`solve_entities_row_split`).  Regularization is added once
    globally — data terms psum, l2/l1 do not.
    """

    def __init__(self, obj: GlmObjective, axis_name: str = DATA_AXIS):
        self.obj = obj
        self.axis_name = axis_name

    @property
    def l1_weight(self):
        return self.obj.l1_weight

    def value_and_grad(self, w: Array, batch: Batch) -> tuple[Array, Array]:
        v, g = jax.value_and_grad(self.obj.data_value)(w, batch)
        v = lax.psum(v, self.axis_name)
        g = lax.psum(g, self.axis_name)
        l2 = self.obj.l2_weight
        if not _static_zero(l2):
            v = v + 0.5 * l2 * jnp.dot(w, w)
            g = g + l2 * w
        return v, g

    def value(self, w: Array, batch: Batch) -> Array:
        v = lax.psum(self.obj.data_value(w, batch), self.axis_name)
        if not _static_zero(self.obj.l2_weight):
            v = v + 0.5 * self.obj.l2_weight * jnp.dot(w, w)
        return v

    def grad(self, w: Array, batch: Batch) -> Array:
        return self.value_and_grad(w, batch)[1]

    def hessian_vector(self, w: Array, v: Array, batch: Batch) -> Array:
        hv = jax.jvp(
            lambda u: jax.grad(self.obj.data_value)(u, batch), (w,), (v,)
        )[1]
        hv = lax.psum(hv, self.axis_name)
        if not _static_zero(self.obj.l2_weight):
            hv = hv + self.obj.l2_weight * v
        return hv

    def hessian_diagonal(self, w: Array, batch: Batch) -> Array:
        l2 = self.obj.l2_weight
        local = self.obj.hessian_diagonal(w, batch) - l2
        return lax.psum(local, self.axis_name) + l2

    def hessian_matrix(self, w: Array, batch: Batch) -> Array:
        d = w.shape[0]
        l2 = self.obj.l2_weight
        local = self.obj.hessian_matrix(w, batch) - l2 * jnp.eye(d, dtype=w.dtype)
        return lax.psum(local, self.axis_name) + l2 * jnp.eye(d, dtype=w.dtype)


jax.tree_util.register_pytree_node(
    RowSplitGlmObjective,
    lambda o: ((o.obj,), (o.axis_name,)),
    lambda aux, children: RowSplitGlmObjective(children[0], aux[0]),
)


def solve_entities_row_split(
    objective: GlmObjective,
    config,
    batches: Batch,
    w0s: Array,
    mesh: Mesh,
    axis_name: str = DATA_AXIS,
):
    """Solve every entity's GLM with its rows SHARDED across ``axis_name``.

    ``batches`` leaves are ``[E, R, ...]`` (entity-major, per-entity padded
    rows — zero-weight padding as usual) with ``R`` divisible by the axis
    size; ``w0s`` is ``[E, dim]`` replicated.  Each shard holds the
    ``R/num_shards`` row slice of EVERY entity; the vmapped optimizer runs
    replicated on all shards, driven by psum-exact global gradients
    (:class:`RowSplitGlmObjective`).  Returns (Coefficients, OptimizerResult)
    pytrees with leading entity axes, replicated across the mesh.

    This is the rows-exceed-host-memory leg of the random-effect story: on a
    multi-process mesh each process contributes only the rows IT read, and
    no row ever crosses a host — the reference's shuffle traffic becomes one
    psum per objective evaluation over ICI/DCN.
    """
    n_shards = mesh.shape[axis_name]
    r = jax.tree.leaves(batches)[0].shape[1]
    if r % n_shards:
        raise ValueError(
            f"per-entity row capacity ({r}) must be divisible by the mesh "
            f"axis size ({n_shards}); pad entity rows first"
        )
    if getattr(batches, "fm", None) is not None:
        batches = batches._replace(fm=None)  # row-major path under vmap

    program = _row_split_program(
        mesh, axis_name, config.optimizer.lower(), config.optimizer_config,
        config.variance_computation,
        jax.tree.structure(batches),
        tuple(leaf.ndim for leaf in jax.tree.leaves(batches)),
    )
    return program(RowSplitGlmObjective(objective, axis_name), batches, w0s)


@functools.lru_cache(maxsize=32)
def _row_split_program(mesh, axis_name, optimizer, opt_cfg, variance,
                       batch_treedef, batch_ranks):
    """One shard_map'd solve program per (mesh, static config, batch
    structure): the per-bucket/per-descent-iteration calls in
    RandomEffectCoordinate.train hit jax's trace cache instead of retracing
    the whole vmapped optimizer every call (same discipline as
    core/problem.cached_solver; the objective rides along as a replicated
    pytree argument)."""
    from photon_tpu.core.problem import cached_solver

    solver = cached_solver(optimizer, opt_cfg, variance, vmapped=True)
    batch_specs = jax.tree.unflatten(
        batch_treedef,
        [P(None, axis_name, *([None] * (r - 2))) for r in batch_ranks],
    )
    return shard_map(
        lambda split_obj, local, w0s: solver(split_obj, local, w0s),
        mesh=mesh,
        in_specs=(P(), batch_specs, P()),
        out_specs=P(),
        check_vma=False,  # optimizer state is replicated by construction:
        # every shard runs the identical update from psum-ed gradients
    )
