"""GLM objective functions: weighted loss + regularization, with derivatives.

This is the rebuild of the reference's objective-function stack —
``ObjectiveFunction`` / ``DiffFunction`` / ``TwiceDiffFunction`` traits plus
``DistributedGLMLossFunction`` / ``SingleNodeGLMLossFunction`` and the
per-partition aggregators (``ValueAndGradientAggregator``,
``HessianVectorAggregator``, ``HessianDiagonalAggregator``) — SURVEY.md
§2.1/§2.2/§3.4.  Where the reference folds examples through Breeze/BLAS
``dot``/``axpy`` per partition and tree-aggregates to the driver, here the
whole evaluation is one XLA program: ``jax.value_and_grad`` over a batched
margin computation; Hessian-vector products come from ``jax.jvp`` of the
gradient (exact for GLM objectives).  Under a sharded mesh the same code runs
per shard and `psum`s — see :mod:`photon_tpu.parallel`.

The L2 term is added analytically (as in the reference); L1 is *not* part of
the smooth objective — OWL-QN handles it via its orthant logic, matching the
reference's split (SURVEY.md §2.1 "Regularization").
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import tree_util

from photon_tpu.core.losses import PointwiseLoss, get_loss
from photon_tpu.core.normalization import NormalizationContext
from photon_tpu.core.optimizers.tron import MarginForm
from photon_tpu.data.batch import (
    LAYOUT_FIELDS,
    Batch,
    DenseBatch,
    FeatureMajorAux,
    SparseBatch,
    margins,
)
from photon_tpu.ops.sparse_grad_select import (
    differentiable,
    has_own_forward,
    select_kernel,
)

Array = jax.Array


def _fm_segment_grad(per_row: Array, fm: FeatureMajorAux, dim: int) -> Array:
    """``g[f] = sum_e per_row[row_e] * val_e`` over a feature-major layout.

    The production sparse-gradient kernel (VERDICT r2 item 1): entries are
    pre-sorted by feature id within each block, so the reduction is a
    ``segment_sum(indices_are_sorted=True)`` — no per-evaluation device sort,
    unlike the unsorted scatter-add XLA would otherwise lower.  ``per_row``
    is any per-row scalar (dz for gradients, d2·(x·v) for Hv products).

    Handles both the block-local view (S == 1: inside shard_map, or a
    single-device batch) and a multi-block batch evaluated on one device
    (S > 1: block-local rows are offset to global rows; per-block sorted
    segment sums are summed).
    """
    s, _ = fm.ids.shape
    ns = per_row.shape[0] // s
    with jax.named_scope("fm/gather"):
        rows = fm.rows + (jnp.arange(s, dtype=fm.rows.dtype) * ns)[:, None]
        contrib = (
            jnp.take(per_row, rows.reshape(-1), axis=0).reshape(s, -1)
            * fm.vals
        )

    def _block(c, i):
        return jax.ops.segment_sum(
            c, i, num_segments=dim, indices_are_sorted=True
        )

    with jax.named_scope("fm/segment_sum"):
        if s == 1:
            return _block(contrib[0], fm.ids[0])
        return jnp.sum(jax.vmap(_block)(contrib, fm.ids), axis=0)


def _lane_form(slab):
    """``slab`` over one entity's operands (features ``[R, d]`` first), and
    under ``vmap`` over ALL of them the same arithmetic once, with the
    mapped axis moved behind every other: the entities on the lane axis, as
    ``newton._spd_solve_lanes`` has them.  A caller that maps the features
    with ``in_axes=-1`` (``batched_solve._entity_solve_newton``) gets the
    inverse of ``vmap``'s own ``moveaxis`` here, and no transpose of them is
    left.  Any other pattern of mapped operands is a plain ``vmap``."""
    fn = jax.custom_batching.custom_vmap(slab)

    @fn.def_vmap
    def _rule(axis_size, in_batched, *args):
        if all(in_batched):
            out = slab(*(jnp.moveaxis(a, 0, -1) for a in args))
            return jnp.moveaxis(out, -1, 0), True
        in_axes = [0 if b else None for b in in_batched]
        return jax.vmap(slab, in_axes, axis_size=axis_size)(*args), True

    return fn


# The three dense products of a GLM as float32 elementwise products and sums
# over non-lane axes, no ``dot_general``: features ``x [R, d, ...]``, the
# trailing axes batch axes (none for one entity, the entities under vmap).
_lane_xw = _lane_form(lambda x, v: jnp.sum(x * v[None], axis=1))
_lane_xtu = _lane_form(lambda x, u: jnp.sum(x * u[:, None], axis=0))
_lane_xtdx = _lane_form(
    lambda x, u: jnp.sum(x[:, :, None] * (x * u[:, None])[:, None], axis=0)
)


@dataclasses.dataclass(frozen=True)
class RegularizationContext:
    """L1/L2/elastic-net configuration.

    Mirrors the reference's ``RegularizationContext`` /
    ``RegularizationType`` (NONE/L1/L2/ELASTIC_NET).  ``alpha`` is the
    elastic-net mixing weight: ``l1 = alpha * weight``,
    ``l2 = (1 - alpha) * weight``.
    """

    reg_type: str = "none"  # none | l1 | l2 | elastic_net
    reg_weight: float = 0.0
    alpha: float = 0.5

    def __post_init__(self):
        if self.reg_type not in ("none", "l1", "l2", "elastic_net"):
            raise ValueError(f"unknown regularization type {self.reg_type!r}")

    @property
    def l1_weight(self) -> float:
        if self.reg_type == "l1":
            return self.reg_weight
        if self.reg_type == "elastic_net":
            return self.alpha * self.reg_weight
        return 0.0

    @property
    def l2_weight(self) -> float:
        if self.reg_type == "l2":
            return self.reg_weight
        if self.reg_type == "elastic_net":
            return (1.0 - self.alpha) * self.reg_weight
        return 0.0

    def replace(self, **kw) -> "RegularizationContext":
        return dataclasses.replace(self, **kw)


NO_REG = RegularizationContext()


def _static_zero(x) -> bool:
    """True only for a concrete (Python-scalar) zero weight.

    Objectives are jit pytrees whose reg weights may be tracers (so one
    compiled sweep program serves every lambda); a tracer is never
    "statically zero" and takes the unconditional-arithmetic path."""
    return isinstance(x, (int, float)) and x == 0.0


@dataclasses.dataclass(frozen=True)
class GlmObjective:
    """Smooth part of a GLM objective: sum_i weight_i * loss(margin_i, y_i)
    + (l2/2) ||w||^2, with optional feature normalization applied inside the
    objective (the model itself stays in the original feature space, as in
    the reference's NormalizationContext design).

    All methods are pure functions of ``(w, batch)`` and jit/vmap/shard
    cleanly.  ``l1_weight`` is carried for OWL-QN but never enters the smooth
    value/gradient.
    """

    loss: PointwiseLoss
    l2_weight: float = 0.0
    l1_weight: float = 0.0
    normalization: Optional[NormalizationContext] = None

    @classmethod
    def create(
        cls,
        loss: str | PointwiseLoss,
        reg: RegularizationContext = NO_REG,
        normalization: Optional[NormalizationContext] = None,
    ) -> "GlmObjective":
        if isinstance(loss, str):
            loss = get_loss(loss)
        return cls(
            loss=loss,
            l2_weight=reg.l2_weight,
            l1_weight=reg.l1_weight,
            normalization=normalization,
        )

    # -- margins under normalization ------------------------------------------
    def _margins(self, w: Array, batch: Batch) -> Array:
        if self.normalization is None:
            return margins(w, batch)
        # (x - shift) * factor . w  ==  x . (factor * w) - (shift * factor) . w:
        # keeps sparse batches sparse (SURVEY.md §2.1 Normalization).
        w_eff, correction = self.normalization.effective_coefficients(w)
        return margins(w_eff, batch) - correction

    def _xu_product(self, kernel: str, u: Array, batch: Batch) -> Array:
        """Per-row ``X u`` products (no offset) through the selected
        kernel's forward: the blocked path gathers inside VMEM over the
        batch's entry tiles (ops/block_tiles.py); the pallas path uses the
        TRANSPOSED aligned layout when the batch carries one
        (``sum_e u[f_e] v_e`` per row via the same position-reduce kernel —
        KERNEL_NOTES.md option (a)); everything else takes the row-major
        XLA gather.  The single dispatch point for margins AND Hv's
        ``X v``."""
        if not has_own_forward(kernel, batch):
            return jnp.sum(jnp.take(u, batch.ids, axis=0) * batch.vals, axis=-1)
        if kernel == "blocked":
            from photon_tpu.ops.block_tiles import block_tiles_product

            with jax.named_scope("blocked/xw"):
                return block_tiles_product(u, batch.bt, batch.ids.shape[0])
        from photon_tpu.ops.pallas_gather import aligned_segment_grad

        return aligned_segment_grad(u, batch.al_t, batch.ids.shape[0])

    def _margins_for_kernel(self, kernel: Optional[str], w: Array,
                            batch: Batch) -> Array:
        if kernel is None or not has_own_forward(kernel, batch):
            # Single home of the normalization algebra for the XLA forward.
            return self._margins(w, batch)
        if self.normalization is None:
            return self._xu_product(kernel, w, batch) + batch.offset
        w_eff, correction = self.normalization.effective_coefficients(w)
        return self._xu_product(kernel, w_eff, batch) + batch.offset - correction

    # -- value / gradient ------------------------------------------------------
    # The phases of one value+gradient evaluation carry named scopes
    # (HLO metadata only): ``valuegrad/margins`` (the forward X·w: the
    # gather of w at the ids), ``valuegrad/loss`` (the pointwise loss, its
    # derivative, the L2 term) and ``valuegrad/grad`` (the reduction into
    # the coefficients, with the kernel's own stages under it:
    # ``fm/gather``, ``fm/segment_sum``, ``pallas/gather``,
    # ``pallas/reduce``; the blocked kernel's two directions are
    # ``blocked/xw`` under margins and ``blocked/xtdz`` under grad).  On
    # the autodiff path the gradient is the
    # transpose of the forward, so its scatter-add reads
    # ``transpose(jvp(valuegrad/margins))`` — see ops/KERNEL_NOTES.md.
    def data_value(self, w: Array, batch: Batch) -> Array:
        with jax.named_scope("valuegrad/margins"):
            z = self._margins(w, batch)
        with jax.named_scope("valuegrad/loss"):
            return jnp.sum(batch.weight * self.loss.value(z, batch.label))

    def value(self, w: Array, batch: Batch) -> Array:
        v = self.data_value(w, batch)
        if not _static_zero(self.l2_weight):
            with jax.named_scope("valuegrad/loss"):
                v = v + 0.5 * self.l2_weight * jnp.dot(w, w)
        return v

    # -- static-sparsity fast path --------------------------------------------
    def _sparse_kernel(self, batch: Batch, dim: int) -> Optional[str]:
        """Which static-layout gradient kernel applies to this batch:
        ``"fm"`` (pre-sorted segment sum over FeatureMajorAux), ``"pallas"``
        (slab-aligned Mosaic reduce over AlignedLayoutDev), ``"blocked"``
        (both directions in VMEM over BlockTiles), or ``None``
        (autodiff — the unsorted scatter XLA lowers is faster on some
        platforms): the measured-on-this-backend selection
        (ops/sparse_grad_select.py) among the layouts the batch carries."""
        if not (isinstance(batch, SparseBatch) and batch.ids.ndim == 2):
            return None
        if all(getattr(batch, field) is None for field in LAYOUT_FIELDS):
            return None
        choice = select_kernel(batch, dim)
        return None if choice == "autodiff" else choice

    def _segment_grad(self, kernel: str, per_row: Array, batch: Batch, dim: int) -> Array:
        """``g[f] = sum_e per_row[row_e] * val_e`` via the selected static
        layout (the reduction both the gradient and Hv share)."""
        if kernel == "blocked":
            from photon_tpu.ops.block_tiles import block_tiles_product

            with jax.named_scope("blocked/xtdz"):
                return block_tiles_product(
                    per_row, batch.bt, dim, transpose=True
                )
        if kernel == "pallas":
            from photon_tpu.ops.pallas_gather import aligned_segment_grad

            return aligned_segment_grad(per_row, batch.al, dim)
        return _fm_segment_grad(per_row, batch.fm, dim)

    def _fast_data_value_and_grad(
        self, w: Array, batch: Batch, kernel: str = "fm"
    ) -> tuple[Array, Array]:
        """Data term (no regularization) of value+gradient via the selected
        static entry layout; the TPU replacement for the reference's
        ValueAndGradientAggregator fold (SURVEY.md §3.4).

        Under normalization the margin is ``F(x - s) · w`` per example, so
        ``g = F (Xᵀ dz - s Σ dz)`` — one extra scalar sum and two
        elementwise ops over the same sorted segment sum (the sparse batch
        never densifies, mirroring hessian_diagonal's algebra)."""
        with jax.named_scope("valuegrad/margins"):
            z = self._margins_for_kernel(kernel, w, batch)
        dim = w.shape[0]
        return self._data_value_and_grad_at(
            z, batch, lambda dz: self._segment_grad(kernel, dz, batch, dim),
            dim,
        )

    def _data_value_and_grad_at(self, z: Array, batch: Batch, xtu, dim: int
                                ) -> tuple[Array, Array]:
        """Data term of value+gradient from the margins ``z``: the loss over
        the rows and one ``Xᵀ dz`` pass (``xtu``), no forward pass."""
        with jax.named_scope("valuegrad/loss"):
            v = jnp.sum(batch.weight * self.loss.value(z, batch.label))
            dz = batch.weight * self.loss.d1(z, batch.label)
        with jax.named_scope("valuegrad/grad"):
            g = xtu(dz)
            norm = self.normalization
            if norm is not None:
                if norm.shifts is not None:
                    g = g - norm.shifts * jnp.sum(dz)
                g = g * norm.factors_or_ones(dim)
        return v, g

    def _with_l2(self, val: Array, g: Array, w: Array) -> tuple[Array, Array]:
        if not _static_zero(self.l2_weight):
            with jax.named_scope("valuegrad/loss"):
                val = val + 0.5 * self.l2_weight * jnp.dot(w, w)
            with jax.named_scope("valuegrad/grad"):
                g = g + self.l2_weight * w
        return val, g

    def _fast_data_hessian_vector(
        self, w: Array, v: Array, batch: Batch, kernel: str = "fm"
    ) -> Array:
        """Data term of ``H v = Xᵀ diag(weight·d2) X v`` — exact for GLMs
        (margins are linear in w), same layout trick as the gradient.
        Both ``X·u`` products route through the kernel's forward (the
        pallas path reuses the transposed layout for ``X v`` too).
        Unnormalized objectives only — callers gate on it (normalized Hv
        goes through jvp of the normalized gradient instead), and the
        algebra below would be silently half-normalized otherwise."""
        assert self.normalization is None, (
            "fast Hv requires an unnormalized objective"
        )
        z = self._margins_for_kernel(kernel, w, batch)
        d2w = batch.weight * self.loss.d2(z, batch.label)
        xv = self._xu_product(kernel, v, batch)
        return self._segment_grad(kernel, d2w * xv, batch, w.shape[0])

    def value_and_grad(self, w: Array, batch: Batch) -> tuple[Array, Array]:
        kernel = self._sparse_kernel(batch, int(w.shape[0]))
        if kernel is not None:
            return self._with_l2(
                *self._fast_data_value_and_grad(w, batch, kernel), w)
        return jax.value_and_grad(self.value)(w, batch)

    def grad(self, w: Array, batch: Batch) -> Array:
        if self._sparse_kernel(batch, int(w.shape[0])) is not None:
            return self.value_and_grad(w, batch)[1]
        return jax.grad(self.value)(w, batch)

    def _differentiable_grad(self, w: Array, batch: Batch) -> Array:
        """Gradient via a kernel jax.jvp can differentiate THROUGH: the
        pallas and blocked kernels have no JVP rule (``pallas_call`` is not
        differentiable), so callers that re-differentiate the gradient
        (normalized Hv below) route it to the fm layout where the batch
        carries one (a pinned or sharded attach builds it beside theirs),
        else to plain autodiff over the row-major entries: what a batch
        attached after the probe's verdict runs, since it carries the
        winner's layout alone — exact either way, and on the v5e the faster
        of the two (ops/KERNEL_NOTES.md "Selection defaults")."""
        kernel = self._sparse_kernel(batch, int(w.shape[0]))
        if kernel is not None and not differentiable(kernel):
            kernel = "fm" if batch.fm is not None else None
        if kernel is not None:
            _, g = self._fast_data_value_and_grad(w, batch, kernel)
            if not _static_zero(self.l2_weight):
                g = g + self.l2_weight * w
            return g
        return jax.grad(self.value)(w, batch)

    # -- second order ----------------------------------------------------------
    def hessian_vector(self, w: Array, v: Array, batch: Batch) -> Array:
        """Exact Hessian-vector product via jvp of the gradient — the TPU
        equivalent of the reference's HessianVectorAggregator treeAggregate
        (SURVEY.md §3.4, 'TRON's Hv = jax.jvp')."""
        kernel = (
            self._sparse_kernel(batch, int(w.shape[0]))
            if self.normalization is None
            else None
        )
        if kernel is not None:
            # (normalized Hv falls back to jvp-of-grad, which differentiates
            # through the normalized fast gradient and stays exact)
            hv = self._fast_data_hessian_vector(w, v, batch, kernel)
            if not _static_zero(self.l2_weight):
                hv = hv + self.l2_weight * v
            return hv
        return jax.jvp(lambda u: self._differentiable_grad(u, batch), (w,), (v,))[1]

    def hvp_operator(self, w: Array, batch: Batch):
        """Curvature operator at ``w``: precompute the per-row curvature
        ``D(w) = weight·d2(margins)`` ONCE and return ``v -> Xᵀ(D·(X v)) +
        λ₂ v`` — the matrix-free Newton-CG inner-loop workhorse (ISSUE 14:
        two sparse matvecs per CG iteration, never a ``[d, d]`` matrix,
        and no margin recomputation per product).  Exact for GLMs (margins
        are linear in ``w``).  Built from :meth:`tron_form`; normalized
        objectives and exotic batch shapes fall back to the per-call
        jvp-of-gradient, still matrix-free."""
        form = self.tron_form(batch, int(w.shape[0]))
        if form is None:
            return lambda v: self.hessian_vector(w, v, batch)
        op = form.curvature(form.margins(w))
        return lambda v: op(v)[0]

    def tron_form(self, batch: Batch, dim: int) -> Optional[MarginForm]:
        """The objective as :func:`tron` carries it (``tron.MarginForm``):
        the margins once at the start, value and gradient from margins (one
        ``Xᵀ`` pass), and the curvature ``D = weight·d2(z)`` from margins,
        each product ``Xᵀ(D·(X v)) + λ₂ v`` handing back its ``X v``.  Both
        passes go through the selected kernel on a static-layout batch (the
        gradient's layout trick), are matrix products on a dense one, gather
        and scatter-add over 2-D ids.  ``None`` under normalization and for
        other batch shapes, where TRON keeps ``value_and_grad`` and the
        per-call Hessian-vector product."""
        if self.normalization is not None:
            return None
        kernel = self._sparse_kernel(batch, dim)
        if kernel is not None:
            xu = lambda v: self._xu_product(kernel, v, batch)  # noqa: E731
            xtu = lambda u: self._segment_grad(kernel, u, batch, dim)  # noqa: E731
        elif isinstance(batch, DenseBatch):
            xu = lambda v: batch.x @ v  # noqa: E731
            xtu = lambda u: batch.x.T @ u  # noqa: E731
        elif batch.ids.ndim == 2:
            xu = lambda v: jnp.sum(  # noqa: E731
                jnp.take(v, batch.ids, axis=0) * batch.vals, axis=-1
            )
            xtu = lambda u: jnp.zeros(dim, u.dtype).at[batch.ids].add(  # noqa: E731
                u[:, None] * batch.vals
            )
        else:
            return None

        def margins(w: Array) -> Array:
            with jax.named_scope("valuegrad/margins"):
                return self._margins_for_kernel(kernel, w, batch)

        def value_and_grad(z: Array, w: Array) -> tuple[Array, Array]:
            return self._with_l2(
                *self._data_value_and_grad_at(z, batch, xtu, dim), w)

        def curvature(z: Array):
            d2w = batch.weight * self.loss.d2(z, batch.label)

            def hv(v: Array) -> tuple[Array, Array]:
                xv = xu(v)
                out = xtu(d2w * xv)
                if not _static_zero(self.l2_weight):
                    out = out + self.l2_weight * v
                return out, xv

            return hv

        return MarginForm(margins, value_and_grad, curvature)

    def hessian_vector_product(self, w: Array, v: Array, batch: Batch) -> Array:
        """One matrix-free ``H v`` (``Xᵀ(D(w)·(X v)) + λ₂ v``) — the
        canonical single-product entry; loops over many ``v`` at one ``w``
        should hold :meth:`hvp_operator` instead (D(w) computed once)."""
        return self.hvp_operator(w, batch)(v)

    def hessian_diagonal(self, w: Array, batch: Batch) -> Array:
        """diag(H) = sum_i weight_i * d2_i * x_ij^2 + l2 (HessianDiagonalAggregator);
        used for per-coefficient variance (VarianceComputationType.SIMPLE)."""
        z = self._margins(w, batch)
        d2w = batch.weight * self.loss.d2(z, batch.label)
        norm = self.normalization
        factors = None if norm is None else norm.factors_or_ones(w.shape[0])
        shifts = None if norm is None else norm.shifts
        # diag_j = f_j^2 * sum_i d2_i (x_ij - s_j)^2
        #        = f_j^2 * (A_j - 2 s_j B_j + s_j^2 C)   with
        # A_j = sum d2_i x_ij^2,  B_j = sum d2_i x_ij,  C = sum d2_i —
        # all three computable without densifying sparse batches.
        if isinstance(batch, DenseBatch):
            a = (batch.x * batch.x).T @ d2w
            b = batch.x.T @ d2w if shifts is not None else None
        else:
            a = jnp.zeros_like(w).at[batch.ids].add(d2w[:, None] * batch.vals * batch.vals)
            b = (
                jnp.zeros_like(w).at[batch.ids].add(d2w[:, None] * batch.vals)
                if shifts is not None
                else None
            )
        diag = a
        if shifts is not None:
            c = jnp.sum(d2w)
            diag = a - 2.0 * shifts * b + shifts * shifts * c
        if factors is not None:
            diag = diag * factors * factors
        return diag + self.l2_weight

    def hessian_matrix(self, w: Array, batch: Batch) -> Array:
        """Full Hessian ``H = Xᵀ diag(weight·d2) X + l2·I`` (the reference's
        HessianMatrixAggregator; used by VarianceComputationType.FULL).
        Feasible for modest dims — per-entity random effects and small
        fixed effects.  Under normalization the Hessian is taken in the
        normalized feature space (matching hessian_diagonal), expanded as
        ``F (A - B sᵀ - s Bᵀ + C s sᵀ) F`` with ``A = Xᵀ D X``,
        ``B = Xᵀ D 1``, ``C = Σ D`` so sparse batches stay sparse."""
        return self.hessian_at_margins(self._margins(w, batch), w, batch)

    # -- functions of the margins ----------------------------------------------
    # ``z = margins(w)`` is affine in ``w``, and value, gradient and Hessian
    # at ``w`` are functions of ``z``: an optimizer that carries ``z``
    # (``newton.MarginForm``) searches a line ``z + t X v`` with no pass
    # over the features a trial.  ``lanes``: the dense products in the form
    # that puts a mapped axis on the lanes (:func:`_lane_form`).
    def _x_dot(self, v: Array, batch: Batch, lanes: bool) -> Array:
        if lanes:
            return _lane_xw(batch.x, v)
        return margins(v, batch._replace(offset=jnp.zeros((), v.dtype)))

    def _xt_dot(self, u: Array, batch: Batch, dim: int, lanes: bool) -> Array:
        if lanes:
            return _lane_xtu(batch.x, u)
        if isinstance(batch, DenseBatch):
            return batch.x.T @ u
        return jnp.zeros(dim, u.dtype).at[batch.ids].add(u[:, None] * batch.vals)

    def direction_margins(self, v: Array, batch: Batch, lanes: bool = False) -> Array:
        """``margins(w + v) - margins(w)``: ``X v`` under the normalization,
        without the offsets."""
        norm = self.normalization
        with jax.named_scope("valuegrad/margins"):
            if norm is None:
                return self._x_dot(v, batch, lanes)
            v_eff = v if norm.factors is None else v * norm.factors
            xv = self._x_dot(v_eff, batch, lanes)
            if norm.shifts is None:
                return xv
            return xv - jnp.sum(norm.shifts * v_eff)

    def margins(self, w: Array, batch: Batch, lanes: bool = False) -> Array:
        return self.direction_margins(w, batch, lanes) + batch.offset

    def value_at_margins(self, z: Array, w: Array, batch: Batch) -> Array:
        with jax.named_scope("valuegrad/loss"):
            v = jnp.sum(batch.weight * self.loss.value(z, batch.label))
            return v + 0.5 * self.l2_weight * jnp.sum(w * w)

    def grad_at_margins(self, z: Array, w: Array, batch: Batch,
                        lanes: bool = False) -> Array:
        """The gradient at ``w`` from ``z = margins(w)``:
        :meth:`_fast_data_value_and_grad`'s algebra, one pass over the
        features."""
        with jax.named_scope("valuegrad/loss"):
            dz = batch.weight * self.loss.d1(z, batch.label)
        with jax.named_scope("valuegrad/grad"):
            g = self._xt_dot(dz, batch, w.shape[0], lanes)
            norm = self.normalization
            if norm is not None:
                if norm.shifts is not None:
                    g = g - norm.shifts * jnp.sum(dz)
                g = g * norm.factors_or_ones(w.shape[0])
            return g + self.l2_weight * w

    def hessian_at_margins(self, z: Array, w: Array, batch: Batch,
                           lanes: bool = False) -> Array:
        """:meth:`hessian_matrix` at ``w`` from ``z = margins(w)``."""
        d2w = batch.weight * self.loss.d2(z, batch.label)
        d = w.shape[0]
        if lanes:
            a, b = _lane_xtdx(batch.x, d2w), self._xt_dot(d2w, batch, d, lanes)
        elif isinstance(batch, DenseBatch):
            a = jnp.einsum("ni,n,nj->ij", batch.x, d2w, batch.x)
            b = batch.x.T @ d2w
        else:
            c_i = d2w[:, None, None] * batch.vals[:, :, None] * batch.vals[:, None, :]
            a = jnp.zeros((d, d), w.dtype).at[
                batch.ids[:, :, None], batch.ids[:, None, :]
            ].add(c_i)
            b = jnp.zeros(d, w.dtype).at[batch.ids].add(d2w[:, None] * batch.vals)
        h = a
        norm = self.normalization
        if norm is not None:
            shifts = norm.shifts
            if shifts is not None:
                c = jnp.sum(d2w)
                h = (
                    h
                    - b[:, None] * shifts[None, :]
                    - shifts[:, None] * b[None, :]
                    + c * shifts[:, None] * shifts[None, :]
                )
            factors = norm.factors_or_ones(d)
            h = h * factors[:, None] * factors[None, :]
        return h + self.l2_weight * jnp.eye(d, dtype=w.dtype)

    # -- prediction ------------------------------------------------------------
    def predict_mean(self, w: Array, batch: Batch) -> Array:
        return self.loss.mean(self._margins(w, batch))


# Objectives are jit/vmap pytrees: reg weights (and normalization arrays) are
# DYNAMIC leaves, so one compiled solver program serves a whole lambda sweep /
# hyperparameter search — only shapes and the loss retrace (see
# core/problem.py's cached solvers).
tree_util.register_dataclass(
    GlmObjective,
    data_fields=("l2_weight", "l1_weight", "normalization"),
    meta_fields=("loss",),
)
