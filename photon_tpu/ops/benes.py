"""The `benes` sparse kernel: value/gradient/Hv with NO random E-access.

Explicit opt-in kernel behind ops/sparse_grad_select (after fm /
autodiff / pallas).  Each of those pays at least one random E-element
gather or scatter per direction (ops/KERNEL_NOTES.md); this kernel
eliminates them:

- FORWARD (margins / ``X u``): per-entry products come from the
  slab-aligned Pallas gather (``w[dup_map]`` is a small dictionary
  gather; the per-entry indexing is Mosaic's in-VMEM ``dynamic_gather``),
  then ONE static Clos permutation (ops/clos.py — row-local shuffles +
  transposes) carries them into row-major order where per-row sums are a
  reshape-sum.
- GRADIENT / Hv reduce: per-entry products are computed in row-major
  order (a broadcast multiply — sequential), carried by the INVERSE Clos
  permutation into the aligned layout's slot order, and reduced by the
  existing Pallas position-reduce + tiny sorted segment-sum
  (ops/pallas_gather.aligned_reduce).

Both permutations come from ONE host-side edge-coloring
(clos.invert_route).  Everything the device touches is sequential
streams, lane-local shuffles, matrix transposes, and an [8,128]-table
dynamic gather — the design goal set in KERNEL_NOTES.md after the
2026-07-31 window.

The reference has no analog of any of this: its Spark shuffle IS a random
exchange (SURVEY.md §2.6); this is the TPU-native re-design of the same
data movement.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp
from jax import tree_util

from photon_tpu.ops.clos import (
    ClosRoute,
    apply_clos_grid,
    default_grid,
    invert_route,
    route_permutation,
)
from photon_tpu.utils.device import pallas_interpret

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class BenesAux:
    """Static routing attached to a SparseBatch for the `benes` kernel.

    ``to_slots`` permutes the zero-padded row-major entry stream (length
    ``a * b``) into aligned-layout slot order; ``to_rows`` is its inverse.
    ``n_rowmajor = n * k`` and ``n_slots = total_sub * 128`` are the real
    prefix lengths on each side of the exchange.
    """

    to_slots: ClosRoute
    to_rows: ClosRoute
    n_rowmajor: int
    n_slots: int

    @property
    def grid(self) -> int:
        return self.to_slots.a * self.to_slots.b


tree_util.register_dataclass(
    BenesAux,
    data_fields=("to_slots", "to_rows"),
    meta_fields=("n_rowmajor", "n_slots"),
)


def build_benes_aux(layout, n: int, k: int, *, a: int | None = None,
                    b: int | None = None) -> BenesAux:
    """Route the row-major <-> aligned-slot exchange for one batch layout.

    ``layout`` is the host :class:`ops.pallas_gather.AlignedLayout` (must
    carry ``src``).  Host cost is the edge-coloring
    (native/src/clos_route.cpp) — one-time per dataset, like the layout
    build itself.
    """
    n_rowmajor = n * k
    slots_src = layout.src.reshape(-1)
    n_slots = int(slots_src.size)
    need = max(n_rowmajor, n_slots)
    if a is None or b is None:
        a, b = default_grid(need)
    total = a * b
    if total < need:
        raise ValueError(f"grid {a}x{b} < required {need}")

    # Full-grid bijection: slot t takes source slots_src[t] (its row-major
    # entry) when real; pad slots and the grid tail take the unused
    # sources (row-major pad entries dropped by the layout's val != 0
    # filter, plus the zero-padded tail) in order — they only ever carry
    # zeros.  (Construction shared with the xchg route.)
    from photon_tpu.ops.vperm import full_bijection

    perm = full_bijection(slots_src, n_rowmajor, total)
    to_slots = route_permutation(perm, a, b)
    return BenesAux(
        to_slots=to_slots,
        to_rows=invert_route(to_slots),
        n_rowmajor=n_rowmajor,
        n_slots=n_slots,
    )


def _pad_to_grid(x: Array, aux: BenesAux) -> Array:
    total = aux.grid
    if x.shape[0] < total:
        x = jnp.concatenate([x, jnp.zeros(total - x.shape[0], x.dtype)])
    return x


def benes_xu_product(u: Array, al, aux: BenesAux, n: int, k: int,
                     interpret: bool | None = None) -> Array:
    """Per-row ``X u`` sums (margins minus offset) — the forward."""
    from photon_tpu.ops.pallas_gather import LANES, aligned_gather_products

    if interpret is None:
        interpret = pallas_interpret()
    u2d = jnp.take(u, al.dup_map, axis=0).reshape(-1, LANES)
    pw = aligned_gather_products(
        u2d, al.slab_of_tile, al.lo, al.vals, interpret=bool(interpret)
    )
    flat = _pad_to_grid(pw.reshape(-1).astype(jnp.float32), aux)
    rowmajor = apply_clos_grid(flat, aux.to_rows)[: aux.n_rowmajor]
    return rowmajor.reshape(n, k).sum(axis=1)


def benes_segment_grad(per_row: Array, vals_rowmajor: Array, al,
                       aux: BenesAux, dim: int,
                       interpret: bool | None = None) -> Array:
    """``g[f] = sum_e per_row[row_e] * val_e`` — the backward reduce."""
    from photon_tpu.ops.pallas_gather import aligned_reduce

    if interpret is None:
        interpret = pallas_interpret()
    pv_row = (per_row[:, None] * vals_rowmajor).astype(jnp.float32)
    flat = _pad_to_grid(pv_row.reshape(-1), aux)
    slots = apply_clos_grid(flat, aux.to_slots)[: aux.n_slots]
    return aligned_reduce(
        slots.reshape(al.lo.shape), al, dim, interpret=interpret
    )
