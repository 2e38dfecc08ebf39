"""Static-permutation routing: arbitrary E-element permutations as
row-local shuffles + transposes (the `benes` kernel's host side).

Motivation (ops/KERNEL_NOTES.md): XLA lowers random E-element
gathers/scatters on TPU as data-dependent, latency-bound access, and every
sparse-GLM kernel pays one per direction for the row-order <->
feature-order exchange.  That exchange is a STATIC
permutation of the entry array, so it can be pre-routed on the host into
a form with NO random device memory access:

    y = x[perm]   ==   P3_rows( T( P2_rows( T( P1_rows(x) ) ) ) )

where x is viewed as an [A, B] grid, T is a matrix transpose, and each
P*_rows applies an independent permutation per row (Clos/Slepian-Duguid
3-stage factorization; see native/src/clos_route.cpp for the
edge-coloring construction and proof sketch).  Row-local permutations in
turn either lower to lane shuffles inside a Pallas kernel or stay as
``jnp.take_along_axis`` (whose within-row gather XLA can tile better
than a flat E-gather — measured per backend, like every kernel choice in
this package).

Reference parity note: the reference has no analog — its Spark shuffle
IS the random exchange (SURVEY.md §2.6); this module is the TPU-native
replacement that makes the exchange bandwidth-bound.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

import jax.numpy as jnp
from jax import tree_util


@dataclasses.dataclass(frozen=True)
class ClosRoute:
    """Device-ready routing for one static permutation ``y = x[perm]``.

    ``p1`` [A, B], ``p2`` [B, A], ``p3`` [A, B] are within-row gather
    index arrays (int32): stage k computes
    ``x = jnp.take_along_axis(x, pk, axis=1)`` with transposes between
    stages.  ``n`` is the unpadded element count (perm length); the grid
    holds ``A * B >= n`` with an identity tail.
    """

    n: int
    a: int
    b: int
    p1: jnp.ndarray
    p2: jnp.ndarray
    p3: jnp.ndarray


# Jit pytree: index arrays are dynamic leaves; the grid shape is static so
# one compiled program serves every evaluation over the same layout.
tree_util.register_dataclass(
    ClosRoute, data_fields=("p1", "p2", "p3"), meta_fields=("n", "a", "b")
)


def default_grid(n: int) -> tuple[int, int]:
    """Most-square power-of-two (A, B) grid covering ``n`` elements.

    B must be a power of two for the Euler-split coloring; A powers of two
    keep the inter-stage transposes tile-friendly.  Shared by
    :func:`route_permutation` and ops/benes.build_benes_aux so the aux
    grid and the router default cannot diverge.
    """
    bits = max(1, int(np.ceil(np.log2(max(n, 2)))))
    a = 1 << ((bits + 1) // 2)
    b = 1 << (bits - (bits + 1) // 2)
    return a, b


def _edge_color_native(l: np.ndarray, r: np.ndarray, a: int,
                       b: int) -> Optional[np.ndarray]:
    from photon_tpu.native import build as native_build

    lib = native_build.get_lib()
    if lib is None:
        return None
    import ctypes

    e = np.int64(l.size)
    color = np.empty(l.size, dtype=np.int32)
    rc = lib.clos_edge_color(
        e, np.int32(a), np.int32(b),
        l.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        r.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        color.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    if rc == -3:
        raise ValueError(
            f"permutation too large for the native router ({l.size:,} "
            f"edges > INT32_MAX/2 — head prefix sums reach 2E); shard "
            f"the layout before routing"
        )
    if rc != 0:
        raise RuntimeError(f"clos_edge_color failed: rc={rc}")
    return color


def _edge_color_python(l: np.ndarray, r: np.ndarray, a: int,
                       b: int) -> np.ndarray:
    """Pure-Python Euler-split coloring (fallback + test oracle).

    Same algorithm as the native version; fine for test sizes, far too
    slow for production E.
    """
    if b & (b - 1):
        raise ValueError(f"B must be a power of two, got {b}")
    color = np.empty(l.size, dtype=np.int32)

    def split(edges: np.ndarray, base: int, span: int) -> None:
        if span == 1:
            color[edges] = base
            return
        # Adjacency over 2a vertices: vertex -> list of (edge, other).
        adj: list[list[int]] = [[] for _ in range(2 * a)]
        for e in edges:
            adj[l[e]].append(int(e))
            adj[a + r[e]].append(int(e))
        cursor = [0] * (2 * a)
        used = {}
        halves: tuple[list[int], list[int]] = ([], [])
        for v0 in range(2 * a):
            while cursor[v0] < len(adj[v0]):
                if adj[v0][cursor[v0]] in used:
                    cursor[v0] += 1
                    continue
                circuit: list[int] = []
                vstack = [v0]
                estack: list[int] = [-1]
                while vstack:
                    v = vstack[-1]
                    while (cursor[v] < len(adj[v])
                           and adj[v][cursor[v]] in used):
                        cursor[v] += 1
                    if cursor[v] < len(adj[v]):
                        e = adj[v][cursor[v]]
                        used[e] = True
                        other = (a + r[e]) if v == l[e] else l[e]
                        vstack.append(other)
                        estack.append(e)
                    else:
                        e = estack.pop()
                        vstack.pop()
                        if e >= 0:
                            circuit.append(e)
                for i, e in enumerate(circuit):
                    halves[i % 2].append(e)
        assert len(halves[0]) == len(halves[1]) == edges.size // 2
        split(np.asarray(halves[0]), base, span // 2)
        split(np.asarray(halves[1]), base + span // 2, span // 2)

    split(np.arange(l.size, dtype=np.int64), 0, b)
    return color


def route_permutation(perm: np.ndarray, a: Optional[int] = None,
                      b: Optional[int] = None, *,
                      use_native: bool = True,
                      device: bool = True) -> ClosRoute:
    """Factor ``y = x[perm]`` into the 3-stage row-local form.

    ``a``/``b`` default to the most square power-of-two grid covering
    ``len(perm)`` (padded with an identity tail when a*b > n).
    ``device=False`` keeps the stage arrays as host numpy (callers that
    re-factor stages, like ops/vperm, avoid shipping hundreds of MB of
    intermediate routing to the device and back).
    """
    perm = np.ascontiguousarray(perm, dtype=np.int64)
    n = perm.size
    if a is None or b is None:
        a, b = default_grid(n)
    total = a * b
    if total < n:
        raise ValueError(f"grid {a}x{b} smaller than permutation ({n})")
    if perm.size and (
        perm.min() < 0 or perm.max() >= n
        or np.bincount(perm, minlength=n).max() != 1
    ):
        raise ValueError("perm is not a permutation of [0, n)")
    full = np.arange(total, dtype=np.int64)
    # Identity tail (when total > n) keeps padding elements in place; they
    # are part of the grid but never read back.
    full[:n] = perm

    src_row = (full // b).astype(np.int32)   # a_s per destination index
    dst_row = (np.arange(total, dtype=np.int64) // b).astype(np.int32)
    src_col = (full % b).astype(np.int32)
    dst_col = (np.arange(total, dtype=np.int64) % b).astype(np.int32)

    color = None
    if use_native:
        color = _edge_color_native(src_row, dst_row, a, b)
    if color is None:
        if total >= (1 << 18):
            # The Python fallback is a per-edge interpreter loop over
            # log2(b) levels — hours at production scale.  Fail fast
            # instead of silently stalling batch attach.
            raise RuntimeError(
                f"native clos_edge_color unavailable and permutation too "
                f"large ({total:,} elements) for the Python fallback; "
                f"build the native library (g++) or unset "
                f"PHOTON_SPARSE_GRAD=benes"
            )
        color = _edge_color_python(src_row, dst_row, a, b)

    # Stage index arrays (see clos_route.cpp header for the derivation):
    #   P1[a_s, c]   = b_s   (source-row shuffle into color columns)
    #   P2[c, a_d]   = a_s   (middle-row shuffle routing to dest rows)
    #   P3[a_d, b_d] = c     (dest-row shuffle into final columns)
    p1 = np.empty((a, b), dtype=np.int32)
    p2 = np.empty((b, a), dtype=np.int32)
    p3 = np.empty((a, b), dtype=np.int32)
    p1[src_row, color] = src_col
    p2[color, dst_row] = src_row
    p3[dst_row, dst_col] = color
    if not device:
        return ClosRoute(n=n, a=a, b=b, p1=p1, p2=p2, p3=p3)
    return ClosRoute(n=n, a=a, b=b, p1=jnp.asarray(p1), p2=jnp.asarray(p2),
                     p3=jnp.asarray(p3))


def apply_clos_grid(x: jnp.ndarray, route: ClosRoute) -> jnp.ndarray:
    """Apply the routed permutation to a FULL-GRID flat array (jit-safe):
    ``x`` has ``a * b`` elements and so does the result.  The device-side
    stage implementation lives here — one home, so swapping the
    take_along_axis stages for a Pallas lane-shuffle kernel changes
    exactly this function."""
    total = route.a * route.b
    g = x.reshape(route.a, route.b)
    g = jnp.take_along_axis(g, route.p1, axis=1)
    g = g.T
    g = jnp.take_along_axis(g, route.p2, axis=1)
    g = g.T
    g = jnp.take_along_axis(g, route.p3, axis=1)
    return g.reshape(total)


def apply_clos(x: jnp.ndarray, route: ClosRoute) -> jnp.ndarray:
    """Apply the routed permutation to a flat array (jit-safe).

    Equivalent to ``x[perm]`` for the routed perm; pads with zeros to the
    grid, runs the 3 row-local stages + 2 transposes, and slices the
    result back to ``route.n``.
    """
    total = route.a * route.b
    if x.shape[0] != route.n:
        raise ValueError(f"length {x.shape[0]} != routed n {route.n}")
    if total > route.n:
        x = jnp.concatenate(
            [x, jnp.zeros((total - route.n,), dtype=x.dtype)]
        )
    return apply_clos_grid(x, route)[: route.n]


def invert_route(route: ClosRoute, n: Optional[int] = None) -> ClosRoute:
    """The inverse permutation's route, from the same routing.

    ``(P1 . T . P2 . T . P3)^-1 = P3^-1 . T . P2^-1 . T . P1^-1`` — the
    same 3-stage structure with each stage's rows inverted row-wise
    (``argsort`` of a permutation row is its inverse), so ONE edge-coloring
    serves both directions of an exchange.  ``n`` sets the unpadded length
    of the inverse (defaults to the forward's)."""

    def inv_rows(p: jnp.ndarray) -> jnp.ndarray:
        return jnp.argsort(p, axis=1).astype(p.dtype)

    return ClosRoute(
        n=route.n if n is None else n, a=route.a, b=route.b,
        p1=inv_rows(route.p3), p2=inv_rows(route.p2), p3=inv_rows(route.p1),
    )


