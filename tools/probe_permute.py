"""Primitive probes for the static-permutation ("benes") sparse-grad design.

Every kernel in production (autodiff scatter / fm segment-sum / pallas
aligned reduce) bottlenecks on ONE pathology measured in the round-4
hardware window: XLA lowers random E-element gathers and scatters on TPU
essentially serially (~0.1% of HBM roofline at the baseline shape).  The
candidate fix is to eliminate random access entirely: the row-order ->
feature-order exchange is a STATIC permutation, and a static permutation
can be decomposed into hardware-friendly primitives.  This probe times
each candidate building block on the live backend so the design choice is
measurement-driven (KERNEL_NOTES.md round-4 verdict 3):

  a. baseline: full-array XLA gather x[perm]                (the pathology)
  b. XLA 2-D transpose at the exchange shape               (Clos middle stage)
  c. in-kernel jnp.take_along_axis along lanes (Mosaic
     dynamic-gather lowering, if supported)                 (would collapse
                                                            the whole network
                                                            to one pass)
  d. Pallas masked-XOR-swap stage built from pltpu.roll     (Benes stage)
  e. windowed one-hot matmul segment-sum (MXU)              (sorted-side
                                                            reduce/gather)
  f. jnp.repeat monotonic expand w[f] by static counts      (forward side)
  g. XLA sort-by-key at E (dynamic-permutation alternative)
  h. within-row take_along_axis at the stage shape          (one Clos stage
                                                            as XLA sees it)
  i. full 3-stage Clos apply (P1.T.P2.T.P3)                 (the complete
                                                            XLA-only benes
                                                            permute —
                                                            ops/clos.py)

Timing methodology matches tools/microbench2.py: jit once, warm up, then
median of reps with a scalar reduction brought host-side so the timed
window contains no host copies of the payload.
"""

import argparse

import numpy as np

from probe_common import CHAIN, timed as _time  # noqa: F401 (cpu guard)

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def probe_gather_baseline(E):
    perm = np.random.permutation(E).astype(np.int32)
    x = jnp.asarray(np.random.rand(E).astype(np.float32))
    permd = jnp.asarray(perm)

    @jax.jit
    def f(x, p):
        y = x
        for _ in range(CHAIN):
            y = y[p]  # output feeds the next gather: no step can be elided
        return y.sum()

    t = _time(f, x, permd) / CHAIN
    print(f"a. XLA random gather     E={E:>10,}  {t*1e3:8.2f} ms  "
          f"{E/t/1e6:10.1f} Melem/s  {E*4/t/1e9:7.2f} GB/s")
    return t


def probe_transpose(E):
    # Exchange shape for the Clos middle stage: [A, B] -> [B, A].
    A = 8192
    B = E // A
    x = jnp.asarray(np.random.rand(A, B).astype(np.float32))

    @jax.jit
    def f(x):
        y = x
        for i in range(CHAIN):
            # *1.0000001 keeps each stage a distinct computation (T.T would
            # fold to identity); the multiply fuses into the transpose
            # write.  The barrier stops XLA from treating the transpose as
            # a free layout change absorbed by a layout-agnostic consumer.
            y = jax.lax.optimization_barrier(y.T) * jnp.float32(1.0000001)
        return y.sum()

    t = _time(f, x) / CHAIN
    print(f"b. XLA transpose [{A}x{B}]      {t*1e3:8.2f} ms  "
          f"{A*B*4/t/1e9:7.2f} GB/s")
    return t


def probe_lane_gather_kernel(E):
    # Per-sublane arbitrary lane gather inside a Pallas kernel.  If Mosaic
    # lowers take_along_axis on the lane axis, a static tile-local
    # permutation is ONE vector op per tile and the Benes network is
    # unnecessary.
    TILE = (512, 128)
    n_tiles = E // (TILE[0] * TILE[1])
    E = n_tiles * TILE[0] * TILE[1]  # actual processed count

    def kernel(x_ref, idx_ref, o_ref):
        o_ref[...] = jnp.take_along_axis(x_ref[...], idx_ref[...], axis=1)

    xh = np.random.rand(n_tiles * TILE[0], 128).astype(np.float32)
    x = jnp.asarray(xh)
    idx = jnp.asarray(
        np.argsort(np.random.rand(n_tiles * TILE[0], 128), axis=1).astype(
            np.int32
        )
    )

    try:
        f = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec(TILE, lambda i: (i, 0)),
                pl.BlockSpec(TILE, lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec(TILE, lambda i: (i, 0)),
        )
        def chained(x, idx):
            y = x
            for _ in range(CHAIN):
                y = f(y, idx)
            return y.sum()

        g = jax.jit(chained)
        # Correctness first: the permuted rows must sum to the same total.
        total = float(g(x, idx))
        np.testing.assert_allclose(
            total, float(xh.astype(np.float64).sum()), rtol=1e-3
        )
        t = _time(g, x, idx) / CHAIN
        print(f"c. pallas lane-gather    E={E:>10,}  {t*1e3:8.2f} ms  "
              f"{E/t/1e6:10.1f} Melem/s  {E*4/t/1e9:7.2f} GB/s")
        return t
    except Exception as e:  # noqa: BLE001 - probe must report, not crash
        print(f"c. pallas lane-gather    UNSUPPORTED: {type(e).__name__}: "
              f"{str(e)[:120]}")
        return None


def probe_benes_stage(E):
    # One masked XOR-swap stage (stride 32 within lanes) via two rolls and
    # a select, which is the per-stage cost of a lane-level Benes network.
    # Stride 32 keeps the two rolls distinct expressions (at stride 64 the
    # +s and -s rolls coincide and CSE would time half a real stage).
    TILE = (512, 128)
    n_tiles = E // (TILE[0] * TILE[1])
    E = n_tiles * TILE[0] * TILE[1]  # actual processed count

    def kernel(x_ref, m_ref, o_ref):
        x = x_ref[...]
        up = pltpu.roll(x, 32, axis=1)
        dn = pltpu.roll(x, 128 - 32, axis=1)  # roll is cyclic: -s == size-s
        m = m_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        swapped = jnp.where((lane // 32) % 2 == 0, up, dn)
        o_ref[...] = jnp.where(m > 0, swapped, x)

    x = jnp.arange(E, dtype=jnp.float32).reshape(n_tiles * TILE[0], 128)
    m = jnp.asarray(
        (np.random.rand(n_tiles * TILE[0], 128) < 0.5).astype(np.float32)
    )

    try:
        f = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec(TILE, lambda i: (i, 0)),
                pl.BlockSpec(TILE, lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec(TILE, lambda i: (i, 0)),
        )
        def chained(x, m):
            y = x
            for _ in range(CHAIN):
                y = f(y, m)
            return y.sum()

        g = jax.jit(chained)
        t = _time(g, x, m) / CHAIN
        print(f"d. benes swap stage      E={E:>10,}  {t*1e3:8.2f} ms  "
              f"{E/t/1e6:10.1f} Melem/s  (x19 stages ~ "
              f"{19*t*1e3:6.1f} ms/full-perm upper bound)")
        return t
    except Exception as e:  # noqa: BLE001
        print(f"d. benes swap stage      UNSUPPORTED: {type(e).__name__}: "
              f"{str(e)[:120]}")
        return None


def probe_onehot_segsum(E):
    # Sorted-side segment-sum as a windowed one-hot MXU matmul: tiles of
    # sorted entries whose feature ids span a 128-wide window; the reduce
    # is onehot[T,128]^T @ pv[T] accumulated per window.
    T = 2048  # entries per tile
    GROUP = 128  # tiles whose one-hot materializes at once (134 MB f32)
    n_groups = E // (T * GROUP)
    n_tiles = n_groups * GROUP
    E = n_tiles * T  # actual processed count
    # Synthetic sorted ids: each tile covers its own 128-window densely.
    local = np.sort(np.random.randint(0, 128, size=(n_tiles, T))).astype(
        np.int32
    )
    pv = jnp.asarray(
        np.random.rand(n_tiles, T).astype(np.float32).reshape(
            n_groups, GROUP, T
        )
    )
    idx = jnp.asarray(local.reshape(n_groups, GROUP, T))

    @jax.jit
    def f(pv, idx):
        # lax.map over groups bounds the materialized one-hot to
        # GROUP*T*128*4 bytes; a single whole-E one-hot would exceed the
        # 16 GB HBM of the target chip at the default entry count.
        def group(args):
            pv_g, idx_g = args
            onehot = (
                idx_g[..., None] == jnp.arange(128)[None, None, :]
            ).astype(jnp.float32)
            return jnp.einsum("nt,ntw->nw", pv_g, onehot).sum()

        s = jnp.float32(0.0)
        for _ in range(CHAIN):
            # Chain through the scalar: each pass's input is perturbed by
            # the previous pass's result, so no pass can be elided.  The
            # perturbing broadcast-add is stream-speed (noise next to the
            # matmul passes being timed).
            s = jax.lax.map(group, (pv + s * 1e-30, idx)).sum()
        return s

    t = _time(f, pv, idx) / CHAIN
    print(f"e. onehot segsum (MXU)   E={E:>10,}  {t*1e3:8.2f} ms  "
          f"{E/t/1e6:10.1f} Melem/s")
    return t


def probe_repeat_expand(E, d=262144):
    # Forward-side monotonic expand: w[f] repeated by static per-feature
    # counts (ids sorted by feature).  Implemented as the standard
    # cumsum-searchsorted-free gather on a SORTED index vector so XLA can
    # see monotonicity.
    per = max(1, E // d)
    E = per * d  # actual processed count
    sorted_feat = jnp.asarray(np.repeat(np.arange(d), per).astype(np.int32))
    w = jnp.asarray(np.random.rand(d).astype(np.float32))

    @jax.jit
    def f(w, f_sorted):
        s = jnp.float32(0.0)
        for _ in range(CHAIN):
            s = (w + s * 1e-30)[f_sorted].sum()  # scalar-chained: see _time
        return s

    t = _time(f, w, sorted_feat) / CHAIN
    print(f"f. monotonic gather w[f] E={E:>10,}  {t*1e3:8.2f} ms  "
          f"{E/t/1e6:10.1f} Melem/s")
    return t


def probe_rowwise_gather(E):
    # One Clos stage as XLA sees it: within-row gather on the [A, B] grid
    # with a DIFFERENT random perm per row.  Random per-row indices are
    # timing-equivalent to real routed stages, so no router is needed.
    A = 8192
    B = E // A
    E = A * B
    x = jnp.asarray(np.random.rand(A, B).astype(np.float32))
    idx = jnp.asarray(
        np.argsort(np.random.rand(A, B), axis=1).astype(np.int32)
    )

    @jax.jit
    def f(x, idx):
        y = x
        for _ in range(CHAIN):
            y = jnp.take_along_axis(y, idx, axis=1)
        return y.sum()

    t = _time(f, x, idx) / CHAIN
    print(f"h. row-wise gather [{A}x{B}]  {t*1e3:8.2f} ms  "
          f"{E/t/1e6:10.1f} Melem/s  {E*4/t/1e9:7.2f} GB/s")
    return t


def probe_clos_composite(E):
    # Full 3-stage Clos apply (P1, T, P2, T, P3) with random per-row
    # perms; upper-bounds the XLA-only benes permute cost per direction.
    A = 8192
    B = E // A
    E = A * B
    x = jnp.asarray(np.random.rand(A, B).astype(np.float32))
    rng = np.random.default_rng(0)
    p1 = jnp.asarray(np.argsort(rng.random((A, B)), axis=1).astype(np.int32))
    p2 = jnp.asarray(np.argsort(rng.random((B, A)), axis=1).astype(np.int32))
    p3 = jnp.asarray(np.argsort(rng.random((A, B)), axis=1).astype(np.int32))

    @jax.jit
    def f(x, p1, p2, p3):
        g = x
        for _ in range(CHAIN):
            g = jnp.take_along_axis(g, p1, axis=1)
            g = g.T
            g = jnp.take_along_axis(g, p2, axis=1)
            g = g.T
            g = jnp.take_along_axis(g, p3, axis=1)
        return g.sum()

    t = _time(f, x, p1, p2, p3) / CHAIN
    print(f"i. clos 3-stage apply    E={E:>10,}  {t*1e3:8.2f} ms  "
          f"{E/t/1e6:10.1f} Melem/s  (vs probe a = the op it replaces)")
    return t


def probe_sort(E):
    k = jnp.asarray(np.random.randint(0, E, size=E).astype(np.int32))
    v = jnp.arange(E, dtype=jnp.float32)

    @jax.jit
    def f(k, v):
        for _ in range(CHAIN):
            k, v = jax.lax.sort([k, v], num_keys=1)
            # Re-randomize keys from the sorted values (cheap elementwise
            # hash) so every chained sort does full work on unsorted keys.
            vb = jax.lax.bitcast_convert_type(v, jnp.int32)
            k = (vb * jnp.int32(-1640531527)) ^ k
        return v.sum()

    t = _time(f, k, v) / CHAIN
    print(f"g. XLA sort-by-key       E={E:>10,}  {t*1e3:8.2f} ms  "
          f"{E/t/1e6:10.1f} Melem/s")
    return t


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--entries", type=int, default=1 << 25)
    args = ap.parse_args()
    E = args.entries
    print(f"backend={jax.default_backend()} devices={jax.devices()} E={E:,}")
    # Each probe is individually guarded: a mid-run failure (OOM,
    # unsupported lowering) must not cost the remaining rows — partial
    # output is still evidence.
    for probe in (
        probe_gather_baseline,
        probe_transpose,
        probe_lane_gather_kernel,
        probe_benes_stage,
        probe_onehot_segsum,
        probe_repeat_expand,
        probe_sort,
        probe_rowwise_gather,
        probe_clos_composite,
    ):
        try:
            probe(E)
        except Exception as e:  # noqa: BLE001 - report and continue
            print(f"{probe.__name__} FAILED: {type(e).__name__}: "
                  f"{str(e)[:160]}")


if __name__ == "__main__":
    main()
