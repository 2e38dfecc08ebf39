"""Probes for the fused chunk-local permutation kernel (the `xchg` plan).

KERNEL_NOTES.md (round-4 third window) reduces the sparse-GLM exchange
problem to one question: how fast can this chip run a STATIC permutation
of the E-element entry stream, given that the only fast data movers are
pallas lane-local gathers (3.4 Gelem/s), sublane-local gathers, XLA
strided transposes (14 GB/s), and sequential streams?  The planned
decomposition is chunk-Clos: arbitrary perm = chunk-local perm → (T ·
lane-perm · T) middle → chunk-local perm, with each chunk-local perm
itself a fused in-VMEM mixed-radix Benes.  These probes time the
candidate device pieces with the chained methodology
(tools/probe_permute.py 2026-07-31 note):

  a. tall-tile lane-gather (one stage at h=2048: refats the 9.9 ms/pass)
  b. in-kernel VMEM transpose [2048,128] -> [128,2048] (support + speed)
  c. fused 5-stage chunk kernel: lane-gather / transpose / lane-gather /
     transpose / lane-gather, all inside one pallas_call per [2048,128]
     chunk (the v2 fused chunk-perm; random per-stage routing is
     timing-equivalent to real routing)
  d. the middle-stage sandwich: XLA transpose + lane-gather pass + XLA
     transpose at the full-E shape
  e. sublane-gather stage (take_along_axis axis=0 within [8,128] groups)

Verdict rule: pipeline cost/direction ~= 2 x (c) + (d), to be compared
with the measured autodiff step at the same shape (not measured on the
current chip).  NOTE (PR 21): on the v5e Mosaic refuses the chunk kernel's
CH-wide lane gather, so probe (c) does not lower there as written.
"""

import argparse
import os
import sys

import numpy as np

from probe_common import CHAIN, LANES, timed as _time  # noqa: F401

# Repo root on the path: probe_scans times the PRODUCTION compensated
# scan from photon_tpu.ops.vperm, not a copy.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

CH = 2048  # chunk sublane-rows: chunk = [CH, 128] = 2^18 elements (1 MB)
INTERPRET = False  # --interpret: validate kernel logic off-TPU


def _pallas(*args, **kwargs):
    return pl.pallas_call(*args, interpret=INTERPRET, **kwargs)


def _rand_lane_idx(rows, rng):
    return jnp.asarray(
        np.argsort(rng.random((rows, LANES)), axis=1).astype(np.int32)
    )


def probe_tall_lane_gather(E):
    rng = np.random.default_rng(0)
    rows = E // LANES
    x = jnp.asarray(rng.random((rows, LANES)).astype(np.float32))
    idx = _rand_lane_idx(rows, rng)
    n_tiles = rows // CH

    def kernel(x_ref, i_ref, o_ref):
        o_ref[...] = jnp.take_along_axis(x_ref[...], i_ref[...], axis=1)

    f = _pallas(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((CH, LANES), lambda i: (i, 0)),
            pl.BlockSpec((CH, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((CH, LANES), lambda i: (i, 0)),
    )

    @jax.jit
    def g(x, idx):
        y = x
        for _ in range(CHAIN):
            y = f(y, idx)
        return y.sum()

    t = _time(g, x, idx) / CHAIN
    print(f"a. lane-gather h={CH}     E={E:>10,}  {t*1e3:8.2f} ms  "
          f"{E/t/1e6:9.1f} Melem/s")
    return t


def probe_vmem_transpose(E):
    rng = np.random.default_rng(1)
    rows = E // LANES
    n_tiles = rows // CH
    x = jnp.asarray(rng.random((rows, LANES)).astype(np.float32))

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...].T

    try:
        f = _pallas(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n_tiles * LANES, CH), jnp.float32),
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((CH, LANES), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((LANES, CH), lambda i: (i, 0)),
        )

        @jax.jit
        def g(x):
            y = x
            for _ in range(CHAIN // 2):
                z = f(y)  # [R,128] -> tiles of [128, CH]
                y = f(z.reshape(rows, LANES))  # keep shapes cycling
            return y.sum()

        t = _time(g, x) / CHAIN
        print(f"b. in-kernel transpose [{CH},128]  {t*1e3:8.2f} ms/pass  "
              f"{E/t/1e6:9.1f} Melem/s")
        return t
    except Exception as e:  # noqa: BLE001 - probe reports, never crashes
        print(f"b. in-kernel transpose   UNSUPPORTED: {type(e).__name__}: "
              f"{str(e)[:110]}")
        return None


def probe_fused_chunk(E):
    # 3 lane-gather stages + 2 in-VMEM transposes fused per chunk — the
    # v2 chunk-local Benes body.  Random per-stage routing times the same
    # as real routing (identical op sequence, data-independent).
    rng = np.random.default_rng(2)
    rows = E // LANES
    n_tiles = rows // CH
    x = jnp.asarray(rng.random((rows, LANES)).astype(np.float32))
    i1 = _rand_lane_idx(rows, rng)
    # Stage-2 indices live on the transposed [128, CH] view, one tile each.
    i2 = jnp.asarray(
        np.argsort(rng.random((n_tiles * LANES, CH)), axis=1).astype(np.int32)
    )
    i3 = _rand_lane_idx(rows, rng)

    def kernel(x_ref, i1_ref, i2_ref, i3_ref, o_ref):
        y = jnp.take_along_axis(x_ref[...], i1_ref[...], axis=1)
        y = y.T  # [128, CH] in VMEM
        y = jnp.take_along_axis(y, i2_ref[...], axis=1)
        y = y.T  # back to [CH, 128]
        o_ref[...] = jnp.take_along_axis(y, i3_ref[...], axis=1)

    try:
        f = _pallas(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((CH, LANES), lambda i: (i, 0)),
                pl.BlockSpec((CH, LANES), lambda i: (i, 0)),
                pl.BlockSpec((LANES, CH), lambda i: (i, 0)),
                pl.BlockSpec((CH, LANES), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((CH, LANES), lambda i: (i, 0)),
        )

        @jax.jit
        def g(x, i1, i2, i3):
            y = x
            for _ in range(CHAIN):
                y = f(y, i1, i2, i3)
            return y.sum()

        t = _time(g, x, i1, i2, i3) / CHAIN
        print(f"c. fused 5-stage chunk   E={E:>10,}  {t*1e3:8.2f} ms  "
              f"{E/t/1e6:9.1f} Melem/s  (chunk-local arbitrary perm, fused)")
        return t
    except Exception as e:  # noqa: BLE001
        print(f"c. fused 5-stage chunk   UNSUPPORTED: {type(e).__name__}: "
              f"{str(e)[:110]}")
        return None


def probe_middle_sandwich(E):
    # Middle macro-stage: XLA transpose, lane-gather pass, XLA transpose.
    rng = np.random.default_rng(3)
    rows = E // LANES  # [rows, 128] -> T -> [128, rows]
    n_tiles = rows // CH
    x = jnp.asarray(rng.random((rows, LANES)).astype(np.float32))
    # Indices must be PER-TILE (each [128, CH] tile gathers within its
    # own 2048-wide window), not global 0..rows-1 — out-of-tile indices
    # would clamp and time a degenerate gather.
    idx = jnp.asarray(
        np.argsort(rng.random((LANES, n_tiles, CH)), axis=-1)
        .reshape(LANES, rows)
        .astype(np.int32)
    )

    def kernel(x_ref, i_ref, o_ref):
        o_ref[...] = jnp.take_along_axis(x_ref[...], i_ref[...], axis=1)

    # Lane-gather on the transposed view: tiles of [128, CH] columns.
    f = _pallas(
        kernel,
        out_shape=jax.ShapeDtypeStruct((LANES, rows), jnp.float32),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((LANES, CH), lambda i: (0, i)),
            pl.BlockSpec((LANES, CH), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((LANES, CH), lambda i: (0, i)),
    )

    @jax.jit
    def g(x, idx):
        y = x
        for _ in range(CHAIN):
            z = jax.lax.optimization_barrier(y.T)  # [128, rows]
            z = f(z, idx)
            y = jax.lax.optimization_barrier(z.T)  # [rows, 128]
        return y.sum()

    try:
        t = _time(g, x, idx) / CHAIN
        print(f"d. T+lane-gather+T middle E={E:>10,}  {t*1e3:8.2f} ms  "
              f"{E/t/1e6:9.1f} Melem/s")
        return t
    except Exception as e:  # noqa: BLE001
        print(f"d. middle sandwich       FAILED: {type(e).__name__}: "
              f"{str(e)[:110]}")
        return None


def probe_sublane_gather(E):
    # take_along_axis along sublanes within [8,128] groups (the radix-8
    # stage; production _gather_kernel already uses this lowering).
    rng = np.random.default_rng(4)
    rows = E // LANES
    n_tiles = rows // CH
    x = jnp.asarray(rng.random((rows, LANES)).astype(np.float32))
    idx = jnp.asarray(
        rng.integers(0, 8, size=(rows, LANES)).astype(np.int32)
    )

    def kernel(x_ref, i_ref, o_ref):
        for s in range(CH // 8):
            sl = slice(s * 8, (s + 1) * 8)
            o_ref[sl, :] = jnp.take_along_axis(
                x_ref[sl, :], i_ref[sl, :], axis=0
            )

    try:
        f = _pallas(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((CH, LANES), lambda i: (i, 0)),
                pl.BlockSpec((CH, LANES), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((CH, LANES), lambda i: (i, 0)),
        )

        @jax.jit
        def g(x, idx):
            y = x
            for _ in range(CHAIN):
                y = f(y, idx)
            return y.sum()

        t = _time(g, x, idx) / CHAIN
        print(f"e. sublane-gather (r8)   E={E:>10,}  {t*1e3:8.2f} ms  "
              f"{E/t/1e6:9.1f} Melem/s")
        return t
    except Exception as e:  # noqa: BLE001
        print(f"e. sublane-gather (r8)   UNSUPPORTED: {type(e).__name__}: "
              f"{str(e)[:110]}")
        return None


def probe_scans(E):
    # The cumsum-reduce's per-step scan: plain f32 cumsum vs the
    # compensated (hi, lo) two-sum associative scan ops/vperm.py uses.
    rng = np.random.default_rng(5)
    x0 = jnp.asarray(rng.standard_normal(E).astype(np.float32))

    @jax.jit
    def plain(x):
        y = x
        s = jnp.float32(0)
        for _ in range(CHAIN):
            ps = jnp.cumsum(y)
            s = s + ps[-1]
            y = jax.lax.optimization_barrier(y + s * 1e-30)
        return s

    t = _time(plain, x0) / CHAIN
    print(f"f. plain f32 cumsum      E={E:>10,}  {t*1e3:8.2f} ms  "
          f"{E/t/1e6:9.1f} Melem/s")

    from photon_tpu.ops.vperm import _compensated_cumsum

    @jax.jit
    def comp(x):
        y = x
        s = jnp.float32(0)
        for _ in range(CHAIN):
            hi, lo = _compensated_cumsum(y)
            s = s + hi[-1] + lo[-1]
            y = jax.lax.optimization_barrier(y + s * 1e-30)
        return s

    t = _time(comp, x0) / CHAIN
    print(f"g. compensated cumsum    E={E:>10,}  {t*1e3:8.2f} ms  "
          f"{E/t/1e6:9.1f} Melem/s")


def probe_inkernel_repeat(E):
    # Stage-A fusion candidate: expand dz inside the chunk kernel via
    # jnp.repeat along lanes ([CH, 128/k] -> [CH, 128], k=32).
    k = 32
    rng = np.random.default_rng(6)
    rows = E // LANES
    n_tiles = rows // CH
    x = jnp.asarray(rng.random((rows, LANES // k)).astype(np.float32))

    def kernel(x_ref, o_ref):
        o_ref[...] = jnp.repeat(x_ref[...], k, axis=1)

    try:
        f = _pallas(
            kernel,
            out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((CH, LANES // k), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((CH, LANES), lambda i: (i, 0)),
        )

        @jax.jit
        def g(x):
            s = jnp.float32(0)
            y = x
            for _ in range(CHAIN):
                s = s + f(y).sum()
                y = jax.lax.optimization_barrier(y + s * 1e-30)
            return s

        t = _time(g, x) / CHAIN
        print(f"h. in-kernel lane repeat E={E:>10,}  {t*1e3:8.2f} ms  "
              f"{E/t/1e6:9.1f} Melem/s (out elems)")
        return t
    except Exception as e:  # noqa: BLE001
        print(f"h. in-kernel lane repeat UNSUPPORTED: {type(e).__name__}: "
              f"{str(e)[:110]}")
        return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--entries", type=int, default=1 << 25)
    ap.add_argument("--interpret", action="store_true",
                    help="run kernels in interpret mode (correctness "
                    "check off-TPU; timings meaningless)")
    args = ap.parse_args()
    global INTERPRET
    INTERPRET = args.interpret
    E = args.entries
    print(f"backend={jax.default_backend()} devices={jax.devices()} E={E:,}")
    for probe in (
        probe_fused_chunk,       # the decision-maker runs first
        probe_scans,             # the cumsum-reduce's dominant unknown
        probe_middle_sandwich,
        probe_tall_lane_gather,
        probe_vmem_transpose,
        probe_sublane_gather,
        probe_inkernel_repeat,
    ):
        try:
            probe(E)
        except Exception as e:  # noqa: BLE001
            print(f"{probe.__name__} FAILED: {type(e).__name__}: "
                  f"{str(e)[:160]}")


if __name__ == "__main__":
    main()
