"""Pallas grid-overhead probe: the same kernel at varying tile heights.

Motivation: every pallas measurement in the round-4 windows clusters
around 400-560 Melem/s (~2 GB/s) regardless of what the kernel computes
— the production aligned_reduce, probe_permute's lane-gather, swap-stage
and one-hot rows all hit the same plateau, while plain XLA elementwise
sustains ~180 GB/s on the same chip.  A per-element cost that does not
depend on the computation points at per-GRID-STEP overhead (dispatch /
semaphore / DMA setup per tile), not bandwidth.  This probe times a
minimal copy-scale kernel and the benes swap-stage kernel over a sweep
of tile heights at fixed total size: if time/element falls as tiles get
taller, the production kernels' tile of 128 sublanes is leaving an
order of magnitude on the table and `TILE_SUBLANES` should rise.

Methodology: chained calls (each step's input is the previous output)
inside one jit + a host-fetched scalar (tools/probe_common.py) — bare
block_until_ready timings of identical calls are not decision-grade.
"""

import argparse

import numpy as np

from probe_common import CHAIN, LANES, timed as _time  # noqa: F401

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * jnp.float32(1.0000001)


def swap_kernel(x_ref, o_ref):
    x = x_ref[...]
    up = pltpu.roll(x, 32, axis=1)
    dn = pltpu.roll(x, LANES - 32, axis=1)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    o_ref[...] = jnp.where((lane // 32) % 2 == 0, up, dn)


def sweep(kernel, name, E):
    x0 = jnp.asarray(np.random.rand(E // LANES, LANES).astype(np.float32))
    for h in (8, 32, 128, 512, 2048, 8192):
        rows = E // LANES
        if rows % h:
            continue
        n_tiles = rows // h
        try:
            f = pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(x0.shape, x0.dtype),
                grid=(n_tiles,),
                in_specs=[pl.BlockSpec((h, LANES), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((h, LANES), lambda i: (i, 0)),
            )

            @jax.jit
            def g(x, f=f):
                y = x
                for _ in range(CHAIN):
                    y = f(y)
                return y.sum()

            t = _time(g, x0) / CHAIN
            print(
                f"{name} h={h:<5} tiles={n_tiles:<6} {t*1e3:8.2f} ms  "
                f"{E/t/1e6:9.1f} Melem/s  {E*4*2/t/1e9:7.2f} GB/s r+w  "
                f"{t/n_tiles*1e6:7.1f} us/tile"
            )
        except Exception as e:  # noqa: BLE001 - probe reports, never crashes
            print(f"{name} h={h:<5} FAILED: {type(e).__name__}: {str(e)[:90]}")


def xla_baseline(E):
    x0 = jnp.asarray(np.random.rand(E // LANES, LANES).astype(np.float32))

    @jax.jit
    def g(x):
        y = x
        for _ in range(CHAIN):
            # Barrier per step: without it XLA fuses the chain into one
            # HBM pass (or folds to a single multiply) and /CHAIN
            # under-reports ~CHAIN-fold (probe_common methodology note).
            y = jax.lax.optimization_barrier(y * jnp.float32(1.0000001))
        return y.sum()

    t = _time(g, x0) / CHAIN
    print(f"xla elementwise baseline       {t*1e3:8.2f} ms  "
          f"{E/t/1e6:9.1f} Melem/s  {E*4*2/t/1e9:7.2f} GB/s r+w")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--entries", type=int, default=1 << 25)
    args = ap.parse_args()
    E = args.entries
    print(f"backend={jax.default_backend()} devices={jax.devices()} E={E:,}")
    xla_baseline(E)
    sweep(copy_kernel, "pallas copy", E)
    sweep(swap_kernel, "pallas swap", E)


if __name__ == "__main__":
    main()
