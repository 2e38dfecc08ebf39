"""Shared harness for the TPU primitive probes.

Methodology: bare ``block_until_ready`` timings of repeated identical
calls are not decision-grade (a repeat can be elided or served from a
cache).  Every probe therefore CHAINS its
op ``CHAIN`` times inside one jit with a data dependency per step (no
step can be cached or elided) and ``float()``-fetches the final scalar
host-side; report median wall time / CHAIN.

XLA fusion caveat: chains of fusible elementwise ops must insert
``jax.lax.optimization_barrier`` per step, or XLA collapses the chain
into one pass and the /CHAIN division under-reports ~CHAIN-fold.
Pallas calls and data-movement ops with distinct index operands are
opaque enough already.
"""

import time

import numpy as np

import jax

CHAIN = 8
LANES = 128


def timed(fn, *args, reps=5):
    """Median wall seconds of ``fn(*args)`` with host-fetched result."""
    out = fn(*args)
    float(np.asarray(out).ravel()[0])
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        float(np.asarray(out).ravel()[0])
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))
