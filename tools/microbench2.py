"""Decision-grade micro-benchmarks of the sparse-GLM primitive ops.

Device->host copies of large outputs would dominate wall time, so every
timed program here reduces its result to a scalar INSIDE jit; only 4 bytes
come back to the host.
Each row reports throughput against ITS OWN element count (a pallas row
processes padded slots, not raw entries).

Run on the real chip; record the table in photon_tpu/ops/KERNEL_NOTES.md —
it decides whether the crossing-stage kernels are worth building.
"""

from __future__ import annotations

import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np


def tm(fn, *args, reps=10):
    fj = jax.jit(fn)
    out = fj(*args)
    np.asarray(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fj(*args)
    np.asarray(out)
    return (time.perf_counter() - t0) / reps


def main():
    n, k, d = 1 << 20, 32, 1 << 18
    e = n * k
    rng = np.random.default_rng(0)
    ids = rng.integers(1, d, size=(n, k), dtype=np.int32)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    w = jnp.asarray(rng.standard_normal(d).astype(np.float32))
    ids_j = jnp.asarray(ids)
    vals_j = jnp.asarray(vals)

    flat = ids.reshape(-1)
    order = np.argsort(flat, kind="stable").astype(np.int32)
    sorted_ids = jnp.asarray(flat[order])
    rows_sorted = jnp.asarray((order // k).astype(np.int32))
    perm = jnp.asarray(order)
    qe = jnp.asarray(rng.standard_normal(e).astype(np.float32))
    u = jnp.asarray(rng.standard_normal(n).astype(np.float32))

    res = {}  # name -> (seconds, element_count)
    res["fwd: gather w[ids] + rowsum margins"] = (tm(
        lambda w, i, v: jnp.sum((jnp.take(w, i, axis=0) * v).sum(axis=-1)),
        w, ids_j, vals_j), e)
    res["gather dz[rows] 33.5M from 4MB"] = (tm(
        lambda u, r: jnp.sum(jnp.take(u, r, axis=0)), u, rows_sorted), e)
    res["permute 33.5M from 134MB"] = (tm(
        lambda q, p: jnp.sum(jnp.take(q, p, axis=0)), qe, perm), e)
    res["cumsum 33.5M"] = (tm(lambda q: jnp.cumsum(q)[-1], qe), e)
    res["bwd today: scatter-add unsorted"] = (tm(
        lambda q, i: jnp.sum(jnp.zeros(d, jnp.float32).at[i.reshape(-1)].add(q)),
        qe, ids_j), e)
    res["bwd fast: segment_sum sorted"] = (tm(
        lambda q, i: jnp.sum(jax.ops.segment_sum(
            q, i, num_segments=d, indices_are_sorted=True)), qe, sorted_ids), e)
    # Lowering-diagnostic variants: if these differ materially from the rows
    # above, the bottleneck is XLA's choice of lowering, not the hardware.
    res["bwd alt: scatter-add 2D [n,k] ids"] = (tm(
        lambda v2, i2: jnp.sum(jnp.zeros(d, jnp.float32).at[i2].add(v2)),
        vals_j, ids_j), e)
    res["bwd alt: weighted bincount"] = (tm(
        lambda q, i: jnp.sum(jnp.bincount(i.reshape(-1), weights=q, length=d)),
        qe, ids_j), e)
    # Small-table gather: same 33.5M lookups, 1024-entry (4KB) table.  If
    # this is fast while the 4MB-table row is slow, gathers are cache/HBM
    # bound (layout fixes help); if both are slow, the lowering is serial
    # per element (only an in-kernel gather helps).
    small = jnp.asarray(rng.standard_normal(1024).astype(np.float32))
    rows_small = jnp.asarray(((order // k) % 1024).astype(np.int32))
    res["gather small-table 33.5M from 4KB"] = (tm(
        lambda t, r: jnp.sum(jnp.take(t, r, axis=0)), small, rows_small), e)

    al = al_t = None
    try:
        from photon_tpu.ops.pallas_gather import (
            aligned_gather_products, aligned_segment_grad,
            build_aligned_layout, device_layout)
        lay = build_aligned_layout(ids, vals, d)
        al = device_layout(lay)
        smap = jnp.asarray(lay.slab_of_tile)
        lo = jnp.asarray(lay.lo)
        lvals = jnp.asarray(lay.vals)
        dup = jnp.asarray(lay.dup_map)
        t = tm(lambda w, s, l, v: jnp.sum(aligned_gather_products(w, s, l, v)),
               jnp.take(w, dup, axis=0).reshape(-1, 128), smap, lo, lvals)
        res[f"pallas aligned gather (pad {lay.padding_factor:.2f}x)"] = (
            t, lay.padded_entries)
        res["dup-gather w[dup_map]"] = (tm(
            lambda w, m: jnp.sum(jnp.take(w, m, axis=0)), w, dup), dup.size)
        # The round-4 production gradient kernel: dz[rows] gather + Pallas
        # position reduce + dictionary segment-sum (vs "bwd fast" above,
        # whose segment-sum runs over all E entries).
        res["bwd pallas: aligned_segment_grad"] = (tm(
            lambda u: jnp.sum(aligned_segment_grad(u, al, d, interpret=False)),
            u), lay.padded_entries)
        # The transposed (row-dictionary) layout: same kernel runs the
        # FORWARD — margins as per-row sums (vs "fwd: gather+rowsum" above).
        from photon_tpu.ops.pallas_gather import build_row_aligned_layout

        lay_t = build_row_aligned_layout(ids, vals)
        al_t = device_layout(lay_t)
        res[f"fwd pallas: aligned margins (pad {lay_t.padding_factor:.2f}x)"] = (
            tm(lambda w: jnp.sum(aligned_segment_grad(w, al_t, n, interpret=False)),
               w), lay_t.padded_entries)
    except Exception as ex:  # noqa: BLE001
        print("pallas aligned kernels FAILED:", str(ex)[:200])

    # End-to-end: the three production value_and_grad paths (env-pinned so
    # the measured routing is the named one, not the auto measurement).
    import os

    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.data.batch import SparseBatch, attach_feature_major

    batch = SparseBatch(ids_j, vals_j, jnp.asarray((rng.random(n) < 0.5).astype(np.float32)),
                        jnp.zeros(n, jnp.float32), jnp.ones(n, jnp.float32))
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 1.0))
    prev = os.environ.get("PHOTON_SPARSE_GRAD")
    try:
        os.environ["PHOTON_SPARSE_GRAD"] = "autodiff"
        res["value_and_grad autodiff (r1 path)"] = (tm(
            lambda w: obj.value_and_grad(w, batch)[1].sum(), w), e)
        os.environ["PHOTON_SPARSE_GRAD"] = "fm"
        fast = attach_feature_major(batch)
        res["value_and_grad fast (fm path)"] = (tm(
            lambda w: obj.value_and_grad(w, fast)[1].sum(), w), e)
        if al is not None:
            os.environ["PHOTON_SPARSE_GRAD"] = "pallas"
            aligned = fast._replace(al=al)
            res["value_and_grad pallas bwd (r4)"] = (tm(
                lambda w: obj.value_and_grad(w, aligned)[1].sum(), w), e)
            if al_t is not None:
                aligned_fb = aligned._replace(al_t=al_t)
                res["value_and_grad pallas fwd+bwd (r4)"] = (tm(
                    lambda w: obj.value_and_grad(w, aligned_fb)[1].sum(), w), e)
    finally:
        if prev is None:
            os.environ.pop("PHOTON_SPARSE_GRAD", None)
        else:
            os.environ["PHOTON_SPARSE_GRAD"] = prev

    for name, (t, cnt) in res.items():
        print(f"{name:45s} {t*1e3:8.2f} ms   {cnt/t/1e9:7.2f} Gelem/s")


if __name__ == "__main__":
    main()
