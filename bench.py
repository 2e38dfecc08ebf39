"""Headline benchmark: GLM grad-steps/sec (BASELINE.json primary metric).

Times the innermost distributed operation of the framework — one full
value-and-gradient evaluation of a logistic-GLM objective over a sparse
batch (the rebuild of the reference's ``DistributedGLMLossFunction.calculate``
treeAggregate hot path, SURVEY.md §3.4) — as a jit-compiled XLA program on
the platform the device policy resolves (``drivers/common.select_backend``):
a TPU, or the host when ``JAX_PLATFORMS=cpu`` asks for it explicitly.  With
neither, the run fails; it never falls back.

Prints one JSON line per metric:
    {"metric": "glm_grad_steps_per_sec", "value": N, "unit": "steps/s",
     "detail": {..., "platform": ..., "device_kind": ..., "device_count": N}}

and exits non-zero when a mode raises.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

# Set once by main() from the device policy; stamped into every emitted line.
_DEVICE: dict = {}

# Error signatures of a jaxlib whose CPU backend cannot run cross-process
# collectives at all — a platform limitation, not a failure.  The ONE copy:
# _run_stream_workers re-raises on these so the signature survives the
# bench_error detail truncation, and tests/test_multiprocess.py +
# tests/test_streaming.py import this tuple to skip-with-reason.
MP_UNSUPPORTED_MARKERS = (
    "Multiprocess computations aren't implemented",
    "multiprocess computations are not supported",
)


def _build_batch(n: int, k: int, d: int, seed: int = 0):
    """Synthetic sparse logistic data in the framework's padded-COO layout.

    ``PHOTON_BENCH_SKEW=zipf`` draws power-law feature ids (the realistic
    sparse-GLM regime and the adversarial case for aligned-layout padding);
    default is uniform ids.
    """
    import jax.numpy as jnp

    from photon_tpu.data.batch import SparseBatch

    rng = np.random.default_rng(seed)
    if os.environ.get("PHOTON_BENCH_SKEW", "uniform") == "zipf":
        ids = (1 + (rng.zipf(1.3, size=(n, k)) - 1) % (d - 1)).astype(np.int32)
    else:
        ids = rng.integers(1, d, size=(n, k), dtype=np.int32)  # id 0 = pad/intercept
    vals = rng.standard_normal((n, k)).astype(np.float32)
    w_true = rng.standard_normal(d).astype(np.float32) * 0.1
    margin = (w_true[ids] * vals).sum(axis=1)
    label = (rng.random(n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)
    from photon_tpu.data.batch import attach_feature_major

    return attach_feature_major(SparseBatch(
        ids=jnp.asarray(ids),
        vals=jnp.asarray(vals),
        label=jnp.asarray(label),
        offset=jnp.zeros(n, jnp.float32),
        weight=jnp.ones(n, jnp.float32),
    ), aligned_dim=d)


# Peak HBM bandwidth by ``device_kind`` (GB/s).  An unknown device is an
# error, not a default; the full peaks table is the benchmark PR's
# (ROADMAP S0(c)).  Source: Google Cloud documentation, "TPU v5e" — 16 GB
# HBM2e at 819 GB/s per chip.
_HBM_PEAK_GB_S = {"TPU v5 lite": 819.0}


def _hbm_peak_gb_s(device_kind: str) -> float:
    try:
        return _HBM_PEAK_GB_S[device_kind]
    except KeyError:
        raise KeyError(
            f"no HBM peak recorded for device_kind {device_kind!r}; add it "
            "to bench._HBM_PEAK_GB_S with its source"
        ) from None


def _emit(metric: str, value: float, unit: str, detail: dict) -> None:
    print(json.dumps({
        "metric": metric,
        "value": round(value, 3),
        "unit": unit,
        "detail": {**_DEVICE, **detail},
    }))


def _bench_config(num: int) -> None:
    """The five BASELINE.json bench configs (SURVEY.md §6), scaled to the
    local platform (full scale on accelerators, small on CPU sanity runs).
    Each run is a REAL driver invocation end-to-end (read -> fit -> eval).
    """
    import tempfile
    import jax

    import numpy as np

    from photon_tpu.data.synthetic import make_game_data, make_glm_data, write_libsvm

    if num not in (1, 2, 3, 4, 5):
        raise ValueError(f"unknown bench config {num}; valid: 1-5 (SURVEY.md §6)")

    platform = jax.devices()[0].platform
    big = platform != "cpu"
    tmp = tempfile.mkdtemp(prefix="photon_bench_")

    if num in (1, 2, 3):
        # (1) a1a-statistics logistic + L-BFGS (committed fixture, real AUC
        # anchor); (2) linear elastic-net OWL-QN; (3) Poisson TRON.  All
        # through the legacy-driver path.
        from photon_tpu.drivers import train

        task, opt, reg = {
            1: ("logistic_regression", "lbfgs", "l2"),
            2: ("linear_regression", "owlqn", "elastic_net"),
            3: ("poisson_regression", "tron", "l2"),
        }[num]
        extra = []
        if num == 1:
            from photon_tpu.data.fixtures import a1a_fixture_paths

            path, test_path = a1a_fixture_paths()
            n, d = 1605, 123
            extra = ["--validation-input", test_path]
        else:
            # Quality anchor for every config (VERDICT r3 weak 6): a 20%
            # held-out split from the same generated population gives each
            # perf row a validation metric (RMSE / Poisson NLL via the
            # task's default evaluators) so a broken optimizer can't hide
            # behind a fast wall-clock.
            n, d = (200_000, 1024) if big else (5000, 128)
            n_val = n // 5
            batch, _ = make_glm_data(n + n_val, d, task=task, seed=0)
            x, y = np.asarray(batch.x)[:, :-1], np.asarray(batch.label)
            path = os.path.join(tmp, "train.libsvm")
            val_path = os.path.join(tmp, "val.libsvm")
            write_libsvm(path, x[:n], y[:n])
            write_libsvm(val_path, x[n:], y[n:])
            extra = ["--validation-input", val_path]
        t0 = time.perf_counter()
        summary = train.run(train.build_parser().parse_args([
            "--input", path, "--task", task, "--optimizer", opt,
            "--reg-type", reg, "--reg-weights", "1.0",
            "--max-iterations", "100",
            "--output-dir", os.path.join(tmp, "out"),
        ] + extra))
        wall = time.perf_counter() - t0
        entry = summary["sweep"][0]
        _emit(f"config{num}_fit_seconds", wall, "s", {
            "task": task, "optimizer": opt, "rows": n, "dim": d,
            "iterations": entry["iterations"],
            "reason": entry["convergence_reason"],
            "rows_per_sec": round(n * entry["iterations"] / max(wall, 1e-9), 1),
            "metrics": entry.get("metrics"),
            "platform": platform,
        })
        return

    # (4) GAME fixed + user random effect on the MovieLens-shaped fixture
    #     (real Avro path, zipf item popularity, per-user skew);
    # (5) GAME fixed + user + item random effects (LinkedIn-scale, scaled
    #     to the chip: rows/sec is the comparable number).
    from photon_tpu.drivers import train_game

    if num == 4:
        from photon_tpu.data.fixtures import movielens_dataset
        from photon_tpu.data.game_io import write_game_avro

        # MovieLens-1M user/item counts; ratings-per-user scaled so the
        # host-side Avro fixture write stays bounded (~300K rows).  When
        # PHOTON_REAL_DATA_DIR/ml-1m exists, the REAL MovieLens-1M is used
        # instead (true literature-comparable metrics).
        ml_kw = dict(n_users=6040, n_items=3700, mean_ratings=50) if big \
            else {}
        data, ml_maps = movielens_dataset(**ml_kw)
        avro_path = os.path.join(tmp, "movielens.avro")
        write_game_avro(avro_path, data, ml_maps)
        coords = [
            "--coordinate", "fixed:type=fixed,shard=global,max_iters=30",
            "--coordinate",
            "per_user:type=random,shard=per_user,entity=userId,max_iters=20",
        ]
        t0 = time.perf_counter()
        summary = train_game.run(train_game.build_parser().parse_args([
            "--input", avro_path,
            "--feature-bags", "global=global,per_user=per_user",
            "--id-columns", "userId,itemId",
            *coords,
            "--descent-iterations", "2",
            "--validation-split", "0.2",
            "--output-dir", os.path.join(tmp, "out"),
        ]))
        wall = time.perf_counter() - t0
        n_rows = data.num_examples
        _emit("config4_game_epoch_seconds", wall / 2.0, "s/epoch", {
            "fixture": "movielens-like",
            "metrics": summary["best_metrics"],
            "rows": n_rows,
            "users": len(set(np.asarray(data.id_columns["userId"]).tolist())),
            "rows_per_sec": round(2.0 * n_rows / wall, 1),
            "platform": platform,
        })
        return
    else:
        spec = "synthetic-game:20000:100:128:16:2:0" if big else \
            "synthetic-game:400:12:32:8:2:0"
        coords = [
            "--coordinate", "fixed:type=fixed,shard=global,max_iters=20",
            "--coordinate", "per_user:type=random,shard=re0,entity=re0,max_iters=15",
            "--coordinate", "per_item:type=random,shard=re1,entity=re1,max_iters=15",
        ]
    t0 = time.perf_counter()
    summary = train_game.run(train_game.build_parser().parse_args([
        "--input", spec, *coords,
        "--descent-iterations", "2",
        "--validation-split", "0.2",
        "--output-dir", os.path.join(tmp, "out"),
    ]))
    wall = time.perf_counter() - t0
    n_rows = int(spec.split(":")[1]) * int(spec.split(":")[2])
    _emit(f"config{num}_game_epoch_seconds", wall / 2.0, "s/epoch", {
        "spec": spec,
        "metrics": summary["best_metrics"],
        "approx_rows": n_rows,
        "rows_per_sec": round(2.0 * n_rows / wall, 1),
        "platform": jax.devices()[0].platform,
    })


def _game_bench_fixture(n_random_coords: int, descent_iterations: int,
                        sizes=None):
    """Shared synthetic-fit fixture of the GAME micro-benches: one dataset
    + configuration sized so the path under test (residual passing /
    validation) is a visible slice of the wall clock — solver work is
    capped at a few inner iterations.  ~200k rows x coordinates on CPU:
    below that, solve noise swamps the deltas.  ONE builder so the descent
    and validation benches can never drift onto differently-shaped fits.
    """
    import jax

    from photon_tpu.core.objective import RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import ProblemConfig
    from photon_tpu.data.synthetic import make_game_dataset
    from photon_tpu.game.coordinate import (
        FixedEffectCoordinateConfig,
        RandomEffectCoordinateConfig,
    )
    from photon_tpu.game.estimator import GameOptimizationConfiguration

    platform = jax.devices()[0].platform
    if sizes is None:
        big = platform != "cpu"
        n_entities, rows_mean = (20_000, 50) if big else (8000, 25)
    else:
        # Explicit sizes: the resharded-restore subprocess must rebuild
        # the PARENT's fixture (its own platform is forced CPU, so the
        # platform-derived sizes could differ from the checkpoint's).
        n_entities, rows_mean = sizes
    data, _ = make_game_dataset(
        n_entities, rows_mean, 32, 8, seed=0,
        n_random_coords=n_random_coords,
    )

    def problem(lam: float, max_iters: int) -> ProblemConfig:
        return ProblemConfig(
            regularization=RegularizationContext("l2", lam),
            optimizer_config=OptimizerConfig(max_iterations=max_iters),
        )

    coordinates = {"fixed": FixedEffectCoordinateConfig("global", problem(0.01, 5))}
    for i in range(n_random_coords):
        coordinates[f"re{i}"] = RandomEffectCoordinateConfig(
            f"re{i}", f"re{i}", problem(1.0, 4)
        )
    config = GameOptimizationConfiguration(
        coordinates=coordinates, descent_iterations=descent_iterations
    )
    # Sizes ride the return so subprocess rebuilds (the resharded-restore
    # worker) use the PARENT's fixture shape verbatim instead of
    # re-deriving it from their own (forced-CPU) platform.
    return platform, (n_entities, rows_mean), data, config


def _bench_ooc(spill: bool = False, tile_dtype: str | None = None) -> None:
    """Out-of-core GAME micro-bench (``--mode ooc [--spill]`` — ISSUE
    10/11).

    Runs the SAME synthetic GAME fit — resident (device residual engine),
    streamed under a FORCED small ``--max-resident-mb``-style chunk
    budget, and (``spill=True``, the default bench run) streamed again
    through the DISK-backed tile store under a ``--max-host-mb`` budget
    small enough to force LRU eviction.  Emits ``game_ooc_rows_per_sec``
    (streamed training rows/s vs resident) and, with spill,
    ``game_ooc_disk_rows_per_sec`` with per-tier stall fractions and the
    cache/store shape.  The spilled leg ASSERTS the ISSUE 11 acceptance
    bars in-bench: forced evictions observed, spilled-vs-host-resident
    tiles bit-identical (``np.array_equal`` against a recomputation from
    the host-resident fit's final models), metrics ≤1e-6, and the spilled
    rate ≥ 0.5× the host-resident streamed rate on CPU.  Each mode times
    its SECOND fit (the first pays compilation, all modes alike).
    """
    import tempfile

    from photon_tpu.game.estimator import GameEstimator
    from photon_tpu.game.tile_store import TileStore
    from photon_tpu.game.tiles import (
        PREFETCH_DEPTH,
        RESIDUAL_TILE_KIND as TILES,
        ChunkPlan,
        ChunkStreamer,
        per_row_bytes,
        score_model_chunks,
        stream_host_bytes_estimate,
    )
    from photon_tpu.telemetry import TelemetrySession

    iters = 2
    platform, (n_entities, _rows_mean), data, config = _game_bench_fixture(
        n_random_coords=1, descent_iterations=iters
    )
    # Force a budget ~1/8 of the dataset: the streamed fit must page.
    chunk_rows = max(1, data.num_examples // 8)
    chunk_mb = (
        (PREFETCH_DEPTH + 1) * chunk_rows * per_row_bytes(data) / (1 << 20)
    )

    resident = GameEstimator("logistic_regression", data,
                             residual_mode="device")
    resident.fit([config])  # warm-up: compile + device-data upload
    t0 = time.perf_counter()
    resident.fit([config])
    resident_wall = time.perf_counter() - t0

    session = TelemetrySession("bench-ooc")
    streamed = GameEstimator("logistic_regression", data,
                             stream_chunks=chunk_rows, telemetry=session)
    streamed.fit([config])  # warm-up
    stall_c = session.registry.counter("stream.stall_s", tier="h2d")
    overlap_c = session.registry.counter(
        "stream.prefetch_overlap_s", tier="h2d"
    )
    stall0, overlap0 = stall_c.value, overlap_c.value
    t0 = time.perf_counter()
    host_fit = streamed.fit([config])[0]
    streamed_wall = time.perf_counter() - t0
    stall = stall_c.value - stall0
    overlap = overlap_c.value - overlap0
    peak = streamed._streamer.peak_in_flight_bytes
    # Chunk compute ≈ streamed wall minus the time spent stalled on loads.
    compute = max(1e-9, streamed_wall - stall)

    _emit("game_ooc_rows_per_sec",
          iters * data.num_examples / streamed_wall, "rows/s", {
              "rows": data.num_examples,
              "entities": n_entities,
              "descent_iterations": iters,
              "chunk_rows": chunk_rows,
              "chunk_budget_mb": round(chunk_mb, 2),
              "device_peak_in_flight_bytes": int(peak),
              "streamed_fit_seconds": round(streamed_wall, 4),
              "resident_fit_seconds": round(resident_wall, 4),
              "resident_rows_per_sec": round(
                  iters * data.num_examples / resident_wall, 1
              ),
              "streaming_overhead_x": round(
                  streamed_wall / resident_wall, 3
              ),
              "stall_s": round(stall, 4),
              "prefetch_overlap_s": round(overlap, 4),
              "stall_fraction_of_compute": round(stall / compute, 4),
              "platform": platform,
          })
    if not spill:
        return

    # -- the disk tier (ISSUE 11): tile+feature bytes must EXCEED the host
    # budget so the LRU cache pages against the store.
    host_set = stream_host_bytes_estimate(data, n_coordinates=2)
    max_host_mb = host_set / (1 << 20) / 4
    with tempfile.TemporaryDirectory() as td:
        sp_session = TelemetrySession("bench-ooc-spill")
        spilled = GameEstimator(
            "logistic_regression", data, stream_chunks=chunk_rows,
            spill_dir=td, max_host_mb=max_host_mb, telemetry=sp_session,
        )
        spilled.fit([config])  # warm-up
        d_stall_c = sp_session.registry.counter(
            "stream.stall_s", tier="disk"
        )
        h_stall_c = sp_session.registry.counter(
            "stream.stall_s", tier="h2d"
        )
        d_overlap_c = sp_session.registry.counter(
            "stream.prefetch_overlap_s", tier="disk"
        )
        evict_c = sp_session.registry.counter("tiles.cache_evictions")
        d0, h0, o0 = d_stall_c.value, h_stall_c.value, d_overlap_c.value
        e0 = evict_c.value
        t0 = time.perf_counter()
        result = spilled.fit([config])[0]
        spill_wall = time.perf_counter() - t0
        disk_stall = d_stall_c.value - d0
        h2d_stall = h_stall_c.value - h0
        disk_overlap = d_overlap_c.value - o0
        # Delta around the timed fit, like the stall/overlap counters:
        # the warm-up fit evicts too, and the acceptance bar is "the
        # MEASURED fit pages against the store".
        evictions = evict_c.value - e0
        cache_bytes = sp_session.registry.gauge(
            "tiles.host_cache_bytes"
        ).value
        disk_bytes = sp_session.registry.gauge("tiles.disk_bytes").value

        # ISSUE 11 acceptance, asserted in-bench --------------------------
        if not evictions > 0:
            raise AssertionError(
                f"--spill bench must force LRU eviction (budget "
                f"{max_host_mb:.2f} MB vs host set "
                f"{host_set / (1 << 20):.2f} MB) but "
                f"tiles.cache_evictions == {evictions}"
            )
        # Models bit-identical => every downstream artifact is too; check
        # them directly, then check the PUBLISHED tiles against a
        # recomputation from the host-resident fit's final models.
        def model_table(m):
            if hasattr(m, "table"):
                return np.asarray(m.table)
            return np.asarray(m.model.coefficients.means)

        sp_last = result.descent.last_model.coordinates
        host_last = host_fit.descent.last_model.coordinates
        for name, host_model in host_last.items():
            if not np.array_equal(
                model_table(host_model), model_table(sp_last[name])
            ):
                raise AssertionError(
                    f"spilled fit diverged from host-resident streamed "
                    f"fit on coordinate {name!r}"
                )
        for name, value in host_fit.metrics.items():
            if abs(value - result.metrics[name]) > 1e-6:
                raise AssertionError(
                    f"spilled metrics diverged: {name} "
                    f"{value} vs {result.metrics[name]}"
                )
        plan = ChunkPlan(data.num_examples, chunk_rows)
        store = TileStore(td)
        oracle_streamer = ChunkStreamer()
        names = list(config.coordinates)
        oracle_rows = {
            name: score_model_chunks(
                host_last[name], data, plan, oracle_streamer
            )
            for name in names
        }
        for k in range(plan.num_chunks):
            arrays, _ = store.read(TILES, k)
            lo, hi = plan.bounds(k)
            want = np.stack([oracle_rows[name][lo:hi] for name in names])
            if not np.array_equal(arrays["tile"], want):
                raise AssertionError(
                    f"published tile {k} differs from the host-resident "
                    "recomputation (spill roundtrip not bit-exact)"
                )
        host_rate = iters * data.num_examples / streamed_wall
        spill_rate = iters * data.num_examples / spill_wall
        if spill_rate < 0.5 * host_rate:
            raise AssertionError(
                f"spilled rate {spill_rate:.1f} rows/s fell below 0.5x the "
                f"host-resident streamed rate {host_rate:.1f} rows/s"
            )
        _emit("game_ooc_disk_rows_per_sec", spill_rate, "rows/s", {
            "rows": data.num_examples,
            "chunk_rows": chunk_rows,
            "max_host_mb": round(max_host_mb, 3),
            "host_set_mb": round(host_set / (1 << 20), 3),
            "spilled_fit_seconds": round(spill_wall, 4),
            "host_resident_rows_per_sec": round(host_rate, 1),
            "spill_overhead_x": round(spill_wall / streamed_wall, 3),
            "disk_stall_s": round(disk_stall, 4),
            "h2d_stall_s": round(h2d_stall, 4),
            "disk_overlap_s": round(disk_overlap, 4),
            # Per-tier stall fractions of WALL: disk stalls land on h2d
            # worker threads (overlapping consumer compute), so wall is
            # the only denominator that cannot double-count.
            "disk_stall_fraction_of_wall": round(
                disk_stall / spill_wall, 4
            ),
            "h2d_stall_fraction_of_wall": round(
                h2d_stall / spill_wall, 4
            ),
            "cache_evictions": int(evictions),
            "host_cache_bytes": int(cache_bytes),
            "disk_bytes": int(disk_bytes),
            "tiles_vs_host_resident": "bit-identical",
            "platform": platform,
        })

    # -- ISSUE 17 precision tiers on the DISK tier: rerun the spilled fit
    # with the tile store's bf16/int8 codecs.  Host-resident tiles and all
    # accumulation stay f32, so the only drift is the store roundtrip of
    # evicted-then-reloaded tiles; final metrics must stay under the
    # per-codec TILE_METRIC_TOL bound vs the host-resident streamed fit.
    from photon_tpu.game.lowp import tile_metric_tol_for

    f32_disk_bytes = disk_bytes
    # --tile-dtype restricts the lossy legs (f32 above always runs: it is
    # the parity oracle and the rate/bytes denominator).
    lossy_legs = (
        ("bf16", "int8") if tile_dtype is None
        else () if tile_dtype == "f32" else (tile_dtype,)
    )
    for dtype in lossy_legs:
        tol = tile_metric_tol_for(dtype)
        with tempfile.TemporaryDirectory() as td:
            lp_session = TelemetrySession(f"bench-ooc-spill-{dtype}")
            lp = GameEstimator(
                "logistic_regression", data, stream_chunks=chunk_rows,
                spill_dir=td, max_host_mb=max_host_mb,
                telemetry=lp_session, tile_dtype=dtype,
            )
            lp.fit([config])  # warm-up
            lp_evict_c = lp_session.registry.counter("tiles.cache_evictions")
            e0 = lp_evict_c.value
            t0 = time.perf_counter()
            lp_result = lp.fit([config])[0]
            lp_wall = time.perf_counter() - t0
            lp_evictions = lp_evict_c.value - e0
            lp_disk_bytes = lp_session.registry.gauge(
                "tiles.disk_bytes"
            ).value
            if not lp_evictions > 0:
                raise AssertionError(
                    f"[{dtype}] spill bench must force LRU eviction but "
                    f"tiles.cache_evictions == {lp_evictions}"
                )
            # Parity vs the host-resident streamed fit: final coordinate
            # tables (the fixture carries no validation metrics) plus any
            # metrics, all under the codec's declared bound.
            worst = 0.0
            lp_last = lp_result.descent.last_model.coordinates
            for name, host_model in host_last.items():
                worst = max(worst, float(np.max(np.abs(
                    model_table(host_model) - model_table(lp_last[name])
                ))))
            for name, value in host_fit.metrics.items():
                worst = max(worst, abs(value - lp_result.metrics[name]))
            if worst > tol:
                raise AssertionError(
                    f"[{dtype}] spilled fit drifted {worst:.2e} from the "
                    f"host-resident streamed fit; the codec's declared "
                    f"bound is {tol:g}"
                )
            lp_rate = iters * data.num_examples / lp_wall
            _emit(f"game_ooc_disk_rows_per_sec_{dtype}", lp_rate, "rows/s", {
                "rows": data.num_examples,
                "chunk_rows": chunk_rows,
                "max_host_mb": round(max_host_mb, 3),
                "tile_dtype": dtype,
                "spilled_fit_seconds": round(lp_wall, 4),
                "f32_disk_rows_per_sec": round(spill_rate, 1),
                "rate_vs_f32": round(lp_rate / spill_rate, 3),
                "cache_evictions": int(lp_evictions),
                "disk_bytes": int(lp_disk_bytes),
                "disk_bytes_vs_f32": round(
                    f32_disk_bytes / max(1, lp_disk_bytes), 2
                ),
                "max_metric_delta": worst,
                "metric_bound": tol,
                "platform": platform,
            })


def _bench_descent() -> None:
    """GAME coordinate-descent residual micro-bench (``--mode descent``).

    Runs the SAME synthetic multi-coordinate GAME fit twice — once under the
    seed's host float64 residual path (``PHOTON_RESIDUALS=host``) and once
    under the device-resident residual engine (``game/residuals.py``) — and
    emits one JSON line whose value is the device path's descent
    iterations/sec, with the host path's number and the speedup in detail.
    Each mode is timed on its SECOND fit: the first pays compilation and the
    estimator's one-time device-data upload, which both modes share.
    """
    from photon_tpu.game.estimator import GameEstimator

    iters = 3
    platform, (n_entities, _rows_mean), data, config = _game_bench_fixture(
        n_random_coords=3, descent_iterations=iters
    )

    walls = {}
    reps = 3
    for mode in ("host", "device"):
        estimator = GameEstimator(
            "logistic_regression", data, residual_mode=mode
        )
        estimator.fit([config])  # warm-up: compile + device-data upload
        best = float("inf")
        for _ in range(reps):  # best-of-reps: shared-CPU noise rejection
            t0 = time.perf_counter()
            estimator.fit([config])
            best = min(best, time.perf_counter() - t0)
        walls[mode] = best

    _emit("game_descent_iters_per_sec", iters / walls["device"], "iters/s", {
        "rows": data.num_examples,
        "entities": n_entities,
        "coordinates": 4,
        "descent_iterations": iters,
        "device_fit_seconds": round(walls["device"], 4),
        "host_fit_seconds": round(walls["host"], 4),
        "host_iters_per_sec": round(iters / walls["host"], 3),
        "speedup_vs_host": round(walls["host"] / walls["device"], 3),
        "rows_per_sec": round(iters * data.num_examples / walls["device"], 1),
        "platform": platform,
    })


def _bench_validation() -> None:
    """GAME validation-pipeline micro-bench (``--mode validation``).

    Fits one synthetic multi-coordinate GAME model, then times the per-
    outer-iteration validation step both ways on the SAME fit: the seed's
    host path (full ``GameModel.score`` fetch + numpy evaluator pass, once
    per iteration) against the device pipeline (incremental re-score of the
    one coordinate that "just trained", compensated composite, jitted
    device metrics — one scalar sync per metric).  Emits one JSON line
    whose value is the device path's validation rows/sec.
    """
    from photon_tpu.evaluation.evaluators import MultiEvaluator, get_evaluator
    from photon_tpu.game.data import split_game_dataset
    from photon_tpu.game.estimator import GameEstimator
    from photon_tpu.game.model import DeviceScoringCache
    from photon_tpu.game.residuals import ValidationEngine

    platform, _, data, config = _game_bench_fixture(
        n_random_coords=2, descent_iterations=1
    )
    train, val = split_game_dataset(data, 0.25)
    evaluators = MultiEvaluator(
        [get_evaluator("auc"), get_evaluator("logistic_loss"),
         get_evaluator("sharded_auc:re0")]
    )
    model = GameEstimator(
        "logistic_regression", train, val, evaluators=evaluators
    ).fit([config])[0].model
    names = list(model.coordinates)
    n_val, iters, reps = val.num_examples, 8, 3

    # Host path: what every outer iteration used to pay — full composite
    # re-score (margins of EVERY coordinate to host) + numpy evaluators.
    def host_pass() -> None:
        scores = model.score(val)
        evaluators.evaluate(scores, val.label, val.weight, dict(val.id_columns))

    host_pass()  # warm-up: jitted per-coordinate margins compile
    host_best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            host_pass()
        host_best = min(host_best, time.perf_counter() - t0)

    # Device pipeline: the steady state of descent — only the coordinate
    # that just trained re-scores; metrics are jitted device kernels.
    cache = DeviceScoringCache(val)
    engine = ValidationEngine(val.offset, names=names)
    entity_ids = {"re0": cache.entity_codes("re0")}
    for name in names:
        engine.update(name, cache.score(model.coordinates[name]))

    def device_pass(i: int) -> None:
        name = names[i % len(names)]
        engine.update(name, cache.score(model.coordinates[name]))
        evaluators.evaluate(
            engine.composite(), cache.label, cache.weight, entity_ids
        )

    device_pass(0)  # warm-up: metric kernels compile
    device_best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for i in range(iters):
            device_pass(i)
        device_best = min(device_best, time.perf_counter() - t0)

    _emit("game_validation_rows_per_sec", iters * n_val / device_best, "rows/s", {
        "validation_rows": n_val,
        "iterations": iters,
        "coordinates": len(names),
        "metrics": [ev.name for ev in evaluators.evaluators],
        "device_seconds": round(device_best, 4),
        "host_seconds": round(host_best, 4),
        "host_rows_per_sec": round(iters * n_val / host_best, 1),
        "speedup_vs_host": round(host_best / device_best, 3),
        "platform": platform,
    })


def _entities_dataset(n_entities: int, rows_mean: int = 3, dim: int = 8,
                      seed: int = 0):
    """Synthetic single-coordinate per-entity dataset for the entity-scaling
    bench: geometric (skewed) rows per entity, dense ``dim``-feature shard
    with an intercept column — the per-user/per-item shape at whatever
    entity count the curve point asks for (vectorized: the 1M point builds
    in seconds, not minutes)."""
    from photon_tpu.game.data import DenseShard, GameDataset

    rng = np.random.default_rng(seed)
    counts = np.maximum(1, rng.geometric(1.0 / rows_mean, n_entities))
    n = int(counts.sum())
    ent = np.repeat(np.arange(n_entities, dtype=np.int64), counts)
    x = rng.standard_normal((n, dim)).astype(np.float32)
    x[:, -1] = 1.0
    w_true = (rng.standard_normal((n_entities, dim)) * 0.5).astype(np.float32)
    z = np.einsum("nd,nd->n", x, w_true[ent])
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)
    return GameDataset.create(y, {"re0": DenseShard(x)},
                              id_columns={"re0": ent})


def _entities_problem():
    from photon_tpu.core.objective import RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import ProblemConfig

    return ProblemConfig(
        regularization=RegularizationContext("l2", 1.0),
        optimizer_config=OptimizerConfig(max_iterations=50),
    )


def _solve_path_env(path: str) -> dict:
    """Env knobs of one entity-solve path: ``batched`` (the default size-
    binned Newton), ``bucket_loop`` (the seed's per-capacity loop + vmapped
    L-BFGS — the perf baseline), ``bucket_loop_newton`` (per-capacity loop,
    Newton solver — the exact-parity baseline: same solver, so the only
    delta is the batched restructuring)."""
    return {
        "batched": {"PHOTON_SOLVE_BINNING": "on", "PHOTON_SOLVE_NEWTON": "on"},
        "bucket_loop": {"PHOTON_SOLVE_BINNING": "off",
                        "PHOTON_SOLVE_NEWTON": "off"},
        "bucket_loop_newton": {"PHOTON_SOLVE_BINNING": "off",
                               "PHOTON_SOLVE_NEWTON": "on"},
    }[path]


def _bench_entities(max_entities: int | None = None) -> None:
    """Entity-scaling micro-bench (``--mode entities``) — the ISSUE 8
    headline: a 10k → 1M synthetic-entity CPU curve timing one
    ``RandomEffectCoordinate.train`` under the size-binned batched
    Cholesky/Newton path against the seed's bucket-loop path, plus a small
    coordinate-descent fit in BOTH residual modes checking solver parity
    and the one-host-sync-per-iteration contract.

    Asserted per curve point: the batched path matches the bucket-loop
    path run with the SAME (Newton) solver to ≤1e-5 (the batched
    restructuring is exact) and the seed's iterative solver to ≤5e-3 at
    the 99.9th percentile (the f32 cross-solver agreement; the max is
    bounded at 5e-2 — the seed solver's own stall tail over a million
    entities; the batched path itself sits ~1e-7 from the f64
    ground-truth optimum — tests/test_batched_solve.py pins that).
    At ≥100k entities the batched path must BEAT the bucket loop on
    entity-solves/sec.  ``PHOTON_BENCH_ENTITIES_MAX`` caps the curve (the
    default bench run rides with a 100k cap; standalone runs the full 1M).
    """
    import jax

    from photon_tpu.game.coordinate import (
        RandomEffectCoordinate,
        RandomEffectCoordinateConfig,
    )

    platform = jax.devices()[0].platform
    cap = int(
        max_entities
        if max_entities is not None
        else os.environ.get("PHOTON_BENCH_ENTITIES_MAX", str(1_000_000))
    )
    curve_points = [n for n in (10_000, 100_000, 1_000_000) if n <= cap]
    if not curve_points:
        curve_points = [cap]
    config = RandomEffectCoordinateConfig(
        shard_name="re0", entity_column="re0", problem=_entities_problem()
    )

    def run_path(data, path: str) -> tuple:
        saved = {
            k: os.environ.get(k)
            for k in ("PHOTON_SOLVE_BINNING", "PHOTON_SOLVE_NEWTON")
        }
        os.environ.update(_solve_path_env(path))
        try:
            coord = RandomEffectCoordinate(data, config, "logistic_regression")
            offsets = np.zeros(data.num_examples, np.float32)
            model, _ = coord.train(offsets)  # warm-up: compile + upload
            np.asarray(model.table)  # block: warm-up fully done pre-timing
            best = float("inf")
            for _ in range(2):  # best-of-reps: shared-CPU noise rejection
                t0 = time.perf_counter()
                model, _ = coord.train(offsets)
                np.asarray(model.table)  # block: solves actually ran
                best = min(best, time.perf_counter() - t0)
            table = np.asarray(model.table)
            bins = len(coord.device_data.buckets)
        finally:
            for k, v in saved.items():
                os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
        return best, table, bins

    curve = []
    for n_entities in curve_points:
        data = _entities_dataset(n_entities)
        results = {p: run_path(data, p) for p in
                   ("batched", "bucket_loop", "bucket_loop_newton")}
        batched_s, batched_table, n_bins = results["batched"]
        loop_s, loop_table, n_buckets = results["bucket_loop"]
        exact = np.abs(batched_table - results["bucket_loop_newton"][1]).max()
        cross_diff = np.abs(batched_table - loop_table)
        cross = float(cross_diff.max())
        # The seed's L-BFGS stalls in a per-entity ~1e-4 f32 value basin
        # whose worst case grows with the max over a million entities, so
        # the cross-solver sanity check is quantile-based: virtually every
        # entity agrees to the f32 floor, and even the seed solver's worst
        # stall stays bounded.  The ≤1e-5 acceptance parity is the
        # same-solver check above it, where the only delta is the batched
        # restructuring.
        cross_p999 = float(np.quantile(cross_diff, 0.999))
        if exact > 1e-5:
            raise RuntimeError(
                f"batched vs bucket-loop (same solver) parity {exact:.3e} "
                f"> 1e-5 at {n_entities} entities"
            )
        if cross_p999 > 5e-3 or cross > 5e-2:
            raise RuntimeError(
                f"batched vs seed-solver agreement p99.9={cross_p999:.3e} "
                f"max={cross:.3e} (bounds 5e-3 / 5e-2) at "
                f"{n_entities} entities"
            )
        speedup = loop_s / batched_s
        if n_entities >= 100_000 and speedup <= 1.0:
            raise RuntimeError(
                f"batched path did not beat the bucket loop at "
                f"{n_entities} entities ({speedup:.3f}x)"
            )
        curve.append({
            "entities": n_entities,
            "rows": data.num_examples,
            "bins": n_bins,
            "buckets": n_buckets,
            "batched_solve_seconds": round(batched_s, 4),
            "bucket_loop_solve_seconds": round(loop_s, 4),
            "batched_solves_per_sec": round(n_entities / batched_s, 1),
            "bucket_loop_solves_per_sec": round(n_entities / loop_s, 1),
            "speedup_vs_bucket_loop": round(speedup, 3),
            "max_same_solver_diff": float(exact),
            "max_cross_solver_diff": cross,
            "p999_cross_solver_diff": cross_p999,
        })
        del results, batched_table, loop_table, data

    descent = _entities_descent_checks()

    top = curve[-1]
    _emit("game_entity_solves_per_sec", top["batched_solves_per_sec"],
          "solves/s", {
              "entities": top["entities"],
              "rows": top["rows"],
              "speedup_vs_bucket_loop": top["speedup_vs_bucket_loop"],
              "curve": curve,
              "descent_parity": descent,
              "platform": platform,
          })
    # The high-dim Newton-CG leg (ISSUE 14) rides every entities
    # invocation; PHOTON_BENCH_HIDIM=off skips it (it pays 6 compiled
    # programs up to d=1024 — real money on a cold cache).
    if os.environ.get("PHOTON_BENCH_HIDIM", "on").strip().lower() not in (
        "off", "0", "false",
    ):
        _bench_entities_hidim()


def _hidim_solve_env(path: str) -> dict:
    """Env knobs of one HIGH-DIM entity-solve path: ``newton_cg`` (the
    ISSUE 14 matrix-free route — ``PHOTON_NEWTON_MAX_DIM=0`` forces it at
    EVERY dim so the d=64 point measures CG, not the dense Cholesky) vs
    ``lbfgs`` (the vmapped iterative baseline every over-cap bin used to
    fall back to)."""
    return {
        "newton_cg": {
            "PHOTON_SOLVE_BINNING": "on", "PHOTON_SOLVE_NEWTON": "on",
            "PHOTON_SOLVE_NEWTON_CG": "on", "PHOTON_NEWTON_MAX_DIM": "0",
            # Pinned so an ambient shell override cannot shrink the CG
            # window below the d=1024 point and abort the route assertion.
            "PHOTON_NEWTON_CG_MAX_DIM": "1024",
        },
        "lbfgs": {
            "PHOTON_SOLVE_BINNING": "on", "PHOTON_SOLVE_NEWTON": "off",
            "PHOTON_SOLVE_NEWTON_CG": "off",
        },
    }[path]


_HIDIM_ENV_KEYS = ("PHOTON_SOLVE_BINNING", "PHOTON_SOLVE_NEWTON",
                   "PHOTON_SOLVE_NEWTON_CG", "PHOTON_NEWTON_MAX_DIM",
                   "PHOTON_NEWTON_CG_MAX_DIM")


def _bench_entities_hidim() -> None:
    """High-dim entity-solve leg of ``--mode entities`` (ISSUE 14): a
    d=64/256/1024 curve timing one ``RandomEffectCoordinate.train`` under
    the matrix-free Newton-CG route against the vmapped L-BFGS program
    those dims used to fall back to, emitting
    ``game_entity_solves_per_sec_hidim`` (the d=256 Newton-CG rate) on the
    default run.

    Asserted per point: the two solvers agree at the f32 cross-solver
    floor (p99 ≤ 5e-3, max ≤ 5e-2 — tests/test_newton_cg.py pins the
    Newton-CG path itself ≤1e-5 from the f64 ground truth) and every bin
    actually routed ``newton_cg``.  The acceptance bar — Newton-CG ≥ 1×
    the L-BFGS rate at d=256 — is asserted in-bench with the retry-once
    de-flake (1-core timing tails swing ±2×: a real regression fails both
    draws; only the timing is re-drawn, parity failures raise first)."""
    import jax

    from photon_tpu.game.coordinate import (
        RandomEffectCoordinate,
        RandomEffectCoordinateConfig,
    )

    platform = jax.devices()[0].platform
    points = ((64, 384), (256, 160), (1024, 32))  # (dim, entities)
    config = RandomEffectCoordinateConfig(
        shard_name="re0", entity_column="re0", problem=_entities_problem()
    )

    def run_path(data, path: str) -> tuple:
        saved = {k: os.environ.get(k) for k in _HIDIM_ENV_KEYS}
        os.environ.update(_hidim_solve_env(path))
        try:
            coord = RandomEffectCoordinate(data, config,
                                           "logistic_regression")
            routes = coord._bin_routes()
            offsets = np.zeros(data.num_examples, np.float32)
            model, _ = coord.train(offsets)  # warm-up: compile + upload
            np.asarray(model.table)
            best = float("inf")
            for _ in range(2):  # best-of-reps: shared-CPU noise rejection
                t0 = time.perf_counter()
                model, _ = coord.train(offsets)
                np.asarray(model.table)
                best = min(best, time.perf_counter() - t0)
            table = np.asarray(model.table)
        finally:
            for k, v in saved.items():
                os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
        return best, table, routes

    def measure(dim: int, n_entities: int) -> dict:
        data = _entities_dataset(n_entities, rows_mean=6, dim=dim, seed=5)
        cg_s, cg_table, cg_routes = run_path(data, "newton_cg")
        lb_s, lb_table, _ = run_path(data, "lbfgs")
        if any(r != "newton_cg" for r in cg_routes):
            raise RuntimeError(
                f"hidim d={dim}: expected every bin on the newton_cg "
                f"route, got {cg_routes}"
            )
        diff = np.abs(cg_table - lb_table)
        p99 = float(np.quantile(diff, 0.99))
        worst = float(diff.max())
        if p99 > 5e-3 or worst > 5e-2:
            raise RuntimeError(
                f"hidim d={dim}: newton_cg vs vmapped-lbfgs agreement "
                f"p99={p99:.3e} max={worst:.3e} (bounds 5e-3 / 5e-2)"
            )
        return {
            "dim": dim,
            "entities": n_entities,
            "rows": data.num_examples,
            "newton_cg_solve_seconds": round(cg_s, 4),
            "lbfgs_solve_seconds": round(lb_s, 4),
            "newton_cg_solves_per_sec": round(n_entities / cg_s, 1),
            "lbfgs_solves_per_sec": round(n_entities / lb_s, 1),
            "speedup_vs_vmapped_lbfgs": round(lb_s / cg_s, 3),
            "p99_cross_solver_diff": p99,
            "max_cross_solver_diff": worst,
        }

    curve = [measure(dim, n) for dim, n in points]
    bar_idx = next(i for i, p in enumerate(curve) if p["dim"] == 256)
    if curve[bar_idx]["speedup_vs_vmapped_lbfgs"] < 1.0:
        # Retry-once de-flake: re-draw ONLY the d=256 timing (parity
        # re-checks ride along); a real regression fails both draws.
        curve[bar_idx] = measure(*points[bar_idx])
        if curve[bar_idx]["speedup_vs_vmapped_lbfgs"] < 1.0:
            raise RuntimeError(
                f"newton_cg did not reach the vmapped L-BFGS rate at "
                f"d=256 on both draws "
                f"({curve[bar_idx]['speedup_vs_vmapped_lbfgs']:.3f}x < 1.0x)"
            )
    bar = curve[bar_idx]
    _emit("game_entity_solves_per_sec_hidim",
          bar["newton_cg_solves_per_sec"], "solves/s", {
              "dim": bar["dim"],
              "entities": bar["entities"],
              "speedup_vs_vmapped_lbfgs": bar["speedup_vs_vmapped_lbfgs"],
              "curve": curve,
              "platform": platform,
          })


def _entities_descent_checks() -> dict:
    """The ``--mode entities`` descent-level assertions: a small GAME fit
    (fixed + per-entity coordinate) under the batched path vs the
    bucket-loop path with the same solver, in BOTH residual modes — final
    random-effect tables must agree ≤1e-5 — and ``descent.host_syncs``
    must stay exactly 1 per outer iteration under the batched path."""
    from photon_tpu.game.coordinate import (
        FixedEffectCoordinateConfig,
        RandomEffectCoordinateConfig,
    )
    from photon_tpu.game.estimator import (
        GameEstimator,
        GameOptimizationConfiguration,
    )
    from photon_tpu.telemetry import TelemetrySession

    iters = 3
    data = _entities_dataset(4000, seed=7)
    # A one-shard fixture: the fixed effect trains on the same dense shard
    # (a global bias model), the random coordinate on per-entity rows.
    config = GameOptimizationConfiguration(
        coordinates={
            "fixed": FixedEffectCoordinateConfig("re0", _entities_problem()),
            "per_entity": RandomEffectCoordinateConfig(
                "re0", "re0", _entities_problem()
            ),
        },
        descent_iterations=iters,
    )
    out: dict = {}
    for residual_mode in ("device", "host"):
        tables = {}
        for path in ("batched", "bucket_loop_newton"):
            saved = {
                k: os.environ.get(k)
                for k in ("PHOTON_SOLVE_BINNING", "PHOTON_SOLVE_NEWTON")
            }
            os.environ.update(_solve_path_env(path))
            try:
                session = TelemetrySession(f"bench-entities-{residual_mode}")
                result = GameEstimator(
                    "logistic_regression", data,
                    residual_mode=residual_mode, telemetry=session,
                ).fit([config])[0]
                tables[path] = np.asarray(
                    result.model.coordinate("per_entity").table
                )
                if path == "batched" and residual_mode == "device":
                    syncs = int(
                        session.counter("descent.host_syncs", kind="stats").value
                    )
                    if syncs != iters:
                        raise RuntimeError(
                            f"descent.host_syncs == {syncs}, want {iters} "
                            "(one per outer iteration) under the batched path"
                        )
                    out["host_syncs_per_iteration"] = syncs / iters
            finally:
                for k, v in saved.items():
                    os.environ.pop(k, None) if v is None \
                        else os.environ.__setitem__(k, v)
        diff = float(
            np.abs(tables["batched"] - tables["bucket_loop_newton"]).max()
        )
        if diff > 1e-5:
            raise RuntimeError(
                f"descent-level batched parity {diff:.3e} > 1e-5 in "
                f"{residual_mode} residual mode"
            )
        out[f"max_table_diff_{residual_mode}"] = diff
    return out


def _serving_fixture():
    """Synthetic GAME model + request source for the serving bench: the
    model is CONSTRUCTED (seeded coefficient tables over the dataset's
    entity vocabulary), not fitted — serving measures scoring, and a fit
    would dominate the bench's wall clock for nothing."""
    import jax

    from photon_tpu.data.synthetic import make_game_dataset
    from photon_tpu.game.model import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import Coefficients, model_for_task

    platform = jax.devices()[0].platform
    big = platform != "cpu"
    n_entities, rows_mean = (20_000, 20) if big else (4000, 8)
    # random_dim 32: wide enough that the int8 tier's per-row scale
    # column amortizes (bytes ratio 4d/(d+4) = 3.56x >= the 3.5x bar);
    # at the pre-ISSUE-17 dim of 8 the ratio tops out at 2.67x.
    fixed_dim, random_dim = 32, 32
    data, _ = make_game_dataset(
        n_entities, rows_mean, fixed_dim, random_dim, seed=0,
        n_random_coords=2,
    )
    rng = np.random.default_rng(7)
    coordinates = {
        "fixed": FixedEffectModel(
            model_for_task("logistic_regression", Coefficients(
                rng.standard_normal(fixed_dim).astype(np.float32)
            )),
            "global",
        )
    }
    for name in ("re0", "re1"):
        keys = np.unique(data.id_columns[name])
        coordinates[name] = RandomEffectModel(
            table=rng.standard_normal(
                (len(keys), random_dim)
            ).astype(np.float32),
            keys=keys, entity_column=name, shard_name=name,
            task_type="logistic_regression",
        )
    model = GameModel(
        coordinates=coordinates, task_type="logistic_regression"
    )
    return platform, model, data


def _bench_serving(dtypes=("f32", "bf16", "int8")) -> None:
    """Online GAME scoring-service micro-bench (``--mode serving``).

    Drives a seeded long-tailed request stream (the serve_game driver's
    size distribution) through the device-resident
    :class:`~photon_tpu.serving.GameScorer` + async batcher with
    closed-loop clients, and reports p50/p99 request latency and QPS
    against the per-request HOST-scoring baseline (``GameModel.score`` on
    each request's dataset slice — the only serving story the repo had
    before the serving layer).  The emitted value is the served QPS;
    the baseline QPS and the ratio ride the detail so the speedup is a
    printed comparison, not a bare number.

    The ISSUE 17 precision tiers ride the same harness: the f32 leg keeps
    the historical ``game_serving_qps`` name (baseline continuity), then
    bf16 and int8 legs re-run the identical request stream against scorers
    whose gather tables store the reduced dtype, emitting
    ``game_serving_qps_bf16`` / ``game_serving_qps_int8``.  Asserted
    in-bench, per dtype: parity vs the f32 HOST oracle under the declared
    ``PARITY_TOL`` bound, table-bytes reduction vs f32 (bf16 >= 1.9x,
    int8 >= 3.5x), and bf16 QPS >= f32 QPS on accelerators — each leg's
    QPS is the best of ``passes`` closed-loop runs, which damps scheduler
    noise; on the CPU fixture (decode ALU cost, no bandwidth win) the bar
    is a no-collapse floor instead, recorded in ``qps_bar``."""
    from photon_tpu.drivers.serve_game import request_sizes
    from photon_tpu.game.data import take_rows
    from photon_tpu.game.lowp import parity_tol_for
    from photon_tpu.serving import (
        GameScorer,
        RequestBatcher,
        build_requests,
        request_spec_for_dataset,
        run_closed_loop,
    )
    from photon_tpu.telemetry import TelemetrySession

    platform, model, data = _serving_fixture()
    max_batch, clients, mean_rows = 128, 16, 8.0
    n_requests = 1500 if platform != "cpu" else 400
    sizes = request_sizes(n_requests, mean_rows, max_batch, seed=0)
    requests = build_requests(data, model, sizes)
    rows = int(sizes.sum())
    spec = request_spec_for_dataset(model, data)

    def leg(dtype: str, passes: int) -> dict:
        """One storage-dtype leg: warm a scorer, drive the stream
        ``passes`` times through a fresh batcher, keep the fastest pass."""
        session = TelemetrySession(f"bench-serving-{dtype}")
        scorer = GameScorer(
            model, request_spec=spec, max_batch=max_batch,
            telemetry=session, table_dtype=dtype,
        )
        t0 = time.perf_counter()
        scorer.warmup()
        warmup_s = time.perf_counter() - t0
        warm_programs = scorer.compilations
        best = None
        with RequestBatcher(
            scorer, max_batch=max_batch, max_delay_s=0.001,
            telemetry=session,
        ) as batcher:
            for _ in range(passes):
                scores, latencies, wall = run_closed_loop(
                    batcher, requests, clients=clients
                )
                if best is None or wall < best[2]:
                    best = (scores, latencies, wall)
        scores, latencies, wall = best
        snapshot = session.registry.snapshot()
        totals = {}
        for m in snapshot["counters"]:
            totals[m["name"]] = totals.get(m["name"], 0) + m["value"]
        batches = totals.get("serving.batches", 0)
        if totals.get("serving.host_syncs", 0) > batches:
            raise AssertionError(
                f"[{dtype}] serving.host_syncs exceeded one per batch"
            )
        # Post-warmup recompiles are forbidden for EVERY storage dtype:
        # the decode lives inside the warmed bucket programs.
        if scorer.compilations != warm_programs:
            raise AssertionError(
                f"[{dtype}] serving recompiled under traffic: "
                f"{scorer.compilations} programs vs {warm_programs} at "
                "warmup"
            )
        pad_hist = next(
            (h for h in snapshot["histograms"]
             if h["name"] == "serving.padded_fraction"), {},
        )
        return {
            "dtype": dtype,
            "scores": scores,
            "qps": len(requests) / wall,
            "wall": wall,
            "lat_ms": np.sort(np.asarray(latencies, np.float64)) * 1e3,
            "batches": int(batches),
            "pad_mean": round(pad_hist.get("mean") or 0.0, 3),
            "cold": int(totals.get("serving.cold_entities", 0)),
            "compiled": scorer.compilations,
            "warmup_s": warmup_s,
            "table_bytes": int(session.registry.gauge(
                "serving.table_bytes", dtype=dtype
            ).value),
        }

    # f32 always runs: it is the historical headline AND the denominator
    # for every cross-dtype bar (--table-dtype restricts the LOSSY legs).
    dtypes = tuple(dict.fromkeys(("f32",) + tuple(dtypes)))
    passes = 3 if platform == "cpu" else 2
    legs = {d: leg(d, passes) for d in dtypes}

    # Host baseline: per-request GameModel.score over the SAME row windows
    # (request_windows — the definition build_requests cut from, so the
    # parity oracle cannot drift onto misaligned rows; a warmup pass pays
    # each distinct shape's compile, as serving's warmup did), on a subset
    # big enough to time and small enough not to dominate the bench.  One
    # oracle serves every dtype leg: host scoring is always f32.
    from photon_tpu.serving import request_windows

    n_base = min(len(requests), 100)
    windows = request_windows(data.num_examples, sizes[:n_base])
    chunks = [take_rows(data, w) for w in windows]
    host_scores = [model.score(c) for c in chunks]  # warmup + parity oracle
    t0 = time.perf_counter()
    for c in chunks:
        model.score(c)
    host_wall = time.perf_counter() - t0
    host_qps = n_base / host_wall

    parity = {}
    for dtype, lg in legs.items():
        worst = max(
            float(np.abs(s[: len(h)] - h).max())
            for s, h in zip(lg["scores"][:n_base], host_scores)
        )
        tol = 1e-3 if dtype == "f32" else parity_tol_for(dtype)
        if worst > tol:
            raise AssertionError(
                f"[{dtype}] serving/host parity broke: max |delta| "
                f"{worst:.2e} > declared bound {tol:g}"
            )
        parity[dtype] = worst

    # ISSUE 17 acceptance, asserted in-bench --------------------------
    f32_bytes = legs["f32"]["table_bytes"]
    for dtype, floor in (("bf16", 1.9), ("int8", 3.5)):
        if dtype not in legs:
            continue
        ratio = f32_bytes / max(1, legs[dtype]["table_bytes"])
        legs[dtype]["bytes_ratio"] = ratio
        if ratio < floor:
            raise AssertionError(
                f"[{dtype}] table bytes only {ratio:.2f}x smaller than "
                f"f32 ({legs[dtype]['table_bytes']} vs {f32_bytes}); "
                f"the precision tier promises >= {floor}x"
            )
    # bf16 QPS bar, platform-scoped like the fleet scaling bar: where the
    # accelerator's memory system is the gather bottleneck the half-width
    # table must not lose to f32 (>= 1.0x); on the CPU fixture the decode
    # convert costs real ALU while the bandwidth saving buys nothing
    # (tables fit in cache), so the bar drops to a no-collapse floor.
    # The emitted ``qps_bar`` says which bar applied.
    qps_bar = 1.0 if platform != "cpu" else 0.8
    if "bf16" in legs:
        ratio = legs["bf16"]["qps"] / legs["f32"]["qps"]
        if ratio < qps_bar:
            raise AssertionError(
                f"bf16 serving QPS {legs['bf16']['qps']:.1f} fell below "
                f"{qps_bar}x f32's {legs['f32']['qps']:.1f} (best of "
                f"{passes} passes each) — the half-width table must not "
                "decode slower than it gathers"
            )

    for dtype, lg in legs.items():
        name = (
            "game_serving_qps" if dtype == "f32"
            else f"game_serving_qps_{dtype}"
        )
        detail = {
            "requests": len(requests),
            "rows": rows,
            "clients": clients,
            "max_batch": max_batch,
            "passes": passes,
            "mean_request_rows": round(float(sizes.mean()), 2),
            "latency_p50_ms": round(float(np.percentile(lg["lat_ms"], 50)), 3),
            "latency_p99_ms": round(float(np.percentile(lg["lat_ms"], 99)), 3),
            "rows_per_sec": round(rows / lg["wall"], 1),
            "batches": lg["batches"],
            "requests_per_batch": round(len(requests) / lg["batches"], 2)
            if lg["batches"] else None,
            "padded_fraction_mean": lg["pad_mean"],
            "cold_entities": lg["cold"],
            "compiled_programs": lg["compiled"],
            "warmup_seconds": round(lg["warmup_s"], 3),
            "host_baseline_qps": round(host_qps, 2),
            "speedup_vs_host_qps": round(lg["qps"] / host_qps, 2),
            "max_parity_delta": parity[dtype],
            "parity_bound": 1e-3 if dtype == "f32" else parity_tol_for(dtype),
            "table_bytes": lg["table_bytes"],
            "platform": platform,
        }
        if dtype != "f32":
            detail["table_dtype"] = dtype
            detail["table_bytes_vs_f32"] = round(lg["bytes_ratio"], 2)
            detail["qps_vs_f32"] = round(lg["qps"] / legs["f32"]["qps"], 3)
            if dtype == "bf16":
                detail["qps_bar"] = qps_bar
        _emit(name, lg["qps"], "req/s", detail)


def _bench_fleet(table_dtype: str = "f32") -> None:
    """Fleet-serving macro-bench (``--mode fleet`` — the ISSUE 12
    tentpole's measurement, and the serving number that rides BENCH_*.json
    going forward).

    Replays GENERATED traffic — power-law entity popularity, diurnal ramp,
    a cold-start storm segment — through the replicated serving fleet over
    the real TCP loopback transport, and measures what the single-scorer
    serving bench cannot: QPS-vs-replicas scaling, admitted-request p50/p99
    under offered load past saturation, and the admission-control shed
    fraction that keeps the tail bounded there.

    In-bench acceptance (raises on violation):

    - per-request score parity vs the host oracle ≤ 1e-3 on EVERY served
      request of every leg (storm requests included — they must ride the
      zero-row fallback, not corrupt);
    - 2-replica QPS ≥ 1.6x single-replica on the same replayed traffic —
      asserted where the host can physically scale (≥ 2 effective cores or
      a real accelerator); on a single-core CPU fixture thread-backed
      replicas share the one core, so the bar drops to a no-collapse floor
      (≥ 0.6x) and the emitted ``scaling_bar`` says which bar applied;
    - at 2x-saturation offered load, admitted-request p99 ≤ 2x the
      unsaturated p99, with the shed fraction (> 10%) reported;
    - ZERO jax compile events across every post-warmup leg (the recompile-
      freedom contract holds fleet-wide, storm and saturation included);
    - the storm segment's unknown entities are counted
      (``serving.cold_entities`` > 0) — the fallback actually exercised.
    """
    import dataclasses as _dc

    import jax.monitoring

    from photon_tpu.game.lowp import parity_tol_for
    from photon_tpu.serving import (
        AsyncScoringClient,
        ScoringClient,
        ServingFleet,
        SupervisorPolicy,
        TrafficSpec,
        generate_traffic,
        host_score_request,
        replay_open_loop,
        request_spec_for_dataset,
        run_closed_loop_outcomes,
    )
    from photon_tpu.telemetry import TelemetrySession

    platform, model, data = _serving_fixture()
    max_batch, clients = 128, 8
    n_requests = 1000 if platform != "cpu" else 300
    # --table-dtype widens the host-parity bound to the storage codec's
    # declared one (the host oracle always scores f32).
    parity_bound = 1e-3 if table_dtype == "f32" else parity_tol_for(
        table_dtype
    )
    spec = request_spec_for_dataset(model, data)
    base_traffic = TrafficSpec(
        requests=n_requests, mean_rows=8.0, max_rows=max_batch,
        popularity="powerlaw", alpha=1.1, ramp="diurnal",
        storm_frac=0.05, storm_at=0.7, seed=0,
    )
    traffic = generate_traffic(data, model, base_traffic)

    def check_parity(outcomes, leg):
        """Every served response vs the host oracle of ITS OWN request
        (each leg replays its own seeded traffic)."""
        worst = 0.0
        for out in outcomes:
            if out.status != "ok":
                continue
            want = host_score_request(model, out.item.request)
            worst = max(worst, float(np.max(np.abs(
                np.asarray(out.scores, np.float64) - want
            ))))
        if worst > parity_bound:
            raise AssertionError(
                f"fleet/host parity broke on the {leg} leg "
                f"({table_dtype} tables): max |delta| {worst:.2e} > "
                f"{parity_bound:g}"
            )
        return worst

    compile_events = []

    def listener(event, **kwargs):
        if "compile" in event:
            compile_events.append(event)

    # -- capacity legs: closed-loop clients over the TCP loopback ingest ----
    def measure_capacity(n_replicas, session):
        from photon_tpu.serving import AdmissionPolicy

        fleet = ServingFleet(
            model, replicas=n_replicas, request_spec=spec,
            max_batch=max_batch, max_delay_s=0.001, telemetry=session,
            table_dtype=table_dtype,
            # safety > 1: admission compares 2x the projected queue wait
            # against the deadline budget, absorbing EWMA estimation lag —
            # the knob that keeps the admitted tail INSIDE the 2x-p99
            # acceptance bound at the cost of shedding a little more.
            admission=AdmissionPolicy(safety=2.0),
        ).warmup()
        server = fleet.serve()
        client_pool = []

        def factory(tid):
            client = ScoringClient(server.address, telemetry=session)
            client_pool.append(client)
            return lambda item: client.score(item.request)

        jax.monitoring.register_event_listener(listener)
        try:
            outcomes, wall = run_closed_loop_outcomes(
                factory, traffic.items, clients=clients
            )
        finally:
            jax.monitoring.unregister_event_listener(listener)
            for client in client_pool:
                client.close()
        errors = [o for o in outcomes if o.status != "ok"]
        if errors:
            raise AssertionError(
                f"{len(errors)} failed requests at {n_replicas} replicas; "
                f"first: {errors[0].reason}"
            )
        parity = check_parity(outcomes, f"{n_replicas}-replica capacity")
        return fleet, server, outcomes, len(outcomes) / wall, parity

    cores = len(os.sched_getaffinity(0))
    can_scale = platform != "cpu" or cores >= 2
    scaling_bar = 1.6 if can_scale else 0.6
    # One retry on a scaling miss: on the 1-core fixture the 1-replica
    # leg's closed-loop QPS swings ±2x run-to-run with OS scheduling (8
    # client threads + handlers + batcher on one core), so a single draw
    # under the no-collapse floor can be pure noise — a REAL collapse
    # fails both draws.
    for attempt in range(2):
        session1 = TelemetrySession("bench-fleet-1r")
        fleet1, _, _, qps1, _ = measure_capacity(1, session1)
        fleet1.close()
        session2 = TelemetrySession("bench-fleet-2r")
        fleet2, server2, _, qps2, parity_cap = measure_capacity(2, session2)
        scaling = qps2 / qps1
        if scaling >= scaling_bar or attempt == 1:
            break
        fleet2.close()
    if scaling < scaling_bar:
        raise AssertionError(
            f"2-replica QPS scaling {scaling:.2f}x under the "
            f"{scaling_bar:.1f}x bar ({qps2:.0f} vs {qps1:.0f} req/s, "
            f"{cores} effective cores)"
        )

    # -- unsaturated vs 2x-saturation open-loop replays THROUGH the socket
    # (ISSUE 13 satellite / ROADMAP fleet edge (c)): the pipelined
    # AsyncScoringClient tags request frames with sequence ids and the
    # server responds out of order, so the replay's arrival schedule
    # drives the TCP transport itself — framing + socket backpressure sit
    # inside the overload measurement, while admission keeps its
    # fast-fail semantics (sheds come back as typed frames).  fleet2's
    # per-row service EWMA is already warm from the capacity leg, so the
    # saturation leg's admission projections are live from the first
    # arrival — exactly how a long-running fleet meets an overload.
    open_client = AsyncScoringClient(
        server2.address, connections=clients, telemetry=session2
    )

    def open_loop_legs(seed_base: int):
        unsat = generate_traffic(data, model, _dc.replace(
            base_traffic, target_qps=0.4 * qps2, seed=seed_base,
        ))
        out_unsat = replay_open_loop(open_client.submit, unsat,
                                     timeout_s=120.0)
        ok_unsat = [o for o in out_unsat if o.status == "ok"]
        if len(ok_unsat) != len(out_unsat):
            raise AssertionError(
                f"unsaturated replay shed/failed "
                f"{len(out_unsat) - len(ok_unsat)} requests"
            )
        lat_unsat = np.sort([o.latency_s for o in ok_unsat])
        p50_u = float(np.percentile(lat_unsat, 50))
        p99_u = float(np.percentile(lat_unsat, 99))
        check_parity(out_unsat, "unsaturated")

        deadline = 1.5 * p99_u
        # 2x requests on the saturation leg: its admitted set is the
        # ~(1 - shed) tail of the stream, and a p99 over a few dozen
        # admitted samples is essentially a max — double the sample so
        # the tail gate measures the system, not one scheduler hiccup.
        sat = generate_traffic(data, model, _dc.replace(
            base_traffic, requests=2 * n_requests,
            target_qps=2.0 * qps2, seed=seed_base + 1,
            deadline_ms=deadline * 1e3,
        ))
        out_s = replay_open_loop(open_client.submit, sat, timeout_s=120.0)
        ok_s = [o for o in out_s if o.status == "ok"]
        errors_s = [o for o in out_s if o.status == "error"]
        if errors_s:
            raise AssertionError(
                f"{len(errors_s)} failed requests in the saturation leg; "
                f"first: {errors_s[0].reason}"
            )
        if not ok_s:
            raise AssertionError("saturation leg admitted nothing")
        p99_s = float(np.percentile(
            np.sort([o.latency_s for o in ok_s]), 99
        ))
        shed_frac = sum(1 for o in out_s if o.status == "shed") / len(out_s)
        parity = check_parity(out_s, "saturation")
        return {
            "p50_unsat": p50_u, "p99_unsat": p99_u, "p99_sat": p99_s,
            "deadline_s": deadline, "shed_fraction": shed_frac,
            "admitted_sat": len(ok_s), "parity_sat": parity,
        }

    jax.monitoring.register_event_listener(listener)
    try:
        # One retry on a bounds miss: the 1-core fixture's open-loop tails
        # ride the OS scheduler (client readers + server handlers +
        # batcher threads on one core), so a single p99 gate draw can
        # fail on a hiccup — a REAL tail regression fails both draws.
        legs = open_loop_legs(seed_base=1)
        if (legs["p99_sat"] > 2.0 * legs["p99_unsat"]
                or legs["shed_fraction"] <= 0.10):
            legs = open_loop_legs(seed_base=11)
    finally:
        jax.monitoring.unregister_event_listener(listener)
        open_client.close()
    p50_unsat, p99_unsat = legs["p50_unsat"], legs["p99_unsat"]
    p99_sat, deadline_s = legs["p99_sat"], legs["deadline_s"]
    shed_fraction, parity_sat = legs["shed_fraction"], legs["parity_sat"]
    if p99_sat > 2.0 * p99_unsat:
        raise AssertionError(
            f"admitted-request p99 {p99_sat * 1e3:.2f} ms at 2x saturation "
            f"exceeds 2x the unsaturated p99 ({p99_unsat * 1e3:.2f} ms) — "
            "admission control is not bounding the tail"
        )
    if shed_fraction <= 0.10:
        raise AssertionError(
            f"only {shed_fraction:.1%} shed at 2x saturation offered load "
            "— past-saturation load is not actually shedding"
        )
    if compile_events:
        raise AssertionError(
            f"{len(compile_events)} jax compile events after warmup "
            f"(first: {compile_events[0]}) — fleet serving recompiled"
        )

    def totals(session, name):
        return sum(
            m["value"] for m in session.registry.snapshot()["counters"]
            if m["name"] == name
        )

    for s in (session1, session2):
        if totals(s, "serving.host_syncs") > totals(s, "serving.batches"):
            raise AssertionError("serving.host_syncs exceeded one per batch")
    cold = totals(session2, "serving.cold_entities")
    if cold <= 0:
        raise AssertionError(
            "the cold-start storm never hit the zero-row fallback "
            "(serving.cold_entities == 0)"
        )
    fleet2.close()

    _emit("game_fleet_qps", qps2, "req/s", {
        "replicas": 2,
        "requests_per_leg": n_requests,
        "clients": clients,
        "transport": "tcp-loopback (capacity legs closed-loop; open-loop "
                     "legs pipelined through AsyncScoringClient)",
        "qps_1_replica": round(qps1, 2),
        "qps_2_replicas": round(qps2, 2),
        "scaling_x": round(scaling, 3),
        "scaling_bar": scaling_bar,
        "effective_cores": cores,
        "latency_p50_unsat_ms": round(p50_unsat * 1e3, 3),
        "latency_p99_unsat_ms": round(p99_unsat * 1e3, 3),
        "latency_p99_saturated_ms": round(p99_sat * 1e3, 3),
        "deadline_ms": round(deadline_s * 1e3, 3),
        "offered_qps_saturated": round(2.0 * qps2, 1),
        "admitted_saturated": legs["admitted_sat"],
        "shed_fraction_saturated": round(shed_fraction, 4),
        "storm_requests": sum(
            1 for item in traffic.items if item.kind == "storm"
        ),
        "cold_entities": int(cold),
        "max_parity_delta": max(parity_cap, parity_sat),
        "compiled_programs_2r": fleet2.compilations,
        "platform": platform,
    })

    # -- CHAOS leg (ISSUE 13): replica kill mid-replay under supervision --
    # A supervised 2-replica fleet takes a replica kill in the middle of
    # an open-loop replay.  In-bench bars: ZERO lost futures (every
    # request resolves ok or shed — exactly-once through the reroute
    # path), the shed fraction during the outage window stays bounded
    # (the survivor serves; no collapse), the replica is resurrected
    # through the canary-gated rejoin, post-rejoin closed-loop QPS
    # recovers to >= 0.9x the pre-kill burst, and the parent records zero
    # jax compile events across the whole cycle.  Backend: subprocess
    # where the host can actually scale processes (>= 2 effective cores
    # or an accelerator — the kill is a real SIGKILL of the child), the
    # thread backend with the same bars on the 1-core fixture.
    import signal
    import threading as _threading
    import time as _time

    from photon_tpu.fault.injection import FaultPlan, set_plan
    from photon_tpu.serving import AdmissionPolicy as _Admission

    chaos_backend = "subprocess" if can_scale else "thread"
    session3 = TelemetrySession("bench-fleet-chaos")
    fleet3 = ServingFleet(
        model, replicas=2, request_spec=spec, backend=chaos_backend,
        max_batch=max_batch, max_delay_s=0.001, telemetry=session3,
        admission=_Admission(safety=2.0), table_dtype=table_dtype,
    ).warmup()
    fleet3.supervise(SupervisorPolicy(
        probe_interval_s=0.1, probe_deadline_s=60.0,
        respawn_base_s=0.05, max_deaths=5,
    ))
    import shutil as _shutil
    import tempfile as _tempfile

    from photon_tpu.serving import ObservePolicy
    from photon_tpu.telemetry import TraceSampler

    flight_dir = _tempfile.mkdtemp(prefix="bench-fleet-flight-")
    compile_events.clear()
    jax.monitoring.register_event_listener(listener)
    try:
        burst_items = generate_traffic(data, model, _dc.replace(
            base_traffic, requests=150, seed=4,
        )).items

        def chaos_factory(tid):
            return lambda item: fleet3.score(item.request)

        # Best-of-3: a single 150-request closed-loop burst covers ~0.1s
        # of wall on the 1-core fixture and swings 30%+ with OS
        # scheduling; the recovery bar below compares PEAK achievable
        # rates (a hiccup only ever slows a draw down, never speeds it
        # up), so one unlucky draw on either side can't fail a healthy
        # fleet while a sustained regression still fails every draw.
        qps_pre = 0.0
        for _ in range(3):
            out_pre, wall_pre = run_closed_loop_outcomes(
                chaos_factory, burst_items, clients=clients
            )
            if any(o.status != "ok" for o in out_pre):
                raise AssertionError("pre-kill burst failed requests")
            qps_pre = max(qps_pre, len(out_pre) / wall_pre)

        # -- observability leg (ISSUE 16): tracing overhead + merged trace.
        # Attach the fleet observer at full sampling, replay the SAME
        # closed-loop burst traced, and bar the overhead: tracing is
        # per-request dict bookkeeping and must cost < 5% QPS.  One-core
        # closed-loop QPS swings with OS scheduling, so a miss re-draws
        # BOTH sides (the sampler toggled off IS the untraced path) — a
        # real overhead regression fails every pair.
        observer = fleet3.observe(
            policy=ObservePolicy(sample_rate=1.0, poll_interval_s=0.1),
            flight_dir=flight_dir,
        )

        def burst_qps(leg):
            out, wall = run_closed_loop_outcomes(
                chaos_factory, burst_items, clients=clients
            )
            if any(o.status != "ok" for o in out):
                raise AssertionError(f"{leg} burst failed requests")
            return len(out) / wall

        qps_untraced = qps_pre
        for t_attempt in range(3):
            qps_traced = burst_qps("traced")
            overhead_x = qps_traced / qps_untraced
            if overhead_x >= 0.95:
                break
            observer.sampler = TraceSampler(0.0)
            qps_untraced = burst_qps("untraced re-draw")
            observer.sampler = TraceSampler(1.0)
        if overhead_x < 0.95:
            raise AssertionError(
                f"traced QPS is {overhead_x:.3f}x untraced "
                f"({qps_traced:.0f} vs {qps_untraced:.0f} req/s) — "
                "tracing overhead exceeds the 5% budget"
            )

        # One request through the full client→router→replica path over
        # TCP: the merged trace tree must span the processes and its
        # critical-path stage sum must reconcile with the end-to-end
        # latency the router observed.
        server3 = fleet3.serve()
        obs_client = AsyncScoringClient(
            server3.address, connections=1, telemetry=session3,
            observer=observer,
        )
        try:
            t_probe0 = _time.monotonic()
            obs_client.submit(burst_items[0].request).result(timeout=60.0)
            probe_wall = _time.monotonic() - t_probe0
        finally:
            obs_client.close()
        observer.poll_once()  # drain child spans shipped inline/ctrl
        tid = next(
            (t for t in reversed(observer.collector.trace_ids())
             if any(d.get("name") == "client.request"
                    for d in observer.collector.trace(t))),
            None,
        )
        if tid is None:
            raise AssertionError(
                "the traced probe request produced no merged trace with a "
                "client span"
            )
        cp = observer.collector.critical_path(tid)
        if cp is None:
            raise AssertionError(
                "no critical path for the probe trace (router span missing)"
            )
        n_procs = len(cp["processes"])
        want_procs = 3 if chaos_backend == "subprocess" else 2
        if n_procs < want_procs:
            raise AssertionError(
                f"probe trace spans {n_procs} process(es) "
                f"({cp['processes']}) — expected >= {want_procs} on the "
                f"{chaos_backend} backend"
            )
        if abs(cp["stage_sum_s"] - cp["total_s"]) > 1e-6 + 1e-3 * cp["total_s"]:
            raise AssertionError(
                f"critical-path stages sum to {cp['stage_sum_s']:.6f}s but "
                f"the request took {cp['total_s']:.6f}s — the decomposition "
                "does not reconcile"
            )
        if cp["total_s"] > probe_wall + 0.05:
            raise AssertionError(
                f"router-observed latency {cp['total_s']:.3f}s exceeds the "
                f"client-measured wall {probe_wall:.3f}s"
            )

        _emit("game_fleet_traced_qps", qps_traced, "req/s", {
            "backend": chaos_backend,
            "sample_rate": 1.0,
            "qps_untraced": round(qps_untraced, 2),
            "overhead_x": round(overhead_x, 3),
            "trace_processes": n_procs,
            "trace_spans": cp["spans"],
            "critical_path_ms": {
                s["stage"]: round(s["duration_s"] * 1e3, 3)
                for s in cp["stages"]
            },
            "end_to_end_ms": round(cp["total_s"] * 1e3, 3),
            "platform": platform,
        })

        rate = min(0.4 * qps2, 150.0)
        horizon_s = 12.0 if chaos_backend == "subprocess" else 8.0
        chaos = generate_traffic(data, model, _dc.replace(
            base_traffic, requests=max(200, int(rate * horizon_s)),
            target_qps=rate, seed=5,
            deadline_ms=max(4.0 * p99_unsat * 1e3, 50.0),
        ))
        kill_at_s = 0.3 * chaos.duration_s
        marks = {}
        t0 = _time.monotonic()

        def chaos_monkey():
            _time.sleep(kill_at_s)
            r0 = fleet3.replicas[0]
            if chaos_backend == "subprocess":
                os.kill(r0.child_pid, signal.SIGKILL)
            else:
                set_plan(FaultPlan.parse(
                    "replica:crash:replica=r0:times=1"
                ))
            # The kill LANDS when the replica actually latches dead (the
            # next batch on it, or the supervisor's probe) — the outage
            # window is [landed, rejoined], not [injected, rejoined].
            while r0.alive and _time.monotonic() - t0 < 120.0:
                _time.sleep(0.02)
            marks["kill"] = _time.monotonic() - t0
            while (not r0.alive
                   and _time.monotonic() - t0 < 120.0):
                _time.sleep(0.02)
            marks["rejoin"] = _time.monotonic() - t0

        monkey = _threading.Thread(target=chaos_monkey, daemon=True)
        monkey.start()
        out_chaos = replay_open_loop(fleet3.submit, chaos, timeout_s=180.0)
        monkey.join(timeout=120.0)
        set_plan(None)

        lost = [o for o in out_chaos if o.status == "error"]
        if lost:
            raise AssertionError(
                f"chaos leg LOST {len(lost)} futures (first: "
                f"{lost[0].reason}) — the exactly-once reroute broke"
            )
        check_parity(out_chaos, "chaos")
        if "rejoin" not in marks or not fleet3.replicas[0].alive:
            raise AssertionError(
                "the killed replica never rejoined the dispatch set"
            )
        deaths3 = sum(
            m["value"] for m in session3.registry.snapshot()["counters"]
            if m["name"] == "serving.replica_deaths"
        )
        resurrections3 = sum(
            m["value"] for m in session3.registry.snapshot()["counters"]
            if m["name"] == "serving.replica_resurrections"
        )
        if deaths3 < 1 or resurrections3 < 1:
            raise AssertionError(
                f"chaos accounting off: deaths={deaths3}, "
                f"resurrections={resurrections3}"
            )
        # Window on COMPLETION times (Outcome.finished_at_s): on the
        # 1-core fixture the replay lags its schedule, so scheduled
        # arrival offsets drift from when requests actually hit the
        # dead-replica window.
        outage = [
            o for o in out_chaos
            if o.finished_at_s is not None
            and marks["kill"] <= o.finished_at_s <= marks["rejoin"]
        ]
        outage_shed = (
            sum(1 for o in outage if o.status == "shed") / len(outage)
            if outage else 0.0
        )
        if outage and outage_shed > 0.9:
            raise AssertionError(
                f"shed fraction {outage_shed:.1%} during the outage — the "
                "survivor is not actually serving through the failure"
            )
        # Best-of-3, mirroring the pre-kill measurement above.
        qps_post = 0.0
        for _ in range(3):
            out_post, wall_post = run_closed_loop_outcomes(
                chaos_factory, burst_items, clients=clients
            )
            if any(o.status != "ok" for o in out_post):
                raise AssertionError("post-rejoin burst failed requests")
            qps_post = max(qps_post, len(out_post) / wall_post)
        recovered = qps_post / qps_pre
        if recovered < 0.9:
            raise AssertionError(
                f"post-rejoin QPS recovered only {recovered:.2f}x of "
                f"pre-kill ({qps_post:.0f} vs {qps_pre:.0f} req/s)"
            )
        # The kill must leave a postmortem: the supervisor hands the victim
        # to the observer, which persists the flight ring next to the run
        # artifacts (ISSUE 16 flight recorder).
        if not observer.flight_dumps:
            raise AssertionError(
                "no flight dump collected after the chaos kill"
            )
        flight0 = observer.flight_dumps[0]
        if not flight0["path"] or not os.path.exists(flight0["path"]):
            raise AssertionError(
                f"flight dump for {flight0['replica']} was not persisted "
                f"({flight0['path']!r})"
            )
    finally:
        jax.monitoring.unregister_event_listener(listener)
        fleet3.close()
        _shutil.rmtree(flight_dir, ignore_errors=True)
    if compile_events:
        raise AssertionError(
            f"{len(compile_events)} jax compile events across the chaos "
            f"kill->resurrect cycle (first: {compile_events[0]})"
        )

    _emit("game_fleet_chaos_recovery_x", recovered, "x pre-kill QPS", {
        "backend": chaos_backend,
        "qps_pre_kill": round(qps_pre, 2),
        "qps_post_rejoin": round(qps_post, 2),
        "offered_qps_during_outage": round(rate, 1),
        "outage_s": round(marks["rejoin"] - marks["kill"], 3),
        "outage_requests": len(outage),
        "outage_shed_fraction": round(outage_shed, 4),
        "chaos_requests": len(out_chaos),
        "deaths": int(deaths3),
        "resurrections": int(resurrections3),
        "flight_dumps": len(observer.flight_dumps),
        "lost_spans_recovered": int(sum(
            d.get("lost_spans_recovered", 0) for d in observer.flight_dumps
        )),
        "platform": platform,
    })


def _bench_fleet_chaos_matrix(table_dtype: str = "f32") -> None:
    """Partition-tolerance chaos matrix (``--mode fleet --chaos-matrix``
    — the ISSUE 19 acceptance sweep).

    Five deterministic network-fault cells against supervised 2-replica
    SUBPROCESS fleets, each injected through the seeded transport shim
    (``serving/netfault.py``), plus the capacity-boundary background-
    rebuild leg:

    - ``partition_heal``  — both-way partition SHORTER than the lease:
      the replica rejoins silently (zero deaths, lease misses counted);
    - ``partition_lease`` — partition PAST the lease: death declared
      with cause ``lease``, canary-gated resurrection after heal;
    - ``zombie_fenced``   — seeded frame drops force timeout/resend, and
      a generation-ratcheted child (the resurrection race, distilled)
      must have its stale-generation answer FENCED, never served;
    - ``duplicate``       — every data frame duplicated both ways: the
      extra responses are fenced by seq, each request served once;
    - ``slow_replica``    — byte-rate throttle + per-frame delay: slow
      is not dead (zero deaths, zero false resurrections).

    Every cell bars ZERO lost futures and per-response parity vs the
    host oracle (a double-served or cross-wired response breaks parity;
    the fence counters prove the stale answers existed and were
    discarded).  The rebuild leg grows the vocabulary PAST the serving
    tables' headroom under live traffic: ``rollout_with_rebuild`` must
    cross the capacity boundary with zero shed/lost requests and zero
    parent-side recompiles."""
    import dataclasses as _dc
    import threading as _threading
    import time as _time

    import jax.monitoring

    from photon_tpu.game.lowp import parity_tol_for
    from photon_tpu.game.model import GameModel, RandomEffectModel
    from photon_tpu.serving import (
        AdmissionPolicy,
        ReplicaDeadError,
        ServingFleet,
        SupervisorPolicy,
        TrafficSpec,
        generate_traffic,
        host_score_request,
        replay_open_loop,
        request_spec_for_dataset,
    )
    from photon_tpu.serving.netfault import (
        LinkRule,
        NetFaultPlan,
        partition,
        set_net_plan,
    )
    from photon_tpu.telemetry import TelemetrySession

    platform, model, data = _serving_fixture()
    parity_bound = 1e-3 if table_dtype == "f32" else parity_tol_for(
        table_dtype
    )
    spec = request_spec_for_dataset(model, data)
    n_requests = 60 if platform == "cpu" else 200
    cells: dict = {}

    def counter_sum(session, name, **labels):
        return sum(
            m["value"] for m in session.registry.snapshot()["counters"]
            if m["name"] == name and all(
                m["labels"].get(k) == v for k, v in labels.items()
            )
        )

    def check_parity(outcomes, cell, ref_model=None):
        m = ref_model if ref_model is not None else model
        worst = 0.0
        for out in outcomes:
            if out.status != "ok":
                continue
            want = host_score_request(m, out.item.request)
            worst = max(worst, float(np.max(np.abs(
                np.asarray(out.scores, np.float64) - want
            ))))
        if worst > parity_bound:
            raise AssertionError(
                f"chaos cell {cell}: served/host parity {worst:.2e} > "
                f"{parity_bound:g} — a double-served or cross-wired "
                "response leaked through"
            )
        return worst

    def assert_none_lost(outcomes, cell):
        lost = [o for o in outcomes if o.status == "error"]
        if lost:
            raise AssertionError(
                f"chaos cell {cell}: LOST {len(lost)} futures (first: "
                f"{lost[0].reason})"
            )

    def rewire(fleet):
        """Close every replica's parent-side sockets: the next exchange's
        silent reconnect dials back through ``maybe_shim``, so the links
        pick up (or drop) the installed plan without restarting children."""
        for r in fleet.replicas:
            sc = getattr(r, "scorer", None)
            for ch in ("_data", "_ctrl"):
                s = getattr(sc, ch, None)
                if s is not None:
                    try:
                        s.close()
                    except OSError:
                        pass

    def make_fleet(session, *, lease_s, probe_deadline_s):
        set_net_plan(None)
        fleet = ServingFleet(
            model, replicas=2, request_spec=spec, backend="subprocess",
            max_batch=64, max_delay_s=0.001, telemetry=session,
            admission=AdmissionPolicy(safety=2.0), table_dtype=table_dtype,
        ).warmup()
        fleet.supervise(SupervisorPolicy(
            probe_interval_s=0.1, probe_deadline_s=probe_deadline_s,
            hang_timeout_s=120.0, lease_s=lease_s,
            respawn_base_s=0.05, max_deaths=10,
        ))
        # Tight exchange timeout: a black-holed frame resolves in ~0.25s
        # resends, so the cell's fault window dominates its wall clock.
        for r in fleet.replicas:
            r.scorer.exchange_timeout_s = 0.25
        return fleet

    def traffic_for(seed, requests=n_requests, qps=25.0):
        # No per-request deadline: chaos cells bar exactly-once delivery,
        # not latency — a deadline would let the admission controller shed
        # the very requests whose survival is under test.
        return generate_traffic(data, model, TrafficSpec(
            requests=requests, mean_rows=8.0, max_rows=64,
            popularity="powerlaw", alpha=1.1, ramp="flat",
            target_qps=qps, seed=seed,
        ))

    # ---- cell 1: partition-then-heal-WITHIN-lease (silent rejoin) ----------
    session = TelemetrySession("chaos-partition-heal")
    fleet = make_fleet(session, lease_s=3.0, probe_deadline_s=1.0)
    try:
        plan = NetFaultPlan([partition("r0:*", 0.4, 1.2)], seed=11)
        set_net_plan(plan)
        rewire(fleet)
        out = replay_open_loop(fleet.submit, traffic_for(1), timeout_s=180.0)
        _time.sleep(0.5)  # a post-heal supervisor pass renews the lease
        assert_none_lost(out, "partition_heal")
        worst = check_parity(out, "partition_heal")
        deaths = counter_sum(session, "serving.replica_deaths")
        misses = counter_sum(session, "serving.lease_probe_misses")
        if deaths:
            raise AssertionError(
                f"partition_heal: {deaths} death(s) declared inside the "
                "lease window — the lease did not tolerate the partition"
            )
        if not misses:
            raise AssertionError(
                "partition_heal: zero lease probe misses counted — the "
                "partition never actually hit the control channel"
            )
        if not fleet.replicas[0].alive:
            raise AssertionError("partition_heal: r0 did not rejoin")
        cells["partition_heal"] = {
            "requests": len(out), "lease_misses": int(misses),
            "partitioned_frames": plan.total("partitioned"),
            "resends": int(counter_sum(
                session, "serving.exchange_resends"
            )),
            "parity": worst,
        }
    finally:
        set_net_plan(None)
        fleet.close()

    # ---- cell 2: partition PAST the lease (death + resurrection) -----------
    session = TelemetrySession("chaos-partition-lease")
    fleet = make_fleet(session, lease_s=1.0, probe_deadline_s=0.5)
    try:
        plan = NetFaultPlan([partition("r0:*", 0.3, 4.0)], seed=12)
        set_net_plan(plan)
        rewire(fleet)
        out = replay_open_loop(
            fleet.submit, traffic_for(2, qps=15.0), timeout_s=180.0
        )
        t0 = _time.monotonic()
        while (not fleet.replicas[0].alive
               and _time.monotonic() - t0 < 120.0):
            _time.sleep(0.05)
        assert_none_lost(out, "partition_lease")
        worst = check_parity(out, "partition_lease")
        lease_deaths = counter_sum(
            session, "serving.replica_deaths", cause="lease"
        )
        resurrections = counter_sum(
            session, "serving.replica_resurrections"
        )
        if lease_deaths < 1:
            raise AssertionError(
                "partition_lease: no death with cause 'lease' — expiry "
                "did not declare"
            )
        if resurrections < 1 or not fleet.replicas[0].alive:
            raise AssertionError(
                "partition_lease: the expired replica never resurrected "
                "after the heal"
            )
        cells["partition_lease"] = {
            "requests": len(out), "lease_deaths": int(lease_deaths),
            "resurrections": int(resurrections), "parity": worst,
        }
    finally:
        set_net_plan(None)
        fleet.close()

    # ---- cells 3-5 share one fleet (generous lease: no deaths expected) ----
    session = TelemetrySession("chaos-frames")
    fleet = make_fleet(session, lease_s=60.0, probe_deadline_s=5.0)
    try:
        # -- duplicate-frames: every data frame duplicated, both ways.
        plan = NetFaultPlan(
            [LinkRule(link="r0:data", direction="both", dup_p=1.0)],
            seed=13,
        )
        set_net_plan(plan)
        rewire(fleet)
        out = replay_open_loop(fleet.submit, traffic_for(3), timeout_s=180.0)
        assert_none_lost(out, "duplicate")
        worst = check_parity(out, "duplicate")
        if plan.total("duplicated") < 1:
            raise AssertionError("duplicate: the dup rule never fired")
        fenced_seq = counter_sum(
            session, "serving.fenced_responses", reason="stale_seq"
        )
        cells["duplicate"] = {
            "requests": len(out),
            "duplicated_frames": plan.total("duplicated"),
            "fenced_stale_seq": int(fenced_seq), "parity": worst,
        }

        # -- slow-replica: throttle + delay; slow is NOT dead.
        plan = NetFaultPlan([LinkRule(
            link="r0:data", direction="both", delay_s=0.03,
            rate_bytes_per_s=2e6,
        )], seed=14)
        set_net_plan(plan)
        rewire(fleet)
        out = replay_open_loop(
            fleet.submit, traffic_for(4, qps=15.0), timeout_s=180.0
        )
        assert_none_lost(out, "slow_replica")
        worst = check_parity(out, "slow_replica")
        if plan.total("throttled") < 1:
            raise AssertionError("slow_replica: the throttle never fired")
        if counter_sum(session, "serving.replica_deaths"):
            raise AssertionError(
                "slow_replica: a merely-slow replica was declared dead"
            )
        if counter_sum(session, "serving.replica_resurrections"):
            raise AssertionError(
                "slow_replica: false-positive resurrection"
            )
        cells["slow_replica"] = {
            "requests": len(out),
            "throttled_frames": plan.total("throttled"),
            "parity": worst,
        }

        # -- zombie-fenced: seeded drops force timeout/resend; then the
        # distilled resurrection race — the child ratcheted PAST the
        # router's recorded generation must have its answer fenced.
        plan = NetFaultPlan(
            [LinkRule(link="r0:data", direction="both", drop_p=0.3)],
            seed=15,
        )
        set_net_plan(plan)
        rewire(fleet)
        out = replay_open_loop(fleet.submit, traffic_for(5), timeout_s=180.0)
        assert_none_lost(out, "zombie_fenced")
        worst = check_parity(out, "zombie_fenced")
        resends = counter_sum(session, "serving.exchange_resends")
        if plan.total("dropped") < 1 or resends < 1:
            raise AssertionError(
                "zombie_fenced: drops/resends never fired "
                f"(dropped={plan.total('dropped')}, resends={resends})"
            )
        set_net_plan(None)
        rewire(fleet)
        r0 = fleet.replicas[0]
        r0.scorer.ping(10.0, gen=r0.generation + 3)  # child ratchets ahead
        try:
            r0.scorer.score_batch(traffic_for(6, requests=1).items[0].request)
            raise AssertionError(
                "zombie_fenced: a stale-generation response was SERVED"
            )
        except ReplicaDeadError:
            pass
        fenced_gen = counter_sum(
            session, "serving.fenced_responses", reason="stale_gen"
        )
        if fenced_gen < 1:
            raise AssertionError(
                "zombie_fenced: the stale-generation answer was not "
                "counted as fenced"
            )
        # Re-sync the ratchet we injected so teardown sees a sane replica.
        r0.generation += 3
        r0.scorer.generation = r0.generation
        cells["zombie_fenced"] = {
            "requests": len(out), "dropped_frames": plan.total("dropped"),
            "resends": int(resends), "fenced_stale_gen": int(fenced_gen),
            "parity": worst,
        }
    finally:
        set_net_plan(None)
        fleet.close()

    # ---- rebuild leg: growth past headroom, zero-downtime cutover ----------
    # The grown model is built BEFORE the compile listener attaches:
    # with_entities scatters on device (legitimate one-time compiles that
    # are the MODEL's, not the serving path's).
    coords = dict(model.coordinates)
    for name, coord in model.coordinates.items():
        if isinstance(coord, RandomEffectModel):
            keys = np.asarray(coord.keys)
            extra = max(4, len(keys))  # past the factor-1 headroom (E+1)
            if keys.dtype.kind in "iu":
                new = keys.max() + np.arange(
                    1, extra + 1, dtype=np.int64
                ).astype(keys.dtype)
            else:
                new = np.array([f"grown-{i:06d}" for i in range(extra)])
            coords[name] = coord.with_entities(
                np.unique(np.concatenate([keys, new]))
            )
    grown = GameModel(coordinates=coords, task_type=model.task_type)
    import jax as _jax
    _jax.block_until_ready([
        c.table for c in grown.coordinates.values()
        if isinstance(c, RandomEffectModel)
    ])

    compile_events: list = []

    def listener(event, **kwargs):
        if "compile" in event:
            compile_events.append(event)

    session = TelemetrySession("chaos-rebuild")
    set_net_plan(None)
    fleet = ServingFleet(
        model, replicas=2, request_spec=spec, backend="subprocess",
        max_batch=64, max_delay_s=0.001, telemetry=session,
        admission=AdmissionPolicy(safety=2.0), table_dtype=table_dtype,
        table_capacity_factor=1,
    ).warmup()
    fleet.supervise(SupervisorPolicy(
        probe_interval_s=0.2, probe_deadline_s=60.0, lease_s=30.0,
    ))
    live = traffic_for(7, requests=max(40, n_requests)).items
    stop = _threading.Event()
    served: list = []
    errors: list = []

    def client(tid):
        i = tid
        while not stop.is_set():
            req = live[i % len(live)].request
            try:
                served.append((req, fleet.score(req)))
            except Exception as e:  # noqa: BLE001 — audited below
                errors.append(e)
            i += 1
            _time.sleep(0.02)

    threads = [
        _threading.Thread(target=client, args=(t,), daemon=True)
        for t in range(2)
    ]
    jax.monitoring.register_event_listener(listener)
    try:
        for t in threads:
            t.start()
        _time.sleep(0.3)
        rebuilt = fleet.rollout_with_rebuild(grown)
        _time.sleep(0.5)  # post-cutover traffic rides the new tables
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        jax.monitoring.unregister_event_listener(listener)
    try:
        if not rebuilt:
            raise AssertionError(
                "rebuild leg: the grown model fit the old tables — the "
                "growth did not cross the capacity boundary"
            )
        if errors:
            raise AssertionError(
                f"rebuild leg: {len(errors)} shed/lost request(s) during "
                f"the background rebuild (first: {errors[0]!r})"
            )
        if compile_events:
            raise AssertionError(
                f"rebuild leg: {len(compile_events)} parent-side compile "
                f"event(s) (first: {compile_events[0]}) — the surviving "
                "path recompiled"
            )
        if counter_sum(session, "serving.fleet_rebuilds") != 1:
            raise AssertionError("rebuild leg: fleet_rebuilds != 1")
        # Post-cutover responses during the window must match ONE of the
        # two published models (old before the atomic cut, grown after).
        for req, scores in served[:: max(1, len(served) // 64)]:
            worst = min(
                float(np.abs(np.asarray(scores, np.float64)
                             - host_score_request(m, req)).max())
                for m in (model, grown)
            )
            if worst > parity_bound:
                raise AssertionError(
                    f"rebuild leg: mixed-model response ({worst:.2e})"
                )
        # The grown entities actually serve from the rebuilt tables.
        from photon_tpu.serving.supervisor import probe_request_for
        probe = probe_request_for(grown, spec, rows=4, seed=9)
        got = fleet.score(probe)
        want = host_score_request(grown, probe)
        if float(np.abs(np.asarray(got, np.float64) - want).max()) \
                > parity_bound:
            raise AssertionError(
                "rebuild leg: grown-vocabulary probe parity broke"
            )
        cells["rebuild"] = {
            "served_during_rebuild": len(served),
            "rebuilds": int(counter_sum(
                session, "serving.replica_rebuilds"
            )),
        }
    finally:
        fleet.close()

    _emit("game_fleet_chaos_matrix", float(len(cells)), "cells passed", {
        "backend": "subprocess",
        "table_dtype": table_dtype,
        "platform": platform,
        **{f"{cell}_{k}": (round(v, 8) if isinstance(v, float) else v)
           for cell, info in cells.items() for k, v in info.items()},
    })


def _tenant_clone(model, seed: int):
    """A tenant model for the multi-model arena bench: SAME coordinate
    structure and entity vocabulary as ``model`` (one arena layout hosts
    them all), freshly seeded coefficient tables (so per-tenant parity
    actually distinguishes the tenants)."""
    import dataclasses as _dc

    from photon_tpu.game.model import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import Coefficients, model_for_task

    rng = np.random.default_rng(seed)
    coords = {}
    for name, coord in model.coordinates.items():
        if isinstance(coord, RandomEffectModel):
            coords[name] = _dc.replace(
                coord,
                table=rng.standard_normal(
                    np.asarray(coord.table).shape
                ).astype(np.float32),
            )
        else:
            dim = int(np.asarray(coord.coefficients.means).shape[0])
            coords[name] = FixedEffectModel(
                model_for_task(model.task_type, Coefficients(
                    rng.standard_normal(dim).astype(np.float32)
                )),
                coord.shard_name,
            )
    return GameModel(coordinates=coords, task_type=model.task_type)


def _bench_fleet_multimodel(table_dtype: str = "f32",
                            n_models: int = 8) -> None:
    """Multi-model arena macro-bench (``--mode fleet --models N`` — the
    ISSUE 18 tentpole's measurement).

    Hosts ``n_models`` tenant models in ONE fleet replica — one shared
    gather-table arena allocation, one compiled bucket ladder — and
    serves seeded mixed-tenant traffic (hash-of-user split arms route
    each request to its tenant).  In-bench acceptance (raises on
    violation):

    - ZERO jax compile events across the whole mixed-tenant serve (model
      identity is a per-request offset vector, never a program key);
    - per-tenant score parity vs a SOLO single-model ``GameScorer`` of
      the same storage dtype ≤ the codec's declared bound on every
      sampled served request;
    - arena bytes ≤ 1.15x the sum of the tenants' solo table bytes (the
      shared allocation carries headroom, not duplication);
    - the seeded split assignment is deterministic (regenerating the
      stream reproduces every arm) and every tenant receives traffic.
    """
    import jax.monitoring

    from photon_tpu.game.lowp import parity_tol_for
    from photon_tpu.serving import (
        AdmissionPolicy,
        ServingFleet,
        TrafficSpec,
        generate_traffic,
        request_spec_for_dataset,
        run_closed_loop_outcomes,
    )
    from photon_tpu.serving.scorer import GameScorer
    from photon_tpu.telemetry import TelemetrySession

    platform, base_model, data = _serving_fixture()
    models = {
        f"m{i}": _tenant_clone(base_model, seed=100 + i)
        for i in range(n_models)
    }
    parity_bound = parity_tol_for(table_dtype)
    spec = request_spec_for_dataset(base_model, data)
    max_batch, clients = 128, 8
    n_requests = 600 if platform != "cpu" else 240
    splits = {mid: 1.0 / n_models for mid in models}
    tspec = TrafficSpec(
        requests=n_requests, mean_rows=8.0, max_rows=max_batch,
        popularity="powerlaw", alpha=1.1, storm_frac=0.0, seed=0,
        splits=splits,
    )
    traffic = generate_traffic(data, base_model, tspec)
    # Split determinism + coverage: the same seed reproduces every arm,
    # and the uniform split actually reaches every tenant.
    arms = [item.arm for item in traffic.items]
    if arms != [item.arm for item in
                generate_traffic(data, base_model, tspec).items]:
        raise AssertionError("seeded split arms are not deterministic")
    arm_counts = {mid: arms.count(mid) for mid in models}
    missing = [mid for mid, c in arm_counts.items() if c == 0]
    if missing:
        raise AssertionError(
            f"tenants {missing} received no traffic from the uniform split"
        )

    # Solo baseline: ONE single-model scorer, swapped per tenant — its
    # scores are the isolation oracle, its table bytes the per-tenant
    # allocation the arena must not exceed in sum.
    solo_session = TelemetrySession("bench-multimodel-solo")
    solo = GameScorer(
        models["m0"], request_spec=spec, max_batch=max_batch,
        telemetry=solo_session, table_dtype=table_dtype,
    ).warmup()
    solo_bytes = 0
    solo_scores: dict = {}
    sample_per_tenant = 15
    for mid, m in models.items():
        if mid != "m0":
            solo.swap_model(m)
        solo_bytes += sum(
            leaf.nbytes
            for leaf in jax.tree_util.tree_leaves(solo._tables)
        )
        picked = [
            item for item in traffic.items if item.arm == mid
        ][:sample_per_tenant]
        solo_scores[mid] = {
            id(item): solo.score_batch(item.request) for item in picked
        }

    session = TelemetrySession("bench-fleet-multimodel")
    fleet = ServingFleet(
        None, models=models, replicas=1, request_spec=spec,
        max_batch=max_batch, max_delay_s=0.001, telemetry=session,
        table_dtype=table_dtype, admission=AdmissionPolicy(safety=2.0),
    ).warmup()
    arena = fleet.replicas[0].scorer.arena
    arena_bytes = arena.arena_bytes()
    compiled_programs = fleet.compilations
    if arena_bytes > 1.15 * solo_bytes:
        raise AssertionError(
            f"arena allocates {arena_bytes} bytes for {n_models} tenants "
            f"> 1.15x the {solo_bytes} bytes their solo tables sum to"
        )

    compile_events: list = []

    def listener(event, **kwargs):
        if "compile" in event:
            compile_events.append(event)

    def factory(tid):
        return lambda item: fleet.score(item.request)

    jax.monitoring.register_event_listener(listener)
    try:
        outcomes, wall = run_closed_loop_outcomes(
            factory, traffic.items, clients=clients
        )
    finally:
        jax.monitoring.unregister_event_listener(listener)
        fleet.close()
    bad = [o for o in outcomes if o.status != "ok"]
    if bad:
        raise AssertionError(
            f"{len(bad)} mixed-tenant requests failed/shed; first: "
            f"{bad[0].reason}"
        )
    if compile_events:
        raise AssertionError(
            f"{len(compile_events)} jax compile events across the "
            f"{n_models}-tenant mixed serve (first: {compile_events[0]}) "
            "— model identity leaked into a program key"
        )
    worst, compared = 0.0, 0
    for out in outcomes:
        want = solo_scores.get(out.item.arm, {}).get(id(out.item))
        if want is None:
            continue
        compared += 1
        worst = max(worst, float(np.max(np.abs(
            np.asarray(out.scores, np.float64)
            - np.asarray(want, np.float64)
        ))))
    if compared < n_models:
        raise AssertionError(
            f"parity sample covered only {compared} requests across "
            f"{n_models} tenants"
        )
    if worst > parity_bound:
        raise AssertionError(
            f"arena/solo per-tenant parity broke ({table_dtype} tables): "
            f"max |delta| {worst:.2e} > {parity_bound:g} over {compared} "
            "sampled requests"
        )
    qps = len(outcomes) / wall if wall > 0 else 0.0
    _emit("game_fleet_multimodel_qps", qps, "req/s", {
        "models": n_models,
        "requests": len(outcomes),
        "clients": clients,
        "table_dtype": table_dtype,
        "arena_bytes": int(arena_bytes),
        "solo_bytes_sum": int(solo_bytes),
        "bytes_ratio": round(arena_bytes / solo_bytes, 4),
        "compiled_programs": compiled_programs,
        "parity_sampled": compared,
        "max_parity_delta": worst,
        "arm_counts": arm_counts,
        "platform": platform,
    })


def _bench_online() -> None:
    """Online-learning refresh micro-bench (``--mode online`` — ISSUE 15).

    Builds a synthetic GAME fixture, fits + serves it on a 2-replica
    fleet, then drives TWO online refresh rounds through the
    :class:`~photon_tpu.online.service.OnlineLearningService` — each
    appending rows for BOTH existing and new entities — measuring the
    append→published refresh latency (``game_online_refresh_secs``, lower
    is better; the second round is the steady-state number: the first pays
    the grown-shape fixed-effect compile).

    Asserts IN-BENCH:
    - refreshed model ≡ a full offline retrain on the merged dataset
      (rebuilt-from-scratch layouts, same warm start/iterations) to ≤1e-4
      on scores — the in-place-growth data path changes NOTHING;
    - zero full random-effect layout rebuilds
      (``estimator.device_data_rebuilds{kind=random}`` == 0) and >0 rows
      grown in place;
    - zero serving-side compile events across both publishes
      (``fleet.compilations`` unchanged after warmup).
    """
    import numpy as np

    from photon_tpu.data.synthetic import make_game_data
    from photon_tpu.game.data import DenseShard, GameDataset
    from photon_tpu.game.estimator import GameEstimator
    from photon_tpu.game.model import GameModel
    from photon_tpu.online import (
        OnlineLearningService,
        QueueFeed,
        RefreshPolicy,
    )
    from photon_tpu.serving.fleet import ServingFleet
    from photon_tpu.serving.scorer import request_spec_for_dataset
    from photon_tpu.telemetry import TelemetrySession

    platform, _sizes, _data, config = _game_bench_fixture(
        n_random_coords=2, descent_iterations=3
    )
    task = "linear_regression"

    def cut(n_ent, seed, keep=None):
        raw = make_game_data(n_ent, 6, 32, 8, seed=seed, n_random_coords=2)
        sel = (
            slice(None) if keep is None
            else keep(raw["entity_ids"]["re0"])
        )
        return GameDataset.create(
            raw["label"][sel],
            {
                "global": DenseShard(raw["x_fixed"][sel]),
                "re0": DenseShard(raw["x_random"]["re0"][sel]),
                "re1": DenseShard(raw["x_random"]["re1"][sel]),
            },
            id_columns={
                "re0": raw["entity_ids"]["re0"][sel],
                "re1": raw["entity_ids"]["re1"][sel],
            },
        )

    n_entities = 2000
    base = cut(n_entities, 0)
    session = TelemetrySession("bench-online")
    estimator = GameEstimator(task, base, telemetry=session)
    model0 = estimator.fit([config])[0].model
    fleet = ServingFleet(
        model0, replicas=2,
        request_spec=request_spec_for_dataset(model0, base),
        telemetry=session, table_capacity_factor=2,
    ).warmup()
    compiles0 = fleet.compilations
    feed = QueueFeed()
    service = OnlineLearningService(
        estimator, config, feed, model=model0, fleet=fleet,
        policy=RefreshPolicy(refresh_iterations=3), telemetry=session,
    )

    latencies = []
    grow = int(n_entities * 1.05)
    try:
        # Round 1: parity round — its merged dataset and refreshed model
        # feed the full-retrain oracle below.
        feed.append(cut(
            grow, 1,
            keep=lambda ids: (ids < n_entities // 10)
            | (ids >= n_entities),
        ))
        result1 = service.refresh_once()
        assert result1 is not None and result1.published
        latencies.append(result1.latency_s)
        merged1 = estimator.training_data
        # Round 2: steady-state latency (round 1 pays the grown-shape
        # fixed-effect compile; the bins themselves never recompile).
        feed.append(cut(
            grow, 2,
            keep=lambda ids: (ids < n_entities // 10)
            | (ids >= n_entities),
        ))
        result2 = service.refresh_once()
        assert result2 is not None and result2.published
        latencies.append(result2.latency_s)
        assert fleet.compilations == compiles0, (
            f"serving-side compiles during online publish: "
            f"{fleet.compilations - compiles0}"
        )
    finally:
        fleet.close()

    # Full-retrain oracle for round 1: rebuilt-from-scratch layouts over
    # the SAME merged dataset, warm-started from the same grown serving
    # model, same iteration budget, no locks — the in-place-growth data
    # path must change nothing.
    fresh = GameEstimator(task, merged1)
    warm_coords = {}
    for name, m in model0.coordinates.items():
        cc = config.coordinates[name]
        if hasattr(m, "with_entities"):
            warm_coords[name] = m.with_entities(
                fresh.device_layout(cc).dataset.keys
            )
        else:
            warm_coords[name] = m
    full_model = fresh.fit(
        [config], initial_model=GameModel(warm_coords, task)
    )[0].model
    parity = float(np.abs(
        result1.model.score(merged1) - full_model.score(merged1)
    ).max())
    assert parity <= 1e-4, (
        f"online refresh diverged from the full offline retrain: {parity}"
    )

    def counter_total(name, **labels):
        return sum(
            m["value"] for m in session.registry.snapshot()["counters"]
            if m["name"] == name
            and all((m.get("labels") or {}).get(k) == v
                    for k, v in labels.items())
        )

    random_rebuilds = counter_total(
        "estimator.device_data_rebuilds", kind="random"
    )
    rows_in_place = counter_total("onboard.rows_in_place")
    assert random_rebuilds == 0, random_rebuilds
    assert rows_in_place > 0

    _emit("game_online_refresh_secs", latencies[-1], "s", {
        "rows_base": base.num_examples,
        "rows_ingested": int(counter_total("online.rows_ingested")),
        "entities": n_entities,
        "rounds": 2,
        "first_round_secs": round(latencies[0], 4),
        "steady_round_secs": round(latencies[-1], 4),
        "refresh_iterations": 3,
        "parity_vs_full_retrain": parity,
        "rows_grown_in_place": int(rows_in_place),
        "rows_migrated": int(counter_total("onboard.rows_migrated")),
        "entities_new": int(counter_total("onboard.entities_new")),
        "random_layout_rebuilds": int(random_rebuilds),
        "serving_compiles_during_publish": fleet.compilations - compiles0,
        "platform": platform,
    })


def _bench_recovery() -> None:
    """Checkpoint write/restore overhead micro-bench (``--mode recovery``).

    Fits the shared synthetic GAME fixture four ways on one estimator:
    plain (no checkpointing), with SYNCHRONOUS per-outer-iteration descent
    checkpoints (``--checkpoint-async off`` — the inline serialize + fsync
    + rename the loop used to pay), with the ASYNC publisher (staging on
    the loop, publish behind the next iteration's compute), and resumed
    from the completed checkpoint (pure load + rebuild, no solves).  Emits
    ``game_checkpoint_secs`` (mean loop-side write seconds per iteration,
    sync mode — the insurance premium baseline) and
    ``game_checkpoint_overhead_pct`` — the async fit's measured
    per-iteration checkpoint premium as a percentage of the sync fit's
    (the ISSUE 5 acceptance number: <= 20 means the publisher hides at
    least 80% of the premium).
    """
    import shutil
    import tempfile

    from photon_tpu.game.estimator import GameEstimator
    from photon_tpu.telemetry import TelemetrySession

    iters = 3
    platform, sizes, data, config = _game_bench_fixture(
        n_random_coords=2, descent_iterations=iters
    )
    n_entities, rows_mean = sizes
    tmp = tempfile.mkdtemp(prefix="photon-bench-recovery-")
    try:
        session = TelemetrySession("bench-recovery")
        estimator = GameEstimator(
            "logistic_regression", data, telemetry=session
        )
        estimator.fit([config])  # warm-up: compile + device-data upload
        t0 = time.perf_counter()
        estimator.fit([config])
        plain = time.perf_counter() - t0

        ckpt_sync = os.path.join(tmp, "ckpt-sync")
        t0 = time.perf_counter()
        estimator.fit([config], checkpoint_dir=ckpt_sync,
                      checkpoint_async="off")
        with_sync = time.perf_counter() - t0
        # Snapshot the mean NOW: the histogram is live on the shared
        # session, and the async fit below observes its own near-zero
        # loop-side write times into it (same reason saves is int()-ed).
        sync_write_mean = float(
            session.histogram("checkpoint.write_seconds").mean or 0.0
        )
        sync_writes = int(session.counter("checkpoint.saves").value)

        ckpt_async = os.path.join(tmp, "ckpt-async")
        t0 = time.perf_counter()
        estimator.fit([config], checkpoint_dir=ckpt_async,
                      checkpoint_async="on")
        with_async = time.perf_counter() - t0

        t0 = time.perf_counter()
        estimator.fit([config], checkpoint_dir=ckpt_sync, resume="auto")
        restore = time.perf_counter() - t0

        # Elastic restore: the SAME checkpoint restored in a subprocess
        # under a forced 2-device CPU mesh — a different device count than
        # wrote it (checkpoints are mesh-shape portable; the restored
        # tables re-pad/re-shard onto the new mesh).  Subprocess because a
        # device count cannot change after jax initializes in-process.
        resharded_restore = None
        worker_err = None
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        try:
            proc = subprocess.run(
                [sys.executable, "-c",
                 "import bench; bench._resharded_restore_worker"
                 f"({ckpt_sync!r}, {n_entities}, {rows_mean}, {iters})"],
                capture_output=True, text=True, timeout=900, env=env,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            if proc.returncode == 0 and proc.stdout.strip():
                payload = json.loads(proc.stdout.strip().splitlines()[-1])
                resharded_restore = float(payload["restore_secs"])
            else:
                worker_err = (proc.stderr or "worker failed").strip()[-500:]
        except Exception as ex:  # noqa: BLE001 — sub-metric isolation
            worker_err = f"{type(ex).__name__}: {ex}"[:500]

        sync_premium = max(with_sync - plain, 0.0)
        async_premium = max(with_async - plain, 0.0)
        overhead_pct = (
            100.0 * async_premium / sync_premium if sync_premium > 0 else 0.0
        )
        detail = {
            "rows": data.num_examples,
            "entities": n_entities,
            "coordinates": 3,
            "descent_iterations": iters,
            "plain_fit_seconds": round(plain, 4),
            "sync_fit_seconds": round(with_sync, 4),
            "async_fit_seconds": round(with_async, 4),
            "sync_premium_seconds": round(sync_premium, 4),
            "async_premium_seconds": round(async_premium, 4),
            "restore_seconds": round(restore, 4),
            "checkpoint_writes": sync_writes,
            "publish_lag_mean_s": round(
                session.histogram("checkpoint.publish_lag_s").mean or 0.0, 4
            ),
            "blocked_mean_s": round(
                session.histogram("checkpoint.blocked_s").mean or 0.0, 4
            ),
            "platform": platform,
        }
        _emit("game_checkpoint_secs", sync_write_mean, "s/iter", detail)
        _emit("game_checkpoint_overhead_pct", overhead_pct, "%", detail)
        if resharded_restore is not None:
            _emit("game_resharded_restore_secs", resharded_restore, "s", {
                **detail,
                "restore_devices": 2,
                "restore_platform": "cpu (forced 2-device)",
            })
        else:
            _emit("game_resharded_restore_error", 0.0, "error", {
                "error": worker_err or "unknown",
            })
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _resharded_restore_worker(ckpt_dir: str, n_entities: int,
                              rows_mean: int, iters: int) -> None:
    """Subprocess entry of the ``--mode recovery`` resharded-restore
    sub-metric: rebuild the recovery fixture, construct a mesh over this
    process's (forced, different) device count, and restore the completed
    checkpoint chain onto it — no solves, pure load + re-pad + re-shard +
    rebuild.  Prints one JSON line ``{"restore_secs": ...}``."""
    import jax

    from photon_tpu.game.estimator import GameEstimator
    from photon_tpu.parallel.mesh import create_mesh

    platform, _, data, config = _game_bench_fixture(
        n_random_coords=2, descent_iterations=iters,
        sizes=(n_entities, rows_mean),
    )
    assert platform == "cpu", "resharded restore is a forced-CPU check"
    mesh = create_mesh()
    estimator = GameEstimator("logistic_regression", data, mesh=mesh)
    t0 = time.perf_counter()
    estimator.fit([config], checkpoint_dir=ckpt_dir, resume="auto")
    secs = time.perf_counter() - t0
    print(json.dumps({
        "restore_secs": round(secs, 4), "devices": len(jax.devices()),
    }))


def _generate_stream_files(
    out_dir: str, total_rows: int, n_files: int, k: int, d: int, seed: int = 0
) -> list:
    """Generate LIBSVM part files for the streaming-scale bench (vectorized
    formatting; cached by a manifest so repeat runs skip the write).

    Feature ids are drawn one-per-stride (id_j in [j*d/k, (j+1)*d/k)), so
    rows are ascending-unique by construction — vectorizable, and shaped
    like a hashed/bucketed production feature space."""
    import json as _json

    manifest = os.path.join(out_dir, "manifest.json")
    spec = {"total_rows": total_rows, "n_files": n_files, "k": k, "d": d,
            "seed": seed}
    if os.path.exists(manifest):
        try:
            with open(manifest) as f:
                if _json.load(f) == spec:
                    return sorted(
                        os.path.join(out_dir, f) for f in os.listdir(out_dir)
                        if f.startswith("part-")
                    )
        except Exception:  # noqa: BLE001 — stale manifest: regenerate
            pass
    os.makedirs(out_dir, exist_ok=True)
    # Invalidate BEFORE mutating parts: a crash mid-generation must not
    # leave an old manifest validating a half-written part set.
    if os.path.exists(manifest):
        os.unlink(manifest)
    for f in os.listdir(out_dir):
        if f.startswith("part-"):
            os.unlink(os.path.join(out_dir, f))
    rows_per_file = -(-total_rows // n_files)
    stride = d // k
    rng = np.random.default_rng(seed)
    w_true = (rng.standard_normal(k) * 0.5).astype(np.float32)  # one per stride
    files = []
    for fi in range(n_files):
        n = min(rows_per_file, total_rows - fi * rows_per_file)
        if n <= 0:
            break
        ids = (
            np.arange(k, dtype=np.int64)[None, :] * stride
            + rng.integers(0, stride, size=(n, k))
            + 1  # libsvm ids are 1-based
        )
        vals = rng.standard_normal((n, k)).astype(np.float32)
        margin = vals @ w_true
        label = np.where(rng.random(n) < 1.0 / (1.0 + np.exp(-margin)), 1, -1)
        path = os.path.join(out_dir, f"part-{fi:05d}.libsvm")
        files.append(path)
        acc = np.char.mod("%d", label.astype(np.int64))
        for j in range(k):
            acc = np.char.add(acc, " ")
            acc = np.char.add(acc, np.char.add(
                np.char.mod("%d:", ids[:, j]), np.char.mod("%.4f", vals[:, j])
            ))
        with open(path, "w") as f:
            f.write("\n".join(acc.tolist()))
            f.write("\n")
    with open(manifest, "w") as f:
        _json.dump(spec, f)
    return files


def _stream_kernel_report() -> tuple:
    """(kernel, why) the streamed pass runs with — the VERDICT r5 item-3
    ask: a reader of the stream-scale line can state which kernel ran
    and why."""
    from photon_tpu.data.stream_layouts import stream_kernel, stream_kernel_why

    k = stream_kernel()
    return k, stream_kernel_why(k)


def _stream_scale() -> None:
    """Streaming-ingestion scale proof (VERDICT r3 item 3): stream
    PHOTON_STREAM_SCALE_ROWS (default 10M) generated LIBSVM rows
    file-at-a-time through the production streamed-objective path
    (LibsvmFileSource -> stream_chunks prefetch -> jitted per-chunk
    value+grad), report sustained rows/s, and assert peak RSS stays
    bounded (< PHOTON_STREAM_SCALE_RSS_GB, default 4) — host memory must
    not scale with dataset size.  Invoke: ``python bench.py --stream-scale``.
    """
    import resource

    import jax
    import jax.numpy as jnp

    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.data.streaming import LibsvmFileSource, StreamingObjective

    rss_cap_gb = float(os.environ.get("PHOTON_STREAM_SCALE_RSS_GB", "4"))
    stream_kernel_name, stream_kernel_why = _stream_kernel_report()
    t_gen = time.perf_counter()
    files, _, _, _, k, d = _stream_scale_spec()
    gen_s = time.perf_counter() - t_gen

    t_scan = time.perf_counter()
    source = LibsvmFileSource(files, intercept=True, feature_dim=d)
    scan_s = time.perf_counter() - t_scan
    objective = StreamingObjective(
        GlmObjective.create("logistic", RegularizationContext("l2", 1.0)),
        source.chunk_iter_factory,
    )
    w = jnp.zeros(source.dim, jnp.float32)
    # Pass 1 warms the per-chunk compilation; passes 2..P are the sustained
    # measurement (every L-BFGS iteration in production is one such pass).
    v, g = objective.value_and_grad(w)
    np.asarray(g)
    passes = 2
    t0 = time.perf_counter()
    for _ in range(passes):
        w2 = w - 1e-3 * g  # new point each pass: no result can be reused
        v, g = objective.value_and_grad(w2)
    np.asarray(g)
    wall = time.perf_counter() - t0
    rows_per_sec = passes * source.num_examples / wall
    # ru_maxrss is kilobytes on Linux but BYTES on macOS.
    rss_unit = 1e9 if sys.platform == "darwin" else 1e6
    peak_rss_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / rss_unit
    _emit("config5_stream_rows_per_sec", rows_per_sec, "rows/s", {
        "rows": source.num_examples,
        "files": len(files),
        "nnz_per_row": k,
        "dim": source.dim,
        "passes_timed": passes,
        "seconds_per_pass": round(wall / passes, 2),
        "metadata_scan_s": round(scan_s, 2),
        "generate_s": round(gen_s, 2),
        "final_value": float(v),
        # What actually ran (first chunk's measured selection) vs. what
        # the attach intended — a reader must be able to state the
        # operative kernel from this line alone (VERDICT r5 item 3).
        "kernel": objective.last_kernel or "autodiff",
        "kernel_attach": stream_kernel_name,
        "kernel_why": stream_kernel_why,
        "peak_rss_gb": round(peak_rss_gb, 3),
        "rss_cap_gb": rss_cap_gb,
        "rss_bounded": peak_rss_gb < rss_cap_gb,
        "platform": jax.devices()[0].platform,
    })
    if peak_rss_gb >= rss_cap_gb:
        raise RuntimeError(
            f"streaming pass peak RSS {peak_rss_gb:.2f} GB exceeds the "
            f"{rss_cap_gb:.0f} GB bound — host memory is scaling with data"
        )


# Worker for --stream-scale-mp: one streamed value+grad pass, CPU-pinned.
# argv: repo coordinator nproc pid data_dir out_path d.  With nproc=1 it is
# the single-process reference (no distributed init, no all_reduce) on the
# IDENTICAL platform and code path as the 2-process run — cross-backend
# float comparisons are structurally impossible.
_MP_STREAM_WORKER = r"""
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
sys.path.insert(0, sys.argv[1])
coordinator, nproc, pid, data_dir, out_path, d = (
    sys.argv[2], int(sys.argv[3]), int(sys.argv[4]), sys.argv[5],
    sys.argv[6], int(sys.argv[7])
)
import jax
jax.config.update("jax_platforms", "cpu")
if nproc > 1:
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=nproc, process_id=pid)
import jax.numpy as jnp
import numpy as np

from photon_tpu.core.objective import GlmObjective, RegularizationContext
from photon_tpu.data.streaming import (
    LibsvmFileSource, StreamingObjective, shard_files_for_process,
)

files = sorted(os.path.join(data_dir, f) for f in os.listdir(data_dir)
               if f.startswith("part-"))
source = LibsvmFileSource(files, intercept=True, feature_dim=d)
all_reduce = None
local = source
if nproc > 1:
    from jax.experimental import multihost_utils

    local = source.with_files(shard_files_for_process(files))

    def all_reduce(x):
        return multihost_utils.process_allgather(x).sum(axis=0)

obj = StreamingObjective(
    GlmObjective.create("logistic", RegularizationContext("l2", 1.0)),
    local.chunk_iter_factory, all_reduce=all_reduce,
)
w = jnp.zeros(source.dim, jnp.float32)
v, g = obj.value_and_grad(w)          # warm (compile)
np.asarray(g)
t0 = time.perf_counter()
v, g = obj.value_and_grad(w)
g_host = np.asarray(g)
wall = time.perf_counter() - t0
if pid == 0:
    with open(out_path, "w") as f:
        json.dump({
            "value": float(v),
            "grad_l1": float(np.abs(g_host).sum()),
            "pass_seconds": wall,
            "rows": source.num_examples,
        }, f)
"""


def _stream_scale_spec() -> tuple:
    """Shared scenario of the streaming-scale proofs (--stream-scale and
    --stream-scale-mp): env knobs, shape constants, generated files."""
    total_rows = int(os.environ.get("PHOTON_STREAM_SCALE_ROWS", str(10_000_000)))
    n_files, k, d = 64, 16, 1 << 17
    data_dir = os.environ.get(
        "PHOTON_STREAM_SCALE_DIR",
        os.path.join(os.environ.get("TMPDIR", "/tmp"), "photon_stream_scale"),
    )
    files = _generate_stream_files(data_dir, total_rows, n_files, k, d)
    return files, data_dir, total_rows, n_files, k, d


def _run_stream_workers(nproc: int, data_dir: str, d: int, log_dir: str) -> dict:
    """Spawn ``nproc`` CPU-pinned streamed-pass workers, return rank 0's
    result JSON.  Worker output goes to files (PIPEs could deadlock the
    collective if one worker fills its buffer while the parent drains the
    other); on any failure or timeout every worker is killed, never
    orphaned mid-collective."""
    import socket
    import subprocess

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    out_path = os.path.join(log_dir, f"mp_result_{nproc}.json")
    repo = os.path.dirname(os.path.abspath(__file__))
    procs, logs = [], []
    try:
        for pid in range(nproc):
            log = open(os.path.join(log_dir, f"worker_{nproc}_{pid}.log"), "w")
            logs.append(log)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _MP_STREAM_WORKER, repo, coordinator,
                 str(nproc), str(pid), data_dir, out_path, str(d)],
                stdout=log, stderr=log,
            ))
        for p in procs:
            p.wait(timeout=1200)
        for pid, p in enumerate(procs):
            if p.returncode != 0:
                tail = open(
                    os.path.join(log_dir, f"worker_{nproc}_{pid}.log")
                ).read()[-2000:]
                # Surface the platform-limitation signature up front: the
                # emitted bench_error detail is truncated, and consumers
                # (tests, the BENCH parser) must still be able to tell "this
                # jaxlib cannot do multi-process CPU" from a real failure.
                for marker in MP_UNSUPPORTED_MARKERS:
                    if marker in tail:
                        raise RuntimeError(
                            f"{marker} on this jaxlib's CPU backend"
                        )
                raise RuntimeError(
                    f"stream worker {pid}/{nproc} failed:\n{tail}"
                )
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for log in logs:
            log.close()
    with open(out_path) as f:
        return json.load(f)


def _stream_scale_mp() -> None:
    """Two-process streamed objective at the full streaming-proof scale:
    each process streams its file shard, per-shard gradients allgather-sum
    across processes (the reference's treeAggregate-across-hosts analog),
    and the distributed (value, |grad|_1) must match a single-process pass
    over all files (rel <= 1e-5; float32 accumulation order differs between
    the 64-term sequential sum and the two 32-term shard sums).  Completes
    VERDICT r3 item 3's "on 1-2 processes" at 10M rows; invoke:
    ``python bench.py --stream-scale-mp``.  Both runs are CPU-pinned
    subprocesses by design — this proves the multi-process ingestion +
    collective path on identical hardware, not chip compute (a chip
    belongs to one process).
    """
    import tempfile

    files, data_dir, _, _, _, d = _stream_scale_spec()
    log_dir = tempfile.mkdtemp(prefix="photon_stream_mp_")
    sp = _run_stream_workers(1, data_dir, d, log_dir)
    mp = _run_stream_workers(2, data_dir, d, log_dir)
    value_match = abs(mp["value"] - sp["value"]) <= 1e-5 * max(
        abs(sp["value"]), 1.0
    )
    grad_match = abs(mp["grad_l1"] - sp["grad_l1"]) <= 1e-5 * max(
        sp["grad_l1"], 1.0
    )
    _emit("config5_stream_mp_rows_per_sec",
          mp["rows"] / mp["pass_seconds"], "rows/s", {
              "processes": 2,
              "rows": mp["rows"],
              "files": len(files),
              "pass_seconds": round(mp["pass_seconds"], 2),
              "value_mp": mp["value"],
              "value_single": sp["value"],
              "value_match": value_match,
              "grad_l1_match": grad_match,
              "platform": "cpu (by design: multi-process ingestion proof)",
          })
    if not (value_match and grad_match):
        raise RuntimeError(
            f"2-process streamed objective diverged from single-process: "
            f"value {mp['value']} vs {sp['value']}, "
            f"grad_l1 {mp['grad_l1']} vs {sp['grad_l1']}"
        )


def main() -> None:
    from photon_tpu.drivers.common import select_backend

    # The device policy: a TPU, or the host when JAX_PLATFORMS=cpu asks
    # for it; anything else raises.  Also switches on the one persistent
    # compile cache (JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache).
    _DEVICE.update(select_backend("tpu"))
    # Kernel attribution (VERDICT r3 weak 2): every emitted line names the
    # kernel its numbers belong to.  An explicit PHOTON_SPARSE_GRAD is the
    # operator's pin; otherwise the headline stays in auto mode but raises
    # the selection probe's size cap to the FULL headline entry count, so
    # the one-time eager measurement (ops/sparse_grad_select) compares
    # fm/autodiff/pallas at the true shape on the live backend and the
    # round-end number automatically belongs to the day's fastest kernel.
    # The resolved choice is recorded in the emitted JSON ("kernel").
    if os.environ.get("PHOTON_SPARSE_GRAD", "auto") == "auto":
        os.environ.setdefault(
            "PHOTON_SPARSE_PROBE_MAX_ENTRIES", str(1 << 25)
        )
    if len(sys.argv) > 1 and sys.argv[1] == "--stream-scale":
        _stream_scale()
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--stream-scale-mp":
        _stream_scale_mp()
        return
    if len(sys.argv) > 2 and sys.argv[1] == "--config":
        _bench_config(int(sys.argv[2]))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--mode":
        mode = sys.argv[2] if len(sys.argv) > 2 else ""
        modes = {
            "descent": _bench_descent,
            "validation": _bench_validation,
            "recovery": _bench_recovery,
            "entities": _bench_entities,
            "serving": _bench_serving,
            "fleet": _bench_fleet,
            "ooc": _bench_ooc,
            "online": _bench_online,
        }
        def flag_value(name):
            rest = sys.argv[3:]
            if name in rest and rest.index(name) + 1 < len(rest):
                return rest[rest.index(name) + 1]
            return None

        if mode == "ooc" and "--spill" in sys.argv[3:]:
            # ``--mode ooc --spill``: add the disk-tier leg (ISSUE 11) —
            # forced-eviction spilled fit, in-bench parity assertions,
            # game_ooc_disk_rows_per_sec, plus the ISSUE 17 bf16/int8
            # codec legs (``--tile-dtype`` restricts them to one).
            modes["ooc"] = lambda: _bench_ooc(
                spill=True, tile_dtype=flag_value("--tile-dtype")
            )
        if mode == "serving" and flag_value("--table-dtype"):
            # ``--mode serving --table-dtype bf16``: only that lossy leg
            # (f32 always runs as the denominator).
            modes["serving"] = lambda: _bench_serving(
                dtypes=(flag_value("--table-dtype"),)
            )
        if mode == "fleet" and flag_value("--table-dtype"):
            # ``--mode fleet --table-dtype bf16``: the whole fleet cycle
            # (capacity/saturation/chaos) on reduced-precision tables,
            # parity-gated at the codec's declared bound.
            modes["fleet"] = lambda: _bench_fleet(
                table_dtype=flag_value("--table-dtype")
            )
        if mode == "fleet" and "--chaos-matrix" in sys.argv[3:]:
            # ``--mode fleet --chaos-matrix``: the ISSUE 19 partition-
            # tolerance sweep — five deterministic network-fault cells
            # (lease-tolerated partition, lease expiry, zombie fencing,
            # duplicate frames, slow replica) plus the capacity-boundary
            # background-rebuild leg, each with in-bench acceptance.
            modes["fleet"] = lambda: _bench_fleet_chaos_matrix(
                table_dtype=flag_value("--table-dtype") or "f32"
            )
        if mode == "fleet" and flag_value("--models"):
            # ``--mode fleet --models N``: the ISSUE 18 multi-model arena
            # leg alone — N tenants, one arena, one ladder; zero-recompile
            # + per-tenant-parity + arena-bytes bars in-bench.
            modes["fleet"] = lambda: _bench_fleet_multimodel(
                table_dtype=flag_value("--table-dtype") or "f32",
                n_models=int(flag_value("--models")),
            )
        if mode not in modes:
            # An unknown mode must not silently fall through to the full
            # (minutes-long) default run; the raise reaches the top-level
            # handler and emits a bench_error JSON line.
            raise ValueError(
                f"unknown bench mode {mode!r}; valid: {', '.join(modes)}"
            )
        modes[mode]()
        return
    if len(sys.argv) <= 1 or sys.argv[1] != "--headline-only":
        # Default run: all five SURVEY.md §6 configs first (one JSON line
        # each; a failing config emits its own error line and never blocks
        # the others), then the headline metric LAST — drivers that parse a
        # single line take the final one.  A soft wall-clock budget guards
        # the headline: on a cold accelerator each config pays real compile
        # time, and an external runner's timeout must never expire before
        # the headline (the one number tracked round-over-round) prints.
        budget_s = float(os.environ.get("PHOTON_BENCH_BUDGET_S", "480"))
        t_start = time.perf_counter()
        for num in (1, 2, 3, 4, 5):
            elapsed = time.perf_counter() - t_start
            if elapsed > budget_s:
                _emit(f"config{num}_skipped", 0.0, "skipped", {
                    "reason": f"bench budget exhausted after {elapsed:.0f}s "
                              f"(PHOTON_BENCH_BUDGET_S={budget_s:.0f}); "
                              "run `bench.py --config "
                              f"{num}` individually",
                })
                continue
            try:
                _bench_config(num)
            except Exception as ex:  # noqa: BLE001 — config isolation
                _emit(f"config{num}_error", 0.0, "error", {
                    "error": f"{type(ex).__name__}: {ex}"[:500],
                })
        # The GAME residual-engine, validation-pipeline, and checkpoint-
        # recovery micro-benches ride the full run (their JSON lines land
        # next to the headline), same budget guard + isolation as the
        # numbered configs.
        # The entity-scaling bench rides the default run CAPPED at 100k
        # entities (the full 10k -> 1M curve is the standalone
        # `--mode entities` invocation; the 1M point alone costs minutes).
        import functools as _functools

        for label, fn in (("game_descent", _bench_descent),
                          ("game_validation", _bench_validation),
                          ("game_recovery", _bench_recovery),
                          ("game_serving", _bench_serving),
                          # Fleet serving (ISSUE 12): replicated scorers
                          # over the TCP ingest, traffic replay, admission
                          # control — the serving number going forward.
                          ("game_fleet", _bench_fleet),
                          # Multi-model arena (ISSUE 18): N tenants in one
                          # gather-table allocation and one compiled
                          # bucket ladder, mixed split-arm traffic.
                          ("game_fleet_multimodel",
                           _bench_fleet_multimodel),
                          # Online learning (ISSUE 15): append->serving
                          # refresh latency + refreshed-vs-full-retrain
                          # parity on the CPU fixture.
                          ("game_online", _bench_online),
                          # spill=True: game_ooc_disk_rows_per_sec + the
                          # per-tier stall fractions ride the default run
                          # (ISSUE 11).
                          ("game_ooc",
                           _functools.partial(_bench_ooc, spill=True)),
                          ("game_entities",
                           _functools.partial(_bench_entities, 100_000))):
            elapsed = time.perf_counter() - t_start
            if elapsed > budget_s:
                _emit(f"{label}_skipped", 0.0, "skipped", {
                    "reason": f"bench budget exhausted after {elapsed:.0f}s; "
                              f"run `bench.py --mode "
                              f"{label.split('_', 1)[1]}` individually",
                })
                continue
            try:
                fn()
            except Exception as ex:  # noqa: BLE001 — config isolation
                _emit(f"{label}_error", 0.0, "error", {
                    "error": f"{type(ex).__name__}: {ex}"[:500],
                })
    import jax
    import jax.numpy as jnp

    from photon_tpu.core.objective import GlmObjective, RegularizationContext

    platform = jax.devices()[0].platform
    # Problem size: ~32M nonzeros on an accelerator keeps the gather/scatter
    # hot loop HBM-bound like production GLM batches; small on CPU so the
    # driver's sanity runs stay fast.
    if platform == "cpu":
        n, k, d = 1 << 16, 16, 1 << 14
    else:
        n, k, d = 1 << 20, 32, 1 << 18

    batch = _build_batch(n, k, d)
    bench_dtype = os.environ.get("PHOTON_BENCH_DTYPE", "float32")
    try:
        jnp.dtype(bench_dtype)
    except TypeError:
        # An invalid dtype must not kill the run before the headline prints
        # (the budget guard's whole purpose); fall back and say so.
        print(
            f"WARNING: invalid PHOTON_BENCH_DTYPE={bench_dtype!r}; "
            "benchmarking float32",
            file=sys.stderr,
        )
        bench_dtype = "float32"
    if bench_dtype != "float32":
        from photon_tpu.data.batch import batch_astype

        batch = batch_astype(batch, bench_dtype)
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 1.0))
    w = jnp.zeros(d, jnp.float32)

    # Each "grad step" is one full value+gradient over all n rows followed by
    # a small coefficient update — chaining steps through w gives a real
    # optimizer-trajectory dependency so no execution can be elided.
    @jax.jit
    def step(w, batch):
        v, g = obj.value_and_grad(w, batch)
        return w - 1e-3 * g, v

    reps = 20 if platform != "cpu" else 5
    # PHOTON_BENCH_FUSED=1 runs all reps inside ONE dispatch (lax.scan over
    # the same chained step) — the shape real fits take (optimizers are
    # fully jitted while_loops, one dispatch per fit), and the honest view
    # once per-step time approaches the per-dispatch overhead.  Default
    # stays per-step dispatch.
    fused = os.environ.get("PHOTON_BENCH_FUSED", "0") == "1"
    if fused:
        from jax import lax

        @jax.jit
        def run_all(w, batch):
            def body(w, _):
                w2, v = step(w, batch)
                return w2, v
            return lax.scan(body, w, None, length=reps)

    # Warm up: compile + one execution.  The timed window ends in
    # np.asarray (device_get): a host copy of the result is a sync.
    if fused:
        w0, vs = run_all(w, batch)
        np.asarray(w0)
        t0 = time.perf_counter()
        w, vs = run_all(w, batch)
        np.asarray(w)
    else:
        w, v = step(w, batch)
        np.asarray(w)
        t0 = time.perf_counter()
        for _ in range(reps):
            w, v = step(w, batch)
        np.asarray(w)
    wall = time.perf_counter() - t0
    steps_per_sec = reps / wall

    # Effective bandwidth: per step the sparse hot loop must touch ids+vals
    # once in each direction (fwd gather products, bwd segment reduction).
    nnz = n * k
    val_bytes = jnp.dtype(bench_dtype).itemsize
    eff_gb_s = steps_per_sec * nnz * 2 * (4 + val_bytes) / 1e9  # 2 passes x (id + val)
    # Roofline share is a device metric: looked up by device_kind (an
    # unknown TPU raises), never reported from a CPU run.
    hbm_gb_s = (
        _hbm_peak_gb_s(jax.devices()[0].device_kind)
        if platform == "tpu" else None
    )
    # Attribute the number to the kernel that actually ran: in auto mode
    # select_kernel's cache already holds the measured winner for this
    # shape (the timed steps above used it), so this lookup is a cache hit.
    kernel = os.environ.get("PHOTON_SPARSE_GRAD", "auto")
    if kernel == "auto":
        from photon_tpu.ops.sparse_grad_select import select_kernel

        kernel = "auto:" + select_kernel(batch, d)
    _emit("glm_grad_steps_per_sec", steps_per_sec, "steps/s", {
        "rows": n,
        "nnz_per_row": k,
        "dim": d,
        "dtype": bench_dtype,
        "kernel": kernel,
        "dispatch": "fused" if fused else "per-step",
        "skew": os.environ.get("PHOTON_BENCH_SKEW", "uniform"),
        "platform": platform,
        "rows_per_sec": round(steps_per_sec * n, 1),
        "effective_gb_per_sec": round(eff_gb_s, 2),
        "pct_hbm_roofline": None if hbm_gb_s is None
        else round(100.0 * eff_gb_s / hbm_gb_s, 2),
    })


if __name__ == "__main__":
    try:
        main()
    except Exception as ex:
        # One machine-readable line saying which mode died, then the
        # traceback and a non-zero exit: a failed bench is a failure.
        if len(sys.argv) > 2 and sys.argv[1] == "--config":
            metric = f"config{sys.argv[2]}_error"
        else:
            metric = "bench_error"
        _emit(metric, 0.0, "error", {
            "error": f"{type(ex).__name__}: {ex}"[:500],
        })
        raise
