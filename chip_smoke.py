#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One command, one process, the normal entry points only:

1. GAME train at full width through ``photon_tpu.drivers.train_game``
   (the repo's accelerator-size synthetic spec: 20k users x 20k items,
   ~2M rows, dense fixed effect d=128, two random effects d=16);
2. serve the model that run saved through ``photon_tpu.drivers.serve_game``
   and check every served score against the host oracle;
3. a sparse fixed-effect fit at the canonical width (d=262,144, 32 nnz/row)
   through ``photon_tpu.drivers.train`` — the only path Pallas runs on —
   with the per-kernel compile/parity table.

Sizes are never cut on a chip.  The device count comes from
``jax.devices()``: on a four-chip host the same legs run over a 4-device
data mesh and four one-chip serving replicas, and the smoke asserts the
arrays really sit on four devices.

It FAILS (non-zero, no result line) when JAX finds no TPU, and on any
failed leg.  ``--cpu-rehearsal`` is the one way to run it on the host: an
explicit flag that shrinks every size and labels every line ``cpu`` — a
debugging aid that prints no result line either.

Last line of stdout on success:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import sys
import tempfile
import time

REHEARSAL = "--cpu-rehearsal" in sys.argv[1:]
if REHEARSAL:
    # Explicit CPU is a request the device policy honours, not a fallback.
    os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

# -- sizes ------------------------------------------------------------------

# entities, rows/entity, fixed dim, random dim, random coordinates, seed
GAME_DIMS = (20000, 100, 128, 16, 2, 0)
# Validation AUC of this spec on the host (this PR, jax 0.9.0) — the
# arguments are game_train_args(); best_metrics.AUC of the summary:
#   JAX_PLATFORMS=cpu python -m photon_tpu.drivers.train_game --backend cpu \
#     --input synthetic-game:20000:100:128:16:2:0 --task logistic_regression \
#     --coordinate fixed:type=fixed,shard=global,max_iters=20 \
#     --coordinate per_user:type=random,shard=re0,entity=re0,max_iters=15 \
#     --coordinate per_item:type=random,shard=re1,entity=re1,max_iters=15 \
#     --descent-iterations 2 --validation-split 0.2 --max-quarantined 0 \
#     --output-dir out
GAME_CPU_AUC = 0.9449328780174255
# Different f32 solvers/reduction orders agree to ~1e-4 in the metric; a
# Hessian that lost definiteness or a mis-sharded table moves it by far more.
GAME_AUC_BAND = 5e-4
SERVE_REQUESTS = 200
SPARSE_ROWS, SPARSE_NNZ, SPARSE_DIM = 1 << 20, 32, 1 << 18
# Objective after the 5 L-BFGS iterations of the sparse leg under
# JAX_PLATFORMS=cpu (this PR): write_libsvm(path, SPARSE_ROWS, SPARSE_NNZ,
# SPARSE_DIM), then `python -m photon_tpu.drivers.train --backend cpu` with
# the arguments leg_sparse passes; sweep[0].final_value of the summary.
SPARSE_CPU_FINAL_VALUE = 345340.0
SPARSE_VALUE_BAND = 1e-3  # relative; a wrong gradient misses by far more
KERNEL_TABLE_ROWS = 1 << 16  # x 32 nnz = 2^21 entries, the probe's own cap

if REHEARSAL:
    GAME_DIMS = (400, 12, 32, 8, 2, 0)
    SERVE_REQUESTS = 40
    SPARSE_ROWS, SPARSE_DIM = 1 << 12, 1 << 12
    KERNEL_TABLE_ROWS = 1 << 9
GAME_SPEC = "synthetic-game:" + ":".join(map(str, GAME_DIMS))

TIME_LIMIT_S = 1150  # the contract allows 1200, compilation included


def say(*parts) -> None:
    print(*((("cpu",) if REHEARSAL else ()) + parts), flush=True)


def check(cond, message: str) -> None:
    if not cond:
        raise AssertionError(message)


# -- compile accounting -----------------------------------------------------


def run_leg(name, fn, totals):
    """One leg, with the compile requests it made: the program's own count
    (``compile.requests{outcome}`` in the process registry), those that went
    to the compiler (persistent cache misses) and those the cache served."""
    from photon_tpu.utils.compilation_cache import request_counts

    before = request_counts()
    t0 = time.monotonic()
    say(f"[{name}] start")
    fn()
    wall = time.monotonic() - t0
    after = request_counts()
    compiled = after["miss"] - before["miss"]
    cache_hits = after["hit"] - before["hit"]
    totals.append((name, wall, compiled, cache_hits))
    say(f"[{name}] ok wall={wall:.1f}s compiled={compiled} "
        f"cache_hits={cache_hits}")


# -- run-report helpers -----------------------------------------------------


def load_report(out_dir):
    with open(os.path.join(out_dir, "telemetry", "run_report.json")) as f:
        report = json.load(f)
    check(report["status"] == "success", f"{out_dir}: run report status "
          f"{report['status']!r}")
    return report


def metric_rows(report, name):
    metrics = report["metrics"]
    return [
        m for m in metrics["counters"] + metrics["gauges"]
        if m["name"] == name
    ]


def metric_sum(report, name, **labels):
    return sum(
        m["value"] for m in metric_rows(report, name)
        if all(m.get("labels", {}).get(k) == v for k, v in labels.items())
    )


def check_no_refusals(report, where):
    refused = {
        m["labels"]["kernel"]: m["value"]
        for m in metric_rows(report, "kernels.refused")
    }
    bad = {k: v for k, v in refused.items() if k in AUTO_KERNELS}
    check(not bad, f"{where}: the compiler refused auto-candidate "
          f"kernel(s) {bad} — see the WARNING above")


# -- leg 1: GAME train ------------------------------------------------------


def game_train_args(spec, out_dir):
    return [
        "--input", spec, "--task", "logistic_regression",
        "--coordinate", "fixed:type=fixed,shard=global,max_iters=20",
        "--coordinate",
        "per_user:type=random,shard=re0,entity=re0,max_iters=15",
        "--coordinate",
        "per_item:type=random,shard=re1,entity=re1,max_iters=15",
        "--descent-iterations", "2", "--validation-split", "0.2",
        "--max-quarantined", "0", "--output-dir", out_dir,
    ]


def leg_game_train(out_dir, device):
    from photon_tpu.drivers import train_game
    from photon_tpu.game.model import FixedEffectModel
    from photon_tpu.game.model_io import load_game_model

    summary = train_game.run(
        train_game.build_parser().parse_args(game_train_args(GAME_SPEC,
                                                             out_dir))
    )
    check(summary["device"] == device, f"summary device {summary['device']}")
    report = load_report(out_dir)
    check_no_refusals(report, "train_game")

    syncs = metric_sum(report, "descent.host_syncs")
    check(syncs == 2, f"descent.host_syncs {syncs} != 2 iterations")
    quarantined = metric_sum(report, "descent.quarantined")
    check(quarantined == 0, f"{quarantined} quarantined solves")
    for coord in ("per_user", "per_item"):
        live = metric_sum(report, "solves.bin_occupancy", coordinate=coord)
        newton = metric_sum(
            report, "solves.routed", coordinate=coord, route="newton"
        )
        routed = metric_sum(report, "solves.routed", coordinate=coord)
        check(live > 0 and newton == live == routed,
              f"{coord}: {newton} newton-routed of {routed} routed, "
              f"{live} live entities")
    # Rows (fixed) and entity blocks (random) really sit on every device.
    for coord in ("fixed", "per_user", "per_item"):
        devices = metric_sum(report, "placement.devices", coordinate=coord)
        slices = metric_sum(report, "placement.slices", coordinate=coord)
        check(devices == slices == device["device_count"],
              f"{coord}: training arrays on {devices} device(s) in "
              f"{slices} distinct slice(s), want {device['device_count']}")

    model, _ = load_game_model(os.path.join(out_dir, "best_model"))
    for name, coord in model.coordinates.items():
        table = np.asarray(
            coord.coefficients.means if isinstance(coord, FixedEffectModel)
            else coord.table
        )
        check(np.all(np.isfinite(table)), f"{name}: non-finite coefficients")
    auc = summary["best_metrics"]["AUC"]
    check(np.isfinite(auc), f"AUC {auc}")
    if REHEARSAL:
        say(f"  validation AUC {auc:.6f} (rehearsal size: no reference)")
    else:
        check(abs(auc - GAME_CPU_AUC) <= GAME_AUC_BAND,
              f"validation AUC {auc} outside {GAME_CPU_AUC} "
              f"+/- {GAME_AUC_BAND} (the CPU reference)")
        say(f"  validation AUC {auc:.7f} (cpu reference "
            f"{GAME_CPU_AUC:.7f}, band {GAME_AUC_BAND})")
    say(f"  host_syncs=2/2 iterations, quarantined=0, newton covers all "
        f"live entities, arrays on {device['device_count']} device(s)")


# -- leg 2: serve -----------------------------------------------------------


def leg_serve(model_dir, out_dir, device):
    from photon_tpu.drivers import serve_game
    from photon_tpu.game import lowp
    from photon_tpu.game.model_io import load_game_model
    from photon_tpu.data.synthetic import make_game_dataset
    from photon_tpu.serving import TrafficSpec, generate_traffic
    from photon_tpu.serving.router import host_score_request, parity_worst
    from photon_tpu.serving.scorer import DEFAULT_MIN_BUCKET, bucket_ladder

    replicas = device["device_count"]
    args = serve_game.build_parser().parse_args([
        "--model", model_dir, "--input", GAME_SPEC,
        "--requests", str(SERVE_REQUESTS), "--clients", "4",
        "--replicas", str(replicas), "--output-dir", out_dir,
    ])
    summary = serve_game.run(args)
    check(summary["device"] == device, f"summary device {summary['device']}")
    report = load_report(out_dir)
    check(summary["served"] == SERVE_REQUESTS and summary["shed"] == 0,
          f"served {summary['served']}/{SERVE_REQUESTS}, "
          f"shed {summary['shed']}")
    batches = metric_sum(report, "serving.batches")
    syncs = metric_sum(report, "serving.host_syncs")
    check(batches > 0 and syncs == batches,
          f"serving.host_syncs {syncs} != serving.batches {batches}")
    ladder = bucket_ladder(None, args.max_batch, DEFAULT_MIN_BUCKET)
    check(summary["compiled_programs"] == len(ladder) * replicas,
          f"{summary['compiled_programs']} programs, ladder {ladder} x "
          f"{replicas} replica(s)")
    check(summary["compiled_during_traffic"] == 0,
          f"{summary['compiled_during_traffic']} compile(s) during traffic")
    placed = summary["replica_devices"]
    check(len(placed) == replicas
          and all(len(ids) == 1 for ids in placed.values())
          and len({ids[0] for ids in placed.values()}) == replicas,
          f"replica tables on devices {placed}, want {replicas} distinct")

    # The host oracle: the same seeded traffic, scored in numpy from the
    # saved tables — no serving table, no device.
    model, _ = load_game_model(model_dir)
    data, _ = make_game_dataset(
        *GAME_DIMS[:4], n_random_coords=GAME_DIMS[4], seed=GAME_DIMS[5]
    )
    traffic = generate_traffic(data, model, TrafficSpec(
        requests=args.requests, mean_rows=args.request_rows_mean,
        max_rows=args.max_batch, popularity=args.traffic,
        alpha=args.popularity_alpha, storm_frac=args.storm_frac,
        seed=args.seed,
    ))
    want = np.concatenate(
        [host_score_request(model, item.request) for item in traffic.items]
    )
    got = np.loadtxt(os.path.join(out_dir, "scores.txt"), dtype=np.float64,
                     ndmin=1)
    worst = parity_worst(got, want)
    tol = lowp.PARITY_TOL["f32"]
    check(worst <= tol, f"served vs host oracle: max abs err {worst} > {tol}")
    say(f"  served {summary['served']}/{SERVE_REQUESTS} ({got.size} rows), "
        f"host_syncs == batches == {int(batches)}, "
        f"{summary['compiled_programs']} programs, 0 compiled during "
        f"traffic, replica devices {placed}")
    say(f"  served vs host oracle: max abs err {worst:.3g} (tol {tol})")


# -- leg 3: sparse fixed effect ---------------------------------------------


def write_libsvm(path, rows, nnz, dim, seed=0):
    """Seeded logistic LIBSVM data, ``nnz`` features per row over ``dim``
    ids, written as fixed-width tokens (`` 012345:+1.2345``) so the whole
    file is one vectorized digit-plane fill instead of ``rows * nnz``
    Python string formats."""
    rng = np.random.default_rng(seed)
    stride = dim // nnz
    w_true = (rng.standard_normal(nnz) * 0.5).astype(np.float32)
    tok = 15  # ' ' + 6 id digits + ':' + sign + 'd.dddd'
    width = 2 + nnz * tok + 1
    chunk = 1 << 17
    with open(path, "wb") as f:
        for start in range(0, rows, chunk):
            n = min(chunk, rows - start)
            # One id per stride: ascending and unique within a row, like a
            # hashed feature space; the last row pins the maximum id so
            # the file's width is exactly ``dim``.
            ids = (
                np.arange(nnz, dtype=np.int64)[None, :] * stride
                + rng.integers(0, stride, size=(n, nnz)) + 1
            )
            if start + n == rows:
                ids[-1, -1] = dim
            vals = np.clip(rng.standard_normal((n, nnz)), -9.0, 9.0)
            fixed = np.rint(np.abs(vals) * 1e4).astype(np.int64)
            signed = np.where(vals < 0, -1.0, 1.0) * fixed / 1e4
            p = 1.0 / (1.0 + np.exp(-(signed @ w_true)))
            positive = rng.random(n) < p
            buf = np.full((n, width), ord(" "), np.uint8)
            buf[:, 0] = np.where(positive, ord("+"), ord("-"))
            buf[:, 1] = ord("1")
            buf[:, -1] = ord("\n")
            body = buf[:, 2:-1].reshape(n, nnz, tok)
            for j in range(6):
                body[:, :, 1 + j] = (ids // 10 ** (5 - j)) % 10 + ord("0")
            body[:, :, 7] = ord(":")
            body[:, :, 8] = np.where(vals < 0, ord("-"), ord("+"))
            body[:, :, 9] = fixed // 10 ** 4 + ord("0")
            body[:, :, 10] = ord(".")
            for j in range(4):
                body[:, :, 11 + j] = (fixed // 10 ** (3 - j)) % 10 + ord("0")
            f.write(buf.tobytes())


def sparse_fit(path, out_dir, device, seen, pin=None):
    """One 5-iteration L-BFGS fit through ``drivers.train``; ``pin`` is the
    operator's ``PHOTON_SPARSE_GRAD`` (None = auto selection).  Returns the
    kernels this fit selected (``seen`` carries the process-wide
    ``kernels.selected`` counts across fits)."""
    from photon_tpu.drivers import train

    check(os.environ.get("PHOTON_SPARSE_GRAD") is None,
          "PHOTON_SPARSE_GRAD must be unset when the smoke starts")
    if pin is not None:
        import jax

        # The pin is read when the optimizer loop is TRACED, and an
        # earlier fit over the same shapes left that trace cached.
        jax.clear_caches()
        os.environ["PHOTON_SPARSE_GRAD"] = pin
    try:
        summary = train.run(train.build_parser().parse_args([
            "--input", path, "--no-intercept",
            "--task", "logistic_regression", "--optimizer", "lbfgs",
            "--reg-type", "l2", "--reg-weights", "1.0",
            "--max-iterations", "5", "--output-dir", out_dir,
        ]))
    finally:
        os.environ.pop("PHOTON_SPARSE_GRAD", None)
    check(summary["device"] == device, f"summary device {summary['device']}")
    report = load_report(out_dir)
    check(metric_sum(report, "train.num_features") == SPARSE_DIM
          and metric_sum(report, "train.num_examples") == SPARSE_ROWS,
          "the fit did not see the generated shape")
    check_no_refusals(report, f"train (PHOTON_SPARSE_GRAD={pin or 'auto'})")
    (entry,) = summary["sweep"]
    values = [value for value, _ in entry["states"]]
    check(entry["iterations"] == 5 and len(values) >= 2
          and np.all(np.isfinite(values)) and values[-1] < values[0],
          f"L-BFGS took {entry['iterations']} iterations, values {values}")
    if not REHEARSAL:
        off = abs(values[-1] / SPARSE_CPU_FINAL_VALUE - 1.0)
        check(off <= SPARSE_VALUE_BAND,
              f"objective after 5 iterations {values[-1]} vs the CPU "
              f"reference {SPARSE_CPU_FINAL_VALUE}: off by {off:.3g} "
              f"(band {SPARSE_VALUE_BAND})")
    selected = {}
    for m in metric_rows(report, "kernels.selected"):
        kernel, count = m["labels"]["kernel"], int(m["value"])
        if count > seen.get(kernel, 0):
            selected[kernel] = count - seen.get(kernel, 0)
        seen[kernel] = count
    say(f"  {pin or 'auto'}: L-BFGS 5 iterations, objective "
        f"{values[0]:.7g} -> {values[-1]:.7g}"
        + ("" if REHEARSAL
           else f" (cpu reference {SPARSE_CPU_FINAL_VALUE:.7g})")
        + f"; kernel the fit used: {selected}")
    return selected


def leg_sparse(work_dir, device):
    from photon_tpu.ops import sparse_grad_select

    path = os.path.join(work_dir, "sparse.libsvm")
    t0 = time.monotonic()
    write_libsvm(path, SPARSE_ROWS, SPARSE_NNZ, SPARSE_DIM)
    say(f"  generated {SPARSE_ROWS} rows x {SPARSE_NNZ} nnz, d={SPARSE_DIM} "
        f"({os.path.getsize(path) >> 20} MiB) in "
        f"{time.monotonic() - t0:.1f}s")
    seen: dict = {}
    # Auto selection first; then the Pallas kernel pinned, so Mosaic runs
    # inside the real optimizer loop (and, on several chips, inside
    # shard_map) whichever kernel the measurement preferred.
    sparse_fit(path, os.path.join(work_dir, "sparse-auto"), device, seen)
    pinned = sparse_fit(path, os.path.join(work_dir, "sparse-pallas"),
                        device, seen, pin="pallas")
    os.unlink(path)
    check(set(pinned) == {"pallas"}, f"the pinned fit selected {pinned}")

    # The per-kernel table, on a probe problem of the same width.
    table = sparse_grad_select.kernel_report(
        KERNEL_TABLE_ROWS * SPARSE_NNZ, SPARSE_DIM, KERNEL_TABLE_ROWS
    )
    for kernel, status in table.items():
        say(f"  kernel {kernel}: {status}")
    if not REHEARSAL:
        # Every kernel is an auto candidate: each must compile and match
        # on this device.
        bad = {k: s for k, s in table.items() if s != "compiled+parity ok"}
        check(not bad, f"kernel(s) failed on the chip: {bad}")


# -- main -------------------------------------------------------------------


def _out_of_time(signum, frame):
    raise TimeoutError(f"chip_smoke exceeded {TIME_LIMIT_S}s")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cpu-rehearsal", action="store_true",
                        help="shrink every size and run on the host; every "
                        "line is labelled cpu and no result line is printed")
    parser.parse_args()

    import jax

    from photon_tpu.native import build as native_build
    from photon_tpu.utils import compilation_cache
    from photon_tpu.utils.device import device_facts

    device = device_facts()
    wanted = "cpu" if REHEARSAL else "tpu"
    if device["platform"] != wanted:
        # Nothing on stdout: no chip, no result.
        print(f"chip_smoke: jax {jax.__version__} found platform "
              f"{device['platform']!r} ({device['device_kind']}, "
              f"{device['device_count']} device(s)), not {wanted!r}; this "
              "smoke runs on a TPU (or, with --cpu-rehearsal, on the host)",
              file=sys.stderr)
        return 1
    say(f"jax {jax.__version__} platform={device['platform']} "
        f"device_kind={device['device_kind']} "
        f"device_count={device['device_count']}")

    # A hung leg must fail inside the contract's time limit, not outlive it.
    signal.signal(signal.SIGALRM, _out_of_time)
    signal.alarm(TIME_LIMIT_S)

    native_build.get_lib()
    say(f"native: {native_build.status()}")
    check(native_build.status() in ("built", "loaded"),
          "the native library is unavailable: the readers would silently "
          "run in Python")

    compilation_cache.enable()  # and its listeners, before the first leg
    totals: list = []
    t0 = time.monotonic()
    work = tempfile.mkdtemp(prefix="chip_smoke-")
    try:
        train_out = os.path.join(work, "game")
        run_leg("game-train", lambda: leg_game_train(train_out, device),
                totals)
        run_leg("serve", lambda: leg_serve(
            os.path.join(train_out, "best_model"),
            os.path.join(work, "served"), device), totals)
        run_leg("sparse-train", lambda: leg_sparse(work, device), totals)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    signal.alarm(0)

    say(f"total wall={time.monotonic() - t0:.1f}s "
        f"compiled={sum(t[2] for t in totals)} "
        f"cache_hits={sum(t[3] for t in totals)} "
        f"compile_cache={os.environ['JAX_COMPILATION_CACHE_DIR']}")
    if REHEARSAL:
        say("rehearsal passed (no result line: results come from a chip)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"],
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
