"""Online GAME scoring service driver (fleet serving loop).

Loads a saved GAME model ONCE (shared model artifact), builds ``--replicas``
scorer replicas — each owning device-resident serving tables — behind the
deadline-aware fleet router, pre-compiles every replica's bucket ladder,
then drives a seeded traffic stream through the service with closed-loop
clients.  ``--traffic powerlaw`` (default) generates requests through the
fleet traffic generator — power-law entity popularity, optional cold-start
storm segment — while ``--traffic geometric`` keeps the PR 9 seeded
geometric row-window stream for bench continuity.  ``--transport tcp``
serves over the real socket ingest (loopback; clients are
``ScoringClient`` connections) instead of in-process submission, and
``--deadline-ms`` arms admission control (requests whose queue-wait
projection blows the budget are shed and counted, never queued).

Scores land in ``<output-dir>/scores.txt`` in request order (admitted
requests only); the telemetry run report carries the full ``serving.*``
block including the "Serving fleet" section (per-replica QPS/depth, shed
breakdown, deadline hit rate).

    python -m photon_tpu.drivers.serve_game \\
        --model out/best_model --input test.avro \\
        --feature-bags global=features,per_user=userFeatures \\
        --id-columns userId \\
        --requests 500 --clients 8 --replicas 2 --transport tcp \\
        --deadline-ms 25 --max-batch 128 --max-delay-ms 2 \\
        --output-dir served
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

from photon_tpu.drivers import common
from photon_tpu.drivers.train_game import _load_game_data


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "photon_tpu.drivers.serve_game", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    common.add_common_args(p)
    p.add_argument("--model", required=True, help="GAME model directory")
    p.add_argument("--input", required=True,
                   help="request feature source: Avro file/dir/glob or "
                   "synthetic-game spec (see train_game); requests are row "
                   "sets cut from it")
    p.add_argument("--feature-bags", default=None)
    p.add_argument("--id-columns", default=None)
    p.add_argument("--requests", type=int, default=256,
                   help="number of requests to serve")
    p.add_argument("--request-rows-mean", type=float, default=8.0,
                   help="mean rows per request (geometric long-tail, "
                   "clipped to [1, --max-batch])")
    p.add_argument("--clients", type=int, default=4,
                   help="closed-loop client threads (tcp: one connection "
                   "each)")
    p.add_argument("--replicas", type=int, default=1,
                   help="scorer replicas behind the fleet router (each "
                   "owns its device-resident tables)")
    p.add_argument("--traffic", choices=("powerlaw", "geometric"),
                   default="powerlaw",
                   help="request stream: power-law entity popularity via "
                   "the fleet traffic generator (default), or the PR 9 "
                   "seeded geometric row windows (bench continuity)")
    p.add_argument("--popularity-alpha", type=float, default=1.1,
                   help="power-law popularity exponent (powerlaw traffic)")
    p.add_argument("--storm-frac", type=float, default=0.0,
                   help="fraction of requests in a cold-start storm "
                   "segment (unknown entities; powerlaw traffic)")
    p.add_argument("--transport", choices=("inproc", "tcp"),
                   default="inproc",
                   help="inproc: submit straight to the router; tcp: "
                   "serve over the loopback socket ingest")
    p.add_argument("--replica-backend", choices=("thread", "subprocess"),
                   default="thread",
                   help="replica runtime: threads in this process, or one "
                   "child process per replica (own Python/jax runtime, "
                   "frame protocol over loopback; --backend cpu only — a "
                   "chip belongs to one process)")
    p.add_argument("--supervise", action="store_true",
                   help="attach the self-healing supervisor: health probes "
                   "(ping + known-answer score vs the host oracle), "
                   "crash/hang detection, backed-off resurrection with "
                   "canary-gated rejoin, flap quarantine")
    p.add_argument("--probe-interval-ms", type=float, default=500.0,
                   help="supervisor health-probe interval")
    p.add_argument("--deadline-ms", type=float, default=0.0,
                   help="per-request deadline budget; 0 disables "
                   "admission shedding")
    p.add_argument("--max-batch", type=int, default=128,
                   help="bucket-ladder cap / batcher coalescing cap (rows)")
    p.add_argument("--max-delay-ms", type=float, default=2.0,
                   help="batcher window: max time the first queued request "
                   "waits for coalescing partners")
    p.add_argument("--seed", type=int, default=0,
                   help="traffic stream seed")
    p.add_argument("--table-dtype", choices=("f32", "bf16", "int8"),
                   default="f32",
                   help="storage dtype for the device-resident serving "
                   "tables (ISSUE 17): bf16 halves table bytes, int8 "
                   "quarters them (per-row absmax scale row); gathers "
                   "decode on device and ALL accumulation stays f32")
    p.add_argument("--models", type=int, default=1,
                   help="tenant models hosted per replica (ISSUE 18 "
                   "multi-model arena): N tenants m0..m{N-1} of the saved "
                   "model share ONE gather-table allocation and ONE "
                   "compiled bucket ladder; traffic is split across them "
                   "by seeded hash-of-user arms unless --splits overrides")
    p.add_argument("--splits", default=None,
                   help="traffic split spec 'm0=0.7,m1=0.3' (weights "
                   "normalize): each request's user hashes to an arm, the "
                   "arm is the tenant model id it scores against")
    p.add_argument("--tenant-queue-rows", type=int, default=0,
                   help="per-tenant admission budget (queued rows cap per "
                   "model id); 0 disables tenant isolation shedding")
    return p


def request_sizes(n_requests: int, mean: float, cap: int,
                  seed: int) -> np.ndarray:
    """Seeded long-tailed request-size stream (geometric, clipped to
    [1, cap]) — shared by ``--traffic geometric``, the traffic generator,
    and ``bench.py --mode serving`` so the measured arrival pattern is the
    served one."""
    from photon_tpu.serving.traffic import geometric_sizes

    return geometric_sizes(n_requests, mean, cap, np.random.default_rng(seed))


def _publish_text(output_dir: str, name: str, write_fn, session,
                  logger) -> None:
    """Atomic, retried artifact publish (the score_game convention, PR 7):
    each attempt writes a fresh temp file and renames it into place, so a
    crash or a stall-escalated abandoned writer can never leave a torn
    artifact — readers see the previous complete file or the new one."""
    import tempfile

    from photon_tpu.fault.injection import fault_point
    from photon_tpu.fault.retry import retry_call

    def attempt():
        fault_point("io:write", path=name)
        fd, tmp = tempfile.mkstemp(
            prefix=f".{name}-", suffix=".tmp", dir=output_dir
        )
        try:
            with os.fdopen(fd, "w") as f:
                write_fn(f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, os.path.join(output_dir, name))
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    retry_call(attempt, site="serve:write", telemetry=session, logger=logger)


def run(args: argparse.Namespace) -> dict:
    device = common.select_backend(args.backend)
    from photon_tpu.utils import PhotonLogger

    logger = PhotonLogger("photon_tpu.serve_game", args.log_file)
    with common.telemetry_run(args, "serve_game", logger) as session:
        return _run(args, logger, session, device)


def _run(args: argparse.Namespace, logger, session, device: dict) -> dict:
    from photon_tpu.fault.retry import retry_call
    from photon_tpu.game.model_io import load_game_model
    from photon_tpu.serving import (
        AdmissionPolicy,
        ScoringClient,
        ServingFleet,
        TrafficSpec,
        generate_traffic,
        request_spec_for_dataset,
        run_closed_loop_outcomes,
    )

    os.makedirs(args.output_dir, exist_ok=True)

    with logger.timed("load-model"):
        model, index_maps = retry_call(
            lambda: load_game_model(args.model),
            site="model:load", telemetry=session, logger=logger,
        )
        logger.info("model: %s, coordinates %s", model.task_type,
                    list(model.coordinates))

    with logger.timed("load-data"):
        data, _ = _load_game_data(
            args.input, args, index_maps=index_maps, telemetry=session
        )
        logger.info("request source: %d rows", data.num_examples)

    deadline_s = (
        args.deadline_ms / 1000.0 if args.deadline_ms > 0 else None
    )
    # Multi-model arena (ISSUE 18): N tenants of the saved model share one
    # arena allocation + one compiled ladder per replica; traffic routes by
    # seeded split arms (arm id == tenant model id).
    models = (
        {f"m{i}": model for i in range(args.models)}
        if args.models > 1 else None
    )
    splits = None
    if args.splits:
        splits = {}
        for part in args.splits.split(","):
            arm, _, weight = part.partition("=")
            splits[arm.strip()] = float(weight or 1.0)
    elif models:
        splits = {mid: 1.0 / len(models) for mid in models}
    with logger.timed("build-fleet"):
        fleet = ServingFleet(
            model,
            replicas=args.replicas,
            backend=args.replica_backend,
            request_spec=request_spec_for_dataset(model, data),
            max_batch=args.max_batch,
            max_delay_s=args.max_delay_ms / 1000.0,
            telemetry=session,
            admission=AdmissionPolicy(
                default_deadline_s=deadline_s,
                tenant_queue_rows=args.tenant_queue_rows or None,
            ),
            table_dtype=args.table_dtype,
            models=models,
        ).warmup()
        if args.supervise:
            from photon_tpu.serving import SupervisorPolicy

            fleet.supervise(
                SupervisorPolicy(
                    probe_interval_s=args.probe_interval_ms / 1000.0
                ),
                logger=logger,
            )
        logger.info("fleet warm: %d %s replicas, %d programs compiled%s",
                    args.replicas, args.replica_backend, fleet.compilations,
                    ", supervised" if args.supervise else "")
        warm_compilations = fleet.compilations
        # Read off the table arrays themselves (thread replicas; a
        # subprocess replica's tables live in its child).
        replica_devices = {
            r.replica_id: [d.id for d in r.scorer.table_devices()]
            for r in fleet.replicas if hasattr(r.scorer, "table_devices")
        }

    spec = TrafficSpec(
        requests=args.requests,
        mean_rows=args.request_rows_mean,
        max_rows=args.max_batch,
        popularity=args.traffic,
        alpha=args.popularity_alpha,
        storm_frac=args.storm_frac if args.traffic == "powerlaw" else 0.0,
        deadline_ms=args.deadline_ms if args.deadline_ms > 0 else None,
        seed=args.seed,
        splits=splits,
    )
    traffic = generate_traffic(data, model, spec)

    server = fleet.serve() if args.transport == "tcp" else None
    clients: list = []

    def factory(tid: int):
        if server is None:
            return lambda item: fleet.score(
                item.request, deadline_s=item.deadline_s
            )
        client = ScoringClient(server.address, telemetry=session)
        clients.append(client)
        return lambda item: client.score(
            item.request, deadline_s=item.deadline_s
        )

    try:
        with logger.timed("serve"):
            outcomes, wall = run_closed_loop_outcomes(
                factory, traffic.items, clients=args.clients
            )
    finally:
        for client in clients:
            client.close()
        fleet.close()

    ok = [o for o in outcomes if o.status == "ok"]
    shed = [o for o in outcomes if o.status == "shed"]
    errors = [o for o in outcomes if o.status == "error"]
    if errors:
        raise RuntimeError(
            f"{len(errors)} request(s) failed; first: {errors[0].reason}"
        )

    rows = int(sum(o.item.request.num_rows for o in ok))
    qps = len(ok) / wall if wall > 0 else 0.0
    lat_ms = np.sort(np.asarray(
        [o.latency_s for o in ok], np.float64
    )) * 1e3 if ok else np.zeros(1)
    p50 = float(np.percentile(lat_ms, 50))
    p99 = float(np.percentile(lat_ms, 99))
    session.gauge("serving.qps").set(qps)
    session.gauge("serving.rows_per_second").set(rows / wall if wall else 0.0)

    _publish_text(
        args.output_dir, "scores.txt",
        lambda f: np.savetxt(
            f,
            np.concatenate([o.scores for o in ok])
            if ok else np.zeros(0, np.float32),
            fmt="%.8g",
        ),
        session, logger,
    )

    def _counter(name):
        return sum(
            m["value"]
            for m in session.registry.snapshot().get("counters", [])
            if m["name"] == name
        ) if session.enabled else 0

    cold = _counter("serving.cold_entities")
    summary = {
        "device": device,
        "requests": len(outcomes),
        "served": len(ok),
        "shed": len(shed),
        "shed_fraction": round(len(shed) / len(outcomes), 4)
        if outcomes else 0.0,
        "rows": rows,
        "wall_s": round(wall, 4),
        "qps": round(qps, 2),
        "rows_per_sec": round(rows / wall, 1) if wall else 0.0,
        "latency_p50_ms": round(p50, 3),
        "latency_p99_ms": round(p99, 3),
        "cold_entities": int(cold),
        "compiled_programs": fleet.compilations,
        "compiled_during_traffic": fleet.compilations - warm_compilations,
        "replicas": args.replicas,
        "replica_devices": replica_devices,
        "replica_backend": args.replica_backend,
        "supervised": bool(args.supervise),
        "replica_deaths": int(_counter("serving.replica_deaths")),
        "resurrections": int(_counter("serving.replica_resurrections")),
        "quarantined": int(_counter("serving.replica_quarantined")),
        "transport": args.transport,
        "traffic": args.traffic,
        "deadline_ms": args.deadline_ms,
        "table_dtype": args.table_dtype,
        "models": args.models,
        "splits": splits,
        "tenant_shed": sum(
            1 for o in shed if "tenant_budget" in str(o.reason or "")
        ),
    }
    _publish_text(
        args.output_dir, "serving_summary.json",
        lambda f: json.dump(summary, f, indent=1),
        session, logger,
    )
    logger.info(
        "served %d/%d requests (%d rows, %d shed) at %.1f req/s; latency "
        "p50 %.2f ms p99 %.2f ms; %d cold entities",
        summary["served"], summary["requests"], rows, summary["shed"],
        qps, p50, p99, summary["cold_entities"],
    )
    return summary


def main(argv=None) -> None:
    run(build_parser().parse_args(argv))


if __name__ == "__main__":
    main()
