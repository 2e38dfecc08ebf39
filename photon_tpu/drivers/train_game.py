"""GAME training driver (the reference's ``GameTrainingDriver``).

End-to-end (SURVEY.md §3.1): read Avro training data (feature bags +
entity-id columns) → build per-coordinate GAME datasets → GameEstimator.fit
over the per-coordinate regularization sweep → evaluate → save the best GAME
model directory (per-coordinate name/term-keyed Avro coefficients).

Coordinate configs are ``name:key=value,...`` specs (or ``@file.json``):

    python -m photon_tpu.drivers.train_game \\
        --input train.avro --task logistic_regression \\
        --feature-bags global=features,per_user=userFeatures \\
        --id-columns userId \\
        --coordinate global:type=fixed,shard=global,optimizer=lbfgs,reg_weights=0.1+1 \\
        --coordinate per_user:type=random,shard=per_user,entity=userId,reg_weights=1 \\
        --descent-iterations 2 --validation-split 0.2 --output-dir out

Spec keys: ``type`` (fixed|random|factored_random), ``shard``, ``entity``
(random variants only), ``latent_dim``/``latent_iterations`` (factored),
``optimizer`` (lbfgs|owlqn|tron), ``reg_type``, ``reg_weights`` (``+``-joined
sweep list), ``alpha`` (elastic net), ``max_iters``, ``tolerance``,
``variance`` (none|simple), ``active_row_cap`` (random), ``downsample``
(fixed), ``seed``.  The sweep is the cross product of every coordinate's
``reg_weights`` list (the reference's GameOptimizationConfiguration grid).
"""

from __future__ import annotations

import argparse
import itertools
import json
import os

from photon_tpu.drivers import common


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "photon_tpu.drivers.train_game", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    common.add_common_args(p)
    common.add_distributed_args(p)
    p.add_argument("--input", required=True,
                   help="training data: Avro file/dir/glob, or "
                   "synthetic-game:<entities>:<rows_mean>:<fixed_dim>:"
                   "<random_dim>[:n_random[:seed]]")
    p.add_argument("--validation-input", default=None,
                   help="validation data (same format as --input)")
    p.add_argument("--validation-split", type=float, default=None,
                   help="fraction of --input rows held out for validation "
                   "(alternative to --validation-input)")
    p.add_argument("--feature-bags", default=None,
                   help="shard=recordField pairs, comma separated "
                   "(Avro input only)")
    p.add_argument("--id-columns", default=None,
                   help="entity id columns to read, comma separated "
                   "(Avro input only)")
    p.add_argument("--index-maps", default=None,
                   help="directory of feature_index_<shard>.json maps from "
                   "the index_features driver; features absent from a map "
                   "are dropped (fixed-index training)")
    p.add_argument("--data-validation", default="error",
                   choices=("error", "warn", "off"),
                   help="row sanity checks before training (the reference's "
                   "DataValidators strictness)")
    p.add_argument("--task", default="logistic_regression",
                   choices=("logistic_regression", "linear_regression",
                            "poisson_regression", "smoothed_hinge_loss_linear_svm"))
    p.add_argument("--coordinate", action="append", required=True,
                   dest="coordinates", metavar="NAME:K=V,...",
                   help="one per coordinate, in update order; or a single "
                   "@configs.json")
    p.add_argument("--descent-iterations", type=int, default=1)
    p.add_argument("--residuals", default=None,
                   choices=("auto", "device", "host"),
                   help="residual passing between coordinates: 'device' "
                   "keeps per-coordinate score vectors in a device-resident "
                   "sharded table (default via auto; SPMD-safe, runs under "
                   "multi-process meshes), 'host' restores the float64 "
                   "numpy accumulate (escape hatch).  Overrides "
                   "PHOTON_RESIDUALS")
    p.add_argument("--validation-pipeline", default=None,
                   choices=("auto", "device", "host"),
                   help="validation scoring/evaluation: 'device' keeps a "
                   "per-coordinate validation score table on device, "
                   "re-scores only retrained coordinates, and runs the "
                   "jitted metrics (one scalar sync per metric); 'host' "
                   "restores the full per-iteration GameModel.score fetch "
                   "+ numpy evaluators.  'auto' (default) follows "
                   "--residuals.  Overrides PHOTON_VALIDATION")
    p.add_argument("--stream-chunks", type=int, default=None,
                   metavar="ROWS",
                   help="out-of-core GAME: train with the streamed descent "
                   "— rows partitioned into ROWS-sized chunks, score "
                   "tables tiled at the host tier, chunks double-buffered "
                   "h2d on the io pool (device residency bounded by the "
                   "chunk window, not the dataset).  Single-controller; "
                   "replaces --residuals/--validation-pipeline.  Also "
                   "auto-enabled by --max-resident-mb")
    p.add_argument("--max-resident-mb", type=float, default=None,
                   help="device-residency budget in MB: when the dataset's "
                   "resident-fit estimate exceeds it, streaming "
                   "auto-enables with a chunk size whose in-flight window "
                   "fits the budget (explicit --stream-chunks wins)")
    p.add_argument("--max-host-mb", type=float, default=None,
                   help="host-RAM budget in MB for the streamed tier "
                   "(mirrors --max-resident-mb one tier up): when the "
                   "streamed fit's host working set — feature chunks + "
                   "score tiles — exceeds it, the disk-backed tile store "
                   "auto-enables (spilling to --spill-dir) with an LRU "
                   "host cache bounded by this budget, and streaming "
                   "itself auto-enables if no device budget already did. "
                   "NOTE: the ingestion path still materializes the "
                   "dataset once to build the store (ROADMAP tiering "
                   "edge (a)); the budget bounds the fit's STEADY-STATE "
                   "working set, not the initial load")
    p.add_argument("--spill-dir", default=None,
                   help="directory for the disk-backed tile store "
                   "(per-chunk feature blocks + score tiles).  Setting it "
                   "forces spilling; otherwise --max-host-mb derives "
                   "<output-dir>/tile_store when the host budget is "
                   "exceeded.  Requires streamed mode")
    p.add_argument("--tile-dtype", choices=("f32", "bf16", "int8"),
                   default="f32",
                   help="storage codec for the DISK tier's tile store "
                   "(ISSUE 17): bf16 halves and int8 (per-row absmax "
                   "scale row) quarters spilled feature blocks and score "
                   "tiles; host-resident tiles and all accumulation stay "
                   "f32.  Requires --spill-dir (or a --max-host-mb that "
                   "derives one)")
    p.add_argument("--dtype", default="float32",
                   choices=("float32", "bfloat16"),
                   help="storage dtype for FEATURE VALUES in every shard "
                   "(labels, weights, coefficients, and all arithmetic stay "
                   "float32); bfloat16 halves the value stream each "
                   "coordinate's gathers read from HBM")
    p.add_argument("--evaluators", default=None,
                   help="comma-separated; sharded variants take the id "
                   "column, e.g. SHARDED_AUC:userId")
    p.add_argument("--initial-model", default=None,
                   help="GAME model directory for warm start")
    p.add_argument("--locked-coordinates", default=None,
                   help="comma-separated coordinates to freeze at the "
                   "initial model (partial retraining)")
    p.add_argument("--tuning", default="none",
                   choices=("none", "random", "bayesian"),
                   help="tune per-coordinate regularization weights on the "
                   "validation metric (reference: hyperParameterTuning "
                   "RANDOM|BAYESIAN) instead of the reg_weights grid")
    p.add_argument("--tuning-iterations", type=int, default=10)
    p.add_argument("--tuning-range", default="1e-4:1e4",
                   help="lo:hi log-scale range for tuned reg weights")
    p.add_argument("--model-format", default="avro", choices=("avro", "json"))
    p.add_argument("--save-all-models", action="store_true")
    p.add_argument("--checkpoint", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="write each sweep entry's model as it finishes "
                   "(resume via --initial-model)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="preemption-safe descent checkpointing: after every "
                   "outer iteration the full restart state (models, "
                   "residual score rows, best-model tracking, history) is "
                   "published atomically under this directory (one "
                   "subdirectory per sweep entry; rank 0 writes under "
                   "multi-controller)")
    p.add_argument("--checkpoint-async", default=None, choices=("on", "off"),
                   help="publish descent checkpoints from a background "
                   "thread (default on, or PHOTON_CHECKPOINT_ASYNC): the "
                   "loop stages the d2h copies (copy_to_host_async) and "
                   "the serialize+fsync+rename runs behind the next "
                   "iteration's compute; LATEST may lag the loop by one "
                   "iteration.  'off' restores inline synchronous writes")
    p.add_argument("--checkpoint-max-staged-mb", type=float, default=None,
                   help="cap the async publisher's staged host copies "
                   "(checkpoint.staged_bytes): a snapshot over this many "
                   "MB publishes blocking on the loop thread instead of "
                   "holding a second snapshot-sized host allocation while "
                   "training runs ahead.  Default: "
                   "PHOTON_CHECKPOINT_MAX_STAGED_MB, else unbounded")
    p.add_argument("--resume", default=None, metavar="auto|latest|PATH",
                   help="restore a descent mid-sweep from --checkpoint-dir: "
                   "'auto' resumes whatever is checkpointed (fresh start "
                   "otherwise), 'latest' requires a checkpoint, a path "
                   "names one checkpoint version directory.  Completed "
                   "sweep entries are rebuilt from their snapshots without "
                   "re-running; a resumed fit matches an uninterrupted one "
                   "— including on a DIFFERENT device/process count "
                   "(checkpoints are mesh-shape portable)")
    p.add_argument("--max-quarantined", type=int, default=8,
                   help="how many non-finite solves/score rows may be "
                   "quarantined (previous iterate kept, descent.quarantined "
                   "telemetry) before the run fails; -1 = unlimited")
    return p


_KNOWN_COORDINATE_KEYS = {
    "type", "shard", "entity", "optimizer", "reg_type", "reg_weights",
    "alpha", "max_iters", "tolerance", "variance", "active_row_cap",
    "downsample", "downsampler", "projection", "projected_dim", "seed",
    "row_split",
    "latent_dim", "latent_iterations",
}


def _validate_coordinate(name: str, kv: dict, origin: str) -> tuple[str, dict]:
    unknown = set(kv) - _KNOWN_COORDINATE_KEYS
    if unknown:
        raise ValueError(f"unknown coordinate key(s) {sorted(unknown)} in {origin}")
    if kv.get("type", "fixed") not in ("fixed", "random", "factored_random"):
        raise ValueError(
            f"coordinate type must be fixed|random|factored_random in {origin}"
        )
    if "shard" not in kv:
        raise ValueError(f"coordinate {name!r} needs shard=<feature shard>")
    if kv.get("type") in ("random", "factored_random") and "entity" not in kv:
        raise ValueError(f"random coordinate {name!r} needs entity=<id column>")
    return name, kv


def parse_coordinate_spec(spec: str):
    """``name:key=value,...`` -> (name, dict).  Raises on unknown keys."""
    name, _, body = spec.partition(":")
    if not name or not body:
        raise ValueError(f"bad coordinate spec {spec!r} (want name:key=value,...)")
    kv = {}
    for tok in body.split(","):
        k, _, v = tok.partition("=")
        kv[k.strip()] = v.strip()
    return _validate_coordinate(name, kv, repr(spec))


def _coordinate_specs(args) -> list[tuple[str, dict]]:
    if len(args.coordinates) == 1 and args.coordinates[0].startswith("@"):
        path = args.coordinates[0][1:]
        with open(path) as f:
            payload = json.load(f)
        return [
            _validate_coordinate(c.pop("name"), c, f"{path} entry {i}")
            for i, c in enumerate(payload)
        ]
    return [parse_coordinate_spec(s) for s in args.coordinates]


def _coord_bool(value) -> bool:
    """Coordinate-spec boolean: accepts JSON true/false (the @file path
    passes Python bools through) and the CLI strings true/1/yes /
    false/0/no.  Anything else raises — a typo like ``row_split=ture``
    silently disabling a feature is exactly the spec-validation failure
    mode the other keys reject (ADVICE r3)."""
    if isinstance(value, bool):
        return value
    s = str(value).strip().lower()
    if s in ("true", "1", "yes"):
        return True
    if s in ("false", "0", "no"):
        return False
    raise ValueError(
        f"coordinate-spec boolean must be true/false/1/0/yes/no, got {value!r}"
    )


def _coord_config(kv: dict, lam: float, task: str = "logistic_regression"):
    """Build one coordinate's config with regularization weight ``lam``.

    ``downsampler`` defaults to the task-appropriate sampler (binary for
    logistic/hinge, uniform otherwise — the reference's rule).
    """
    from photon_tpu.core.objective import RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import ProblemConfig
    from photon_tpu.game.coordinate import (
        FactoredRandomEffectCoordinateConfig,
        FixedEffectCoordinateConfig,
        RandomEffectCoordinateConfig,
    )

    reg_type = kv.get("reg_type", "l2")
    optimizer = kv.get("optimizer", "lbfgs")
    if reg_type in ("l1", "elastic_net"):
        optimizer = "owlqn"
    problem = ProblemConfig(
        optimizer=optimizer,
        regularization=RegularizationContext(
            reg_type, lam, float(kv.get("alpha", 0.5))
        ),
        optimizer_config=OptimizerConfig(
            max_iterations=int(kv.get("max_iters", 50)),
            tolerance=float(kv.get("tolerance", 1e-7)),
        ),
        variance_computation=kv.get("variance", "none"),
    )
    if kv.get("type", "fixed") == "fixed":
        if _coord_bool(kv.get("row_split", False)):
            raise ValueError(
                "row_split applies to random coordinates only (the fixed "
                "effect is already data-sharded with psum)"
            )
        downsampler = kv.get("downsampler") or "auto"
        if downsampler == "auto":
            from photon_tpu.core.losses import BINARY_TASKS

            downsampler = "binary" if task.lower() in BINARY_TASKS else "default"
        return FixedEffectCoordinateConfig(
            shard_name=kv["shard"],
            problem=problem,
            downsampling_rate=float(kv.get("downsample", 1.0)),
            downsampler=downsampler,
            seed=int(kv.get("seed", 0)),
        )
    cap = kv.get("active_row_cap")
    if kv.get("type") == "factored_random":
        if _coord_bool(kv.get("row_split", False)):
            raise ValueError(
                "row_split is not supported for factored_random coordinates "
                "(the pooled latent solve already spans the mesh)"
            )
        if kv.get("projection") or kv.get("projected_dim") or kv.get("variance"):
            raise ValueError(
                "projection/projected_dim/variance are not supported for "
                "factored_random coordinates (the latent projection IS the "
                "dimensionality reduction; z-space variances do not "
                "transport to w = L z)"
            )
        return FactoredRandomEffectCoordinateConfig(
            shard_name=kv["shard"],
            entity_column=kv["entity"],
            latent_dim=int(kv.get("latent_dim", 4)),
            latent_iterations=int(kv.get("latent_iterations", 2)),
            problem=problem,
            active_row_cap=None if cap in (None, "") else int(cap),
            seed=int(kv.get("seed", 0)),
        )
    pdim = kv.get("projected_dim")
    return RandomEffectCoordinateConfig(
        shard_name=kv["shard"],
        entity_column=kv["entity"],
        problem=problem,
        active_row_cap=None if cap in (None, "") else int(cap),
        projection=kv.get("projection", "none"),
        projected_dim=None if pdim in (None, "") else int(pdim),
        seed=int(kv.get("seed", 0)),
        row_split=_coord_bool(kv.get("row_split", False)),
    )


def _combo_label(specs, combo) -> str:
    return ",".join(f"{name}={lam:g}" for (name, _), lam in zip(specs, combo))


def _build_sweep(specs, task: str):
    """Cross product of per-coordinate reg weights -> configuration list."""
    weight_lists = []
    for _, kv in specs:
        weights = [float(w) for w in str(kv.get("reg_weights", "1.0")).split("+")]
        weight_lists.append(weights)

    configurations = []
    for combo in itertools.product(*weight_lists):
        coords = {
            name: _coord_config(kv, lam, task)
            for (name, kv), lam in zip(specs, combo)
        }
        configurations.append((_combo_label(specs, combo), coords, combo))
    return configurations


def _load_game_data(spec: str, args, index_maps=None, telemetry=None):
    """(dataset, index_maps) from an input spec (Avro or synthetic-game)."""
    if spec.startswith("synthetic-game:"):
        from photon_tpu.data.synthetic import make_game_dataset

        parts = spec.split(":")
        n_e, rows, fdim, rdim = (int(x) for x in parts[1:5])
        n_random = int(parts[5]) if len(parts) > 5 else 1
        seed = int(parts[6]) if len(parts) > 6 else 0
        data, maps = make_game_dataset(
            n_e, rows, fdim, rdim, seed=seed, n_random_coords=n_random
        )
        if index_maps is not None:
            # Synthetic features are positional; a model trained on other
            # data can only be applied if its maps agree key-for-key —
            # otherwise coefficients would land on the wrong columns.
            for name, imap in maps.items():
                other = index_maps.get(name)
                if other is not None and list(other.keys()) != list(imap.keys()):
                    raise ValueError(
                        f"model's index map for shard {name!r} does not match "
                        "the synthetic-game feature layout; score the data "
                        "the model was trained for"
                    )
        return data, (index_maps or maps)
    from photon_tpu.data.game_io import read_game_avro

    bags, id_cols = parse_bags_and_id_columns(args)
    return read_game_avro(
        spec, bags, id_cols, index_maps=index_maps, telemetry=telemetry
    )


def parse_feature_bags(feature_bags: str) -> dict:
    """--feature-bags 'shard=field,...' -> dict; the ONE parse of this flag
    (training, index-map loading, and streamed scoring all share it)."""
    return dict(tok.split("=", 1) for tok in feature_bags.split(","))


def parse_bags_and_id_columns(args) -> tuple[dict, list]:
    """--feature-bags + --id-columns -> (dict, list); shared by the training
    and (streamed) scoring drivers so parsing can never diverge."""
    if not args.feature_bags or not args.id_columns:
        raise ValueError(
            "Avro input needs --feature-bags and --id-columns "
            "(shard=field pairs and entity id fields)"
        )
    bags = parse_feature_bags(args.feature_bags)
    id_cols = [c.strip() for c in args.id_columns.split(",") if c.strip()]
    return bags, id_cols


def _has_published_checkpoint(checkpoint_dir) -> bool:
    """True when any descent checkpoint chain under ``checkpoint_dir`` has
    a published version (shared strictness rule — fault.checkpoint)."""
    from photon_tpu.fault.checkpoint import has_published_checkpoint

    return has_published_checkpoint(checkpoint_dir)


def run(args: argparse.Namespace) -> dict:
    common.maybe_init_distributed(args)
    device = common.select_backend(args.backend)
    from photon_tpu.utils import PhotonLogger

    logger = PhotonLogger("photon_tpu.train_game", args.log_file)
    with common.telemetry_run(
        args, "train_game", logger, preemptible=True
    ) as session:
        return _run(args, logger, session, device)


def _run(args: argparse.Namespace, logger, session, device: dict) -> dict:
    from photon_tpu.evaluation.evaluators import (
        MultiEvaluator,
        default_evaluators_for_task,
        get_evaluator,
    )
    from photon_tpu.game.data import split_game_dataset
    from photon_tpu.game.estimator import GameEstimator, GameOptimizationConfiguration
    from photon_tpu.game.model_io import load_game_model, save_game_model
    from photon_tpu.utils.logging import maybe_profile

    os.makedirs(args.output_dir, exist_ok=True)
    specs = _coordinate_specs(args)
    if args.resume and not args.checkpoint_dir:
        raise ValueError("--resume needs --checkpoint-dir")
    if args.resume == "latest" and not _has_published_checkpoint(
        args.checkpoint_dir
    ):
        # Strictness means a PUBLISHED checkpoint (a LATEST pointer), not
        # just directory debris from a run killed before its first publish.
        raise ValueError(
            f"--resume latest: no published checkpoint under "
            f"{args.checkpoint_dir!r}"
        )
    if args.resume and args.resume not in ("auto", "latest"):
        # An explicit checkpoint path names one descent run, so a
        # multi-entry sweep (or tuning, whose configurations are sampled)
        # is rejected up front — before the data load, not after entry 0
        # has already burned its fit.
        if args.tuning != "none" or len(_build_sweep(specs, args.task)) > 1:
            raise ValueError(
                "an explicit --resume path applies to a single sweep "
                "entry; use --resume auto for sweeps/tuning"
            )

    prebuilt_maps = None
    if args.index_maps:
        if not args.feature_bags:
            raise ValueError("--index-maps needs --feature-bags")
        from photon_tpu.data.index_map import IndexMap

        bags = parse_feature_bags(args.feature_bags)
        prebuilt_maps = {
            shard: IndexMap.load(
                os.path.join(args.index_maps, f"feature_index_{shard}.json")
            )
            for shard in bags
        }

    with logger.timed("load-data"):
        data, index_maps = _load_game_data(
            args.input, args, index_maps=prebuilt_maps, telemetry=session
        )
        val_data = None
        if args.validation_input:
            val_data, _ = _load_game_data(
                args.validation_input, args, index_maps=index_maps,
                telemetry=session,
            )
        elif args.validation_split:
            data, val_data = split_game_dataset(data, args.validation_split)
        if args.dtype != "float32":
            from photon_tpu.game.data import dataset_astype

            # Training data only: validation stays f32 (scoring promotes
            # anyway; metrics must not depend on the storage option).
            data = dataset_astype(data, args.dtype)
            logger.info("feature values stored as %s (f32 arithmetic)",
                        args.dtype)
        logger.info(
            "train: %d examples, shards %s", data.num_examples,
            {n: s.dim for n, s in data.shards.items()},
        )
        session.gauge("train.num_examples").set(data.num_examples)
        for shard_name, shard in data.shards.items():
            session.gauge("train.shard_dim", shard=shard_name).set(shard.dim)

    if args.data_validation != "off":
        from photon_tpu.data.validation import (
            apply_validation,
            validate_game_dataset,
        )

        apply_validation(
            validate_game_dataset(data, args.task), args.data_validation, logger
        )

    if args.evaluators:
        evaluators = MultiEvaluator(
            [get_evaluator(n) for n in args.evaluators.split(",")]
        )
    else:
        evaluators = MultiEvaluator(default_evaluators_for_task(args.task))

    initial_model = None
    if args.initial_model:
        initial_model, _ = load_game_model(args.initial_model)
    locked = (
        [c.strip() for c in args.locked_coordinates.split(",") if c.strip()]
        if args.locked_coordinates else []
    )

    mesh = common.maybe_mesh()
    stream_rows = None
    if args.stream_chunks is not None:
        if args.stream_chunks < 1:
            raise ValueError(
                f"--stream-chunks must be >= 1, got {args.stream_chunks}"
            )
        stream_rows = args.stream_chunks
    elif args.max_resident_mb is not None:
        from photon_tpu.game.tiles import (
            chunk_rows_for_budget,
            resident_bytes_estimate,
        )

        estimate = resident_bytes_estimate(data, n_coordinates=len(specs))
        budget = int(args.max_resident_mb * (1 << 20))
        session.gauge("stream.resident_estimate_bytes").set(estimate)
        if estimate > budget:
            stream_rows = chunk_rows_for_budget(data, args.max_resident_mb)
            logger.info(
                "resident estimate %.1f MB exceeds --max-resident-mb %.1f: "
                "streaming enabled with %d-row chunks",
                estimate / (1 << 20), args.max_resident_mb, stream_rows,
            )
    if args.max_host_mb is not None and args.max_host_mb <= 0:
        raise ValueError(
            f"--max-host-mb must be > 0, got {args.max_host_mb}"
        )
    spill_dir = args.spill_dir
    if args.max_host_mb is not None:
        # ISSUE 11 satellite: the auto-enable gate used to size against
        # device memory only — fold the HOST estimate in, so a dataset
        # past host RAM auto-enables streaming AND spilling instead of
        # OOM-ing the host tier.
        from photon_tpu.game.tiles import (
            chunk_rows_for_budget,
            stream_host_bytes_estimate,
        )

        host_estimate = stream_host_bytes_estimate(
            data, n_coordinates=len(specs)
        )
        host_budget = int(args.max_host_mb * (1 << 20))
        session.gauge("stream.host_estimate_bytes").set(host_estimate)
        if host_estimate > host_budget:
            if stream_rows is None:
                # Past host RAM with no device pressure configured:
                # stream anyway (the resident path would pin even more),
                # chunked so the in-flight window fits the host budget.
                stream_rows = chunk_rows_for_budget(data, args.max_host_mb)
                logger.info(
                    "host estimate %.1f MB exceeds --max-host-mb %.1f: "
                    "streaming enabled with %d-row chunks",
                    host_estimate / (1 << 20), args.max_host_mb,
                    stream_rows,
                )
            if spill_dir is None:
                spill_dir = os.path.join(args.output_dir, "tile_store")
            logger.info(
                "host estimate %.1f MB exceeds --max-host-mb %.1f: "
                "disk-backed tile store enabled at %s",
                host_estimate / (1 << 20), args.max_host_mb, spill_dir,
            )
    if spill_dir is not None and not stream_rows:
        raise ValueError(
            "--spill-dir requires streamed mode (--stream-chunks or a "
            "--max-resident-mb/--max-host-mb budget the dataset exceeds)"
        )
    if spill_dir is not None:
        session.gauge("stream.spilled").set(1)
    if stream_rows:
        import jax as _jax_stream

        if _jax_stream.process_count() > 1:
            raise ValueError(
                "--stream-chunks/--max-resident-mb streaming runs "
                "single-controller; drop the multi-process flags"
            )
        if mesh is not None:
            # A single-host multi-device mesh is an execution choice the
            # streamed loop does not use: fall back to one device rather
            # than refuse the run.
            logger.info(
                "streamed descent is single-controller: ignoring the "
                "%d-device mesh", len(_jax_stream.devices()),
            )
            mesh = None
        if args.residuals not in (None, "auto") or (
            args.validation_pipeline not in (None, "auto")
        ):
            logger.info(
                "streamed descent replaces --residuals/"
                "--validation-pipeline; ignoring the explicit flags"
            )
        session.gauge("stream.chunk_rows").set(stream_rows)
    estimator = GameEstimator(
        args.task,
        data,
        validation_data=val_data,
        evaluators=evaluators if val_data is not None else None,
        mesh=mesh,
        logger=logger,
        telemetry=session,
        # The streamed estimator refuses explicit engine modes; the driver
        # already warned above, so strip them here.
        residual_mode=None if stream_rows else args.residuals,
        validation_mode=None if stream_rows else args.validation_pipeline,
        stream_chunks=stream_rows,
        spill_dir=spill_dir,
        max_host_mb=args.max_host_mb if spill_dir is not None else None,
        tile_dtype=args.tile_dtype,
    )

    import jax as _jax

    # Multi-process runs: only process 0 writes checkpoints, models, and
    # summaries (the reference's driver-writes semantics; every rank still
    # participates in the collectives inside fit).
    is_primary = _jax.process_index() == 0
    session.write = is_primary

    results = []
    checkpoint_fn = None
    if args.checkpoint and is_primary:
        # Per-descent-iteration intermediate model (SURVEY.md §5): each
        # completed coordinate pass overwrites checkpoint/latest, so a
        # killed run resumes via --initial-model <out>/checkpoint/latest.
        ckpt_base = os.path.join(args.output_dir, "checkpoint")
        ckpt_dir = os.path.join(ckpt_base, "latest")

        def checkpoint_fn(iteration, model):
            # Atomic publish: write each checkpoint into an alternating slot
            # dir, then atomically repoint the `latest` symlink (os.replace
            # on a symlink is atomic; directories cannot be swapped
            # atomically on POSIX) — a crash at ANY instant leaves `latest`
            # resolving to a complete checkpoint (ADVICE r1).
            import shutil

            # Write into whichever slot `latest` does NOT currently resolve
            # to, so the live checkpoint is never touched mid-write.
            live = (
                os.path.basename(os.path.realpath(ckpt_dir))
                if os.path.islink(ckpt_dir) else None
            )
            slot = os.path.join(
                ckpt_base, "slot-1" if live == "slot-0" else "slot-0"
            )
            shutil.rmtree(slot, ignore_errors=True)
            save_game_model(slot, model, index_maps, fmt=args.model_format,
                            telemetry=session)
            tmp_link = os.path.join(ckpt_base, ".latest.tmp")
            if os.path.lexists(tmp_link):
                os.remove(tmp_link)
            if os.path.isdir(ckpt_dir) and not os.path.islink(ckpt_dir):
                # Migrate a pre-symlink layout: park the old dir aside first
                # (never deleted until the new link is live).  A dir cannot
                # be atomically replaced by a symlink on POSIX, so migration
                # has a one-time window where `latest` is missing — both
                # `latest.pre-symlink` and the new slot hold complete
                # checkpoints throughout it.
                aside = ckpt_dir + ".pre-symlink"
                shutil.rmtree(aside, ignore_errors=True)
                os.rename(ckpt_dir, aside)
            else:
                aside = None
            os.symlink(os.path.basename(slot), tmp_link)
            os.replace(tmp_link, ckpt_dir)
            if aside is not None:
                shutil.rmtree(aside, ignore_errors=True)
            logger.info("checkpoint: iteration %d -> %s", iteration, ckpt_dir)

    max_quarantined = (
        None if args.max_quarantined < 0 else args.max_quarantined
    )
    fit_seq = itertools.count()

    def _slug(label: str) -> str:
        return "".join(c if c.isalnum() else "-" for c in label)[:80]

    def fit_config(config) -> "object":
        # One stable checkpoint subdirectory per sweep entry (sequence
        # number + sanitized label), so every descent run owns its own
        # versioned checkpoint chain and mid-sweep resume can tell finished
        # entries from the interrupted one.
        ckpt_dir = resume = None
        if args.checkpoint_dir:
            seq = next(fit_seq)
            ckpt_dir = os.path.join(
                args.checkpoint_dir,
                f"{seq:03d}-{_slug(config.name or 'config')}",
            )
            # Per-entry resume is auto-style: entries the interrupted run
            # never reached have no checkpoint and start fresh ('latest'
            # strictness — at least one checkpoint exists — was enforced
            # above; explicit paths were validated single-entry up front).
            resume = args.resume if args.resume != "latest" else "auto"
        result = estimator.fit(
            [config], initial_model=initial_model, locked_coordinates=locked,
            checkpoint_fn=checkpoint_fn,
            checkpoint_dir=ckpt_dir, resume=resume,
            max_quarantined=max_quarantined,
            checkpoint_async=args.checkpoint_async,
            checkpoint_max_staged_mb=args.checkpoint_max_staged_mb,
        )[0]
        results.append(result)
        if (args.checkpoint or args.save_all_models) and is_primary:
            save_game_model(
                os.path.join(args.output_dir, f"model_{config.name}"),
                result.model, index_maps, fmt=args.model_format,
                telemetry=session,
            )
        return result

    with maybe_profile(args.profile_dir):
        if args.tuning != "none":
            # Tune per-coordinate reg weights on the validation metric
            # (reference: hyperParameterTuning RANDOM|BAYESIAN, §3.5).
            if val_data is None:
                raise ValueError("--tuning needs validation data")
            from photon_tpu.hyperparameter import (
                GaussianProcessSearch,
                RandomSearch,
                SearchDimension,
                SearchSpace,
            )

            lo, hi = (float(x) for x in args.tuning_range.split(":"))
            # Locked coordinates keep their configured weight: their model is
            # frozen, so searching their dimension would be dead weight.
            space = SearchSpace([
                SearchDimension(name, lo, hi, log_scale=True)
                for name, _ in specs
                if name not in locked
            ])
            if not space.dimensions:
                raise ValueError(
                    "--tuning needs at least one unlocked coordinate"
                )
            primary = evaluators.primary

            def weight_for(name: str, kv: dict, params) -> float:
                if name in locked:
                    return float(str(kv.get("reg_weights", "1.0")).split("+")[0])
                return params[name]

            def evaluate(params):
                combo = [weight_for(name, kv, params) for name, kv in specs]
                config = GameOptimizationConfiguration(
                    coordinates={
                        name: _coord_config(kv, weight_for(name, kv, params), args.task)
                        for name, kv in specs
                    },
                    descent_iterations=args.descent_iterations,
                    name=_combo_label(specs, combo),
                )
                result = fit_config(config)
                return result.metrics[primary.name]

            search_cls = (
                GaussianProcessSearch if args.tuning == "bayesian" else RandomSearch
            )
            search_cls(
                space, evaluate, maximize=primary.maximize
            ).find(args.tuning_iterations)
        else:
            for label, coords, _ in _build_sweep(specs, args.task):
                fit_config(GameOptimizationConfiguration(
                    coordinates=coords,
                    descent_iterations=args.descent_iterations,
                    name=label,
                ))
    best = estimator.select_best(results)
    for name, value in best.metrics.items():
        session.gauge("train.best_metric", metric=name).set(value)
    if not is_primary:
        return {"rank": _jax.process_index(), "best": best.configuration.name}

    with logger.timed("save-model"):
        save_game_model(
            os.path.join(args.output_dir, "best_model"),
            best.model, index_maps, fmt=args.model_format, telemetry=session,
        )
    summary = {
        "task": args.task,
        "device": device,
        "best_configuration": best.configuration.name,
        "best_metrics": best.metrics,
        "sweep": [
            {
                "configuration": r.configuration.name,
                "metrics": r.metrics,
                "history": [
                    {"iteration": h["iteration"], "metrics": h["metrics"]}
                    for h in r.descent.history
                ],
            }
            for r in results
        ],
        "phase_times": logger.phase_times,
    }
    with open(os.path.join(args.output_dir, "training_summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    logger.info(
        "best configuration %s -> %s/best_model",
        best.configuration.name, args.output_dir,
    )
    return summary


def main(argv=None) -> None:
    # PreemptedError -> exit 75 (EX_TEMPFAIL): a preempted run is a clean,
    # resumable stop, not a crash.
    common.run_cli(run, build_parser().parse_args(argv))


if __name__ == "__main__":
    main()
