"""Single-model GLM training driver (the reference's legacy ``Driver``).

End-to-end: read data → optional normalization → regularization-weight sweep
→ validate each model → select best → write models + metrics
(SURVEY.md §3.2).  Runs the fixed-effect distributed path when more than one
device is visible (mesh + psum), single-device otherwise — same optimizer
code either way.

Usage:
    python -m photon_tpu.drivers.train \\
        --input a1a.libsvm --task logistic_regression \\
        --optimizer lbfgs --reg-type l2 --reg-weights 0.1,1,10 \\
        --validation-input a1a.t --evaluators AUC,LOGISTIC_LOSS \\
        --output-dir /tmp/model --backend tpu
"""

from __future__ import annotations

import argparse
import os
import time

import jax.numpy as jnp
import numpy as np

from photon_tpu.drivers import common


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "photon_tpu.drivers.train", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    common.add_common_args(p)
    common.add_distributed_args(p)
    common.add_data_args(p)
    p.add_argument("--task", default="logistic_regression",
                   choices=("logistic_regression", "linear_regression",
                            "poisson_regression", "smoothed_hinge_loss_linear_svm"))
    p.add_argument("--optimizer", default="lbfgs", choices=("lbfgs", "owlqn", "tron"))
    p.add_argument("--reg-type", default="l2",
                   choices=("none", "l1", "l2", "elastic_net"))
    p.add_argument("--reg-weights", default="1.0",
                   help="comma-separated sweep of regularization weights")
    p.add_argument("--elastic-net-alpha", type=float, default=0.5)
    p.add_argument("--max-iterations", type=int, default=100)
    p.add_argument("--tolerance", type=float, default=1e-7)
    p.add_argument("--dtype", default="float32",
                   choices=("float32", "bfloat16"),
                   help="storage dtype for FEATURE VALUES (labels, weights, "
                   "coefficients, and all arithmetic stay float32); "
                   "bfloat16 halves the value stream the sparse hot loop "
                   "reads from HBM")
    p.add_argument("--normalization", default="none",
                   choices=("none", "scale_with_standard_deviation",
                            "scale_with_max_magnitude", "standardization"))
    p.add_argument("--evaluators", default=None,
                   help="comma-separated evaluator names; default per task")
    p.add_argument("--variance-computation", default="none",
                   choices=("none", "simple", "full"))
    p.add_argument("--model-format", default="avro", choices=("avro", "json"))
    p.add_argument("--sweep-warm-start", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="start each regularization weight's fit from the "
                   "previous weight's solution (the regularization-path "
                   "trick; the reference's warm-start option). "
                   "--no-sweep-warm-start makes every lambda start cold")
    p.add_argument("--save-all-models", action="store_true",
                   help="write every sweep model, not just the best")
    p.add_argument("--stream", action="store_true",
                   help="host-streamed training for data beyond device "
                   "memory: --input is a glob/dir of LIBSVM files, each "
                   "re-streamed per objective evaluation (lbfgs only)")
    p.add_argument("--feature-dim", type=int, default=None,
                   help="with --stream: known feature dimension (e.g. from "
                   "a feature-indexing run) — skips the full metadata "
                   "parse in favor of a cheap row/nnz scan")
    p.add_argument("--checkpoint-dir", default=None,
                   help="preemption-safe sweep checkpoints under this "
                   "directory (one lam-NNN chain per sweep weight; rank 0 "
                   "writes).  With --stream: the full mid-fit L-BFGS loop "
                   "state every --checkpoint-every iterations.  Resident "
                   "path: one completed snapshot per finished lambda, so "
                   "a killed sweep resumes without re-fitting finished "
                   "weights")
    p.add_argument("--checkpoint-every", type=int, default=1,
                   help="with --stream + --checkpoint-dir: snapshot every "
                   "N L-BFGS iterations (each iteration is >= one full "
                   "streamed pass, so the default checkpoints every "
                   "iteration).  The resident path checkpoints per "
                   "completed lambda and ignores this")
    p.add_argument("--checkpoint-async", default=None, choices=("on", "off"),
                   help="publish checkpoints from a background thread "
                   "(default on, or PHOTON_CHECKPOINT_ASYNC); 'off' "
                   "restores inline synchronous writes")
    p.add_argument("--checkpoint-max-staged-mb", type=float, default=None,
                   help="cap the async publisher's staged host copies: a "
                   "snapshot over this many MB publishes blocking instead "
                   "of holding a second snapshot-sized host allocation "
                   "(PHOTON_CHECKPOINT_MAX_STAGED_MB; default unbounded)")
    p.add_argument("--resume", default=None, choices=("auto", "latest"),
                   help="with --checkpoint-dir: restore the sweep from its "
                   "checkpoints — completed weights are rebuilt from their "
                   "final snapshots without re-fitting (streamed: without "
                   "streaming a pass; the interrupted streamed weight "
                   "continues mid-fit); 'latest' requires a published "
                   "checkpoint, 'auto' starts fresh when there is none")
    return p


def _run_streaming(args: argparse.Namespace, logger, session,
                   device: dict) -> dict:
    """Host-streamed lambda sweep (data beyond device memory; lbfgs)."""
    import glob as globmod

    import jax

    from photon_tpu.core.losses import BINARY_TASKS
    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.core.optimizers import OptimizationStatesTracker, OptimizerConfig
    from photon_tpu.data.index_map import IndexMap, feature_key
    from photon_tpu.data.streaming import (
        LibsvmFileSource,
        StreamingObjective,
        shard_files_for_process,
        streaming_lbfgs,
    )
    from photon_tpu.evaluation.evaluators import (
        MultiEvaluator,
        default_evaluators_for_task,
    )
    from photon_tpu.models.glm import Coefficients, model_for_task

    os.makedirs(args.output_dir, exist_ok=True)
    if args.normalization != "none":
        raise ValueError("--stream does not support --normalization")
    if getattr(args, "dtype", "float32") != "float32":
        raise ValueError("--stream does not support --dtype yet")
    if args.optimizer != "lbfgs" or args.reg_type in ("l1", "elastic_net"):
        raise ValueError("--stream supports the lbfgs optimizer with l2/none "
                         "regularization")
    from photon_tpu.fault.checkpoint import StreamCheckpointer

    if os.path.isdir(args.input):
        files = sorted(
            os.path.join(args.input, f) for f in os.listdir(args.input)
            if not f.startswith((".", "_"))
        )
    else:
        files = sorted(globmod.glob(args.input)) or [args.input]
    with logger.timed("scan-metadata"):
        # Metadata over the GLOBAL list (all hosts must agree on dim);
        # each process then streams only its file shard.
        source = LibsvmFileSource(
            files, intercept=args.intercept,
            binary_labels=args.task in BINARY_TASKS,
            feature_dim=args.feature_dim,
            telemetry=session,  # io.retries from retried part reads
        ).with_files(shard_files_for_process(files))
    logger.info(
        "streaming %d of %d files, %d rows total, dim %d, nnz capacity %d",
        len(source.files), len(files), source.num_examples, source.dim,
        source.capacity,
    )
    # Multi-process: all ranks record metrics, only rank 0 writes artifacts.
    session.write = jax.process_index() == 0
    session.gauge("train.num_examples").set(source.num_examples)
    session.gauge("train.num_features").set(source.dim)
    session.gauge("train.stream_files").set(len(source.files))
    if args.data_validation != "off":
        # Streamed data must get the same validation as resident data
        # (ADVICE r1: the streaming path skipped it entirely): one extra
        # host pass over this process's chunks before training starts.
        from photon_tpu.data.libsvm import normalize_binary_labels, parse_libsvm
        from photon_tpu.data.validation import (
            DataValidationError,
            _feature_issues,
            apply_validation,
            validate_columns,
        )

        with logger.timed("validate-data"):
            # Host-side pass over the raw parses: no device round-trip for
            # data that is streamed precisely because it is large.  Files
            # validate on the host-IO pool; issues keep file order.  Each
            # in-progress file holds a full parse transiently, so cap the
            # concurrency below the general IO width.
            from photon_tpu.utils.io_pool import io_threads, map_ordered

            def _file_issues(fpath):
                from photon_tpu.data.libsvm import parse_csr_or_none

                csr = parse_csr_or_none(fpath)
                if csr is not None:  # flat values, no per-row views
                    labels, _, _, allv, _ = csr
                else:
                    data = parse_libsvm(fpath)
                    labels = data.labels
                    allv = (
                        np.concatenate([v for _, v in data.rows])
                        if data.rows else np.zeros(0, np.float32)
                    )
                if args.task in BINARY_TASKS:
                    labels = normalize_binary_labels(labels)
                out = list(validate_columns(labels, None, None, args.task))
                if allv.size:
                    out.extend(
                        _feature_issues(
                            allv.reshape(-1, 1), os.path.basename(fpath)
                        )
                    )
                return out

            issues = []
            for file_issues in map_ordered(
                _file_issues, source.files, workers=min(io_threads(), 4)
            ):
                issues.extend(file_issues)
            if jax.process_count() > 1:
                # Agreement step: every process must reach the same
                # pass/fail decision, else a bad shard on one host would
                # leave the clean hosts hanging in the first collective.
                from jax.experimental import multihost_utils

                import numpy as _np

                totals = multihost_utils.process_allgather(
                    _np.asarray([len(issues)], _np.int32)
                )
                remote = int(_np.sum(totals)) - len(issues)
                if remote > 0 and args.data_validation == "error":
                    raise DataValidationError(
                        f"data validation failed on another process "
                        f"({remote} issues elsewhere; local: {len(issues)})"
                    )
            apply_validation(issues, args.data_validation, logger)

    val_batch = common.load_validation(
        args.validation_input, source.dim, args.intercept, args.task
    )
    if args.evaluators:
        evaluators = common.build_flat_evaluators(args.evaluators, "training")
    else:
        evaluators = MultiEvaluator(default_evaluators_for_task(args.task))

    opt_config = OptimizerConfig(
        max_iterations=args.max_iterations, tolerance=args.tolerance
    )
    # Multi-process runs: each host streams its file shard; gradients sum
    # across hosts so every process optimizes the GLOBAL objective.
    all_reduce = None
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        def all_reduce(x):
            return multihost_utils.process_allgather(x).sum(axis=0)

    sweep = []
    w_start = jnp.zeros(source.dim, jnp.float32)
    for i, lam in enumerate(common.parse_weights_list(args.reg_weights)):
        reg = RegularizationContext(args.reg_type, lam, args.elastic_net_alpha)
        objective = StreamingObjective(
            GlmObjective.create(args.task, reg), source.chunk_iter_factory,
            all_reduce=all_reduce,
        )
        # Mid-fit checkpointing: one chain per sweep weight, published
        # through the shared (async-capable) checkpoint publisher.  The
        # fingerprint pins what makes a snapshot THIS fit's state — the
        # iteration budget is deliberately excluded (resuming with more
        # iterations continues the fit, same rule as descent checkpoints).
        checkpointer = resume_state = None
        fingerprint = {
            "kind": StreamCheckpointer.KIND,
            "task": args.task,
            "reg_type": args.reg_type,
            "lambda": lam,
            "alpha": args.elastic_net_alpha,
            "dim": int(source.dim),
            "num_examples": int(source.num_examples),
            "intercept": bool(args.intercept),
            "warm_start": bool(args.sweep_warm_start),
            # Optimizer state-shape/semantics: the snapshot's S/Y/rho ring
            # buffers are sized by history_length, and tolerance changes
            # what "converged" means — a resume across either must refuse
            # loudly, not continue with mismatched curvature state.
            "history_length": int(opt_config.history_length),
            "tolerance": float(opt_config.tolerance),
        }
        if args.checkpoint_dir:
            checkpointer = StreamCheckpointer(
                os.path.join(args.checkpoint_dir, f"lam-{i:03d}"),
                telemetry=session, logger=logger,
                async_publish=args.checkpoint_async,
                max_staged_mb=args.checkpoint_max_staged_mb,
            )
            if args.resume:
                # Per-weight resume is auto-style: weights the interrupted
                # run never reached have no chain and start fresh (the
                # 'latest' strictness was enforced up front).
                from photon_tpu.fault.checkpoint import require_fingerprint

                resume_state = require_fingerprint(
                    checkpointer.load("auto"), fingerprint,
                    f"lambda={lam:g}",
                )
        with logger.timed(f"train-lambda-{lam}", span=False), \
                session.span("train.lambda", reg_weight=float(lam)):
            t0 = time.monotonic()
            result = streaming_lbfgs(
                objective, w_start, opt_config,
                checkpointer=checkpointer,
                checkpoint_every=max(1, args.checkpoint_every),
                resume_state=resume_state,
                fingerprint=fingerprint,
            )
            jax.block_until_ready(result.w)
            wall = time.monotonic() - t0
        if args.sweep_warm_start:
            w_start = result.w
        tracker = OptimizationStatesTracker(result, wall)
        tracker.record_to(session.registry, optimizer="lbfgs", lam=f"{lam:g}")
        logger.info("lambda=%g %s", lam, tracker.summary().splitlines()[0])
        model = model_for_task(args.task, Coefficients(result.w))
        metrics = {}
        if val_batch is not None:
            scores = common.scores_on(val_batch, model)
            metrics = evaluators.evaluate(
                scores, np.asarray(val_batch.label), np.asarray(val_batch.weight)
            )
            logger.info("lambda=%g validation %s", lam, metrics)
        sweep.append({
            "lambda": lam, "model": model, "metrics": metrics,
            "iterations": tracker.iterations,
            "convergence_reason": tracker.convergence_reason,
            "wall_time_s": wall, "final_value": float(result.value),
            "states": tracker.states(),
        })

    index_map = IndexMap.build(
        [feature_key(f"f{i}") for i in range(source.feature_dim)],
        intercept=args.intercept,
    )
    if jax.process_index() != 0:
        # Every host trained the same global model; only rank 0 writes.
        return {"streaming": True, "rank": jax.process_index()}
    return common.select_and_save_sweep(
        sweep, evaluators, val_batch is not None, index_map, args, logger,
        extra_summary={"optimizer": "lbfgs", "streaming": True,
                       "device": device},
        telemetry=session,
    )


def run(args: argparse.Namespace) -> dict:
    distributed = common.maybe_init_distributed(args)
    device = common.select_backend(args.backend)
    from photon_tpu.utils import PhotonLogger

    logger = PhotonLogger("photon_tpu.train", args.log_file)
    with common.telemetry_run(
        args, "train", logger, preemptible=True
    ) as session:
        # Shared --resume strictness of BOTH data paths ('latest' means a
        # PUBLISHED checkpoint, not .tmp debris) — validated before any
        # data work.
        if args.resume and not args.checkpoint_dir:
            raise ValueError("--resume needs --checkpoint-dir")
        if args.resume == "latest":
            from photon_tpu.fault.checkpoint import has_published_checkpoint

            if not has_published_checkpoint(args.checkpoint_dir):
                raise ValueError(
                    f"--resume latest: no published checkpoint under "
                    f"{args.checkpoint_dir!r}"
                )
        if getattr(args, "stream", False):
            return _run_streaming(args, logger, session, device)
        if distributed:
            # The resident-data path has no work to split across processes —
            # every rank would redundantly load the full dataset and race on
            # the output files.  Multi-process GLM training is the streaming
            # path's job (per-process file shards + cross-process gradient
            # sum).
            raise ValueError(
                "--coordinator requires --stream for this driver (the "
                "resident-data path is single-process; use --stream for "
                "multi-process)"
            )
        return _run_resident(args, logger, session, device)


def _run_resident(args: argparse.Namespace, logger, session,
                  device: dict) -> dict:
    """Device-resident lambda sweep (the default path)."""
    # Imports after backend pinning (device init happens on first jax use).
    import jax

    from photon_tpu.core.normalization import NormalizationContext
    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.core.optimizers import OptimizationStatesTracker, OptimizerConfig
    from photon_tpu.core.problem import GlmOptimizationProblem, ProblemConfig
    from photon_tpu.core.stats import BasicStatisticalSummary
    from photon_tpu.evaluation.evaluators import (
        MultiEvaluator,
        default_evaluators_for_task,
    )
    from photon_tpu.models.glm import Coefficients, model_for_task
    from photon_tpu.parallel import DistributedGlmObjective, shard_batch
    from photon_tpu.utils.logging import maybe_profile

    os.makedirs(args.output_dir, exist_ok=True)

    with logger.timed("load-data"):
        batch, dim, index_map = common.load_dataset(
            args.input, args.intercept, args.task,
            avro_field=args.avro_feature_field,
        )
        val_batch = common.load_validation(
            args.validation_input, dim, args.intercept, args.task,
            avro_field=args.avro_feature_field, index_map=index_map,
        )
        logger.info("train: %d examples, %d features", batch.num_examples, dim)
        # Logical row count, captured BEFORE any mesh padding below: the
        # resident checkpoint fingerprint must be mesh-shape independent.
        n_examples = batch.num_examples
        session.gauge("train.num_examples").set(batch.num_examples)
        session.gauge("train.num_features").set(dim)

    if args.data_validation != "off":
        from photon_tpu.data.validation import apply_validation, validate_batch

        apply_validation(
            validate_batch(batch, args.task), args.data_validation, logger
        )

    norm = None
    if args.normalization != "none":
        with logger.timed("summarize"):
            summary = BasicStatisticalSummary.from_batch(batch, dim)
            norm = NormalizationContext.build(
                args.normalization, summary, intercept_id=index_map.intercept_id
            )

    mesh = common.maybe_mesh()
    if mesh is not None:
        logger.info("mesh: %d devices on axis 'data'", mesh.devices.size)
        # Attaches the per-shard feature-major layout — and the per-shard
        # aligned layouts when the kernel selector could route to them
        # (decided inside attach_feature_major), so the fast kernels run
        # under the sharded objective too.
        batch = shard_batch(batch, mesh, aligned_dim=dim)
    else:
        from photon_tpu.data.batch import SparseBatch, attach_feature_major

        if isinstance(batch, SparseBatch) and batch.ids.ndim == 2:
            # Single-device: the attach takes the kernel selector's verdict
            # (the pin, the probe floor, else the probe, run there and
            # cached for the trace) and builds the layout the winning
            # kernel reads; without a measurement it builds the pre-sorted
            # layout, and a pinned kernel's beside it.
            batch = attach_feature_major(batch, aligned_dim=dim)

    if args.dtype != "float32":
        from photon_tpu.data.batch import batch_astype

        # After normalization stats (summaries use full-precision values)
        # and after the feature-major attach (astype converts its vals too).
        batch = batch_astype(batch, args.dtype)
        logger.info("feature values stored as %s (f32 arithmetic)", args.dtype)

    if args.evaluators:
        evaluators = common.build_flat_evaluators(args.evaluators, "training")
    else:
        evaluators = MultiEvaluator(default_evaluators_for_task(args.task))

    lambdas = common.parse_weights_list(args.reg_weights)
    opt_config = OptimizerConfig(
        max_iterations=args.max_iterations, tolerance=args.tolerance
    )
    optimizer = args.optimizer
    if args.reg_type in ("l1", "elastic_net") and optimizer != "owlqn":
        logger.warning("reg-type %s requires owlqn; switching optimizer", args.reg_type)
        optimizer = "owlqn"

    # Minimal resident checkpoint/resume (ROADMAP known edge): one
    # COMPLETED snapshot per finished lambda, in the StreamCheckpointer's
    # state shape — a killed sweep resumes by rebuilding finished weights
    # from their snapshots instead of re-fitting them.  (Mid-fit
    # granularity stays a --stream feature: a resident fit is one jitted
    # optimizer run with no interior host loop to snapshot.)
    from photon_tpu.core.optimizers.base import OptimizerResult
    from photon_tpu.fault.checkpoint import (
        StreamCheckpointer,
        StreamState,
        require_fingerprint,
    )
    from photon_tpu.fault.preemption import (
        PreemptedError,
        preemption_requested,
        preemption_reason,
    )

    sweep = []
    w_start = jnp.zeros(dim, jnp.float32)
    for i, lam in enumerate(lambdas):
        # The resident path's preemption boundary: between lambdas (each
        # lambda is one jitted solve with no interior host loop).  Every
        # finished lambda is already checkpointed, so stopping here loses
        # nothing resumable.
        if preemption_requested():
            hint = (
                "resume with --resume auto" if args.checkpoint_dir
                else "no --checkpoint-dir — a restart begins from scratch"
            )
            raise PreemptedError(
                f"preempted ({preemption_reason()}) before lambda={lam:g}; "
                f"{hint}"
            )
        reg = RegularizationContext(args.reg_type, lam, args.elastic_net_alpha)
        # What makes a snapshot THIS lambda's completed fit.  Unlike the
        # streamed fingerprint, max_iterations IS pinned: only the final
        # state is snapshotted, so a raised budget cannot continue a
        # completed resident fit — it must refuse and re-fit.
        fingerprint = {
            "kind": StreamCheckpointer.KIND,
            "path": "resident",
            "task": args.task,
            "optimizer": optimizer,
            "reg_type": args.reg_type,
            "lambda": lam,
            "alpha": args.elastic_net_alpha,
            "dim": int(dim),
            "num_examples": int(n_examples),
            "intercept": bool(args.intercept),
            "normalization": args.normalization,
            "dtype": args.dtype,
            "variance": args.variance_computation,
            "warm_start": bool(args.sweep_warm_start),
            "max_iterations": int(opt_config.max_iterations),
            "tolerance": float(opt_config.tolerance),
        }
        checkpointer = resume_state = None
        if args.checkpoint_dir:
            checkpointer = StreamCheckpointer(
                os.path.join(args.checkpoint_dir, f"lam-{i:03d}"),
                telemetry=session, logger=logger,
                async_publish=args.checkpoint_async,
                max_staged_mb=args.checkpoint_max_staged_mb,
            )
            if args.resume:
                resume_state = require_fingerprint(
                    checkpointer.load("auto"), fingerprint,
                    f"lambda={lam:g}",
                )
        if resume_state is not None and resume_state.completed:
            # Finished weight: rebuild model + convergence record from the
            # snapshot, zero solves.  The solver-space iterate (w_opt)
            # restores the warm-start chain exactly, so later un-resumed
            # lambdas fit from the same start the uninterrupted sweep used.
            arrays_ = resume_state.arrays
            result = OptimizerResult(
                w=jnp.asarray(arrays_["w_opt"]),
                value=jnp.asarray(float(resume_state.scalars["f"])),
                grad_norm=jnp.asarray(float(resume_state.scalars["gnorm"])),
                iterations=jnp.asarray(resume_state.iteration, jnp.int32),
                converged=jnp.asarray(
                    bool(resume_state.scalars.get("converged", False))
                ),
                reason=jnp.asarray(int(resume_state.reason), jnp.int32),
                history_value=jnp.asarray(arrays_["hv"]),
                history_grad_norm=jnp.asarray(arrays_["hg"]),
                history_valid=jnp.asarray(arrays_["hvalid"]),
            )
            wall = 0.0
            means = jnp.asarray(arrays_["means"])
            variances = (
                jnp.asarray(arrays_["variances"])
                if "variances" in arrays_ else None
            )
            if args.sweep_warm_start:
                w_start = result.w
            session.counter("train.lambdas_resumed").inc()
            logger.info(
                "lambda=%g restored from completed checkpoint (no refit)",
                lam,
            )
        else:
            obj = GlmObjective.create(args.task, reg, normalization=norm)
            objective = (
                obj if mesh is None else DistributedGlmObjective(obj, mesh)
            )
            problem = GlmOptimizationProblem(
                objective,
                ProblemConfig(
                    optimizer=optimizer,
                    regularization=reg,
                    optimizer_config=opt_config,
                    variance_computation=args.variance_computation,
                ),
            )
            with logger.timed(f"train-lambda-{lam}", span=False), \
                    maybe_profile(args.profile_dir), \
                    session.span("train.lambda", reg_weight=float(lam)):
                t0 = time.monotonic()
                coefficients, result = problem.run(batch, w_start)
                jax.block_until_ready(coefficients.means)
                wall = time.monotonic() - t0
            if args.sweep_warm_start:
                # Next lambda starts from this optimum (normalized space —
                # the original-space conversion below works on copies).
                w_start = coefficients.means
            # Store the model in the original feature space (variances too
            # — mixing original-space means with normalized-space variances
            # would mis-scale the GLMix posterior by factor^2/coordinate).
            means = coefficients.means
            variances = coefficients.variances
            if norm is not None:
                means = norm.model_to_original_space(means)
                variances = norm.variances_to_original_space(variances)
            if checkpointer is not None:
                arrays_ = {
                    # Solver-space iterate (the warm-start chain) AND the
                    # original-space model are both snapshotted; history
                    # buffers make the convergence trace restorable.
                    "w_opt": coefficients.means,
                    "means": means,
                    "hv": result.history_value,
                    "hg": result.history_grad_norm,
                    "hvalid": result.history_valid,
                }
                if variances is not None:
                    arrays_["variances"] = variances
                checkpointer.save(StreamState(
                    iteration=int(result.iterations),
                    arrays=arrays_,
                    scalars={
                        "f": float(result.value),
                        "gnorm": float(result.grad_norm),
                        "converged": bool(result.converged),
                    },
                    completed=True,
                    reason=int(result.reason),
                    fingerprint=fingerprint,
                ))
                checkpointer.drain()
        tracker = OptimizationStatesTracker(result, wall)
        tracker.record_to(session.registry, optimizer=optimizer, lam=f"{lam:g}")
        logger.info("lambda=%g %s", lam, tracker.summary().splitlines()[0])
        model = model_for_task(args.task, Coefficients(means, variances))

        metrics = {}
        if val_batch is not None:
            scores = common.scores_on(val_batch, model)
            metrics = evaluators.evaluate(
                scores, np.asarray(val_batch.label), np.asarray(val_batch.weight)
            )
            logger.info("lambda=%g validation %s", lam, metrics)
        sweep.append(
            {
                "lambda": lam,
                "model": model,
                "metrics": metrics,
                "iterations": tracker.iterations,
                "convergence_reason": tracker.convergence_reason,
                "wall_time_s": wall,
                "final_value": float(result.value),
                "states": tracker.states(),
            }
        )

    return common.select_and_save_sweep(
        sweep, evaluators, val_batch is not None, index_map, args, logger,
        extra_summary={"optimizer": optimizer, "device": device},
        telemetry=session,
    )


def main(argv=None) -> None:
    # PreemptedError -> exit 75 (EX_TEMPFAIL): a preempted run is a clean,
    # resumable stop, not a crash.
    common.run_cli(run, build_parser().parse_args(argv))


if __name__ == "__main__":
    main()
