"""Back-to-back whole GAME fits on one estimator whose fixed effect is a
sparse shard.

``game_fit`` with one difference: the ``global`` shard is handed to the
program as ``SparseShard(ids, vals, dim)`` (``fixed_nnz_per_row`` entries a
row of a ``fixed_dim``-wide feature space), as ``linkedin/photon-ml`` reads
every feature shard, so the fixed effect's fits run the sparse
value+gradient kernel the program selects (``PHOTON_SPARSE_GRAD`` unset:
``auto``) inside coordinate descent, with the other coordinates' scores as
its offsets, and its scores over the training and the validation rows are
taken from the sparse entries.  The estimator, its coordinate
configurations and the fitted configuration are built as
``photon_tpu.drivers.train_game._run`` builds them; onboarding (the kernel
verdict and its probe, the winner's layout, entity binning, h2d) happens
inside set-up's ``clock("layout")``.

What holds of ``game_fit`` is taken from it (``produced``, ``release``,
``compare``, ``counters``).  What differs:

* the data: ``benchmarks/generate_game_sparse.py``;
* ``step`` also takes the fixed coordinate's ``optimizer.evaluations`` and
  ``score.sparse_entries`` of the fit (host counters of the session, read
  after the fit's ``block_until_ready``; a program that does not publish the
  second, the parent of the PR that added it, reads 0 and ``work`` leaves
  it out);
* ``work`` / ``floor``: ``benchmarks/rooflines_game_sparse.py``;
* the reference: ``benchmarks/reference/game_sparse.py``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.runners import game_fit as dense

State = dense.State
produced = dense.produced
release = dense.release
compare = dense.compare
counters = dense.counters


def setup(config: dict, traffic: dict, seed: int, clock) -> State:
    import jax

    from benchmarks import generate_game_sparse
    from photon_tpu.drivers import train_game
    from photon_tpu.evaluation.evaluators import (
        MultiEvaluator,
        default_evaluators_for_task,
    )
    from photon_tpu.game.data import DenseShard, GameDataset, SparseShard
    from photon_tpu.game.estimator import (
        GameEstimator,
        GameOptimizationConfiguration,
    )
    from photon_tpu.telemetry import TelemetrySession

    state = State()
    with clock("data"):
        data = generate_game_sparse.game_sparse(config["sizes"], seed)
    state.data = data
    fit = traffic["fit"]
    task = config["task"]
    with clock("layout"):
        def dataset(split):
            shards = {"global": SparseShard(
                split.ids_fixed, split.vals_fixed, data.fixed_dim)}
            for name in data.coordinates:
                shards[name] = DenseShard(split.x_random[name])
            return GameDataset.create(
                split.label, shards, id_columns=dict(split.entity_ids)
            )

        state.session = TelemetrySession("benchmarks.game_sparse_fit")
        state.estimator = GameEstimator(
            task, dataset(data.train),
            validation_data=dataset(data.validation),
            evaluators=MultiEvaluator(default_evaluators_for_task(task)),
            mesh=None, telemetry=state.session,
        )
        specs = [train_game.parse_coordinate_spec(s)
                 for s in fit["coordinates"]]
        ((label, coords, _),) = train_game._build_sweep(specs, task)
        state.configuration = GameOptimizationConfiguration(
            coordinates=coords,
            descent_iterations=int(fit["descent_iterations"]), name=label,
        )
        # Onboarding on first use: the kernel verdict and the winner's
        # layout (fixed effect), entity binning (random effects), h2d.
        for coord_config in coords.values():
            layout = state.estimator.device_layout(coord_config)
            jax.block_until_ready(getattr(layout, "batch", None))
    state.fit = fit
    state.max_quarantined = int(fit["max_quarantined"])
    state.fixed_dim = data.fixed_dim
    state.fixed_nnz = data.train.ids_fixed.shape[1]
    state.random_dim = next(iter(data.train.x_random.values())).shape[1]
    state.last = None
    return state


def _sparse_counts(state: State) -> dict:
    """Running totals of the session's counters the sparse fixed effect
    moves (host counters, as ``game_fit._fit_counts`` reads its own)."""
    session = state.session
    return {
        "fixed_evaluations": session.counter(
            "optimizer.evaluations", coordinate="fixed").value,
        "sparse_entries": session.counter(
            "score.sparse_entries", coordinate="fixed").value,
    }


def step(state: State) -> dict:
    before = _sparse_counts(state)
    counts = dense.step(state)
    for key, value in _sparse_counts(state).items():
        counts[key] = value - before[key]
    return counts


def work(state: State, steps: list) -> dict:
    out = dense.work(state, steps)
    out["fixed_nnz"] = state.fixed_nnz
    for key in ("fixed_evaluations", "sparse_entries"):
        mean = float(np.mean([s[key] for s in steps]))
        if mean:  # 0: the program does not publish the counter
            out[key] = mean
    return out


def floor(state: State, steps: list, peak: dict) -> dict:
    from benchmarks import rooflines_game_sparse

    return rooflines_game_sparse.game_sparse_fit_floor(
        work(state, steps), peak)


def reference(state: State, lowp: bool = False, weight=None) -> dict:
    from benchmarks.reference import game_sparse

    fit = state.fit
    return game_sparse.fit(state.data, {
        "l2": float(fit["reg_weight"]),
        "descent_iterations": int(fit["descent_iterations"]),
        "fixed_max_iterations": int(fit["fixed_max_iterations"]),
        "tolerance": float(fit["tolerance"]),
        "gradient_tolerance": float(fit["gradient_tolerance"]),
    }, lowp=lowp, weight=weight)


def check(state: State) -> dict:
    got = produced(state)
    release(state)
    return compare(got, reference(state))
