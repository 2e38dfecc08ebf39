"""``correct`` comes out false when it should: the rest of a run is driven
(no look for a chip) with the timed path broken underneath, and the control
(the reference in bfloat16 in the program's place) fails a number.  Tiny
sizes on the CPU; the readings at the cells' own sizes are in PERF.md."""

import dataclasses

import numpy as np
import pytest

from benchmarks import run as harness

SEED = 2 ** 31 + 23


def _half(n):
    return np.where(np.arange(n) % 2 == 0, 2.0, 0.0).astype(np.float32)


def glm_state_unchanged(runner, state):
    """The step returns its state unchanged: w stays where it started."""
    real = state.problem.run

    def run(batch, w0):
        coefficients, result = real(batch, w0)
        return coefficients._replace(means=w0) if hasattr(
            coefficients, "_replace"
        ) else dataclasses.replace(coefficients, means=w0), result

    state.problem.run = run


def glm_half_batch(runner, state):
    """Every other row left out, the rest weighted double."""
    import jax.numpy as jnp

    state.batch = state.batch._replace(
        weight=jnp.asarray(_half(state.data.rows))
    )


def game_state_unchanged(runner, state):
    """The fit returns the model it started from: every table zero."""
    import jax.numpy as jnp

    real = state.estimator.fit

    def fit(*args, **kwargs):
        results = real(*args, **kwargs)
        model = results[0].descent.last_model
        for name, coord in list(model.coordinates.items()):
            if hasattr(coord, "table"):
                model.coordinates[name] = dataclasses.replace(
                    coord, table=jnp.zeros_like(coord.table)
                )
        return results

    state.estimator.fit = fit


def game_half_batch(runner, state):
    from photon_tpu.game.estimator import GameEstimator

    old = state.estimator
    train = dataclasses.replace(
        old.training_data, weight=_half(old.training_data.num_examples)
    )
    state.estimator = GameEstimator(
        old.task_type, train, validation_data=old.validation_data,
        evaluators=old.evaluators, mesh=None, telemetry=state.session,
    )


CASES = [
    ("glm_sparse_fit", None, True),
    ("glm_sparse_fit", glm_state_unchanged, False),
    ("glm_sparse_fit", glm_half_batch, False),
    ("game_fit", None, True),
    ("game_fit", game_state_unchanged, False),
    ("game_fit", game_half_batch, False),
]


@pytest.mark.parametrize(
    "cell,fault,expected", CASES,
    ids=[f"{c}-{f.__name__ if f else 'sound'}" for c, f, _ in CASES],
)
def test_a_broken_timed_path_is_not_correct(cell, fault, expected):
    result = harness.run_cell(cell, SEED, 0.3, False, rehearsal=True,
                              hook=fault)
    assert result["correct"] is expected, result["compared"]
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("cell", ["glm_sparse_fit", "game_fit"])
def test_the_control_fails_a_number(cell):
    spec = harness.load_cell(cell)
    config = dict(spec["config"], sizes=dict(
        spec["config"]["sizes"], **spec["config"]["rehearsal_sizes"]))
    runner = harness.load_module(spec["runner_dir"], spec["traffic"]["runner"])
    state = runner.setup(config, spec["traffic"], SEED, harness.Clock())
    runner.release(state)
    want = runner.reference(state)
    control = runner.compare(runner.reference(state, lowp=True), want)
    limits = spec["traffic"]["limits"]
    assert any(control[k] > limits[k] for k in limits), (control, limits)
    sound = runner.compare(want, runner.reference(state))
    assert all(sound[k] <= limits[k] for k in limits)
