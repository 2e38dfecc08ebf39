"""Compiles for the described v5e, off the chip (no device needed, nothing
runs): what only the TPU compiler decides.  One file, so that one test
worker loads the TPU library; the topology is described inside a fixture.

ISSUE 39: the lane form of an entity solve's dense products is a LAYOUT,
and the layout is the compiler's choice.  It lays a bin's features out with
their rows on the lanes whatever the logical order, and with the entities
on the lanes only when their count needs no padding, which is why
``batched_solve._entity_solve_newton`` pads each device's entities to a
multiple of ``newton.LANES`` where ``newton.reduction_kind`` says ``lanes``
(a bin under 128 rows an entity).  These tests hold the compiled program
to that: inside the solver's loops every features-sized array is
entity-minor and none is copied or transposed.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ENTITIES, ROWS, DIM = 13124, 32, 16  # game_fit's 32-row bin
PADDED = 13184


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture()
def no_compile_cache():
    """An executable compiled without a chip cannot be read back from the
    persistent cache: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _compiled_solver(sharding, entities, **kwargs) -> str:
    from photon_tpu.core.objective import GlmObjective, RegularizationContext
    from photon_tpu.core.optimizers import OptimizerConfig
    from photon_tpu.core.problem import ProblemConfig
    from photon_tpu.data.batch import DenseBatch
    from photon_tpu.game.batched_solve import cached_newton_solver

    def shape(*s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding(len(s)))

    reg = RegularizationContext("l2", 1.0)
    problem = ProblemConfig(
        regularization=reg,
        optimizer_config=OptimizerConfig(max_iterations=15, tolerance=1e-6),
    )
    batch = DenseBatch(
        shape(entities, ROWS, DIM), shape(entities, ROWS),
        shape(entities, ROWS), shape(entities, ROWS),
    )
    return cached_newton_solver(problem).lower(
        GlmObjective.create("logistic_regression", reg), batch,
        shape(entities, DIM), **kwargs,
    ).compile().as_text()


def _loop_bodies(text: str) -> list:
    computations = re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text)
    bodies = set(re.findall(r"body=(%[\w.\-]+)", text))
    return [c for c in computations if c.split(" ", 1)[0] in bodies]


def _assert_loops_are_entity_minor(text: str) -> None:
    bodies = _loop_bodies(text)
    assert len(bodies) == 3  # Newton's loop, its line search's, the polish
    slab = re.compile(
        r"= \(?f32\[(?:%d,%d|%d,%d),%d\]\{([\d,]+)" % (
            ROWS, DIM, DIM, DIM, PADDED))
    seen = 0
    for body in bodies:
        for line in body.splitlines():
            if not re.search(r" (fusion|copy|transpose)\(", line):
                continue
            m = slab.search(line)
            if m is None:
                continue
            seen += 1
            assert m.group(1) == "2,1,0", line[:200]
            assert " fusion(" in line, line[:200]  # no copy, no transpose
        assert not re.search(
            r"f32\[%d,%d,%d\]\S* (copy|transpose)\(" % (PADDED, ROWS, DIM),
            body)
    assert seen >= 2  # x * d2w, in the loop and in the polish


def test_entity_solve_loops_are_entity_minor_on_one_chip(topo, no_compile_cache):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    text = _compiled_solver(lambda ndim: one_chip, ENTITIES)
    _assert_loops_are_entity_minor(text)


def test_entity_solve_on_four_chips_moves_no_features(topo, no_compile_cache):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(topo.devices), ("data",))
    text = _compiled_solver(
        lambda ndim: NamedSharding(mesh, P("data", *([None] * (ndim - 1)))),
        4 * ENTITIES, entity_shards=4,
    )
    _assert_loops_are_entity_minor(text)
    for collective in ("all-gather", "all-to-all", "collective-permute",
                       "reduce-scatter"):
        assert f" {collective}(" not in text, collective
    # What crosses chips: the lockstep loops' "any lane active?" scalars.
    reduced = set(re.findall(r"= (\w+)\[\]\S* all-reduce\(", text))
    assert reduced and reduced <= {"pred", "u32", "s32"}, reduced
