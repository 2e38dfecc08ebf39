"""ops/sparse_grad_select.py owns the sparse-kernel decision: the selection
table over every set of layouts a batch can carry, the refusal of a pin that
names no kernel, the one tuple of layout fields every batch transformation
iterates, and a guard that no other module takes the decision back."""

import ast
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import photon_tpu.ops.sparse_grad_select as sel
from photon_tpu.core.objective import GlmObjective, RegularizationContext
from photon_tpu.data.batch import (
    LAYOUT_FIELDS,
    SparseBatch,
    attach_feature_major,
    batch_astype,
    pad_batch,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, K, D = 96, 4, 40


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    return SparseBatch(
        ids=jnp.asarray(rng.integers(0, D, size=(N, K), dtype=np.int32)),
        vals=jnp.asarray(rng.standard_normal((N, K)).astype(np.float32)),
        label=jnp.asarray((rng.random(N) < 0.5).astype(np.float32)),
        offset=jnp.asarray(rng.standard_normal(N).astype(np.float32) * 0.1),
        weight=jnp.asarray(rng.uniform(0.5, 2.0, N).astype(np.float32)),
    )


# -- (a) the selection table ---------------------------------------------------

MODES = ("autodiff", "fm", "pallas", "blocked", "auto")  # auto: under the floor
SELECTION = {
    # layouts carried: the kernel each mode runs
    (): ("autodiff", "autodiff", "autodiff", "autodiff", "autodiff"),
    ("fm",): ("autodiff", "fm", "fm", "fm", "autodiff"),
    ("al",): ("autodiff", "autodiff", "pallas", "pallas", "autodiff"),
    ("bt",): ("autodiff", "autodiff", "autodiff", "blocked", "autodiff"),
    ("fm", "al"): ("autodiff", "fm", "pallas", "pallas", "autodiff"),
    ("fm", "bt"): ("autodiff", "fm", "fm", "blocked", "autodiff"),
    ("al", "bt"): ("autodiff", "autodiff", "pallas", "blocked", "autodiff"),
    ("fm", "al", "bt"): ("autodiff", "fm", "pallas", "blocked", "autodiff"),
}


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize(
    "carried", list(SELECTION), ids=lambda c: "+".join(c) or "none"
)
def test_selection_table(monkeypatch, carried, mode):
    """A pin runs its kernel when the batch carries that kernel's layout,
    else the nearest earlier kernel whose layout it does carry; auto under
    the probe floor runs autodiff whatever is carried."""
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", mode)
    monkeypatch.setattr(
        sel, "_measure", lambda *a, **kw: pytest.fail("probed under the floor")
    )
    batch = _batch()._replace(**{field: object() for field in carried})
    want = SELECTION[carried][MODES.index(mode)]
    assert sel.select_kernel(batch, D) == want
    obj = GlmObjective.create("logistic")
    assert obj._sparse_kernel(batch, D) == (None if want == "autodiff" else want)


# -- (b) a pin that names no kernel --------------------------------------------


@pytest.mark.parametrize("variable,value", [
    ("PHOTON_SPARSE_GRAD", "xchg"),
    ("PHOTON_SPARSE_GRAD", "benes"),
    ("PHOTON_SPARSE_GRAD", "palas"),
    ("PHOTON_STREAM_KERNEL", "xchg"),
])
def test_unknown_pin_raises_and_names_the_valid_values(
    monkeypatch, variable, value
):
    from photon_tpu.data.stream_layouts import stream_kernel

    monkeypatch.delenv("PHOTON_STREAM_KERNEL", raising=False)
    monkeypatch.setenv(variable, value)
    if variable == "PHOTON_SPARSE_GRAD":
        # Every read goes through the one accessor: the attach's question
        # and the objective's raise alike, before anything is built or run.
        reads = (
            sel.pinned_kernel,
            sel.layouts_wanted,
            lambda: attach_feature_major(_batch(), aligned_dim=D),
            lambda: sel.select_kernel(attach_feature_major(_batch()), D),
            stream_kernel,
        )
        valid = "autodiff|fm|pallas|blocked|auto"
    else:
        reads, valid = (stream_kernel,), "autodiff|fm|pallas"
    for read in reads:
        with pytest.raises(ValueError) as err:
            read()
        assert f"{variable}={value!r}" in str(err.value)
        assert f"valid: {valid}" in str(err.value)


# -- (c) the layout fields, named once -----------------------------------------

PIN = {"fm": "fm", "al": "pallas", "al_t": "pallas", "bt": "blocked"}


def _carrying(field, monkeypatch):
    """A batch and its copy carrying ``field``, under the pin that reads it."""
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", PIN[field])
    # al_t is the pallas forward's layout: an opt-in beside al.
    monkeypatch.setenv(
        "PHOTON_SPARSE_MARGIN", "pallas" if field == "al_t" else "xla"
    )
    batch = _batch(seed=LAYOUT_FIELDS.index(field))
    fast = attach_feature_major(batch, aligned_dim=D)
    assert getattr(fast, field) is not None
    return batch, fast


def _reference(obj, w, batch, monkeypatch, pin):
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "autodiff")
    v, g = obj.value_and_grad(w, batch)
    monkeypatch.setenv("PHOTON_SPARSE_GRAD", pin)
    return float(v), np.asarray(g)


def test_layout_fields_are_the_optional_fields_of_a_sparse_batch():
    assert LAYOUT_FIELDS == ("fm", "al", "al_t", "bt")
    assert SparseBatch._fields == (
        "ids", "vals", "label", "offset", "weight"
    ) + LAYOUT_FIELDS


@pytest.mark.parametrize("field", LAYOUT_FIELDS)
def test_astype_keeps_margins_and_gradient_on_one_value_stream(
    monkeypatch, field
):
    """The layout holds its own copy of the values; ``batch_astype`` must
    round it with the row-major one, or the direction that reads the layout
    and the direction that reads the rows would see different numbers."""
    batch, fast = _carrying(field, monkeypatch)
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 0.3))
    w = jnp.asarray(
        np.random.default_rng(9).standard_normal(D).astype(np.float32) * 0.1
    )
    low = batch_astype(fast, jnp.bfloat16)
    assert obj._sparse_kernel(low, D) == PIN[field]
    v, g = obj.value_and_grad(w, low)
    v_ref, g_ref = _reference(
        obj, w, batch_astype(batch, jnp.bfloat16), monkeypatch, PIN[field]
    )
    np.testing.assert_allclose(float(v), v_ref, rtol=2e-5)
    scale = max(float(np.abs(g_ref).max()), 1.0)
    np.testing.assert_allclose(
        np.asarray(g), g_ref, rtol=2e-4, atol=2e-4 * scale
    )
    # Not vacuous: the float32 values give another gradient.
    assert np.abs(np.asarray(obj.value_and_grad(w, fast)[1]) - g_ref).max() > 1e-4


@pytest.mark.parametrize("field", LAYOUT_FIELDS)
def test_row_padding_strips_the_layout(monkeypatch, field):
    """Every layout depends on the row count: padded per leaf it would be
    corrupt, so ``pad_batch`` drops it and the caller attaches again."""
    _, fast = _carrying(field, monkeypatch)
    assert getattr(pad_batch(fast, N), field) is not None  # nothing to pad
    padded = pad_batch(fast, N + 8)
    assert all(getattr(padded, name) is None for name in LAYOUT_FIELDS)
    assert padded.ids.shape == (N + 8, K)
    assert not np.asarray(padded.weight[N:]).any()


@pytest.mark.parametrize("field", LAYOUT_FIELDS)
def test_a_mesh_of_one_device_places_the_layout(monkeypatch, field):
    """``shard_batch`` on one device is a single-block attach: the layout is
    rebuilt (never the caller's copy), every leaf lands on the mesh, and
    the sharded objective runs the kernel that reads it."""
    from photon_tpu.parallel import (
        DistributedGlmObjective,
        create_mesh,
        shard_batch,
    )

    batch, fast = _carrying(field, monkeypatch)
    mesh = create_mesh(1)
    sharded = shard_batch(fast, mesh, aligned_dim=D)
    placed = getattr(sharded, field)
    assert placed is not None and placed is not getattr(fast, field)
    for leaf in jax.tree.leaves(placed):
        assert set(leaf.sharding.device_set) == set(mesh.devices.flat)
    obj = GlmObjective.create("logistic", RegularizationContext("l2", 0.3))
    w = jnp.asarray(
        np.random.default_rng(10).standard_normal(D).astype(np.float32) * 0.1
    )
    dist = DistributedGlmObjective(obj, mesh)
    assert dist._sparse_kernel(w, sharded) == PIN[field]
    v, g = dist.value_and_grad(w, sharded)
    v_ref, g_ref = _reference(obj, w, batch, monkeypatch, PIN[field])
    np.testing.assert_allclose(float(v), v_ref, rtol=2e-5)
    scale = max(float(np.abs(g_ref).max()), 1.0)
    np.testing.assert_allclose(
        np.asarray(g), g_ref, rtol=2e-4, atol=2e-4 * scale
    )


# -- (d) nobody takes the decision back ----------------------------------------


def _sources(*suffixes):
    for root, _, names in os.walk(os.path.join(REPO, "photon_tpu")):
        for name in names:
            if name.endswith(suffixes):
                yield os.path.join(root, name)


def test_one_module_reads_the_pin_and_the_deleted_kernels_stay_deleted():
    """``PHOTON_SPARSE_GRAD`` as a string of its own (an environment read or
    write; docstrings and log texts hold it inside longer strings) appears
    in ops/sparse_grad_select.py only, and the names of the kernels that
    were deleted (PR 32) appear nowhere in the program."""
    owner = os.path.join(REPO, "photon_tpu", "ops", "sparse_grad_select.py")
    readers = set()
    for path in _sources(".py"):
        with open(path) as f:
            tree = ast.parse(f.read())
        if any(
            isinstance(node, ast.Constant)
            and node.value == "PHOTON_SPARSE_GRAD"
            for node in ast.walk(tree)
        ):
            readers.add(path)
    assert readers == {owner}
    deleted = re.compile("xchg|benes|vperm", re.IGNORECASE)
    program = list(_sources(".py", ".cpp")) + [
        os.path.join(REPO, "chip_smoke.py"),
        os.path.join(REPO, "__graft_entry__.py"),
    ]
    found = []
    for path in program:
        with open(path) as f:
            found += [
                f"{os.path.relpath(path, REPO)}:{i}"
                for i, line in enumerate(f, 1) if deleted.search(line)
            ]
    assert not found
