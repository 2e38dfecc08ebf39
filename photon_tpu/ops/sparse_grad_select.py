"""Runtime selection of the sparse-gradient reduction kernel.

The production gradient has four lowerings auto mode chooses between
(see ops/KERNEL_NOTES.md):

- **fm** — the pre-sorted segment-sum over the static FeatureMajorAux
  layout (no per-evaluation device sort, but pays an extra
  ``dz[rows]`` gather and an E-element segment sum);
- **autodiff** — differentiate through the row-major margins, whose
  transpose is an unsorted scatter-add (XLA lowers it as sort +
  segmented reduce on TPU, but as a fast native scatter on CPU);
- **pallas** — the slab-aligned Mosaic kernel
  (ops/pallas_gather.aligned_segment_grad): same ``dz[rows]`` gather,
  then a per-tile 8-way masked position reduce in VMEM and a TINY
  sorted segment-sum over the slab dictionary (n_slabs*1024 values
  instead of E).  Requires the batch to carry an AlignedLayoutDev
  (``attach_feature_major(..., aligned_dim=d)``); a candidate on TPU
  only (interpret mode on CPU is a test vehicle, orders of magnitude
  slower);
- **blocked** — row-block x feature-block entry tiles
  (ops/block_tiles.block_tiles_product): no XLA gather or scatter in
  either direction; the tile's window of the vector it reads and of the
  vector it adds into both sit in VMEM: a lane gather reads, the MXU adds
  (exact: three bfloat16 terms a float32).  Margins, gradient and Hv all
  route through it.  Requires the batch to carry BlockTiles (same attach
  call, single-block batches); a candidate on TPU only, like pallas.

Which wins is a hardware property — so, like the reference's BLAS
dispatch, the choice is made by a one-time EAGER measurement on the live
backend, cached per (platform, size bucket, candidate set).  The probe
times a problem of its own, so it needs none of the batch's layouts:
**verdict, then build**.  A single-block attach asks
:func:`kernel_for_shape` BEFORE it builds (every kernel that could be
built for the shape is a candidate) and builds the winner's layout alone;
the trace-time :func:`select_kernel`, which asks among what the batch
carries, finds that verdict cached.  Every candidate first passes
:func:`check_kernel` — compiled on this device and compared against the
NumPy reference — and a candidate the compiler refuses (or that fails
parity) is excluded LOUDLY: a WARNING and a ``kernels.refused{kernel=…}``
counter in the run report (utils/device.record_kernel_refusal), never a
quiet switch of path.

Override with ``PHOTON_SPARSE_GRAD=autodiff|fm|pallas|blocked|auto``
(default auto); any other value raises at the first read
(:func:`pinned_kernel`).

This module is the one owner of the decision: it alone knows the kernel
names and reads ``PHOTON_SPARSE_GRAD``, and :data:`_KERNELS` says for each
kernel which batch layout it reads, whether it needs compiled Mosaic to be
an auto candidate, whether it brings its own forward and whether
``jax.jvp`` can go through it.  core/objective.py dispatches on the name;
every other module asks here.
"""

from __future__ import annotations

import functools
import os
import time
from typing import NamedTuple, Optional

import numpy as np

_CACHE: dict = {}


class _Kernel(NamedTuple):
    layout: Optional[str]  # the SparseBatch field its gradient reads
    mosaic: bool  # an auto candidate only where Mosaic compiles (a TPU)
    forward: Optional[str]  # the field that gives it a forward of its own
    jvp: bool  # jax.jvp can differentiate through it (no pallas_call)


# In order of preference: a pin whose layout the batch does not carry falls
# to the nearest earlier kernel whose layout it does.
_KERNELS = {
    "autodiff": _Kernel(layout=None, mosaic=False, forward=None, jvp=True),
    "fm": _Kernel(layout="fm", mosaic=False, forward=None, jvp=True),
    "pallas": _Kernel(layout="al", mosaic=True, forward="al_t", jvp=False),
    "blocked": _Kernel(layout="bt", mosaic=True, forward="bt", jvp=False),
}
KERNELS = tuple(_KERNELS)


def pinned_kernel() -> Optional[str]:
    """The kernel ``PHOTON_SPARSE_GRAD`` forces, or None in auto mode.  A
    value that names no kernel raises: an operator's pin must never quietly
    run something else."""
    mode = os.environ.get("PHOTON_SPARSE_GRAD", "auto")
    if mode == "auto":
        return None
    if mode not in _KERNELS:
        raise ValueError(
            f"PHOTON_SPARSE_GRAD={mode!r}; valid: {'|'.join(KERNELS)}|auto"
        )
    return mode


def pin_for_multiprocess() -> None:
    """Auto mode becomes ``autodiff`` for this process and its children: the
    selection is a per-process wall-clock measurement, and ranks that
    measured different winners would run different reduction orders.  An
    operator's pin is the same on every rank already and stays."""
    if pinned_kernel() is None:
        os.environ["PHOTON_SPARSE_GRAD"] = "autodiff"


def has_own_forward(kernel: str, batch) -> bool:
    """Does ``kernel`` compute ``X u`` over a layout of its own on this
    batch (else the row-major XLA gather does)?"""
    field = _KERNELS[kernel].forward
    return field is not None and getattr(batch, field) is not None


def differentiable(kernel: str) -> bool:
    """Can ``jax.jvp`` go through ``kernel``'s gradient?"""
    return _KERNELS[kernel].jvp


def _nearest(kernel: str, carried: tuple) -> str:
    """``kernel`` if the batch carries its layout, else the nearest earlier
    one it carries (``autodiff`` needs none)."""
    upto = KERNELS[: KERNELS.index(kernel) + 1]
    return [name for name in upto if name in carried][-1]

# Probe arrays are capped so the one-time measurement stays cheap even for
# billion-entry datasets; relative kernel cost is stable above this size.
# Overridable (PHOTON_SPARSE_PROBE_MAX_ENTRIES) for callers who want the
# probe at the true problem shape — bench.py pays ~10 s once to attribute
# its headline to the kernel that actually wins at full size.
_PROBE_MAX_ENTRIES = 1 << 21


def _probe_cap() -> int:
    # Clamp at 1: 0 would divide-by-zero in the ceil, negatives would uncap
    # the probe (a billion-entry dataset would then build a multi-GB probe).
    from photon_tpu.utils.env import env_int

    return env_int(
        "PHOTON_SPARSE_PROBE_MAX_ENTRIES", _PROBE_MAX_ENTRIES, minimum=1
    )


def _probe_floor() -> int:
    # 0 (or negative == default-out) disables the floor entirely.
    from photon_tpu.utils.env import env_int

    return env_int("PHOTON_SPARSE_PROBE_FLOOR", 1 << 20, minimum=0)


def _bucket(n: int) -> int:
    return max(int(n).bit_length(), 1)


def _probe_problem(e: int, d: int, n: int):
    """A seeded [n, k] padded-COO problem of ~``e`` entries, its per-row
    vector and the float64 NumPy gradient every kernel must reproduce; and a
    coefficient vector with its float64 margins, for a kernel that replaces
    the forward too."""
    import types

    rng = np.random.default_rng(0)
    k = max(e // max(n, 1), 1)
    n = max(e // k, 1)
    ids = rng.integers(0, d, size=(n, k), dtype=np.int32)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    dz = rng.standard_normal(n).astype(np.float32)
    ref = np.zeros(d, np.float64)
    np.add.at(
        ref, ids.reshape(-1),
        (dz[:, None] * vals).reshape(-1).astype(np.float64),
    )
    w = rng.standard_normal(d).astype(np.float32)
    ref_xw = (w[ids].astype(np.float64) * vals).sum(axis=1)
    return types.SimpleNamespace(n=n, k=k, d=d, ids=ids, vals=vals, dz=dz,
                                 ref=ref, w=w, ref_xw=ref_xw)


def _kernel_fn(name: str, p):
    """``dz -> g[d]`` through kernel ``name`` over the probe problem's
    static layout (built here, host side).  A kernel that also replaces the
    margins carries that direction as ``fn.forward`` (``w -> Xw[n]``)."""
    import jax
    import jax.numpy as jnp

    d = p.d
    if name == "autodiff":
        ids, vals = jnp.asarray(p.ids), jnp.asarray(p.vals)
        return lambda dz: jnp.zeros(d, jnp.float32).at[ids].add(
            dz[:, None] * vals
        )
    if name == "fm":
        flat = p.ids.reshape(-1)
        order = np.argsort(flat, kind="stable")
        rows = jnp.asarray((order // p.k).astype(np.int32))
        sorted_ids = jnp.asarray(flat[order])
        sorted_vals = jnp.asarray(p.vals.reshape(-1)[order])
        return lambda dz: jax.ops.segment_sum(
            jnp.take(dz, rows, axis=0) * sorted_vals, sorted_ids,
            num_segments=d, indices_are_sorted=True,
        )
    if name == "blocked":
        from photon_tpu.ops import block_tiles

        bt = block_tiles.device_block_tiles(
            block_tiles.build_block_tiles(p.ids, p.vals, d)
        )

        # Looked up at call time: tests substitute the kernel.
        def fn(dz):
            return block_tiles.block_tiles_product(dz, bt, d, transpose=True)

        fn.forward = lambda w: block_tiles.block_tiles_product(w, bt, p.n)
        return fn
    if name == "pallas":
        from photon_tpu.ops import pallas_gather

        al = pallas_gather.device_layout(
            pallas_gather.build_aligned_layout(p.ids, p.vals, d)
        )
        # Looked up at call time: tests substitute the kernel.
        return lambda dz: pallas_gather.aligned_segment_grad(dz, al, d)
    raise ValueError(f"no probe for kernel {name!r}")


def check_kernel(name: str, p) -> tuple:
    """Compile kernel ``name`` on the live backend and compare it with the
    NumPy reference on probe problem ``p``.  Returns ``(fn, status)``:
    ``fn`` is the ``dz -> g`` callable when the kernel compiled and
    matched (else None; a kernel that replaces the margins too must match
    in that direction as well), ``status`` one of ``"compiled+parity ok"``,
    ``"refused: <first line of the compiler error>"``, ``"parity failed:
    <max abs err>"``.  A refusal or parity failure is recorded
    (WARNING + ``kernels.refused``) — this is the one place a lowering
    error on the selection path is caught."""
    import jax.numpy as jnp

    from photon_tpu.utils.device import record_kernel_refusal

    try:
        fn = _kernel_fn(name, p)
        pairs = [(np.asarray(fn(jnp.asarray(p.dz))), p.ref)]
        if hasattr(fn, "forward"):
            pairs.append((np.asarray(fn.forward(jnp.asarray(p.w))), p.ref_xw))
    except Exception as exc:  # noqa: BLE001 — recorded, never discarded
        return None, f"refused: {record_kernel_refusal(name, exc)}"
    for got, ref in pairs:
        scale = max(float(np.abs(ref).max()), 1.0)
        if not np.allclose(got, ref, rtol=2e-4, atol=1e-4 * scale):
            err = float(np.abs(got - ref).max())
            return None, record_kernel_refusal(
                name, ValueError(f"parity failed: {err:.3g}")
            )
    return fn, "compiled+parity ok"


def kernel_report(e: int, d: int, n: int, kernels=KERNELS) -> dict:
    """``{kernel: status}`` of :func:`check_kernel` at one probe problem —
    the per-kernel compile/parity table ``chip_smoke.py`` prints."""
    p = _probe_problem(e, d, n)
    return {name: check_kernel(name, p)[1] for name in kernels}


def _measure(e: int, d: int, n: int, names: tuple) -> str:
    """The fastest of the candidate kernels ``names`` on an evaluation of a
    probe problem of this size (those that pass :func:`check_kernel`)."""
    import jax
    import jax.numpy as jnp

    p = _probe_problem(e, d, n)
    dz, w = jnp.asarray(p.dz), jnp.asarray(p.w)
    ids, vals = jnp.asarray(p.ids), jnp.asarray(p.vals)

    def evaluation(u, v, ids, vals, fn):
        # The margins of a kernel that has no forward of its own are the
        # row-major gather; its entries arrive as arguments (closed over,
        # they would be 16 MB of constants in each candidate's program).
        if hasattr(fn, "forward"):
            xw = fn.forward(u)
        else:
            xw = jnp.sum(jnp.take(u, ids, axis=0) * vals, axis=-1)
        return jnp.sum(xw) + jnp.sum(fn(v))

    timings = {}
    for name in names:
        fn, _ = check_kernel(name, p)
        if fn is None:
            continue
        # An evaluation is the margins and the gradient: a candidate is
        # timed on both, so one that replaces the forward is ranked on all
        # it changes.  Salt the argument per rep so no call can be served
        # from a cache, prepare the salt OUTSIDE the timed window, and fetch
        # the scalar host-side per rep (the sync a host copy cannot fake).
        fj = jax.jit(functools.partial(evaluation, fn=fn))
        float(np.asarray(fj(w, dz, ids, vals)))  # compile + sync
        ts = []
        for i in range(3):
            salted = dz + jnp.float32((i + 1) * 1e-12)
            jax.block_until_ready(salted)
            t0 = time.perf_counter()
            float(np.asarray(fj(w, salted, ids, vals)))
            ts.append(time.perf_counter() - t0)
        timings[name] = float(np.median(ts))
    if not timings:
        raise RuntimeError(
            f"no sparse-gradient kernel passed its check on this device "
            f"(tried {list(names)}); see the kernels.refused warnings"
        )
    return min(timings, key=timings.get)


def _pallas_eligible() -> bool:
    """Compiled Mosaic only: the interpreter is a test vehicle."""
    from photon_tpu.utils.device import pallas_interpret

    return not pallas_interpret()


def select_kernel(batch, dim: int) -> str:
    """Pick the gradient kernel — one of :data:`KERNELS` — for this 2-D
    sparse batch on the current backend, among the kernels whose layout the
    batch carries."""
    from photon_tpu.utils.device import record_kernel_selected

    choice = _select(batch, dim)
    record_kernel_selected(choice)
    return choice


def carried_kernel(batch, dim: int) -> str:
    """The kernel :func:`select_kernel` will answer for ``batch``, told
    without measuring (a pin, the probe floor, one candidate, a verdict the
    probe has cached: the attach's own, when it asked first); ``unprobed``
    where only a probe not yet run can say.  Nothing is recorded: this is
    for a layout's label, not a selection."""
    return _select(batch, dim, measure=False) or "unprobed"


def _select(batch, dim: int, measure: bool = True) -> Optional[str]:
    carried = tuple(
        name for name, kernel in _KERNELS.items()
        if kernel.layout is None or getattr(batch, kernel.layout) is not None
    )
    pin = pinned_kernel()
    if pin is not None:
        # A forced Mosaic kernel runs in interpret mode off the TPU (tests,
        # parity checks); it still needs its layout on the batch.
        return _nearest(pin, carried)
    n_rows, k = batch.ids.shape
    # Probe floor: below ~1M entries the eager measurement costs more than
    # any kernel difference could repay (GAME runs hit MANY small shape
    # buckets — one probe each).
    if n_rows * k < _probe_floor():
        return "autodiff"

    candidates = tuple(
        name for name in carried
        if not _KERNELS[name].mosaic or _pallas_eligible()
    )
    if candidates == ("autodiff",):
        return "autodiff"  # single-candidate set: nothing to measure
    return _probed(n_rows, k, dim, candidates, measure)


def _probed(n_rows: int, k: int, dim: int, candidates: tuple,
            measure: bool = True) -> Optional[str]:
    """The fastest of ``candidates`` at this size on the live backend:
    measured once a process per (backend, size bucket, dim bucket,
    candidates) and cached.  A cached verdict over MORE candidates answers
    too when its winner is among these: the attach asks about every kernel
    it could build (:func:`kernel_for_shape`) and builds the winner's layout
    alone, so the trace-time question over what the batch then carries is
    already answered.  With ``measure`` off a question the cache cannot
    answer gets None."""
    import jax

    e_total = n_rows * k
    where = (jax.default_backend(), _bucket(e_total), _bucket(dim))
    for key, winner in _CACHE.items():
        if (
            key[:3] == where and winner in candidates
            and set(candidates) <= set(key[3])
        ):
            return winner
    if not measure:
        return None
    scale = max(1, -(-e_total // _probe_cap()))  # ceil: cap probe size
    e = max(e_total // scale, 1 << 10)
    n = max(n_rows // scale, 64)
    # eval_context: at trace time this runs while an ENCLOSING jit (the
    # optimizer's while_loop, a streamed chunk program) is being traced,
    # and under omnistaging even jit calls on concrete inputs inline into
    # the outer trace — the probe's host synchronizations would raise.
    # Stepping out to the eval trace executes the probe eagerly, so the
    # cache holds a real measurement wherever the first call happens.
    # (NOT ensure_compile_time_eval: on jax 0.9 that also constant-folds
    # inside the Pallas kernel-body trace, where ``program_id`` has no
    # evaluation rule — every pallas probe was refused that way on the
    # chip, PR 21.)  A probe that fails outright raises: there is no
    # default kernel to fall back to.
    from photon_tpu import telemetry

    with telemetry.span(
        "kernels.probe", candidates=len(candidates), size=e
    ), jax.core.eval_context():
        winner = _CACHE[where + (candidates,)] = _measure(
            e, dim, n, candidates
        )
    import logging

    # Logged because auto-selection is a wall-clock measurement: on a
    # machine near the kernel crossover two runs can pick different
    # kernels, whose different reduction orders give slightly different
    # float results.  Pin PHOTON_SPARSE_GRAD=fm|autodiff|pallas|blocked for
    # bitwise same-seed reproducibility (SURVEY.md §5 determinism note).
    logging.getLogger("photon_tpu.sparse_grad").info(
        "sparse-grad kernel for backend=%s e~2^%d d~2^%d: %s",
        *where, winner,
    )
    return winner


class Verdict(NamedTuple):
    kernel: str
    probed: bool  # a measurement decided it, not a pin or the floor

    @property
    def layout(self) -> Optional[str]:
        """The ``SparseBatch`` field the kernel's gradient reads (None:
        ``autodiff`` reads the row-major entries)."""
        return _KERNELS[self.kernel].layout


def kernel_for_shape(n_rows: int, k: int, dim: int) -> Verdict:
    """Which kernel will a single-block ``[n_rows, k]`` batch of dimension
    ``dim`` run on this backend?  Asked by the attach BEFORE it builds
    anything, so that it builds the winner's layout alone: the pin; under
    the probe floor ``autodiff``; else the probe's verdict
    (:func:`_probed`: the same measurement and the same cache as at trace
    time) among the kernels whose layout could be built here — ``autodiff``
    and ``fm`` anywhere, the Mosaic kernels where Mosaic compiles,
    ``blocked`` only for a batch its tile table can hold."""
    pin = pinned_kernel()
    if pin is not None:
        return Verdict(pin, False)
    if n_rows * k < _probe_floor():
        return Verdict("autodiff", False)
    from photon_tpu.ops.block_tiles import block_tile_geometry

    mosaic = _pallas_eligible()
    candidates = tuple(
        name for name, kernel in _KERNELS.items()
        if (mosaic or not kernel.mosaic) and (
            kernel.layout != "bt"
            or block_tile_geometry(n_rows, dim, n_rows * k) is not None
        )
    )
    return Verdict(_probed(n_rows, k, dim, candidates), True)


def layouts_wanted(e_total: int | None = None) -> tuple[bool, bool]:
    """``(aligned, block_tiles)``: which static layouts COULD be wanted
    here, besides the feature-major aux.  A layout is wanted when a kernel
    that reads it is forced, or could win auto-selection on this backend
    (compiled Mosaic: a TPU), so CPU runs never pay for a kernel auto mode
    will not pick.  Pass the entry count when known: below the probe floor
    auto mode is guaranteed to run autodiff, so a build would be pure
    wasted host time.  Where no measurement decides (a pin, a sharded
    attach) these are the layouts built; where the probe decides, the
    single-block attach narrows them to the winner's
    (:func:`kernel_for_shape`)."""
    pin = pinned_kernel()
    if pin is not None:
        wanted = (_KERNELS[pin].layout,)
    elif e_total is not None and e_total < _probe_floor():
        wanted = ()
    else:
        wanted = tuple(
            kernel.layout for kernel in _KERNELS.values()
            if kernel.mosaic and _pallas_eligible()
        )
    return "al" in wanted, "bt" in wanted


def aligned_layout_wanted(e_total: int | None = None) -> bool:
    """Does :func:`layouts_wanted` want any layout?  The gate
    ``attach_feature_major(batch, aligned_dim=d)`` applies to itself; a
    caller that must decide on other grounds (a multi-process assembly, on
    the global entry count) asks it here and passes ``aligned_dim`` or
    None."""
    return any(layouts_wanted(e_total))
