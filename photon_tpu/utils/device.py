"""What device this process runs on, and what its kernels did there.

One home for the three facts every layer used to work out for itself:

- which platform JAX gave us (:func:`device_facts`), read by the driver
  policy (``drivers/common.select_backend``), the run report and the
  summaries;
- whether Pallas kernels compile or interpret (:func:`pallas_interpret`) —
  decided from the platform in ONE place, so an unexpected platform
  string is an error instead of a silent interpreter in production;
- which kernels the compiler refused or selection picked
  (:func:`record_kernel_refusal` / :func:`record_kernel_selected`) — a
  refusal is a WARNING plus a ``kernels.refused{kernel=…}`` counter in
  every run report of the process, never a quiet switch to another path.
  The counts live in the process registry
  (``photon_tpu.telemetry.process_registry``): this module holds no metric
  state of its own;
- what its device programs are called (:func:`named_jit`): a jitted
  program takes its name from the function it wraps, and a name the
  program chose survives a refactor where ``jit__unknown`` does not.

JAX is imported lazily: the indexing driver and telemetry import this
module without initializing a backend.
"""

from __future__ import annotations

import logging
import sys


def backend_initialized() -> bool:
    """Has this process already created an XLA client?  Reads the private
    ``xla_bridge._backends`` (there is no public query that does not
    itself initialize the backend); the ONE guarded copy — telemetry and
    the dry-run bootstrap both ask here."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return bool(getattr(xla_bridge, "_backends", None))


def device_facts() -> dict:
    """``{"platform", "device_kind", "device_count"}`` as JAX reports them
    (initializes the backend)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def pallas_interpret() -> bool:
    """Whether ``pallas_call`` sites run the interpreter: never on a TPU,
    always on an (explicitly requested) CPU, and an error anywhere else —
    there is no platform on which interpreting silently is right."""
    import jax

    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile on 'tpu' and interpret on 'cpu'; this "
        f"process runs on {platform!r}"
    )


def named_jit(name: str, fun, **jit_kwargs):
    """``jax.jit(fun)`` as the device program ``jit_<name>``: the profiler's
    ``XLA Modules`` line, the HLO module and the compile-cache key all take
    the wrapped function's ``__name__``, which for a ``functools.partial``
    or a lambda is ``_unknown`` / ``<lambda>``.  The published names are
    listed in README "Telemetry"."""
    import functools

    import jax

    @functools.wraps(fun)
    def program(*args, **kwargs):
        return fun(*args, **kwargs)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **jit_kwargs)


def count_h2d(what: str, tree) -> None:
    """Add the bytes of a layout or shard just handed to the device (any
    pytree of arrays) to ``layout.h2d_bytes{what}`` in the process registry.
    Uploads are asynchronous: this is a byte count, not a span that would
    have to block."""
    import jax

    from photon_tpu.telemetry import process_registry

    process_registry().counter("layout.h2d_bytes", what=what).inc(
        sum(leaf.nbytes for leaf in jax.tree.leaves(tree))
    )


def count_layout_skipped(layout: str) -> None:
    """One build of ``layout`` (a ``SparseBatch`` field) spared because the
    kernel verdict came first and another kernel won:
    ``layout.skipped{layout}`` in the process registry."""
    from photon_tpu.telemetry import process_registry

    process_registry().counter("layout.skipped", layout=layout).inc()


def record_kernel_refusal(kernel: str, exc: BaseException) -> str:
    """The compiler (or an on-device parity gate) refused ``kernel``: log
    it once per occurrence at WARNING and count it for the run report.
    Returns the first line of the error, the part worth repeating."""
    from photon_tpu.telemetry import process_registry

    first = (str(exc).strip().splitlines() or [type(exc).__name__])[0][:400]
    process_registry().counter("kernels.refused", kernel=kernel).inc()
    logging.getLogger("photon_tpu.kernels").warning(
        "kernel %s refused on this device: %s", kernel, first
    )
    return first


def record_kernel_selected(kernel: str) -> None:
    from photon_tpu.telemetry import process_registry

    process_registry().counter("kernels.selected", kernel=kernel).inc()


def kernel_metrics() -> list:
    """Every counter of the process registry, as registry-snapshot rows
    (``{"name", "labels", "value"}``): kernel refusals and selections, the
    layout and probe spans (``span.seconds`` / ``span.count``), bytes handed
    to the device, the evaluation counts of fits run with no session.  The
    run report and both benchmark runners append these rows to their own
    counters; the name is from when the kernels' were the only ones."""
    from photon_tpu.telemetry import process_registry

    return process_registry().snapshot()["counters"]
