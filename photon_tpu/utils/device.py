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

JAX is imported lazily: the indexing driver and telemetry import this
module without initializing a backend.
"""

from __future__ import annotations

import logging
import sys
import threading
from typing import Dict

_lock = threading.Lock()
# Process-wide like the backend itself: kernel capability is a property of
# (process, device), and the probes that feed these run at trace time,
# far from any run-scoped telemetry session.
_refused: Dict[str, dict] = {}
_selected: Dict[str, int] = {}


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


def record_kernel_refusal(kernel: str, exc: BaseException) -> str:
    """The compiler (or an on-device parity gate) refused ``kernel``: log
    it once per occurrence at WARNING and count it for the run report.
    Returns the first line of the error, the part worth repeating."""
    first = (str(exc).strip().splitlines() or [type(exc).__name__])[0][:400]
    with _lock:
        entry = _refused.setdefault(kernel, {"count": 0, "error": first})
        entry["count"] += 1
    logging.getLogger("photon_tpu.kernels").warning(
        "kernel %s refused on this device: %s", kernel, first
    )
    return first


def record_kernel_selected(kernel: str) -> None:
    with _lock:
        _selected[kernel] = _selected.get(kernel, 0) + 1


def kernel_refusals() -> Dict[str, dict]:
    with _lock:
        return {k: dict(v) for k, v in _refused.items()}


def kernel_metrics() -> list:
    """Counter rows (registry-snapshot shape) for the run report."""
    with _lock:
        rows = [
            {"name": "kernels.refused", "labels": {"kernel": k},
             "value": float(v["count"])}
            for k, v in sorted(_refused.items())
        ]
        rows += [
            {"name": "kernels.selected", "labels": {"kernel": k},
             "value": float(n)}
            for k, n in sorted(_selected.items())
        ]
    return rows
