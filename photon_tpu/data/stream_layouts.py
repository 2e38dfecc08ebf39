"""Fast-kernel layouts for STREAMED chunks (VERDICT r5 item 3).

The streaming tier (data/streaming.py tier 3) re-parses the same part
files on every objective evaluation, so until now it could only run the
row-major autodiff kernel: the aligned layout costs orders of magnitude
more host time than a chunk parse, and rebuilding it per pass is
economically impossible.  But a chunk's layout is a pure function of its
FILE — identical on every pass — so it can be built once, persisted
under the cache root, and re-attached to each freshly parsed chunk at
stat+load cost:

- **Cache key = file identity (abspath, size, mtime) + parse params**,
  not content: the hit path per pass is one ``stat`` and one ``npz``
  load — no per-pass hashing of multi-MB id streams.
- **Pow2-bucketed geometry**: per-file natural geometry (aligned
  slabs/tiles) is padded UP to powers of two, so equal-shaped chunks
  (every full part file of a dataset) share one stacked treedef and
  therefore ONE jitted per-chunk program — without any global pre-pass
  over all files.

Select with ``PHOTON_STREAM_KERNEL=autodiff|fm|pallas`` (default: a
pinned ``PHOTON_SPARSE_GRAD`` of ``fm`` or ``pallas``, else ``autodiff`` —
the right default while streamed passes are host-parse-bound).
"""

from __future__ import annotations

import hashlib
import logging
import os
from typing import Optional

import numpy as np

from photon_tpu.data.batch import SparseBatch

_VERSION = 1
_LOG = logging.getLogger("photon_tpu.stream_layouts")

_KERNELS = ("autodiff", "fm", "pallas")


def stream_kernel() -> str:
    """The kernel streamed chunks should carry layouts for.

    Defaults to following a FORCED ``PHOTON_SPARSE_GRAD`` (so pinning
    the production kernel pins the streamed path too, with no second
    knob to forget), else ``autodiff``.  Note the layouts only make the
    chunk ELIGIBLE — in ``PHOTON_SPARSE_GRAD=auto`` mode the measured
    selection still arbitrates per shape bucket, exactly as for
    resident batches."""
    k = os.environ.get("PHOTON_STREAM_KERNEL")
    if k is None:
        from photon_tpu.ops.sparse_grad_select import pinned_kernel

        forced = pinned_kernel()
        k = forced if forced in ("fm", "pallas") else "autodiff"
    if k not in _KERNELS:
        raise ValueError(
            f"PHOTON_STREAM_KERNEL={k!r}; valid: {'|'.join(_KERNELS)}"
        )
    return k


def stream_kernel_why(kernel: str) -> str:
    """One-line provenance for bench/driver reporting."""
    if kernel == "autodiff":
        return (
            "default: streamed passes are host-parse-bound; set "
            "PHOTON_STREAM_KERNEL to attach cached fast-kernel layouts "
            "per chunk"
        )
    return (
        f"PHOTON_STREAM_KERNEL={kernel}: per-file layouts built once "
        "and cached (pow2-bucketed geometry), re-attached per pass at "
        "stat+load cost"
    )


def _pow2(x: int) -> int:
    from photon_tpu.utils import pow2_at_least

    return pow2_at_least(int(x))


def _cache_root() -> Optional[str]:
    from photon_tpu.utils.caches import resolve_cache_dir

    return resolve_cache_dir("PHOTON_STREAM_LAYOUT_CACHE", "stream")


def _layout_cache_path(file_path: str, dim: int,
                       capacity: int) -> Optional[str]:
    root = _cache_root()
    if root is None:
        return None
    try:
        st = os.stat(file_path)
        ident = (os.path.abspath(file_path), st.st_size,
                 int(st.st_mtime_ns))
    except OSError:
        return None
    h = hashlib.sha256()
    h.update(repr(ident).encode())
    h.update(f"|{dim}|{capacity}|pallas|v{_VERSION}".encode())
    return os.path.join(root, "aux_" + h.hexdigest()[:32] + ".npz")


def _build_padded_layout(ids_np: np.ndarray, vals_np: np.ndarray,
                         dim: int):
    """Aligned layout padded to pow2-bucketed (slabs, tiles) so chunks
    of equal shape share one compiled program."""
    from photon_tpu.ops.pallas_gather import (
        build_aligned_layout,
        pad_aligned_layout,
    )

    lay = build_aligned_layout(ids_np, vals_np, dim)
    s2 = _pow2(lay.n_slabs)
    t2 = _pow2(lay.n_tiles + (s2 - lay.n_slabs))
    return pad_aligned_layout(lay, s2, t2)


def _save_layout(path: str, layout) -> None:
    out = {
        "lay_" + name: np.asarray(getattr(layout, name))
        for name in ("lo", "vals", "rows", "slab_of_tile", "dup_map")
    }
    out["lay_n_entries"] = np.int64(layout.n_entries)
    import tempfile

    try:
        directory = os.path.dirname(path) or "."
        os.makedirs(directory, exist_ok=True)
        # mkstemp: a name no other thread or process can pick (two io-pool
        # workers caching the same file used to collide on a pid+id name).
        fd, tmp = tempfile.mkstemp(
            prefix=os.path.basename(path) + ".", suffix=".tmp", dir=directory
        )
        try:
            with os.fdopen(fd, "wb") as f:
                np.savez(f, **out)
            os.replace(tmp, path)
        except BaseException:
            os.unlink(tmp)
            raise
    except Exception as exc:  # noqa: BLE001 — best-effort cache
        _LOG.warning("stream layout cache write failed (%s)", exc)


def _load_layout(path: str):
    """The aligned layout from a cache file, or None on any read failure
    (caller rebuilds)."""
    from photon_tpu.ops.pallas_gather import AlignedLayout

    try:
        with np.load(path) as z:
            lo = z["lay_lo"]
            return AlignedLayout(
                lo=lo,
                vals=z["lay_vals"],
                rows=z["lay_rows"],
                slab_of_tile=z["lay_slab_of_tile"],
                dup_map=z["lay_dup_map"],
                # Host-only field the device layout never reads (and not
                # cached for size).
                src=np.full(lo.shape, -1, np.int64),
                n_entries=int(z["lay_n_entries"]),
            )
    except Exception as exc:  # noqa: BLE001 — corrupt cache = rebuild
        _LOG.warning("stream layout cache read failed (%s); rebuilding",
                     exc)
        return None


def attach_stream_aux(batch: SparseBatch, dim: int,
                      file_path: str) -> SparseBatch:
    """Attach the PHOTON_STREAM_KERNEL layouts to a freshly parsed
    chunk, building them on first touch and loading from the stream
    cache afterwards.  The returned batch routes to the fast kernels
    through the ordinary selection machinery (core/objective)."""
    kernel = stream_kernel()
    if kernel == "autodiff" or not (
        isinstance(batch, SparseBatch) and batch.ids.ndim == 2
    ):
        return batch
    from photon_tpu.data.batch import attach_feature_major

    if kernel == "fm":
        # Cheap (one argsort) relative to the parse; rebuilt per pass.
        return attach_feature_major(batch)
    from photon_tpu.ops.pallas_gather import device_layout

    path = _layout_cache_path(file_path, dim, int(batch.ids.shape[1]))
    layout = None
    if path is not None and os.path.exists(path):
        layout = _load_layout(path)
    if layout is None:
        # Host copies of the chunk arrays happen ONLY on this build
        # branch — the per-pass hit path stays stat + npz load.
        ids_np = np.asarray(batch.ids)
        vals_np = np.asarray(batch.vals, np.float32)
        _LOG.warning(
            "building the %s stream layout for %s (%d entries) — one-time "
            "host work, cached for every later pass%s",
            kernel, os.path.basename(file_path), ids_np.size,
            "" if path is not None else
            " (caching DISABLED via PHOTON_STREAM_LAYOUT_CACHE=0)",
        )
        layout = _build_padded_layout(ids_np, vals_np, dim)
        if path is not None:
            _save_layout(path, layout)
    return batch._replace(al=device_layout(layout))
