"""Lazy on-demand build of the native shared library.

Compiles ``src/*.cpp`` with g++ the first time a native entry point is
used.  The library's file name carries a hash of the sources
(``_photon_native.<digest>.so``), so a binary built from other sources —
a tree copy that kept an old ``.so``, an archive extraction that reset
mtimes — is never loaded: the name does not match and the library is
rebuilt.  Failures are cached for the process so a missing toolchain
costs one attempt, not one per call, and :func:`status` says why.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

_HERE = os.path.dirname(__file__)
_SRC_DIR = os.path.join(_HERE, "src")
_LOG = logging.getLogger("photon_tpu.native")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_failed = False
_status = "not loaded"


def native_disabled() -> bool:
    return os.environ.get("PHOTON_TPU_NO_NATIVE", "") not in ("", "0")


def status() -> str:
    """``built`` | ``loaded`` (an up-to-date binary was already there) |
    ``unavailable (<why>)`` | ``not loaded`` (nothing asked for it yet)."""
    return _status


def _lib_path(sources: list[str]) -> str:
    h = hashlib.sha256()
    for path in sources:
        h.update(os.path.basename(path).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return os.path.join(_HERE, f"_photon_native.{h.hexdigest()[:12]}.so")


def _compile(sources: list[str], lib_path: str) -> Optional[str]:
    """Build ``lib_path``; returns None on success, else why it failed."""
    # Compile to a process-unique temp path and os.replace() atomically:
    # concurrent first-use builds must never CDLL a half-written .so.
    tmp_path = f"{lib_path}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        "-o", tmp_path, *sources,
    ]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=300
        )
        if proc.returncode != 0 or not os.path.exists(tmp_path):
            _LOG.warning(
                "native build failed (g++ rc=%d):\n%s",
                proc.returncode, proc.stderr[-4000:],
            )
            return f"g++ exited {proc.returncode}"
        os.replace(tmp_path, lib_path)
    except (OSError, subprocess.TimeoutExpired) as exc:
        _LOG.warning("native build failed: %s: %s", type(exc).__name__, exc)
        return f"{type(exc).__name__}: {exc}"
    finally:
        if os.path.exists(tmp_path):
            try:
                os.unlink(tmp_path)
            except OSError:
                pass
    # Binaries of other source revisions are dead weight now.
    for stale in glob.glob(os.path.join(_HERE, "_photon_native*.so")):
        if stale != lib_path:
            try:
                os.unlink(stale)
            except OSError:
                pass
    return None


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.svm_open.restype = c.c_void_p
    lib.svm_open.argtypes = [c.c_char_p]
    lib.svm_rows.restype = c.c_int64
    lib.svm_rows.argtypes = [c.c_void_p]
    lib.svm_total_nnz.restype = c.c_int64
    lib.svm_total_nnz.argtypes = [c.c_void_p]
    lib.svm_row_nnz.restype = None
    lib.svm_row_nnz.argtypes = [c.c_void_p, c.POINTER(c.c_int64)]
    lib.svm_parse.restype = c.c_int64
    lib.svm_parse.argtypes = [
        c.c_void_p, c.POINTER(c.c_int64), c.POINTER(c.c_float),
        c.POINTER(c.c_int32), c.POINTER(c.c_float), c.c_int,
    ]
    lib.svm_close.restype = None
    lib.svm_close.argtypes = [c.c_void_p]

    lib.ixs_build.restype = c.c_int
    lib.ixs_build.argtypes = [
        c.c_char_p, c.c_char_p, c.POINTER(c.c_int64),
        c.POINTER(c.c_int64), c.c_int64,
    ]
    lib.ixs_open.restype = c.c_void_p
    lib.ixs_open.argtypes = [c.c_char_p]
    lib.ixs_n_keys.restype = c.c_int64
    lib.ixs_n_keys.argtypes = [c.c_void_p]
    lib.ixs_get.restype = c.c_int64
    lib.ixs_get.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.ixs_key_at.restype = c.c_int64
    lib.ixs_key_at.argtypes = [c.c_void_p, c.c_int64, c.c_char_p, c.c_int64]
    lib.ixs_close.restype = None
    lib.ixs_close.argtypes = [c.c_void_p]


def get_lib() -> Optional[ctypes.CDLL]:
    """The native library, building it if needed; None when unavailable
    (:func:`status` says why — the Python readers then run instead)."""
    global _lib, _failed, _status
    if native_disabled():
        return None
    if _lib is not None:
        return _lib
    if _failed:
        return None
    with _lock:
        if _lib is not None or _failed:
            return _lib
        sources = sorted(glob.glob(os.path.join(_SRC_DIR, "*.cpp")))
        if not sources:
            _failed, _status = True, "unavailable (no sources)"
            return None
        lib_path = _lib_path(sources)
        built = not os.path.exists(lib_path)
        why = _compile(sources, lib_path) if built else None
        if why is None:
            try:
                lib = ctypes.CDLL(lib_path)
                _declare(lib)
            except (OSError, AttributeError) as exc:
                why = f"{type(exc).__name__}: {exc}"
                _LOG.warning("native library %s unusable: %s", lib_path, why)
        if why is not None:
            _failed, _status = True, f"unavailable ({why})"
            return None
        _lib, _status = lib, "built" if built else "loaded"
    return _lib
