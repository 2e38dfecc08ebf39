"""The one persistent-compile-cache contract.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that directory
and this module sets no other.  If it is not, the cache lives at ONE fixed
path inside the checkout (``<repo>/.jax_cache``, git-ignored), resolved
from this file — never from ``$HOME``, ``$TMPDIR``, a pid, a time or a
digest: the directory is part of JAX's cache key, so a cache that moves
never hits.  Drivers, ``bench.py`` and ``chip_smoke.py`` all come through
:func:`enable`; child processes inherit the directory through the
environment.
"""

from __future__ import annotations

import os

from photon_tpu.utils.caches import CHECKOUT_ROOT

_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def enable() -> str:
    """Make sure a persistent compilation cache is on; return its
    directory.  Call before the first compile."""
    import jax

    cache_dir = os.environ.get(_ENV_VAR)
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # Exported so subprocess replicas and bench workers share it.
        os.environ[_ENV_VAR] = cache_dir
    # Driver programs are many and small (one per size bin / bucket); the
    # JAX defaults (>= 1 s compiles only) would leave most of a warm run
    # recompiling.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
