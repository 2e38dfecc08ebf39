"""Shared disk-cache root resolution.

Three host-side caches share one precedence contract — the exchange
routes (``PHOTON_ROUTE_CACHE``), the streamed-chunk layouts
(``PHOTON_STREAM_LAYOUT_CACHE``), and the aligned layouts
(``PHOTON_LAYOUT_CACHE``): a specific env var overrides (value ``"0"``
disables), otherwise they live in subdirectories of the route-cache
root so one knob relocates or disables everything together.  One helper
so the contract cannot drift between hand-rolled copies.
"""

from __future__ import annotations

import os
from typing import Optional

# Anchored to the checkout (this file's location), never to the working
# directory or $HOME: two processes started from different directories
# must share ONE cache, and a sealed machine has no $HOME worth keeping.
CHECKOUT_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_ROUTE_CACHE_ROOT = os.path.join(CHECKOUT_ROOT, ".photon_route_cache")


def resolve_cache_dir(env_name: str, subdir: str) -> Optional[str]:
    """The directory a named cache should use, or None when disabled.

    ``env_name`` (when set in the environment) overrides; its value
    ``"0"`` disables.  Otherwise the cache follows ``PHOTON_ROUTE_CACHE``
    (same ``"0"`` semantics) into ``<route root>/<subdir>`` — with
    ``subdir == ""`` meaning the route root itself (how the route cache
    resolves its own root: an explicit override and the followed root
    coincide there).
    """
    root = os.environ.get(env_name)
    if root == "0":
        return None
    if root is not None:
        return root  # explicit override: use as-is
    base = os.environ.get("PHOTON_ROUTE_CACHE")
    if base == "0":
        return None
    if base is None:
        base = DEFAULT_ROUTE_CACHE_ROOT
    return os.path.join(base, subdir) if subdir else base
