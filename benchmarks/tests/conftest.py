"""Run by hand: ``python -m pytest benchmarks/tests -q`` (a few minutes on
the host).  Not part of tier-1.  Everything here runs on the CPU at tiny
sizes; no number it produces is a device number."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
