#!/usr/bin/env python3
"""Record the small trace that ``test_trace_reduce.py`` checks the reduction
on.  Run on the chip (``chiprun -- python benchmarks/tests/record_small_trace.py``):
three jitted steps of a 512x512 matmul chain, 20 ms of host sleep between
them under a ``bench.sleep`` annotation, all inside ``bench.step``.  Writes
``chiprun_out/small_trace/`` and prints what the reduction reads there; the
``.xplane.pb`` is then copied to ``benchmarks/tests/data/small_trace.xplane.pb``
and the printed numbers into the test."""

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks import trace_reduce  # noqa: E402

out = os.path.join(ROOT, "chiprun_out", "small_trace")
shutil.rmtree(out, ignore_errors=True)


@jax.jit
def small_step(x):
    for _ in range(4):
        x = jnp.tanh(x @ x)
    return x


x = jnp.ones((512, 512), jnp.float32) * 0.01
jax.block_until_ready(small_step(x))
options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0
jax.profiler.start_trace(out, profiler_options=options)
for _ in range(3):
    with jax.profiler.TraceAnnotation("bench.step"):
        jax.block_until_ready(small_step(x))
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.02)
jax.profiler.stop_trace()
path = trace_reduce.find_xplane(out)
print(os.path.getsize(path), "bytes", path)
print(json.dumps({"describe": trace_reduce.describe(path),
                  "reduce": trace_reduce.reduce(path)}, indent=1))
