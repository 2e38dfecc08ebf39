#!/usr/bin/env python3
"""Readings that a cell's limits are set from, many seeds in one process.

    python benchmarks/calibrate.py --workload <cell> --seeds 11,12,... \
        [--control-seeds 3] [--out chiprun_out/calibrate_<cell>.jsonl]

For every seed: set-up as a run does, ONE timed-path step, then the program
is freed and the plain reference follows the same fit; the numbers compared
are printed (the lower readings).  For the first ``--control-seeds`` seeds
the reference is also put in the program's place twice and read against
itself: computed in bfloat16 (the control: must fail a number) and with
every other row left out and the rest weighted double (the planted fault:
half the batch missing, the mean taken over the rest).  A training cell's
readings need no measured window, so this pays set-up once a seed and
nothing else.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--out", default=None)
    parser.add_argument("--time-second-step", action="store_true",
                        help="also time one more step (no compile in it)")
    parser.add_argument("--cpu-rehearsal", action="store_true")
    args = parser.parse_args()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    from benchmarks import run as harness
    from photon_tpu.utils import compilation_cache

    spec = harness.load_cell(args.workload)
    harness.device_or_exit(spec["cell"]["chips"], args.cpu_rehearsal)
    compilation_cache.enable()
    config, traffic = spec["config"], spec["traffic"]
    if args.cpu_rehearsal:
        config = dict(config, sizes=dict(config["sizes"],
                                         **config["rehearsal_sizes"]))
    runner = harness.load_module(spec["runner_dir"], traffic["runner"])
    out = open(args.out, "a") if args.out else None
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        state = runner.setup(config, traffic, seed, harness.Clock())
        t_fit = time.monotonic()
        runner.step(state)  # the first step also compiles or loads programs
        fit_s = time.monotonic() - t_fit
        second_s = None
        if args.time_second_step:
            t_fit = time.monotonic()
            runner.step(state)
            second_s = time.monotonic() - t_fit
        got = runner.produced(state)
        selected = runner.counters(state)["counters"]
        runner.release(state)
        t_ref = time.monotonic()
        want = runner.reference(state)
        ref_s = time.monotonic() - t_ref
        line = {"workload": args.workload, "seed": seed,
                "program": runner.compare(got, want),
                "first_step_s": fit_s, "second_step_s": second_s,
                "reference_s": ref_s,
                "kernels_selected": {
                    m["labels"]["kernel"]: m["value"] for m in selected
                    if m["name"] == "kernels.selected"
                }}
        if i < args.control_seeds:
            line["control_bf16"] = runner.compare(
                runner.reference(state, lowp=True), want
            )
            n = state.data.fit_rows
            half = np.where(np.arange(n) % 2 == 0, 2.0, 0.0).astype(np.float32)
            line["fault_half_batch"] = runner.compare(
                runner.reference(state, weight=half), want
            )
        line["seconds"] = time.monotonic() - t0
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
        del state, got, want
    return 0


if __name__ == "__main__":
    sys.exit(main())
