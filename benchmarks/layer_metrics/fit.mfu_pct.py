"""The whole fit's share of the chip's peak: the least time the fit's
algorithm needs (per phase the larger of FLOPs over peak FLOP/s and bytes
over peak bytes/s, ``rooflines.*_fit_floor``) over the wall time of one fit,
idle included, taken over the window's fits that ran with the profiler
off."""


def read(run):
    floor = run.get("floor")
    if not floor or not run.get("seconds_per_step"):
        return None
    return 100.0 * floor["seconds"] / run["seconds_per_step"]
