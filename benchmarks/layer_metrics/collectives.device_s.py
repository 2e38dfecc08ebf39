"""Device seconds of one traced fit inside collective operations
(all-reduce, all-gather, reduce-scatter, all-to-all, collective-permute and
their ``-start`` / ``-done`` halves): the union of their intervals on each
device, mean over the mesh's devices.  The mesh runner reduces the trace
(``benchmarks/trace_collectives.py``) and hands the number on under
``mesh_trace``; a run with no trace, or a runner that reduces none, reads
nothing."""


def read(run):
    mesh = run["counters"].get("mesh_trace")
    if not mesh or not mesh["devices"] or not run.get("traced_steps"):
        return None
    return mesh["collective_s"] / run["traced_steps"]
