"""The sparse passes of a TRON fit as a share of their HBM roofline: the
least time the fit's evaluations and CG steps could take
(``rooflines_glm_tron.glm_tron_fit_floor``: ``rooflines.bytes_valuegrad(E,
d, n)`` an evaluation, the same plus 4 B a row a Hessian-vector product,
over the peak bytes/s; shapes and the program's own counts, whichever kernel
ran; the curvature pass is not counted) over the device-busy time of one
traced fit."""


def read(run):
    floor, trace = run.get("floor"), run.get("trace")
    if not floor or "passes_seconds" not in floor:
        return None
    if not trace or not trace["busy_s"] or not run["traced_steps"]:
        return None
    return 100.0 * floor["passes_seconds"] / (
        trace["busy_s"] / run["traced_steps"]
    )
