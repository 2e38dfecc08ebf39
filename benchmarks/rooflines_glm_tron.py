"""The least work of one TRON fit of a sparse fixed-effect GLM.

Every count is a function of shapes and of the counts the program reported
for the fit, whichever kernel ran:

* **an evaluation** (the start and each trust-region trial:
  ``OptimizerResult.evaluations``): ``rooflines.bytes_valuegrad(E, d, n)`` /
  ``flops_valuegrad(E, n)``, the margins and the transposed reduction;
* **a CG step** (one Hessian-vector product ``Xᵀ(D · X v)``:
  ``OptimizerResult.cg_iterations``): the same two passes over the entries,
  plus the per-row curvature ``D`` read once (4 B a row).

Not counted: the curvature pass that builds ``D(w)`` once a trust-region
iteration (the evaluation at ``w`` has already produced the margins it
needs, so a program that keeps them pays nothing there), and CG's own
vector updates (a few d-long vectors a step: about 0.1 % of a pass at the
cell's size).
"""

from __future__ import annotations

from benchmarks import rooflines

CURVATURE_BYTES_PER_ROW = 4


def bytes_cg_step(entries: float, dim: float, rows: float) -> float:
    return (rooflines.bytes_valuegrad(entries, dim, rows)
            + CURVATURE_BYTES_PER_ROW * rows)


def glm_tron_fit_floor(work: dict, peak: dict) -> dict:
    """``work``: ``entries``, ``dim``, ``rows``, and the fit's
    ``evaluations`` and ``cg_iterations`` as the program reported them."""
    e, d, n = work["entries"], work["dim"], work["rows"]
    flops_pass = rooflines.flops_valuegrad(e, n)
    t_eval, eval_bound = rooflines.least_seconds(
        flops_pass, rooflines.bytes_valuegrad(e, d, n), peak)
    t_cg, cg_bound = rooflines.least_seconds(
        flops_pass, bytes_cg_step(e, d, n), peak)
    passes = (work["evaluations"] * t_eval
              + work["cg_iterations"] * t_cg)
    return {
        "seconds": passes,
        "passes_seconds": passes,
        "flops": (work["evaluations"] + work["cg_iterations"]) * flops_pass,
        "phases": {"valuegrad": eval_bound, "hessian_vector": cg_bound},
    }
