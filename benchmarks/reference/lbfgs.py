"""L-BFGS as the configurations state it, driven from the host.

The algorithm (Photon ML's ``LBFGS``, i.e. breeze's, as the repo documents
it): memory ``m`` of cautious (s, y) pairs, two-loop recursion scaled by
``gamma = s.y / y.y``, Armijo backtracking (c1 = 1e-4, halving) from step 1
— from ``1 / max(|g|, 1)`` while there is no pair yet — steepest descent when
the direction does not descend, stop on the relative function or gradient
tolerance, and two guarded full quasi-Newton steps at the end.  Every
decision is taken on the host from float32 scalars; ``fun`` is any
``w -> (value, grad)`` in ``jax.numpy``.
"""

from __future__ import annotations

import jax.numpy as jnp

ARMIJO_C1 = 1e-4
PAIR_EPS = 1e-10


def _direction(g, pairs, gamma):
    """-H g by the two-loop recursion; ``pairs`` is oldest first."""
    q = g
    alphas = []
    for s, y, rho in reversed(pairs):
        alpha = rho * jnp.dot(s, q)
        q = q - alpha * y
        alphas.append(alpha)
    r = gamma * q
    for (s, y, rho), alpha in zip(pairs, reversed(alphas)):
        beta = rho * jnp.dot(y, r)
        r = r + (alpha - beta) * s
    return -r


def lbfgs(fun, w0, max_iterations, tolerance=1e-7, gradient_tolerance=1e-6,
          history_length=10, max_line_search=25):
    """Returns ``(w, values, grad_norms, iterations)``: the final iterate,
    the objective and gradient norm at the start and after every accepted
    iteration, and the number of iterations run."""
    w = w0
    f, g = fun(w)
    gnorm0 = jnp.linalg.norm(g)
    values, grad_norms = [float(f)], [float(gnorm0)]
    pairs: list = []
    gamma = jnp.float32(1.0)
    iterations = 0
    active = float(gnorm0) != 0.0
    while active:
        d = _direction(g, pairs, gamma)
        dir_deriv = jnp.dot(g, d)
        if float(dir_deriv) >= 0.0:
            d, dir_deriv = -g, -jnp.dot(g, g)
        t = (
            1.0 / jnp.maximum(jnp.linalg.norm(g), 1.0) if not pairs
            else jnp.float32(1.0)
        )
        trials = 0
        while True:
            f_new, g_new = fun(w + t * d)
            ok = bool(
                (f_new <= f + ARMIJO_C1 * t * dir_deriv) & jnp.isfinite(f_new)
            )
            if ok or trials >= max_line_search:
                break
            t, trials = t * 0.5, trials + 1
        w_new = w + t * d
        s, y = w_new - w, g_new - g
        sy = jnp.dot(s, y)
        if ok and float(sy) > PAIR_EPS:
            pairs = (pairs + [(s, y, 1.0 / sy)])[-history_length:]
            gamma = sy / jnp.maximum(jnp.dot(y, y), 1e-30)
        gnorm_new = jnp.linalg.norm(g_new)
        rel = float(jnp.abs(f - f_new) / jnp.maximum(jnp.abs(f), 1e-12))
        converged = rel <= tolerance or float(gnorm_new) <= (
            gradient_tolerance * max(float(gnorm0), 1.0)
        )
        iterations += 1
        active = not (converged or not ok or iterations >= max_iterations)
        if ok:
            w, f, g = w_new, f_new, g_new
            values.append(float(f))
            grad_norms.append(float(gnorm_new))
    for _ in range(2):  # the guarded full-step polish
        step = _direction(g, pairs, gamma)
        near = bool(
            jnp.all(jnp.isfinite(step))
            & (jnp.linalg.norm(step)
               <= 1e-3 * jnp.maximum(jnp.linalg.norm(w), 1.0))
        )
        w_new = w + step if near else w
        f_new, g_new = fun(w_new)
        keep = near and bool(
            jnp.isfinite(f_new) & jnp.all(jnp.isfinite(g_new))
            & (jnp.linalg.norm(g_new) <= jnp.linalg.norm(g))
        )
        if keep:
            w, f, g = w_new, f_new, g_new
    return w, values + [float(f)], grad_norms, iterations
