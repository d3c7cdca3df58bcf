"""Plain reference for an L2-regularised logistic GLM.

Straightforward ``jax.numpy`` in float32 with the matrix products at
"highest", computed in blocks of rows so that it fits beside the data. It
imports nothing of the program. The sums follow ``chip_smoke.py``'s
``_reference_sums`` (copied; changed: row blocks, weights and offsets, the
Hessian), with the where-form of the logistic that the TPU needs: on a v5e
``jax.nn.sigmoid`` and ``1/(1+exp(-z))`` are off by a one-sided ulp, which
shows in sums that nearly cancel (PERF.md, PR 22). The minimiser is found by
Newton's method, so it does not depend on the path any solver of the
program takes: an L2-regularised logistic objective has one minimiser.

The control of the comparison is this same code with ``low_precision=True``:
X and the coefficients rounded to bfloat16 before every product, sums kept
in float32. That is the step below float32 that tempts a later change.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, low_precision: bool):
    if low_precision:
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.matmul(a, b, precision=HIGHEST)


def logistic_terms(z, y):
    """Pointwise loss, probability and curvature at margin ``z``."""
    e = jnp.exp(-jnp.abs(z))
    p = jnp.where(z >= 0, 1.0 / (1.0 + e), e / (1.0 + e))
    loss = jnp.maximum(z, 0.0) + jnp.log1p(e) - y * z
    return loss, p, p * (1.0 - p)


@functools.partial(jax.jit, static_argnames=("block", "hessian",
                                             "low_precision"))
def logistic_sums(X, y, offsets, weights, w, *, block: int,
                  hessian: bool = False, low_precision: bool = False):
    """(sum of weighted losses, X^T r, X^T D X or None) over all rows, one
    block of ``block`` rows at a time, and the rows left over last."""
    n, d = X.shape
    w = w.astype(jnp.float32)

    def add(acc, lo, size):
        value, grad, hess = acc
        Xb = jax.lax.dynamic_slice_in_dim(X, lo, size).astype(jnp.float32)
        yb = jax.lax.dynamic_slice_in_dim(y, lo, size)
        ob = jax.lax.dynamic_slice_in_dim(offsets, lo, size)
        wb = jax.lax.dynamic_slice_in_dim(weights, lo, size)
        z = _dot(Xb, w, low_precision) + ob
        loss, p, curv = logistic_terms(z, yb)
        value = value + jnp.sum(wb * loss)
        grad = grad + _dot(wb * (p - yb), Xb, low_precision)
        if hessian:
            # the Hessian only steers Newton's steps (the fixed point is
            # where the gradient vanishes), so one bf16 pass is enough
            hess = hess + jnp.matmul((Xb * (wb * curv)[:, None]).T, Xb)
        return value, grad, hess

    init = (jnp.float32(0.0), jnp.zeros(d, jnp.float32),
            jnp.zeros((d, d) if hessian else (1, 1), jnp.float32))
    acc = jax.lax.fori_loop(
        0, n // block, lambda i, acc: add(acc, i * block, block), init)
    if n % block:
        acc = add(acc, n - n % block, n % block)
    value, grad, hess = acc
    return value, grad, (hess if hessian else None)


def objective(X, y, offsets, weights, w, l2: float, *, block: int,
              low_precision: bool = False):
    """Objective value and gradient at ``w`` as float64 numpy."""
    value, grad, _ = logistic_sums(X, y, offsets, weights,
                                   jnp.asarray(w, jnp.float32), block=block,
                                   low_precision=low_precision)
    w64 = np.asarray(w, np.float64)
    return (float(value) + 0.5 * l2 * float(w64 @ w64),
            np.asarray(grad, np.float64) + l2 * w64)


def newton(X, y, offsets, weights, l2: float, start=None, *, block: int,
           low_precision: bool = False, max_steps: int = 30,
           tolerance: float = 1e-7):
    """The minimiser of ``sum_i weights_i * logistic(x_i . w + offsets_i,
    y_i) + l2/2 |w|^2``: Newton steps, each solved on the host in float64
    and halved while the objective rises, until a step is shorter than
    ``tolerance`` of the coefficients' norm. Returns (w, steps)."""
    d = X.shape[1]
    w = np.zeros(d) if start is None else np.asarray(start, np.float64)
    eye = np.eye(d)
    for step_no in range(1, max_steps + 1):
        value, grad, hess = logistic_sums(
            X, y, offsets, weights, jnp.asarray(w, jnp.float32),
            block=block, hessian=True, low_precision=low_precision)
        f = float(value) + 0.5 * l2 * float(w @ w)
        g = np.asarray(grad, np.float64) + l2 * w
        step = np.linalg.solve(np.asarray(hess, np.float64) + l2 * eye, g)
        scale = 1.0
        for _ in range(6):  # a Newton step overshoots only far from home
            trial = w - scale * step
            f_trial, _ = objective(X, y, offsets, weights, trial, l2,
                                   block=block, low_precision=low_precision)
            if f_trial <= f + 1e-6 * abs(f) or scale * np.linalg.norm(
                    step) <= 1e-4 * max(np.linalg.norm(w), 1e-30):
                break
            scale *= 0.5
        w = w - scale * step
        if scale * np.linalg.norm(step) <= tolerance * np.linalg.norm(w):
            return w, step_no
    return w, max_steps
