"""Plain reference for an L2-regularised linear SVM, Rennie's smoothed hinge,
over row-sparse data whose rows differ in length.

The data are the flat arrays of ``glm_ragged.flat_blocks``, one entry a
stored cell: the row it lies in, its column, its value. Margins are a
``segment_sum`` of ``w[column] * value`` over the rows, the gradient a
``zeros(dim).at[column].add(value * r[row])``. Straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
one block of rows at a time so that it fits beside the data; it imports
nothing of the program.

With labels in {0, 1} mapped to ``s = 2y - 1`` and ``t = s z``, the loss is
0 where ``t >= 1``, ``(1 - t)^2 / 2`` where ``0 < t < 1`` and ``1/2 - t``
where ``t <= 0`` (Rennie and Srebro, "Loss functions for preference
levels", 2005), and the objective ``F(w) = sum_i w_i l(z_i, y_i) + (lambda
/ 2) ||w||^2``. At 16.6 million columns no minimiser is affordable, so the
solver is judged by the textbook L-BFGS of ``glm_sparse.lbfgs`` over this
file's evaluations.

The control of the comparison is this same code with ``low_precision=True``:
the values, the coefficients and the rows' residuals rounded to bfloat16
before every product, sums kept in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _round(x, low_precision: bool):
    return x.astype(jnp.bfloat16).astype(jnp.float32) if low_precision else x


def hinge_terms(z, y):
    """(loss, d loss / d z) of the smoothed hinge at margins ``z``, labels
    ``y`` in {0, 1}."""
    s = 2.0 * y - 1.0
    t = s * z
    loss = jnp.where(t >= 1.0, 0.0,
                     jnp.where(t <= 0.0, 0.5 - t, 0.5 * (1.0 - t) ** 2))
    slope = jnp.where(t >= 1.0, 0.0, jnp.where(t <= 0.0, -1.0, t - 1.0))
    return loss, s * slope


@functools.partial(jax.jit, static_argnames=("low_precision",))
def hinge_sums(rows, cols, vals, y, offsets, weights, w, *,
               low_precision: bool = False):
    """(sum of weighted losses, X^T r [dim]) over all rows, one block of
    ``flat_blocks`` at a time; ``y``, ``offsets`` and ``weights`` are
    ``[B * block]``."""
    blocks = rows.shape[0]
    block = y.shape[0] // blocks
    w = _round(w.astype(jnp.float32), low_precision)

    def add(b, acc):
        value, grad = acc
        rows_b, cols_b = rows[b], cols[b]
        vals_b = _round(vals[b], low_precision)
        yb = jax.lax.dynamic_slice_in_dim(y, b * block, block)
        ob = jax.lax.dynamic_slice_in_dim(offsets, b * block, block)
        wb = jax.lax.dynamic_slice_in_dim(weights, b * block, block)
        z = jax.ops.segment_sum(w[cols_b] * vals_b, rows_b,
                                num_segments=block,
                                indices_are_sorted=True) + ob
        loss, slope = hinge_terms(z, yb)
        r = _round(wb * slope, low_precision)
        return (value + jnp.sum(wb * loss),
                grad.at[cols_b].add(vals_b * r[rows_b]))

    with jax.default_matmul_precision("highest"):
        return jax.lax.fori_loop(0, blocks, add, (
            jnp.float32(0.0), jnp.zeros(w.shape[0], jnp.float32)))


def objective(rows, cols, vals, y, offsets, weights, w, l2: float, *,
              low_precision: bool = False):
    """``F`` and its gradient at ``w``, as float64 numpy."""
    value, grad = hinge_sums(rows, cols, vals, y, offsets, weights,
                             jnp.asarray(w, jnp.float32),
                             low_precision=low_precision)
    w64 = np.asarray(w, np.float64)
    grad = np.asarray(grad, np.float64)
    grad += l2 * w64
    return float(value) + 0.5 * l2 * float(w64 @ w64), grad
