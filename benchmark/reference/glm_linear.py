"""Plain reference for an L2-regularised linear (squared-loss) GLM.

Straightforward ``jax.numpy`` in float32 with the matrix products at
"highest", in blocks of rows; it imports nothing of the program. The loss
of a row is ``weight / 2 * (x . w + offset - y) ** 2``, so the minimiser has
a closed form that no solver's path enters: the normal equations
``(X^T W X + l2 I) w = X^T W (y - offsets)``, summed over the row blocks on
the device and solved on the host in float64.

The control of the comparison is this same code with ``low_precision=True``:
X, the coefficients and the residuals rounded to bfloat16 before every
product, sums kept in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.glm import _dot


def _blocks(n: int, block: int, add, init):
    block = min(block, n)
    acc = jax.lax.fori_loop(
        0, n // block, lambda i, acc: add(acc, i * block, block), init)
    if n % block:
        acc = add(acc, n - n % block, n % block)
    return acc


@functools.partial(jax.jit, static_argnames=("block", "low_precision"))
def squared_sums(X, y, offsets, weights, w, *, block: int,
                 low_precision: bool = False):
    """(sum of weighted losses, X^T r) over all rows, r the weighted
    residuals."""
    n, d = X.shape
    w = w.astype(jnp.float32)

    def add(acc, lo, size):
        value, grad = acc
        Xb = jax.lax.dynamic_slice_in_dim(X, lo, size).astype(jnp.float32)
        yb = jax.lax.dynamic_slice_in_dim(y, lo, size)
        ob = jax.lax.dynamic_slice_in_dim(offsets, lo, size)
        wb = jax.lax.dynamic_slice_in_dim(weights, lo, size)
        e = _dot(Xb, w, low_precision) + ob - yb
        return (value + 0.5 * jnp.sum(wb * e * e),
                grad + _dot(wb * e, Xb, low_precision))

    return _blocks(n, block, add, (jnp.float32(0.0),
                                   jnp.zeros(d, jnp.float32)))


@functools.partial(jax.jit, static_argnames=("block", "low_precision"))
def normal_sums(X, y, offsets, weights, *, block: int,
                low_precision: bool = False):
    """(X^T W X [d, d], X^T W (y - offsets) [d]) over all rows."""
    n, d = X.shape

    def add(acc, lo, size):
        gram, rhs = acc
        Xb = jax.lax.dynamic_slice_in_dim(X, lo, size).astype(jnp.float32)
        yb = jax.lax.dynamic_slice_in_dim(y, lo, size)
        ob = jax.lax.dynamic_slice_in_dim(offsets, lo, size)
        wb = jax.lax.dynamic_slice_in_dim(weights, lo, size)
        return (gram + _dot((Xb * wb[:, None]).T, Xb, low_precision),
                rhs + _dot(wb * (yb - ob), Xb, low_precision))

    return _blocks(n, block, add, (jnp.zeros((d, d), jnp.float32),
                                   jnp.zeros(d, jnp.float32)))


def objective(X, y, offsets, weights, w, l2: float, *, block: int,
              low_precision: bool = False):
    """Objective value and gradient at ``w`` as float64 numpy."""
    value, grad = squared_sums(X, y, offsets, weights,
                               jnp.asarray(w, jnp.float32), block=block,
                               low_precision=low_precision)
    w64 = np.asarray(w, np.float64)
    return (float(value) + 0.5 * l2 * float(w64 @ w64),
            np.asarray(grad, np.float64) + l2 * w64)


def minimiser(X, y, offsets, weights, l2: float, *, block: int,
              low_precision: bool = False) -> np.ndarray:
    """The closed form, solved on the host in float64."""
    gram, rhs = normal_sums(X, y, offsets, weights, block=block,
                            low_precision=low_precision)
    gram = np.asarray(gram, np.float64)
    return np.linalg.solve(gram + l2 * np.eye(gram.shape[0]),
                           np.asarray(rhs, np.float64))
