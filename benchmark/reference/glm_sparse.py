"""Plain reference for an L2-regularised logistic GLM over padded row-sparse
data: margins by gather, the gradient by ``zeros(dim).at[ids].add(...)``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, in blocks of rows so that it
fits beside the data; it imports nothing of the program. The data are the
benchmark's own planes ``ids``/``vals`` ``[slots, rows]``: slot k of row i
holds column ``ids[k, i]`` with value ``vals[k, i]``.

At 1,000,000 columns no Newton step is affordable (a 1M x 1M Hessian), so
there is no minimiser to compare with. The solver is judged by a textbook
L-BFGS instead (:func:`lbfgs`: the two-loop recursion of Nocedal and Wright,
algorithm 7.4, with Armijo backtracking, on the host in float64 over this
file's own evaluations): after the same number of iterations from the same
start, the program's coefficients must reach an objective no worse than the
textbook's by more than a stated share of the decrease.

The control of the comparison is this same code with ``low_precision=True``:
the values, the coefficients and the rows' residuals rounded to bfloat16
before every product, sums kept in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.glm import logistic_terms


def _round(x, low_precision: bool):
    return x.astype(jnp.bfloat16).astype(jnp.float32) if low_precision else x


@functools.partial(jax.jit, static_argnames=("block", "low_precision"))
def logistic_sums(ids, vals, y, offsets, weights, w, *, block: int,
                  low_precision: bool = False):
    """(sum of weighted losses, X^T r [dim]) over all rows, one block of
    ``block`` rows at a time, and the rows left over last."""
    n = ids.shape[1]
    block = min(block, n)
    w = _round(w.astype(jnp.float32), low_precision)

    def add(acc, lo, size):
        value, grad = acc
        ids_b = jax.lax.dynamic_slice_in_dim(ids, lo, size, axis=1)
        vals_b = _round(jax.lax.dynamic_slice_in_dim(
            vals, lo, size, axis=1).astype(jnp.float32), low_precision)
        yb = jax.lax.dynamic_slice_in_dim(y, lo, size)
        ob = jax.lax.dynamic_slice_in_dim(offsets, lo, size)
        wb = jax.lax.dynamic_slice_in_dim(weights, lo, size)
        z = jnp.sum(w[ids_b] * vals_b, axis=0) + ob
        loss, p, _ = logistic_terms(z, yb)
        r = _round(wb * (p - yb), low_precision)
        return (value + jnp.sum(wb * loss),
                grad.at[ids_b].add(vals_b * r[None, :]))

    with jax.default_matmul_precision("highest"):
        acc = (jnp.float32(0.0), jnp.zeros(w.shape[0], jnp.float32))
        acc = jax.lax.fori_loop(
            0, n // block, lambda i, acc: add(acc, i * block, block), acc)
        if n % block:
            acc = add(acc, n - n % block, n % block)
    return acc


def objective(ids, vals, y, offsets, weights, w, l2: float, *, block: int,
              low_precision: bool = False):
    """Objective value and gradient at ``w`` as float64 numpy."""
    value, grad = logistic_sums(ids, vals, y, offsets, weights,
                                jnp.asarray(w, jnp.float32), block=block,
                                low_precision=low_precision)
    w64 = np.asarray(w, np.float64)
    return (float(value) + 0.5 * l2 * float(w64 @ w64),
            np.asarray(grad, np.float64) + l2 * w64)


MEMORY = 10  # pairs the two-loop recursion keeps
ARMIJO = 1e-4  # sufficient-decrease constant
MAX_HALVINGS = 30


def lbfgs(fn, start, iterations: int, at_start=None):
    """``iterations`` iterations of textbook L-BFGS on ``fn(w) -> (value,
    gradient)`` from ``start``: the two-loop recursion over the last
    ``MEMORY`` pairs scaled by s.y / y.y, the first step 1 / |g| long, every
    step halved until the Armijo condition holds. Returns (w, [value at the
    start and after every iteration], gradient norm at w). Ends early only
    where no halving descends. ``at_start`` is ``fn(start)`` where the
    caller has it already (a pass over the rows saved)."""
    w = np.asarray(start, np.float64)
    f, g = fn(w) if at_start is None else at_start
    values, pairs = [f], []
    for _ in range(iterations):
        q = g.copy()
        alphas = []
        for s, yv in reversed(pairs):
            a = float(s @ q) / float(yv @ s)
            alphas.append(a)
            q -= a * yv
        if pairs:
            s, yv = pairs[-1]
            q *= float(s @ yv) / float(yv @ yv)
        for (s, yv), a in zip(pairs, reversed(alphas)):
            q += (a - float(yv @ q) / float(yv @ s)) * s
        direction = -q
        slope = float(g @ direction)
        step = 1.0 if pairs else 1.0 / max(float(np.linalg.norm(g)), 1e-30)
        for _ in range(MAX_HALVINGS):
            f_new, g_new = fn(w + step * direction)
            if f_new <= f + ARMIJO * step * slope:
                break
            step *= 0.5
        else:
            break
        s, yv = step * direction, g_new - g
        if float(s @ yv) > 1e-10 * float(yv @ yv):
            pairs = (pairs + [(s, yv)])[-MEMORY:]
        w, f, g = w + s, f_new, g_new
        values.append(f)
    return w, values, float(np.linalg.norm(g))
