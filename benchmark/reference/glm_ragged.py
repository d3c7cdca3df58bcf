"""Plain reference for an elastic-net logistic GLM over row-sparse data whose
rows differ in length, and a textbook OWL-QN.

The data are flat arrays, one entry a stored cell: the row it lies in, its
column, its value. Margins are a ``segment_sum`` of ``w[column] * value``
over the rows, the gradient a ``zeros(dim).at[column].add(value * r[row])``.
Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one block of rows at a time so
that it fits beside the data; it imports nothing of the program. So that
every block has one shape, :func:`flat_blocks` cuts the rows into blocks of
``block`` rows and gives every block room for the fullest one's cells; a
cell of padding lies in the block's row 0 with value 0 and adds nothing.

The objective is ``F(x) = sum_i w_i l(z_i, y_i) + lambda1 ||x||_1 +
(lambda2 / 2) ||x||^2``. At 29.9 million columns no minimiser is
affordable, so the solver is judged by a textbook OWL-QN instead
(:func:`owlqn`, Andrew and Gao, "Scalable training of L1-regularized
log-linear models", ICML 2007), on the host in float64 over this file's own
evaluations: after the same number of iterations from the same start, the
program's coefficients must reach an ``F`` no worse than the textbook's by
more than a stated share of the decrease, and leave about as many
coefficients at exactly zero.

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


def flat_blocks(indptr, columns, values, block: int):
    """A CSR matrix's three arrays as ``(rows [B, C] int32, columns [B, C]
    int32, values [B, C] float32)`` on the host: block ``b`` holds the cells
    of rows ``[b * block, (b + 1) * block)``, ``rows`` counted from the
    block's first, ``C`` the fullest block's cells; cells of padding lie in
    row 0 with value 0. The number of rows must be a multiple of
    ``block``."""
    indptr = np.asarray(indptr, np.int64)
    n = len(indptr) - 1
    if n % block:
        raise ValueError("rows must be a multiple of the block")
    edges = indptr[::block]
    cells = np.diff(edges)
    cap = int(cells.max()) if len(cells) else 0
    shape = (len(cells), cap)
    rows = np.zeros(shape, np.int32)
    cols = np.zeros(shape, np.int32)
    vals = np.zeros(shape, np.float32)
    row_of = np.repeat(np.arange(n, dtype=np.int32) % block,
                       np.diff(indptr))
    for b, (lo, c) in enumerate(zip(edges[:-1], cells)):
        rows[b, :c] = row_of[lo:lo + c]
        cols[b, :c] = columns[lo:lo + c]
        vals[b, :c] = values[lo:lo + c]
    return rows, cols, vals


def _round(x, low_precision: bool):
    return x.astype(jnp.bfloat16).astype(jnp.float32) if low_precision else x


@functools.partial(jax.jit, static_argnames=("low_precision",))
def logistic_sums(rows, cols, vals, y, offsets, weights, w, *,
                  low_precision: bool = False):
    """(sum of weighted losses, X^T r [dim]) over all rows, one block of
    :func:`flat_blocks` at a time; ``y``, ``offsets`` and ``weights`` are
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
        loss, p, _ = logistic_terms(z, yb)
        r = _round(wb * (p - yb), low_precision)
        return (value + jnp.sum(wb * loss),
                grad.at[cols_b].add(vals_b * r[rows_b]))

    with jax.default_matmul_precision("highest"):
        return jax.lax.fori_loop(0, blocks, add, (
            jnp.float32(0.0), jnp.zeros(w.shape[0], jnp.float32)))


def smooth(rows, cols, vals, y, offsets, weights, w, l2: float, *,
           low_precision: bool = False):
    """The smooth part ``sum_i w_i l(z_i, y_i) + (l2 / 2) ||w||^2`` and its
    gradient at ``w``, as float64 numpy."""
    value, grad = logistic_sums(rows, cols, vals, y, offsets, weights,
                                jnp.asarray(w, jnp.float32),
                                low_precision=low_precision)
    w64 = np.asarray(w, np.float64)
    grad = np.asarray(grad, np.float64)
    grad += l2 * w64
    return float(value) + 0.5 * l2 * float(w64 @ w64), grad


# The host's vectors are float64 over every column (240 MB each at 29.9
# million), and a fresh one costs more in page faults than the arithmetic
# that fills it: the functions below write into what they have where that
# keeps the formula readable.


def pseudo_gradient(x, g, l1: float):
    """Andrew and Gao's equation 4: the derivative of ``f + l1 ||x||_1``
    along each axis, one-sided at 0 (0 where 0 is a minimiser along it):
    ``g + l1 sign(x)`` off 0, and at 0 ``g`` shrunk towards 0 by ``l1``."""
    pg = np.sign(x)
    pg *= l1
    pg += g
    shrunk = np.abs(g)
    shrunk -= l1
    np.maximum(shrunk, 0.0, out=shrunk)
    np.copysign(shrunk, g, out=shrunk)
    np.copyto(pg, shrunk, where=x == 0)
    return pg


def penalised(fn, x, l1: float):
    """``(F, smooth gradient, pseudo-gradient)`` at ``x`` for the smooth
    part ``fn(x) -> (value, gradient)``."""
    f, g = fn(x)
    return f + l1 * float(np.abs(x).sum()), g, pseudo_gradient(x, g, l1)


MEMORY = 10  # pairs the two-loop recursion keeps
DECREASE = 1e-4  # the sufficient-decrease constant
MAX_HALVINGS = 30


def owlqn(fn, l1: float, start, iterations: int, at_start=None):
    """``iterations`` iterations of textbook OWL-QN on ``F = f + l1
    ||x||_1`` from ``start``, ``fn(x) -> (f, gradient of f)``. As the paper
    has it: the direction is the two-loop recursion (over the last
    ``MEMORY`` pairs of steps and *smooth*-gradient differences, scaled by
    s.y / y.y) applied to the pseudo-gradient, its components kept only
    where they descend along the pseudo-gradient; a trial point is
    projected onto the orthant of the iterate (of minus the
    pseudo-gradient where the iterate is 0); the step is halved until
    ``F(trial) <= F(x) + DECREASE * pg . (trial - x)``. Returns (x, [F at
    the start and after every iteration], pseudo-gradient norm at x).

    Departures from the paper, each small: the first step is ``1 / ||d||``
    long (the paper's own choice for the first iteration, named here
    because its later steps start at 1, as these do); backtracking halves
    (the paper leaves the factor open); a pair is kept only where s.y >
    1e-10 y.y (the paper assumes convexity keeps it positive); it ends
    early only where no halving descends. ``at_start`` is ``penalised(fn,
    start, l1)`` where the caller has it already."""
    x = np.array(start, np.float64)
    F, g, pg = penalised(fn, x, l1) if at_start is None else at_start
    values, pairs = [F], []
    d, orthant, scratch = (np.empty_like(x) for _ in range(3))
    for _ in range(iterations):
        np.copyto(d, pg)  # the two-loop recursion turns pg into H pg
        alphas = []
        for s, yv in reversed(pairs):
            a = float(s @ d) / float(yv @ s)
            alphas.append(a)
            d -= np.multiply(yv, a, out=scratch)
        if pairs:
            s, yv = pairs[-1]
            d *= float(s @ yv) / float(yv @ yv)
        for (s, yv), a in zip(pairs, reversed(alphas)):
            d += np.multiply(s, a - float(yv @ d) / float(yv @ s),
                             out=scratch)
        # the direction -H pg, kept only where it descends along pg
        np.negative(d, out=d)
        np.copyto(d, 0.0, where=np.multiply(d, pg, out=scratch) >= 0)
        # the orthant: sign(x), or sign(-pg) where x is 0
        np.sign(x, out=orthant)
        np.copyto(orthant, np.negative(np.sign(pg, out=scratch),
                                       out=scratch), where=x == 0)
        step = 1.0 if pairs else 1.0 / max(float(np.linalg.norm(d)), 1e-30)
        for _ in range(MAX_HALVINGS):
            trial = step * d
            trial += x
            np.copyto(trial, 0.0,
                      where=np.multiply(trial, orthant, out=scratch) <= 0)
            F_new, g_new, pg_new = penalised(fn, trial, l1)
            np.subtract(trial, x, out=scratch)  # the step taken
            if F_new <= F + DECREASE * float(pg @ scratch):
                break
            step *= 0.5
        else:
            break
        s, yv = scratch.copy(), g_new - g
        if float(s @ yv) > 1e-10 * float(yv @ yv):
            pairs = (pairs + [(s, yv)])[-MEMORY:]
        x, F, g, pg = trial, F_new, g_new, pg_new
        values.append(F)
    return x, values, float(np.linalg.norm(pg))
