"""Plain reference for GLMix training: exact coordinate descent.

A fixed-effect logistic GLM over the dense global features and one logistic
GLM per user over that user's one-hot movie features, each L2-regularised,
fitted in turn against the other's scores. Every coordinate's problem has
one minimiser, so this follows no solver's path: the fixed effect by
Newton's method (``reference/glm.py``), and each user's model exactly,
because one-hot rows make a user's problem fall apart into one
one-dimensional problem per (user, movie) pair, solved by bisection.
Float32, products at "highest"; imports nothing of the program and takes
nothing it made: the rows come from the benchmark's generator, and the rows
each user trains on from the sampling rule the configuration states
(``generators/glmix_rows.py:active_rows``).

``low_precision=True`` is the control: the fixed effect's products and each
pair's coefficient and residual rounded to bfloat16.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.generators.glmix_rows import active_rows
from benchmark.reference import glm


PAIR_QUANTUM = 1 << 18  # the pairs' arrays are padded to a multiple of it
SLOT_QUANTUM = 4  # and the rows under a pair to a multiple of this


class Fit(NamedTuple):
    objectives: list  # after every update: fixed, per-user, fixed, ...
    w_fixed: np.ndarray  # [d]
    pair_key: np.ndarray  # [P] sorted, user * movies + movie
    pair_coef: np.ndarray  # [P]
    pair_weight: np.ndarray  # [P] its user's rows over its sampled rows
    first_fixed: tuple  # the first fixed-effect solve: (w, value, |grad|)
    probe: tuple  # the same objective at ``probe_fixed``, and |grad| at 0


def _pairs(user, movie, movies: int, cap: int, sample_seed: int):
    """The (user, movie) pairs among the active rows, the dense [P, K]
    layout of the active rows under their pair, and every row's pair."""
    rows, weight = active_rows(user, cap, sample_seed)
    key_all = user.astype(np.int64) * movies + movie
    pair_key, pair_of_active = np.unique(key_all[rows], return_inverse=True)
    num_pairs = len(pair_key)
    order = np.argsort(pair_of_active, kind="stable")
    sorted_pair = pair_of_active[order]
    counts = np.bincount(sorted_pair, minlength=num_pairs)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(len(rows)) - starts[sorted_pair]
    width = int(counts.max())
    row_at = np.zeros((num_pairs, width), np.int32)
    weight_at = np.zeros((num_pairs, width), np.float32)
    row_at[sorted_pair, slot] = rows[order]
    weight_at[sorted_pair, slot] = weight[order]
    # rows of a pair no user trains on score 0: they look up slot P
    at = np.minimum(np.searchsorted(pair_key, key_all), num_pairs - 1)
    pair_of_row = np.where(pair_key[at] == key_all, at, num_pairs).astype(
        np.int32)
    return pair_key, row_at, weight_at, pair_of_row


@functools.partial(jax.jit, static_argnames=("steps", "low_precision"))
def solve_pairs(offsets, y, weight, l2, *, steps: int, low_precision: bool):
    """argmin_b sum_k weight[p,k] * logistic(b + offsets[p,k], y[p,k])
    + l2/2 b^2 for every pair p, by halving a bracket of the derivative's
    sign change ``steps`` times: the derivative rises with b, and its data
    part is at most sum_k weight[p,k] in size, which bounds the root."""
    reach = jnp.sum(weight, axis=1) / l2 + 1.0

    def body(_, bracket):
        lo, hi = bracket
        b = 0.5 * (lo + hi)
        seen = b.astype(jnp.bfloat16).astype(jnp.float32) \
            if low_precision else b
        _, p, _ = glm.logistic_terms(seen[:, None] + offsets, y)
        r = p - y
        if low_precision:
            r = r.astype(jnp.bfloat16).astype(jnp.float32)
        g = jnp.sum(weight * r, axis=1) + l2 * b
        return jnp.where(g < 0, b, lo), jnp.where(g < 0, hi, b)

    lo, hi = jax.lax.fori_loop(0, steps, body, (-reach, reach))
    return 0.5 * (lo + hi)


def fixed_objective(X, y, weights, w, l2_fixed: float, *, block: int,
                    low_precision: bool = False) -> tuple:
    """The fixed effect's own objective and gradient norm at ``w`` with no
    user scores yet: what its first solve of a training ends on."""
    value, grad = glm.objective(X, y, jnp.zeros_like(y), weights, w,
                                l2_fixed, block=block,
                                low_precision=low_precision)
    return value, float(np.linalg.norm(grad))


def fit(rows, movies: int, cap: int, sample_seed: int, sweeps: int,
        l2_fixed: float, l2_user: float, *, block: int,
        low_precision: bool = False, pair_steps: int = 60,
        row_weight=None, exchange: bool = True, probe_fixed=None) -> Fit:
    """``row_weight`` and ``exchange`` plant the faults the comparison has
    to catch (rows left out; the coordinates blind to each other's scores);
    the objectives are always those of the whole data.

    Every gather by row or pair is done on the host: the number of pairs
    changes with the seed, and the TPU's compiler took five minutes over one
    eager gather of that shape (my chip runs, PR 26). The pairs' arrays are
    padded to ``PAIR_QUANTUM`` so that the one program with that shape is
    compiled once for nearly every seed."""
    pair_key, row_at, weight_at, pair_of_row = _pairs(
        rows.user, rows.movie, movies, cap, sample_seed)
    pair_weight = weight_at.max(axis=1)
    if row_weight is not None:
        weight_at = weight_at * np.asarray(row_weight, np.float32)[row_at]
    num_pairs = len(pair_key)
    pad = (-num_pairs % PAIR_QUANTUM, -row_at.shape[1] % SLOT_QUANTUM)
    row_at = np.pad(row_at, ((0, pad[0]), (0, pad[1])))
    weight_at = jnp.asarray(np.pad(weight_at, ((0, pad[0]), (0, pad[1]))))
    y_at = jnp.asarray(rows.y[row_at])

    X, y = jnp.asarray(rows.X), jnp.asarray(rows.y)
    ones = jnp.ones_like(y)
    zeros = jnp.zeros_like(y)
    trained = ones if row_weight is None else jnp.asarray(
        row_weight, jnp.float32)
    l2u = jnp.float32(l2_user)

    w = np.zeros(X.shape[1])
    b = np.zeros(num_pairs, np.float32)
    user_scores = zeros
    objectives, first_fixed = [], None

    def objective():
        value, _ = glm.objective(X, y, user_scores, ones, w, l2_fixed,
                                 block=block, low_precision=low_precision)
        return value + 0.5 * l2_user * float(b.astype(np.float64) @ b)

    for _ in range(sweeps):
        w, _ = glm.newton(X, y, user_scores if exchange else zeros, trained,
                          l2_fixed, w, block=block,
                          low_precision=low_precision)
        objectives.append(objective())
        if first_fixed is None:
            first_fixed = (w,) + fixed_objective(
                X, y, trained, w, l2_fixed, block=block,
                low_precision=low_precision)
        fixed_scores = np.asarray(glm._dot(X, jnp.asarray(w, jnp.float32),
                                           low_precision))
        seen = fixed_scores[row_at] if exchange else np.zeros(
            row_at.shape, np.float32)
        b = np.asarray(solve_pairs(
            jnp.asarray(seen), y_at, weight_at, l2u, steps=pair_steps,
            low_precision=low_precision))[:num_pairs]
        user_scores = jnp.asarray(np.append(b, np.float32(0))[pair_of_row])
        objectives.append(objective())
    probe = None
    if probe_fixed is not None:  # always in full precision, on all rows
        probe = fixed_objective(X, y, ones, probe_fixed, l2_fixed,
                                block=block) + (fixed_objective(
                                    X, y, ones, np.zeros(X.shape[1]), 0.0,
                                    block=block)[1],)
    return Fit(objectives, np.asarray(w, np.float64), pair_key,
               b.astype(np.float64), pair_weight, first_fixed, probe)
