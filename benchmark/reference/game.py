"""Plain reference for the full GAME sweep: fixed effect, per-user and
per-item random effects and a factored (matrix-factorisation) random effect,
one coordinate-descent sweep in that order, each coordinate fitted against
the others' scores.

By the equations, not by the program's layouts. For user ``u`` with latent
coefficients ``c_u`` in R^K and the shared projection ``B`` in R^{K x
movies}, a row of ``u`` on movie ``m`` scores ``c_u . B[:, m]`` (one-hot
movie features; entities are named by the rows' ``user`` / ``movie`` ids,
columns by their ``movie_feature`` / ``user_feature`` indices), where the pair (u, m) is among the rows ``u`` trains on;
else 0, as for the per-user coefficients: a user's model lives on the
columns its training rows touch.

- fixed effect: Newton's method to the one minimiser (``reference/glm``);
- per-user and per-item: one-hot rows make an entity's problem fall apart
  into one one-dimensional problem a (user, movie) pair, solved by
  bisection (``reference/glmix.solve_pairs``);
- factored, stage 1: every user's K-dimensional problem to its one
  minimiser by Newton's method on dense ``[rows, K]`` features, the K x K
  systems solved directly;
- factored, stage 2: the projection's problem (convex in ``B`` for fixed
  ``c``) by as many iterations of textbook L-BFGS
  (``reference/glm_sparse.lbfgs``) as the program's budget, from the same
  ``B0``: a budgeted refit ends where its path ends, so this stage follows
  a path, the textbook's.

Float32, products at "highest", users in blocks; imports nothing of the
program. The rows each entity trains on follow the sampling rule the
configuration states (``generators/glmix_rows.active_rows``), on both
sides. ``low_precision=True`` is the control: the products of every stage
with their factors rounded to bfloat16.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.generators.glmix_rows import active_rows
from benchmark.reference import glm, glm_sparse
from benchmark.reference.glmix import fixed_objective, solve_pairs

HIGHEST = jax.lax.Precision.HIGHEST
USERS_PER_BLOCK = 4096


class Pairs(NamedTuple):
    """One side's (entity, column) pairs among the rows its entities train
    on: a user rates a movie once, so a pair is one row."""

    key: np.ndarray  # [P] sorted, entity * columns + column
    row: np.ndarray  # [P] the pair's row
    weight: np.ndarray  # [P] the entity's rows over its sampled rows
    of_row: np.ndarray  # [n] every row's pair, P where it has none


class Fit(NamedTuple):
    objectives: list  # after each update: fixed, per-user, per-item, mf
    w_fixed: np.ndarray  # [d]
    first_fixed: tuple  # the fixed-effect solve: (w, value, |grad|)
    probe: tuple  # the same objective at ``probe_fixed``, and |grad| at 0
    user_key: np.ndarray  # [P] sorted, user * movies + movie
    user_coef: np.ndarray  # [P]
    user_weight: np.ndarray  # [P]
    item_key: np.ndarray  # [Q] sorted, movie * users + user
    item_coef: np.ndarray  # [Q]
    item_weight: np.ndarray  # [Q]
    latent: np.ndarray  # [users, K]
    user_weight_of: np.ndarray  # [users] a user's rows over sampled rows
    projection: np.ndarray  # [K, movies]
    refit_values: list  # the refit's objective at B0 and after every step
    probe_objective: float  # the whole objective at ``probe_model``


def pairs_of(entity, column, columns: int, cap: int, sample_seed: int
             ) -> Pairs:
    rows, weight = active_rows(entity, cap, sample_seed)
    key_all = entity.astype(np.int64) * columns + column
    order = np.argsort(key_all[rows], kind="stable")
    key = key_all[rows][order]
    if len(key) > 1 and np.any(np.diff(key) == 0):
        raise ValueError("an entity holds a column twice among its rows")
    at = np.minimum(np.searchsorted(key, key_all), len(key) - 1)
    of_row = np.where(key[at] == key_all, at, len(key)).astype(np.int32)
    return Pairs(key, rows[order], weight[order], of_row)


def _round(x, low_precision: bool):
    return x.astype(jnp.bfloat16).astype(jnp.float32) if low_precision else x


# --- the factored coordinate -------------------------------------------------


class Blocks(NamedTuple):
    """The rows every user trains on, user-major and padded: [U, N]."""

    movie: np.ndarray  # int32, 0 on padding
    row: np.ndarray  # int32 row of the data, 0 on padding
    weight: np.ndarray  # float32, 0 on padding


def user_blocks(pairs: Pairs, users: int, movies: int) -> Blocks:
    user = (pairs.key // movies).astype(np.int64)
    counts = np.bincount(user, minlength=users)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(len(user)) - starts[user]  # keys are sorted by user
    width = int(counts.max())
    movie = np.zeros((users, width), np.int32)
    row = np.zeros((users, width), np.int32)
    weight = np.zeros((users, width), np.float32)
    movie[user, slot] = pairs.key % movies
    row[user, slot] = pairs.row
    weight[user, slot] = pairs.weight
    return Blocks(movie, row, weight)


def _latent_terms(c, F, o, y, w, l2, low_precision: bool):
    z = jnp.einsum("unk,uk->un", F, _round(c, low_precision),
                   precision=HIGHEST) + o
    loss, p, curv = glm.logistic_terms(z, y)
    value = jnp.sum(w * loss, axis=1) + 0.5 * l2 * jnp.sum(c * c, axis=1)
    return value, p, curv


@functools.partial(jax.jit, static_argnames=("steps", "low_precision"))
def solve_latent(B, movie, o, y, w, l2, *, steps: int, low_precision: bool):
    """argmin_c sum_n w[u,n] logistic(o[u,n] + c . B[:, movie[u,n]], y[u,n])
    + l2/2 |c|^2 for every user of a block, by ``steps`` Newton steps from
    zero, each tried whole, halved and quartered ... and the first that does
    not raise the user's objective taken."""
    F = _round(B.T[movie], low_precision)  # [U, N, K]
    k = B.shape[0]
    eye = jnp.eye(k, dtype=jnp.float32)
    scales = 0.5 ** jnp.arange(8, dtype=jnp.float32)

    def body(_, c):
        value, p, curv = _latent_terms(c, F, o, y, w, l2, low_precision)
        g = jnp.einsum("unk,un->uk", F, w * (p - y),
                       precision=HIGHEST) + l2 * c
        H = jnp.einsum("unk,un,unj->ukj", F, w * curv, F,
                       precision=HIGHEST) + l2 * eye
        step = jnp.linalg.solve(H, g[..., None])[..., 0]
        trials = c[None] - scales[:, None, None] * step[None]  # [T, U, K]
        values = jax.vmap(lambda t: _latent_terms(
            t, F, o, y, w, l2, low_precision)[0])(trials)  # [T, U]
        ok = values <= value[None] * (1 + 1e-6)
        first = jnp.argmax(ok, axis=0)
        taken = jnp.take_along_axis(
            trials, first[None, :, None], axis=0)[0]
        return jnp.where(jnp.any(ok, axis=0)[:, None], taken, c)

    return jax.lax.fori_loop(
        0, steps, body, jnp.zeros((movie.shape[0], k), jnp.float32))


@functools.partial(jax.jit, static_argnames=("low_precision",))
def refit_sums(B, c, movie, o, y, w, *, low_precision: bool):
    """(sum of weighted losses, d/dB of it as [movies, K]) over one block of
    users at projection ``B`` [K, movies] and latent coefficients ``c``."""
    F = _round(B.T[movie], low_precision)  # [U, N, K]
    cc = _round(c, low_precision)
    z = jnp.einsum("unk,uk->un", F, cc, precision=HIGHEST) + o
    loss, p, _ = glm.logistic_terms(z, y)
    r = _round(w * (p - y), low_precision)
    grad = jnp.zeros((B.shape[1], B.shape[0]), jnp.float32).at[movie].add(
        r[:, :, None] * cc[:, None, :])
    return jnp.sum(w * loss), grad


@jax.jit
def latent_scores(B, c, movie):
    """c_u . B[:, m] for every (user, slot) of a block."""
    return jnp.einsum("unk,uk->un", B.T[movie], c, precision=HIGHEST)


def _by_block(users: int):
    return [slice(lo, min(lo + USERS_PER_BLOCK, users))
            for lo in range(0, users, USERS_PER_BLOCK)]


# --- the sweep --------------------------------------------------------------


def fit(rows, users: int, movies: int, cap: int, sample_seed: int,
        l2: dict, B0: np.ndarray, refit_iterations: int, *, block: int,
        low_precision: bool = False, pair_steps: int = 60,
        latent_steps: int = 20, probe_fixed=None, probe_model=None,
        no_item_exchange: bool = False, refit_drops_factor: bool = False,
        stale_projection: bool = False, row_weight=None) -> Fit:
    """One sweep fixed -> per-user -> per-item -> factored from zero state
    and ``B0``. ``l2`` holds the five weights (``fixed``, ``per_user``,
    ``per_item``, ``latent``, ``projection``). The three flags plant the
    faults the comparison has to catch: the per-item coordinate's scores
    left out of the offsets the factored coordinate sees; the refit's
    gradient without its last latent factor; the refit's result dropped, so
    that the coordinate's state and scores hold ``B0``. ``row_weight`` plants
    a fourth: every solve trains on the rows under these weights (rows left
    out, others weighed twice). The objectives are always those of the whole
    model on the whole data. ``probe_model`` is a
    whole model from elsewhere (``w_fixed``, ``pairs`` of both sides as
    (keys, coefficients), ``latent_by_user``, ``projection``): the whole
    objective is evaluated there too, scores and penalties by the equations,
    which follows no solver's path."""
    n = len(rows.y)
    user_pairs = pairs_of(rows.user, rows.movie_feature, movies, cap,
                          sample_seed)
    item_pairs = pairs_of(rows.movie, rows.user_feature, users, cap,
                          sample_seed)
    X, y = jnp.asarray(rows.X), jnp.asarray(rows.y)
    ones, zeros = jnp.ones_like(y), jnp.zeros_like(y)
    planted = (np.ones(n, np.float32) if row_weight is None
               else np.asarray(row_weight, np.float32))
    trained = jnp.asarray(planted)
    B0 = np.asarray(B0, np.float32)
    k = B0.shape[0]
    penalties = {"mf": 0.5 * l2["projection"] * float(
        np.sum(B0.astype(np.float64) ** 2))}
    scores = {name: np.zeros(n, np.float32)
              for name in ("fixed", "per-user", "per-item", "mf")}
    objectives = []

    def total(*names):
        return sum(scores[name] for name in names)

    def objective():
        loss, _, _ = glm.logistic_terms(jnp.asarray(total(*scores)), y)
        return float(jnp.sum(loss)) + sum(penalties.values())

    def solve_side(pairs: Pairs, seen, l2_side: float):
        count = len(pairs.key)
        pad = -count % (1 << 18)
        coef = np.asarray(solve_pairs(
            jnp.asarray(np.pad(seen[pairs.row], (0, pad)))[:, None],
            jnp.asarray(np.pad(rows.y[pairs.row], (0, pad)))[:, None],
            jnp.asarray(np.pad(pairs.weight * planted[pairs.row],
                               (0, pad)))[:, None],
            jnp.float32(l2_side), steps=pair_steps,
            low_precision=low_precision))[:count]
        return coef, np.append(coef, np.float32(0))[pairs.of_row]

    # fixed effect
    w, _ = glm.newton(X, y, zeros, trained, l2["fixed"], block=block,
                      low_precision=low_precision)
    first_fixed = (w,) + fixed_objective(X, y, trained, w, l2["fixed"],
                                         block=block,
                                         low_precision=low_precision)
    scores["fixed"] = np.asarray(glm._dot(X, jnp.asarray(w, jnp.float32),
                                          low_precision))
    penalties["fixed"] = 0.5 * l2["fixed"] * float(w @ w)
    objectives.append(objective())

    # per-user, then per-item: each against everything fitted so far
    user_coef, scores["per-user"] = solve_side(
        user_pairs, total("fixed"), l2["per_user"])
    penalties["per-user"] = 0.5 * l2["per_user"] * float(
        user_coef.astype(np.float64) @ user_coef)
    objectives.append(objective())
    item_coef, scores["per-item"] = solve_side(
        item_pairs, total("fixed", "per-user"), l2["per_item"])
    penalties["per-item"] = 0.5 * l2["per_item"] * float(
        item_coef.astype(np.float64) @ item_coef)
    objectives.append(objective())

    # factored: the latent coefficients against B0, then the projection
    blocks = user_blocks(user_pairs, users, movies)
    seen = total("fixed", "per-user") if no_item_exchange else total(
        "fixed", "per-user", "per-item")
    o_at = np.where(blocks.weight > 0, seen[blocks.row], 0).astype(
        np.float32)
    y_at = rows.y[blocks.row]
    # every block of users on the device once: (movies, offsets, labels,
    # weights) of the rows they train on
    parts = [tuple(jnp.asarray(a[s]) for a in (
        blocks.movie, o_at, y_at, blocks.weight * planted[blocks.row]))
             for s in _by_block(users)]
    B0_dev = jnp.asarray(B0)
    latent_parts = [solve_latent(
        B0_dev, *part, jnp.float32(l2["latent"]), steps=latent_steps,
        low_precision=low_precision) for part in parts]
    latent = np.concatenate([np.asarray(c) for c in latent_parts])

    def refit_objective(flat):
        B = jnp.asarray(flat.reshape(k, movies), jnp.float32)
        value, grad = 0.0, np.zeros((movies, k))
        for c, part in zip(latent_parts, parts):
            v, g = refit_sums(B, c, *part, low_precision=low_precision)
            value += float(v)
            grad += np.asarray(g, np.float64)
        if refit_drops_factor:
            grad[:, k - 1] = 0.0
        return (value + 0.5 * l2["projection"] * float(flat @ flat),
                grad.T.reshape(-1) + l2["projection"] * flat)

    flat, refit_values, _ = glm_sparse.lbfgs(
        refit_objective, B0.reshape(-1).astype(np.float64),
        refit_iterations)
    B = B0 if stale_projection else flat.reshape(k, movies).astype(
        np.float32)

    def objective_at(c, P):
        """The whole objective with the factored coordinate at (c, P)."""
        c, P = np.asarray(c, np.float32), np.asarray(P, np.float32)
        P_dev = jnp.asarray(P)
        at_pairs = np.concatenate([np.asarray(latent_scores(
            P_dev, jnp.asarray(c[s]), part[0]))
            for s, part in zip(_by_block(users), parts)])
        by_row = np.zeros(n + 1, np.float32)
        by_row[np.where(blocks.weight > 0, blocks.row, n)] = at_pairs
        scores["mf"] = by_row[:n]
        penalties["mf"] = (
            0.5 * l2["latent"] * float(np.sum(c.astype(np.float64) ** 2))
            + 0.5 * l2["projection"] * float(
                np.sum(P.astype(np.float64) ** 2)))
        return objective()

    objectives.append(objective_at(latent, B))

    def objective_of(model):
        """The whole objective at ``model``: every coordinate's scores and
        penalty from its coefficients (a pair no entity trains on here
        scores nothing)."""
        w_p = np.asarray(model["w_fixed"], np.float64)
        scores["fixed"] = np.asarray(glm._dot(
            X, jnp.asarray(w_p, jnp.float32), False))
        penalties["fixed"] = 0.5 * l2["fixed"] * float(w_p @ w_p)
        for name, pairs, weight in (
                ("per-user", user_pairs, l2["per_user"]),
                ("per-item", item_pairs, l2["per_item"])):
            keys, values = model["pairs"][name]
            coef = np.zeros(len(pairs.key) + 1, np.float32)
            at = np.minimum(np.searchsorted(pairs.key, keys),
                            len(pairs.key) - 1)
            known = pairs.key[at] == keys
            coef[at[known]] = np.asarray(values)[known]
            scores[name] = coef[pairs.of_row]
            penalties[name] = 0.5 * weight * float(
                np.sum(np.asarray(values, np.float64) ** 2))
        return objective_at(model["latent_by_user"], model["projection"])

    probe_objective = None if probe_model is None else objective_of(
        probe_model)

    probe = None
    if probe_fixed is not None:  # always in full precision, on all rows
        probe = fixed_objective(X, y, ones, probe_fixed, l2["fixed"],
                                block=block) + (fixed_objective(
                                    X, y, ones, np.zeros(X.shape[1]), 0.0,
                                    block=block)[1],)
    return Fit(objectives, np.asarray(w, np.float64), first_fixed, probe,
               user_pairs.key, user_coef.astype(np.float64),
               user_pairs.weight, item_pairs.key,
               item_coef.astype(np.float64), item_pairs.weight,
               latent.astype(np.float64), blocks.weight.max(axis=1),
               np.asarray(B, np.float64), list(refit_values),
               probe_objective)
