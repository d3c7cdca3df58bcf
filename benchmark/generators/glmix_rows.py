"""MovieLens-shaped GLMix rows, made on the host.

Copied from ``bench.py:_movielens_data`` (dense global features, one one-hot
movie feature a row, labels through a planted fixed effect plus a per-user
effect). Changed: generation in chunks of rows (never a float64 [rows, 64]
array whole); an intercept column of ones; and the two laws the original
guessed, which MovieLens contradicts (``zipf(1.3) % users`` gave one user a
fifth of all rows and most users one or two; movies were uniform, with
repeats):

- a user's rows follow a log-normal law over the documented minimum, fitted
  to what GroupLens publishes of ml-10M100K (every user at least 20 ratings,
  143.1 a user on average, none above the most active user's 7,359): user
  rank i of U has ``min - 1 + exp(mu + sigma * z_i)`` rows, z_i the normal
  quantile at (i + 1/2) / U, cut at the maximum, with mu solved so that the
  counts add up to the published number of ratings: no seed in it, so the
  per-user blocks keep their shapes whatever is drawn;
- a user rates a movie once: each user's movies are drawn from a popularity
  law (``(rank + shift) ** -exponent``) without repeats, and where a heavy
  user's draws run out of distinct movies the most popular ones it lacks
  fill the rest.

The configuration's ``data_seed`` draws the rows and ``--seed`` deals the
users' ids in another order: every seed gives the same per-user problems in
other lanes of the entity axis, and the same work. Rows drawn anew from every
seed changed the work at equal iteration counts: a sweep took 3.33 to 4.41 s
by seed (my chip runs, PR 26: the vmapped line search runs as long as its
slowest lane), which no bound can hold (PERF.md, Findings).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Rows(NamedTuple):
    user: np.ndarray  # [n] int32 user code, every code 0..users-1 present
    movie: np.ndarray  # [n] int32, no (user, movie) pair twice
    X: np.ndarray  # [n, d_global + 1] float32, last column ones
    y: np.ndarray  # [n] float32 in {0, 1}


def user_counts(rows: int, users: int, min_rows: int, max_rows: int,
                sigma: float) -> np.ndarray:
    """Rows per user by rank, ascending: the law of the module's docstring.
    Deterministic: no seed."""
    from scipy.special import ndtri

    if users * min_rows > rows or users * max_rows < rows:
        raise ValueError(f"{rows} rows cannot give {users} users between "
                         f"{min_rows} and {max_rows} rows each")
    z = ndtri((np.arange(users) + 0.5) / users)

    def counts(mu):
        return np.minimum(
            min_rows - 1 + np.floor(np.exp(mu + sigma * z)), max_rows
        ).astype(np.int64).clip(min_rows)

    lo, hi = -20.0, 20.0
    for _ in range(80):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if counts(mid).sum() <= rows else (lo, mid)
    out = counts(lo)
    # what flooring left over goes one row each to the largest users that
    # are still under the maximum
    left = rows - int(out.sum())
    room = np.flatnonzero(out < max_rows)[::-1]
    while left > 0:
        take = room[:left]
        out[take] += 1
        left -= len(take)
        room = room[out[room] < max_rows]
    return out


def movies_of_users(counts: np.ndarray, movies: int, exponent: float,
                    shift: float, rng) -> np.ndarray:
    """For user u, ``counts[u]`` distinct movies, drawn from the popularity
    law in order of drawing; movie ids are popularity ranks. Returns the
    movies user-major: user 0's, then user 1's, ..."""
    if counts.max() > movies:
        raise ValueError("a user cannot rate more movies than there are")
    p = (np.arange(movies) + shift) ** -float(exponent)
    cdf = np.cumsum(p / p.sum())
    draws = counts + counts // 2 + 16
    owner = np.repeat(np.arange(len(counts), dtype=np.int64), draws)
    drawn = np.minimum(np.searchsorted(cdf, rng.random(len(owner))),
                       movies - 1)
    key, first = np.unique(owner * movies + drawn, return_index=True)
    order = np.argsort(first, kind="stable")  # back into drawing order
    key = key[order]
    owner = key // movies  # ascending: drawing order is user-major
    got = np.bincount(owner, minlength=len(counts))
    starts = np.concatenate([[0], np.cumsum(got)[:-1]])
    keep = np.arange(len(key)) - starts[owner] < counts[owner]
    owner, movie = owner[keep], (key % movies)[keep]
    short = np.flatnonzero(got < counts)
    if len(short):
        have = np.minimum(got, counts)
        at = np.concatenate([[0], np.cumsum(have)])
        fills = [np.setdiff1d(np.arange(movies), movie[at[u]:at[u + 1]],
                              assume_unique=True)[:counts[u] - have[u]]
                 for u in short]
        owner = np.concatenate([owner, np.repeat(short, [len(f) for f in
                                                         fills])])
        movie = np.concatenate([movie] + fills)
        movie = movie[np.argsort(owner, kind="stable")]
    return movie.astype(np.int32)


def make_rows(config: dict, seed: int) -> Rows:
    n, users, movies = (int(config["rows"]), int(config["users"]),
                        int(config["movies"]))
    d, chunk = int(config["global_features"]), int(config["rows_per_chunk"])
    data_seed = int(config["data_seed"])
    rng = np.random.default_rng([data_seed, 0])
    counts = user_counts(n, users, int(config["min_rows_per_user"]),
                         int(config["max_rows_per_user"]),
                         float(config["activity_sigma"]))
    counts = counts[rng.permutation(users)]  # dealt to the users
    movie = movies_of_users(counts, movies,
                            float(config["movie_popularity_exponent"]),
                            float(config["movie_popularity_shift"]), rng)
    user = np.repeat(np.arange(users, dtype=np.int32), counts)
    order = rng.permutation(n)
    user, movie = user[order], movie[order]
    w_global = rng.normal(size=d).astype(np.float32)
    user_effect = (float(config["user_effect_scale"])
                   * rng.normal(size=users)).astype(np.float32)

    relabel = np.random.default_rng([int(seed), 2]).permutation(users)
    out = Rows(relabel.astype(np.int32)[user], movie,
               np.empty((n, d + 1), np.float32), np.empty(n, np.float32))
    for c, lo in enumerate(range(0, n, chunk)):
        rng = np.random.default_rng([data_seed, 1, c])
        size = min(chunk, n - lo)
        at = slice(lo, lo + size)
        Xg = rng.standard_normal((size, d), np.float32) / np.float32(
            np.sqrt(d))
        logits = Xg @ w_global + user_effect[user[at]]
        out.X[at, :d] = Xg
        out.X[at, d] = 1.0
        out.y[at] = rng.random(size, np.float32) < 1.0 / (1.0 + np.exp(
            -logits))
    return out


def active_rows(user: np.ndarray, cap: int, sample_seed: int):
    """The rows each user's model is trained on, and their weights: a
    uniform sample of at most ``cap`` rows a user, each weighted by the
    user's rows over its sampled rows. Stated in the configuration as the
    rule both sides follow: the rows with the ``cap`` lowest keys of
    ``numpy.random.default_rng(sample_seed).random(rows)`` within a user.
    Returns (row indices of the active rows, their weights)."""
    n = len(user)
    keys = np.random.default_rng(sample_seed).random(n)
    order = np.lexsort((keys, user))
    sorted_user = user[order]
    counts = np.bincount(sorted_user)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.arange(n) - starts[sorted_user]
    keep = rank < cap
    rows = order[keep]
    kept = np.minimum(counts, cap)
    weight = (counts / np.maximum(kept, 1))[sorted_user[keep]]
    return rows, weight.astype(np.float32)
