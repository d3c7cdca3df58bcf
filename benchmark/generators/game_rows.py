"""MovieLens-20M-shaped rows for the full GAME model, one chip's share of
the users, made on the host.

The deployment deals the 138,493 users of ml-20m over the four chips of one
v5e host; this chip holds every fourth user of a ``data_seed`` shuffle and
all of their rows. The laws are ``glmix_rows``' (imported, not copied),
fitted to what GroupLens publishes of ml-20m:

- rows per user: the log-normal law over the documented minimum of 20,
  over ALL 138,493 users (so that the total is the published 20,000,263),
  cut at the most active user's count; the share then takes its users'
  counts, whatever they add up to;
- a user rates a movie once: each user's movies come without repeats from
  the popularity law ``(rank + shift) ** -exponent`` over all 26,744 rated
  movies, whose two constants are solved (:func:`popularity_constants`) so
  that the most popular movie expects the published head's share of all
  ratings and the least popular one rating of the whole data set;
- labels: Bernoulli through the logistic of a planted fixed effect + a
  normal effect per user + a normal effect per movie + a planted
  low-rank user-movie product (the structure the four coordinates fit).

``data_seed`` draws the rows; ``--seed`` never draws rows: it picks one of the
configuration's ``lane_seeds`` (by ``--seed`` modulo their number), and that
one deals the users' and the movies' entity ids in another order: the same
per-entity problems in other lanes of both entity axes, the same block
shapes, the same work (PERF.md section 4, "the seed and the work"). The
dealings are listed, not drawn at large, because one at large does change
the work: of five fresh seeds dealt freely, two ran one bucket of the latent
stage 20 and 40 rounds longer (a marginal lane's line search fails in
another iteration: sweeps of 2.67, 2.73 and 2.78 s; my chip runs, PR 33), on
the tree before and after the review round alike. The feature columns (a
movie as a feature of its user's rows, a user as a feature of its movie's
rows, the projection's columns) keep the data's own numbering under every
seed: an entity's id and a feature's index are two namespaces, and dealing
the columns too re-orders the sums inside every entity's block, which moves
a marginal lane's line search by a trial and with it the rounds the whole
bucket runs (sweeps of 2.68 to 2.75 s by seed; my chip runs, PR 33).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from benchmark.generators import glmix_rows


class Rows(NamedTuple):
    user: np.ndarray  # [n] int32 user id, every id 0..users-1 present
    movie: np.ndarray  # [n] int32 movie id in 0..movies-1
    X: np.ndarray  # [n, d_global + 1] float32, last column ones
    y: np.ndarray  # [n] float32 in {0, 1}
    # the same users and movies as feature columns, in the data's own
    # numbering whatever the seed; no (user, movie) pair twice
    user_feature: np.ndarray  # [n] int32 in 0..users-1
    movie_feature: np.ndarray  # [n] int32 in 0..movies-1


def popularity_constants(movies: int, ratings: int, head: int) -> tuple:
    """(exponent, shift) of the law ``p(rank) ~ (rank + shift) **
    -exponent`` over ``movies`` ranks under which the first rank expects
    ``head`` of ``ratings`` draws and the last expects one. The second
    condition gives the shift from the exponent; the first, that the law
    adds up to one, is solved for the exponent by bisection."""
    ranks = np.arange(movies, dtype=np.float64)

    def shift_of(exponent):
        return (movies - 1) / (float(head) ** (1.0 / exponent) - 1.0)

    def excess(exponent):
        shift = shift_of(exponent)
        return float(np.sum(((ranks + shift) / shift) ** -exponent)
                     - ratings / head)

    lo, hi = 0.5, 8.0
    for _ in range(100):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if excess(mid) < 0 else (lo, mid)
    return lo, shift_of(lo)


def share_counts(config: dict, rng) -> np.ndarray:
    """Rows of each of this chip's users: the whole data set's counts by
    rank, dealt to the users by ``rng``, every ``chips``-th user kept."""
    published = config["published"]
    counts = glmix_rows.user_counts(
        int(published["ratings"]), int(published["users"]),
        int(config["min_rows_per_user"]), int(config["max_rows_per_user"]),
        float(config["activity_sigma"]))
    dealt = counts[rng.permutation(len(counts))]
    return dealt[int(config["chip_index"])::int(config["chips"])]


def make_rows(config: dict, seed: int) -> Rows:
    users, movies = int(config["users"]), int(config["movies"])
    d, chunk = int(config["global_features"]), int(config["rows_per_chunk"])
    rank = int(config["interaction_rank"])
    data_seed = int(config["data_seed"])
    rng = np.random.default_rng([data_seed, 0])
    counts = share_counts(config, rng)
    n = int(counts.sum())
    if len(counts) != users or n != int(config["rows"]):
        raise ValueError(
            f"the share holds {len(counts)} users and {n} rows, the "
            f"configuration states {users} and {config['rows']}")
    movie = glmix_rows.movies_of_users(
        counts, movies, float(config["movie_popularity_exponent"]),
        float(config["movie_popularity_shift"]), rng)
    user = np.repeat(np.arange(users, dtype=np.int32), counts)
    order = rng.permutation(n)
    user, movie = user[order], movie[order]
    w_global = rng.normal(size=d).astype(np.float32)
    user_effect = (float(config["user_effect_scale"])
                   * rng.normal(size=users)).astype(np.float32)
    movie_effect = (float(config["movie_effect_scale"])
                    * rng.normal(size=movies)).astype(np.float32)
    scale = np.float32(float(config["interaction_scale"]) / np.sqrt(rank))
    user_factors = rng.normal(size=(users, rank)).astype(np.float32)
    movie_factors = rng.normal(size=(movies, rank)).astype(np.float32)

    lanes = config["lane_seeds"]
    lanes = int(lanes[int(seed) % len(lanes)])
    user_order = np.random.default_rng([lanes, 2]).permutation(
        users).astype(np.int32)
    movie_order = np.random.default_rng([lanes, 3]).permutation(
        movies).astype(np.int32)
    out = Rows(user_order[user], movie_order[movie],
               np.empty((n, d + 1), np.float32), np.empty(n, np.float32),
               user, movie)
    for c, lo in enumerate(range(0, n, chunk)):
        rng = np.random.default_rng([data_seed, 1, c])
        size = min(chunk, n - lo)
        at = slice(lo, lo + size)
        Xg = rng.standard_normal((size, d), np.float32) / np.float32(
            np.sqrt(d))
        logits = (Xg @ w_global + user_effect[user[at]]
                  + movie_effect[movie[at]]
                  + scale * np.sum(user_factors[user[at]]
                                   * movie_factors[movie[at]], axis=1))
        out.X[at, :d] = Xg
        out.X[at, d] = 1.0
        out.y[at] = rng.random(size, np.float32) < 1.0 / (1.0 + np.exp(
            -logits))
    return out


def starting_projection(config: dict) -> np.ndarray:
    """The factored coordinate's starting projection ``B0`` [K, movies]:
    standard normal / sqrt(K) from ``data_seed``, a column a movie feature,
    the same under every seed."""
    k, movies = int(config["latent_dim"]), int(config["movies"])
    return np.random.default_rng([int(config["data_seed"]), 4]).normal(
        size=(k, movies)).astype(np.float32) / np.float32(np.sqrt(k))


def describe_rows(rows: Rows, config: dict) -> dict:
    """What the configuration's ``data_report`` states, read from the rows
    as made."""
    cap = int(config["active_rows_cap"])
    by_user = np.bincount(rows.user, minlength=int(config["users"]))
    by_movie = np.bincount(rows.movie, minlength=int(config["movies"]))
    return {
        "users": int(np.sum(by_user > 0)), "rows": int(len(rows.y)),
        "movies_hit": int(np.sum(by_movie > 0)),
        "users_at_the_cap": int(np.sum(by_user >= cap)),
        "movies_at_the_cap": int(np.sum(by_movie >= cap)),
        "passive_rows_per_user_side": int(
            np.sum(np.maximum(by_user - cap, 0))),
        "passive_rows_per_item_side": int(
            np.sum(np.maximum(by_movie - cap, 0))),
        "heaviest_movie_share": float(by_movie.max() / len(rows.y)),
        "positive_rate": float(rows.y.mean())}
