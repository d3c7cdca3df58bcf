"""The generators are deterministic in ``--seed``; every seed gives the same
set of rows in another order; every GLMix user has at least 20 rows and rates
no movie twice."""

import numpy as np

from benchmark.generators import glmix_rows
from benchmark.kinds import glm_grid_fit

CONFIG = {"rows": 30000, "users": 400, "movies": 300, "global_features": 64,
          "rows_per_chunk": 4096, "data_seed": 5, "min_rows_per_user": 20,
          "max_rows_per_user": 250, "activity_sigma": 1.1,
          "movie_popularity_exponent": 1.0, "movie_popularity_shift": 6.0,
          "user_effect_scale": 0.5}
BIG_SEED = 2**31 + 12345


def test_glmix_rows_repeat_for_a_seed_and_relabel_users_for_another():
    a, b = glmix_rows.make_rows(CONFIG, BIG_SEED), glmix_rows.make_rows(
        CONFIG, BIG_SEED)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    c = glmix_rows.make_rows(CONFIG, 3)
    assert not np.array_equal(a.user, c.user)
    # the same rows, the same blocks per user, under other user ids
    for x, y in zip(a[1:], c[1:]):
        assert np.array_equal(x, y)
    _, first_a = np.unique(a.user, return_index=True)
    assert len(np.unique(c.user[first_a])) == CONFIG["users"]
    assert np.array_equal(np.sort(np.bincount(a.user)),
                          np.sort(np.bincount(c.user)))
    other = glmix_rows.make_rows(dict(CONFIG, data_seed=6), BIG_SEED)
    assert not np.array_equal(a.X, other.X)  # the data seed draws the rows
    assert a.X.dtype == np.float32 and a.X.shape == (30000, 65)
    assert np.all(a.X[:, 64] == 1.0)
    assert set(np.unique(a.y)) == {0.0, 1.0}


def test_every_glmix_user_has_the_minimum_and_rates_a_movie_once():
    rows = glmix_rows.make_rows(CONFIG, 1)
    counts = np.bincount(rows.user, minlength=CONFIG["users"])
    assert len(counts) == CONFIG["users"] and counts.sum() == CONFIG["rows"]
    assert counts.min() >= 20 and counts.max() == 250  # heavy users fill up
    pairs = rows.user.astype(np.int64) * CONFIG["movies"] + rows.movie
    assert len(np.unique(pairs)) == len(pairs)
    assert 0 <= rows.movie.min() and rows.movie.max() < CONFIG["movies"]
    seen = np.bincount(rows.movie, minlength=CONFIG["movies"])
    assert seen[:30].mean() > 2 * seen[-30:].mean()  # the popular head


def test_the_activity_law_at_movielens_10m():
    counts = glmix_rows.user_counts(10000054, 69878, 20, 7359, 1.1)
    assert counts.sum() == 10000054 and len(counts) == 69878
    assert counts.min() == 20 and counts.max() == 7359
    assert np.all(np.diff(counts) >= 0)
    assert 80 <= np.median(counts) <= 95
    assert 0.30 < np.mean(counts >= 128) < 0.36  # about a third at the cap
    assert np.array_equal(counts, glmix_rows.user_counts(
        10000054, 69878, 20, 7359, 1.1))  # no seed in it


def test_active_rows_cap_and_weights():
    user = np.repeat(np.arange(3), [2, 5, 9]).astype(np.int32)
    rows, weight = glmix_rows.active_rows(user, 4, 0)
    kept = np.bincount(user[rows], minlength=3)
    assert list(kept) == [2, 4, 4]
    assert np.allclose(weight[user[rows] == 0], 1.0)
    assert np.allclose(weight[user[rows] == 1], 5 / 4)
    assert np.allclose(weight[user[rows] == 2], 9 / 4)
    again, _ = glmix_rows.active_rows(user, 4, 0)
    assert np.array_equal(rows, again)


def test_glm_data_repeats_and_the_seed_deals_the_offsets_cycle():
    make = lambda: glm_grid_fit.make_data(2048, 16, 256, 9, 1.0)
    (Xa, ya), (Xb, yb) = make(), make()
    assert np.array_equal(np.asarray(Xa), np.asarray(Xb))
    assert np.array_equal(np.asarray(ya), np.asarray(yb))
    assert str(Xa.dtype) == "float32" and Xa.shape == (2048, 16)
    scales = glm_grid_fit.column_scales(16, 1.0, 9)
    assert scales.max() == 1.0 and np.isclose(scales.min(), 0.1)

    def offsets(seed):
        state = glm_grid_fit.State()
        state.rows, state.data_seed, state.jitter = 64, 9, 0.001
        state.cycle = np.random.default_rng(seed).permutation(8)
        return [np.asarray(glm_grid_fit.jitter(state, i)) for i in range(16)]

    a, b, c = offsets(BIG_SEED), offsets(BIG_SEED), offsets(4)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert all(np.array_equal(a[i], a[i + 8]) for i in range(8))  # a cycle
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    as_set = lambda vs: sorted(float(v[0]) for v in vs[:8])
    assert as_set(a) == as_set(c)  # the same eight, in another order
    assert 0 < np.abs(a[0]).max() < 0.01
