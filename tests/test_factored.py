"""The factored random-effect coordinate on index-map-projected, bucketed
blocks (PR 33): the projection refit's batch layout against ``jax.grad`` of
the plain loss, the bucketed form against the identity, one-block form,
passive rows through their own entity's columns, the share of a deployment
against the whole, and the counters' ``coordinate`` label."""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.batch import (
    ProjectionRefitBatch,
    gather_projection,
    projection_table,
)
from photon_ml_tpu.game.coordinate import (
    FactoredRandomEffectCoordinate,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
    _refit_batch,
)
from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
from photon_ml_tpu.game.dataset import (
    GameDataset,
    RandomEffectDataConfiguration,
    build_fixed_effect_dataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.game.random_effect import RandomEffectOptimizationProblem
from photon_ml_tpu.obs import trace
from photon_ml_tpu.obs.metrics import REGISTRY
from photon_ml_tpu.ops.aggregators import GLMObjective
from photon_ml_tpu.ops.losses import get_loss
from photon_ml_tpu.optimize.config import (
    GLMOptimizationConfiguration,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
    TaskType,
)
from photon_ml_tpu.optimize.problem import GLMOptimizationProblem
from photon_ml_tpu.projector.projectors import ProjectorConfig, ProjectorType

TASK = TaskType.LOGISTIC_REGRESSION
K = 3


def _l2(lam=1.0, iterations=30, tolerance=1e-9):
    return GLMOptimizationConfiguration(
        max_iterations=iterations, tolerance=tolerance,
        regularization_weight=lam, optimizer_type=OptimizerType.LBFGS,
        regularization_context=RegularizationContext(RegularizationType.L2))


def _data(seed=0, users=24, movies=30, d_global=4):
    """MovieLens-shaped rows: a user rates a movie once, one-hot movie
    features a user and one-hot user features a movie, skewed counts."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(3, 19, size=users)
    user = np.repeat(np.arange(users), counts)
    movie = np.concatenate([rng.choice(movies, size=c, replace=False)
                            for c in counts])
    order = rng.permutation(len(user))
    user, movie = user[order], movie[order]
    n = len(user)
    X = rng.normal(size=(n, d_global)).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    return user, movie, X, y


def _game_dataset(user, movie, X, y, users, movies):
    n = len(y)
    ones, at = np.ones(n, np.float32), np.arange(n + 1)
    data = GameDataset(responses=y, feature_shards={
        "global": sp.csr_matrix(X),
        "per_user": sp.csr_matrix((ones, movie, at), shape=(n, movies)),
        "per_item": sp.csr_matrix((ones, user, at), shape=(n, users))})
    data.encode_ids("userId", user)
    data.encode_ids("movieId", movie)
    return data


def _user_dataset(data, num_buckets=1, projector=None, cap=None, keep=None):
    kw = {} if projector is None else {"projector": projector}
    return build_random_effect_dataset(
        data, RandomEffectDataConfiguration(
            "userId", "per_user", 1,
            num_active_data_points_upper_bound=cap,
            num_features_to_keep_upper_bound=keep, **kw),
        num_buckets=num_buckets)


def _factored(ds, iterations=30, inner=1, lam=1.0):
    return FactoredRandomEffectCoordinate(
        dataset=ds,
        problem=RandomEffectOptimizationProblem(
            config=_l2(lam, iterations), task=TASK),
        latent_problem=GLMOptimizationProblem(
            config=_l2(lam, iterations), task=TASK),
        latent_dim=K, num_inner_iterations=inner)


# --- the refit's batch layout ------------------------------------------------


def _small_refit(rng, dim=11):
    """Two blocks of different shapes, some slots unused (column = dim)."""
    blocks, dense = [], []
    for e, n, d in ((3, 4, 5), (2, 6, 3)):
        X = rng.normal(size=(e, n, d)).astype(np.float32)
        cols = np.stack([rng.choice(dim, size=d, replace=False)
                         for _ in range(e)]).astype(np.int32)
        cols[0, -1] = dim  # an unused slot, with values in its column of X
        latent = rng.normal(size=(e, K)).astype(np.float32)
        blocks.append((jnp.asarray(X), jnp.asarray(cols),
                       jnp.asarray(latent)))
        # the Kronecker features c_e (x) x, every row in raw space
        raw = np.zeros((e, n, dim + 1))
        np.put_along_axis(raw, np.broadcast_to(
            cols[:, None, :], X.shape).astype(np.int64), X, axis=2)
        dense.append(np.einsum("ek,end->enkd", latent,
                               raw[:, :, :dim]).reshape(e * n, K * dim))
    rows = sum(len(d) for d in dense)
    labels = (rng.random(rows) < 0.5).astype(np.float32)
    offsets = rng.normal(size=rows).astype(np.float32) * 0.3
    weights = (rng.random(rows) + 0.5).astype(np.float32)
    batch = ProjectionRefitBatch(
        blocks, jnp.asarray(labels), jnp.asarray(offsets),
        jnp.asarray(weights), dim=dim)
    return batch, np.concatenate(dense), labels, offsets, weights


def test_the_refit_layout_is_the_kronecker_batch_it_never_builds():
    rng = np.random.default_rng(3)
    batch, kron, labels, offsets, weights = _small_refit(rng)
    assert batch.num_features == K * 11 and batch.latent_dim == K
    w = jnp.asarray(rng.normal(size=K * 11))
    np.testing.assert_allclose(batch.margins(w, 0.0),
                               kron @ np.asarray(w) + offsets, rtol=1e-5,
                               atol=1e-6)
    r = jnp.asarray(rng.normal(size=len(labels)))
    np.testing.assert_allclose(batch.weighted_feature_sum(r),
                               kron.T @ np.asarray(r), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(batch.hadamard_square_sum(r),
                               (kron * kron).T @ np.asarray(r), rtol=1e-5,
                               atol=1e-6)


def test_the_refit_objectives_gradient_is_jax_grad_of_the_plain_loss():
    rng = np.random.default_rng(4)
    batch, kron, labels, offsets, weights = _small_refit(rng)
    obj = GLMObjective(loss=get_loss("logistic"), l2_lambda=0.7)
    w = jnp.asarray(rng.normal(size=K * 11) * 0.5)

    def plain(w):
        z = jnp.asarray(kron) @ w + offsets
        loss = jnp.maximum(z, 0) + jnp.log1p(jnp.exp(-jnp.abs(z))) \
            - labels * z
        return jnp.sum(weights * loss) + 0.35 * jnp.dot(w, w)

    value, grad = obj.calculate(w, batch)
    assert float(value) == pytest.approx(float(plain(w)), rel=1e-6)
    np.testing.assert_allclose(grad, jax.grad(plain)(w), rtol=1e-5,
                               atol=1e-6)
    # and a Hessian-vector product goes through the same two halves
    v = jnp.asarray(rng.normal(size=K * 11))
    np.testing.assert_allclose(
        obj.hessian_vector(w, v, batch),
        jax.jvp(jax.grad(plain), (w,), (v,))[1], rtol=1e-4, atol=1e-5)


def test_unused_slots_gather_zero_and_scatter_nowhere():
    rng = np.random.default_rng(5)
    batch, *_ = _small_refit(rng)
    B = jnp.asarray(rng.normal(size=(K, 11)).astype(np.float32))
    got = np.asarray(gather_projection(projection_table(B),
                                       batch.blocks[0].columns))
    assert np.all(got[0, -1] == 0.0)  # the sentinel's row of the table
    np.testing.assert_array_equal(
        got[0, 0], np.asarray(B)[:, int(batch.blocks[0].columns[0, 0])])
    # whatever X holds under an unused slot moves neither half of a pass
    loud = batch._replace(blocks=(
        batch.blocks[0]._replace(X=batch.blocks[0].X.at[0, :, -1].set(1e6)),
        batch.blocks[1]))
    w = jnp.asarray(rng.normal(size=K * 11))
    r = jnp.asarray(rng.normal(size=batch.labels.shape[0]))
    np.testing.assert_array_equal(loud.margins(w, 0.0),
                                  batch.margins(w, 0.0))
    np.testing.assert_array_equal(loud.weighted_feature_sum(r),
                                  batch.weighted_feature_sum(r))


# --- the coordinate ----------------------------------------------------------


def test_index_map_buckets_equal_the_identity_one_block_form():
    """The same users, rows and starting projection: index-map projected
    and bucketed, each user's block holds only its own movies' columns;
    identity projected, every block holds all 30. The objective is the
    same function of (c, B), so both forms walk the same path up to the
    order of their float64 sums (x64 is on in the tests): 1e-6, where a
    step of either solver moves a coefficient by 1e-2."""
    user, movie, X, y = _data()
    data = _game_dataset(user, movie, X, y, 24, 30)
    plain = _factored(_user_dataset(
        data, projector=ProjectorConfig(ProjectorType.IDENTITY)), inner=2)
    bucketed = _factored(_user_dataset(data, num_buckets=3), inner=2)
    assert bucketed.dataset.buckets is not None
    assert plain.dataset.buckets is None and plain.raw_dim == 30
    assert bucketed.dataset.reduced_dim < 30 == bucketed.raw_dim
    scores = jnp.asarray(np.random.default_rng(1).normal(size=len(y)) * 0.2)
    _, B0 = plain.initial_state()
    outs = {}
    for name, coord in (("plain", plain), ("bucketed", bucketed)):
        e = coord.dataset.num_entities
        (c, B), tracker = coord.update((jnp.zeros((e, K)), B0), scores)
        by_user = np.zeros((24, K))
        by_user[np.asarray(coord.dataset.entity_codes)] = np.asarray(c)
        outs[name] = (by_user, np.asarray(B),
                      np.asarray(coord.score((c, B))))
        assert len(tracker.inner) == 2
    for a, b in zip(outs["plain"], outs["bucketed"]):
        np.testing.assert_allclose(b, a, rtol=1e-6, atol=1e-8)
    assert np.abs(outs["plain"][1] - np.asarray(B0)).max() > 1e-2


def test_passive_rows_score_through_their_own_entitys_columns():
    """With a cap, a user's other rows are scored and not trained on, in
    the user's own reduced space: of a passive row only the columns the
    user's training rows touched (P_u) count, so it scores c_u^T B[:, P_u]
    x[P_u], and nothing where it shares no column with them."""
    rng = np.random.default_rng(2)
    users, dim, n = 12, 30, 400
    user = rng.integers(0, users, size=n)
    feats = np.zeros((n, dim), np.float32)
    for i in range(n):
        feats[i, rng.choice(dim, size=3, replace=False)] = rng.normal(size=3)
    y = (rng.random(n) < 0.5).astype(np.float32)
    data = GameDataset(responses=y,
                       feature_shards={"per_user": sp.csr_matrix(feats)})
    data.encode_ids("userId", user)
    ds = _user_dataset(data, num_buckets=2, cap=4)
    assert ds.num_passive > 0 and ds.reduced_dim < dim
    coord = _factored(ds)
    c = rng.normal(size=(ds.num_entities, K)).astype(np.float32)
    B = rng.normal(size=(K, dim)).astype(np.float32)
    got = np.asarray(coord.score((jnp.asarray(c), jnp.asarray(B))))
    lane = {int(code): i for i, code in enumerate(ds.entity_codes)}
    own = np.zeros((users, dim + 1))
    for u, e in lane.items():
        own[u, ds.projectors.raw_indices[e]] = 1.0
    seen = feats * own[user, :dim]  # x restricted to P_u
    expect = np.einsum("nk,kd,nd->n", c[[lane[u] for u in user]], B, seen)
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)
    passive = np.asarray(ds.passive_row_ids)
    full = np.einsum("nk,kd,nd->n", c[[lane[u] for u in user]], B, feats)
    assert np.any(expect[passive] != 0)
    assert not np.allclose(expect[passive], full[passive])  # P_u matters


def test_the_starting_projection_can_be_handed_in():
    user, movie, X, y = _data(seed=3)
    data = _game_dataset(user, movie, X, y, 24, 30)
    ds = _user_dataset(data, num_buckets=2)
    B0 = jnp.asarray(np.random.default_rng(8).normal(
        size=(K, 30)).astype(np.float32))
    vectors = (jnp.asarray(y), jnp.ones(len(y)), jnp.zeros(len(y)))

    def sweep(initial):
        return run_coordinate_descent(
            {"mf": _factored(ds, iterations=2)}, 1, TASK, *vectors,
            initial_states=initial).model.get("mf")

    start = (jnp.zeros((ds.num_entities, K)), B0)
    given = sweep({"mf": start})
    (_, direct), _ = _factored(ds, iterations=2).update(
        start, jnp.zeros(len(y)))
    np.testing.assert_allclose(np.asarray(given.projection),
                               np.asarray(direct), rtol=1e-12)
    # and not where the coordinate's own drawn start leads
    drawn = sweep(None)
    assert np.abs(np.asarray(drawn.projection)
                  - np.asarray(given.projection)).max() > 1e-2


# --- the share of a deployment -----------------------------------------------


def test_the_four_user_shares_add_up_to_the_whole_data_set():
    users, movies = 40, 30
    user, movie, X, y = _data(seed=5, users=users, movies=movies)
    whole = _game_dataset(user, movie, X, y, users, movies)
    rng = np.random.default_rng(9)
    w = jnp.asarray(rng.normal(size=X.shape[1]))
    B = jnp.asarray(rng.normal(size=(K, movies)))
    latent = rng.normal(size=(users, K))
    obj = GLMObjective(loss=get_loss("logistic"), l2_lambda=0.0)

    def parts(data, ids):
        """(fixed-effect gradient at w, per-user coefficients in raw space
        by user, the refit's gradient at B and the users' common latent
        coefficients) of one data set."""
        fixed = build_fixed_effect_dataset(data, "global")
        ds = _user_dataset(data, num_buckets=2)
        problem = RandomEffectOptimizationProblem(
            config=_l2(iterations=200, tolerance=1e-13), task=TASK)
        scores = jnp.zeros(data.num_samples)
        coefs = np.asarray(problem.run(ds, ds.offsets_with(scores))[0])
        lanes = ids[np.asarray(ds.entity_codes)]  # share code -> user
        raw = np.zeros((users, movies + 1))
        raw[lanes[:, None], ds.projectors.raw_indices] = coefs
        coord = _factored(ds)
        batch = _refit_batch(coord._data, coord._spans, ds.buckets is None,
                             coord.raw_dim, jnp.asarray(latent[lanes]),
                             ds.offsets_with(scores))
        return (np.asarray(obj.calculate(w, fixed.batch)[1]),
                raw[:, :movies],
                np.asarray(obj.calculate(B.reshape(-1), batch)[1]))

    fixed_all, users_all, refit_all = parts(whole, np.arange(users))
    fixed_sum, refit_sum = 0.0, 0.0
    for chip in range(4):
        mine = np.flatnonzero(user % 4 == chip)
        ids = np.unique(user[mine])
        share = _game_dataset(user[mine], movie[mine], X[mine], y[mine],
                              users, movies)
        fixed, by_user, refit = parts(share, ids)
        fixed_sum, refit_sum = fixed_sum + fixed, refit_sum + refit
        # a user's solve sees its own rows only: the share's is the whole's
        # (to where float32 blocks let a solve go: it ends, unable to
        # improve, an iteration sooner or later by the lanes beside it,
        # 1e-4 apart on coefficients of 0.4)
        np.testing.assert_allclose(by_user[ids], users_all[ids], rtol=1e-3,
                                   atol=5e-4)
        assert not by_user[np.setdiff1d(np.arange(users), ids)].any()
    # float32 blocks, float32 sums in another order: 5e-7 of the gradient
    np.testing.assert_allclose(fixed_sum, fixed_all, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(refit_sum, refit_all, rtol=1e-5, atol=1e-5)


# --- tracing -----------------------------------------------------------------


def _by_coordinate(name):
    out = {}
    for key, value in REGISTRY.counter(name).items().items():
        labels = dict(key)
        pair = (labels.get("coordinate"), labels.get("site"))
        out[pair] = out.get(pair, 0) + value
    return out


def test_the_solver_counters_name_the_coordinate_and_the_spans_the_stages():
    user, movie, X, y = _data(seed=6)
    data = _game_dataset(user, movie, X, y, 24, 30)
    ds = _user_dataset(data, num_buckets=2)
    item = build_random_effect_dataset(
        data, RandomEffectDataConfiguration("movieId", "per_item", 1),
        num_buckets=2)
    coords = {
        "fixed": FixedEffectCoordinate(
            dataset=build_fixed_effect_dataset(data, "global"),
            problem=GLMOptimizationProblem(config=_l2(iterations=4),
                                           task=TASK)),
        "per-user": RandomEffectCoordinate(
            dataset=ds, problem=RandomEffectOptimizationProblem(
                config=_l2(iterations=4), task=TASK)),
        "per-item": RandomEffectCoordinate(
            dataset=item, problem=RandomEffectOptimizationProblem(
                config=_l2(iterations=4), task=TASK)),
        "mf": _factored(ds, iterations=4)}
    names = ("solver_iterations", "solver_evaluations",
             "solver_lane_evaluations")
    before = {n: _by_coordinate(n) for n in names}
    totals = {n: REGISTRY.counter(n).total() for n in names}
    tracer = trace.enable()
    try:
        result = run_coordinate_descent(
            coords, 1, TASK, jnp.asarray(y), jnp.ones(len(y)),
            jnp.zeros(len(y)))
        spans = [e for e in tracer.events()
                 if e["name"].startswith("factored.")]
    finally:
        trace.disable()
    assert [e["name"] for e in spans] == ["factored.latent_solve",
                                          "factored.refit"]
    trackers = {st.coordinate_id: st.tracker.materialize()
                for st in result.states}
    new = {n: {k: v - before[n].get(k, 0)
               for k, v in _by_coordinate(n).items()
               if v != before[n].get(k, 0)} for n in names}
    assert set(new["solver_evaluations"]) == {
        ("fixed", "optimizer.lbfgs"), ("per-user", "re.fit_blocks"),
        ("per-item", "re.fit_blocks"), ("mf", "re.fit_blocks"),
        ("mf", "optimizer.lbfgs")}
    latent, refit = trackers["mf"].inner[0]
    assert new["solver_evaluations"][("mf", "optimizer.lbfgs")] \
        == refit.result.evaluations
    assert new["solver_iterations"][("mf", "optimizer.lbfgs")] \
        == refit.result.iterations == 4
    assert new["solver_evaluations"][("mf", "re.fit_blocks")] \
        == int(latent.evaluations.sum())
    assert new["solver_evaluations"][("per-item", "re.fit_blocks")] \
        == int(trackers["per-item"].evaluations.sum())
    assert new["solver_lane_evaluations"][("per-item", "re.fit_blocks")] \
        >= new["solver_evaluations"][("per-item", "re.fit_blocks")]
    # a reader that filters on site alone still reads the totals
    for n in names:
        assert REGISTRY.counter(n).total() - totals[n] \
            == sum(new[n].values())


def test_the_refit_runs_as_a_module_of_its_own_name():
    user, movie, X, y = _data(seed=6)
    data = _game_dataset(user, movie, X, y, 24, 30)
    coord = _factored(_user_dataset(data, num_buckets=2))
    assert coord._refit.__wrapped__.__name__ == "_factored_refit_impl"
    state, _ = coord.update(None, jnp.zeros(len(y)))
    lowered = coord._refit.lower(
        coord.latent_problem.objective(), coord._data, state[0],
        coord.dataset.offsets_with(jnp.zeros(len(y))),
        state[1].reshape(-1))
    assert "jit__factored_refit_impl" in lowered.as_text()[:400]
