"""GAME stack tests: dataset build, vmapped RE solver, coordinate descent.

Mirrors the reference's GAME test tiers (SURVEY §4): GameTestUtils-style
synthetic generators + end-to-end coordinate-descent runs with metric
assertions (integTest/.../cli/game/training/DriverTest.scala analog).
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax.numpy as jnp

from photon_ml_tpu.game.coordinate import (
    FactoredRandomEffectCoordinate,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.coordinate_descent import (
    run_coordinate_descent,
    training_loss_evaluator,
)
from photon_ml_tpu.game.dataset import (
    GameDataset,
    RandomEffectDataConfiguration,
    balanced_entity_order,
    build_fixed_effect_dataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.game.models import GameModel, MatrixFactorizationModel
from photon_ml_tpu.game.random_effect import (
    RandomEffectOptimizationProblem,
    score_random_effect,
)
from photon_ml_tpu.optimize.config import (
    GLMOptimizationConfiguration,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
    TaskType,
)
from photon_ml_tpu.optimize.problem import GLMOptimizationProblem
from photon_ml_tpu.projector.projectors import ProjectorConfig, ProjectorType


def make_game_data(rng, n=600, d_global=8, d_entity=4, n_entities=12,
                   task="logistic"):
    """Synthetic GAME data: global margin + per-entity margin."""
    Xg = rng.normal(size=(n, d_global))
    Xe = rng.normal(size=(n, d_entity))
    users = rng.integers(0, n_entities, size=n)
    w_g = rng.normal(size=d_global)
    W_e = rng.normal(size=(n_entities, d_entity)) * 2.0
    margin = Xg @ w_g + np.einsum("nd,nd->n", Xe, W_e[users])
    if task == "logistic":
        p = 1.0 / (1.0 + np.exp(-margin))
        y = (rng.uniform(size=n) < p).astype(np.float64)
    else:
        y = margin + 0.1 * rng.normal(size=n)
    data = GameDataset(
        responses=y,
        feature_shards={"global": sp.csr_matrix(Xg),
                        "per_user": sp.csr_matrix(Xe)},
    )
    data.encode_ids("userId", users)
    return data, w_g, W_e, users


def l2_config(lam=1.0, max_iter=30):
    return GLMOptimizationConfiguration(
        max_iterations=max_iter, tolerance=1e-8, regularization_weight=lam,
        optimizer_type=OptimizerType.LBFGS,
        regularization_context=RegularizationContext(RegularizationType.L2))


class TestRandomEffectDataset:
    def test_grouping_and_row_ids_roundtrip(self, rng):
        data, *_ = make_game_data(rng, n=200, n_entities=7)
        cfg = RandomEffectDataConfiguration(
            random_effect_type="userId", feature_shard_id="per_user",
            num_partitions=1)
        ds = build_random_effect_dataset(data, cfg)
        # every real sample appears exactly once in the active blocks
        ids = np.asarray(ds.row_ids).ravel()
        real = ids[ids < data.num_samples]
        assert sorted(real.tolist()) == list(range(data.num_samples))
        # weights nonzero exactly on real rows
        w = np.asarray(ds.weights).ravel()
        assert ((w > 0) == (ids < data.num_samples)).all()

    def test_reservoir_cap_and_passive(self, rng):
        data, *_ = make_game_data(rng, n=400, n_entities=5)
        cfg = RandomEffectDataConfiguration(
            random_effect_type="userId", feature_shard_id="per_user",
            num_partitions=1, num_active_data_points_upper_bound=30)
        ds = build_random_effect_dataset(data, cfg)
        counts = (np.asarray(ds.weights) > 0).sum(axis=1)
        assert counts.max() <= 30
        # active + passive covers every sample exactly once
        total = (counts.sum() + ds.num_passive)
        assert total == data.num_samples
        # weight rescaling preserves expected total weight per entity
        w = np.asarray(ds.weights)
        for e in range(ds.num_entities):
            we = w[e][w[e] > 0]
            if len(we) == 30:  # capped entity
                assert we.sum() == pytest.approx(
                    (we.sum() / we.mean()) * we.mean())
                assert we.mean() > 1.0  # rescaled up

    def test_feature_selection_bounds_dim(self, rng):
        data, *_ = make_game_data(rng, n=300, d_entity=6, n_entities=4)
        cfg = RandomEffectDataConfiguration(
            random_effect_type="userId", feature_shard_id="per_user",
            num_partitions=1, num_features_to_keep_upper_bound=3)
        ds = build_random_effect_dataset(data, cfg)
        assert (np.asarray(ds.projectors.reduced_dims) <= 3).all()

    def test_random_projection(self, rng):
        data, *_ = make_game_data(rng, n=120, d_entity=6, n_entities=4)
        cfg = RandomEffectDataConfiguration(
            random_effect_type="userId", feature_shard_id="per_user",
            num_partitions=1,
            projector=ProjectorConfig(ProjectorType.RANDOM, projected_dim=3))
        ds = build_random_effect_dataset(data, cfg)
        assert ds.reduced_dim == 3
        assert ds.random_projector.matrix.shape == (6, 3)

    def test_parse_config_string(self):
        # Field 5 is a features-to-samples RATIO (double), per-entity keep
        # count = ceil(ratio * samples) — RandomEffectDataConfiguration.
        # scala:104-109, RandomEffectDataSet.scala:386.
        cfg = RandomEffectDataConfiguration.parse(
            "userId,shardA,4,100,20,0.5,random=16")
        assert cfg.random_effect_type == "userId"
        assert cfg.num_active_data_points_upper_bound == 100
        assert cfg.num_passive_data_points_lower_bound == 20
        assert cfg.num_features_to_samples_ratio_upper_bound == 0.5
        assert cfg.features_to_keep(25) == 13
        assert cfg.projector.kind == ProjectorType.RANDOM
        assert cfg.projector.projected_dim == 16
        # Negative bounds mean "no bound" (DriverTest passes -1).
        cfg2 = RandomEffectDataConfiguration.parse(
            "userId,shardA,4,-1,0,-1,index_map")
        assert cfg2.num_active_data_points_upper_bound is None
        assert cfg2.num_features_to_samples_ratio_upper_bound is None
        assert cfg2.features_to_keep(10) is None

    def test_duplicate_csr_entries_summed(self):
        # Non-canonical CSR (duplicate (row,col) entries) must behave as the
        # summed matrix: the block fill scatters mat.data by (row, col), so
        # GameDataset canonicalizes shards up front.
        data_v = np.array([1.0, 2.0, 5.0])
        indices = np.array([3, 3, 0])
        indptr = np.array([0, 2, 3])
        mat = sp.csr_matrix((data_v, indices, indptr), shape=(2, 4))
        assert not mat.has_canonical_format
        ds = GameDataset(responses=np.array([1.0, 0.0]),
                         feature_shards={"s": mat})
        ds.encode_ids("u", np.array([0, 0]))
        assert ds.feature_shards["s"].has_canonical_format
        assert not mat.has_canonical_format  # caller's matrix untouched
        re_ds = build_random_effect_dataset(
            ds, RandomEffectDataConfiguration("u", "s", 1))
        X = np.asarray(re_ds.X)[0]  # [N_max, d_red]
        row_ids = np.asarray(re_ds.row_ids)[0]  # slot -> raw dataset row
        # raw row 0 must carry 3.0 (=1+2) at col 3, raw row 1 carries 5.0
        # at col 0 (reservoir sort may permute rows within the entity).
        dense = np.zeros((2, 4), np.float32)
        ri = re_ds.projectors.raw_indices[0]
        for slot, col in enumerate(ri):
            if col < 4:
                for s in range(2):
                    dense[row_ids[s], col] = X[s, slot]
        np.testing.assert_allclose(dense[0], [0, 0, 0, 3.0])
        np.testing.assert_allclose(dense[1], [5.0, 0, 0, 0])

    def test_balanced_entity_order(self):
        counts = np.array([100, 1, 1, 1, 50, 49, 1, 1])
        perm = balanced_entity_order(counts, num_bins=2)
        half = len(perm) // 2
        loads = counts[perm[:half]].sum(), counts[perm[half:]].sum()
        assert abs(loads[0] - loads[1]) <= 52  # near-balanced


class TestEntityBucketing:
    """(N, D) size bucketing of entity blocks (SURVEY §7 hard part 1;
    reference analog: exactly-sized per-entity LocalDataSets,
    data/LocalDataSet.scala:34-155)."""

    @staticmethod
    def _skewed_data(rng, d_entity=6, n_entities=24):
        # zipf-ish entity sizes: one giant, a few medium, many tiny
        sizes = np.maximum(1, (400 / np.arange(1, n_entities + 1) ** 1.3)
                           .astype(int))
        users = rng.permutation(np.repeat(np.arange(n_entities), sizes))
        n = len(users)
        Xe = rng.normal(size=(n, d_entity))
        W = rng.normal(size=(n_entities, d_entity))
        y = np.einsum("nd,nd->n", Xe, W[users]) + 0.01 * rng.normal(size=n)
        data = GameDataset(responses=y,
                           feature_shards={"s": sp.csr_matrix(Xe)})
        data.encode_ids("u", users)
        return data, W, users

    def test_bucket_plan_minimizes_padded_area(self):
        from photon_ml_tpu.game.dataset import _bucket_plan

        counts = np.array([100] + [3] * 30)
        n_max, bucket_of = _bucket_plan(counts, num_buckets=2, multiple=8)
        assert list(n_max) == [104, 8]
        assert bucket_of[0] == 0 and (bucket_of[1:] == 1).all()
        # bucketed area far below the single-block padding
        area = sum(int(n_max[b]) * (bucket_of == b).sum()
                   for b in range(len(n_max)))
        assert area == 104 + 30 * 8 < 31 * 104

    def test_bucketed_build_covers_every_sample(self, rng):
        data, _, users = self._skewed_data(rng)
        cfg = RandomEffectDataConfiguration("u", "s", 1)
        ds = build_random_effect_dataset(data, cfg, num_buckets=3)
        assert ds.buckets is not None and 1 < len(ds.buckets) <= 3
        ids = np.concatenate(
            [np.asarray(b.row_ids).ravel() for b in ds.buckets])
        real = ids[ids < data.num_samples]
        assert sorted(real.tolist()) == list(range(data.num_samples))
        # shrinking bucket shapes and a real padding win
        single = build_random_effect_dataset(data, cfg)
        area_bucketed = sum(int(np.prod(b.X.shape[:2])) for b in ds.buckets)
        area_single = int(np.prod(np.asarray(single.X).shape[:2]))
        assert area_bucketed < area_single
        assert ds.num_entities == len(ds.entity_codes)

    def test_bucketed_solve_matches_single_block(self, rng):
        data, W, users = self._skewed_data(rng)
        cfg = RandomEffectDataConfiguration("u", "s", 1)
        prob = RandomEffectOptimizationProblem(
            config=l2_config(lam=1e-3), task=TaskType.LINEAR_REGRESSION)

        single = build_random_effect_dataset(data, cfg)
        c1, *_ = prob.run(single, single.base_offsets)
        bucketed = build_random_effect_dataset(data, cfg, num_buckets=3)
        c2, *_ = prob.run(bucketed, bucketed.offsets_with(
            jnp.zeros(data.num_samples)))

        # entity order differs (bucket-major); compare per entity code
        # after scattering each build's reduced space back to raw columns
        raw1 = single.projectors.scatter_coefficients(np.asarray(c1)).dense()
        raw2 = bucketed.projectors.scatter_coefficients(
            np.asarray(c2)).dense()
        row1 = {int(c): i for i, c in enumerate(single.entity_codes)}
        for i, code in enumerate(bucketed.entity_codes):
            np.testing.assert_allclose(raw2[i], raw1[row1[int(code)]],
                                       rtol=2e-4, atol=2e-4)

    def test_bucketed_scoring_matches_single_block(self, rng):
        data, W, users = self._skewed_data(rng)
        cfg = RandomEffectDataConfiguration("u", "s", 1)
        prob = RandomEffectOptimizationProblem(
            config=l2_config(lam=1e-3), task=TaskType.LINEAR_REGRESSION)
        single = build_random_effect_dataset(data, cfg)
        c1, *_ = prob.run(single, single.base_offsets)
        s1 = score_random_effect(single, c1)
        bucketed = build_random_effect_dataset(data, cfg, num_buckets=3)
        c2, *_ = prob.run(bucketed, bucketed.offsets_with(
            jnp.zeros(data.num_samples)))
        s2 = score_random_effect(bucketed, c2)
        np.testing.assert_allclose(np.asarray(s2), np.asarray(s1),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("num_buckets", [1, 3])
    def test_scores_gathered_by_position_equal_the_scatter(self, rng,
                                                           num_buckets):
        """``score_random_effect`` gathers each row's score from its one
        place among the blocks' margins and the passive rows'
        (``RandomEffectDataset.score_positions``): bit for bit what the
        scatter by ``row_ids`` gives, block by block, active rows capped
        so that there are passive ones; a row in two blocks is refused."""
        from photon_ml_tpu.game.random_effect import (
            score_active,
            score_passive,
        )

        data, _, _ = self._skewed_data(rng)
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration(
                "u", "s", 1, num_active_data_points_upper_bound=40),
            num_buckets=num_buckets)
        assert ds.num_passive > 0
        coefs = jnp.asarray(rng.normal(
            size=(ds.num_entities, ds.reduced_dim)), jnp.float32)
        n = data.num_samples
        want = score_passive(ds.passive_X, ds.passive_entity, coefs,
                             ds.passive_row_ids, n)
        if ds.buckets is None:
            want = want + score_active(ds.X, coefs, ds.row_ids, ds.weights,
                                       n)
        else:
            for b in ds.buckets:
                c_b = jnp.zeros((b.X.shape[0], b.X.shape[2]), coefs.dtype)
                c_b = c_b.at[:b.num_real].set(coefs[
                    b.entity_start:b.entity_start + b.num_real,
                    :b.X.shape[2]])
                want = want + score_active(b.X, c_b, b.row_ids, b.weights, n)
        got = score_random_effect(ds, coefs)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        positions = np.asarray(ds.score_positions())
        assert positions.shape == (n,) and positions.dtype == np.int32
        assert len(np.unique(positions)) == n  # every row scored, once

        first = ds if ds.buckets is None else ds.buckets[0]
        twice = np.asarray(first.row_ids).copy()
        twice[0, 1] = twice[0, 0]
        first.row_ids = jnp.asarray(twice)
        ds._score_positions = None
        with pytest.raises(ValueError, match="two blocks"):
            ds.score_positions()

    def test_bucketed_cd_matches_single_block(self, rng):
        """Full coordinate descent (fixed + bucketed RE) reaches the same
        objective as the single-block build."""
        data, *_ = make_game_data(rng, n=500, n_entities=16)
        # skew the entity sizes so bucketing has something to do
        fe_cfg = l2_config(lam=0.1, max_iter=15)
        re_cfg = l2_config(lam=0.5, max_iter=15)

        def run(num_buckets):
            fe_ds = build_fixed_effect_dataset(data, "global")
            fixed = FixedEffectCoordinate(
                dataset=fe_ds,
                problem=GLMOptimizationProblem(
                    config=fe_cfg, task=TaskType.LOGISTIC_REGRESSION))
            re_ds = build_random_effect_dataset(
                data, RandomEffectDataConfiguration(
                    "userId", "per_user", 1), num_buckets=num_buckets)
            rand = RandomEffectCoordinate(
                dataset=re_ds,
                problem=RandomEffectOptimizationProblem(
                    config=re_cfg, task=TaskType.LOGISTIC_REGRESSION))
            return run_coordinate_descent(
                {"fixed": fixed, "perUser": rand}, 2,
                TaskType.LOGISTIC_REGRESSION,
                jnp.asarray(data.responses), jnp.asarray(data.weights),
                jnp.asarray(data.offsets))

        r1, r2 = run(1), run(4)
        o1 = [s.objective for s in r1.states]
        o2 = [s.objective for s in r2.states]
        np.testing.assert_allclose(o2, o1, rtol=1e-4)

    def test_bucketed_warm_start_roundtrip(self, rng):
        """initial= warm start slices the compact global block correctly."""
        data, _, users = self._skewed_data(rng)
        cfg = RandomEffectDataConfiguration("u", "s", 1)
        prob = RandomEffectOptimizationProblem(
            config=l2_config(lam=1e-3, max_iter=40),
            task=TaskType.LINEAR_REGRESSION)
        ds = build_random_effect_dataset(data, cfg, num_buckets=3)
        offs = ds.offsets_with(jnp.zeros(data.num_samples))
        c1, *_ = prob.run(ds, offs)
        # restarting AT the optimum must stay there (few extra iterations)
        c2, iters, *_ = prob.run(ds, offs, initial=c1)
        np.testing.assert_allclose(np.asarray(c2), np.asarray(c1),
                                   rtol=1e-3, atol=1e-4)

    def test_bucketed_active_passive_coverage(self, rng):
        """Reservoir cap + bucketing: every sample lands exactly once in
        an active bucket slot or the (global) passive side."""
        data, _, users = self._skewed_data(rng)
        cfg = RandomEffectDataConfiguration(
            "u", "s", 1, num_active_data_points_upper_bound=20)
        ds = build_random_effect_dataset(data, cfg, num_buckets=3)
        ids = np.concatenate(
            [np.asarray(b.row_ids).ravel() for b in ds.buckets])
        active = sorted(ids[ids < data.num_samples].tolist())
        passive = (sorted(np.asarray(ds.passive_row_ids).tolist())
                   if ds.num_passive else [])
        assert len(active) + len(passive) == data.num_samples
        assert sorted(active + passive) == list(range(data.num_samples))
        # the cap binds inside every bucket
        for b in ds.buckets:
            counts = (np.asarray(b.weights) > 0).sum(axis=1)
            assert counts.max() <= 20

    def test_factored_coordinate_accepts_buckets(self, rng):
        """Until PR 33 the factored coordinate refused every bucketed
        dataset; it takes them now, and solves the same problem on them as
        on the one block (one more solve in other lanes, so close, not
        equal to the bit)."""
        data, *_ = self._skewed_data(rng)
        task = TaskType.LINEAR_REGRESSION

        def fit(num_buckets):
            ds = build_random_effect_dataset(
                data, RandomEffectDataConfiguration(
                    "u", "s", 1,
                    projector=ProjectorConfig(ProjectorType.IDENTITY)),
                num_buckets=num_buckets)
            coord = FactoredRandomEffectCoordinate(
                dataset=ds,
                problem=RandomEffectOptimizationProblem(
                    config=l2_config(), task=task),
                latent_problem=GLMOptimizationProblem(
                    config=l2_config(), task=task),
                latent_dim=2, num_inner_iterations=1)
            state, _ = coord.update(None, jnp.zeros(data.num_samples))
            return ds, np.asarray(coord.score(state)), np.asarray(state[1])

        ds3, score3, B3 = fit(3)
        ds1, score1, B1 = fit(1)
        assert ds3.buckets is not None and len(ds3.buckets) > 1
        assert ds1.buckets is None
        np.testing.assert_allclose(B3, B1, rtol=1e-6, atol=1e-8)
        np.testing.assert_allclose(score3, score1, rtol=1e-6, atol=1e-8)

    def test_factored_coordinate_rejects_a_random_projection(self, rng):
        data, *_ = self._skewed_data(rng)
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration(
                "u", "s", 1, projector=ProjectorConfig(
                    ProjectorType.RANDOM, projected_dim=3)))
        with pytest.raises(ValueError, match="raw feature space"):
            FactoredRandomEffectCoordinate(
                dataset=ds,
                problem=RandomEffectOptimizationProblem(
                    config=l2_config(), task=TaskType.LINEAR_REGRESSION),
                latent_problem=GLMOptimizationProblem(
                    config=l2_config(), task=TaskType.LINEAR_REGRESSION),
                latent_dim=2)


class TestStreamedBlockBuild:
    """Streamed / memmap-backed entity-block build
    (build_random_effect_dataset_streamed): the single-host analog of the
    reference's streamed shuffle into entity-major layout
    (data/RandomEffectDataSet.scala:169-206), parity-tested against the
    in-RAM builder."""

    @staticmethod
    def _data(rng, n=900, d=10, n_entities=21):
        sizes = np.maximum(1, (300 / np.arange(1, n_entities + 1) ** 1.2)
                           .astype(int))
        users = rng.permutation(np.repeat(np.arange(n_entities), sizes))
        n = len(users)
        X = rng.normal(size=(n, d)) * (rng.random((n, d)) < 0.4)
        y = rng.normal(size=n)
        data = GameDataset(responses=y,
                           feature_shards={"s": sp.csr_matrix(X)},
                           offsets=rng.normal(size=n) * 0.1,
                           weights=rng.uniform(0.5, 1.5, size=n))
        data.encode_ids("u", users)
        return data

    @staticmethod
    def _cfg(**kw):
        base = dict(num_active_data_points_upper_bound=16,
                    num_passive_data_points_lower_bound=1,
                    num_features_to_keep_upper_bound=6)
        base.update(kw)
        return RandomEffectDataConfiguration("u", "s", 1, **base)

    def _assert_parity(self, ds_ram, ds_st):
        assert list(ds_st.entity_codes) == list(ds_ram.entity_codes)
        assert len(ds_st.buckets) == len(ds_ram.buckets)
        for br, bs in zip(ds_ram.buckets, ds_st.buckets):
            assert br.entity_start == bs.entity_start
            assert br.num_real == bs.num_real
            assert tuple(br.X.shape) == tuple(bs.X.shape)
            np.testing.assert_allclose(np.asarray(bs.X), np.asarray(br.X),
                                       rtol=1e-6, atol=1e-7)
            np.testing.assert_array_equal(np.asarray(bs.row_ids),
                                          np.asarray(br.row_ids))
            np.testing.assert_allclose(np.asarray(bs.weights),
                                       np.asarray(br.weights), rtol=1e-6)
            np.testing.assert_allclose(np.asarray(bs.labels),
                                       np.asarray(br.labels), rtol=1e-6)
            np.testing.assert_allclose(np.asarray(bs.base_offsets),
                                       np.asarray(br.base_offsets),
                                       rtol=1e-6, atol=1e-7)
        assert ds_st.num_passive == ds_ram.num_passive
        if ds_ram.num_passive:
            np.testing.assert_array_equal(
                np.asarray(ds_st.passive_row_ids),
                np.asarray(ds_ram.passive_row_ids))
            np.testing.assert_array_equal(
                np.asarray(ds_st.passive_entity),
                np.asarray(ds_ram.passive_entity))
            np.testing.assert_allclose(np.asarray(ds_st.passive_X),
                                       np.asarray(ds_ram.passive_X),
                                       rtol=1e-6, atol=1e-7)

    @pytest.mark.parametrize("projector", ["indexmap", "random", "identity"])
    def test_streamed_matches_in_ram(self, rng, projector):
        from photon_ml_tpu.game.dataset import (
            build_random_effect_dataset,
            build_random_effect_dataset_streamed,
            dataset_row_stream,
        )

        data = self._data(rng)
        kw = {}
        if projector == "random":
            kw = dict(projector=ProjectorConfig(ProjectorType.RANDOM,
                                                projected_dim=8),
                      num_features_to_keep_upper_bound=None)
        elif projector == "identity":
            kw = dict(projector=ProjectorConfig(ProjectorType.IDENTITY),
                      num_features_to_keep_upper_bound=None)
        cfg = self._cfg(**kw)
        ds_ram = build_random_effect_dataset(data, cfg, num_buckets=3)
        # chunk size deliberately misaligned with entity boundaries
        ds_st = build_random_effect_dataset_streamed(
            dataset_row_stream(data, cfg, chunk_rows=113), cfg,
            raw_dim=data.shard_dim("s"), num_buckets=3)
        self._assert_parity(ds_ram, ds_st)

    def test_streamed_memmap_blocks_on_disk(self, rng, tmp_path):
        from photon_ml_tpu.game.dataset import (
            build_random_effect_dataset,
            build_random_effect_dataset_streamed,
            dataset_row_stream,
        )

        data = self._data(rng)
        cfg = self._cfg()
        ds_ram = build_random_effect_dataset(data, cfg, num_buckets=3)
        ds_mm = build_random_effect_dataset_streamed(
            dataset_row_stream(data, cfg, chunk_rows=97), cfg,
            raw_dim=data.shard_dim("s"), num_buckets=3,
            blocks_dir=str(tmp_path))
        # blocks really live on disk
        assert isinstance(ds_mm.buckets[0].X, np.memmap)
        assert any(f.endswith(".f32") for f in
                   __import__("os").listdir(tmp_path))
        self._assert_parity(ds_ram, ds_mm)

        # the memmap-backed dataset solves and scores like the in-RAM one
        prob = RandomEffectOptimizationProblem(
            config=l2_config(lam=1e-2), task=TaskType.LINEAR_REGRESSION)
        zeros = jnp.zeros(data.num_samples, jnp.float32)
        c_ram, *_ = prob.run(ds_ram, ds_ram.offsets_with(zeros))
        c_mm, *_ = prob.run(ds_mm, ds_mm.offsets_with(zeros))
        np.testing.assert_allclose(np.asarray(c_mm), np.asarray(c_ram),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(score_random_effect(ds_mm, c_mm)),
            np.asarray(score_random_effect(ds_ram, c_ram)),
            rtol=2e-4, atol=2e-4)

    def test_entity_sharded_slices_concatenate_to_full(self, rng):
        """entity_shard=(k, K): the K per-shard builds hold exactly the
        K contiguous entity slices of the full build's buckets — the
        per-host-sharded block build no host-holds-all contract."""
        from photon_ml_tpu.game.dataset import (
            build_random_effect_dataset_streamed,
            dataset_row_stream,
        )

        data = self._data(rng)
        cfg = self._cfg()
        K = 2
        full = build_random_effect_dataset_streamed(
            dataset_row_stream(data, cfg, chunk_rows=113), cfg,
            raw_dim=data.shard_dim("s"), num_buckets=3,
            entity_axis_size=2 * K, keep_host_blocks=True)
        shards = [build_random_effect_dataset_streamed(
            dataset_row_stream(data, cfg, chunk_rows=113), cfg,
            raw_dim=data.shard_dim("s"), num_buckets=3,
            entity_axis_size=2 * K, keep_host_blocks=True,
            entity_shard=(k, K)) for k in range(K)]
        for b, fb in enumerate(full.buckets):
            for field in ("X", "labels", "base_offsets", "weights",
                          "row_ids"):
                whole = np.asarray(getattr(fb, field))
                parts = [np.asarray(getattr(s.buckets[b], field))
                         for s in shards]
                assert all(p.shape[0] == whole.shape[0] // K
                           for p in parts)
                np.testing.assert_array_equal(
                    np.concatenate(parts, axis=0), whole,
                    err_msg=f"bucket {b} field {field}")
            for k, s in enumerate(shards):
                assert (s.buckets[b].local_entity_offset
                        == k * whole.shape[0] // K)
        # passive side stays global and identical
        if full.num_passive:
            for s in shards:
                np.testing.assert_array_equal(
                    np.asarray(s.passive_X), np.asarray(full.passive_X))

    def test_streamed_single_bucket_covers_all_rows(self, rng):
        from photon_ml_tpu.game.dataset import (
            build_random_effect_dataset_streamed,
            dataset_row_stream,
        )

        data = self._data(rng)
        cfg = RandomEffectDataConfiguration("u", "s", 1)  # no caps
        ds = build_random_effect_dataset_streamed(
            dataset_row_stream(data, cfg, chunk_rows=101), cfg,
            raw_dim=data.shard_dim("s"))
        assert len(ds.buckets) == 1 and ds.num_passive == 0
        ids = np.asarray(ds.buckets[0].row_ids).ravel()
        real = ids[ids < data.num_samples]
        assert sorted(real.tolist()) == list(range(data.num_samples))


class TestEntityBucketingSolvers:
    """Bucketed solves across the full optimizer family + precision/resume
    interplay (the bucketed analog of BaseGLMIntegTest's cross-optimizer
    discipline)."""

    @staticmethod
    def _skewed(rng, task="linear"):
        return TestEntityBucketing._skewed_data(rng)

    def test_bucketed_tron_matches_lbfgs(self, rng):
        data, W, users = TestEntityBucketing._skewed_data(rng)
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("u", "s", 1), num_buckets=3)

        def cfg(opt):
            return GLMOptimizationConfiguration(
                max_iterations=60, tolerance=1e-10,
                regularization_weight=0.1, optimizer_type=opt,
                regularization_context=RegularizationContext(
                    RegularizationType.L2))

        offs = ds.offsets_with(jnp.zeros(data.num_samples))
        task = TaskType.LINEAR_REGRESSION
        c_tron, *_ = RandomEffectOptimizationProblem(
            config=cfg(OptimizerType.TRON), task=task).run(ds, offs)
        c_lbfgs, *_ = RandomEffectOptimizationProblem(
            config=cfg(OptimizerType.LBFGS), task=task).run(ds, offs)
        np.testing.assert_allclose(np.asarray(c_tron), np.asarray(c_lbfgs),
                                   atol=2e-3)

    def test_bucketed_owlqn_sparsifies(self, rng):
        """L1 through the bucketed path engages OWL-QN per bucket and
        produces sparse per-entity models."""
        data, W, users = TestEntityBucketing._skewed_data(rng)
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("u", "s", 1), num_buckets=3)
        cfg = GLMOptimizationConfiguration(
            max_iterations=50, tolerance=1e-9, regularization_weight=5.0,
            optimizer_type=OptimizerType.LBFGS,
            regularization_context=RegularizationContext(
                RegularizationType.L1))
        coefs, *_ = RandomEffectOptimizationProblem(
            config=cfg, task=TaskType.LINEAR_REGRESSION).run(
                ds, ds.offsets_with(jnp.zeros(data.num_samples)))
        w = np.asarray(coefs)
        assert np.all(np.isfinite(w))
        # strong L1 must zero a solid fraction of coefficients exactly
        assert (np.abs(w) < 1e-12).mean() > 0.2

    def test_bucketed_bf16_blocks_close_to_f32(self, rng):
        """bf16 entity blocks (half the HBM stream on TPU) with f32 solver
        state stay close to the f32 solve — the RE-side mixed-precision
        lever (solver_x0 promotes state to >=f32)."""
        data, W, users = TestEntityBucketing._skewed_data(rng)
        cfg = RandomEffectDataConfiguration("u", "s", 1)
        prob = RandomEffectOptimizationProblem(
            config=l2_config(lam=1e-2), task=TaskType.LINEAR_REGRESSION)
        f32 = build_random_effect_dataset(data, cfg, num_buckets=3)
        bf16 = build_random_effect_dataset(data, cfg, num_buckets=3,
                                           dtype=jnp.bfloat16)
        assert bf16.buckets[0].X.dtype == jnp.bfloat16
        c32, *_ = prob.run(f32, f32.offsets_with(
            jnp.zeros(data.num_samples)))
        c16, *_ = prob.run(bf16, bf16.offsets_with(
            jnp.zeros(data.num_samples)))
        assert np.asarray(c16).dtype == np.float32  # state stayed f32
        np.testing.assert_allclose(np.asarray(c16), np.asarray(c32),
                                   rtol=0.1, atol=0.05)

    def test_bucketed_cd_checkpoint_resume(self, rng, tmp_path):
        """Mid-run resume with a bucketed RE coordinate reproduces the
        uninterrupted run (compact [E, D] state round-trips)."""
        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent as run_cd,
        )
        from photon_ml_tpu.utils.checkpoint import CheckpointManager

        data, *_ = make_game_data(rng, n=400, n_entities=10)
        task = TaskType.LOGISTIC_REGRESSION

        def build():
            return {
                "fixed": FixedEffectCoordinate(
                    dataset=build_fixed_effect_dataset(data, "global"),
                    problem=GLMOptimizationProblem(
                        config=l2_config(lam=0.1), task=task)),
                "perUser": RandomEffectCoordinate(
                    dataset=build_random_effect_dataset(
                        data, RandomEffectDataConfiguration(
                            "userId", "per_user", 1), num_buckets=3),
                    problem=RandomEffectOptimizationProblem(
                        config=l2_config(lam=0.5), task=task)),
            }

        labels = jnp.asarray(data.responses)
        weights = jnp.asarray(data.weights)
        offsets = jnp.asarray(data.offsets)
        res_full = run_cd(build(), 2, task, labels, weights, offsets)
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        run_cd(build(), 1, task, labels, weights, offsets,
               checkpoint_manager=mgr)
        snap = mgr.restore()
        restored = {cid: jnp.asarray(v)
                    for cid, v in snap["states"].items()}
        res_resumed = run_cd(build(), 2, task, labels, weights, offsets,
                             initial_states=restored,
                             start_iteration=int(snap["iteration"]))
        np.testing.assert_allclose(
            res_resumed.states[-1].objective,
            res_full.states[-1].objective, rtol=1e-6)


class TestRandomEffectSolver:
    def test_recovers_per_entity_coefficients(self, rng):
        # linear task, no global effect: RE solve should recover W_e
        n_entities, d = 6, 3
        n = 900
        Xe = rng.normal(size=(n, d))
        users = rng.integers(0, n_entities, size=n)
        W = rng.normal(size=(n_entities, d))
        y = np.einsum("nd,nd->n", Xe, W[users]) + 0.01 * rng.normal(size=n)
        data = GameDataset(responses=y,
                           feature_shards={"s": sp.csr_matrix(Xe)})
        data.encode_ids("u", users)
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("u", "s", 1))
        prob = RandomEffectOptimizationProblem(
            config=l2_config(lam=1e-4), task=TaskType.LINEAR_REGRESSION)
        coefs, iters, values, codes, _ = prob.run(ds, ds.base_offsets)
        # scatter back to raw space and compare per entity
        raw = ds.projectors.scatter_coefficients(np.asarray(coefs)).dense()
        for e_i, code in enumerate(ds.entity_codes):
            np.testing.assert_allclose(raw[e_i], W[int(code)], atol=0.05)

    def test_scores_match_direct_computation(self, rng):
        data, _, W_e, users = make_game_data(rng, n=150, n_entities=5,
                                             task="linear")
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("userId", "per_user", 1))
        prob = RandomEffectOptimizationProblem(
            config=l2_config(), task=TaskType.LINEAR_REGRESSION)
        coefs, *_ = prob.run(ds, ds.base_offsets)
        s = score_random_effect(ds, coefs)
        # recompute: raw coefficients dotted with raw features per sample
        raw = ds.projectors.scatter_coefficients(np.asarray(coefs)).dense()
        code_to_local = {int(c): i for i, c in enumerate(ds.entity_codes)}
        Xe = np.asarray(data.feature_shards["per_user"].todense())
        expected = np.array([
            Xe[i] @ raw[code_to_local[int(data.id_columns["userId"][i])]]
            for i in range(data.num_samples)])
        np.testing.assert_allclose(np.asarray(s), expected, rtol=1e-4,
                                   atol=1e-5)

    def test_convergence_counts_by_reason(self, rng):
        """Per-entity convergence-reason counts surface through the tracker
        (RandomEffectOptimizationTracker.countsByConvergence analog)."""
        data, *_ = make_game_data(rng, n=300, n_entities=8)
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("userId", "per_user", 1))

        def fit(max_iter):
            coord = RandomEffectCoordinate(
                dataset=ds,
                problem=RandomEffectOptimizationProblem(
                    config=l2_config(lam=0.5, max_iter=max_iter),
                    task=TaskType.LOGISTIC_REGRESSION))
            _, tracker = coord.update(None, jnp.zeros(data.num_samples))
            return tracker

        starved = fit(1).counts_by_convergence()
        assert sum(starved.values()) == ds.num_entities
        assert starved.get("MaxIterations", 0) >= ds.num_entities - 1

        generous = fit(200)
        counts = generous.counts_by_convergence()
        assert sum(counts.values()) == ds.num_entities
        assert counts.get("MaxIterations", 0) == 0
        assert set(counts) <= {"FunctionValuesConverged",
                               "GradientConverged",
                               "ObjectiveNotImproving"}
        assert "convergence" in generous.summary()

    def test_tron_matches_lbfgs_per_entity(self, rng):
        # Per-entity TRON (TRON.scala:84-341 under vmap) must land on the
        # same per-entity optima as L-BFGS, mirroring the reference's
        # TRON-vs-LBFGS max-difference discipline (BaseGLMIntegTest.scala).
        data, _, W_e, users = make_game_data(rng, n=400, n_entities=6,
                                             task="logistic")
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("userId", "per_user", 1))

        def cfg(opt):
            return GLMOptimizationConfiguration(
                max_iterations=60, tolerance=1e-10,
                regularization_weight=0.1, optimizer_type=opt,
                regularization_context=RegularizationContext(
                    RegularizationType.L2))

        task = TaskType.LOGISTIC_REGRESSION
        c_tron, it_tron, v_tron, *_ = RandomEffectOptimizationProblem(
            config=cfg(OptimizerType.TRON), task=task).run(
                ds, ds.base_offsets)
        c_lbfgs, _, v_lbfgs, *_ = RandomEffectOptimizationProblem(
            config=cfg(OptimizerType.LBFGS), task=task).run(
                ds, ds.base_offsets)
        assert int(np.min(np.asarray(it_tron))) > 0  # TRON actually iterated
        np.testing.assert_allclose(np.asarray(c_tron), np.asarray(c_lbfgs),
                                   atol=2e-3)
        np.testing.assert_allclose(np.asarray(v_tron), np.asarray(v_lbfgs),
                                   rtol=1e-5)

    def test_tron_rejects_smoothed_hinge(self, rng):
        data, *_ = make_game_data(rng, n=100, n_entities=3)
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("userId", "per_user", 1))
        prob = RandomEffectOptimizationProblem(
            config=GLMOptimizationConfiguration(
                max_iterations=10, tolerance=1e-6, regularization_weight=1.0,
                optimizer_type=OptimizerType.TRON,
                regularization_context=RegularizationContext(
                    RegularizationType.L2)),
            task=TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM)
        with pytest.raises(ValueError, match="twice-differentiable"):
            prob.run(ds, ds.base_offsets)

    def test_passive_data_scored(self, rng):
        data, *_ = make_game_data(rng, n=300, n_entities=3, task="linear")
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration(
                "userId", "per_user", 1,
                num_active_data_points_upper_bound=40))
        assert ds.num_passive > 0
        prob = RandomEffectOptimizationProblem(
            config=l2_config(), task=TaskType.LINEAR_REGRESSION)
        coefs, *_ = prob.run(ds, ds.base_offsets)
        s = np.asarray(score_random_effect(ds, coefs))
        # passive rows must receive nonzero scores too
        passive_ids = np.asarray(ds.passive_row_ids)
        assert np.abs(s[passive_ids]).max() > 0


class TestCoordinateDescent:
    def test_fixed_plus_random_beats_fixed_only(self, rng):
        data, w_g, W_e, users = make_game_data(rng, n=800, n_entities=10)
        task = TaskType.LOGISTIC_REGRESSION

        fe_ds = build_fixed_effect_dataset(data, "global")
        fixed = FixedEffectCoordinate(
            dataset=fe_ds,
            problem=GLMOptimizationProblem(config=l2_config(lam=0.1),
                                           task=task))
        re_ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("userId", "per_user", 1))
        rand = RandomEffectCoordinate(
            dataset=re_ds,
            problem=RandomEffectOptimizationProblem(
                config=l2_config(lam=0.5), task=task))

        labels = jnp.asarray(data.responses)
        weights = jnp.asarray(data.weights)
        offsets = jnp.asarray(data.offsets)

        res_fixed = run_coordinate_descent(
            {"fixed": fixed}, 1, task, labels, weights, offsets)
        res_game = run_coordinate_descent(
            {"fixed": fixed, "perUser": rand}, 2, task, labels, weights,
            offsets)

        assert res_game.states[-1].objective < res_fixed.states[-1].objective
        # objective must be monotonically non-increasing over CD sweeps
        objs = [s.objective for s in res_game.states]
        assert objs[-1] <= objs[0] + 1e-9

    def test_validation_tracking_selects_best(self, rng):
        data, *_ = make_game_data(rng, n=500, n_entities=8)
        val_data, *_ = make_game_data(np.random.default_rng(7), n=200,
                                      n_entities=8)
        task = TaskType.LOGISTIC_REGRESSION
        fixed = FixedEffectCoordinate(
            dataset=build_fixed_effect_dataset(data, "global"),
            problem=GLMOptimizationProblem(config=l2_config(lam=0.1),
                                           task=task))

        from photon_ml_tpu.evaluation.metrics import area_under_roc_curve

        def evaluator(scores):
            return {"AUC": float(area_under_roc_curve(
                jnp.asarray(val_data.responses), scores))}

        res = run_coordinate_descent(
            {"fixed": fixed}, 2, task,
            jnp.asarray(data.responses), jnp.asarray(data.weights),
            jnp.asarray(data.offsets),
            validation_data=val_data, validation_evaluator=evaluator,
            validation_metric="AUC")
        assert res.best_model is not None
        assert res.best_metric is not None
        assert all(s.validation_metrics is not None for s in res.states)

    def test_factored_random_effect_runs(self, rng):
        data, *_ = make_game_data(rng, n=300, d_entity=6, n_entities=6,
                                  task="linear")
        task = TaskType.LINEAR_REGRESSION
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration(
                "userId", "per_user", 1,
                projector=ProjectorConfig(ProjectorType.IDENTITY)))
        coord = FactoredRandomEffectCoordinate(
            dataset=ds,
            problem=RandomEffectOptimizationProblem(
                config=l2_config(lam=0.1, max_iter=10), task=task),
            latent_problem=GLMOptimizationProblem(
                config=l2_config(lam=0.1, max_iter=10), task=task),
            latent_dim=3, num_inner_iterations=2)
        res = run_coordinate_descent(
            {"factored": coord}, 2, task,
            jnp.asarray(data.responses), jnp.asarray(data.weights),
            jnp.asarray(data.offsets))
        objs = [s.objective for s in res.states]
        assert objs[-1] < objs[0]
        model = res.model.models["factored"]
        assert model.projection.shape == (3, 6)
        # published model scores finitely
        s = model.score(data)
        assert np.isfinite(np.asarray(s)).all()


class _RecordingCoordinate:
    """Mock coordinate (algorithm/CoordinateDescentTest.scala's Mockito
    analog): scores a constant vector, records every partial-score offset
    handed to update()."""

    def __init__(self, n, constant):
        self._n = n
        self._constant = constant
        self.seen_partials = []
        self.update_count = 0

    @property
    def num_samples(self):
        return self._n

    def initial_state(self):
        return jnp.zeros(1)

    def update(self, state, extra_scores):
        self.seen_partials.append(np.asarray(extra_scores).copy())
        self.update_count += 1

        class _Tracker:
            def for_coordinate(self, coordinate):
                return self

            def summary(self):
                return "mock"

        return state + 1.0, _Tracker()

    def score(self, state):
        return jnp.full(self._n, self._constant) * jnp.minimum(state[0], 1.0)

    def regularization_value(self, state):
        return 0.25

    def publish(self, state):
        return ("mock-model", float(state[0]))


class TestCoordinateDescentContract:
    def test_partial_score_injection_and_objective(self):
        """CoordinateDescent.scala:143-151: each coordinate's update sees
        EXACTLY the sum of the other coordinates' current scores; :199-205:
        the logged objective is lossEval(Σ scores) + Σ regularization."""
        n = 16
        a = _RecordingCoordinate(n, 2.0)
        b = _RecordingCoordinate(n, 3.0)
        labels = jnp.zeros(n)
        res = run_coordinate_descent(
            {"A": a, "B": b}, 2, TaskType.LINEAR_REGRESSION,
            labels, jnp.ones(n), jnp.zeros(n))
        assert a.update_count == b.update_count == 2
        # sweep 1: A sees zeros (B not yet scored), B sees A's fresh score
        np.testing.assert_allclose(a.seen_partials[0], np.zeros(n))
        np.testing.assert_allclose(b.seen_partials[0], np.full(n, 2.0))
        # sweep 2: A sees only B's score, B sees only A's
        np.testing.assert_allclose(a.seen_partials[1], np.full(n, 3.0))
        np.testing.assert_allclose(b.seen_partials[1], np.full(n, 2.0))
        # objective after the final update: squared loss of total score 5
        # against zero labels plus the two coordinates' reg values
        expected = 0.5 * n * 5.0 ** 2 + 0.5
        assert res.states[-1].objective == pytest.approx(expected)
        # publish() receives each coordinate's final state
        assert res.model.models["A"] == ("mock-model", 2.0)
        assert res.model.models["B"] == ("mock-model", 2.0)


class TestGameModels:
    def test_projected_model_raw_conversion_consistent(self, rng):
        data, *_ = make_game_data(rng, n=200, n_entities=5, task="linear")
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("userId", "per_user", 1))
        prob = RandomEffectOptimizationProblem(
            config=l2_config(), task=TaskType.LINEAR_REGRESSION)
        coefs, *_ = prob.run(ds, ds.base_offsets)
        coord = RandomEffectCoordinate(dataset=ds, problem=prob)
        model = coord.publish(coefs)
        # model.score (raw path) == coordinate score (projected path)
        np.testing.assert_allclose(
            np.asarray(model.score(data)),
            np.asarray(coord.score(coefs)), rtol=1e-4, atol=1e-5)

    def test_matrix_factorization_model(self, rng):
        n, r, c, k = 100, 6, 5, 3
        rows = rng.integers(0, r, size=n)
        cols = rng.integers(0, c, size=n)
        RF = rng.normal(size=(r, k)).astype(np.float32)
        CF = rng.normal(size=(c, k)).astype(np.float32)
        data = GameDataset(
            responses=np.zeros(n),
            feature_shards={"s": sp.csr_matrix(np.ones((n, 1)))})
        data.encode_ids("rowId", rows)
        data.encode_ids("colId", cols)
        m = MatrixFactorizationModel("rowId", "colId", jnp.asarray(RF),
                                     jnp.asarray(CF))
        s = np.asarray(m.score(data))
        # vocabulary is sorted unique values; codes index it directly here
        # since rows/cols are already 0..K-1 ints
        expected = np.sum(RF[rows] * CF[cols], axis=1)
        np.testing.assert_allclose(s, expected, rtol=1e-5, atol=1e-6)

    def test_game_model_score_is_sum(self, rng):
        data, *_ = make_game_data(rng, n=100, n_entities=4, task="linear")
        fe_ds = build_fixed_effect_dataset(data, "global")
        task = TaskType.LINEAR_REGRESSION
        fixed = FixedEffectCoordinate(
            dataset=fe_ds,
            problem=GLMOptimizationProblem(config=l2_config(), task=task))
        coefs, _ = fixed.update(fixed.initial_state(),
                                jnp.zeros(data.num_samples))
        fe_model = fixed.publish(coefs)
        gm = GameModel({"fixed": fe_model})
        np.testing.assert_allclose(np.asarray(gm.score(data)),
                                   np.asarray(fe_model.score(data)))


class TestSamplers:
    def test_binary_downsampler_keeps_positives(self, rng):
        import jax

        from photon_ml_tpu.data.batch import dense_batch
        from photon_ml_tpu.sampler.samplers import (
            binary_classification_down_sample,
        )

        n = 2000
        y = (rng.uniform(size=n) < 0.3).astype(np.float64)
        b = dense_batch(rng.normal(size=(n, 3)), y)
        out = binary_classification_down_sample(
            b, 0.5, jax.random.PRNGKey(0))
        w = np.asarray(out.weights)
        assert (w[y > 0.5] == 1.0).all()  # positives untouched
        neg = w[y <= 0.5]
        # kept negatives reweighted by 1/r; expectation preserved
        assert set(np.unique(neg)).issubset({0.0, 2.0})
        assert neg.sum() == pytest.approx((y <= 0.5).sum(), rel=0.15)

    def test_default_downsampler_expectation(self, rng):
        import jax

        from photon_ml_tpu.data.batch import dense_batch
        from photon_ml_tpu.sampler.samplers import default_down_sample

        n = 4000
        b = dense_batch(rng.normal(size=(n, 2)), np.zeros(n))
        out = default_down_sample(b, 0.25, jax.random.PRNGKey(1))
        w = np.asarray(out.weights)
        assert w.sum() == pytest.approx(n, rel=0.15)


class TestCheckpointedCoordinateDescent:
    def test_midrun_resume_matches_uninterrupted(self, rng, tmp_path):
        """Resume after sweep 1 of a 2-coordinate model must continue from
        the restored scores, not zeros (code-review regression)."""
        from photon_ml_tpu.utils.checkpoint import CheckpointManager

        data, w_g, W_e, users = make_game_data(rng, n=400, n_entities=6)
        task = TaskType.LOGISTIC_REGRESSION

        def build():
            fixed = FixedEffectCoordinate(
                dataset=build_fixed_effect_dataset(data, "global"),
                problem=GLMOptimizationProblem(config=l2_config(lam=0.1),
                                               task=task))
            rand = RandomEffectCoordinate(
                dataset=build_random_effect_dataset(
                    data, RandomEffectDataConfiguration("userId",
                                                        "per_user", 1)),
                problem=RandomEffectOptimizationProblem(
                    config=l2_config(lam=0.5), task=task))
            return {"fixed": fixed, "perUser": rand}

        labels = jnp.asarray(data.responses)
        weights = jnp.asarray(data.weights)
        offsets = jnp.asarray(data.offsets)

        # uninterrupted 2 sweeps
        res_full = run_coordinate_descent(build(), 2, task, labels, weights,
                                          offsets)

        # sweep 1 with checkpoint, then resume for sweep 2
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        run_coordinate_descent(build(), 1, task, labels, weights, offsets,
                               checkpoint_manager=mgr)
        snap = mgr.restore()
        restored = {cid: jnp.asarray(v) for cid, v in
                    snap["states"].items()}
        res_resumed = run_coordinate_descent(
            build(), 2, task, labels, weights, offsets,
            initial_states=restored,
            start_iteration=int(snap["iteration"]))

        full_obj = res_full.states[-1].objective
        resumed_obj = res_resumed.states[-1].objective
        assert resumed_obj == pytest.approx(full_obj, rel=1e-4)


def lanes_that_skip_the_store_before(chunk, blocks, obj, l1, solver,
                                     tolerance):
    """The lanes of ``blocks`` = (X, labels, offsets, weights, x0) whose
    iteration ``chunk`` moves the iterate but stores no curvature pair
    (``s.y <= 1e-10``), and that are not done by then: with chunks of
    ``chunk`` their history crosses a chunk boundary as it was. Read off
    the per-lane carry of two solves stopped one iteration apart. Shared
    with tests/test_re_sharding.py."""
    from photon_ml_tpu.game import random_effect as re_mod

    def stopped_at(budget):
        *_, iters, _, codes, _, _, _, carry = re_mod._fit_blocks(
            *blocks, obj, l1, solver, budget, tolerance,
            boundary_convergence=True, return_carry=True)
        return np.asarray(iters), np.asarray(codes), carry

    _, _, before = stopped_at(chunk - 1)
    iters, codes, at = stopped_at(chunk)
    assert at.head is None  # the per-entity carry: newest-first
    return [
        e for e in range(len(iters))
        if iters[e] == chunk and codes[e] == re_mod.CONV_MAX_ITERATIONS
        and np.array_equal(np.asarray(at.S[e]), np.asarray(before.S[e]))
        and not np.array_equal(np.asarray(at.x[e]), np.asarray(before.x[e]))]


class TestLaneEvaluationCounts:
    """The vmapped path's counts: each lane's own evaluations, the rounds
    the batched loop ran, and the fill they give (LaneCounts, the tracker
    and the ``solver_*`` counters)."""

    @staticmethod
    def _blocks(rng, e=5, n=40, d=4):
        X = rng.normal(size=(e, n, d))
        # unequal scales: the lanes need unequal numbers of trials
        X *= np.logspace(0, 1.5, e)[:, None, None]
        w = rng.normal(size=(e, d))
        z = np.einsum("end,ed->en", X, w)
        y = (rng.random((e, n)) < 1 / (1 + np.exp(-z))).astype(float)
        return (jnp.asarray(X), jnp.asarray(y), jnp.zeros((e, n)),
                jnp.ones((e, n)), jnp.zeros((e, d)))

    @staticmethod
    def _problem(optimizer, l1=False, **kw):
        reg = RegularizationContext(
            RegularizationType.ELASTIC_NET, alpha=0.5) if l1 \
            else RegularizationContext(RegularizationType.L2)
        return RandomEffectOptimizationProblem(
            config=GLMOptimizationConfiguration(
                optimizer_type=optimizer, max_iterations=12, tolerance=1e-9,
                regularization_weight=0.5, regularization_context=reg),
            task=TaskType.LOGISTIC_REGRESSION, **kw)

    SOLVERS = {"lbfgs": (OptimizerType.LBFGS, False),
               "owlqn": (OptimizerType.LBFGS, True),
               "tron": (OptimizerType.TRON, False)}

    @pytest.mark.parametrize("solver", sorted(SOLVERS))
    def test_each_lane_counts_its_solo_solve(self, rng, solver):
        from photon_ml_tpu.data.batch import DenseBatch
        from photon_ml_tpu.game import random_effect as re_mod
        from photon_ml_tpu.optimize.lbfgs import minimize_lbfgs
        from photon_ml_tpu.optimize.owlqn import minimize_owlqn
        from photon_ml_tpu.optimize.tron import minimize_tron

        X, y, off, wts, x0 = self._blocks(rng)
        prob = self._problem(*self.SOLVERS[solver])
        obj = prob.objective()
        l1 = jnp.full(X.shape[2], 0.25 if solver == "owlqn" else 0.0)
        _, iters, _, _, evals, trials, rounds = re_mod._fit_blocks(
            X, y, off, wts, x0, obj, l1, solver, 12, 1e-9)
        solo, solo_trials = [], []
        for e in range(X.shape[0]):
            payload = (obj, DenseBatch(X=X[e], labels=y[e], offsets=off[e],
                                       weights=wts[e]))
            kw = dict(max_iter=12, tolerance=1e-9)
            if solver == "owlqn":
                out = minimize_owlqn(re_mod._vg, x0[e], payload, l1=l1, **kw)
            elif solver == "tron":
                out = minimize_tron(re_mod._vg, re_mod._hvp, x0[e], payload,
                                    **kw)
            else:  # the form the per-entity solves ask for
                out = minimize_lbfgs(re_mod._vg, x0[e], payload,
                                     newest_first=True, line_fn=re_mod._line,
                                     **kw)
            assert int(out[1].num_iterations) == int(iters[e])
            solo.append(int(np.asarray(out[1].evaluations).sum()))
            solo_trials.append(0 if out[1].line_trials is None
                               else int(np.asarray(out[1].line_trials).sum()))
        assert list(np.asarray(evals)) == solo
        assert list(np.asarray(trials)) == solo_trials
        assert len(set(solo)) > 1  # the lanes do differ
        if solver == "lbfgs":
            # a full evaluation at the start and at each accepted point
            assert solo == [1 + int(k) for k in np.asarray(iters)]
            assert all(t >= int(k) for t, k in zip(solo_trials,
                                                   np.asarray(iters)))
        else:
            assert not any(solo_trials)
        # the batched loop ran at least what its slowest lane needed, and
        # no lane can need more in a round than the round's largest
        assert rounds.shape == (1,)
        assert max(solo) <= int(rounds[0]) <= sum(solo)

    @pytest.mark.parametrize("solver", sorted(SOLVERS))
    def test_rounds_booked_are_the_passes_the_batched_loop_ran(
            self, rng, solver, monkeypatch):
        """``rounds`` against a count the solvers have no hand in: a host
        counter the objective bumps once per execution, however many lanes
        that execution carries. With a budget that ends every lane's solve
        (none finishes early and rides along), rounds x lanes booked is
        exactly what ran: no evaluation under a batched conditional is
        paid once per branch. L-BFGS's one pass an iteration over the rows
        for its line (``_line``) is counted apart and is in no round."""
        import jax

        from photon_ml_tpu.game import random_effect as re_mod

        ran = {"vg": 0, "line": 0}

        def bump(name):
            jax.debug.callback(lambda: ran.__setitem__(name, ran[name] + 1))

        def counted_vg(w, payload):
            bump("vg")
            obj, batch = payload
            return obj.calculate(w, batch)

        def counted_line(w, d, payload):
            bump("line")
            obj, batch = payload
            return obj.line(w, d, batch)

        monkeypatch.setattr(re_mod, "_vg", counted_vg)
        monkeypatch.setattr(re_mod, "_line", counted_line)
        X, y, off, wts, x0 = self._blocks(rng)
        prob = self._problem(*self.SOLVERS[solver])
        l1 = jnp.full(X.shape[2], 0.25 if solver == "owlqn" else 0.0)
        budget = 3
        _, iters, _, _, evals, trials, rounds = re_mod._fit_blocks_impl(
            X, y, off, wts, x0, prob.objective(), l1, solver, budget, 1e-30)
        jax.effects_barrier()
        assert list(np.asarray(iters)) == [budget] * X.shape[0]
        if solver == "owlqn":  # the others evaluate once an iteration
            assert len(set(np.asarray(evals).tolist())) > 1  # unequal lanes
        if solver == "lbfgs":  # whose trials are what differs
            assert len(set(np.asarray(trials).tolist())) > 1
        assert int(rounds[0]) == ran["vg"]
        assert ran["line"] == (budget if solver == "lbfgs" else 0)

    @pytest.mark.parametrize("solver", sorted(SOLVERS))
    def test_compacted_chunks_count_what_one_dispatch_counts(self, rng,
                                                             solver):
        data, *_ = make_game_data(rng, n=500, n_entities=16)
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("userId", "per_user", 1))
        *_, whole = self._problem(*self.SOLVERS[solver]).run(
            ds, ds.base_offsets)
        *_, chunked = self._problem(
            *self.SOLVERS[solver], lane_compaction_chunk=4).run(
            ds, ds.base_offsets)
        nr = len(ds.entity_codes)
        np.testing.assert_array_equal(
            np.asarray(chunked.evaluations)[:nr],
            np.asarray(whole.evaluations)[:nr])
        # one program a chunk, each over fewer (padded) lanes; together
        # they run no more lane-evaluations than the one dispatch did
        assert len(chunked.bucket_lanes) == len(chunked.evaluation_rounds) > 1
        assert len(whole.bucket_lanes) == 1

        def ran(counts):
            return int(np.dot(counts.bucket_lanes,
                              np.asarray(counts.evaluation_rounds)))

        assert ran(chunked) <= ran(whole)

    @pytest.mark.parametrize("solver,chunk", [("lbfgs", 9), ("owlqn", 10)])
    def test_a_store_skipped_before_a_chunk_boundary_resumes_exactly(
            self, rng, solver, chunk):
        """The chunked solve against the single dispatch where a lane's
        last iteration before the boundary stored no pair (``s.y <= 1e-10``,
        a lane near its optimum under a tolerance it has not met yet): its
        newest-first history crosses the boundary unshifted, the next
        chunk's first direction reads the same pairs, and that lane's
        coefficients, iterations, value and code are the single dispatch's
        bit for bit."""
        from photon_ml_tpu.game import random_effect as re_mod

        X, y, off, wts, x0 = self._blocks(rng)
        obj = self._problem(*self.SOLVERS[solver]).objective()
        l1 = jnp.full(X.shape[2], 0.25 if solver == "owlqn" else 0.0)
        tolerance, max_iter = 1e-13, 30

        skipped_and_goes_on = lanes_that_skip_the_store_before(
            chunk, (X, y, off, wts, x0), obj, l1, solver, tolerance)
        assert skipped_and_goes_on

        whole = re_mod._fit_blocks(X, y, off, wts, x0, obj, l1, solver,
                                   max_iter, tolerance)
        chunked = re_mod._fit_blocks_compacted(
            X, y, off, wts, x0, obj, l1, solver, max_iter, tolerance, chunk,
            donate=False)
        lanes = np.asarray(skipped_and_goes_on)
        assert (np.asarray(whole[1])[lanes] > chunk).all()
        for mine, theirs in zip(chunked[:4], whole[:4]):
            np.testing.assert_array_equal(np.asarray(mine)[lanes],
                                          np.asarray(theirs)[lanes])
        # every lane: the same iterations and evaluations; a lane that
        # jitters at the float's floor until its budget ends (OWL-QN at
        # this tolerance) differs by an ulp between a 5-lane program and a
        # padded 8-lane one, as it did with the circular carry
        np.testing.assert_allclose(np.asarray(chunked[0]),
                                   np.asarray(whole[0]), rtol=1e-12)
        np.testing.assert_array_equal(np.asarray(chunked[1]),
                                      np.asarray(whole[1]))
        np.testing.assert_array_equal(np.asarray(chunked[4].evaluations),
                                      np.asarray(whole[4]))
        assert len(chunked[4].bucket_lanes) > 1  # it did run in chunks

    def test_lane_fill_of_a_two_lane_bucket(self, rng):
        """One lane done at once (no weight: its gradient at the start is
        zero, so it makes the start's evaluation and stops), one slow: the
        slow lane sets every round, and the fill is what arithmetic says.
        An L-BFGS lane makes its start and one full evaluation an
        iteration, so the slow lane's rounds are 1 + its iterations; its
        trials go on ``solver_line_trials``."""
        from photon_ml_tpu.game import random_effect as re_mod
        from photon_ml_tpu.game.coordinate import RandomEffectTracker
        from photon_ml_tpu.obs.metrics import REGISTRY

        X, y, off, wts, x0 = self._blocks(rng, e=2)
        wts = wts.at[0].set(0.0)
        prob = self._problem(OptimizerType.LBFGS)
        out = re_mod._fit_blocks(X, y, off, wts, x0, prob.objective(),
                                 jnp.zeros(X.shape[2]), "lbfgs", 12, 1e-9)
        coefs, iters, values, codes, evals, trials, rounds = out
        slow = int(evals[1])
        assert int(iters[0]) == 0 and int(evals[0]) == 1 and slow > 3
        assert slow == 1 + int(iters[1]) and int(trials[0]) == 0
        assert int(trials[1]) >= int(iters[1])
        assert int(rounds[0]) == slow
        tracker = RandomEffectTracker(
            iters, values, codes, evaluations=evals,
            evaluation_rounds=rounds, bucket_lanes=np.asarray([2]),
            site="t.two_lanes", line_trials=trials)

        def booked(name):
            return REGISTRY.counter(name).value(site="t.two_lanes")

        before = {n: booked(n) for n in (
            "solver_iterations", "solver_evaluations",
            "solver_lane_evaluations", "solver_line_trials")}
        assert tracker.lane_fill() == pytest.approx((1 + slow) / (2 * slow))
        tracker.materialize().materialize()  # booked once
        assert booked("solver_iterations") \
            - before["solver_iterations"] == int(iters[1])
        assert booked("solver_evaluations") \
            - before["solver_evaluations"] == 1 + slow
        assert booked("solver_lane_evaluations") \
            - before["solver_lane_evaluations"] == 2 * slow
        assert booked("solver_line_trials") \
            - before["solver_line_trials"] == int(trials[1])

    def test_trials_on_margins_land_where_full_trials_do(self, rng):
        """A small float32 bucket of one-hot rows (a user's rated movies),
        weights up to 8, L2 1, 8 iterations and a tolerance no solve meets,
        as the sweep cells solve their users: the per-entity form, whose
        trials are made on carried margins, against the same vmapped solve
        whose every trial is a full evaluation. The coefficients agree to
        1e-4 of their norm."""
        import jax

        from photon_ml_tpu.data.batch import DenseBatch
        from photon_ml_tpu.game import random_effect as re_mod
        from photon_ml_tpu.optimize.lbfgs import minimize_lbfgs

        e, n, d = 64, 32, 16
        f32 = jnp.float32
        X = np.eye(d)[rng.integers(0, d, size=(e, n))]
        rows = rng.integers(4, n + 1, size=e)  # the rest are padding
        live = np.arange(n)[None, :] < rows[:, None]
        z = np.einsum("end,ed->en", X, rng.normal(size=(e, d)))
        y = (rng.random((e, n)) < 1 / (1 + np.exp(-z))).astype(float)
        blocks = (jnp.asarray(X, f32), jnp.asarray(y, f32),
                  jnp.asarray(rng.normal(size=(e, n)) * 0.3, f32),
                  jnp.asarray(live * rng.uniform(1.0, 8.0, size=(e, 1)), f32),
                  jnp.zeros((e, d), f32))
        obj = self._problem(OptimizerType.LBFGS).objective().with_l2(1.0)
        on_margins = re_mod._fit_blocks(*blocks, obj, jnp.zeros(d, f32),
                                        "lbfgs", 8, 1e-30)[0]

        def full_trials(Xe, ye, oe, we, x0):
            return minimize_lbfgs(
                re_mod._vg, x0,
                (obj, DenseBatch(X=Xe, labels=ye, offsets=oe, weights=we)),
                max_iter=8, tolerance=1e-30, newest_first=True)[0]

        full = jax.vmap(full_trials)(*blocks)
        assert on_margins.dtype == full.dtype == f32
        gap = float(jnp.linalg.norm(on_margins - full)
                    / jnp.linalg.norm(full))
        assert gap <= 1e-4, gap

    def test_bucketed_update_fills_the_tracker(self, rng):
        data, *_ = make_game_data(rng, n=600, n_entities=24)
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("userId", "per_user", 1),
            num_buckets=3)
        coord = RandomEffectCoordinate(ds, self._problem(OptimizerType.LBFGS))
        _, tracker = coord.update(None, jnp.zeros(data.num_samples))
        from photon_ml_tpu.obs.metrics import REGISTRY

        def booked(name):
            return REGISTRY.counter(name).value(site="re.fit_blocks")

        before = {n: booked(n) for n in (
            "solver_evaluations", "solver_line_trials")}
        tracker.materialize()
        assert tracker.site == "re.fit_blocks"
        assert tracker.evaluations.shape == tracker.iterations.shape
        # L-BFGS: the start and one full evaluation an iteration
        np.testing.assert_array_equal(tracker.evaluations,
                                      tracker.iterations + 1)
        assert (tracker.line_trials >= tracker.iterations).all()
        assert booked("solver_evaluations") - before["solver_evaluations"] \
            == len(tracker.iterations) + int(tracker.iterations.sum())
        assert booked("solver_line_trials") - before["solver_line_trials"] \
            == int(tracker.line_trials.sum()) > 0
        assert len(tracker.bucket_lanes) == len(ds.buckets) \
            == len(tracker.evaluation_rounds)
        assert list(tracker.bucket_lanes) == [
            int(b.X.shape[0]) for b in ds.buckets]
        assert 0.0 < tracker.lane_fill() <= 1.0
