"""Hot-loop sync discipline: one device round-trip per coordinate update.

The CD hot loop's contract (game/coordinate_descent.py): every
non-validation coordinate update performs EXACTLY ONE blocking
device→host fetch — the fused epilogue's small scalar pytree. The
transfer-guard test runs a real sweep under
``jax.transfer_guard("disallow")`` so any future accidental implicit
``float()``/``bool()``/``np.asarray`` in the hot loop fails CI loudly
instead of silently re-serializing the loop.

Also here: parity tests for the two paths the perf work rewired — the
fused epilogue's objective against a by-hand recomputation of the
reference formula, and the lane-compacted chunked solver's coefficients
against the single-dispatch solve.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from photon_ml_tpu.game.coordinate import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game import coordinate_descent as cd
from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
from photon_ml_tpu.game.dataset import (
    RandomEffectDataConfiguration,
    build_fixed_effect_dataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.game import random_effect as re_mod
from photon_ml_tpu.game.random_effect import (
    RandomEffectOptimizationProblem,
)
from photon_ml_tpu.optimize.config import (
    GLMOptimizationConfiguration,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
    TaskType,
)
from photon_ml_tpu.optimize.problem import GLMOptimizationProblem
from photon_ml_tpu.utils import sync_telemetry


def make_game_data(rng, n=600, d_global=8, d_entity=4, n_entities=12):
    """Synthetic GAME data (test_game.make_game_data's logistic recipe)."""
    from photon_ml_tpu.game.dataset import GameDataset

    Xg = rng.normal(size=(n, d_global))
    Xe = rng.normal(size=(n, d_entity))
    users = rng.integers(0, n_entities, size=n)
    w_g = rng.normal(size=d_global)
    W_e = rng.normal(size=(n_entities, d_entity)) * 2.0
    margin = Xg @ w_g + np.einsum("nd,nd->n", Xe, W_e[users])
    p = 1.0 / (1.0 + np.exp(-margin))
    y = (rng.uniform(size=n) < p).astype(np.float64)
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


def _build_coords(data, re_chunk=0, max_iter=20):
    fixed = FixedEffectCoordinate(
        dataset=build_fixed_effect_dataset(data, "global"),
        problem=GLMOptimizationProblem(
            config=l2_config(lam=0.5, max_iter=max_iter),
            task=TaskType.LOGISTIC_REGRESSION))
    re_ds = build_random_effect_dataset(
        data, RandomEffectDataConfiguration("userId", "per_user", 1))
    rand = RandomEffectCoordinate(
        dataset=re_ds,
        problem=RandomEffectOptimizationProblem(
            config=l2_config(lam=0.5, max_iter=max_iter),
            task=TaskType.LOGISTIC_REGRESSION,
            lane_compaction_chunk=re_chunk))
    return {"fixed": fixed, "perUser": rand}


class TestOneRoundTripPerUpdate:
    def test_sweep_under_transfer_guard_single_epilogue_fetch(self, rng):
        """One CD sweep with implicit device→host transfers DISALLOWED:
        the only whitelisted read is the fused epilogue's explicit
        ``jax.device_get`` (plus the equally explicit lazy-tracker /
        checkpoint fetches, none of which fire in a bare run). Exactly one
        epilogue fetch per coordinate update. A future accidental
        ``float()``/``bool()``/``np.asarray`` in the hot loop is an
        implicit transfer and fails here. (The guard is scoped to the
        device→host direction — the one-round-trip contract — because the
        full ``transfer_guard("disallow")`` also bans the benign async
        scalar constants that eager ``jnp.zeros``/``jnp.full`` stage
        host-side.)"""
        data, *_ = make_game_data(rng, n=240, n_entities=6)
        coords = _build_coords(data)
        labels = jnp.asarray(data.responses)
        weights = jnp.asarray(data.weights)
        offsets = jnp.asarray(data.offsets)

        # warm-up: compile every kernel at these shapes OUTSIDE the guard
        run_coordinate_descent(coords, 1, TaskType.LOGISTIC_REGRESSION,
                               labels, weights, offsets)

        cd.reset_hot_loop_stats()
        sync_telemetry.reset_host_fetches()
        with jax.transfer_guard_device_to_host("disallow"):
            res = run_coordinate_descent(
                coords, 1, TaskType.LOGISTIC_REGRESSION,
                labels, weights, offsets)
        assert len(res.states) == len(coords)
        assert cd.HOT_LOOP_STATS["updates"] == len(coords)
        assert (cd.HOT_LOOP_STATS["epilogue_fetches"]
                == cd.HOT_LOOP_STATS["updates"])
        # the process-wide explicit-fetch counter agrees: inside the sweep
        # only the epilogue fetched (one per update); the remaining
        # fetches are the sweep-BOUNDARY tracker drain (one per
        # coordinate, off the per-update hot path, bounds HBM growth)
        assert sync_telemetry.host_fetch_count() == 2 * len(coords)

    def test_compacted_sweep_survives_transfer_guard(self, rng):
        """Lane compaction's per-chunk unconverged-mask read is an
        EXPLICIT fetch too: a compacted sweep still runs with implicit
        transfers disallowed."""
        data, *_ = make_game_data(rng, n=240, n_entities=6)
        coords = _build_coords(data, re_chunk=4)
        labels = jnp.asarray(data.responses)
        weights = jnp.asarray(data.weights)
        offsets = jnp.asarray(data.offsets)
        run_coordinate_descent(coords, 1, TaskType.LOGISTIC_REGRESSION,
                               labels, weights, offsets)
        with jax.transfer_guard_device_to_host("disallow"):
            res = run_coordinate_descent(
                coords, 1, TaskType.LOGISTIC_REGRESSION,
                labels, weights, offsets)
        assert len(res.states) == len(coords)


class TestFusedEpilogueParity:
    def test_objective_matches_reference_formula(self, rng):
        """The fused epilogue's objective equals the reference
        ``trainingLossEvaluator(Σ scores) + Σ regularization``
        (CoordinateDescent.scala:199-205) recomputed by hand with the
        legacy eager ops."""
        data, *_ = make_game_data(rng, n=300, n_entities=8)
        re_ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("userId", "per_user", 1))
        prob = RandomEffectOptimizationProblem(
            config=l2_config(lam=0.5), task=TaskType.LOGISTIC_REGRESSION)
        coord = RandomEffectCoordinate(dataset=re_ds, problem=prob)
        labels = jnp.asarray(data.responses)
        weights = jnp.asarray(data.weights)
        offsets = jnp.asarray(data.offsets)

        res = run_coordinate_descent(
            {"perUser": coord}, 1, TaskType.LOGISTIC_REGRESSION,
            labels, weights, offsets)

        # by hand: the same deterministic update, scored and penalized
        # through the pre-fusion eager path
        cand, _ = coord.update(coord.initial_state(),
                               jnp.zeros(data.num_samples))
        score = coord.score(cand)
        from photon_ml_tpu.game.coordinate_descent import (
            training_loss_evaluator,
        )
        loss_eval = training_loss_evaluator(
            TaskType.LOGISTIC_REGRESSION, labels, weights, offsets)
        expected = loss_eval(score) + coord.regularization_value(cand)
        assert res.states[-1].objective == pytest.approx(expected,
                                                         rel=1e-6)


class TestLaneCompactionParity:
    def test_compacted_coefficients_match_single_dispatch(self, rng):
        data, *_ = make_game_data(rng, n=500, n_entities=16)
        re_ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("userId", "per_user", 1))
        base = RandomEffectOptimizationProblem(
            config=l2_config(lam=0.5, max_iter=40),
            task=TaskType.LOGISTIC_REGRESSION)
        compacted = RandomEffectOptimizationProblem(
            config=l2_config(lam=0.5, max_iter=40),
            task=TaskType.LOGISTIC_REGRESSION, lane_compaction_chunk=5)
        offs = re_ds.base_offsets
        c0, it0, _, k0, n0 = base.run(re_ds, offs)
        c1, it1, _, k1, n1 = compacted.run(re_ds, offs)
        # chunk restarts resume the FULL solver carry with the ORIGINAL
        # f₀/‖g₀‖ anchors, so the chunked solve runs exactly the
        # iterations the single dispatch would: coefficients AND
        # per-lane iteration counts are bit-identical, not merely close
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c0))
        nr = len(re_ds.entity_codes)
        np.testing.assert_array_equal(np.asarray(it1)[:nr],
                                      np.asarray(it0)[:nr])
        assert np.asarray(k1).shape == np.asarray(k0).shape
        # ... and so are the evaluation counts: a resumed chunk books no
        # start evaluation, so the chunks add up to the single dispatch
        np.testing.assert_array_equal(np.asarray(n1.evaluations)[:nr],
                                      np.asarray(n0.evaluations)[:nr])

    def test_compacted_bucketed_matches_single_dispatch(self, rng):
        data, *_ = make_game_data(rng, n=500, n_entities=16)

        def run(chunk):
            ds = build_random_effect_dataset(
                data, RandomEffectDataConfiguration(
                    "userId", "per_user", 1), num_buckets=3)
            prob = RandomEffectOptimizationProblem(
                config=l2_config(lam=0.5, max_iter=40),
                task=TaskType.LOGISTIC_REGRESSION,
                lane_compaction_chunk=chunk)
            offs = ds.offsets_with(jnp.zeros(data.num_samples))
            c, *_ = prob.run(ds, offs)
            return np.asarray(c)

        # exact-resume chunking: bit-identical per bucket too
        np.testing.assert_array_equal(run(4), run(0))

    def test_compaction_shrinks_active_lanes(self, rng):
        """On entity blocks with heterogeneous convergence the lane count
        entering successive chunks must be non-increasing (that shrinkage
        IS the FLOP saving) and the telemetry must record it."""
        data, *_ = make_game_data(rng, n=600, n_entities=24)
        re_ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("userId", "per_user", 1))
        prob = RandomEffectOptimizationProblem(
            config=l2_config(lam=0.5, max_iter=60),
            task=TaskType.LOGISTIC_REGRESSION, lane_compaction_chunk=3)
        re_mod.reset_solve_stats()
        prob.run(re_ds, re_ds.base_offsets)
        stats = re_mod.SOLVE_STATS
        assert stats["chunks"] >= 1
        lanes = stats["lane_counts"]
        assert lanes == sorted(lanes, reverse=True)
        if lanes:  # stragglers existed: fewer than all lanes re-ran
            assert lanes[-1] < re_ds.X.shape[0]


class TestLazyMaterialization:
    def test_deferred_result_matches_eager_run(self, rng):
        data, *_ = make_game_data(rng, n=300, n_entities=6)
        ds = build_fixed_effect_dataset(data, "global")
        prob = GLMOptimizationProblem(config=l2_config(lam=0.5),
                                      task=TaskType.LOGISTIC_REGRESSION)
        # f32 extra scores: mixing an f64 offset vector into an f32 batch
        # is a pre-existing solver-dtype limitation unrelated to laziness
        batch = ds.with_offsets(jnp.zeros(data.num_samples, jnp.float32))
        _, eager = prob.run(batch)
        lazy = prob.run_lazy(batch)
        np.testing.assert_allclose(np.asarray(lazy.coefficients),
                                   np.asarray(eager.coefficients))
        assert lazy.iterations == eager.iterations
        assert lazy.convergence_reason == eager.convergence_reason
        assert lazy.value == pytest.approx(eager.value)

    def test_lazy_tracker_counts_match(self, rng):
        data, *_ = make_game_data(rng, n=300, n_entities=8)
        re_ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("userId", "per_user", 1))
        coord = RandomEffectCoordinate(
            dataset=re_ds,
            problem=RandomEffectOptimizationProblem(
                config=l2_config(lam=0.5),
                task=TaskType.LOGISTIC_REGRESSION))
        _, tracker = coord.update(None, jnp.zeros(data.num_samples))
        # lazy: per-entity arrays still on device, then one fetch
        counts = tracker.counts_by_convergence()
        assert sum(counts.values()) == re_ds.num_entities
        assert isinstance(tracker.iterations, np.ndarray)
        assert len(tracker.iterations) == re_ds.num_entities
        assert "entities" in tracker.summary()
