"""Mesh-sharded GAME: random-effect entity blocks partitioned over the
device mesh's entity axis, and the fixed-effect weight update sharded
across replicas (arXiv 2004.13336).

Parity strategy mirrors test_mesh_routing.py: the strict gates run in
float64, where the sharded solve's only legitimate deviation — reduction
order — sits at machine epsilon. Sharded solves are another XLA program
(``shard_map``) than the unsharded ``vmap`` dispatch, so both the
single-bucket and the bucketed cases are held to rtol 1e-10 / atol 1e-12
(2.3e-12 measured), and the single-bucket one to equal per-lane
iteration counts besides (same lanes, same chunk schedule). The 4-way
entity mesh is carved from the conftest's 8 virtual CPU devices
(2 data x 4 entity), so the `shard_map` dispatch, the per-shard lane
compaction, and the psum score reduction all run for real.
"""

import dataclasses
import logging
import os

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.batch import dense_batch
from photon_ml_tpu.game.coordinate import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.coordinate_descent import (
    RecoveryPolicy,
    run_coordinate_descent,
)
from photon_ml_tpu.game.dataset import (
    GameDataset,
    RandomEffectDataConfiguration,
    build_fixed_effect_dataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.game import random_effect as re_mod
from photon_ml_tpu.game.random_effect import (
    RandomEffectOptimizationProblem,
    SOLVE_STATS,
    reset_solve_stats,
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
from photon_ml_tpu.parallel import distributed
from photon_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    ENTITY_AXIS,
    largest_entity_divisor,
    make_mesh,
    set_default_mesh,
    setup_default_mesh,
)
from photon_ml_tpu.utils import faults
from photon_ml_tpu.utils import sync_telemetry
from photon_ml_tpu.utils.events import EventEmitter, RecoveryEvent
from test_game import lanes_that_skip_the_store_before


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.disarm_all()
    yield
    faults.disarm_all()


RE_CFG = RandomEffectDataConfiguration(
    random_effect_type="userId", feature_shard_id="per_user",
    num_partitions=1)

#: (name, optimizer, regularization, lambda) — all three solver paths
SOLVERS = [
    ("lbfgs", OptimizerType.LBFGS, RegularizationType.L2, 0.5),
    ("owlqn", OptimizerType.LBFGS, RegularizationType.L1, 0.3),
    ("tron", OptimizerType.TRON, RegularizationType.L2, 0.5),
]


def _glm_cfg(opt, reg, lam, max_iter=40):
    return GLMOptimizationConfiguration(
        max_iterations=max_iter, tolerance=1e-9,
        regularization_weight=lam, optimizer_type=opt,
        regularization_context=RegularizationContext(reg))


def _re_data(rng, n=700, d=5, n_entities=33):
    """Zipf-free but ragged: 33 entities never divide 4 shards without
    the dataset's entity_axis_size padding."""
    Xe = rng.normal(size=(n, d))
    users = rng.integers(0, n_entities, size=n)
    W = rng.normal(size=(n_entities, d)) * 2.0
    margin = np.einsum("nd,nd->n", Xe, W[users])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float64)
    data = GameDataset(responses=y,
                       feature_shards={"per_user": sp.csr_matrix(Xe)})
    data.encode_ids("userId", users)
    return data


def _re_ds(data, num_buckets=1):
    return build_random_effect_dataset(
        data, RE_CFG, num_buckets=num_buckets, entity_axis_size=4,
        dtype=jnp.float64)


def _entity_mesh():
    return make_mesh(num_data=2, num_entity=4)


def _run_pair(ds, n, cfg, chunk):
    """(reference unsharded, sharded-over-4) solves of the same dataset."""
    off = ds.offsets_with(np.zeros(n))
    set_default_mesh(None)
    ref = RandomEffectOptimizationProblem(
        cfg, TaskType.LOGISTIC_REGRESSION, lane_compaction_chunk=0,
    ).run(ds, off)
    set_default_mesh(_entity_mesh())
    out = RandomEffectOptimizationProblem(
        cfg, TaskType.LOGISTIC_REGRESSION, lane_compaction_chunk=chunk,
        entity_shards=4,
    ).run(ds, off)
    return ref, out


# ---------------------------------------------------------------------------
# Mesh factorization fallback (setup_default_mesh contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,requested,want", [
    (8, 8, 8), (8, 4, 4), (8, 3, 2), (8, 5, 4), (8, 6, 4),
    (8, 1, 1), (8, 12, 8), (6, 4, 3), (7, 3, 1), (1, 5, 1),
])
def test_largest_entity_divisor(n, requested, want):
    got = largest_entity_divisor(n, requested)
    assert got == want
    assert n % got == 0 and got <= max(1, min(requested, n))


def test_setup_default_mesh_honors_nondividing_with_warning(caplog):
    with caplog.at_level(logging.WARNING,
                         logger="photon_ml_tpu.parallel.mesh"):
        mesh = setup_default_mesh(num_entity=3)  # 3 does not divide 8
    assert mesh is not None
    assert mesh.shape[ENTITY_AXIS] == 2 and mesh.shape[DATA_AXIS] == 4
    assert any("does not divide" in r.getMessage()
               for r in caplog.records)


def test_setup_default_mesh_exact_request_no_warning(caplog):
    with caplog.at_level(logging.WARNING,
                         logger="photon_ml_tpu.parallel.mesh"):
        mesh = setup_default_mesh(num_entity=4)
    assert mesh.shape[ENTITY_AXIS] == 4 and mesh.shape[DATA_AXIS] == 2
    assert not caplog.records


# ---------------------------------------------------------------------------
# Sharded-vs-single solve parity (tentpole numerics)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,opt,reg,lam", SOLVERS,
                         ids=[s[0] for s in SOLVERS])
@pytest.mark.parametrize("chunk", [0, 8])
def test_sharded_single_bucket_parity_f64(rng, name, opt, reg, lam, chunk):
    """One bucket, f64: the sharded solve partitions the SAME lanes the
    unsharded dispatch runs, but as another XLA program (``shard_map``
    against ``vmap``), so the floats agree to the last few bits and not
    bit for bit (2.3e-12 measured): coefficients and scores within the
    file's f64 tolerance, per-lane iteration counts exactly — chunked
    or not."""
    data = _re_data(rng)
    ds = _re_ds(data, num_buckets=1)
    ref, out = _run_pair(ds, len(data.responses),
                         _glm_cfg(opt, reg, lam), chunk)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               rtol=1e-10, atol=1e-12)
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(ref[1]))
    s_ref = np.asarray(score_random_effect(ds, ref[0]))
    set_default_mesh(_entity_mesh())
    s_out = np.asarray(score_random_effect(ds, out[0], entity_shards=4))
    np.testing.assert_allclose(s_out, s_ref, rtol=1e-10, atol=1e-12)


@pytest.mark.parametrize("name,opt,reg,lam", SOLVERS,
                         ids=[s[0] for s in SOLVERS])
def test_sharded_bucketed_parity_f64(rng, name, opt, reg, lam):
    """Ragged entity buckets (33 entities, 3 buckets, shard/unshard
    round-trip through the per-bucket repack), f64: machine-epsilon
    agreement with the unsharded solve."""
    data = _re_data(rng)
    ds = _re_ds(data, num_buckets=3)
    ref, out = _run_pair(ds, len(data.responses),
                         _glm_cfg(opt, reg, lam), chunk=6)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               rtol=1e-10, atol=1e-12)
    s_ref = np.asarray(score_random_effect(ds, ref[0]))
    set_default_mesh(_entity_mesh())
    s_out = np.asarray(score_random_effect(ds, out[0], entity_shards=4))
    np.testing.assert_allclose(s_out, s_ref, rtol=1e-10, atol=1e-12)


@pytest.mark.parametrize("name,opt,reg,lam", SOLVERS[:2],
                         ids=[s[0] for s in SOLVERS[:2]])
def test_sharded_chunks_resume_past_a_skipped_store_f64(rng, name, opt, reg,
                                                        lam):
    """The sharded resume path with the newest-first carry where it is
    tested hardest: under a tolerance of 1e-13 some lanes' 10th iteration
    stores no pair (``s.y <= 1e-10``) and they go on, so with chunks of 10
    their history crosses the boundary unshifted and is gathered, on
    device, into the resumed program. Per-lane iteration counts exactly
    and coefficients to the file's f64 tolerance, as unchunked."""
    chunk = 10
    data = _re_data(rng)
    ds = _re_ds(data, num_buckets=1)
    n = len(data.responses)
    cfg = dataclasses.replace(_glm_cfg(opt, reg, lam), tolerance=1e-13)

    problem = RandomEffectOptimizationProblem(cfg,
                                              TaskType.LOGISTIC_REGRESSION)
    e, _, d = ds.X.shape
    l1 = jnp.full(d, cfg.regularization_context.l1_weight(
        cfg.regularization_weight))

    skipped_and_goes_on = lanes_that_skip_the_store_before(
        chunk, (ds.X, ds.labels, jnp.asarray(ds.offsets_with(np.zeros(n))),
                ds.weights, jnp.zeros((e, d))),
        problem.objective(), l1, name, 1e-13)
    assert skipped_and_goes_on

    ref, out = _run_pair(ds, n, cfg, chunk)
    assert (np.asarray(ref[1])[skipped_and_goes_on] > chunk).all()
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               rtol=1e-10, atol=1e-12)
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(ref[1]))


def test_entity_shards_without_mesh_falls_back_bit_identical(rng, caplog):
    """No default mesh installed: entity_shards>1 degrades to the
    replicated path (one logged warning), bit-identical output."""
    data = _re_data(rng)
    ds = _re_ds(data, num_buckets=1)
    off = ds.offsets_with(np.zeros(len(data.responses)))
    cfg = _glm_cfg(OptimizerType.LBFGS, RegularizationType.L2, 0.5)
    set_default_mesh(None)
    ref = RandomEffectOptimizationProblem(
        cfg, TaskType.LOGISTIC_REGRESSION).run(ds, off)
    re_mod._SHARD_FALLBACK_WARNED.clear()
    with caplog.at_level(logging.WARNING,
                         logger="photon_ml_tpu.game.random_effect"):
        out = RandomEffectOptimizationProblem(
            cfg, TaskType.LOGISTIC_REGRESSION, entity_shards=4,
        ).run(ds, off)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))
    assert any("no default mesh" in r.getMessage()
               for r in caplog.records)


# ---------------------------------------------------------------------------
# Per-shard lane-compaction accounting + sync discipline
# ---------------------------------------------------------------------------


def test_per_shard_padding_accounting(rng):
    """The chunked sharded solve reports per-shard active-lane counts and
    the pow2 pad accounting: padded >= real, padded a multiple of the
    shard count per repack, per-shard rows length 4."""
    data = _re_data(rng)
    ds = _re_ds(data, num_buckets=1)
    off = ds.offsets_with(np.zeros(len(data.responses)))
    set_default_mesh(_entity_mesh())
    reset_solve_stats()
    RandomEffectOptimizationProblem(
        _glm_cfg(OptimizerType.LBFGS, RegularizationType.L2, 0.5),
        TaskType.LOGISTIC_REGRESSION, lane_compaction_chunk=5,
        entity_shards=4,
    ).run(ds, off)
    assert SOLVE_STATS["shard_real_lanes"] > 0
    assert (SOLVE_STATS["shard_padded_lanes"]
            >= SOLVE_STATS["shard_real_lanes"])
    assert SOLVE_STATS["chunks"] >= 1
    for row in SOLVE_STATS["shard_lane_counts"]:
        assert len(row) == 4 and all(c >= 0 for c in row)


def test_sharded_chunked_solve_zero_new_host_fetches(rng):
    """Transfer-guard cell: the sharded chunked solve runs with implicit
    device→host transfers DISALLOWED, and its explicit-fetch count equals
    the unsharded compacted solve's — sharding adds ZERO new sync
    sites (the per-chunk unconverged-mask read is the only one)."""
    data = _re_data(rng)
    ds = _re_ds(data, num_buckets=1)
    off = ds.offsets_with(np.zeros(len(data.responses)))
    cfg = _glm_cfg(OptimizerType.LBFGS, RegularizationType.L2, 0.5)

    set_default_mesh(None)
    prob_ref = RandomEffectOptimizationProblem(
        cfg, TaskType.LOGISTIC_REGRESSION, lane_compaction_chunk=6)
    prob_ref.run(ds, off)  # warm outside any counting
    sync_telemetry.reset_host_fetches()
    prob_ref.run(ds, off)
    base_fetches = sync_telemetry.host_fetch_count()

    set_default_mesh(_entity_mesh())
    prob = RandomEffectOptimizationProblem(
        cfg, TaskType.LOGISTIC_REGRESSION, lane_compaction_chunk=6,
        entity_shards=4)
    prob.run(ds, off)  # compile everything outside the guard
    sync_telemetry.reset_host_fetches()
    with jax.transfer_guard_device_to_host("disallow"):
        out = prob.run(ds, off)
    assert np.isfinite(np.asarray(out[0])).all()
    assert sync_telemetry.host_fetch_count() == base_fetches


# ---------------------------------------------------------------------------
# Fixed-effect weight-update sharding (arXiv 2004.13336)
# ---------------------------------------------------------------------------


def _fe_batch(rng, n=264, d=9, dtype=jnp.float64):
    X = rng.normal(size=(n, d))
    w = rng.normal(size=d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w)))).astype(float)
    return dense_batch(X, y, dtype=dtype)


@pytest.mark.parametrize("name,opt,reg,lam", SOLVERS,
                         ids=[s[0] for s in SOLVERS])
def test_fe_sharded_weight_update_parity_f64(rng, name, opt, reg, lam):
    """The weight-update-sharded fit (optimizer state + coefficient
    update split over replicas, converged shard all-gathered) reaches
    the local optimum to machine epsilon in f64 — d=9 exercises the
    zero-padded non-dividing coefficient split too."""
    batch = _fe_batch(rng)
    problem = GLMOptimizationProblem(
        config=_glm_cfg(opt, reg, lam),
        task=TaskType.LOGISTIC_REGRESSION)
    model_local, _ = problem.run(batch)
    import dataclasses
    sharded = dataclasses.replace(problem, shard_weight_update=True)
    model_dist, _ = distributed.run_glm_shard_map(
        sharded, batch, make_mesh())
    np.testing.assert_allclose(
        np.asarray(model_dist.coefficients.means),
        np.asarray(model_local.coefficients.means),
        rtol=1e-10, atol=1e-12)


# ---------------------------------------------------------------------------
# Chaos cell: re.shard_dispatch rides the CD recovery ladder
# ---------------------------------------------------------------------------


def _game_coords(rng, entity_shards, n=400, d_global=6, d_entity=4,
                 n_entities=24):
    Xg = rng.normal(size=(n, d_global))
    Xe = rng.normal(size=(n, d_entity))
    users = rng.integers(0, n_entities, size=n)
    wg = rng.normal(size=d_global)
    We = rng.normal(size=(n_entities, d_entity))
    margin = Xg @ wg + np.einsum("nd,nd->n", Xe, We[users])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float64)
    data = GameDataset(responses=y,
                       feature_shards={"global": sp.csr_matrix(Xg),
                                       "per_user": sp.csr_matrix(Xe)})
    data.encode_ids("userId", users)
    coords = {
        "fixed": FixedEffectCoordinate(
            dataset=build_fixed_effect_dataset(data, "global"),
            problem=GLMOptimizationProblem(
                config=_glm_cfg(OptimizerType.LBFGS,
                                RegularizationType.L2, 1.0, max_iter=30),
                task=TaskType.LOGISTIC_REGRESSION)),
        "perUser": RandomEffectCoordinate(
            dataset=build_random_effect_dataset(
                data, RE_CFG, entity_axis_size=4),
            problem=RandomEffectOptimizationProblem(
                _glm_cfg(OptimizerType.LBFGS, RegularizationType.L2,
                         1.0, max_iter=30),
                TaskType.LOGISTIC_REGRESSION,
                entity_shards=entity_shards)),
    }
    return data, coords


def _run_cd(data, coords, iters=2, **kw):
    return run_coordinate_descent(
        coords, iters, TaskType.LOGISTIC_REGRESSION,
        jnp.asarray(data.responses), jnp.asarray(data.weights),
        jnp.asarray(data.offsets), **kw)


def test_shard_dispatch_fault_rides_recovery_ladder(rng):
    """A NaN fault injected at re.shard_dispatch (the sharded solve's
    coefficient block, post-dispatch) poisons the mesh-sharded RE update;
    the existing CD recovery ladder catches the non-finite epilogue,
    retries (damping=1.0 -> exact re-solve), and the run lands on the
    unfaulted trajectory bit for bit."""
    data, coords = _game_coords(rng, entity_shards=4)
    set_default_mesh(_entity_mesh())
    ref = _run_cd(data, coords, iters=2)

    faults.arm("re.shard_dispatch", "nan", times=1)
    seen = []
    emitter = EventEmitter()
    emitter.register_listener(seen.append)
    res = _run_cd(
        data, coords, iters=2,
        recovery=RecoveryPolicy(max_retries=2, on_exhausted="abort",
                                damping=1.0),
        events=emitter)

    assert faults.hits("re.shard_dispatch") == 1
    objs = [s.objective for s in res.states]
    assert np.isfinite(objs).all()
    # bit-exact resume onto the clean trajectory
    assert float(res.states[-1].objective) == float(ref.states[-1].objective)
    recov = [e for e in seen if isinstance(e, RecoveryEvent)]
    assert {"retried", "recovered"} <= {e.action for e in recov}


def test_driver_re_entity_shards_auto_parity(tmp_path):
    """Acceptance cell for the driver wiring: one GAME training-driver
    run with ``--re-entity-shards auto`` (8 virtual devices -> an
    8-shard entity mesh) against the default run (8-way data mesh),
    with the sharded dispatch asserted to have actually engaged.

    Tolerance note: ``auto`` changes the mesh factorization for BOTH
    sides — the fixed effect's data axis goes 8 -> 1, which
    reassociates its f32 row sums and (at tolerance 1e-7, below the f32
    noise floor) shifts its stopping point by ~1e-4; those coefficients
    enter the RE solve as offsets, so the whole model is gated at the
    f32 noise-floor bound test_mesh_routing.py pins. The entity
    sharding itself sits at f64 machine epsilon — single-bucket and
    bucketed parity are pinned at rtol 1e-10 / atol 1e-12 in f64 by
    the library-level tests above."""
    from test_drivers import _make_game_avro

    from photon_ml_tpu.cli.game_training_driver import main as game_main
    from photon_ml_tpu.io.model_io import load_game_model

    train = str(tmp_path / "train.avro")
    validate = str(tmp_path / "validate.avro")
    _make_game_avro(train, n=300, seed=0)
    _make_game_avro(validate, n=120, seed=1)
    args = [
        "--train-input-dirs", train,
        "--validate-input-dirs", validate,
        "--task-type", "LOGISTIC_REGRESSION",
        "--feature-shard-id-to-feature-section-keys-map",
        "global:globalFeatures|user:userFeatures",
        "--updating-sequence", "fixed,perUser",
        "--num-iterations", "2",
        "--fixed-effect-data-configurations", "fixed:global,1",
        "--fixed-effect-optimization-configurations",
        "fixed:30,1e-7,0.1,1,LBFGS,L2",
        "--random-effect-data-configurations", "perUser:userId,user,1",
        "--random-effect-optimization-configurations",
        "perUser:30,1e-7,1.0,1,LBFGS,L2",
        "--evaluator-type", "AUC",
    ]
    out_ref = str(tmp_path / "out-ref")
    game_main(args + ["--output-dir", out_ref])
    out_auto = str(tmp_path / "out-auto")
    reset_solve_stats()
    game_main(args + ["--output-dir", out_auto,
                      "--re-entity-shards", "auto"])
    # the sharded dispatch actually ran (full-block dispatches count
    # every lane into both shard counters)
    assert SOLVE_STATS["shard_real_lanes"] > 0
    from photon_ml_tpu.obs.metrics import REGISTRY
    assert REGISTRY.gauge("re_entity_shards").value() == 8

    ref_model, _ = load_game_model(os.path.join(out_ref, "best"),
                                   task=TaskType.LOGISTIC_REGRESSION)
    auto_model, _ = load_game_model(os.path.join(out_auto, "best"),
                                    task=TaskType.LOGISTIC_REGRESSION)
    re_ref = ref_model.models["perUser"]
    re_auto = auto_model.models["perUser"]
    np.testing.assert_array_equal(re_auto.entity_codes,
                                  re_ref.entity_codes)
    np.testing.assert_allclose(np.asarray(re_auto.coefficients),
                               np.asarray(re_ref.coefficients),
                               rtol=1e-3, atol=5e-4)
    np.testing.assert_allclose(
        np.asarray(auto_model.models["fixed"].model.coefficients.means),
        np.asarray(ref_model.models["fixed"].model.coefficients.means),
        rtol=1e-3, atol=5e-4)


def test_shard_dispatch_fault_point_registered():
    assert "re.shard_dispatch" in faults.FAULT_POINTS
    info = faults.FAULT_POINTS["re.shard_dispatch"]
    assert "nan" in info.modes and "raise" in info.modes
