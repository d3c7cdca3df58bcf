"""Pipelined + block-parallel coordinate descent: the parity suite.

Contracts under test (game/coordinate_descent.py):

- the DOUBLE-BUFFERED sweep (``pipeline_depth=1``, the default) is
  BIT-EXACT with the sequential sweep at block size 1 — the speculative
  dispatch consumes the previous epilogue's device arrays, which are the
  very objects the sequential commit installs, so only host ordering
  differs;
- BLOCK-PARALLEL sweeps (``block_size=B``) solve against a stale
  block-start total with one fused re-canonicalizing correction per
  block: trajectories agree with the sequential sweep within tolerance,
  and the amortized hot-loop fetch rate drops to 1/B;
- the recovery ladder tolerates acting one update late: a divergence
  surfacing at a pipelined fetch rolls the in-flight successor back
  (RNG stream positions included) and replays from last-good state,
  landing float-for-float on the sequential recovery run;
- checkpoint snapshots only land at block boundaries, and a mid-run
  resume of a blocked sweep is bit-exact (the in-process half of the
  crash_resume_drill's mid-block cell);
- ``run_lazy`` results are safe multi-in-flight (forced out of order);
- the sweep-boundary drain samples ``hbm_live_bytes`` when tracing.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax.numpy as jnp

from photon_ml_tpu.game import coordinate_descent as cd
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
from photon_ml_tpu.game.random_effect import (
    RandomEffectOptimizationProblem,
)
from photon_ml_tpu.obs import trace
from photon_ml_tpu.obs.metrics import REGISTRY
from photon_ml_tpu.optimize.config import (
    GLMOptimizationConfiguration,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
    TaskType,
)
from photon_ml_tpu.optimize.problem import GLMOptimizationProblem
from photon_ml_tpu.utils import faults
from photon_ml_tpu.utils.events import EventEmitter

TASK = TaskType.LOGISTIC_REGRESSION


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.disarm_all()
    yield
    faults.disarm_all()


def make_data(rng, n=400, d_global=6, d_entity=3, n_users=10, n_items=7):
    """Fixed + per-user + per-item logistic GAME data: three coordinates,
    so a pipelined sweep genuinely overlaps and block size 2 splits a
    sweep into uneven blocks (2 + 1)."""
    Xg = rng.normal(size=(n, d_global))
    Xu = rng.normal(size=(n, d_entity))
    Xi = rng.normal(size=(n, d_entity))
    users = rng.integers(0, n_users, size=n)
    items = rng.integers(0, n_items, size=n)
    w = rng.normal(size=d_global)
    Wu = rng.normal(size=(n_users, d_entity))
    Wi = rng.normal(size=(n_items, d_entity))
    margin = (Xg @ w + np.einsum("nd,nd->n", Xu, Wu[users])
              + np.einsum("nd,nd->n", Xi, Wi[items]))
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(
        np.float64)
    data = GameDataset(
        responses=y,
        feature_shards={"global": sp.csr_matrix(Xg),
                        "per_user": sp.csr_matrix(Xu),
                        "per_item": sp.csr_matrix(Xi)})
    data.encode_ids("userId", users)
    data.encode_ids("itemId", items)
    return data


def l2_config(lam=0.5, max_iter=25):
    return GLMOptimizationConfiguration(
        max_iterations=max_iter, tolerance=1e-8, regularization_weight=lam,
        optimizer_type=OptimizerType.LBFGS,
        regularization_context=RegularizationContext(RegularizationType.L2))


def build_coords(data):
    """Fresh coordinate objects (they hold per-run state: RNG counters,
    lazy caches) over the SAME datasets — every parity run must start
    identical."""
    return {
        "fixed": FixedEffectCoordinate(
            dataset=build_fixed_effect_dataset(data, "global"),
            problem=GLMOptimizationProblem(config=l2_config(),
                                           task=TASK)),
        "perUser": RandomEffectCoordinate(
            dataset=build_random_effect_dataset(
                data, RandomEffectDataConfiguration(
                    "userId", "per_user", 1)),
            problem=RandomEffectOptimizationProblem(
                config=l2_config(), task=TASK)),
        "perItem": RandomEffectCoordinate(
            dataset=build_random_effect_dataset(
                data, RandomEffectDataConfiguration(
                    "itemId", "per_item", 1)),
            problem=RandomEffectOptimizationProblem(
                config=l2_config(), task=TASK)),
    }


def run_cd(data, iters=2, **kwargs):
    return run_coordinate_descent(
        build_coords(data), iters, TASK,
        jnp.asarray(data.responses), jnp.asarray(data.weights),
        jnp.asarray(data.offsets), **kwargs)


def final_states(result):
    """Raw per-coordinate coefficient arrays off the published model."""
    out = {}
    for cid, m in result.model.models.items():
        coefs = getattr(getattr(m, "model", m), "coefficients", None)
        if coefs is not None:
            out[cid] = np.asarray(coefs.means)
        else:
            out[cid] = np.asarray(m.coefficients_projected)
    return out


class TestDoubleBufferingParity:
    def test_block1_pipelined_bitexact_vs_sequential(self, rng):
        data = make_data(rng)
        seq = run_cd(data, iters=2, pipeline_depth=0)
        pipe = run_cd(data, iters=2, pipeline_depth=1)
        # identical device programs consumed in identical order — the
        # committed floats (objectives AND coefficients) are bit-equal
        assert [s.objective for s in seq.states] \
            == [s.objective for s in pipe.states]
        fs, fp = final_states(seq), final_states(pipe)
        assert sorted(fs) == sorted(fp)
        for cid in fs:
            np.testing.assert_array_equal(fs[cid], fp[cid])

    def test_pipeline_overlap_telemetry(self, rng):
        data = make_data(rng)
        run_cd(data, iters=1)  # warm compile outside the measurement
        cd.reset_hot_loop_stats()
        run_cd(data, iters=2, pipeline_depth=1)
        assert cd.HOT_LOOP_STATS["max_inflight"] >= 2
        assert cd.HOT_LOOP_STATS["pipelined_resolves"] >= 1
        assert (cd.HOT_LOOP_STATS["epilogue_fetches"]
                == cd.HOT_LOOP_STATS["updates"])
        assert REGISTRY.gauge("cd_inflight_updates").total() >= 2
        cd.reset_hot_loop_stats()
        run_cd(data, iters=2, pipeline_depth=0)
        assert cd.HOT_LOOP_STATS["max_inflight"] == 0  # never overlapped
        assert cd.HOT_LOOP_STATS["pipelined_resolves"] == 0

    def test_depth_and_block_validation(self, rng):
        data = make_data(rng)
        with pytest.raises(ValueError, match="pipeline_depth"):
            run_cd(data, iters=1, pipeline_depth=2)
        with pytest.raises(ValueError, match="block_size"):
            run_cd(data, iters=1, block_size=0)


class TestBlockParallelSweeps:
    def test_blocked_matches_sequential_within_tolerance(self, rng):
        """Stale block-start partials are Jacobi-style updates: each
        sweep corrects them, so the blocked trajectory converges to the
        sequential optimum geometrically (measured on this fixture:
        objective rel gap ~4e-3 → ~3e-4 from sweep 5 to 8 at full
        parallelism). Assert proximity after enough sweeps AND that more
        sweeps shrink the gap — the correction step is doing its job."""
        data = make_data(rng)
        seq5 = run_cd(data, iters=5, pipeline_depth=0)
        seq8 = run_cd(data, iters=8, pipeline_depth=0)
        for bs in (2, 3):
            blk5 = run_cd(data, iters=5, block_size=bs)
            blk8 = run_cd(data, iters=8, block_size=bs)
            gap5 = abs(blk5.states[-1].objective
                       - seq5.states[-1].objective)
            gap8 = abs(blk8.states[-1].objective
                       - seq8.states[-1].objective)
            assert blk8.states[-1].objective == pytest.approx(
                seq8.states[-1].objective, rel=1e-3)
            assert gap8 < gap5  # staleness correction converges
            fs, fb = final_states(seq8), final_states(blk8)
            for cid in fs:
                np.testing.assert_allclose(fb[cid], fs[cid],
                                           rtol=0.1, atol=0.1)

    def test_block_amortizes_fetches(self, rng):
        data = make_data(rng)
        run_cd(data, iters=1, block_size=2)  # warm
        cd.reset_hot_loop_stats()
        run_cd(data, iters=2, block_size=2)
        # 3 coordinates per sweep in blocks of (2, 1): 2 fetches per
        # sweep for 3 updates — the amortized rate drops below 1
        assert cd.HOT_LOOP_STATS["updates"] == 6
        assert cd.HOT_LOOP_STATS["epilogue_fetches"] == 4
        rate = (cd.HOT_LOOP_STATS["epilogue_fetches"]
                / cd.HOT_LOOP_STATS["updates"])
        assert rate <= 1.0

    def test_block1_is_sequential_semantics(self, rng):
        data = make_data(rng)
        a = run_cd(data, iters=2, block_size=1, pipeline_depth=0)
        b = run_cd(data, iters=2, block_size=1, pipeline_depth=1)
        np.testing.assert_array_equal(
            np.asarray([s.objective for s in a.states]),
            np.asarray([s.objective for s in b.states]))


class TestRecoveryOneUpdateLate:
    def test_transient_fault_while_in_flight_recovers_bitexact(self, rng):
        """A nan fault poisons coordinate 1's update; under pipelining
        the divergence surfaces at its fetch, AFTER coordinate 2 was
        dispatched against the poisoned total. The ladder retries from
        last-good, the speculative successor rolls back and re-runs —
        and the result matches the sequential recovery run float for
        float."""
        data = make_data(rng)
        policy = RecoveryPolicy(max_retries=2, on_exhausted="abort",
                                damping=1.0)

        faults.arm("cd.update", "nan", times=1, tag="0.1")
        seq = run_cd(data, iters=2, pipeline_depth=0, recovery=policy)

        faults.arm("cd.update", "nan", times=1, tag="0.1")
        seen = []
        emitter = EventEmitter()
        emitter.register_listener(seen.append)
        pipe = run_cd(data, iters=2, pipeline_depth=1, recovery=policy,
                      events=emitter)

        kinds = [type(e).__name__ for e in seen]
        assert "FaultEvent" in kinds and "RecoveryEvent" in kinds
        objs = [s.objective for s in pipe.states]
        assert np.isfinite(objs).all()
        assert objs == [s.objective for s in seq.states]
        fs, fp = final_states(seq), final_states(pipe)
        for cid in fs:
            np.testing.assert_array_equal(fs[cid], fp[cid])

    def test_injected_fault_at_speculative_dispatch(self, rng):
        """A raise-mode fault fires DURING the speculative dispatch of
        coordinate 2 (while coordinate 1 is still in flight): the
        pending update settles first, then the faulted coordinate walks
        its ladder — run completes with a recovery event trail."""
        data = make_data(rng)
        faults.arm("cd.update", "raise", times=1, tag="0.2")
        seen = []
        emitter = EventEmitter()
        emitter.register_listener(seen.append)
        res = run_cd(data, iters=2, pipeline_depth=1,
                     recovery=RecoveryPolicy(max_retries=2,
                                             on_exhausted="abort"),
                     events=emitter)
        assert len(res.states) == 6  # 3 coords x 2 sweeps, none lost
        assert np.isfinite([s.objective for s in res.states]).all()
        actions = [getattr(e, "action", None) for e in seen]
        assert "retried" in actions and "recovered" in actions

    def test_quarantine_under_blocked_pipeline(self, rng, tmp_path):
        """A chronically-raising coordinate inside a block is quarantined
        by its own budget while the rest of the blocked sweep continues
        (the block replays members sequentially on failure) — and even
        with the [0,1] block reduced to its surviving member, snapshots
        keep landing at RAW block boundaries (a filtered-block boundary
        would re-partition the sweep on resume)."""
        from photon_ml_tpu.utils.checkpoint import CheckpointManager

        data = make_data(rng)
        for it in range(4):
            faults.arm("cd.update", "raise", times=100, tag=f"{it}.1")
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        res = run_cd(data, iters=4, block_size=2,
                     recovery=RecoveryPolicy(max_retries=0,
                                             on_exhausted="abort",
                                             quarantine_after=2),
                     checkpoint_manager=mgr,
                     checkpoint_every_coordinates=1)
        assert res.quarantined == ["perUser"]
        # the other coordinates kept training every sweep
        per_sweep = {}
        for s in res.states:
            per_sweep.setdefault(s.iteration, []).append(s.coordinate_id)
        assert all("fixed" in v and "perItem" in v
                   for v in per_sweep.values())
        # raw blocks over 3 coordinates at size 2 are [0,1] and [2]:
        # even after perUser (ci=1) quarantines out of its block, legal
        # snapshot indices stay the RAW boundaries {2, 0}, never 1
        indices = {mgr.restore(step=s).get("coordinate_index")
                   for s in mgr.all_steps()}
        assert indices <= {0, 2}, sorted(indices)


class TestSnapshotConsistencyUnderFaults:
    def test_quarantine_snapshot_excludes_speculative_rng_advance(
            self, rng, tmp_path):
        """A chronically-diverging coordinate quarantines while the NEXT
        coordinate's speculative dispatch is in flight. The speculative
        dispatch advanced a down-sampling coordinate's RNG counter; the
        quarantine-path snapshot must record the ROLLED-BACK counter
        (the live run discards that dispatch and re-draws the same key),
        or resume would re-dispatch with a different down-sample and
        break bit-exactness."""
        from photon_ml_tpu.utils.checkpoint import CheckpointManager

        data = make_data(rng)

        def coords_with_downsampling():
            base = build_coords(data)
            # faulting RE coordinate FIRST, down-sampler second: the
            # down-sampler's dispatch is the in-flight speculation when
            # the RE divergence surfaces
            ds_cfg = dataclasses_replace_downsample(l2_config(), 0.7)
            fixed = FixedEffectCoordinate(
                dataset=build_fixed_effect_dataset(data, "global"),
                problem=GLMOptimizationProblem(config=ds_cfg, task=TASK))
            return {"perUser": base["perUser"], "fixed": fixed}

        def run(coords, **kw):
            return run_coordinate_descent(
                coords, 2, TASK, jnp.asarray(data.responses),
                jnp.asarray(data.weights), jnp.asarray(data.offsets),
                recovery=RecoveryPolicy(max_retries=0,
                                        on_exhausted="abort",
                                        quarantine_after=1), **kw)

        faults.arm("cd.update", "nan", times=100, tag="0.0")
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        full = run(coords_with_downsampling(), checkpoint_manager=mgr,
                   checkpoint_every_coordinates=1)
        faults.disarm_all()
        assert full.quarantined == ["perUser"]

        # the quarantine snapshot (step 1: about to run 'fixed' at sweep
        # 0) must NOT carry the speculative dispatch's advanced counter
        snap = mgr.restore(step=1)
        assert snap.get("update_counts", {}).get("fixed", 0) == 0, (
            "snapshot persisted a rolled-back speculative RNG advance")

        resumed = run(coords_with_downsampling(), resume_snapshot=snap)
        ff, fr = final_states(full), final_states(resumed)
        for cid in ff:
            np.testing.assert_array_equal(ff[cid], fr[cid])

    def test_pending_ladder_snapshot_after_dispatch_fault(
            self, rng, tmp_path):
        """A speculative successor dispatch RAISES (injected fault)
        while the pending update is in flight; the pending update then
        diverges and its ladder quarantines + snapshots. The snapshot's
        'about to run the successor' state must hold the successor's
        PRE-dispatch RNG counter — the failed dispatch's advance belongs
        to the seeded ladder that follows, not to the resume point."""
        from photon_ml_tpu.utils.checkpoint import CheckpointManager

        data = make_data(rng)
        base = build_coords(data)
        ds_cfg = dataclasses_replace_downsample(l2_config(), 0.7)
        coords = {
            "perUser": base["perUser"],
            "perItem": base["perItem"],  # ci=1: diverges at its fetch
            "fixed": FixedEffectCoordinate(  # ci=2: faults at dispatch
                dataset=build_fixed_effect_dataset(data, "global"),
                problem=GLMOptimizationProblem(config=ds_cfg, task=TASK)),
        }
        # chronic nan on perItem (quarantines after its retry), one
        # transient raise on fixed's dispatch (recovers); NO mid-sweep
        # cadence — per-update cadence would barrier the pipeline and
        # the in-flight scenario could never arise (quarantine saves
        # fire regardless of cadence)
        faults.arm("cd.update", "nan", times=100, tag="0.1")
        faults.arm("cd.update", "raise", times=1, tag="0.2")
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        res = run_coordinate_descent(
            coords, 1, TASK, jnp.asarray(data.responses),
            jnp.asarray(data.weights), jnp.asarray(data.offsets),
            recovery=RecoveryPolicy(max_retries=1, on_exhausted="abort",
                                    quarantine_after=1),
            checkpoint_manager=mgr)
        assert res.quarantined == ["perItem"]
        # the quarantine snapshot (step 2: about to run 'fixed') was
        # taken while fixed's failed speculative dispatch was
        # outstanding — it must record the pre-dispatch counter
        snap = mgr.restore(step=2)
        assert snap.get("update_counts", {}).get("fixed", 0) == 0, (
            "snapshot persisted the failed speculative dispatch's "
            "RNG advance")

    def test_block_dispatch_fault_restores_rng_positions(self, rng):
        """A fault raised MID-DISPATCH of a 2-wide block (at member 1,
        after member 0's down-sampling update already advanced its RNG
        counter) must restore every member's stream position before the
        sequential replay — otherwise the replayed member double-draws
        and its down-sampled batch diverges from the ladder's."""
        data = make_data(rng)
        ds_cfg = dataclasses_replace_downsample(l2_config(), 0.7)
        coords = build_coords(data)
        coords = {
            "fixed": FixedEffectCoordinate(
                dataset=build_fixed_effect_dataset(data, "global"),
                problem=GLMOptimizationProblem(config=ds_cfg, task=TASK)),
            "perUser": coords["perUser"],
            "perItem": coords["perItem"],
        }
        faults.arm("cd.update", "raise", times=1, tag="0.1")
        run_coordinate_descent(
            coords, 2, TASK, jnp.asarray(data.responses),
            jnp.asarray(data.weights), jnp.asarray(data.offsets),
            block_size=2,
            recovery=RecoveryPolicy(max_retries=2, on_exhausted="abort"))
        # 2 sweeps = 2 COMMITTED fixed-effect updates; the aborted block
        # dispatch must not leave a third advance behind
        assert coords["fixed"]._update_count == 2

    def test_block_replay_never_snapshots_mid_block(self, rng, tmp_path):
        """A transient fault inside a 2-wide block drops the block into
        the sequential member replay — whose snapshots must still land
        only at BLOCK boundaries (a mid-block snapshot would shift the
        sweep's block partition on resume)."""
        from photon_ml_tpu.utils.checkpoint import CheckpointManager

        data = make_data(rng)

        def run(**kw):
            return run_coordinate_descent(
                build_coords(data), 2, TASK,
                jnp.asarray(data.responses), jnp.asarray(data.weights),
                jnp.asarray(data.offsets), block_size=2,
                recovery=RecoveryPolicy(max_retries=2,
                                        on_exhausted="abort",
                                        damping=1.0), **kw)

        faults.arm("cd.update", "nan", times=1, tag="0.1")
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        full = run(checkpoint_manager=mgr, checkpoint_every_coordinates=1)
        faults.disarm_all()

        steps = mgr.all_steps()
        indices = {mgr.restore(step=s).get("coordinate_index")
                   for s in steps}
        # blocks over 3 coordinates at size 2 are [0,1] and [2]:
        # legal snapshot indices are 2 (after block 1) and 0 (sweep end)
        assert indices <= {0, 2}, (
            f"fault replay snapshotted mid-block: {sorted(indices)}")

        # and resuming from the post-replay block-boundary snapshot is
        # bit-exact vs the uninterrupted faulted run
        mid = [s for s in steps
               if mgr.restore(step=s).get("coordinate_index") == 2]
        assert mid
        resumed = run(resume_snapshot=mgr.restore(step=mid[0]))
        ff, fr = final_states(full), final_states(resumed)
        for cid in ff:
            np.testing.assert_array_equal(ff[cid], fr[cid])


def dataclasses_replace_downsample(cfg, rate):
    import dataclasses

    return dataclasses.replace(cfg, down_sampling_rate=rate)


class TestBlockCheckpointBoundaries:
    def test_blocked_resume_is_bitexact(self, rng, tmp_path):
        """Snapshots land only at block boundaries, and resuming a
        blocked run from an intermediate snapshot reproduces the
        uninterrupted blocked run bit for bit (the in-process half of
        the crash_resume_drill mid-block cell)."""
        from photon_ml_tpu.utils.checkpoint import CheckpointManager

        data = make_data(rng)
        ref = run_cd(data, iters=2, block_size=2)

        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        full = run_cd(data, iters=2, block_size=2,
                      checkpoint_manager=mgr,
                      checkpoint_every_coordinates=1)
        steps = mgr.all_steps()
        assert steps, "no snapshots written"
        # block boundaries only: with blocks (2, 1) over 3 coordinates,
        # mid-sweep snapshots land at coordinate_index 2 (after the
        # first block) — never at 1 (inside it)
        indices = {mgr.restore(step=s).get("coordinate_index")
                   for s in steps}
        assert 1 not in indices, (
            f"snapshot landed mid-block: coordinate indices {indices}")

        # resume from an intermediate (mid-sweep, block-boundary) step
        mid = [s for s in steps
               if mgr.restore(step=s).get("coordinate_index", 0) != 0]
        assert mid, f"no mid-sweep snapshot in {steps}"
        snap = mgr.restore(step=mid[0])
        resumed = run_cd(data, iters=2, block_size=2,
                         resume_snapshot=snap)
        ff, fr = final_states(full), final_states(resumed)
        for cid in ff:
            np.testing.assert_array_equal(ff[cid], fr[cid])
        # and the checkpointed run itself matches the clean reference
        fref = final_states(ref)
        for cid in fref:
            np.testing.assert_array_equal(fref[cid], ff[cid])


class TestLazyMultiInFlight:
    def test_deferred_results_force_out_of_order(self, rng):
        """Two run_lazy results stay independently device-resident; the
        later one forces first and both match their eager twins — the
        contract the pipelined sweep's multi-in-flight trackers rely
        on."""
        data = make_data(rng)
        ds = build_fixed_effect_dataset(data, "global")
        prob = GLMOptimizationProblem(config=l2_config(), task=TASK)
        b1 = ds.with_offsets(jnp.zeros(data.num_samples, jnp.float32))
        b2 = ds.with_offsets(
            jnp.full(data.num_samples, 0.25, jnp.float32))
        lazy1 = prob.run_lazy(b1)
        lazy2 = prob.run_lazy(b2)  # second in flight before first forces
        _, eager1 = prob.run(b1)
        _, eager2 = prob.run(b2)
        assert lazy2.value == pytest.approx(eager2.value)
        assert lazy1.value == pytest.approx(eager1.value)
        assert lazy1.iterations == eager1.iterations
        assert lazy2.iterations == eager2.iterations


class TestDriverFlags:
    BASE = ["--train-input-dirs", "x", "--output-dir", "y",
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map", "g:f",
            "--updating-sequence", "fixed"]

    def test_cd_flags_parse_with_defaults(self):
        from photon_ml_tpu.cli.game_training_driver import parse_args

        ns = parse_args(self.BASE)
        assert ns.cd_block_size == 1
        # argparse default None resolves to depth 1 (double-buffering
        # ON) single-process; None lets multi-host tell an explicit
        # request apart from the default
        assert ns.cd_pipeline_depth is None
        ns = parse_args(self.BASE + ["--cd-block-size", "4",
                                     "--cd-pipeline-depth", "0"])
        assert ns.cd_block_size == 4
        assert ns.cd_pipeline_depth == 0

    def test_multihost_rejects_cd_flags(self):
        from photon_ml_tpu.cli.game_training_driver import (
            _check_multihost_args,
            parse_args,
        )

        mh = ["--num-processes", "2", "--coordinator", "h:1",
              "--feature-name-and-term-set-path", "f"]
        for extra, needle in ((["--cd-block-size", "2"],
                               "cd-block-size"),
                              (["--cd-pipeline-depth", "0"],
                               "cd-pipeline-depth"),
                              (["--cd-pipeline-depth", "1"],
                               "cd-pipeline-depth")):
            ns = parse_args(self.BASE + mh + extra)
            with pytest.raises(ValueError, match=needle):
                _check_multihost_args(ns)
        # the defaults pass the multi-host check (the failure expected
        # here is the missing feature-set file, not the CD flags)
        ns = parse_args(self.BASE + mh)
        _check_multihost_args(ns)


class TestHbmSampling:
    def test_live_bytes_gauge_sampled_at_drain(self, rng):
        """Where an observed run asked for it (``obs/run.py`` switches
        ``devicemem.watch_sweeps``), never merely because spans are
        recorded (tests/test_obs_device.py holds that half)."""
        from photon_ml_tpu.obs import devicemem

        data = make_data(rng)
        tracer = trace.enable()
        devicemem.watch_sweeps(True)
        try:
            run_cd(data, iters=1)
        finally:
            devicemem.watch_sweeps(False)
            events = tracer.events()
            trace.disable()
        samples = [e for e in events if e["name"] == "cd.hbm_sample"]
        assert samples, "sweep drain did not sample live bytes"
        assert samples[0]["labels"]["live_bytes"] > 0
        assert REGISTRY.gauge("hbm_live_bytes").value(
            site="cd.sweep_drain") > 0
