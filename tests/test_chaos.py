"""Chaos/degraded-mode layer: retry combinator, probabilistic fault
modes, shard quarantine, torn-checkpoint hardening, stall postmortems,
and the lane-compaction auto-tuner.

The subprocess-level invariant matrix lives in tests/test_chaos_drill.py
(the bounded campaign smoke); these are the fast in-process contracts.
"""

from __future__ import annotations

import errno
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest
import scipy.sparse as sp

import jax

from photon_ml_tpu.data.ingest import (
    IngestPolicy,
    ShardLossExceededError,
)
from photon_ml_tpu.io import avro
from photon_ml_tpu.obs.metrics import REGISTRY
from photon_ml_tpu.utils import faults
from photon_ml_tpu.utils.checkpoint import (
    CheckpointManager,
    CheckpointWriteError,
)
from photon_ml_tpu.utils.retry import (
    DEFAULT_POLICY,
    RetryExhaustedError,
    RetryPolicy,
    backoff_delays,
    call_with_retry,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.disarm_all()
    yield
    faults.disarm_all()


# ---------------------------------------------------------------------------
# Retry combinator
# ---------------------------------------------------------------------------


class TestRetry:
    def test_deterministic_jitter_sequence(self):
        """Same (site, seed) → the identical delay schedule, replayable
        across calls and processes; a different site walks a different
        (but equally deterministic) schedule."""
        a = backoff_delays("io.avro_read", DEFAULT_POLICY)
        b = backoff_delays("io.avro_read", DEFAULT_POLICY)
        assert a == b
        assert len(a) == DEFAULT_POLICY.max_attempts - 1
        # exponential envelope with jitter in [0.5, 1.0)
        for n, d in enumerate(a):
            raw = min(DEFAULT_POLICY.base_delay_seconds * 2 ** n,
                      DEFAULT_POLICY.max_delay_seconds)
            assert 0.5 * raw <= d < raw
        assert backoff_delays("ckpt.write_bytes") != a

    def test_transient_failure_recovers_and_attributes_metrics(self):
        calls = {"n": 0}

        def flaky_twice():
            calls["n"] += 1
            if calls["n"] <= 2:
                raise OSError(errno.EIO, "transient")
            return "ok"

        before = REGISTRY.counter("retries").value(site="t.site")
        policy = RetryPolicy(max_attempts=4, base_delay_seconds=0.001)
        assert call_with_retry(flaky_twice, "t.site", policy) == "ok"
        assert calls["n"] == 3
        # per-site attribution: exactly the two retries, on THIS site
        assert REGISTRY.counter("retries").value(site="t.site") \
            == before + 2

    def test_exhaustion_wraps_last_error(self):
        def always():
            raise OSError(errno.EIO, "down")

        policy = RetryPolicy(max_attempts=3, base_delay_seconds=0.001)
        with pytest.raises(RetryExhaustedError) as ei:
            call_with_retry(always, "t.down", policy)
        assert ei.value.attempts == 3
        assert isinstance(ei.value.last, OSError)

    def test_permanent_error_skips_schedule(self):
        calls = {"n": 0}

        def missing():
            calls["n"] += 1
            raise FileNotFoundError("gone")

        with pytest.raises(FileNotFoundError):
            call_with_retry(missing, "t.missing")
        assert calls["n"] == 1  # no retries burned on a permanent error

    def test_nonretryable_error_propagates_immediately(self):
        def corrupt():
            raise ValueError("corrupt decode")

        with pytest.raises(ValueError):
            call_with_retry(corrupt, "t.corrupt")

    def test_deadline_enforced(self):
        """A deadline bounds total wall-clock INCLUDING pending sleeps:
        the combinator gives up early rather than sleeping past it."""
        def always():
            raise OSError(errno.EIO, "down")

        policy = RetryPolicy(max_attempts=50, base_delay_seconds=0.05,
                             max_delay_seconds=0.05,
                             deadline_seconds=0.12)
        t0 = time.monotonic()
        with pytest.raises(RetryExhaustedError) as ei:
            call_with_retry(always, "t.deadline", policy)
        assert ei.value.deadline_hit
        assert ei.value.attempts < 50
        assert time.monotonic() - t0 < 1.0


# ---------------------------------------------------------------------------
# New fault modes
# ---------------------------------------------------------------------------


class TestFaultModes:
    def test_io_error_and_enospc_raise_oserror(self):
        faults.arm("t.point", "io_error")
        with pytest.raises(OSError) as ei:
            faults.fault_point("t.point")
        assert ei.value.errno == errno.EIO
        faults.disarm_all()
        faults.arm("t.point", "enospc")
        with pytest.raises(OSError) as ei:
            faults.fault_point("t.point")
        assert ei.value.errno == errno.ENOSPC

    def test_partial_truncates_file(self, tmp_path):
        p = tmp_path / "f.bin"
        p.write_bytes(b"x" * 100)
        faults.arm("t.point", "partial")
        faults.fault_point("t.point", path=str(p))
        assert p.stat().st_size == 50

    def test_slow_default_is_small(self):
        spec = faults.arm("t.point", "slow")
        assert spec.delay_seconds == pytest.approx(0.05)

    def test_slow_explicit_one_second_is_kept(self):
        """An EXPLICIT 1.0s slow drill must stay 1.0s — the small
        default applies only when no arg was given (the default is a
        None sentinel, not the magic value 1.0)."""
        spec = faults.arm("t.point", "slow", delay_seconds=1.0)
        assert spec.delay_seconds == pytest.approx(1.0)
        (parsed,) = faults.parse_fault_specs("t.point=slow:1:1.0")
        assert parsed.delay_seconds == pytest.approx(1.0)

    def test_parse_new_modes(self):
        specs = faults.parse_fault_specs(
            "io.avro_read=flaky:9:0.25; ckpt.write_bytes=enospc:2;"
            "io.shard_open=slow:1:0.01; x=partial")
        by = {s.point: s for s in specs}
        assert by["io.avro_read"].mode == "flaky"
        assert by["io.avro_read"].probability == pytest.approx(0.25)
        assert by["io.avro_read"].times == 9
        assert by["ckpt.write_bytes"].mode == "enospc"
        assert by["io.shard_open"].delay_seconds == pytest.approx(0.01)
        assert by["x"].mode == "partial"

    def test_flaky_seeded_reproducibility(self, monkeypatch):
        """Same seed → the same firing pattern; a fresh registry (a new
        process incarnation) replays it identically."""
        monkeypatch.setenv(faults.ENV_SEED, "7")

        def pattern():
            faults.disarm_all()
            faults.arm("t.flaky", "flaky", times=1000, probability=0.5)
            out = []
            for _ in range(40):
                try:
                    faults.fault_point("t.flaky")
                    out.append(0)
                except OSError:
                    out.append(1)
            return out

        first, second = pattern(), pattern()
        assert first == second
        assert 0 < sum(first) < 40  # actually probabilistic at p=0.5
        monkeypatch.setenv(faults.ENV_SEED, "8")
        assert pattern() != first  # the seed IS the pattern

    def test_flaky_pattern_matches_across_processes(self, monkeypatch):
        """The replayability contract: another PROCESS with the same
        seed/point/visit sequence computes the identical pattern."""
        monkeypatch.setenv(faults.ENV_SEED, "1234")
        local = [faults.flaky_decision(1234, "io.shard_open", None, v, 0.5)
                 for v in range(32)]
        code = (
            "from photon_ml_tpu.utils.faults import flaky_decision\n"
            "print([flaky_decision(1234, 'io.shard_open', None, v, 0.5)"
            " for v in range(32)])\n")
        out = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                             capture_output=True, text=True, check=True)
        assert out.stdout.strip() == str(local)

    def test_flaky_p0_never_fires_p1_always(self):
        faults.arm("t.p0", "flaky", times=1000, probability=0.0)
        for _ in range(200):
            faults.fault_point("t.p0")  # must never raise
        faults.arm("t.p1", "flaky", times=1000, probability=1.0)
        with pytest.raises(OSError):
            faults.fault_point("t.p1")

    def test_fault_points_registry_matches_readme_table(self):
        """FAULT_POINTS (the campaign's sweep universe) and the README
        PHOTON_FAULTS table (the operator docs, reconciled against call
        sites by photonlint W401/W402) must list the same points."""
        from photon_ml_tpu.analysis.rules_faults import parse_fault_table

        with open(os.path.join(_REPO, "README.md")) as fh:
            table = parse_fault_table(fh.read().splitlines())
        assert set(table) == set(faults.FAULT_POINTS)


# ---------------------------------------------------------------------------
# Shard quarantine (degraded-mode ingest)
# ---------------------------------------------------------------------------


SCHEMA = {"name": "R", "type": "record",
          "fields": [{"name": "v", "type": "double"}]}


def _write_parts(d, n_parts=4, rows=10):
    os.makedirs(d, exist_ok=True)
    for i in range(n_parts):
        avro.write_container(
            os.path.join(d, f"part-{i:05d}.avro"), SCHEMA,
            [{"v": float(i * rows + j)} for j in range(rows)])


class TestShardQuarantine:
    def test_corrupt_part_quarantined_and_coverage_recorded(self, tmp_path):
        d = str(tmp_path / "data")
        _write_parts(d)
        faults.corrupt_path(os.path.join(d, "part-00001.avro"))
        policy = IngestPolicy(max_shard_loss_frac=0.5)
        _, records = avro.read_directory(d, policy=policy)
        assert len(records) == 30  # 3 surviving shards
        assert policy.shards_lost == 1
        assert policy.coverage_fraction == pytest.approx(0.75)
        assert policy.quarantined[0].stage == "decode"
        assert "part-00001" in policy.quarantined[0].path

    def test_truncated_part_quarantined(self, tmp_path):
        d = str(tmp_path / "data")
        _write_parts(d)
        faults.truncate_path(os.path.join(d, "part-00002.avro"))
        policy = IngestPolicy(max_shard_loss_frac=0.5)
        _, records = avro.read_directory(d, policy=policy)
        assert len(records) == 30
        assert policy.shards_lost == 1

    def test_strict_budget_aborts_cleanly(self, tmp_path):
        d = str(tmp_path / "data")
        _write_parts(d)
        faults.corrupt_path(os.path.join(d, "part-00001.avro"))
        with pytest.raises(ShardLossExceededError, match="quarantined"):
            avro.read_directory(d, policy=IngestPolicy(0.0))

    def test_no_policy_keeps_legacy_raise(self, tmp_path):
        d = str(tmp_path / "data")
        _write_parts(d)
        faults.corrupt_path(os.path.join(d, "part-00001.avro"))
        with pytest.raises(ValueError):
            avro.read_directory(d)

    def test_transient_injected_failure_recovers_without_loss(self, tmp_path):
        d = str(tmp_path / "data")
        _write_parts(d)
        faults.arm("io.shard_open", "io_error", times=1)
        policy = IngestPolicy(max_shard_loss_frac=0.0)
        _, records = avro.read_directory(d, policy=policy)
        assert len(records) == 40  # retried, nothing lost
        assert policy.shards_lost == 0
        assert faults.hits("io.shard_open") == 1

    def test_early_abort_with_expected_total(self):
        """With the shard universe announced, the budget math aborts as
        soon as coverage can no longer recover — not after a full scan."""
        policy = IngestPolicy(max_shard_loss_frac=0.25)
        policy.begin(4)
        policy.quarantine("a", "open", OSError("x"))  # 1/4 = budget edge
        with pytest.raises(ShardLossExceededError):
            policy.quarantine("b", "open", OSError("x"))

    def test_game_dataset_load_with_corrupt_shard(self, tmp_path, rng):
        """End-to-end through load_game_dataset_avro (native columnar
        path): one corrupt shard of four → dataset from the survivors,
        coverage recorded."""
        from photon_ml_tpu.io import schemas
        from photon_ml_tpu.io.data_format import load_game_dataset_avro
        from photon_ml_tpu.io.index_map import IndexMap

        game_schema = {
            "name": "G", "type": "record",
            "fields": [
                {"name": "response", "type": "double"},
                {"name": "f", "type": {"type": "array",
                                       "items": schemas.FEATURE}},
            ]}
        d = str(tmp_path / "game")
        os.makedirs(d)
        for i in range(4):
            avro.write_container(
                os.path.join(d, f"part-{i:05d}.avro"), game_schema,
                [{"response": 1.0,
                  "f": [{"name": "x", "term": "", "value": 2.0}]}
                 for _ in range(5)])
        faults.corrupt_path(os.path.join(d, "part-00003.avro"))
        imap = IndexMap({"x": 0})
        policy = IngestPolicy(max_shard_loss_frac=0.5)
        ds = load_game_dataset_avro(
            d, {"shard": ["f"]}, {"shard": imap}, policy=policy)
        assert ds.num_samples == 15
        assert policy.coverage_fraction == pytest.approx(0.75)

    def test_summary_shape(self):
        policy = IngestPolicy(max_shard_loss_frac=1.0)
        policy.record_ok("a")
        policy.quarantine("b", "decode", ValueError("bad"))
        s = policy.summary()
        assert s["data_coverage"] == pytest.approx(0.5)
        assert s["shards_ok"] == 1
        assert s["shards_quarantined"][0]["path"] == "b"
        json.dumps(s)  # metrics.json-able

    def test_rescan_does_not_double_announce(self):
        """A shard lost in the fast path and AGAIN in the interpreted
        fallback rescan (begin() resets the per-scan lists) is counted/
        warned/emitted once — the metrics must report real losses, not
        scan attempts."""
        warnings: list[str] = []
        start = REGISTRY.counter("quarantined_shards").total()
        policy = IngestPolicy(max_shard_loss_frac=1.0,
                              warn=warnings.append)
        policy.begin(2)
        policy.quarantine("p", "decode", ValueError("bad"))
        policy.begin(2)  # the fallback rescan
        policy.quarantine("p", "decode", ValueError("bad"))
        assert REGISTRY.counter("quarantined_shards").total() - start == 1
        assert len(warnings) == 1
        assert policy.shards_lost == 1  # per-scan list stays accurate


# ---------------------------------------------------------------------------
# Checkpoint hardening (stale tmp + torn writes)
# ---------------------------------------------------------------------------


class TestCheckpointHardening:
    def test_stale_tmp_cleaned_on_next_save(self, tmp_path):
        """Regression (satellite bugfix): a killed save's leftover
        ``step_*.tmp`` dir is removed by the next save()/restore()."""
        mgr = CheckpointManager(str(tmp_path))
        stale = tmp_path / "step_00000007.tmp"
        stale.mkdir()
        (stale / "arrays.npz").write_bytes(b"torn")
        mgr.save(1, {"x": np.arange(3)})
        assert not stale.exists()
        assert mgr.latest_valid_step() == 1

    def test_stale_tmp_cleaned_on_restore(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"x": np.arange(3)})
        stale = tmp_path / "step_00000009.tmp"
        stale.mkdir()
        mgr.restore()
        assert not stale.exists()

    def test_write_bytes_transient_enospc_recovers(self, tmp_path):
        faults.arm("ckpt.write_bytes", "enospc", times=1)
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"x": np.arange(4)})
        assert faults.hits("ckpt.write_bytes") == 1
        out = mgr.restore()
        np.testing.assert_array_equal(out["x"], np.arange(4))

    def test_write_bytes_persistent_failure_raises_clean(self, tmp_path):
        faults.arm("ckpt.write_bytes", "io_error", times=99)
        mgr = CheckpointManager(str(tmp_path))
        with pytest.raises(CheckpointWriteError):
            mgr.save(1, {"x": np.arange(4)})
        # no tmp litter, directory still usable
        assert not [n for n in os.listdir(tmp_path)
                    if n.endswith(".tmp")]
        faults.disarm_all()
        mgr.save(2, {"x": np.arange(5)})
        np.testing.assert_array_equal(mgr.restore()["x"], np.arange(5))

    def test_torn_write_that_checksums_falls_back(self, tmp_path):
        """The ckpt.write_bytes `partial` drill: the payload is torn
        BEFORE checksumming, so the published step VERIFIES but cannot
        be loaded — restore() must fall back to the older intact step
        instead of crashing."""
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"x": np.arange(6)})
        faults.arm("ckpt.write_bytes", "partial", times=1)
        mgr.save(2, {"x": np.arange(7)})
        assert mgr.verify_step(2)  # crc matches the torn bytes
        out = mgr.restore()
        np.testing.assert_array_equal(out["x"], np.arange(6))

    def test_all_torn_raises_documented_error(self, tmp_path):
        from photon_ml_tpu.utils.checkpoint import (
            CheckpointCorruptionError,
        )

        mgr = CheckpointManager(str(tmp_path))
        faults.arm("ckpt.write_bytes", "partial", times=1)
        mgr.save(1, {"x": np.arange(6)})
        with pytest.raises(CheckpointCorruptionError,
                           match="verifies and loads"):
            mgr.restore()

    def test_retention_never_prunes_last_loadable_past_torn_window(
            self, tmp_path):
        """Torn-but-checksummed steps filling the whole keep window must
        not let retention prune the only LOADABLE snapshot: 'verified'
        (crc matches — even torn bytes checksum) is weaker than
        'restorable' (the zip actually opens), and retention's safety
        net has to use the stronger test."""
        mgr = CheckpointManager(str(tmp_path), max_to_keep=2)
        mgr.save(1, {"x": np.arange(6)})
        faults.arm("ckpt.write_bytes", "partial", times=2)
        mgr.save(2, {"x": np.arange(7)})
        mgr.save(3, {"x": np.arange(8)})
        # both kept steps verify (crc over torn bytes) but cannot load;
        # step 1 must have survived retention as the fallback anchor
        assert os.path.isdir(tmp_path / "step_00000001")
        out = mgr.restore()
        np.testing.assert_array_equal(out["x"], np.arange(6))


# ---------------------------------------------------------------------------
# Heartbeat stall postmortem
# ---------------------------------------------------------------------------


class TestStallPostmortem:
    def test_stall_dumps_open_span_stack_with_ages(self):
        import threading

        from photon_ml_tpu.obs.heartbeat import Heartbeat
        from photon_ml_tpu.obs.trace import Tracer

        tracer = Tracer()
        release = threading.Event()
        entered = threading.Event()

        def hang():
            with tracer.span("cd.sweep", sweep=0):
                with tracer.span("cd.update", coordinate="perUser"):
                    entered.set()
                    release.wait(5.0)

        t = threading.Thread(target=hang, daemon=True)
        t.start()
        assert entered.wait(5.0)
        time.sleep(0.05)
        warns: list[str] = []
        hb = Heartbeat(tracer, interval_seconds=0,
                       stall_seconds=0.01, warn=warns.append)
        record = hb.check()
        release.set()
        t.join(5.0)
        assert record["stalled"]
        stall = [w for w in warns if "STALL" in w]
        assert stall, warns
        # the log line alone names the wedged spans AND their ages
        assert "cd.sweep" in stall[0] and "cd.update" in stall[0]
        assert "open" in stall[0] and "s)" in stall[0]


# ---------------------------------------------------------------------------
# Lane-compaction chunk auto-tuner
# ---------------------------------------------------------------------------


class TestChunkAutoTuner:
    def test_controller_probe_and_feedback(self):
        from photon_ml_tpu.game.random_effect import ChunkAutoTuner

        t = ChunkAutoTuner()
        assert t.chunk_for("lbfgs", 4) == 0  # too small to chunk
        c0 = t.chunk_for("lbfgs", 64)
        assert c0 == 16  # pow2 probe ~ max_iter/4
        t.update("lbfgs", 64, [100, 90])  # survival 0.9 → double
        assert t.chunk_for("lbfgs", 64) == 32
        t.update("lbfgs", 64, [100, 10])  # survival 0.1 → halve
        assert t.chunk_for("lbfgs", 64) == 16
        t.update("lbfgs", 64, [100, 50])  # in band → hold
        assert t.chunk_for("lbfgs", 64) == 16
        for _ in range(10):  # clamps at [4, pow2 < max_iter]
            t.update("lbfgs", 64, [100, 1])
        assert t.chunk_for("lbfgs", 64) == 4
        for _ in range(10):
            t.update("lbfgs", 64, [100, 100])
        assert t.chunk_for("lbfgs", 64) == 32  # pow2_at_most(63)
        # independent keys tune independently
        assert t.chunk_for("tron", 64) == 16

    def test_auto_matches_fixed_chunk_parity(self, rng):
        """`--re-lane-compaction-chunk auto` satellite: the auto-tuned
        solve lands on the same optimum as a fixed chunk and as the
        single dispatch (the existing compaction tolerance)."""
        from photon_ml_tpu.game.dataset import (
            GameDataset,
            RandomEffectDataConfiguration,
            build_random_effect_dataset,
        )
        from photon_ml_tpu.game.random_effect import (
            AUTO_COMPACTION_CHUNK,
            RandomEffectOptimizationProblem,
        )
        from photon_ml_tpu.optimize.config import (
            GLMOptimizationConfiguration,
            OptimizerType,
            RegularizationContext,
            RegularizationType,
            TaskType,
        )

        n, d, n_entities = 400, 4, 12
        Xe = rng.normal(size=(n, d))
        users = rng.integers(0, n_entities, size=n)
        W = rng.normal(size=(n_entities, d))
        margin = np.einsum("nd,nd->n", Xe, W[users])
        y = (rng.uniform(size=n)
             < 1.0 / (1.0 + np.exp(-margin))).astype(np.float64)
        data = GameDataset(responses=y,
                           feature_shards={"pu": sp.csr_matrix(Xe)})
        data.encode_ids("userId", users)
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("userId", "pu", 1))

        def cfg():
            return GLMOptimizationConfiguration(
                max_iterations=40, tolerance=1e-8,
                regularization_weight=0.5,
                optimizer_type=OptimizerType.LBFGS,
                regularization_context=RegularizationContext(
                    RegularizationType.L2))

        def solve(prob):
            c, *_ = prob.run(ds, ds.base_offsets)
            return np.asarray(c)

        def problem(chunk):
            return RandomEffectOptimizationProblem(
                config=cfg(), task=TaskType.LOGISTIC_REGRESSION,
                lane_compaction_chunk=chunk)

        plain = solve(problem(0))
        fixed = solve(problem(5))
        # ONE problem instance across both auto solves — the tuner is
        # per-coordinate state living on the problem, so the second
        # solve runs after a real feedback step
        auto_prob = problem(AUTO_COMPACTION_CHUNK)
        auto1 = solve(auto_prob)
        auto2 = solve(auto_prob)  # after one feedback step
        assert auto_prob.chunk_tuner._chunks  # feedback accumulated
        np.testing.assert_allclose(auto1, plain, rtol=1e-2, atol=1e-3)
        np.testing.assert_allclose(auto2, plain, rtol=1e-2, atol=1e-3)
        np.testing.assert_allclose(fixed, plain, rtol=1e-2, atol=1e-3)

    def test_driver_flag_parses_auto(self):
        from photon_ml_tpu.cli.game_training_driver import parse_args
        from photon_ml_tpu.game.random_effect import AUTO_COMPACTION_CHUNK

        base = ["--train-input-dirs", "x", "--output-dir", "y",
                "--task-type", "LOGISTIC_REGRESSION",
                "--feature-shard-id-to-feature-section-keys-map", "g:f",
                "--updating-sequence", "g"]
        ns = parse_args(base + ["--re-lane-compaction-chunk", "auto"])
        assert ns.re_lane_compaction_chunk == AUTO_COMPACTION_CHUNK
        ns = parse_args(base + ["--re-lane-compaction-chunk", "4"])
        assert ns.re_lane_compaction_chunk == 4


# ---------------------------------------------------------------------------
# Armed-but-silent faults: no fire, no sync, no recompile
# ---------------------------------------------------------------------------


class TestArmedSilentOverhead:
    def test_flaky_p0_is_cheap_and_silent(self):
        """Flaky p=0 on the hot-loop point is the chaos machinery's worst
        no-op case; here we pin its correctness (never fires) and a
        generous absolute per-visit cost bound."""
        faults.arm("cd.update", "flaky", times=10**9, probability=0.0)
        t0 = time.perf_counter()
        for _ in range(20_000):
            faults.fault_point("cd.update", tag="0.0")
        per_call = (time.perf_counter() - t0) / 20_000
        assert per_call < 50e-6  # generous: real cost is ~µs
        assert faults.hits("cd.update") == 0

    def test_armed_silent_faults_add_no_sync_and_no_recompile(self, rng):
        """A warm CD run with flaky p=0 armed on `cd.update` does the
        work of the unarmed run and nothing else that a chip would
        feel: it runs with implicit device→host transfers DISALLOWED,
        its explicit-fetch count equals the unarmed run's, no site
        compiles again, the fault never fires, and the objective is the
        unarmed run's bit for bit. What is left is one dict lookup and
        one RNG draw an update on the host (the sibling above bounds
        it)."""
        import test_obs

        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent,
        )
        from photon_ml_tpu.obs import compile as obs_compile
        from photon_ml_tpu.obs.metrics import MetricsRegistry
        from photon_ml_tpu.optimize.config import TaskType
        from photon_ml_tpu.utils import sync_telemetry

        coords, labels, weights, offsets = test_obs._cd_inputs(
            rng, n=240, n_entities=6)
        registry = MetricsRegistry()

        def one_run():
            sync_telemetry.reset_host_fetches()
            compiles = registry.counter("compiles").total()
            with jax.transfer_guard_device_to_host("disallow"):
                res = run_coordinate_descent(
                    coords, 2, TaskType.LOGISTIC_REGRESSION,
                    labels, weights, offsets)
            return (sync_telemetry.host_fetch_count(),
                    registry.counter("compiles").total() - compiles,
                    [st.objective for st in res.states])

        obs_compile.arm(registry=registry)
        try:
            # compile everything at these shapes OUTSIDE the guard
            run_coordinate_descent(coords, 2, TaskType.LOGISTIC_REGRESSION,
                                   labels, weights, offsets)
            assert registry.counter("compiles").total() > 0
            plain_fetches, plain_compiles, plain_objective = one_run()
            spec = faults.arm("cd.update", "flaky", times=10**9,
                              probability=0.0)
            armed_fetches, armed_compiles, armed_objective = one_run()
            fired = faults.hits("cd.update")
        finally:
            faults.disarm_all()
            obs_compile.disarm()
            obs_compile.reset()
        assert plain_fetches > 0
        assert armed_fetches == plain_fetches
        assert plain_compiles == 0 and armed_compiles == 0
        # the armed point was decided at every update, and never fired
        assert spec.visits == 2 * len(coords) and fired == 0
        assert armed_objective == plain_objective


class TestCleanAbortContract:
    def test_types_and_exit(self):
        from photon_ml_tpu.cli import (
            CLEAN_ABORT_EXIT,
            clean_abort,
            clean_abort_types,
        )
        from photon_ml_tpu.utils.checkpoint import (
            CheckpointCorruptionError,
        )

        kinds = clean_abort_types()
        assert ShardLossExceededError in kinds
        assert CheckpointCorruptionError in kinds
        assert RetryExhaustedError in kinds
        assert faults.InjectedFault in kinds
        exc = clean_abort(ShardLossExceededError("over budget"))
        assert isinstance(exc, SystemExit)
        assert exc.code == CLEAN_ABORT_EXIT
