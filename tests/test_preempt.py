"""Preemption-safe training: graceful-stop safe points + the supervisor.

Contracts under test:

- ``run_coordinate_descent`` polls its ``stop`` object ONLY at commit
  barriers (raw block boundaries): a stop requested mid-block is honored
  at the NEXT boundary, after resolving any in-flight pipelined handle,
  with a final snapshot written — and a resume from that snapshot is
  bit-exact vs the uninterrupted run (utils/preempt.py +
  game/coordinate_descent.py);
- :class:`StopController` latches the first reason from any source
  (signal / wall-clock deadline / stop file), throttles stop-file
  stats, and a SECOND delivery of the same signal restores the previous
  disposition (the operator's force escape hatch);
- the driver turns a preemption into the documented surface: exit 75,
  a ``PHOTON_PREEMPTED step=<sweep>.<coord>`` line, and a drained
  ``run_end {status: "preempted"}`` record (cli/game_training_driver);
- ``tools/photon_supervise.py`` carries a run to completion through
  preemptions + crashes (relaunch-with-resume, bit-identical result)
  and SIGTERM→SIGKILL-relaunches a wedged run flagged by the stall
  heartbeat (the self-healing half of the issue).
"""

from __future__ import annotations

import importlib.util
import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest
import scipy.sparse as sp

import jax.numpy as jnp

from photon_ml_tpu.game.coordinate import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
from photon_ml_tpu.game.dataset import (
    GameDataset,
    RandomEffectDataConfiguration,
    build_fixed_effect_dataset,
    build_random_effect_dataset,
)
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
from photon_ml_tpu.utils import faults
from photon_ml_tpu.utils.checkpoint import CheckpointManager
from photon_ml_tpu.utils.preempt import (
    PreemptionRequested,
    StopController,
)

TASK = TaskType.LOGISTIC_REGRESSION
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(filename: str, name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "tools", filename))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the chaos harness already owns the subprocess fixture + driver-args
# idiom; the preemption e2e drills the SAME tiny sharded workload
chaos = _load_tool("chaos_drill.py", "chaos_drill_for_preempt")

PREEMPTED_EXIT = 75


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.disarm_all()
    yield
    faults.disarm_all()


# ---------------------------------------------------------------------------
# In-process: barrier-only stop semantics on a 3-coordinate GAME problem
# ---------------------------------------------------------------------------


def make_data(rng, n=240, d_global=4, d_entity=2, n_users=8, n_items=5):
    """Fixed + per-user + per-item logistic data: three coordinates, so
    block size 2 yields uneven raw blocks [0,1] and [2] and the
    barrier-only contract has a mid-block position to get wrong."""
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


def l2_config(lam=0.5, max_iter=20):
    return GLMOptimizationConfiguration(
        max_iterations=max_iter, tolerance=1e-8,
        regularization_weight=lam,
        optimizer_type=OptimizerType.LBFGS,
        regularization_context=RegularizationContext(
            RegularizationType.L2))


def build_coords(data):
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
    out = {}
    for cid, m in result.model.models.items():
        coefs = getattr(getattr(m, "model", m), "coefficients", None)
        if coefs is not None:
            out[cid] = np.asarray(coefs.means)
        else:
            out[cid] = np.asarray(m.coefficients_projected)
    return out


class CountdownStop:
    """Deterministic stop source: healthy for N barrier polls, then a
    sticky stop — the test-grade stand-in the preempt module promises
    the CD loop accepts (any ``should_stop() -> str | None``)."""

    def __init__(self, healthy_polls: int, reason="test:countdown"):
        self.healthy_polls = healthy_polls
        self.reason = reason
        self.polls = 0

    def should_stop(self):
        self.polls += 1
        if self.polls > self.healthy_polls:
            return self.reason
        return None


class TestBarrierStop:
    def test_stop_snapshots_and_resumes_bitexact(self, rng, tmp_path):
        """Sequential sweep, stop latched before sweep 1: preemption
        names (1, 0) — the NEXT unit of work — a final snapshot exists
        at that step, and resuming from it lands float-for-float on the
        uninterrupted run."""
        data = make_data(rng)
        ref = run_cd(data, iters=2, pipeline_depth=0)

        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        stop = CountdownStop(healthy_polls=3)  # (0,0) (0,1) (0,2) ok
        with pytest.raises(PreemptionRequested) as ei:
            run_cd(data, iters=2, pipeline_depth=0,
                   checkpoint_manager=mgr, stop=stop)
        assert (ei.value.sweep, ei.value.coordinate_index) == (1, 0)
        assert ei.value.step == "1.0"
        assert ei.value.reason == "test:countdown"

        snap = mgr.restore()
        assert (snap["sweep"], snap["coordinate_index"]) == (1, 0)
        resumed = run_cd(data, iters=2, pipeline_depth=0,
                         resume_snapshot=snap)
        fr, ff = final_states(resumed), final_states(ref)
        assert sorted(fr) == sorted(ff)
        for cid in ff:
            np.testing.assert_array_equal(ff[cid], fr[cid])

    def test_no_stop_means_no_polls_needed(self, rng):
        """A healthy stop source never interrupts: the run completes and
        was polled once per raw block (3 blocks × 2 sweeps)."""
        data = make_data(rng)
        stop = CountdownStop(healthy_polls=10**9)
        res = run_cd(data, iters=2, pipeline_depth=0, stop=stop)
        assert len(res.states) > 0
        assert stop.polls == 6

    def test_mid_block_stop_waits_for_raw_boundary(self, rng, tmp_path):
        """Blocked sweep ([0,1] then [2]): a stop that fires at the
        second barrier lands AFTER the whole 2-wide block committed —
        coordinate_index 2, never 1 — and resume is bit-exact vs the
        uninterrupted blocked run."""
        data = make_data(rng)
        ref = run_cd(data, iters=2, block_size=2)

        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        stop = CountdownStop(healthy_polls=1)  # block [0,1] commits
        with pytest.raises(PreemptionRequested) as ei:
            run_cd(data, iters=2, block_size=2,
                   checkpoint_manager=mgr, stop=stop)
        assert (ei.value.sweep, ei.value.coordinate_index) == (0, 2)

        snap = mgr.restore()
        assert snap["coordinate_index"] == 2, (
            "preemption snapshot landed mid-block")
        resumed = run_cd(data, iters=2, block_size=2,
                         resume_snapshot=snap)
        fr, ff = final_states(resumed), final_states(ref)
        for cid in ff:
            np.testing.assert_array_equal(ff[cid], fr[cid])

    def test_pipelined_inflight_handle_resolved_before_stop(
            self, rng, tmp_path):
        """Double-buffered sweep: at the stop barrier the previous
        coordinate's speculative dispatch is still in flight — it must
        be resolved (committed) before the snapshot, or the resume would
        replay an update the interrupted run already took."""
        data = make_data(rng)
        ref = run_cd(data, iters=2, pipeline_depth=1)

        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        stop = CountdownStop(healthy_polls=2)
        with pytest.raises(PreemptionRequested) as ei:
            run_cd(data, iters=2, pipeline_depth=1,
                   checkpoint_manager=mgr, stop=stop)
        assert (ei.value.sweep, ei.value.coordinate_index) == (0, 2)

        resumed = run_cd(data, iters=2, pipeline_depth=1,
                         resume_snapshot=mgr.restore())
        fr, ff = final_states(resumed), final_states(ref)
        for cid in ff:
            np.testing.assert_array_equal(ff[cid], fr[cid])

    def test_stop_without_checkpointing_still_preempts(self, rng):
        data = make_data(rng)
        with pytest.raises(PreemptionRequested) as ei:
            run_cd(data, iters=2, pipeline_depth=0,
                   stop=CountdownStop(healthy_polls=0,
                                      reason="test:immediate"))
        assert ei.value.reason == "test:immediate"
        assert (ei.value.sweep, ei.value.coordinate_index) == (0, 0)


# ---------------------------------------------------------------------------
# StopController: sources, latching, throttling, the signal escape hatch
# ---------------------------------------------------------------------------


class TestStopController:
    def test_first_reason_wins_and_sticks(self):
        ctl = StopController()
        assert ctl.should_stop() is None
        ctl.request_stop("first")
        ctl.request_stop("second")
        assert ctl.should_stop() == "first"
        assert ctl.stop_requested

    def test_deadline_measured_from_construction(self):
        t = [100.0]
        ctl = StopController(max_train_seconds=5.0,
                             clock=lambda: t[0])
        assert ctl.should_stop() is None
        t[0] = 104.9
        assert ctl.should_stop() is None
        t[0] = 105.0
        assert ctl.should_stop() == "deadline:max_train_seconds"

    def test_zero_deadline_disables(self):
        t = [0.0]
        ctl = StopController(max_train_seconds=0.0, clock=lambda: t[0])
        t[0] = 1e9
        assert ctl.should_stop() is None

    def test_stop_file_polls_are_throttled(self, tmp_path):
        from photon_ml_tpu.utils.preempt import STOP_FILE_POLL_SECS

        path = tmp_path / "STOP"
        t = [100.0]
        ctl = StopController(stop_file=str(path), clock=lambda: t[0])
        assert ctl.should_stop() is None  # consumes the free poll
        path.write_text("")
        # the stat budget is spent: within the throttle window the flag
        # stays down no matter how many barriers arrive
        assert ctl.should_stop() is None
        t[0] += STOP_FILE_POLL_SECS + 0.01
        assert ctl.should_stop() == f"stop_file:{path}"

    def test_signal_latches_then_second_delivery_escapes(self):
        """First SIGTERM latches the flag; a second delivery restores
        the PREVIOUS disposition and re-raises, so a run stuck far from
        any barrier can still be forced down."""
        hits = []
        prev = signal.signal(signal.SIGTERM,
                             lambda s, f: hits.append(s))
        ctl = StopController()
        try:
            ctl.install_signal_handlers(signums=(signal.SIGTERM,))
            os.kill(os.getpid(), signal.SIGTERM)
            signal.getsignal(signal.SIGTERM)  # drain pending delivery
            assert ctl.should_stop() == "signal:SIGTERM"
            assert hits == []  # first delivery was absorbed by the latch
            os.kill(os.getpid(), signal.SIGTERM)
            signal.getsignal(signal.SIGTERM)
            assert hits == [signal.SIGTERM]  # escape hatch fired
        finally:
            ctl.uninstall_signal_handlers()
            signal.signal(signal.SIGTERM, prev)


# ---------------------------------------------------------------------------
# Subprocess: the driver's preemption surface + the run supervisor
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def driver_fixture(tmp_path_factory):
    root = tmp_path_factory.mktemp("preempt_fixture")
    return chaos.build_fixture(str(root))


def _run_end_statuses(trace_dir: str) -> list[str]:
    out = []
    path = os.path.join(trace_dir, "metrics.jsonl")
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if rec.get("kind") == "run_end":
                out.append(rec.get("status"))
    return out


def test_driver_stop_file_preempts_with_documented_surface(
        driver_fixture, tmp_path):
    """A pre-existing stop file preempts at the FIRST barrier: exit 75,
    a PHOTON_PREEMPTED line naming step 0.0, no stack trace, and the
    telemetry stream drained with run_end {status: preempted}."""
    stop_file = tmp_path / "STOP"
    stop_file.write_text("")
    out = str(tmp_path / "out")
    trace = str(tmp_path / "trace")
    args = chaos.driver_args(
        driver_fixture["data_dir"], driver_fixture["fs_dir"], out,
        str(tmp_path / "ckpt"), trace) + ["--stop-file", str(stop_file)]
    proc = chaos._run_driver(args)
    assert proc.returncode == PREEMPTED_EXIT, proc.stderr[-2000:]
    assert "PHOTON_PREEMPTED step=0.0" in proc.stderr
    assert f"reason=stop_file:{stop_file}" in proc.stderr
    assert "Traceback (most recent call last)" not in proc.stderr
    assert _run_end_statuses(trace) == ["preempted"]


def _supervise(driver_args, extra_env, sup_flags, timeout=420):
    env = dict(os.environ)
    env.pop("PHOTON_FAULTS", None)
    env.pop("PHOTON_FAULTS_STATE_DIR", None)
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools",
                                      "photon_supervise.py"),
         *sup_flags, "--", *driver_args],
        env=env, cwd=_REPO, text=True, capture_output=True,
        timeout=timeout)


def test_supervisor_heals_preemptions_and_crash(driver_fixture,
                                                tmp_path):
    """The issue's supervised-run scenario: two SIGTERM preemptions
    (honored gracefully, exit 75) plus one hard crash, all inside one
    supervised run — the supervisor relaunches through every one and
    the final model equals the never-interrupted run bit for bit."""
    ref_dir = tmp_path / "ref"
    ref = chaos._run_driver(chaos.driver_args(
        driver_fixture["data_dir"], driver_fixture["fs_dir"],
        str(ref_dir / "out"), str(ref_dir / "ckpt"),
        str(ref_dir / "trace")))
    assert ref.returncode == 0, ref.stderr[-2000:]
    _, ref_obj = chaos._final_objective(str(ref_dir / "out"))

    out = str(tmp_path / "out")
    trace = str(tmp_path / "trace")
    args = chaos.driver_args(
        driver_fixture["data_dir"], driver_fixture["fs_dir"], out,
        str(tmp_path / "ckpt"), trace)
    # shared fault-state dir: each spec fires ONCE across relaunches —
    # incarnation 1 preempts at 0.1, 2 preempts at 1.0, 3 dies hard at
    # 1.1, 4 runs fault-free to completion
    proc = _supervise(args, {
        "PHOTON_FAULTS": ("cd.update@0.1=signal:1;"
                          "cd.update@1.0=signal:1;"
                          f"cd.update@1.1=kill:1:{chaos.KILL_EXIT}"),
        "PHOTON_FAULTS_STATE_DIR": str(tmp_path / "fault_state"),
        "PHOTON_FAULTS_SEED": "42",
    }, ["--max-restarts", "5", "--backoff-base", "0.05",
        "--backoff-max", "0.2", "--poll-seconds", "0.3",
        "--startup-grace-seconds", "60"])
    assert proc.returncode == 0, \
        f"{proc.stdout}\n{proc.stderr[-3000:]}"
    assert "PHOTON_SUPERVISE_OK restarts=3" in proc.stdout

    _, obj = chaos._final_objective(out)
    assert obj == ref_obj, (
        f"supervised run NOT bit-identical: {obj!r} vs {ref_obj!r}")

    with open(os.path.join(trace, "supervisor.jsonl")) as fh:
        recs = [json.loads(line) for line in fh if line.strip()]
    exits = [r for r in recs if r["action"] == "exit"]
    assert [r["preempted"] for r in exits] == [True, True, False]
    assert recs[-1]["action"] == "done"


def test_supervisor_stall_kills_and_relaunches(driver_fixture,
                                               tmp_path):
    """A run wedged inside an update (scripted 300 s hang) never reaches
    a barrier: the stall heartbeat flags it, the supervisor SIGTERMs,
    escalates to SIGKILL when the graceful window lapses, and the
    relaunch (hang spec already consumed) completes the run.

    The stall window is 20 s against the 300 s hang: a healthy child
    holds one span open for 2.8 s on an idle host while its first solve
    compiles, and a 3 s window called that a stall on a host that six
    test workers share, relaunch after relaunch, until the restart
    budget was spent."""
    out = str(tmp_path / "out")
    args = chaos.driver_args(
        driver_fixture["data_dir"], driver_fixture["fs_dir"], out,
        str(tmp_path / "ckpt"), str(tmp_path / "trace"))
    args += ["--trace-stall-seconds", "20"]
    proc = _supervise(args, {
        "PHOTON_FAULTS": "cd.update@0.0=delay:1:300",
        "PHOTON_FAULTS_STATE_DIR": str(tmp_path / "fault_state"),
        "PHOTON_FAULTS_SEED": "42",
    }, ["--max-restarts", "4", "--backoff-base", "0.05",
        "--backoff-max", "0.2", "--poll-seconds", "0.3",
        "--grace-seconds", "2", "--startup-grace-seconds", "20"])
    assert proc.returncode == 0, \
        f"{proc.stdout}\n{proc.stderr[-3000:]}"
    assert "PHOTON_SUPERVISE stall_kill" in proc.stdout
    assert "PHOTON_SUPERVISE escalate_kill" in proc.stdout
    assert "PHOTON_SUPERVISE_OK" in proc.stdout
    assert os.path.exists(os.path.join(out, "metrics.json"))
