"""The GLMix cell over two sweeps at a tiny size on the CPU: the second
sweep's warm-started solves and the user scores fed back to the fixed
effect, under the one-sweep cell's rows and budgets, come out ``correct``
against exact coordinate descent over as many sweeps; the control, every
fault the kind plants and a step that skips its second sweep do not."""

import time

import pytest

from benchmark import harness
from benchmark.kinds import cd_train
from tests.bench_harness import tiny
from tests.bench_harness.test_cells import _check_last_line

CELL = "glmix-ml10m.train-2sweeps"
ONE_SWEEP = "glmix-ml10m.train"

# at two sweeps a tiny copy's fixed effect and users come within 6e-4 and
# 2e-4 of exact coordinate descent (seeds 5 and 11), where one sweep leaves
# them 2e-2 and 6e-3 away (``tiny.TINY_LIMITS``); one sweep in a two-sweep
# step reads 2.5e-2 and 8.4e-3
TWO_SWEEP_LIMITS = {"fixed_coef_gap": 0.005, "user_coef_gap": 0.005,
                    "capped_coef_gap": 0.005}


def _spec() -> harness.Spec:
    """The one-sweep cell's tiny copy, with this cell's workload."""
    full = harness.load_spec(CELL)
    limits = dict(full.workload["limits"], **TWO_SWEEP_LIMITS)
    return full._replace(config=dict(full.config, **tiny.TINY[ONE_SWEEP]),
                         workload=dict(full.workload, limits=limits))


def test_the_two_sweep_cell_runs_and_is_correct_at_a_tiny_size():
    result = harness.run_cell(_spec(), 2**31 + 77, 0.3, False,
                              time.perf_counter(), tiny.DEVICE)
    names = _check_last_line(result, CELL, trace=False)
    assert set(result["metrics"]) == set(names) == {"sweep_s", "setup_s"}
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0


def test_a_step_makes_two_sweeps_of_both_coordinates():
    spec = _spec()
    assert spec.workload["units_per_step"] == spec.workload["step"][
        "sweeps"] == 2
    one = harness.load_spec(ONE_SWEEP).workload
    assert {k: v for k, v in spec.workload["step"].items() if k != "sweeps"
            } == {k: v for k, v in one["step"].items() if k != "sweeps"}
    state = cd_train.build(spec.config, spec.workload, 5, harness.Phases())
    record = cd_train.step(state)
    # fixed, per-user, fixed, per-user: four updates, two solves each
    assert len(record["objectives"]) == 4
    assert len(record["fixed_iterations"]) == len(
        record["user_iterations"]) == 2
    assert harness.judge(cd_train.verify(state, record,
                                         spec.workload["limits"]))


@pytest.fixture(scope="module")
def built():
    spec = _spec()
    return spec, cd_train.build(spec.config, spec.workload, 11,
                                harness.Phases())


def _skip_sweep(state):
    """A step that makes one sweep where the cell asks for two."""
    train = cd_train.train
    cd_train.train = lambda coords, sweeps, task, vectors: train(
        coords, 1, task, vectors)
    try:
        return cd_train.step(state)
    finally:
        cd_train.train = train


@pytest.mark.parametrize("planted", ["control", *cd_train.FAULTS,
                                     "skip_sweep"])
def test_the_control_and_every_fault_read_over_the_limits_at_two_sweeps(
        built, planted):
    spec, state = built
    limits = spec.workload["limits"]
    out = (cd_train.control(state) if planted == "control"
           else _skip_sweep(state) if planted == "skip_sweep"
           else cd_train.FAULTS[planted](state))
    checks = cd_train.verify(state, out, limits)
    assert not harness.judge(checks), (planted, checks)


def test_the_sound_step_reads_under_the_two_sweep_limits(built):
    spec, state = built
    checks = cd_train.verify(state, cd_train.step(state),
                             spec.workload["limits"])
    assert harness.judge(checks), checks
