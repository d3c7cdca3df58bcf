"""Each cell's kind at a tiny size on the CPU: data, build, steps, the
comparison with the plain reference, the last line; the control and every
fault the cell can have come out as not correct; ``run.py`` refuses without
a chip."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness, run
from benchmark.kinds import cd_train, glm_grid_fit
from tests.bench_harness import tiny

GLM = "glm-dense-2048.lbfgs-logistic"
GLMIX = "glmix-ml10m.train"
LAST_LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                  "checks"]


def _check_last_line(result, cell, trace):
    spec = harness.load_spec(cell)
    assert list(result)[:5] == LAST_LINE_KEYS[:5]
    assert list(result)[-1] == "checks"
    json.loads(json.dumps(result))  # plain numbers all through
    names = list(spec.layer_metrics) if trace else \
        harness.end_to_end_names(spec)
    units = harness.units_of(spec)
    for name, metric in result["metrics"].items():
        assert name in names and metric["unit"] == units[name]
        assert isinstance(metric["value"], float)
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert set(result["checks"]) == set(spec.workload["limits"])
    for pair in result["checks"].values():
        assert set(pair) == {"value", "limit"}
    return names


@pytest.mark.parametrize("cell", [GLM, GLMIX])
def test_a_cell_runs_and_is_correct_at_a_tiny_size(cell, capsys):
    result = tiny.run(cell)
    names = _check_last_line(result, cell, trace=False)
    assert set(result["metrics"]) == set(names)  # every end-to-end metric
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    harness.print_result(result)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == result
    tail = err.strip().splitlines()
    assert tail[-1] == "correct: True"
    assert [line.split(":")[0] for line in tail[-1 - len(result["checks"]):-1]
            ] == ["check " + name for name in result["checks"]]


@pytest.mark.parametrize("cell", [GLM, GLMIX])
def test_a_traced_run_reports_layer_metrics_only(cell, tmp_path):
    result = tiny.run(cell, trace=True, trace_dir=str(tmp_path / "trace"))
    names = _check_last_line(result, cell, trace=True)
    got = set(result["metrics"])
    # no device plane on the CPU: the trace's readers return nothing, and
    # the harness leaves those metrics out instead of writing 0
    assert got <= set(names) and not any(
        n.startswith(("device_idle", "hbm_roofline", "fe_solve_ms",
                      "re_solve_ms")) for n in got)
    assert {"compile_s"} <= got and any(n.startswith("step_mfu") for n in got)
    assert not os.path.exists(tmp_path / "trace")  # reduced, then removed


def _glm_state():
    spec = tiny.spec(GLM)
    state = glm_grid_fit.build(spec.config, spec.workload, 11,
                               harness.Phases())
    return spec, state


def test_glm_control_and_faults_read_over_the_limits():
    spec, state = _glm_state()
    limits = spec.workload["limits"]
    sound = glm_grid_fit.verify(state, glm_grid_fit.step(state), limits)
    assert harness.judge(sound), sound
    control = glm_grid_fit.verify(state, glm_grid_fit.control(state), limits)
    assert not harness.judge(control), control
    for name, fault in glm_grid_fit.FAULTS.items():
        planted = glm_grid_fit.verify(state, fault(state), limits)
        assert not harness.judge(planted), (name, planted)


def test_glmix_control_and_faults_read_over_the_limits():
    spec = tiny.spec(GLMIX)
    state = cd_train.build(spec.config, spec.workload, 11, harness.Phases())
    limits = spec.workload["limits"]
    sound = cd_train.verify(state, cd_train.step(state), limits)
    assert harness.judge(sound), sound
    control = cd_train.verify(state, cd_train.control(state), limits)
    assert not harness.judge(control), control
    for name, fault in cd_train.FAULTS.items():
        planted = cd_train.verify(state, fault(state), limits)
        assert not harness.judge(planted), (name, planted)


# --- a whole run with the timed path broken underneath ----------------------


def _glm_unchanged(train):
    def broken(batch, settings):
        return [dataclasses.replace(m, result=dataclasses.replace(
            m.result, coefficients=np.zeros_like(
                np.asarray(m.result.coefficients))))
            for m in train(batch, settings)]
    return broken


def _glm_half(train):
    def broken(batch, settings):
        import jax.numpy as jnp

        n = batch.labels.shape[0]
        return train(batch._replace(weights=jnp.where(
            jnp.arange(n) < n // 2, 2.0, 0.0).astype(jnp.float32)), settings)
    return broken


@pytest.mark.parametrize("breaker", [_glm_unchanged, _glm_half])
def test_a_glm_run_with_a_broken_fit_is_not_correct(breaker, monkeypatch):
    monkeypatch.setattr(glm_grid_fit, "train", breaker(glm_grid_fit.train))
    result = tiny.run(GLM)
    assert result["correct"] is False, result["checks"]
    assert result["metrics"]  # it ran; only the answer is wrong


def _break_unchanged(monkeypatch):
    """Every coordinate update returns the state it was given."""
    import jax.numpy as jnp

    from photon_ml_tpu.game import coordinate

    for cls in (coordinate.FixedEffectCoordinate,
                coordinate.RandomEffectCoordinate):
        update = cls.update

        def unchanged(self, coefs, extra_scores, _update=update):
            new, tracker = _update(self, coefs, extra_scores)
            return (jnp.zeros_like(new) if coefs is None else coefs), tracker

        monkeypatch.setattr(cls, "update", unchanged)


def _break_half(monkeypatch):
    """The second half of the rows weighs nothing, the first half twice."""
    from photon_ml_tpu.game import dataset

    init = dataset.GameDataset.__post_init__

    def halved(self):
        init(self)
        n = len(self.responses)
        self.weights = np.where(np.arange(n) < n // 2, 2.0, 0.0)

    monkeypatch.setattr(dataset.GameDataset, "__post_init__", halved)


def _break_exchange(monkeypatch):
    """Neither coordinate sees the other's scores."""
    from photon_ml_tpu.game import dataset

    with_offsets = dataset.FixedEffectDataset.with_offsets
    offsets_with = dataset.RandomEffectDataset.offsets_with
    monkeypatch.setattr(
        dataset.FixedEffectDataset, "with_offsets",
        lambda self, extra: with_offsets(self, extra * 0))
    monkeypatch.setattr(
        dataset.RandomEffectDataset, "offsets_with",
        lambda self, extra: offsets_with(self, extra * 0))


@pytest.mark.parametrize("breaker", [_break_unchanged, _break_half,
                                     _break_exchange])
def test_a_glmix_run_with_a_broken_training_is_not_correct(breaker,
                                                           monkeypatch):
    breaker(monkeypatch)
    result = tiny.run(GLMIX)
    assert result["correct"] is False, result["checks"]
    assert result["metrics"]


def test_a_step_that_raises_is_counted_as_failed(monkeypatch):
    calls = {"n": 0}
    step = glm_grid_fit.step

    def flaky(state):
        calls["n"] += 1
        if calls["n"] == 4:
            raise RuntimeError("planted")
        return step(state)

    monkeypatch.setattr(glm_grid_fit, "step", flaky)
    result = tiny.run(GLM, seconds=0.5)
    assert result["failed"] == 1 and result["correct"] is False
    assert result["attempted"] == calls["n"] - 2  # two warm-up steps


def test_a_window_closes_on_a_whole_cycle_of_steps():
    class Kind:
        @staticmethod
        def step(state):
            state["now"] += 1.0
            return {}

    for seconds, cycle, steps in ((4.5, 1, 5), (4.5, 4, 8), (8.0, 4, 8),
                                  (0.5, 3, 3)):
        state = {"now": 0.0}
        window = harness.run_window(Kind, state, seconds, None, 1,
                                    clock=lambda: state["now"],
                                    steps_per_cycle=cycle)
        assert len(window["records"]) == steps
        assert window["window_s"] == float(steps)


# --- no chip, no result -----------------------------------------------------


def test_main_refuses_on_the_cpu(capsys):
    with pytest.raises(SystemExit) as refused:
        run.main(["--workload", GLM, "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert refused.value.code not in (0, None)
    assert "no TPU" in str(refused.value.code)
    assert capsys.readouterr().out.strip() == ""


def test_the_command_fails_where_only_the_benchmark_is(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is no program to measure: non-zero, and no result."""
    import shutil

    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", GLM, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert done.returncode != 0
    assert done.stdout.strip() == ""
