"""The ``program_counter`` reader: a ratio of the program's always-on
counters, and the two cells' new metrics in a tiny traced rehearsal (the
counters live in the process, so they read on the CPU too)."""

import pytest

from benchmark.readers import program_counter
from photon_ml_tpu.obs.metrics import REGISTRY
from tests.bench_harness import tiny

GLM = "glm-dense-2048.lbfgs-logistic"
GLMIX = "glmix-ml10m.train"


@pytest.fixture()
def counters():
    """Two made-up counters under labels no program site uses."""
    REGISTRY.counter("t_pc_done").inc(30, site="t.a")
    REGISTRY.counter("t_pc_done").inc(12, site="t.b", kind="x")
    REGISTRY.counter("t_pc_tried").inc(40, site="t.a")
    REGISTRY.counter("t_pc_tried").inc(0, site="t.b", kind="x")
    yield
    REGISTRY.counter("t_pc_done").reset()
    REGISTRY.counter("t_pc_tried").reset()


def read(**entry):
    return program_counter.read(entry, {})


def test_total_ratio_scale_and_label_filter(counters):
    assert read(counter="t_pc_done") == 42.0
    assert read(counter="t_pc_done", labels={"site": "t.a"}) == 30.0
    assert read(counter="t_pc_done", labels={"site": "t.b", "kind": "x"}) \
        == 12.0
    assert read(counter="t_pc_done", per="t_pc_tried") == 42.0 / 40.0
    assert read(counter="t_pc_done", per="t_pc_tried",
                labels={"site": "t.a"}, scale=100) == 75.0
    assert isinstance(read(counter="t_pc_done"), float)


def test_nothing_read_is_none_never_zero(counters):
    # a counter never written, or never under those labels
    assert read(counter="t_pc_never") is None
    assert read(counter="t_pc_done", labels={"site": "t.c"}) is None
    assert read(counter="t_pc_done", labels={"kind": "y"}) is None
    # a denominator of 0, or one that was never written
    assert read(counter="t_pc_done", per="t_pc_tried",
                labels={"site": "t.b"}) is None
    assert read(counter="t_pc_done", per="t_pc_never") is None


@pytest.mark.parametrize("cell,new", [
    (GLM, ("evals_per_iter.fit", "lower_s")),
    (GLMIX, ("evals_per_iter.sweep", "lane_fill.sweep", "lower_s"))])
def test_a_traced_rehearsal_reports_the_counted_metrics(cell, new,
                                                        tmp_path):
    result = tiny.run(cell, trace=True, trace_dir=str(tmp_path / "trace"))
    assert result["correct"] is True
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    for name in new:
        assert isinstance(metrics[name], float), name
    # every iteration evaluates at least once, beside the start
    assert metrics[new[0]] > 1.0
    assert 0.0 < metrics["lower_s"] <= metrics["compile_s"]
    if cell == GLMIX:
        assert 0.0 < metrics["lane_fill.sweep"] <= 100.0
