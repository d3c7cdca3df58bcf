"""The long-row SVM cell at a tiny size on the CPU (cut in rows, columns and
row length, so that its layout still has deep blocks over few rows): the run
through ``csr_to_batch`` and ``train_glm_grid``, the last line, the control
and every planted fault out of their limits; the generator and the
reference they stand on."""

import json
import time

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from benchmark import harness, work_ragged
from benchmark.generators import webspam_rows
from benchmark.kinds import glm_longrow_fit
from benchmark.reference import glm_ragged, glm_svm
from tests.bench_harness.test_cells import _check_last_line

CELL = "glm-longrow-webspam.lbfgs-svm"
# rows about 180 non-zeros long, cut at 2,048: the layout's deep blocks hold
# a few dozen rows, so every block but the first walks tiles of slots
TINY = {"rows": 2000, "rows_per_block": 500, "features": 20000,
        "length_mu": 4.96, "length_cap": 2048}
BIG_SEED = 2**31 + 4242


def _spec() -> harness.Spec:
    full = harness.load_spec(CELL)
    return full._replace(config=dict(full.config, **TINY))


def _run(trace: bool = False, trace_dir=None) -> dict:
    from tests.bench_harness import tiny

    return harness.run_cell(_spec(), BIG_SEED, 0.3, trace,
                            time.perf_counter(), tiny.DEVICE,
                            trace_dir=trace_dir)


@pytest.fixture(scope="module")
def built():
    spec = _spec()
    state = glm_longrow_fit.build(spec.config, spec.workload, 11,
                                  harness.Phases())
    return spec, state


def test_the_cell_runs_and_is_correct_at_a_tiny_size(capsys):
    result = _run()
    names = _check_last_line(result, CELL, trace=False)
    assert set(result["metrics"]) == set(names) == {"fit_s", "setup_s"}
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert result["attempted"] % 2 == 0  # whole cycles of two offsets
    harness.print_result(result)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert err.strip().splitlines()[-1] == "correct: True"


def test_a_traced_run_reports_the_walks_step_and_the_solvers_counts(
        tmp_path):
    result = _run(trace=True, trace_dir=str(tmp_path / "trace"))
    names = _check_last_line(result, CELL, trace=True)
    got = set(result["metrics"])
    # no device plane on the CPU: the trace's readers return nothing
    assert got <= set(names) and not got & {"device_idle.fit",
                                            "hbm_roofline.fit"}
    assert {"compile_s", "lower_s", "block_build_s", "step_mfu.fit",
            "solver_iters.fit", "evals_per_iter.fit", "ell_fill.fit",
            "ell_step_elems.fit"} <= got
    value = {n: m["value"] for n, m in result["metrics"].items()}
    assert value["solver_iters.fit"] == 3.0
    assert 70.0 <= value["ell_fill.fit"] <= 100.0
    # a tile of slots a step: more elements a step than the first block's
    # 2,000 rows give one slot at a time
    assert value["ell_step_elems.fit"] > 2000


def test_the_cell_enters_through_the_programs_own_builder(built):
    from photon_ml_tpu.data.batch import EllBatch, ell_walk_steps

    spec, state = built
    batch = state.batch
    assert type(batch) is EllBatch and len(batch.blocks) >= 4
    assert batch.order is not None
    assert sp.issparse(state.mat) and state.mat.shape == (2000, 20000)
    np.testing.assert_array_equal(np.asarray(batch.labels), state.y)
    assert state.nonzeros == state.mat.nnz
    assert state.nonzeros <= batch.walked_slots <= 1.4 * state.nonzeros
    shapes = [ix.shape[-2:] for ix, _ in batch.blocks]
    assert all(ell_walk_steps(k, n) < k for k, n in shapes)  # every block
    line, = glm_longrow_fit.describe(state)
    assert "loop steps a walk" in line and "2000 rows" in line


def test_the_step_record_and_the_work_it_is_credited(built):
    spec, state = built
    record = glm_longrow_fit.step(state)
    assert record["iterations"] == [3] and record["lambdas"] == [1.0]
    assert record["evaluations"][0] >= 4  # the start and a trial or more
    assert record["coefficients"][0].shape == (20000,)
    assert np.all(np.diff(record["histories"][0]) <= 0)
    # non-zeros, never slots: padding is not credited
    assert glm_longrow_fit.work(state, record) == work_ragged.ragged_work(
        state.nonzeros, 2000, 20000, record["evaluations"][0])
    assert harness.judge(glm_longrow_fit.verify(
        state, record, spec.workload["limits"]))


def test_the_control_and_every_fault_read_over_a_limit(built):
    spec, state = built
    limits = spec.workload["limits"]
    control = glm_longrow_fit.verify(state, glm_longrow_fit.control(state),
                                     limits)
    assert not harness.judge(control), control
    assert set(glm_longrow_fit.FAULTS) == {
        "state_unchanged", "half_batch", "scatter_drops_a_block",
        "logistic_loss"}
    for name, fault in glm_longrow_fit.FAULTS.items():
        planted = glm_longrow_fit.verify(state, fault(state), limits)
        assert not harness.judge(planted), (name, planted)
    assert type(state.batch).__name__ == "EllBatch"  # left as it was


def test_a_run_with_half_the_batch_is_not_correct(monkeypatch):
    train = glm_longrow_fit.train

    def broken(batch, settings):
        n = batch.labels.shape[0]
        return train(batch._replace(weights=jnp.where(
            jnp.arange(n) < n // 2, 2.0, 0.0).astype(jnp.float32)), settings)

    monkeypatch.setattr(glm_longrow_fit, "train", broken)
    result = _run()
    assert result["correct"] is False, result["checks"]
    assert result["metrics"]  # it ran; only the answer is wrong


# --- the generator ----------------------------------------------------------


def test_webspam_rows_repeat_for_a_seed_and_move_as_blocks_for_another():
    config = _spec().config
    block = config["rows_per_block"]
    a, ya = webspam_rows.make_rows(config, BIG_SEED)
    b, yb = webspam_rows.make_rows(config, BIG_SEED)
    assert (a != b).nnz == 0 and np.array_equal(ya, yb)
    c, yc = webspam_rows.make_rows(config, 3)
    order_a = webspam_rows.block_order(config, BIG_SEED)
    order_c = webspam_rows.block_order(config, 3)
    assert sorted(order_a) == sorted(order_c) == list(range(4))
    for j, block_id in enumerate(order_a):  # the same rows, elsewhere
        i = list(order_c).index(block_id)
        rows_a = slice(j * block, (j + 1) * block)
        rows_c = slice(i * block, (i + 1) * block)
        assert (a[rows_a] != c[rows_c]).nnz == 0
        assert np.array_equal(ya[rows_a], yc[rows_c])


def test_webspam_rows_have_the_stated_shape():
    config = _spec().config
    mat, y = webspam_rows.make_rows(config, 1)
    assert mat.shape == (2000, 20000) and mat.indices.dtype == np.int32
    assert mat.data.dtype == np.float32 and y.dtype == np.float32
    lens = np.diff(mat.indptr)
    assert lens.min() >= 1 and lens.max() <= 2048
    assert np.median(lens) == pytest.approx(np.exp(4.96), rel=0.1)
    # rows of unit length, positive counts, columns ascending, none twice
    np.testing.assert_allclose(
        np.asarray(mat.multiply(mat).sum(axis=1)).ravel(), 1.0, rtol=1e-5)
    assert mat.data.min() > 0 and mat.has_canonical_format
    assert mat.indices.min() >= 0 and mat.indices.max() < 20000
    assert set(np.unique(y)) == {0.0, 1.0}


def test_the_configuration_as_published():
    config = harness.load_spec(CELL).config
    published = config["published"]
    assert published["training_rows"] == 350_000
    assert config["features"] == 16_609_143 == published["features"]
    assert published["nonzeros"] == 1_304_697_446
    assert config["rows"] == 350_000 // 4
    assert config["rows"] % config["rows_per_block"] == 0
    assert (config["length_sigma"], config["length_cap"]) == (0.8, 32768)
    # the stated mu gives the published mean
    assert webspam_rows.solve_length_mu(config, 1_304_697_446 / 350_000,
                                        draws=400_000) == pytest.approx(
        config["length_mu"], abs=0.005)
    step = harness.load_spec(CELL).workload["step"]
    assert (step["task"], step["optimizer"], step["regularization"],
            step["lambdas"], step["max_iterations"]) == (
        "SMOOTHED_HINGE_LOSS_LINEAR_SVM", "LBFGS", "L2", [1.0], 3)


# --- the reference ----------------------------------------------------------


def test_the_svm_reference_against_numpy_in_float64():
    rng = np.random.default_rng(5)
    n, d = 300, 40
    lens = rng.integers(0, 9, size=n)
    cols = np.concatenate([np.sort(rng.choice(d, size=l, replace=False))
                           for l in lens]).astype(np.int32)
    indptr = np.concatenate([[0], np.cumsum(lens)])
    vals = rng.normal(size=indptr[-1]).astype(np.float32)
    X = sp.csr_matrix((vals, cols, indptr), shape=(n, d)).toarray()
    y = (rng.random(n) > 0.5).astype(np.float32)
    offsets = (rng.normal(size=n) * 0.1).astype(np.float32)
    weights = (rng.random(n) + 0.5).astype(np.float32)
    data = tuple(jnp.asarray(a) for a in (
        *glm_ragged.flat_blocks(indptr, cols, vals, 100), y, offsets,
        weights))
    w = rng.normal(size=d) * 0.3
    t = (2 * y - 1) * (X.astype(np.float64) @ w + offsets)
    assert (t <= 0).any() and ((t > 0) & (t < 1)).any() and (t >= 1).any()
    loss = np.where(t >= 1, 0.0, np.where(t <= 0, 0.5 - t,
                                          0.5 * (1 - t) ** 2))
    slope = np.where(t >= 1, 0.0, np.where(t <= 0, -1.0, t - 1))
    value = float(np.sum(weights * loss) + 0.5 * 0.7 * w @ w)
    grad = X.T @ (weights * (2 * y - 1) * slope) + 0.7 * w
    got_value, got_grad = glm_svm.objective(*data, w, 0.7)
    assert got_value == pytest.approx(value, rel=1e-5)
    np.testing.assert_allclose(got_grad, grad, rtol=1e-4, atol=1e-4)
    low = glm_svm.objective(*data, w, 0.7, low_precision=True)
    assert 1e-5 < abs(low[0] - value) / value < 1e-2  # bf16 shows, mildly
