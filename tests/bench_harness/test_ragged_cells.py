"""PR 35's cell at a tiny size on the CPU (cut in rows and in columns, so
that the penalty still selects and a fit still takes its five iterations):
the run through ``csr_to_batch`` and ``train_glm_grid``, the last line, the
control and every planted fault out of their limits; the generator, the
reference with its textbook OWL-QN and the work function they stand on."""

import json
import time

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from benchmark import harness, work_ragged
from benchmark.generators import kddb_rows
from benchmark.kinds import glm_ragged_fit
from benchmark.reference import glm_ragged
from tests.bench_harness import tiny
from tests.bench_harness.test_cells import _check_last_line

CELL = "glm-ragged-kddb.owlqn-logistic"
TINY = {"rows": 32768, "rows_per_block": 4096, "features": 20000}
BIG_SEED = 2**31 + 12345


def _spec() -> harness.Spec:
    full = harness.load_spec(CELL)
    return full._replace(config=dict(full.config, **TINY))


def _run(trace: bool = False, trace_dir=None) -> dict:
    return harness.run_cell(_spec(), BIG_SEED, 0.3, trace,
                            time.perf_counter(), tiny.DEVICE,
                            trace_dir=trace_dir)


@pytest.fixture(scope="module")
def built():
    spec = _spec()
    state = glm_ragged_fit.build(spec.config, spec.workload, 11,
                                 harness.Phases())
    return spec, state


def test_the_cell_runs_and_is_correct_at_a_tiny_size(capsys):
    result = _run()
    names = _check_last_line(result, CELL, trace=False)
    assert set(result["metrics"]) == set(names) == {"fit_s", "setup_s"}
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 2 and result["failed"] == 0
    workload = harness.load_spec(CELL).workload
    assert workload["steps_per_cycle"] == 2  # a traced run's second step
    assert result["attempted"] % 2 == 0
    harness.print_result(result)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert err.strip().splitlines()[-1] == "correct: True"


def test_a_traced_run_reports_the_layouts_fill_and_the_solvers_counts(
        tmp_path):
    result = _run(trace=True, trace_dir=str(tmp_path / "trace"))
    names = _check_last_line(result, CELL, trace=True)
    got = set(result["metrics"])
    # no device plane on the CPU: the trace's readers return nothing
    assert got <= set(names) and not got & {"device_idle.fit",
                                            "hbm_roofline.fit"}
    assert {"compile_s", "lower_s", "block_build_s", "step_mfu.fit",
            "solver_iters.fit", "ell_fill.fit",
            "owlqn_evals_per_iter.fit"} <= got
    value = {n: m["value"] for n, m in result["metrics"].items()}
    assert 83.0 <= value["ell_fill.fit"] <= 100.0  # walked <= 1.2x stored
    assert value["solver_iters.fit"] == 5.0
    assert 1.0 <= value["owlqn_evals_per_iter.fit"] <= 3.0
    assert 0 < value["step_mfu.fit"] < 100


def test_the_cell_enters_through_the_programs_own_builder(built):
    """No plane is built by the benchmark: the batch is what
    ``csr_to_batch`` gives the generator's CSR matrix, several blocks of
    slots with the rows in the caller's order at the surface."""
    from photon_ml_tpu.data.batch import EllBatch

    spec, state = built
    batch = state.batch
    assert type(batch) is EllBatch and len(batch.blocks) >= 4
    assert batch.order is not None
    assert sp.issparse(state.mat) and state.mat.shape == (32768, 20000)
    np.testing.assert_array_equal(np.asarray(batch.labels), state.y)
    assert state.nonzeros == state.mat.nnz
    assert state.nonzeros <= batch.walked_slots <= 1.2 * state.nonzeros
    assert batch.walked_slots < 0.5 * state.rows * 128
    line, = glm_ragged_fit.describe(state)
    assert "blocks of slots ending at" in line and "32768 rows" in line


def test_the_step_record_and_the_work_it_is_credited(built):
    spec, state = built
    record = glm_ragged_fit.step(state)
    assert record["iterations"] == [5] and record["lambdas"] == [1.0]
    assert record["evaluations"][0] >= 6  # the start and a trial or more
    w = record["coefficients"][0]
    assert w.shape == (20000,) and record["nonzeros"] == [
        int(np.count_nonzero(w))]
    assert 0.01 * w.size < record["nonzeros"][0] < 0.6 * w.size
    assert np.all(np.diff(record["histories"][0]) <= 0)
    # non-zeros, never slots: padding is not credited
    assert glm_ragged_fit.work(state, record) == work_ragged.ragged_work(
        state.nonzeros, 32768, 20000, record["evaluations"][0])
    assert harness.judge(glm_ragged_fit.verify(
        state, record, spec.workload["limits"]))


def test_the_control_and_every_fault_read_over_a_limit(built):
    spec, state = built
    limits = spec.workload["limits"]
    control = glm_ragged_fit.verify(state, glm_ragged_fit.control(state),
                                    limits)
    assert not harness.judge(control), control
    assert set(glm_ragged_fit.FAULTS) == {
        "state_unchanged", "half_batch", "scatter_drops_a_block",
        "l1_ignored"}
    for name, fault in glm_ragged_fit.FAULTS.items():
        planted = glm_ragged_fit.verify(state, fault(state), limits)
        assert not harness.judge(planted), (name, planted)
    # the faults left the program's batch as it was
    assert type(state.batch).__name__ == "EllBatch"
    assert harness.judge(glm_ragged_fit.verify(
        state, glm_ragged_fit.step(state), limits))


def test_a_fit_that_ignores_the_penalty_leaves_no_coefficient_at_zero(built):
    spec, state = built
    checks = {n: (v, lim) for n, v, lim in glm_ragged_fit.verify(
        state, glm_ragged_fit.fault_l1_ignored(state),
        spec.workload["limits"])}
    value, limit = checks["zero_share_gap"]
    assert value > 10 * limit


def test_a_reported_value_that_rises_is_over_the_trajectory_limit(built):
    spec, state = built
    record = glm_ragged_fit.step(state)
    history = record["histories"][0]
    history[1], history[2] = history[2], history[1]
    checks = {n: (v, lim) for n, v, lim in glm_ragged_fit.verify(
        state, record, spec.workload["limits"])}
    value, limit = checks.pop("trajectory")
    assert value > limit
    assert all(v <= lim for v, lim in checks.values())


def test_a_run_with_half_the_batch_is_not_correct(monkeypatch):
    train = glm_ragged_fit.train

    def broken(batch, settings):
        n = batch.labels.shape[0]
        return train(batch._replace(weights=jnp.where(
            jnp.arange(n) < n // 2, 2.0, 0.0).astype(jnp.float32)), settings)

    monkeypatch.setattr(glm_ragged_fit, "train", broken)
    result = _run()
    assert result["correct"] is False, result["checks"]
    assert result["metrics"]  # it ran; only the answer is wrong


# --- the generator ----------------------------------------------------------


def test_kddb_rows_repeat_for_a_seed_and_move_as_blocks_for_another():
    config = _spec().config
    block = config["rows_per_block"]
    a, ya = kddb_rows.make_rows(config, BIG_SEED)
    b, yb = kddb_rows.make_rows(config, BIG_SEED)
    assert (a != b).nnz == 0 and np.array_equal(ya, yb)
    c, yc = kddb_rows.make_rows(config, 3)
    order_a = kddb_rows.block_order(config, BIG_SEED)
    order_c = kddb_rows.block_order(config, 3)
    assert not np.array_equal(order_a, order_c)
    assert sorted(order_a) == sorted(order_c) == list(range(8))
    for j, block_id in enumerate(order_a):  # the same rows, elsewhere
        i = list(order_c).index(block_id)
        rows_a = slice(j * block, (j + 1) * block)
        rows_c = slice(i * block, (i + 1) * block)
        assert (a[rows_a] != c[rows_c]).nnz == 0
        assert np.array_equal(ya[rows_a], yc[rows_c])
    other, _ = kddb_rows.make_rows(dict(config, data_seed=6), BIG_SEED)
    assert (a != other).nnz > 0
    with pytest.raises(ValueError, match="multiple"):
        kddb_rows.block_order(dict(config, rows=4097), 1)


def test_kddb_rows_have_the_published_shape():
    config = _spec().config
    mat, y = kddb_rows.make_rows(config, 1)
    assert mat.shape == (32768, 20000) and mat.indices.dtype == np.int32
    assert mat.data.dtype == np.float32 and y.dtype == np.float32
    lens = np.diff(mat.indptr)
    # the published mean, the floor and the cut
    assert lens.mean() == pytest.approx(566_345_888 / 19_264_097, rel=0.02)
    assert lens.min() >= 13 and lens.max() == 128
    assert 0.0002 < np.mean(lens == 128) < 0.004
    assert np.median(lens) == pytest.approx(26, abs=1)
    # rows of unit length, columns ascending and none twice
    np.testing.assert_allclose(
        np.asarray(mat.multiply(mat).sum(axis=1)).ravel(), 1.0, rtol=1e-5)
    assert mat.has_canonical_format
    assert mat.indices.min() >= 0 and mat.indices.max() < 20000
    assert set(np.unique(y)) == {0.0, 1.0} and 0.75 < y.mean() < 0.95
    report = kddb_rows.describe_rows(mat, y)
    assert report["cells_out_of_order_or_twice_in_a_row"] == 0
    assert report["nonzeros"] == mat.nnz
    assert report["mean_row_length"] == pytest.approx(lens.mean())
    counts = np.bincount(mat.indices, minlength=20000)
    assert report["heaviest_column_share_of_nonzeros"] == pytest.approx(
        counts.max() / mat.nnz)
    assert report["positive_rate"] == pytest.approx(y.mean())


def test_the_configuration_as_published():
    config = harness.load_spec(CELL).config
    published = config["published"]
    assert published["training_rows"] == 19_264_097
    assert config["features"] == 29_890_095 == published["features"]
    assert published["nonzeros"] == 566_345_888
    assert config["rows"] == 74 * 65536 >= -(-19_264_097 // 4)
    assert config["rows"] - 19_264_097 / 4 < config["rows_per_block"]
    assert (config["length_floor"], config["length_sigma"],
            config["length_cap"]) == (12, 0.7, 128)
    # the stated mu is the one that gives the published mean
    assert kddb_rows.solve_length_mu(config, 566_345_888 / 19_264_097,
                                     draws=400_000) == pytest.approx(
        config["length_mu"], abs=0.005)
    step = harness.load_spec(CELL).workload["step"]
    assert (step["regularization"], step["alpha"], step["lambdas"],
            step["max_iterations"]) == ("ELASTIC_NET", 0.5, [1.0], 5)
    assert (config["lambda"], config["alpha"]) == (1.0, 0.5)


@pytest.mark.parametrize("exponent,shift", [(1.0, 10.0), (0.5, 10.0),
                                            (1.0, 1000.0)])
def test_the_popularity_law_is_the_stated_density(exponent, shift):
    """The rank's density is (rank + shift) ** -exponent over [0,
    features): the share of the draws on the first column is the law's."""
    config = {"features": 20000, "popularity_shift": shift,
              "popularity_exponent": exponent}
    u = (np.arange(2_000_000) + 0.5) / 2_000_000
    rank = kddb_rows._ranks(u, config)
    assert rank.min() == 0 and rank.max() == 19999

    def mass(lo, hi):  # the integral of x ** -exponent
        if exponent == 1.0:
            return np.log(hi / lo)
        return (hi ** (1 - exponent) - lo ** (1 - exponent)) / (1 - exponent)

    assert np.mean(rank == 0) == pytest.approx(
        mass(shift, shift + 1) / mass(shift, shift + 20000), rel=2e-3)


def test_a_columns_number_says_nothing_of_how_often_it_occurs():
    """Rank -> column is a bijection that scatters: the heaviest ranks are
    not the lowest columns (a row's ascending order would else put its
    rarest columns in its last slots)."""
    for features in (20000, 29_890_095):
        ranks = np.arange(min(features, 200_000), dtype=np.int64)
        cols = kddb_rows.columns_of(ranks, features)
        assert len(np.unique(cols)) == len(ranks)
        assert cols.min() >= 0 and cols.max() < features
        assert abs(np.corrcoef(ranks[:2000], cols[:2000])[0, 1]) < 0.1
    with pytest.raises(ValueError, match="share a factor"):
        kddb_rows.columns_of(np.arange(3), kddb_rows.COLUMN_STRIDE * 2)


# --- the reference ----------------------------------------------------------


def _small_ragged(rng, n=300, d=40):
    lens = rng.integers(0, 9, size=n)
    cols = np.concatenate([np.sort(rng.choice(d, size=l, replace=False))
                           for l in lens]).astype(np.int32)
    indptr = np.concatenate([[0], np.cumsum(lens)])
    vals = rng.normal(size=indptr[-1]).astype(np.float32)
    X = sp.csr_matrix((vals, cols, indptr), shape=(n, d)).toarray()
    y = (rng.random(n) > 0.5).astype(np.float32)
    offsets = (rng.normal(size=n) * 0.1).astype(np.float32)
    weights = (rng.random(n) + 0.5).astype(np.float32)
    return indptr, cols, vals, X.astype(np.float64), y, offsets, weights


@pytest.mark.parametrize("block", [50, 100, 300])
def test_the_ragged_reference_against_numpy_in_float64(block):
    rng = np.random.default_rng(5)
    indptr, cols, vals, X, y, offsets, weights = _small_ragged(rng)
    flat = glm_ragged.flat_blocks(indptr, cols, vals, block)
    assert flat[0].shape == flat[1].shape == flat[2].shape
    assert flat[0].shape[0] == 300 // block
    assert flat[0].max() < block and np.count_nonzero(flat[2]) <= len(vals)
    data = tuple(jnp.asarray(a) for a in (*flat, y, offsets, weights))
    w = rng.normal(size=40) * 0.3
    z = X @ w + offsets
    loss = np.maximum(z, 0) + np.log1p(np.exp(-np.abs(z))) - y * z
    value = float(np.sum(weights * loss) + 0.5 * 0.7 * w @ w)
    grad = X.T @ (weights * (1 / (1 + np.exp(-z)) - y)) + 0.7 * w
    got_value, got_grad = glm_ragged.smooth(*data, w, 0.7)
    assert got_value == pytest.approx(value, rel=1e-5)
    np.testing.assert_allclose(got_grad, grad, rtol=1e-4, atol=1e-4)
    F, g, pg = glm_ragged.penalised(
        lambda w: glm_ragged.smooth(*data, w, 0.7), w, 0.3)
    assert F == pytest.approx(value + 0.3 * np.abs(w).sum(), rel=1e-5)
    np.testing.assert_allclose(pg, got_grad + 0.3 * np.sign(w), rtol=1e-6)
    low = glm_ragged.smooth(*data, w, 0.7, low_precision=True)
    assert 1e-5 < abs(low[0] - value) / value < 1e-2  # bf16 shows, mildly
    with pytest.raises(ValueError, match="multiple"):
        glm_ragged.flat_blocks(indptr, cols, vals, 7)


def test_the_pseudo_gradient_is_the_one_sided_derivative():
    x = np.array([1.0, -1.0, 0.0, 0.0, 0.0])
    g = np.array([0.2, 0.2, -0.9, 0.9, 0.3])
    np.testing.assert_allclose(
        glm_ragged.pseudo_gradient(x, g, 0.5), [0.7, -0.3, -0.4, 0.4, 0.0])


def test_the_textbook_owlqn_minimises_selects_and_never_rises():
    """On a lasso problem small enough to solve by coordinate descent."""
    rng = np.random.default_rng(9)
    A = rng.normal(size=(60, 12))
    truth = np.where(np.arange(12) < 4, rng.normal(size=12) * 2, 0.0)
    b = A @ truth + 0.01 * rng.normal(size=60)
    H, c = A.T @ A, A.T @ b

    def quadratic(w):
        return 0.5 * w @ H @ w - c @ w, H @ w - c

    l1 = 5.0
    w, values, gnorm = glm_ragged.owlqn(quadratic, l1, np.zeros(12), 80)
    assert np.all(np.diff(values) <= 0) and len(values) <= 81
    exact = np.zeros(12)
    for _ in range(2000):  # coordinate descent: soft thresholds
        for j in range(12):
            r = c[j] - H[j] @ exact + H[j, j] * exact[j]
            exact[j] = np.sign(r) * max(abs(r) - l1, 0.0) / H[j, j]
    np.testing.assert_allclose(w, exact, atol=1e-5)
    assert np.array_equal(w == 0.0, exact == 0.0) and np.sum(w == 0.0) >= 6
    assert gnorm < 1e-4
    _, two, _ = glm_ragged.owlqn(quadratic, l1, np.zeros(12), 2)
    assert two == values[:3]  # a budget cuts the same path short
    smooth, _, _ = glm_ragged.owlqn(quadratic, 0.0, np.zeros(12), 80)
    np.testing.assert_allclose(smooth, np.linalg.solve(H, c), atol=1e-5)


# --- the work ---------------------------------------------------------------


def test_the_work_of_a_ragged_pass_credits_non_zeros_not_slots():
    assert work_ragged.ragged_pass_flops(142_600_000) == 4 * 142_600_000
    assert work_ragged.ragged_pass_bytes(142_600_000, 4_849_664,
                                         29_890_095) == (
        8 * 142_600_000 + 12 * 4_849_664 + 8 * 29_890_095)
    one = work_ragged.ragged_work(100, 10, 7, 4)
    assert one == {"flops": 4 * 400, "bytes": 4 * (800 + 120 + 56)}
    assert work_ragged.ragged_work(100, 10, 7, [1, 3]) == one
