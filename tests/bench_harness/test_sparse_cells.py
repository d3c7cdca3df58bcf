"""PR 29's two cells at tiny sizes on the CPU (cut in rows, and the sparse
one in columns so that a fit converges in its budget): the run, the last
line, the control and every planted fault out of their limits; the
generator, the references and the work functions they stand on."""

import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, work, work_sparse
from benchmark.generators import criteo_rows
from benchmark.kinds import glm_linear_fit, glm_sparse_fit
from benchmark.readers import module_time
from benchmark.reference import glm_linear, glm_sparse
from tests.bench_harness import tiny
from tests.bench_harness.test_cells import _check_last_line

SPARSE = "glm-sparse-criteo.lbfgs-logistic"
TRON = "glm-dense-2048.tron-linear"
TINY = {
    SPARSE: {"rows": 16384, "rows_per_block": 2048, "features": 512,
             "categorical_cardinalities": [
                 int(c) for c in np.round(np.geomspace(3, 2000, 26))]},
    TRON: {"rows": 8192, "features": 64, "rows_per_block": 1024},
}
KINDS = {SPARSE: glm_sparse_fit, TRON: glm_linear_fit}
BIG_SEED = 2**31 + 12345


def _spec(cell: str) -> harness.Spec:
    full = harness.load_spec(cell)
    return full._replace(config=dict(full.config, **TINY[cell]))


def _run(cell: str, trace: bool = False, trace_dir=None) -> dict:
    return harness.run_cell(_spec(cell), BIG_SEED, 0.3, trace,
                            time.perf_counter(), tiny.DEVICE,
                            trace_dir=trace_dir)


@pytest.mark.parametrize("cell", [SPARSE, TRON])
def test_a_new_cell_runs_and_is_correct_at_a_tiny_size(cell, capsys):
    result = _run(cell)
    names = _check_last_line(result, cell, trace=False)
    assert set(result["metrics"]) == set(names) == {"fit_s", "setup_s"}
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    # whole cycles: two fits in the sparse cell, whose one fit outlasts
    # --seconds on the chip and would leave the profiler no second step
    cycle = harness.load_spec(cell).workload["steps_per_cycle"]
    assert cycle == (2 if cell == SPARSE else 8)
    assert result["attempted"] % cycle == 0
    harness.print_result(result)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert err.strip().splitlines()[-1] == "correct: True"


@pytest.mark.parametrize("cell,counted", [
    (SPARSE, {"evals_per_iter.fit"}), (TRON, {"hvps_per_iter.fit"})])
def test_a_traced_run_of_a_new_cell_reports_its_layer_metrics(
        cell, counted, tmp_path):
    result = _run(cell, trace=True, trace_dir=str(tmp_path / "trace"))
    names = _check_last_line(result, cell, trace=True)
    got = set(result["metrics"])
    # no device plane on the CPU: the trace's readers return nothing
    assert got <= set(names) and not got & {
        "device_idle.fit", "hbm_roofline.fit", "solve_ms.fit"}
    assert {"compile_s", "lower_s", "step_mfu.fit",
            "solver_iters.fit"} | counted <= got
    assert 0 < result["metrics"]["step_mfu.fit"]["value"] < 100
    assert result["metrics"][counted.pop()]["value"] >= 1.0


def test_the_solvers_module_pattern_takes_both_solvers_and_no_other():
    # the TRON cell's alone: in the sparse cell it read the whole fit (all of
    # a fit is one execution of the solver's module) and told nothing
    assert "solve_ms.fit" not in harness.load_spec(SPARSE).layer_metrics
    entry = harness.load_spec(TRON).layer_metrics["solve_ms.fit"]
    modules = [(0, 3_000_000, "jit__minimize_lbfgs_impl(11)"),
               (0, 5_000_000, "jit__minimize_tron_impl(12)"),
               (0, 7_000_000, "jit__fit_blocks_impl(13)"),
               (0, 9_000_000, "jit_make(14)")]
    context = {"trace": {"steps": 2, "chips": 1, "modules": modules},
               "units_per_step": 1.0}
    assert module_time.read(entry, context) == pytest.approx(4.0)


@pytest.mark.parametrize("cell", [SPARSE, TRON])
def test_the_control_and_every_fault_read_over_a_limit(cell):
    spec, kind = _spec(cell), KINDS[cell]
    state = kind.build(spec.config, spec.workload, 11, harness.Phases())
    limits = spec.workload["limits"]
    sound = kind.verify(state, kind.step(state), limits)
    assert harness.judge(sound), sound
    control = kind.verify(state, kind.control(state), limits)
    assert not harness.judge(control), control
    assert len(kind.FAULTS) == (3 if cell == SPARSE else 2)
    for name, fault in kind.FAULTS.items():
        planted = kind.verify(state, fault(state), limits)
        assert not harness.judge(planted), (name, planted)
    if cell == SPARSE:  # the faults left the program's batch as it was
        assert type(state.batch).__name__ == "EllBatch"
        assert harness.judge(kind.verify(state, kind.step(state), limits))


def test_a_reported_value_that_rises_is_over_the_trajectory_limit():
    spec = _spec(SPARSE)
    state = glm_sparse_fit.build(spec.config, spec.workload, 3,
                                 harness.Phases())
    record = glm_sparse_fit.step(state)
    history = record["histories"][0]
    assert np.all(np.diff(history) <= 0) and len(history) >= 3
    history[1], history[2] = history[2], history[1]
    checks = dict((n, (v, lim)) for n, v, lim in glm_sparse_fit.verify(
        state, record, spec.workload["limits"]))
    value, limit = checks.pop("trajectory")
    assert value > limit
    assert all(v <= lim for v, lim in checks.values())


# --- a whole run with the program broken underneath -------------------------


@pytest.fixture
def fresh_traces():
    """A method of the program patched underneath a jitted solve is seen
    only by a fresh trace, and its trace must not outlive the patch: drop
    jit's traces and the executables ``obs/compile.py`` keeps per site."""
    import jax

    from photon_ml_tpu.obs import compile as obs_compile

    def drop():
        jax.clear_caches()
        obs_compile.reset()

    drop()
    yield
    drop()


def _scatter_drops_the_last_slot(monkeypatch):
    from photon_ml_tpu.data.batch import EllBatch

    whole = EllBatch.weighted_feature_sum

    def broken(self, row_scalars):
        return whole(self._replace(
            values=self.values.at[-1].set(0.0)), row_scalars)

    monkeypatch.setattr(EllBatch, "weighted_feature_sum", broken)


def _half_batch(monkeypatch):
    train = glm_sparse_fit.train

    def broken(batch, settings):
        n = batch.labels.shape[0]
        return train(batch._replace(weights=jnp.where(
            jnp.arange(n) < n // 2, 2.0, 0.0).astype(jnp.float32)), settings)

    monkeypatch.setattr(glm_sparse_fit, "train", broken)


def _tron_unchanged(monkeypatch):
    import dataclasses

    train = glm_linear_fit.train

    def broken(batch, settings):
        return [dataclasses.replace(m, result=dataclasses.replace(
            m.result, coefficients=np.zeros_like(
                np.asarray(m.result.coefficients))))
            for m in train(batch, settings)]

    monkeypatch.setattr(glm_linear_fit, "train", broken)


@pytest.mark.parametrize("cell,breaker", [
    (SPARSE, _scatter_drops_the_last_slot), (SPARSE, _half_batch),
    (TRON, _tron_unchanged)])
def test_a_run_with_the_program_broken_is_not_correct(cell, breaker,
                                                      monkeypatch,
                                                      fresh_traces):
    breaker(monkeypatch)
    result = _run(cell)
    assert result["correct"] is False, result["checks"]
    assert result["metrics"]  # it ran; only the answer is wrong


# --- the generator ----------------------------------------------------------


def test_criteo_rows_repeat_for_a_seed_and_move_as_blocks_for_another():
    config = _spec(SPARSE).config
    block = config["rows_per_block"]
    a = criteo_rows.make_rows(config, BIG_SEED)
    b = criteo_rows.make_rows(config, BIG_SEED)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    c = criteo_rows.make_rows(config, 3)
    order_a = criteo_rows.block_order(config, BIG_SEED)
    order_c = criteo_rows.block_order(config, 3)
    assert not np.array_equal(order_a, order_c)
    assert sorted(order_a) == sorted(order_c) == list(range(8))
    for j, block_id in enumerate(order_a):  # the same rows, elsewhere
        i = list(order_c).index(block_id)
        for x, y in ((a[0], c[0]), (a[2][None], c[2][None])):
            assert np.array_equal(
                np.asarray(x)[:, j * block:(j + 1) * block],
                np.asarray(y)[:, i * block:(i + 1) * block])
    other = criteo_rows.make_rows(dict(config, data_seed=6), BIG_SEED)
    assert not np.array_equal(np.asarray(a[0]), np.asarray(other[0]))


def test_criteo_rows_have_the_published_shape():
    config = _spec(SPARSE).config
    cols, vals, y = (np.asarray(x) for x in criteo_rows.make_rows(config, 1))
    assert cols.shape == vals.shape == (39, 16384) and cols.dtype == np.int32
    assert vals.dtype == np.float32 and np.all(vals == np.float32(39 ** -0.5))
    np.testing.assert_allclose((vals.astype(np.float64) ** 2).sum(0), 1.0,
                               rtol=1e-6)  # rows of unit length
    assert cols.min() >= 0 and cols.max() < config["features"]
    assert np.all(np.diff(cols, axis=0) > 0)  # ascending, none twice
    assert set(np.unique(y)) == {0.0, 1.0} and 0.1 < y.mean() < 0.45
    report = criteo_rows.describe_rows(jnp.asarray(cols), jnp.asarray(y),
                                       config["features"])
    assert report["rows_with_a_column_twice"] == 0
    counts = np.bincount(cols.ravel(), minlength=config["features"])
    assert report["columns_hit_share"] == pytest.approx(np.mean(counts > 0))
    assert report["heaviest_column_share_of_nonzeros"] == pytest.approx(
        counts.max() / cols.size)
    assert report["positive_rate"] == pytest.approx(y.mean())


@pytest.mark.parametrize("exponent", [0.0, 0.5, 1.0, 1.5])
def test_the_popularity_exponent_is_the_zipf_laws(exponent):
    """The rank's density is rank ** -exponent over [1, c + 1): the share of
    the draws that land on the first value is the law's own."""
    c = 1000
    u = jnp.arange(400_000, dtype=jnp.float32) / 400_000
    rank = np.floor(np.asarray(criteo_rows._rank(
        u, jnp.float32(np.log(c + 1.0)), exponent)))
    assert rank.min() == 1 and rank.max() == c

    def mass(lo, hi):  # the integral of x ** -exponent
        if exponent == 1.0:
            return np.log(hi / lo)
        return (hi ** (1 - exponent) - lo ** (1 - exponent)) / (1 - exponent)

    assert np.mean(rank == 1) == pytest.approx(
        mass(1, 2) / mass(1, c + 1), rel=2e-3)


def test_the_fields_of_the_configuration_as_published():
    config = harness.load_spec(SPARSE).config
    card = criteo_rows.cardinalities(config)
    assert len(card) == 39 == config["nonzeros_per_row"]
    assert card[:13].min() == 10 and card[:13].max() == 100
    assert card[13:].min() == 3 and card[13:].max() == 10_131_227
    assert len(card[13:]) == config["categorical_fields"] == 26
    assert card[13:].sum() == 33_762_577  # the challenge's distinct values
    assert config["popularity_exponent"] == 1.0
    assert config["features"] == 1_000_000 == config["published"]["features"]
    assert config["value"] == pytest.approx(39 ** -0.5, rel=1e-12)
    assert config["rows"] == 175 * 65536 >= -(-45_840_617 // 4)
    assert config["rows"] - 45_840_617 / 4 < config["rows_per_block"]
    with pytest.raises(ValueError, match="nonzeros_per_row"):
        criteo_rows.cardinalities(dict(config, numeric_fields=12))
    with pytest.raises(ValueError, match="multiple"):
        criteo_rows.block_order(dict(config, rows=65537), 1)


# --- the references ---------------------------------------------------------


def _small_sparse(rng, n=300, d=40, k=5):
    cols = np.stack([rng.choice(d, size=k, replace=False)
                     for _ in range(n)]).astype(np.int32)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    X = np.zeros((n, d))
    X[np.arange(n)[:, None], cols] = vals
    y = (rng.random(n) > 0.5).astype(np.float32)
    offsets = (rng.normal(size=n) * 0.1).astype(np.float32)
    weights = (rng.random(n) + 0.5).astype(np.float32)
    return cols.T, vals.T, X, y, offsets, weights


@pytest.mark.parametrize("block", [64, 300, 1000])
def test_the_sparse_reference_against_numpy_in_float64(block):
    rng = np.random.default_rng(5)
    ids, vals, X, y, offsets, weights = _small_sparse(rng)
    w = rng.normal(size=40) * 0.3
    z = X @ w + offsets
    loss = np.maximum(z, 0) + np.log1p(np.exp(-np.abs(z))) - y * z
    value = float(np.sum(weights * loss) + 0.5 * 0.7 * w @ w)
    grad = X.T @ (weights * (1 / (1 + np.exp(-z)) - y)) + 0.7 * w
    got_value, got_grad = glm_sparse.objective(
        jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(y),
        jnp.asarray(offsets), jnp.asarray(weights), w, 0.7, block=block)
    assert got_value == pytest.approx(value, rel=1e-5)
    np.testing.assert_allclose(got_grad, grad, rtol=1e-4, atol=1e-4)
    low = glm_sparse.objective(
        jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(y),
        jnp.asarray(offsets), jnp.asarray(weights), w, 0.7, block=block,
        low_precision=True)
    assert 1e-5 < abs(low[0] - value) / value < 1e-2  # bf16 shows, mildly


def test_the_textbook_lbfgs_minimises_and_never_rises():
    rng = np.random.default_rng(9)
    A = rng.normal(size=(30, 12))
    H, b = A.T @ A + np.eye(12), rng.normal(size=12)

    def quadratic(w):
        return 0.5 * w @ H @ w - b @ w, H @ w - b

    w, values, gnorm = glm_sparse.lbfgs(quadratic, np.zeros(12), 40)
    assert np.all(np.diff(values) <= 0) and len(values) <= 41
    np.testing.assert_allclose(w, np.linalg.solve(H, b), atol=1e-6)
    assert gnorm < 1e-5
    _, two, _ = glm_sparse.lbfgs(quadratic, np.zeros(12), 2)
    assert two == values[:3]  # a budget cuts the same path short


def test_the_linear_references_closed_form_is_the_minimiser():
    rng = np.random.default_rng(2)
    X = rng.normal(size=(500, 9)).astype(np.float32)
    y = (rng.random(500) > 0.5).astype(np.float32)
    offsets = (rng.normal(size=500) * 0.1).astype(np.float32)
    weights = (rng.random(500) + 0.5).astype(np.float32)
    data = tuple(jnp.asarray(a) for a in (X, y, offsets, weights))
    w = glm_linear.minimiser(*data, 3.0, block=128)
    X64 = X.astype(np.float64)
    expect = np.linalg.solve(
        X64.T @ (X64 * weights[:, None]) + 3.0 * np.eye(9),
        X64.T @ (weights * (y - offsets)))
    np.testing.assert_allclose(w, expect, rtol=1e-5, atol=1e-6)
    value, grad = glm_linear.objective(*data, w, 3.0, block=128)
    assert np.linalg.norm(grad) < 1e-3
    e = X64 @ w + offsets - y
    assert value == pytest.approx(
        0.5 * np.sum(weights * e * e) + 1.5 * w @ w, rel=1e-5)
    low = glm_linear.minimiser(*data, 3.0, block=128, low_precision=True)
    assert 1e-4 < np.linalg.norm(low - w) / np.linalg.norm(w) < 1e-1


# --- the work ---------------------------------------------------------------


def test_the_work_of_a_sparse_pass_and_of_a_tron_solve():
    assert work_sparse.sparse_pass_flops(11468800, 39) == 4 * 447283200
    assert work_sparse.sparse_pass_bytes(11468800, 39, 1000000) == (
        8 * 447283200 + 12 * 11468800 + 8000000)
    one = work_sparse.sparse_work(10, 3, 7, 4)
    assert one == {"flops": 4 * 120, "bytes": 4 * (240 + 120 + 56)}
    assert work_sparse.sparse_work(10, 3, 7, [1, 3]) == one
    dense = work_sparse.dense_work(10, 3, 4, [2, 1], [5])
    assert dense == {"flops": 8 * work.pass_flops(10, 3),
                     "bytes": 8 * work.pass_bytes(10, 3, 4)}
    assert work_sparse.dense_work(10, 3, 4, 3, 5) == dense


def test_the_new_kinds_credit_the_solvers_own_counts():
    spec = _spec(TRON)
    state = glm_linear_fit.build(spec.config, spec.workload, 1,
                                 harness.Phases())
    record = glm_linear_fit.step(state)
    assert record["hvps"][0] >= record["iterations"][0] >= 1
    assert record["evaluations"][0] >= record["iterations"][0] + 1
    passes = record["evaluations"][0] + record["hvps"][0]
    assert glm_linear_fit.work(state, record)["bytes"] == (
        passes * 8192 * 64 * 4)
    spec = _spec(SPARSE)
    state = glm_sparse_fit.build(spec.config, spec.workload, 1,
                                 harness.Phases())
    record = glm_sparse_fit.step(state)
    assert record["evaluations"][0] > record["iterations"][0] == 6
    assert glm_sparse_fit.work(state, record) == work_sparse.sparse_work(
        16384, 39, 512, record["evaluations"][0])
