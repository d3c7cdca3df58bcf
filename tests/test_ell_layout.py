"""The slot-major ELL batch against the dense batch on the same matrix, the
fit on it against the benchmark's plain references, and its row-sharded
form on a mesh against one device."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from benchmark.reference import glm as dense_reference
from benchmark.reference import glm_sparse as sparse_reference
from photon_ml_tpu.data.batch import (
    DenseBatch,
    EllBatch,
    dense_batch,
    ell_batch,
    ell_from_csr,
    ell_from_rows,
    pad_batch,
    row_partition_specs,
)
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops.aggregators import GLMObjective
from photon_ml_tpu.optimize.config import OptimizerType, TaskType
from photon_ml_tpu.training import train_glm_grid
from test_linesearch import _sites

LOSSES = [losses.logistic_loss, losses.squared_loss, losses.poisson_loss]


def _ragged(rng, n=48, d=13, loss_name="logistic"):
    """A matrix with empty rows, a full row and rows of every length
    between, and labels that suit the loss."""
    X = rng.normal(size=(n, d)) * (rng.random((n, d)) > 0.6)
    X[0] = 0.0
    X[1] = rng.normal(size=d)
    if loss_name == "poisson":
        y = rng.poisson(2.0, size=n).astype(float)
    elif loss_name == "squared":
        y = rng.normal(size=n)
    else:
        y = (rng.random(n) > 0.5).astype(float)
    return X, y, rng.normal(size=n) * 0.1, rng.random(n) + 0.5


def _as_rows(X):
    rows = []
    for i in range(X.shape[0]):
        (ix,) = np.nonzero(X[i])
        rows.append((ix.astype(np.int32), X[i, ix]))
    return rows


def _f64(ell: EllBatch) -> EllBatch:
    return ell._replace(values=ell.values.astype(jnp.float64))


def test_the_planes_are_slot_major_and_padded_slots_are_zero(rng):
    X, y, offs, wts = _ragged(rng)
    n, d = X.shape
    for ell in (ell_from_rows(_as_rows(X), d, y, offs, wts),
                ell_from_csr(sp.csr_matrix(X), y, offs, wts)):
        k = ell.indices.shape[0]
        assert ell.indices.shape == ell.values.shape == (k, n)
        assert k == 16 and k % 8 == 0  # 13 slots padded to a multiple of 8
        assert ell.indices.dtype == jnp.int32 and ell.num_features == d
        back = np.zeros((n, d), np.float32)
        np.add.at(back, (np.arange(n)[None, :].repeat(k, 0),
                         np.asarray(ell.indices)), np.asarray(ell.values))
        np.testing.assert_array_equal(back, X.astype(np.float32))
        assert np.count_nonzero(np.asarray(ell.values)[:, 0]) == 0  # row 0
        assert np.count_nonzero(np.asarray(ell.values)[:, 1]) == d


@pytest.mark.parametrize("loss", LOSSES, ids=lambda l: l.name)
def test_value_gradient_hvp_and_diagonal_equal_the_dense_batchs(rng, loss):
    X, y, offs, wts = _ragged(rng, loss_name=loss.name)
    d = X.shape[1]
    dense = dense_batch(X, y, offs, wts, dtype=jnp.float64)
    ell = _f64(ell_from_rows(_as_rows(X), d, y, offs, wts))
    obj = GLMObjective(loss, l2_lambda=0.05)
    w = jnp.asarray(rng.normal(size=d) * 0.3)
    v = jnp.asarray(rng.normal(size=d))
    vd, gd = obj.calculate(w, dense)
    ve, ge = obj.calculate(w, ell)
    assert float(ve) == pytest.approx(float(vd), rel=1e-6)
    np.testing.assert_allclose(np.asarray(ge), np.asarray(gd), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(obj.hessian_vector(w, v, ell)),
        np.asarray(obj.hessian_vector(w, v, dense)), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(obj.hessian_diagonal(w, ell)),
        np.asarray(obj.hessian_diagonal(w, dense)), rtol=1e-5, atol=1e-6)


def test_ell_batch_takes_device_planes_as_they_are(rng):
    X, y, _, _ = _ragged(rng)
    built = ell_from_rows(_as_rows(X), X.shape[1], y)
    again = ell_batch(built.indices, built.values, built.labels,
                      dim=X.shape[1])
    assert again.indices is built.indices and again.values is built.values
    assert float(jnp.sum(again.weights)) == X.shape[0]
    assert float(jnp.sum(jnp.abs(again.offsets))) == 0.0
    with pytest.raises(ValueError, match=r"\[K, N\]"):
        ell_batch(built.indices, built.values[:-1], built.labels,
                  dim=X.shape[1])
    with pytest.raises(ValueError, match=r"\[K, N\]"):
        ell_batch(built.indices[0], built.values[0], built.labels,
                  dim=X.shape[1])
    # the parent's row-major [N, K] planes are refused by name, not by a
    # broadcast error deep in the first pass
    with pytest.raises(ValueError, match="slot-major"):
        ell_batch(built.indices.T, built.values.T, built.labels,
                  dim=X.shape[1])


def test_padded_rows_are_inert_and_the_row_axis_is_named(rng):
    X, y, offs, wts = _ragged(rng)
    d = X.shape[1]
    ell = _f64(ell_from_rows(_as_rows(X), d, y, offs, wts))
    padded = pad_batch(ell, 64)
    assert padded.indices.shape == (16, 64) and padded.labels.shape == (64,)
    obj = GLMObjective(losses.logistic_loss, l2_lambda=0.1)
    w = jnp.asarray(rng.normal(size=d) * 0.3)
    for a, b in zip(obj.calculate(w, ell), obj.calculate(w, padded)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-12)
    specs = row_partition_specs(ell, "data")
    assert isinstance(specs, EllBatch)
    assert tuple(specs.indices) == tuple(specs.values) == (None, "data")
    assert tuple(specs.labels) == tuple(specs.weights) == ("data",)
    dense_specs = row_partition_specs(dense_batch(X, y), "data")
    assert isinstance(dense_specs, DenseBatch)
    assert all(tuple(s) == ("data",) for s in dense_specs)


def _criteo_like(rng, n=4096, d=512, k=12):
    """Rows of ``k`` distinct columns of value 1/sqrt(k), Zipf-heavy."""
    p = 1.0 / (np.arange(d) + 3.0)
    cols = np.stack([rng.choice(d, size=k, replace=False, p=p / p.sum())
                     for _ in range(n)]).astype(np.int32)
    vals = np.full((n, k), k ** -0.5, np.float32)
    w_true = rng.normal(size=d)
    z = (vals * w_true[cols]).sum(1) - 0.5
    y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float32)
    return cols, vals, y


@pytest.mark.parametrize("optimizer", [OptimizerType.LBFGS,
                                       OptimizerType.TRON])
def test_a_fit_on_ell_reaches_the_references_minimiser(rng, optimizer):
    """``train_glm_grid`` on the slot-major batch against the benchmark's
    plain references: the sparse one's evaluation at the fitted
    coefficients, and the dense one's Newton minimiser (affordable at 512
    columns) on the same matrix."""
    cols, vals, y = _criteo_like(rng)
    n, d = cols.shape[0], 512
    ids, planes = jnp.asarray(cols.T), jnp.asarray(vals.T)
    batch = ell_batch(ids, planes, y, dim=d)
    (fit,) = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, [1.0],
                            optimizer_type=optimizer, max_iterations=200,
                            tolerance=1e-9)
    w = np.asarray(fit.result.coefficients, np.float64)
    zeros, ones = jnp.zeros(n, jnp.float32), jnp.ones(n, jnp.float32)
    f_at, g_at = sparse_reference.objective(ids, planes, jnp.asarray(y),
                                            zeros, ones, w, 1.0, block=1024)
    assert float(fit.result.value) == pytest.approx(f_at, rel=1e-5)
    assert float(fit.result.grad_norm) == pytest.approx(
        np.linalg.norm(g_at), abs=2e-3 * np.sqrt(n))
    X = np.zeros((n, d), np.float32)
    X[np.arange(n)[:, None], cols] = vals
    w_star, _ = dense_reference.newton(jnp.asarray(X), jnp.asarray(y), zeros,
                                       ones, 1.0, block=1024)
    assert np.linalg.norm(w - w_star) <= 2e-3 * np.linalg.norm(w_star)
    f_dense, _ = dense_reference.objective(jnp.asarray(X), jnp.asarray(y),
                                           zeros, ones, w, 1.0, block=1024)
    assert f_dense == pytest.approx(f_at, rel=1e-5)  # the two references


@pytest.mark.parametrize("shard_update", [False, True])
def test_the_row_sharded_ell_fit_equals_one_device(rng, shard_update):
    from photon_ml_tpu.optimize.config import (
        GLMOptimizationConfiguration,
        RegularizationContext,
        RegularizationType,
    )
    from photon_ml_tpu.optimize.problem import GLMOptimizationProblem
    from photon_ml_tpu.parallel import distributed
    from photon_ml_tpu.parallel.mesh import make_mesh, shard_batch

    cols, vals, y = _criteo_like(rng, n=1001, d=96, k=7)  # rows need padding
    batch = _f64(ell_batch(cols.T, vals.T, y, dim=96))
    problem = GLMOptimizationProblem(
        config=GLMOptimizationConfiguration(
            max_iterations=60, tolerance=1e-10, regularization_weight=1.0,
            optimizer_type=OptimizerType.LBFGS,
            regularization_context=RegularizationContext(
                RegularizationType.L2)),
        task=TaskType.LOGISTIC_REGRESSION, shard_weight_update=shard_update)
    local, _ = problem.run(batch)
    mesh = make_mesh()
    sharded, _ = distributed.run_glm_shard_map(problem, batch, mesh)
    np.testing.assert_allclose(np.asarray(sharded.coefficients.means),
                               np.asarray(local.coefficients.means),
                               rtol=1e-6, atol=1e-8)
    # placed by shard_batch, the planes are split along their row (minor) axis
    placed = shard_batch(pad_batch(batch, 1008), mesh)
    n_data = mesh.shape["data"]
    assert placed.indices.sharding.shard_shape(placed.indices.shape) == (
        7, 1008 // n_data)
    obj = GLMObjective(losses.logistic_loss, l2_lambda=1.0)
    w = jnp.asarray(rng.normal(size=96) * 0.1)
    for a, b in zip(jax.jit(obj.calculate)(w, placed),
                    obj.calculate(w, batch)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-9)


@pytest.mark.parametrize("slots", [1, 5, 39])
def test_the_slot_walk_equals_a_float64_dense_pass(rng, slots):
    """``margins``, ``weighted_feature_sum`` and ``hadamard_square_sum``
    carry one accumulator through the K slots; each against the dense
    float64 sum over the same rows, with padded slots and one column that
    most rows hold. Under ``vmap`` over a stack of coefficient vectors the
    walk is still one gather a slot."""
    n, d, stack = 257, 64, 3
    X = np.zeros((n, d))
    for i in range(n):
        stored = rng.integers(0, slots + 1)  # rows of 0..K stored slots
        cols = rng.choice(np.arange(1, d), size=stored, replace=False)
        if stored and rng.random() < 0.8:
            cols[0] = 0  # the heavy column
        X[i, cols] = rng.normal(size=stored)
    X[:2] = 0.0  # an empty row and a full one
    X[1, :slots] = rng.normal(size=slots)
    ell = ell_from_rows(_as_rows(X), d, np.zeros(n), rng.normal(size=n) * 0.1,
                        pad_to_multiple=1)
    assert ell.indices.shape == (slots, n)
    assert np.count_nonzero(np.asarray(ell.values)[:, 0]) == 0  # all padding
    X64 = X.astype(np.float32).astype(np.float64)  # what the planes hold
    W = rng.normal(size=(stack, d)).astype(np.float32)
    r = rng.normal(size=n).astype(np.float32)
    shift = jnp.float32(0.25)
    close = dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(ell.margins(jnp.asarray(W[0]), shift)),
        X64 @ W[0] + 0.25 + np.asarray(ell.offsets, np.float64), **close)
    np.testing.assert_allclose(
        np.asarray(ell.weighted_feature_sum(jnp.asarray(r))), X64.T @ r,
        **close)
    np.testing.assert_allclose(
        np.asarray(ell.hadamard_square_sum(jnp.asarray(r))),
        (X64 * X64).T @ r, **close)

    def stacked(W):
        return jax.vmap(lambda w: ell.margins(w, shift))(W)

    np.testing.assert_array_equal(
        np.asarray(stacked(jnp.asarray(W))),
        np.stack([np.asarray(ell.margins(jnp.asarray(w), shift)) for w in W]))
    jaxpr = jax.make_jaxpr(stacked)(jnp.asarray(W)).jaxpr
    assert _sites(jaxpr, "gather") == [("scan",)]  # the loop's body, not L
