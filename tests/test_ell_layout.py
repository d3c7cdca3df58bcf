"""The slot-major ELL batch against the dense batch on the same matrix, the
fit on it against the benchmark's plain references, and its row-sharded
form on a mesh against one device; and, on matrices whose rows differ in
length, the layout in several blocks of slots (rows longest first inside,
the caller's order at the surface) against the dense matrix."""

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
    deal_rows,
    ell_block_bounds,
    ell_tile_slots,
    ell_walk_steps,
    ell_from_csr,
    ell_from_rows,
    pad_batch,
    row_partition_specs,
    rows_in_layout_order,
)
from photon_ml_tpu.data import batch as batch_module
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
    return ell._replace(
        values=ell.values.astype(jnp.float64),
        tail=tuple((ix, v.astype(jnp.float64)) for ix, v in ell.tail))


def _matrix_of(ell: EllBatch) -> np.ndarray:
    """The dense matrix the planes hold, in the caller's row order: every
    block's slots added where they point."""
    n = ell.labels.shape[0]
    order = np.arange(n) if ell.order is None else np.asarray(ell.order)
    back = np.zeros((n, ell.dim), np.asarray(ell.values).dtype)
    for indices, values in ell.blocks:
        indices, values = (np.asarray(a).reshape(-1, a.shape[-1])
                           for a in (indices, values))
        k, rows = indices.shape
        np.add.at(back, (order[:rows][None, :].repeat(k, 0), indices), values)
    return back


def test_the_planes_are_slot_major_and_padded_slots_are_zero(rng):
    X, y, offs, wts = _ragged(rng)
    n, d = X.shape
    for ell in (ell_from_rows(_as_rows(X), d, y, offs, wts),
                ell_from_csr(sp.csr_matrix(X), y, offs, wts)):
        k = ell.indices.shape[0]
        assert ell.indices.shape == ell.values.shape == (k, n)
        # 13 slots padded to a multiple of 8, in blocks that end at one
        depths = [ix.shape[-2] for ix, _ in ell.blocks]
        assert sum(depths) == 16 and all(k % 8 == 0 for k in depths)
        assert ell.indices.dtype == jnp.int32 and ell.num_features == d
        np.testing.assert_array_equal(_matrix_of(ell), X.astype(np.float32))
        where = {int(row): place for place, row in enumerate(
            np.arange(n) if ell.order is None else np.asarray(ell.order))}
        assert np.count_nonzero(np.asarray(ell.values)[:, where[0]]) == 0
        assert where[1] == 0  # the full row lies first
        assert sum(np.count_nonzero(np.asarray(v)[..., 0])
                   for _, v in ell.blocks) == d


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
    assert padded.indices.shape == (ell.indices.shape[0], 64)
    assert padded.labels.shape == (64,) and sum(
        ix.shape[-2] for ix, _ in padded.blocks) == 16
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


@pytest.mark.parametrize("form", ["slot", "tile"])
@pytest.mark.parametrize("slots", [1, 5, 39])
def test_the_slot_walk_equals_a_float64_dense_pass(rng, slots, form,
                                                   monkeypatch):
    """``margins``, ``weighted_feature_sum`` and ``hadamard_square_sum``
    carry one accumulator through the K slots, one a step or a tile of
    them a step (257 rows are too few for a slot a step to pay; with no
    rows too few every walk takes one slot a step); each against the dense
    float64 sum over the same rows, with padded slots and one column that
    most rows hold. Under ``vmap`` over a stack of coefficient vectors the
    walk is still one gather a step."""
    if form == "slot":
        monkeypatch.setattr(batch_module, "ELL_TILE_ROWS", 0)
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
    ix, vals = np.zeros((2, slots, n))  # one block of K slots, by hand
    for i, (cols, v) in enumerate(_as_rows(X)):
        ix[:len(cols), i], vals[:len(cols), i] = cols, v
    ell = ell_batch(ix, vals, np.zeros(n), d, rng.normal(size=n) * 0.1)
    assert ell.indices.shape == (slots, n) and not ell.tail
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


# --- rows of uneven length: several blocks of slots --------------------------


def _ragged_csr(rng, n=6000, d=700, longest=60):
    """A CSR matrix with rows of 1 to ``longest`` cells (a heavy tail: most
    rows short), an empty row, and one cell stored twice (the arrays are
    not canonical: the builder has to sum the two)."""
    lens = np.minimum(1 + np.floor(rng.pareto(1.3, size=n) * 6),
                      longest).astype(int)
    lens[7] = 0
    lens[11] = longest
    rows = np.repeat(np.arange(n), lens)
    cols = np.concatenate([np.sort(rng.choice(d, size=l, replace=False))
                           for l in lens])
    mat = sp.csr_matrix((rng.normal(size=len(rows)), (rows, cols)),
                        shape=(n, d))
    at = int(mat.indptr[3])  # row 3's first cell, stored again
    indptr = mat.indptr.copy()
    indptr[4:] += 1
    twice = sp.csr_matrix(
        (np.insert(mat.data, at, 0.75),
         np.insert(mat.indices, at, mat.indices[at]), indptr), shape=(n, d))
    assert not twice.has_canonical_format and twice.nnz == mat.nnz + 1
    y = (rng.random(n) > 0.5).astype(float)
    return twice, y, rng.normal(size=n) * 0.1, rng.random(n) + 0.5


@pytest.fixture(scope="module")
def ragged():
    """(the matrix, duplicates summed; the program's ELL batch of it through
    ``csr_to_batch``; the dense batch)."""
    from photon_ml_tpu.data.batch import canonicalized_csr
    from photon_ml_tpu.game.dataset import csr_to_batch

    twice, y, offs, wts = _ragged_csr(np.random.default_rng(35))
    ell = csr_to_batch(twice, y, offs, wts, dtype=jnp.float64,
                       dense_threshold=8)
    mat = canonicalized_csr(twice)
    assert mat.nnz == twice.nnz - 1  # the two cells are one
    dense = dense_batch(mat.toarray(), y, offs, wts, dtype=jnp.float64)
    return mat, ell, dense


def test_a_ragged_matrix_is_laid_out_in_blocks_longest_first(ragged):
    mat, ell, _ = ragged
    n = mat.shape[0]
    lens = np.diff(mat.indptr)
    bounds = ell_block_bounds(lens)
    assert len(bounds) > 2 and bounds[-1] == 64 and bounds == sorted(bounds)
    assert all(b % 8 == 0 for b in bounds)
    assert len(ell.blocks) == len(bounds) and ell.tail
    lo, rows_before = 0, n + 1
    for (indices, values), hi in zip(ell.blocks, bounds):
        rows = n if lo == 0 else int(np.sum(lens > lo))
        # the first block [K_0, N]; a further one [1, K, n]: one run of rows
        assert indices.shape == values.shape == (
            (hi, n) if lo == 0 else (1, hi - lo, rows))
        assert indices.dtype == jnp.int32 and rows < rows_before
        lo, rows_before = hi, rows
    order = np.asarray(ell.order)
    assert sorted(order) == list(range(n))
    assert np.all(np.diff(lens[order]) <= 0)  # longest first
    # every stored cell is in exactly one slot: the planes give the matrix
    np.testing.assert_array_equal(_matrix_of(ell), mat.toarray())
    assert ell.walked_slots == sum(
        int(np.prod(ix.shape)) for ix, _ in ell.blocks)
    assert ell.walked_slots < n * 64  # fewer than rows x longest


@pytest.mark.parametrize("loss", LOSSES, ids=lambda l: l.name)
def test_the_ragged_layout_equals_the_dense_matrix(ragged, loss):
    """Value, gradient, Hessian-vector product, Hessian diagonal and the
    margins, at the surface (the caller's order) and in the solver's view
    (the layout's order)."""
    mat, ell, dense = ragged
    rng = np.random.default_rng(3)
    d = mat.shape[1]
    if loss.name == "poisson":  # labels that suit the loss
        y = jnp.asarray(rng.poisson(2.0, size=mat.shape[0]).astype(float))
        ell, dense = ell._replace(labels=y), dense._replace(labels=y)
    obj = GLMObjective(loss, l2_lambda=0.05)
    w = jnp.asarray(rng.normal(size=d) * 0.1)
    v = jnp.asarray(rng.normal(size=d))
    close = dict(rtol=1e-6, atol=1e-7)

    @jax.jit
    def everything(batch):
        return (*obj.calculate(w, batch), obj.hessian_vector(w, v, batch),
                obj.hessian_diagonal(w, batch))

    on_dense = everything(dense)
    for batch in (ell, rows_in_layout_order(ell)):
        on_ell = everything(batch)
        assert float(on_ell[0]) == pytest.approx(float(on_dense[0]),
                                                 rel=1e-9)
        for a, b in zip(on_ell[1:], on_dense[1:]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **close)
    shift = jnp.float64(0.25)
    np.testing.assert_allclose(
        np.asarray(jax.jit(lambda b: b.margins(w, shift))(ell)),
        np.asarray(dense.margins(w, shift)), **close)


def test_the_callers_row_order_holds_at_the_surface(ragged):
    mat, ell, dense = ragged
    n, d = mat.shape
    for name in ("labels", "offsets", "weights"):
        np.testing.assert_array_equal(np.asarray(getattr(ell, name)),
                                      np.asarray(getattr(dense, name)))
    # offsets swapped in by position (FixedEffectDataset.with_offsets) land
    # on the caller's rows
    w = jnp.asarray(np.random.default_rng(1).normal(size=d) * 0.1)
    extra = jnp.arange(n, dtype=jnp.float64) / n
    moved = ell._replace(offsets=ell.offsets + extra)
    np.testing.assert_allclose(
        np.asarray(moved.margins(w, 0.0) - ell.margins(w, 0.0)),
        np.asarray(extra), atol=1e-12)
    # the solver's view: the same rows in the planes' order, no order left
    inside = rows_in_layout_order(moved)
    assert inside.order is None and inside.tail == moved.tail
    order = np.asarray(ell.order)
    np.testing.assert_array_equal(np.asarray(inside.offsets),
                                  np.asarray(moved.offsets)[order])
    np.testing.assert_allclose(np.asarray(inside.margins(w, 0.0)),
                               np.asarray(moved.margins(w, 0.0))[order],
                               atol=1e-12)
    assert rows_in_layout_order(inside) is inside
    assert rows_in_layout_order(dense) is dense
    # and it crosses a jit boundary as it is
    jitted = jax.jit(lambda b, w: b.margins(w, 0.0))
    np.testing.assert_allclose(np.asarray(jitted(moved, w)),
                               np.asarray(moved.margins(w, 0.0)), atol=1e-12)


def test_rows_of_one_length_give_the_one_block_bit_for_bit(rng):
    """A fixed-length matrix through the builders is today's one
    ``[K, N]`` block, no order, and the same numbers as planes handed to
    ``ell_batch`` directly."""
    cols, vals, y = _criteo_like(rng, n=512, d=96, k=7)
    n = cols.shape[0]
    mat = sp.csr_matrix((vals.ravel(), cols.ravel(),
                         np.arange(0, 7 * n + 1, 7)), shape=(n, 96))
    mat.sort_indices()
    built = ell_from_csr(mat, y)
    assert built.tail == () and built.order is None
    assert built.indices.shape == (8, n)  # 7 slots in one sublane group
    srt = np.argsort(cols, axis=1)
    by_hand = ell_batch(
        np.pad(np.take_along_axis(cols, srt, 1), ((0, 0), (0, 1))).T,
        np.pad(np.take_along_axis(vals, srt, 1), ((0, 0), (0, 1))).T,
        y, dim=96)
    for a, b in zip(jax.tree_util.tree_leaves(built),
                    jax.tree_util.tree_leaves(by_hand)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (jax.tree_util.tree_structure(built)
            == jax.tree_util.tree_structure(by_hand))
    obj = GLMObjective(losses.logistic_loss, l2_lambda=1.0)
    w = jnp.asarray(rng.normal(size=96).astype(np.float32) * 0.1)
    for a, b in zip(obj.calculate(w, built), obj.calculate(w, by_hand)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    lowered = [jax.jit(obj.calculate).lower(w, b).as_text()
               for b in (built, by_hand)]
    assert lowered[0] == lowered[1]  # one program, not two


def _one_block(rng):
    cols, vals, y = _criteo_like(rng, n=700, d=96, k=7)
    return ell_batch(cols.T, vals.T, y, dim=96)


# Starts whose every square and partial sum is a float32 integer below
# 2**24, so that no order of summing them rounds: XLA:CPU takes the L2
# term's ``dot(x, x)`` through a library call at the top level of a program
# and as a fused loop inside a ``while``, which round differently (by 1 ulp
# at 96 columns of a normal draw; the TPU makes both a multiply and a reduce
# in one fusion). So what the solves below differ by is the start's form,
# never the CPU's emission of a dot.
_STARTS = {"zeros": lambda rng, d: np.zeros(d),
           "large-and-negative": lambda rng, d: np.where(
               np.arange(d) % 3 == 0, -500.0,
               np.round(rng.normal(size=d) * 30.0))}


def _row_sparse_payload(rng, ragged, layout):
    batch = _one_block(rng) if layout == "one-block" else ragged[1]
    return batch, (GLMObjective(losses.logistic_loss, l2_lambda=0.7),
                   rows_in_layout_order(batch))


@pytest.mark.parametrize("start", sorted(_STARTS))
@pytest.mark.parametrize("layout", ["one-block", "several-blocks"])
def test_the_row_sparse_start_made_in_a_loop_is_the_direct_starts_solve(
        rng, ragged, monkeypatch, layout, start):
    """On an ``EllBatch`` the L-BFGS start is evaluated inside a loop (the
    placement the line search gets on a TPU), booked ``in_loop`` once a
    trace; the solve returns, bit for bit, what the same solve returns
    from the start taken by ``objective.calculate(x0, batch)`` directly."""
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.optimize import lbfgs

    batch, payload = _row_sparse_payload(rng, ragged, layout)
    x0 = jnp.asarray(_STARTS[start](rng, batch.dim), batch.values.dtype)
    booked = REGISTRY.counter("solver_start_lowerings")

    def solve():
        def vg(w, p):  # a new function: the solve traces anew
            return p[0].calculate(w, p[1])

        x, hist, _ = lbfgs.minimize_lbfgs(vg, x0, payload, max_iter=12,
                                          tolerance=1e-12)
        return [x, hist.values, hist.grad_norms, hist.evaluations]

    before = booked.value(site="optimizer.lbfgs", form="in_loop")
    in_loop = solve()
    assert booked.value(site="optimizer.lbfgs",
                        form="in_loop") == before + 1
    with monkeypatch.context() as m:
        m.setattr(lbfgs, "start_evaluation",
                  lambda vg, x0, data: vg(x0, data))
        direct = solve()
    assert int(in_loop[3][0]) == 1 and int(in_loop[3][1]) >= 1
    for a, b in zip(in_loop, direct):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("layout", ["one-block", "several-blocks"])
def test_the_row_sparse_start_keeps_every_bit_of_x0(rng, ragged, layout):
    """The table the loop's body makes is ``x0`` bit for bit: an infinite
    entry stays infinite (``x0 + 0*x0`` would make it NaN, and its column's
    gradient with it) and a negative zero stays negative."""
    from photon_ml_tpu.optimize.lbfgs import start_evaluation

    batch, payload = _row_sparse_payload(rng, ragged, layout)
    x0 = np.round(rng.normal(size=batch.dim) * 3.0)
    x0[[0, 5]] = -0.0, np.inf
    x0 = jnp.asarray(x0, batch.values.dtype)

    def vg(w, p):
        return p[0].calculate(w, p[1])

    got = jax.jit(lambda w, p: start_evaluation(vg, w, p))(x0, payload)
    want = jax.jit(vg)(x0, payload)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.atleast_1d(a).view(np.uint8),
                                      np.atleast_1d(b).view(np.uint8))
    assert np.isinf(np.asarray(got[1])[5])


def _lengths(law: str, n: int) -> np.ndarray:
    z = np.random.default_rng(8).normal(size=n)
    if law == "kddb":  # the KDD Cup 2010 cell's law (benchmark/configs)
        return np.minimum(12 + np.round(np.exp(2.6133 + 0.7 * z)), 128)
    if law == "wide":  # mean 33, a third of the rows under 16, 1% at 128
        return np.minimum(1 + np.round(np.exp(3.0 + 1.0 * z)), 128)
    return np.full(n, 39.0)  # "fixed"


@pytest.mark.parametrize("law,bound", [("kddb", 1.15), ("wide", 1.2),
                                       ("fixed", 40 / 39)])
def test_walked_slots_follow_the_stored_non_zeros(law, bound):
    """At most 1.2x the non-zeros on heavy-tailed lengths of mean 16 or
    more, and never more than rows x longest."""
    lens = _lengths(law, 400_000).astype(np.int64)
    assert lens.mean() >= 16
    bounds = np.asarray(ell_block_bounds(lens))
    assert len(bounds) <= 8 and bounds[-1] == -(-lens.max() // 8) * 8
    walked = bounds[np.searchsorted(bounds, lens)].sum()
    assert walked >= lens.sum()
    assert walked <= bound * lens.sum() + 1e-9 * walked
    assert walked <= len(lens) * bounds[-1]
    if law == "fixed":
        assert list(bounds) == [40]
    else:
        assert walked < 0.5 * len(lens) * bounds[-1]


def test_short_rows_pay_at_most_a_sublane_group():
    lens = np.random.default_rng(2).integers(0, 25, size=200_000)
    bounds = np.asarray(ell_block_bounds(lens))
    assert list(bounds) == [8, 16, 24]
    walked = bounds[np.searchsorted(bounds, np.maximum(lens, 1))].sum()
    assert walked <= lens.sum() + 8 * len(lens)
    # one rule at every size: 48 rows are cut where 200,000 are
    assert ell_block_bounds(np.arange(48) % 14) == [8, 16]
    assert ell_block_bounds(np.zeros(0, np.int64)) == [8]
    assert ell_block_bounds(np.array([0, 0])) == [8]
    assert ell_block_bounds(np.array([3, 5]), multiple=1) == [3, 5]
    # at most eight blocks, the fewest among equals
    assert len(ell_block_bounds(np.arange(1, 400))) == 8
    assert ell_block_bounds(np.array([9, 9, 16])) == [16]


def _elastic_net_problem(**kw):
    from photon_ml_tpu.optimize.config import (
        GLMOptimizationConfiguration,
        RegularizationContext,
        RegularizationType,
    )
    from photon_ml_tpu.optimize.problem import GLMOptimizationProblem

    return GLMOptimizationProblem(
        config=GLMOptimizationConfiguration(
            max_iterations=40, tolerance=1e-10, regularization_weight=2.0,
            optimizer_type=OptimizerType.LBFGS,
            regularization_context=RegularizationContext(
                RegularizationType.ELASTIC_NET, alpha=0.5)),
        task=TaskType.LOGISTIC_REGRESSION, **kw)


def test_padding_a_ragged_batch(ragged):
    mat, ell, dense = ragged
    n, d = mat.shape
    padded = pad_batch(ell, n + 40)
    assert padded.labels.shape == (n + 40,)
    assert padded.indices.shape[1] == n + 40 and padded.tail == ell.tail
    assert sorted(np.asarray(padded.order)) == list(range(n + 40))
    obj = GLMObjective(losses.logistic_loss, l2_lambda=0.1)
    w = jnp.asarray(np.random.default_rng(4).normal(size=d) * 0.1)
    for batch in (padded, rows_in_layout_order(padded)):
        for a, b in zip(jax.jit(obj.calculate)(w, batch),
                        obj.calculate(w, dense)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-9, atol=1e-12)


@pytest.mark.parametrize("shards", [2, 4, 8])
def test_a_ragged_batch_is_dealt_into_one_run_of_rows_a_shard(ragged, shards):
    """Every run longest first and a nested prefix of every block; the
    dealt batch is the same matrix at the surface, and one shard of it is
    the single-run batch of its own rows."""
    mat, ell, dense = ragged
    n, d = mat.shape
    lens = np.diff(mat.indptr)
    whole = pad_batch(ell, -(-n // shards) * shards)
    rows = whole.labels.shape[0]
    dealt = deal_rows(whole, shards)
    assert deal_rows(dealt, shards) is dealt
    assert deal_rows(dense, shards) is dense
    assert dealt.indices.shape == whole.indices.shape
    for (ix, v), (ix1, _) in zip(dealt.tail, whole.tail):
        assert ix.shape == v.shape == (shards, ix1.shape[1],
                                       -(-ix1.shape[2] // shards))
    order = np.asarray(dealt.order).reshape(shards, -1)
    assert sorted(order.ravel()) == list(range(rows))
    run_lens = np.pad(lens, (0, rows - n))[order]
    assert np.all(np.diff(run_lens, axis=1) <= 0)  # every run longest first
    assert np.ptp(run_lens.sum(axis=1)) <= 64 * len(dealt.blocks)
    for name in ("labels", "offsets", "weights"):  # the caller's order
        assert getattr(dealt, name) is getattr(whole, name)
    obj = GLMObjective(losses.logistic_loss, l2_lambda=0.1)
    w = jnp.asarray(np.random.default_rng(4).normal(size=d) * 0.1)
    v = jnp.asarray(np.random.default_rng(5).normal(size=d))

    @jax.jit
    def everything(batch):
        return (*obj.calculate(w, batch), obj.hessian_diagonal(w, batch),
                obj.hessian_vector(w, v, batch))

    def same(batch, other):
        for a, b in zip(everything(batch), everything(other)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-9, atol=1e-12)

    inside = rows_in_layout_order(dealt)
    for batch in (dealt, inside, deal_rows(rows_in_layout_order(whole),
                                           shards)):
        same(batch, dense)
    np.testing.assert_allclose(
        np.asarray(jax.jit(lambda b: b.margins(w, 0.0))(dealt))[:n],
        np.asarray(dense.margins(w, 0.0)), atol=1e-12)
    # one shard of it: what shard_map hands a device
    m = rows // shards
    for s in (0, shards - 1):
        shard = inside._replace(
            indices=inside.indices[:, s * m:(s + 1) * m],
            values=inside.values[:, s * m:(s + 1) * m],
            tail=tuple((ix[s:s + 1], v[s:s + 1]) for ix, v in inside.tail),
            **{name: getattr(inside, name)[s * m:(s + 1) * m]
               for name in ("labels", "offsets", "weights")})
        mine = order[s][order[s] < n]  # its own rows of the matrix
        same(shard, dense_batch(mat.toarray()[mine], *(
            np.asarray(getattr(dense, name))[mine]
            for name in ("labels", "offsets", "weights")), dtype=jnp.float64))
    # what it refuses, by name: padding after the deal, a second deal
    with pytest.raises(ValueError, match="pad_batch before deal_rows"):
        pad_batch(dealt, rows + shards)
    with pytest.raises(ValueError, match="cannot be dealt"):
        deal_rows(dealt, 3)
    with pytest.raises(ValueError, match="cannot be dealt"):
        deal_rows(pad_batch(ell, n + 1 - n % 2), 2)  # rows do not divide


def test_a_ragged_batch_placed_on_a_mesh_is_split_by_runs(ragged):
    from photon_ml_tpu.parallel.mesh import make_mesh, shard_batch

    mat, ell, dense = ragged
    n, d = mat.shape
    mesh = make_mesh()
    n_data = mesh.shape["data"]
    assert n_data == 8 and len(ell.tail) >= 2
    placed = shard_batch(pad_batch(ell, -(-n // n_data) * n_data), mesh)
    rows = placed.labels.shape[0]
    assert placed.indices.sharding.shard_shape(placed.indices.shape) == (
        placed.indices.shape[0], rows // n_data)
    for ix, v in placed.tail:  # a shard holds its own run of every block
        assert ix.sharding.shard_shape(ix.shape) == (1,) + ix.shape[1:]
        assert v.sharding.shard_shape(v.shape) == (1,) + v.shape[1:]
    specs = row_partition_specs(placed, "data")
    assert tuple(specs.order) == ("data",)
    assert all(tuple(a) == ("data", None, None)
               for block in specs.tail for a in block)
    obj = GLMObjective(losses.logistic_loss, l2_lambda=0.1)
    w = jnp.asarray(np.random.default_rng(4).normal(size=d) * 0.1)
    for a, b in zip(jax.jit(obj.calculate)(w, placed),
                    obj.calculate(w, dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-9,
                                   atol=1e-12)


@pytest.mark.parametrize("shard_update", [False, True])
def test_the_row_sharded_ragged_fit_equals_one_device(ragged, shard_update):
    """OWL-QN on the ragged layout through ``run_glm_shard_map`` over the
    eight-device CPU mesh (the drivers' route on any host with more than
    one device), from the builder's batch and from one ``shard_batch``
    placed, against the same fit on one device and on the dense matrix."""
    from photon_ml_tpu.parallel import distributed
    from photon_ml_tpu.parallel.mesh import make_mesh, shard_batch

    mat, ell, dense = ragged
    n = mat.shape[0]
    problem = _elastic_net_problem(shard_weight_update=shard_update,
                                   compute_variances=True)
    local, local_result = problem.run(ell)
    on_dense, _ = problem.run(dense)
    mesh = make_mesh()
    placed = shard_batch(pad_batch(ell, -(-n // 8) * 8), mesh)
    for batch in (ell, placed):
        sharded, result = distributed.run_glm_shard_map(problem, batch, mesh)
        assert int(result.iterations) == int(local_result.iterations)
        for other in (local, on_dense):
            np.testing.assert_allclose(
                np.asarray(sharded.coefficients.means),
                np.asarray(other.coefficients.means), rtol=1e-6, atol=1e-8)
            np.testing.assert_allclose(
                np.asarray(sharded.coefficients.variances),
                np.asarray(other.coefficients.variances), rtol=1e-6)
        zero = np.asarray(sharded.coefficients.means) == 0.0
        assert 0 < zero.sum() < zero.size  # the L1 part holds some at zero
        np.testing.assert_array_equal(
            zero, np.asarray(local.coefficients.means) == 0.0)


def test_a_default_mesh_routes_the_ragged_fit_over_the_shards(ragged):
    """What ``legacy_driver.run`` and ``game_training_driver.run`` do on
    a host with more than one device: ``setup_default_mesh()`` and then
    ``problem.run`` on the batch ``csr_to_batch`` built."""
    from photon_ml_tpu.parallel import mesh as mesh_mod

    mat, ell, _ = ragged
    problem = _elastic_net_problem()
    local, _ = problem.run(ell)
    try:
        assert mesh_mod.setup_default_mesh().shape["data"] == 8
        routed, _ = problem.run(ell)
    finally:
        mesh_mod.set_default_mesh(None)
    np.testing.assert_allclose(np.asarray(routed.coefficients.means),
                               np.asarray(local.coefficients.means),
                               rtol=1e-6, atol=1e-8)


# --- long rows: blocks thousands of slots deep over few rows ---------------


def _long_rows(seed=7, n=512, d=6000, mean=300.0, cap=4096):
    """Rows of log-normal length (mean about ``mean``, cut at ``cap``) over
    ``d`` columns, ascending and distinct, positive values of unit-length
    rows, labels of both signs: the layout's deeper blocks hold a few rows
    each."""
    rng = np.random.default_rng(seed)
    lens = np.clip(np.round(np.exp(np.log(mean) - 0.32
                                   + 0.8 * rng.standard_normal(n))),
                   1, cap).astype(int)
    cols = np.concatenate([np.sort(rng.choice(d, size=l, replace=False))
                           for l in lens]).astype(np.int32)
    indptr = np.concatenate([[0], np.cumsum(lens)])
    vals = rng.random(indptr[-1]).astype(np.float32) + 0.5
    norms = np.sqrt(np.add.reduceat(vals * vals, indptr[:-1]))
    vals /= np.repeat(norms, lens).astype(np.float32)
    mat = sp.csr_matrix((vals, cols, indptr), shape=(n, d))
    return mat, (rng.random(n) < 0.6).astype(np.float32)


@pytest.fixture(scope="module")
def long_rows():
    mat, y = _long_rows()
    return mat, y, ell_from_csr(mat, y)


def test_the_tile_is_read_from_a_blocks_rows():
    rows = batch_module.ELL_TILE_ROWS
    assert ell_tile_slots(64, rows) == ell_tile_slots(64, 10 * rows) == 1
    for n in (1, 7, 100, rows // 3, rows - 1):
        tile = ell_tile_slots(10**6, n)
        assert tile % 8 == 0 and tile * n >= rows > (tile - 8) * n
        assert ell_tile_slots(5, n) == 5  # no deeper than the block
    # a step over the block's depth at most, the last step shorter
    assert ell_walk_steps(40, rows) == 40
    assert ell_walk_steps(40, 1) == 1 and ell_walk_steps(0, 1) == 0
    tile = ell_tile_slots(10**6, rows // 2)
    assert ell_walk_steps(2 * tile + 8, rows // 2) == 3


def _walk_steps_in(jaxpr):
    """Loop steps a traced walk makes: every ``scan``'s length, and every
    ``gather`` outside one (a shorter last step)."""
    steps = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            steps += eqn.params["length"]
            continue
        if eqn.primitive.name == "gather":
            steps += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            steps += _walk_steps_in(sub)
    return steps


def test_long_rows_walk_tiles_and_the_counters_book_them(long_rows):
    from photon_ml_tpu.obs.metrics import REGISTRY

    mat, y, _ = long_rows
    steps = REGISTRY.counter("ell_walk_steps")
    lowerings = REGISTRY.counter("ell_walk_lowerings")
    before = sum(steps.items().values())
    ell = ell_from_csr(mat, y)
    booked = sum(steps.items().values()) - before
    shapes = [ix.shape[-2:] for ix, _ in ell.blocks]
    assert len(shapes) >= 6 and shapes[-1][1] < 50  # deep blocks, few rows
    assert booked == sum(ell_walk_steps(k, n) for k, n in shapes)
    assert booked < sum(k for k, _ in shapes) / 8  # tiles, not slots
    moved = rows_in_layout_order(ell)
    forms = {f: lowerings.value(form=f) for f in ("slot", "tile")}
    jaxpr = jax.make_jaxpr(lambda w: moved.margins(w, 0.0))(
        jnp.zeros(mat.shape[1], jnp.float32)).jaxpr
    assert _walk_steps_in(jaxpr) == booked  # what one walk makes
    assert lowerings.value(form="tile") - forms["tile"] == len(shapes)
    assert lowerings.value(form="slot") == forms["slot"]


@pytest.mark.parametrize("loss", [losses.smoothed_hinge_loss,
                                  losses.logistic_loss], ids=lambda l: l.name)
def test_the_tiled_walk_equals_a_dense_float32_pass(long_rows, loss):
    mat, y, ell = long_rows
    n, d = mat.shape
    rng = np.random.default_rng(3)
    X = mat.toarray().astype(np.float64)
    w = rng.normal(size=d).astype(np.float32) * 0.1
    r = rng.normal(size=n).astype(np.float32)
    margins, sums, squares = jax.jit(lambda b, w, r: (
        b.margins(w, 0.0), b.weighted_feature_sum(r),
        b.hadamard_square_sum(r)))(ell, jnp.asarray(w), jnp.asarray(r))
    np.testing.assert_allclose(np.asarray(margins), X @ w, rtol=0,
                               atol=1e-5 * np.abs(X @ np.abs(w)).max())
    np.testing.assert_allclose(np.asarray(sums), X.T @ r, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(squares), (X * X).T @ r,
                               rtol=1e-5, atol=1e-5)
    obj = jax.jit(GLMObjective(loss, l2_lambda=1.0).calculate)
    dense = dense_batch(X.astype(np.float32), y)
    for a, b in zip(obj(jnp.asarray(w), ell), obj(jnp.asarray(w), dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_a_smoothed_hinge_fit_on_long_rows_follows_the_reference(long_rows):
    """Four L-BFGS iterations of the smoothed-hinge SVM through
    ``train_glm_grid`` on the tiled layout: the values it reports never
    rise and are the plain reference's at its coefficients, it descends as
    far as the reference's textbook L-BFGS in as many iterations, and the
    dense batch of the same matrix takes the same path."""
    from benchmark.reference import glm_ragged, glm_svm

    mat, y, ell = long_rows
    n, d = mat.shape

    def fit(batch):
        model, = train_glm_grid(
            batch, TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
            regularization_weights=[1.0], max_iterations=4,
            tolerance=1e-30)
        return model.result

    got = fit(ell)
    assert int(got.iterations) == 4
    history = np.asarray(got.values, np.float64)
    assert np.all(np.diff(history) <= 0) and history[-1] < history[0]
    data = tuple(jnp.asarray(a) for a in (
        *glm_ragged.flat_blocks(mat.indptr, mat.indices, mat.data, 128), y,
        np.zeros(n, np.float32), np.ones(n, np.float32)))

    def fn(w):
        return glm_svm.objective(*data, w, 1.0)

    value, grad = fn(np.asarray(got.coefficients, np.float64))
    assert float(got.value) == pytest.approx(value, rel=1e-5)
    assert float(got.grad_norm) == pytest.approx(np.linalg.norm(grad),
                                                 rel=1e-4)
    _, textbook, _ = sparse_reference.lbfgs(fn, np.zeros(d), 4)
    assert value <= textbook[-1] + 1e-3 * (textbook[0] - textbook[-1])
    np.testing.assert_allclose(history[0], textbook[0], rtol=1e-6)
    on_dense = fit(dense_batch(mat.toarray(), y))
    np.testing.assert_allclose(history, np.asarray(on_dense.values),
                               rtol=1e-5)
