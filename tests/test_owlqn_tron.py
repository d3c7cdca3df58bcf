"""OWL-QN and TRON solver behavior.

Mirrors reference test tier: OWLQNTest (L1 solutions, sparsity) and the TRON
integration tests (agreement with L-BFGS solutions on twice-differentiable
objectives, BaseGLMIntegTest's max-difference check between TRON and LBFGS).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.batch import dense_batch
from photon_ml_tpu.ops.aggregators import GLMObjective
from photon_ml_tpu.ops.losses import get_loss
from photon_ml_tpu.optimize.common import BoxConstraints, OptimizationResult
from photon_ml_tpu.optimize.lbfgs import minimize_lbfgs
from photon_ml_tpu.optimize.owlqn import minimize_owlqn, pseudo_gradient
from photon_ml_tpu.optimize.tron import minimize_tron
from test_lbfgs import check_vmapped_solve_is_each_lanes_own


def _obj_vg(w, payload):
    obj, batch = payload
    return obj.calculate(w, batch)


def _obj_hvp(w, v, payload):
    obj, batch = payload
    return obj.hessian_vector(w, v, batch)


def _problem(rng, loss="logistic", n=400, d=8, l2=0.0, sparse_truth=False):
    X = rng.normal(size=(n, d))
    X[:, -1] = 1.0
    w_true = rng.normal(size=d)
    if sparse_truth:
        w_true[1:5] = 0.0
    if loss == "squared":
        y = X @ w_true + 0.1 * rng.normal(size=n)
    elif loss == "poisson":
        y = rng.poisson(np.exp(np.clip(X @ w_true * 0.3, -3, 3))).astype(float)
    else:
        y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w_true)))).astype(float)
    batch = dense_batch(X, y, dtype=jnp.float64)
    obj = GLMObjective(get_loss(loss), l2_lambda=l2)
    return batch, obj


# --- pseudo-gradient unit behavior -----------------------------------------

def test_pseudo_gradient_regions():
    x = jnp.asarray([1.0, -1.0, 0.0, 0.0, 0.0])
    g = jnp.asarray([0.5, 0.5, -2.0, 2.0, 0.3])
    l1 = jnp.asarray(1.0)
    pg = np.asarray(pseudo_gradient(x, g, jnp.broadcast_to(l1, (5,))))
    assert pg[0] == pytest.approx(1.5)  # x>0: g + l1
    assert pg[1] == pytest.approx(-0.5)  # x<0: g - l1
    assert pg[2] == pytest.approx(-1.0)  # 0, g+l1<0: g + l1
    assert pg[3] == pytest.approx(1.0)  # 0, g-l1>0: g - l1
    assert pg[4] == pytest.approx(0.0)  # 0, inside [-l1, l1]: 0


# --- OWL-QN ----------------------------------------------------------------

def test_owlqn_zero_l1_matches_lbfgs(rng):
    batch, obj = _problem(rng)
    x_owl, _, _ = minimize_owlqn(_obj_vg, jnp.zeros(8, jnp.float64),
                                 (obj, batch), l1=0.0, tolerance=1e-10)
    x_lb, _, _ = minimize_lbfgs(_obj_vg, jnp.zeros(8, jnp.float64),
                                (obj, batch), tolerance=1e-10)
    np.testing.assert_allclose(np.asarray(x_owl), np.asarray(x_lb), atol=1e-5)


def test_owlqn_l1_induces_sparsity_and_optimality(rng):
    batch, obj = _problem(rng, sparse_truth=True)
    l1 = 20.0
    x, hist, ok = minimize_owlqn(_obj_vg, jnp.zeros(8, jnp.float64),
                                 (obj, batch), l1=l1, tolerance=1e-12)
    xa = np.asarray(x)
    assert np.sum(np.abs(xa) < 1e-8) >= 2, f"expected sparsity, got {xa}"
    # KKT check for F = f + l1|x|: |g_j| <= l1 where x_j == 0, g_j = -l1*sign
    # elsewhere (within solver tolerance).
    _, g = obj.calculate(x, batch)
    g = np.asarray(g)
    for j in range(8):
        if abs(xa[j]) < 1e-8:
            assert abs(g[j]) <= l1 + 1e-3
        else:
            assert g[j] + l1 * np.sign(xa[j]) == pytest.approx(0.0, abs=2e-3)


def test_owlqn_objective_beats_unregularized_point(rng):
    """F(x_owlqn) must be <= F(x_lbfgs): the L1 solution is optimal for F."""
    batch, obj = _problem(rng)
    l1 = 5.0
    x_owl, _, _ = minimize_owlqn(_obj_vg, jnp.zeros(8, jnp.float64),
                                 (obj, batch), l1=l1, tolerance=1e-12)
    x_lb, _, _ = minimize_lbfgs(_obj_vg, jnp.zeros(8, jnp.float64),
                                (obj, batch))

    def F(x):
        v, _ = obj.calculate(x, batch)
        return float(v) + l1 * float(jnp.sum(jnp.abs(x)))

    assert F(x_owl) <= F(x_lb) + 1e-9


def test_owlqn_per_coordinate_l1_spares_intercept(rng):
    batch, obj = _problem(rng, sparse_truth=True)
    l1_vec = np.full(8, 50.0)
    l1_vec[-1] = 0.0  # intercept unregularized
    x, _, _ = minimize_owlqn(_obj_vg, jnp.zeros(8, jnp.float64), (obj, batch),
                             l1=jnp.asarray(l1_vec), tolerance=1e-12)
    xa = np.asarray(x)
    # Heavy L1 kills features but the unpenalized intercept survives.
    assert np.abs(xa[-1]) > 1e-4
    assert np.sum(np.abs(xa[:-1]) < 1e-8) >= 5


# --- TRON ------------------------------------------------------------------

@pytest.mark.parametrize("loss", ["logistic", "squared", "poisson"])
def test_tron_matches_lbfgs_solution(rng, loss):
    """BaseGLMIntegTest analog: TRON and LBFGS must land on the same optimum
    of a strictly convex objective."""
    batch, obj = _problem(rng, loss=loss, l2=1.0)
    x_t, hist_t, ok_t = minimize_tron(_obj_vg, _obj_hvp,
                                      jnp.zeros(8, jnp.float64), (obj, batch),
                                      max_iter=50, tolerance=1e-10)
    x_l, _, _ = minimize_lbfgs(_obj_vg, jnp.zeros(8, jnp.float64), (obj, batch),
                               tolerance=1e-10)
    np.testing.assert_allclose(np.asarray(x_t), np.asarray(x_l), atol=2e-4)
    assert bool(ok_t)


def test_tron_quadratic_converges_in_few_iterations():
    """On a quadratic, Newton + exact CG should converge essentially in one
    accepted step."""
    A = jnp.asarray(np.diag([1.0, 4.0, 9.0, 16.0]))
    b = jnp.asarray([1.0, 2.0, 3.0, 4.0])

    def vg(x, _):
        return 0.5 * x @ A @ x - b @ x, A @ x - b

    def hvp(x, v, _):
        return A @ v

    x, hist, ok = minimize_tron(vg, hvp, jnp.zeros(4, jnp.float64), None,
                                max_iter=30, tolerance=1e-12)
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(np.asarray(A),
                                                              np.asarray(b)),
                               atol=1e-6)
    assert int(hist.num_iterations) <= 5


def test_tron_values_monotone(rng):
    batch, obj = _problem(rng, loss="squared", l2=0.5)
    _, hist, _ = minimize_tron(_obj_vg, _obj_hvp, jnp.zeros(8, jnp.float64),
                               (obj, batch), max_iter=40)
    k = int(hist.num_iterations)
    vals = np.asarray(hist.values)[: k + 1]
    assert np.all(np.isfinite(vals))
    assert np.all(np.diff(vals) <= 1e-10)


def test_all_optimizers_agree_from_random_starts(rng):
    """OptimizerIntegTest analog: on a strongly-convex L2 logistic
    objective, LBFGS and TRON land on the SAME optimum from several random
    starting points (and OWL-QN with l1=0 degenerates to it too)."""
    n, d = 400, 6
    X = rng.normal(size=(n, d))
    w_true = rng.normal(size=d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w_true)))).astype(float)
    batch = dense_batch(X, y, dtype=jnp.float64)
    obj = GLMObjective(loss=get_loss("logistic"), l2_lambda=0.5)
    payload = (obj, batch)

    optima = []
    for s in range(3):
        x0 = jnp.asarray(rng.normal(size=d))
        for run in (
            lambda: minimize_lbfgs(_obj_vg, x0, payload, max_iter=200,
                                   tolerance=1e-12),
            lambda: minimize_tron(_obj_vg, _obj_hvp, x0, payload,
                                  max_iter=60, tolerance=1e-12),
            lambda: minimize_owlqn(_obj_vg, x0, payload, l1=0.0,
                                   max_iter=300, tolerance=1e-12),
        ):
            x, _, _ = run()
            optima.append(np.asarray(x))
    ref = optima[0]
    for w in optima[1:]:
        np.testing.assert_allclose(w, ref, rtol=1e-4, atol=1e-6)


# --- the solvers count their own evaluations -------------------------------

_RAN = {"vg": 0, "hvp": 0}


def _ran(which):
    _RAN[which] += 1


def _counted_vg(w, payload):
    jax.debug.callback(functools.partial(_ran, "vg"))
    return _obj_vg(w, payload)


def _counted_hvp(w, v, payload):
    jax.debug.callback(functools.partial(_ran, "hvp"))
    return _obj_hvp(w, v, payload)


def _minimize(solver, vg, hvp, x0, payload, **kw):
    if solver == "lbfgs":
        return minimize_lbfgs(vg, x0, payload, **kw)
    if solver == "owlqn":
        return minimize_owlqn(vg, x0, payload, l1=0.05, **kw)
    return minimize_tron(vg, hvp, x0, payload, **kw)


SOLVERS = ("lbfgs", "owlqn", "tron")


@pytest.mark.parametrize("boxed", (False, True), ids=("free", "boxed"))
@pytest.mark.parametrize("solver", SOLVERS)
def test_evaluations_count_every_run_of_the_objective(rng, solver, boxed):
    """``RunHistory.evaluations`` (and TRON's ``hvps``) against a count the
    solver has no hand in: a host counter that the test's own objective
    bumps through a callback every time it really runs. Un-vmapped, so a
    ``cond``'s branch that is not taken does not run. The box is tight
    enough to clip iterates, which costs L-BFGS and TRON a re-evaluation."""
    batch, obj = _problem(rng, l2=0.1)
    d = batch.num_features
    box = BoxConstraints(jnp.full(d, -0.3), jnp.full(d, 0.3)) if boxed \
        else None
    _RAN.update(vg=0, hvp=0)
    x, hist, _ = _minimize(solver, _counted_vg, _counted_hvp,
                           jnp.zeros(d, jnp.float64), (obj, batch),
                           max_iter=25, tolerance=1e-9, box=box)
    jax.effects_barrier()
    k = int(hist.num_iterations)
    evaluations = np.asarray(hist.evaluations)
    assert k >= 3
    assert evaluations.dtype == np.int32 and evaluations.shape == (26,)
    assert evaluations[0] == 1  # the start
    assert int(evaluations.sum()) == _RAN["vg"]
    assert (evaluations[1:k + 1] >= 1).all()  # an iteration evaluates
    if boxed:
        assert float(jnp.max(jnp.abs(x))) <= 0.3
    if solver == "tron":
        assert int(np.asarray(hist.hvps).sum()) == _RAN["hvp"] > 0
        assert np.asarray(hist.hvps)[0] == 0
    else:
        assert hist.hvps is None and _RAN["hvp"] == 0
    # the host-side totals ride the history's own fetch
    res = OptimizationResult.from_history(x, hist, 25, 1e-9)
    assert res.evaluations == _RAN["vg"]
    assert res.hvps == (_RAN["hvp"] if solver == "tron" else None)


@pytest.mark.parametrize("solver", SOLVERS)
def test_resumed_chunks_count_what_one_dispatch_counts(rng, solver):
    """A solve split into resumed chunks makes the single dispatch's
    evaluations: a resumed chunk's slot 0 is 0 (its start was the previous
    chunk's last evaluation), and the rest line up iteration by iteration."""
    batch, obj = _problem(rng, l2=0.1)
    x0 = jnp.zeros(batch.num_features, jnp.float64)
    payload = (obj, batch)
    _, whole, _ = _minimize(solver, _obj_vg, _obj_hvp, x0, payload,
                            max_iter=6, tolerance=1e-12)
    k = int(whole.num_iterations)
    assert k == 6  # the budget, not convergence, ends it

    by_iteration, hvps, carry, x = [], 0, None, x0
    for chunk in range(3):
        x, hist, _, carry = _minimize(
            solver, _obj_vg, _obj_hvp, x, payload, max_iter=2,
            tolerance=1e-12, resume=carry, return_carry=True)
        evaluations = np.asarray(hist.evaluations)
        assert evaluations[0] == (1 if chunk == 0 else 0)
        by_iteration += list(evaluations[1:])
        if solver == "tron":
            hvps += int(np.asarray(hist.hvps).sum())
    assert by_iteration == list(np.asarray(whole.evaluations)[1:k + 1])
    if solver == "tron":
        assert hvps == int(np.asarray(whole.hvps).sum())


def test_counters_book_each_solve_once(rng):
    """``solver_*{site}`` are incremented where a history reaches the host,
    once however often the result is read; a history with no counts books
    nothing."""
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.optimize.common import DeferredOptimizationResult

    def totals():
        return {name: REGISTRY.counter(name).value(site="t.solver")
                for name in ("solver_iterations", "solver_evaluations",
                             "solver_lane_evaluations", "solver_hvps")}

    batch, obj = _problem(rng, l2=0.1)
    x0 = jnp.zeros(batch.num_features, jnp.float64)
    before = totals()
    x, hist, ok = minimize_tron(_obj_vg, _obj_hvp, x0, (obj, batch))
    lazy = DeferredOptimizationResult(x, hist, ok, 15, 1e-5,
                                      site="t.solver")
    assert totals() == before  # nothing read, nothing booked
    for _ in range(3):
        lazy.iterations, lazy.evaluations, lazy.value
    after = totals()
    assert after["solver_iterations"] - before["solver_iterations"] \
        == lazy.iterations
    assert after["solver_evaluations"] - before["solver_evaluations"] \
        == lazy.evaluations == int(np.asarray(hist.evaluations).sum())
    # a single solve executes what it needs
    assert after["solver_lane_evaluations"] \
        - before["solver_lane_evaluations"] == lazy.evaluations
    assert after["solver_hvps"] - before["solver_hvps"] == lazy.hvps > 0
    OptimizationResult.from_history(
        x, hist._replace(evaluations=None, hvps=None), 15, 1e-5,
        site="t.solver")
    assert totals() == after


# --- OWL-QN on rows of uneven length (the ragged ELL layout) -----------------


def _ragged_design(rng, n=9000, d=5000):
    """Rows of 13 to 72 cells of value 1 / sqrt(length) over ``d`` columns,
    a few columns in many rows: enough rows for a layout of several blocks
    of slots."""
    import scipy.sparse as sp

    lens = np.minimum(12 + np.round(np.exp(2.6 + 0.7 * rng.normal(size=n))),
                      72).astype(int)
    p = 1.0 / (np.arange(d) + 10.0)
    p /= p.sum()
    cols = np.concatenate([np.sort(rng.choice(d, size=l, replace=False, p=p))
                           for l in lens])
    vals = np.repeat(1.0 / np.sqrt(lens), lens)
    indptr = np.concatenate([[0], np.cumsum(lens)])
    mat = sp.csr_matrix((vals, cols, indptr), shape=(n, d))
    z = mat @ rng.normal(size=d) + 1.0
    y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float64)
    return mat, y


@pytest.mark.parametrize("regularization,alpha", [("ELASTIC_NET", 0.5),
                                                  ("L1", 1.0)])
def test_owlqn_on_the_ragged_layout_against_the_textbook_and_the_dense_fit(
        rng, regularization, alpha):
    """``train_glm_grid`` (LBFGS + an L1 part -> OWL-QN) on the program's
    layout of a ragged matrix: the objective and the zero set of the
    benchmark reference's textbook OWL-QN after as many iterations, and the
    same fit on the densified matrix."""
    from benchmark.reference import glm_ragged as reference
    from photon_ml_tpu.game.dataset import csr_to_batch
    from photon_ml_tpu.optimize.config import (
        OptimizerType,
        RegularizationContext,
        RegularizationType,
        TaskType,
    )
    from photon_ml_tpu.training import train_glm_grid

    mat, y = _ragged_design(rng)
    n, d = mat.shape
    zeros, ones = np.zeros(n), np.ones(n)
    ell = csr_to_batch(mat, y, zeros, ones, dtype=jnp.float64)
    assert len(ell.blocks) >= 3 and ell.order is not None
    dense = dense_batch(mat.toarray(), y, dtype=jnp.float64)
    lam, iterations = 2.0, 12
    context = RegularizationContext(RegularizationType[regularization],
                                    alpha=alpha)
    fits = [train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, [lam],
                           optimizer_type=OptimizerType.LBFGS,
                           regularization_context=context,
                           max_iterations=iterations, tolerance=1e-30)[0]
            for batch in (ell, dense)]
    on_ell, on_dense = (np.asarray(f.result.coefficients) for f in fits)
    assert fits[0].result.iterations == iterations
    # the same fit on the densified matrix: the same path
    np.testing.assert_allclose(on_ell, on_dense, rtol=1e-6, atol=1e-9)
    np.testing.assert_array_equal(on_ell == 0.0, on_dense == 0.0)
    assert float(fits[0].result.value) == pytest.approx(
        float(fits[1].result.value), rel=1e-10)
    # the textbook on the reference's own evaluations (float32 sums)
    l1, l2 = context.l1_weight(lam), context.l2_weight(lam)
    flat = tuple(jnp.asarray(a) for a in reference.flat_blocks(
        mat.indptr, mat.indices, mat.data, 1000))
    data = (*flat, jnp.asarray(y, jnp.float32), jnp.zeros(n, jnp.float32),
            jnp.ones(n, jnp.float32))

    def fn(w):
        return reference.smooth(*data, w, l2)

    w_ref, values_ref, gnorm_ref = reference.owlqn(fn, l1, np.zeros(d),
                                                   iterations)
    F_at, _, pg_at = reference.penalised(fn, on_ell, l1)
    assert float(fits[0].result.value) == pytest.approx(F_at, rel=1e-6)
    assert float(fits[0].result.grad_norm) == pytest.approx(
        np.linalg.norm(pg_at), rel=1e-3)
    decrease = values_ref[0] - values_ref[-1]
    assert decrease > 0.01 * values_ref[0]
    assert abs(F_at - values_ref[-1]) <= 1e-3 * decrease
    zero, zero_ref = on_ell == 0.0, w_ref == 0.0
    assert 0.2 < zero.mean() < 0.99  # the penalty selects
    assert np.mean(zero != zero_ref) <= 0.01


# --- OWL-QN's history in the layout for a solve under vmap ------------------

def test_vmapped_newest_first_owlqn_is_each_lanes_own_solve(rng):
    """OWL-QN shares L-BFGS's recursion and its two history layouts: under
    ``vmap`` with the newest-first one, every lane runs its own circular
    solve (same iterations, same evaluations, coefficients to 1e-6)."""
    check_vmapped_solve_is_each_lanes_own(rng, minimize_owlqn, l1=0.3)


@pytest.mark.parametrize("newest_first", (False, True),
                         ids=("circular", "newest_first"))
def test_an_owlqn_carry_goes_back_into_its_own_layout(rng, newest_first):
    batch, obj = _problem(rng, l2=0.1)
    x0 = jnp.zeros(8, jnp.float64)
    kw = dict(l1=0.05, tolerance=1e-9, newest_first=newest_first)
    whole, whole_hist, _ = minimize_owlqn(_obj_vg, x0, (obj, batch),
                                          max_iter=12, **kw)
    _, _, _, carry = minimize_owlqn(_obj_vg, x0, (obj, batch), max_iter=4,
                                    return_carry=True, **kw)
    assert (carry.head is None) == newest_first
    resumed, hist, _ = minimize_owlqn(_obj_vg, carry.x, (obj, batch),
                                      max_iter=8, resume=carry, **kw)
    np.testing.assert_array_equal(np.asarray(resumed), np.asarray(whole))
    assert int(hist.num_iterations) + 4 == int(whole_hist.num_iterations)
    with pytest.raises(ValueError, match="history layout"):
        minimize_owlqn(_obj_vg, carry.x, (obj, batch), max_iter=8,
                       resume=carry, l1=0.05, tolerance=1e-9,
                       newest_first=not newest_first)
