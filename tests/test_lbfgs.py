"""L-BFGS solver behavior: convergence, constraints, reasons, cache reuse.

Mirrors the reference's optimizer unit tier (test/.../optimization/LBFGSTest
vs TestObjective — a known convex function) plus TPU-specific contracts:
one compiled kernel across batches, EllBatch across the jit boundary.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.optimize

from photon_ml_tpu.data.batch import dense_batch, ell_from_rows
from photon_ml_tpu.ops.aggregators import GLMObjective
from photon_ml_tpu.ops.losses import get_loss
from photon_ml_tpu.optimize.common import (
    BoxConstraints,
    ConvergenceReason,
    OptimizationResult,
)
from photon_ml_tpu.optimize.lbfgs import minimize_lbfgs


def _quadratic(x, data):
    """TestObjective analog: f = sum (x - center)^2 with minimum at center."""
    center = data
    g = 2.0 * (x - center)
    return jnp.sum((x - center) ** 2), g


def test_converges_on_known_convex_function():
    center = jnp.asarray([1.0, -2.0, 3.0, 0.5], jnp.float64)
    x, hist, ok = minimize_lbfgs(_quadratic, jnp.zeros(4, jnp.float64), center)
    np.testing.assert_allclose(np.asarray(x), np.asarray(center), atol=1e-8)
    res = OptimizationResult.from_history(x, hist, 100, 1e-7, bool(ok))
    assert res.convergence_reason in (ConvergenceReason.FUNCTION_VALUES_CONVERGED,
                                      ConvergenceReason.GRADIENT_CONVERGED)
    assert res.iterations <= 3
    # every iteration evaluates at least once, beside the start
    assert res.evaluations >= res.iterations + 1
    assert res.evaluations == int(np.asarray(hist.evaluations).sum())


def test_start_at_optimum_reports_gradient_converged():
    center = jnp.asarray([1.0, -2.0], jnp.float64)
    x, hist, ok = minimize_lbfgs(_quadratic, center, center)
    assert int(hist.num_iterations) == 0
    assert bool(ok)
    res = OptimizationResult.from_history(x, hist, 100, 1e-7, bool(ok))
    assert res.convergence_reason == ConvergenceReason.GRADIENT_CONVERGED
    np.testing.assert_allclose(np.asarray(x), np.asarray(center))
    # the start is the one evaluation a solve that never iterates makes
    assert res.evaluations == 1 and res.hvps is None
    assert list(np.asarray(hist.evaluations)[:3]) == [1, 0, 0]


def _logistic_fit_problem(rng, n=300, d=6, l2=0.5):
    X = rng.normal(size=(n, d))
    X[:, -1] = 1.0
    w_true = rng.normal(size=d)
    y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w_true)))).astype(float)
    batch = dense_batch(X, y, dtype=jnp.float64)
    obj = GLMObjective(get_loss("logistic"), l2_lambda=l2)
    return X, y, batch, obj


def _obj_vg(w, payload):
    obj, batch = payload
    return obj.calculate(w, batch)


def test_matches_scipy_lbfgsb_on_logistic(rng):
    X, y, batch, obj = _logistic_fit_problem(rng)
    x, hist, ok = minimize_lbfgs(_obj_vg, jnp.zeros(6, jnp.float64),
                                 (obj, batch), tolerance=1e-10)

    def f_np(w):
        v, g = obj.calculate(jnp.asarray(w), batch)
        return float(v), np.asarray(g)

    ref = scipy.optimize.minimize(f_np, np.zeros(6), jac=True, method="L-BFGS-B",
                                  options={"ftol": 1e-14, "gtol": 1e-12})
    np.testing.assert_allclose(np.asarray(x), ref.x, atol=2e-5)
    assert float(hist.values[int(hist.num_iterations)]) <= ref.fun + 1e-8


def test_box_constraints_respected(rng):
    X, y, batch, obj = _logistic_fit_problem(rng)
    box = BoxConstraints.from_map(6, {0: (-0.1, 0.1), 2: (0.0, jnp.inf)})
    x, _, _ = minimize_lbfgs(_obj_vg, jnp.zeros(6, jnp.float64), (obj, batch),
                             box=box)
    xa = np.asarray(x)
    assert -0.1 - 1e-9 <= xa[0] <= 0.1 + 1e-9
    assert xa[2] >= -1e-9


def test_one_compiled_kernel_across_batches(rng):
    """Same function object + same shapes => no retrace on the second batch
    (the GAME per-entity workload contract)."""
    _, _, batch1, obj = _logistic_fit_problem(rng)
    _, _, batch2, _ = _logistic_fit_problem(rng)

    with jax.log_compiles(False):
        x1, _, _ = minimize_lbfgs(_obj_vg, jnp.zeros(6, jnp.float64), (obj, batch1))
        before = minimize_lbfgs.__wrapped__._cache_size() if hasattr(
            minimize_lbfgs, "__wrapped__") else None

    from photon_ml_tpu.optimize import lbfgs as lbfgs_mod
    n_before = lbfgs_mod._minimize_lbfgs_impl._cache_size()
    x2, _, _ = minimize_lbfgs(_obj_vg, jnp.zeros(6, jnp.float64), (obj, batch2))
    n_after = lbfgs_mod._minimize_lbfgs_impl._cache_size()
    assert n_after == n_before, "second same-shape batch must not recompile"
    assert not np.allclose(np.asarray(x1), np.asarray(x2))


def test_ell_batch_solves_under_jit(rng):
    """EllBatch must cross the jit boundary (dim is static aux data)."""
    n, d = 60, 9
    X = rng.normal(size=(n, d)) * (rng.random((n, d)) > 0.5)
    X[:, -1] = 1.0
    y = (rng.random(n) > 0.5).astype(float)
    rows = []
    for i in range(n):
        (ix,) = np.nonzero(X[i])
        rows.append((ix.astype(np.int32), X[i, ix]))
    ell = ell_from_rows(rows, d, y)
    ell = ell._replace(values=ell.values.astype(jnp.float64))
    dense = dense_batch(X, y, dtype=jnp.float64)
    obj = GLMObjective(get_loss("logistic"), l2_lambda=0.3)

    x_e, _, _ = minimize_lbfgs(_obj_vg, jnp.zeros(d, jnp.float64), (obj, ell))
    x_d, _, _ = minimize_lbfgs(_obj_vg, jnp.zeros(d, jnp.float64), (obj, dense))
    np.testing.assert_allclose(np.asarray(x_e), np.asarray(x_d), atol=1e-6)


def test_history_trajectory_is_monotone_decreasing(rng):
    _, _, batch, obj = _logistic_fit_problem(rng)
    _, hist, _ = minimize_lbfgs(_obj_vg, jnp.zeros(6, jnp.float64), (obj, batch))
    k = int(hist.num_iterations)
    vals = np.asarray(hist.values)[: k + 1]
    assert np.all(np.isfinite(vals))
    assert np.all(np.diff(vals) <= 1e-12), "objective must not increase"
    assert np.all(np.isnan(np.asarray(hist.values)[k + 1:]))


def test_track_iterates_records_trajectory(rng):
    """track_iterates records x_0..x_k (ModelTracker.models analog); the
    last snapshot equals the returned optimum, and re-evaluating the
    recorded values matches the history."""
    import numpy as np

    from photon_ml_tpu.optimize.lbfgs import minimize_lbfgs
    from photon_ml_tpu.optimize.owlqn import minimize_owlqn
    from photon_ml_tpu.optimize.tron import minimize_tron

    d = 5
    A = jnp.asarray(np.diag(rng.uniform(1.0, 4.0, size=d)))
    b = jnp.asarray(rng.normal(size=d))

    def vg(x, _):
        r = A @ x - b
        return 0.5 * jnp.dot(r, A @ x - b), A.T @ r

    def hvp(x, v, _):
        return A.T @ (A @ v)

    x0 = jnp.zeros(d)
    l1 = 0.01
    for name, run in [
        ("lbfgs", lambda: minimize_lbfgs(vg, x0, None, max_iter=20,
                                         track_iterates=True)),
        ("owlqn", lambda: minimize_owlqn(vg, x0, None, l1=l1, max_iter=20,
                                         track_iterates=True)),
        ("tron", lambda: minimize_tron(vg, hvp, x0, None, max_iter=20,
                                       track_iterates=True)),
    ]:
        x, hist, _ = run()
        k = int(hist.num_iterations)
        assert hist.iterates is not None, name
        its = np.asarray(hist.iterates)
        np.testing.assert_allclose(its[0], np.zeros(d), err_msg=name)
        np.testing.assert_allclose(its[k], np.asarray(x), rtol=1e-6,
                                   err_msg=name)
        # values in the history correspond to the recorded iterates
        # (OWL-QN tracks the FULL objective f + l1 |x|)
        for i in (0, k):
            v, _ = vg(jnp.asarray(its[i]), None)
            v = float(v)
            if name == "owlqn":
                v += l1 * float(np.abs(its[i]).sum())
            assert v == pytest.approx(
                float(np.asarray(hist.values)[i]), rel=1e-5, abs=1e-8), \
                (name, i)

    # default: no iterates recorded
    _, hist, _ = minimize_lbfgs(vg, x0, None, max_iter=5)
    assert hist.iterates is None
