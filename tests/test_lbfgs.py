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


# --- the two history layouts (circular, and newest-first under vmap) -------

HISTORY_M, HISTORY_D, HISTORY_LANES = 10, 24, 64

#: name -> (steps, share of steps whose pair is stored, share whose s.y is
#: not positive, so that the solver's own rule ``sy > 1e-10`` skips them)
HISTORY_CASES = {
    "random_heads": (25, 0.7, 0.0),    # most lanes wrap, heads all over
    "partly_valid": (12, 0.3, 0.0),    # 0..m valid slots, some lanes none
    "skipped_store": (14, 1.0, 0.35),  # sy <= 1e-10 leaves the history be
    "more_than_m": (23, 1.0, 0.0),     # 23 stores: the 13 oldest dropped
}


def _histories(rng, case):
    """Both layouts of the same per-lane sequences of pairs, pushed by the
    solvers' own ``push_pair`` under ``vmap``, float32 as on the chip:
    ((S, Y, rho, valid, head) circular, (S, Y, rho, valid, None)
    newest-first, stores [lanes])."""
    from photon_ml_tpu.optimize.lbfgs import empty_history, push_pair

    steps, stored, skipped = HISTORY_CASES[case]
    lanes, m, d = HISTORY_LANES, HISTORY_M, HISTORY_D
    push = jax.vmap(push_pair)

    def lanes_of(history):
        return tuple(None if leaf is None
                     else jnp.broadcast_to(leaf, (lanes,) + leaf.shape)
                     for leaf in history)

    circular = lanes_of(empty_history(m, d, jnp.float32, False))
    newest_first = lanes_of(empty_history(m, d, jnp.float32, True))
    stores = np.zeros(lanes, int)
    for _ in range(steps):
        s = rng.normal(size=(lanes, d)).astype(np.float32)
        # y = A s with A positive definite: s.y > 0, as after a Wolfe step
        y = s * rng.uniform(0.5, 2.0, size=(lanes, d)).astype(np.float32)
        y = np.where(rng.random(lanes)[:, None] < skipped, -y, y)
        sy = jnp.sum(jnp.asarray(s) * jnp.asarray(y), axis=1)
        store = jnp.asarray(rng.random(lanes) < stored) & (sy > 1e-10)
        stores += np.asarray(store)
        circular = push(*circular, jnp.asarray(s), jnp.asarray(y), sy, store)
        newest_first = push(*newest_first, jnp.asarray(s), jnp.asarray(y),
                            sy, store)
    return circular, newest_first, stores


@pytest.mark.parametrize("case", sorted(HISTORY_CASES))
def test_newest_first_history_holds_the_circular_ones_pairs(rng, case):
    """Slot ``i`` of the newest-first history is the circular history's
    slot ``head - 1 - i``, bit for bit: a skipped store leaves both as they
    were, and past ``m`` stores both have dropped the same oldest pair."""
    circular, newest_first, stores = _histories(rng, case)
    S, Y, rho, valid, head = (np.asarray(a) for a in circular)
    assert newest_first[4] is None
    steps, stored, skipped = HISTORY_CASES[case]
    if case in ("random_heads", "more_than_m"):
        assert stores.max() > HISTORY_M  # the oldest pairs have dropped
    if case != "more_than_m":
        assert len(set(stores)) > 3 and stores.min() < steps
    np.testing.assert_array_equal(head, stores % HISTORY_M)
    order = (head[:, None] - 1 - np.arange(HISTORY_M)) % HISTORY_M
    for mine, theirs in zip(newest_first[:4], (S, Y, rho, valid)):
        np.testing.assert_array_equal(
            np.asarray(mine),
            np.take_along_axis(
                theirs, order.reshape(order.shape + (1,) * (theirs.ndim - 2)),
                axis=1))
    np.testing.assert_array_equal(
        np.asarray(newest_first[3]).sum(axis=1),
        np.minimum(stores, HISTORY_M))


@pytest.mark.parametrize("case", sorted(HISTORY_CASES))
def test_newest_first_direction_is_the_circular_one(rng, case):
    """The same pairs in both layouts give the same direction, to within 4
    ulp of its largest element (the compiler fuses the dots differently,
    nothing else differs), lane by lane under ``vmap``."""
    from photon_ml_tpu.optimize.lbfgs import two_loop_direction

    circular, newest_first, stores = _histories(rng, case)
    g = jnp.asarray(rng.normal(size=(HISTORY_LANES, HISTORY_D)), jnp.float32)
    want = np.asarray(jax.vmap(two_loop_direction)(g, *circular))
    got = np.asarray(jax.vmap(
        lambda g, S, Y, rho, valid: two_loop_direction(g, S, Y, rho, valid,
                                                       None)
    )(g, *newest_first[:4]))
    assert got.dtype == np.float32
    ulp = np.spacing(np.abs(want).max(axis=1, keepdims=True))
    assert (np.abs(got - want) <= 4 * ulp).all()
    empty = stores == 0  # no pair yet: steepest descent, exactly
    np.testing.assert_array_equal(got[empty], -np.asarray(g)[empty])
    assert empty.any() == (case == "partly_valid")


def test_newest_first_direction_lowers_without_a_gather():
    """What the layout is for: under ``vmap`` the circular form indexes its
    history with one ``head`` a lane (gathers), the newest-first form with
    the scans' own counter (none), and its push is no scatter."""
    from photon_ml_tpu.optimize.lbfgs import push_pair, two_loop_direction

    lanes, m, d = 7, HISTORY_M, HISTORY_D
    f32 = jnp.float32
    g, S, rho = jnp.zeros((lanes, d), f32), jnp.zeros((lanes, m, d), f32), \
        jnp.zeros((lanes, m), f32)
    valid, head = jnp.zeros((lanes, m), bool), jnp.zeros(lanes, jnp.int32)
    sy, store = jnp.zeros(lanes, f32), jnp.zeros(lanes, bool)

    def lowered(fn, *args):
        return jax.jit(jax.vmap(fn)).lower(*args).as_text()

    circular = lowered(two_loop_direction, g, S, S, rho, valid, head)
    newest = lowered(lambda *a: two_loop_direction(*a, None),
                     g, S, S, rho, valid)
    assert circular.count("stablehlo.gather") > 8
    assert "stablehlo.gather" not in newest
    assert "stablehlo.dynamic_slice" in newest
    pushed = lowered(lambda *a: push_pair(*a[:4], None, *a[4:]),
                     S, S, rho, valid, g, g, sy, store)
    assert "scatter" not in pushed and "gather" not in pushed
    assert "scatter" in lowered(push_pair, S, S, rho, valid, head, g, g, sy,
                                store)


def _lane_blocks(rng, lanes=9, n=64, d=6):
    """Well-conditioned float32 logistic blocks, unequal across lanes."""
    X = rng.normal(size=(lanes, n, d)) * rng.uniform(
        0.5, 1.5, size=(lanes, 1, 1))
    w = rng.normal(size=(lanes, d))
    z = np.einsum("end,ed->en", X, w)
    y = (rng.random((lanes, n)) < 1 / (1 + np.exp(-z))).astype(float)
    return jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32)


def check_vmapped_solve_is_each_lanes_own(rng, minimize, **solver_kw):
    """The newest-first solve under ``vmap`` against every lane's own
    unbatched (circular) solve: the same iterations and evaluations, the
    coefficients within 1e-6 relative. Shared with tests/test_owlqn_tron."""
    X, y = _lane_blocks(rng)
    lanes, n, d = X.shape
    obj = GLMObjective(loss=get_loss("logistic"), l2_lambda=1.0)
    kw = dict(max_iter=40, tolerance=1e-5, **solver_kw)

    def payload(Xe, ye):
        return obj, dense_batch(Xe, ye, dtype=jnp.float32)

    x, hist, ok = jax.vmap(
        lambda Xe, ye: minimize(_obj_vg, jnp.zeros(d, jnp.float32),
                                payload(Xe, ye), newest_first=True, **kw)
    )(X, y)
    iterations = np.asarray(hist.num_iterations)
    assert len(set(iterations.tolist())) > 1 and iterations.max() < 40
    assert iterations.min() > 3  # the history is in use
    for e in range(lanes):
        x_e, hist_e, ok_e = minimize(_obj_vg, jnp.zeros(d, jnp.float32),
                                     payload(X[e], y[e]), **kw)
        k = int(hist_e.num_iterations)
        assert k == iterations[e], e
        np.testing.assert_array_equal(np.asarray(hist.evaluations)[e],
                                      np.asarray(hist_e.evaluations))
        assert bool(ok_e) == bool(ok[e])
        scale = float(jnp.max(jnp.abs(x_e)))
        np.testing.assert_allclose(np.asarray(x[e]), np.asarray(x_e),
                                   rtol=0, atol=1e-6 * scale)
        # written by select under vmap, by ``.at[].set`` alone
        np.testing.assert_allclose(np.asarray(hist.values)[e, :k + 1],
                                   np.asarray(hist_e.values)[:k + 1],
                                   rtol=1e-6)
        assert np.isnan(np.asarray(hist.values)[e, k + 1:]).all()


def _obj_line(w, d, payload):
    obj, batch = payload
    return obj.line(w, d, batch)


def test_vmapped_newest_first_solve_is_each_lanes_own_solve(rng):
    """The per-entity L-BFGS form (newest-first history, trials on carried
    margins) under ``vmap`` against every lane's own solve in the same
    form: the same iterations, the same full evaluations and trials in
    every iteration, the same verdict, the coefficients and values within
    1e-6 relative. (Not bit for bit: XLA:CPU emits the gradient's
    ``nd,n->d`` sum one way for a block and another for a batch of them,
    1 ulp apart in either form of the search.)"""
    X, y = _lane_blocks(rng)
    lanes, n, d = X.shape
    obj = GLMObjective(loss=get_loss("logistic"), l2_lambda=1.0)
    kw = dict(max_iter=40, tolerance=1e-5, newest_first=True,
              line_fn=_obj_line)

    def solve(Xe, ye):
        return minimize_lbfgs(_obj_vg, jnp.zeros(d, jnp.float32),
                              (obj, dense_batch(Xe, ye, dtype=jnp.float32)),
                              **kw)

    x, hist, ok = jax.vmap(solve)(X, y)
    iterations = np.asarray(hist.num_iterations)
    assert len(set(iterations.tolist())) > 1 and iterations.max() < 40
    assert iterations.min() > 3  # the history is in use
    for e in range(lanes):
        x_e, hist_e, ok_e = solve(X[e], y[e])
        k = int(hist_e.num_iterations)
        assert k == iterations[e], e
        assert bool(ok_e) == bool(ok[e])
        # the start and one full evaluation an iteration; the trials apart
        np.testing.assert_array_equal(np.asarray(hist.evaluations)[e],
                                      [1] * (k + 1) + [0] * (40 - k))
        np.testing.assert_array_equal(np.asarray(hist.evaluations)[e],
                                      np.asarray(hist_e.evaluations))
        np.testing.assert_array_equal(np.asarray(hist.line_trials)[e],
                                      np.asarray(hist_e.line_trials))
        assert int(np.sum(hist_e.line_trials)) >= k
        scale = float(jnp.max(jnp.abs(x_e)))
        np.testing.assert_allclose(np.asarray(x[e]), np.asarray(x_e),
                                   rtol=0, atol=1e-6 * scale)
        np.testing.assert_allclose(np.asarray(hist.values)[e, :k + 1],
                                   np.asarray(hist_e.values)[:k + 1],
                                   rtol=1e-6)


def _line_problem(loss, rng, n=96, d=5):
    """A float32 batch with offsets, weights and a normalization that
    both shifts and scales, for ``loss``, and a point and a direction."""
    from photon_ml_tpu.ops.normalization import NormalizationContext

    X = rng.normal(size=(n, d)) * rng.uniform(0.5, 3.0, size=d) + 0.7
    z = X @ rng.normal(size=d) * 0.3
    y = {"logistic": (rng.random(n) < 1 / (1 + np.exp(-z))).astype(float),
         "squared": z + rng.normal(size=n),
         "poisson": rng.poisson(np.exp(z))}[loss]
    batch = dense_batch(X, y, offsets=rng.normal(size=n) * 0.2,
                        weights=rng.uniform(0.2, 3.0, size=n),
                        dtype=jnp.float32)
    norm = NormalizationContext(
        factors=jnp.asarray(rng.uniform(0.3, 2.0, size=d), jnp.float32),
        shifts=jnp.asarray(rng.normal(size=d), jnp.float32))
    obj = GLMObjective(get_loss(loss), norm=norm, l2_lambda=0.7)
    x = jnp.asarray(rng.normal(size=d) * 0.3, jnp.float32)
    direction = jnp.asarray(rng.normal(size=d) * 0.3, jnp.float32)
    return obj, batch, x, direction


@pytest.mark.parametrize("loss", ["logistic", "squared", "poisson"])
def test_the_line_from_carried_margins_is_the_full_evaluation(rng, loss):
    """``GLMObjective.line``'s ``phi(a)`` and its slope against the full
    evaluation at ``x + a d``: its value and ``g . d``, to float32 rounding,
    through offsets, weights and a normalization's shifts and factors."""
    obj, batch, x, direction = _line_problem(loss, rng)
    phi = jax.jit(lambda a: obj.line(x, direction, batch)(a))
    full = jax.jit(lambda a: obj.calculate(x + a * direction, batch))
    for a in rng.uniform(-2.0, 2.0, size=6).astype(np.float32):
        value, slope = phi(a)
        f, g = full(a)
        assert value.dtype == slope.dtype == jnp.float32
        np.testing.assert_allclose(float(value), float(f), rtol=2e-6)
        # the slope's rounding is that of its terms, not of their sum
        scale = float(jnp.linalg.norm(g) * jnp.linalg.norm(direction))
        assert abs(float(slope) - float(jnp.dot(g, direction))) \
            <= 2e-6 * scale


@pytest.mark.parametrize("batched", (False, True),
                         ids=("unbatched", "vmapped"))
@pytest.mark.parametrize("loss", ["logistic", "squared"])
def test_a_two_pass_dense_start_in_product_sums_is_the_two_pass_evaluation(
        rng, as_on_one_tpu, loss, batched):
    """A dense batch that the fused kernel's gate sends to the two-pass form
    on a TPU starts in float32 products and sums (``product_sum``, booked
    once a trace): at a warm point its value and gradient are
    ``value_and_gradient``'s two-pass einsums to float32 rounding, through
    offsets, weights and a normalization's shifts and factors, for one
    solve and for lanes under ``vmap``."""
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.optimize.lbfgs import start_evaluation

    problems = [_line_problem(loss, rng) for _ in range(3 if batched else 1)]
    obj = problems[0][0]

    def start(x, batch):
        return start_evaluation(_obj_vg, x, (obj, batch))

    def two_pass(x, batch):
        return obj.calculate(x, batch)

    if batched:
        x = jnp.stack([p[2] for p in problems])
        batch = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                             *[p[1] for p in problems])
        start, two_pass = jax.vmap(start), jax.vmap(two_pass)
    else:
        x, batch = problems[0][2], problems[0][1]
    booked = REGISTRY.counter("solver_start_lowerings")
    before = booked.value(site="optimizer.lbfgs", form="product_sum")
    f, g = jax.jit(start)(x, batch)
    assert booked.value(site="optimizer.lbfgs",
                        form="product_sum") == before + 1
    f_ref, g_ref = jax.jit(two_pass)(x, batch)
    assert f.dtype == g.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(f), np.asarray(f_ref), rtol=2e-6)
    scale = float(jnp.max(jnp.abs(g_ref)))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=0,
                               atol=2e-6 * scale)


# (backend, devices, rows, columns or "ell", objective axis) -> start form
START_FORMS = {
    "cpu-narrow": ("cpu", 1, 10_000_054, 65, None, "direct"),
    "tpu-narrow": ("tpu", 1, 10_000_054, 65, None, "product_sum"),
    "tpu-small": ("tpu", 1, 512, 2048, None, "product_sum"),
    "tpu-wide": ("tpu", 1, 262_144, 2048, None, "direct"),
    "tpu-wide-four-chips": ("tpu", 4, 262_144, 2048, None, "product_sum"),
    "tpu-wide-shard-map": ("tpu", 4, 262_144, 2048, "data", "direct"),
    "tpu-row-sparse": ("tpu", 1, 1024, "ell", None, "in_loop"),
}


@pytest.mark.parametrize("case", sorted(START_FORMS))
def test_the_start_form_follows_the_layout_and_the_kernels_gate(
        monkeypatch, case):
    """``start_form`` reads the payload alone: ``in_loop`` for a row-sparse
    batch; for a dense one ``direct`` where the fused kernel's gate takes
    the pass (its width and size rule, and off ``shard_map`` one device:
    an objective's ``axis_name`` says it runs under one) and everywhere off
    a TPU, else ``product_sum``."""
    import dataclasses

    from photon_ml_tpu.data.batch import DenseBatch, EllBatch
    from photon_ml_tpu.optimize.lbfgs import start_form

    backend, devices, n, d, axis, form = START_FORMS[case]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    if d == "ell":
        batch = EllBatch(sds(39, n, dtype=jnp.int32), sds(39, n), sds(n),
                         sds(n), sds(n), dim=1_000_000)
    else:
        batch = DenseBatch(sds(n, d), sds(n), sds(n), sds(n))
    obj = dataclasses.replace(
        GLMObjective(loss=get_loss("logistic"), l2_lambda=1.0),
        axis_name=axis)
    assert start_form((obj, batch)) == form
    assert start_form(None) == "direct"


@pytest.mark.parametrize("newest_first", (False, True),
                         ids=("circular", "newest_first"))
def test_a_carry_goes_back_into_its_own_layout(rng, newest_first):
    """A solve stopped after 3 iterations and resumed is the uninterrupted
    solve in either layout, and a carry is refused by the other layout."""
    _, _, batch, obj = _logistic_fit_problem(rng)
    x0 = jnp.zeros(6, jnp.float64)
    kw = dict(tolerance=1e-9, newest_first=newest_first)
    whole, whole_hist, _ = minimize_lbfgs(_obj_vg, x0, (obj, batch),
                                          max_iter=12, **kw)
    _, _, _, carry = minimize_lbfgs(_obj_vg, x0, (obj, batch), max_iter=3,
                                    return_carry=True, **kw)
    assert (carry.head is None) == newest_first
    resumed, hist, _ = minimize_lbfgs(_obj_vg, carry.x, (obj, batch),
                                      max_iter=9, resume=carry, **kw)
    np.testing.assert_array_equal(np.asarray(resumed), np.asarray(whole))
    assert int(hist.num_iterations) + 3 == int(whole_hist.num_iterations)
    with pytest.raises(ValueError, match="history layout"):
        minimize_lbfgs(_obj_vg, carry.x, (obj, batch), max_iter=9,
                       resume=carry, tolerance=1e-9,
                       newest_first=not newest_first)
