"""The strong-Wolfe line search against the two-stage form it replaced.

``_oracle_strong_wolfe`` is ``strong_wolfe`` as it stood before it was
rewritten to evaluate once a pass (a ``lax.switch`` on the stage, an
evaluation inside each stage's branch, one more before the loop), kept here
verbatim as the oracle: every trial step and every decision of the
single-evaluation form must be the one that form made, batched or not. The
second half checks the property the rewrite is for: under ``jax.vmap`` the
objective runs max-over-lanes(``num_evals``) times a search, once a pass.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from photon_ml_tpu.optimize.linesearch import (
    C1,
    C2,
    MAX_LS_ITER,
    LineSearchResult,
    strong_wolfe,
)

Array = jnp.ndarray
_BRACKET, _ZOOM, _DONE, _FAIL = 0, 1, 2, 3


# --- the oracle: the two-stage form, verbatim ------------------------------

class _OracleState(NamedTuple):
    stage: Array
    it: Array
    # current trial
    a: Array
    phi_a: Array
    dphi_a: Array
    g_a: Array
    # previous trial (bracketing) / zoom interval lo and hi
    a_lo: Array
    phi_lo: Array
    dphi_lo: Array
    g_lo: Array
    a_hi: Array
    phi_hi: Array
    dphi_hi: Array


def _oracle_cubic_min(a, fa, dfa, b, fb, dfb):
    """Minimizer of the cubic interpolating (a,fa,dfa),(b,fb,dfb).

    Falls back to bisection when the cubic is degenerate (N&W eq. 3.59).
    """
    d1 = dfa + dfb - 3.0 * (fa - fb) / (a - b)
    disc = d1 * d1 - dfa * dfb
    sqrt_disc = jnp.sqrt(jnp.maximum(disc, 0.0))
    d2 = jnp.sign(b - a) * sqrt_disc
    denom = dfb - dfa + 2.0 * d2
    cand = b - (b - a) * (dfb + d2 - d1) / denom
    mid = 0.5 * (a + b)
    lo, hi = jnp.minimum(a, b), jnp.maximum(a, b)
    # Guard: inside the interval, not too close to the ends, finite.
    width = hi - lo
    good = (
        (disc >= 0.0)
        & jnp.isfinite(cand)
        & (cand > lo + 0.1 * width)
        & (cand < hi - 0.1 * width)
    )
    return jnp.where(good, cand, mid)


def _oracle_strong_wolfe(value_and_grad_1d, phi0, dphi0, g0, init_alpha=1.0,
                         max_alpha=1e10):
    """The two-stage form, with its final state beside the result."""
    dtype = phi0.dtype

    def evaluate(a):
        phi, dphi, g = value_and_grad_1d(a)
        return phi, dphi, g

    def bracket_step(s: _OracleState) -> _OracleState:
        armijo_fail = (s.phi_a > phi0 + C1 * s.a * dphi0) | (
            (s.it > 0) & (s.phi_a >= s.phi_lo)
        )
        curv_ok = jnp.abs(s.dphi_a) <= -C2 * dphi0
        pos_slope = s.dphi_a >= 0.0

        # -> ZOOM with (lo=prev, hi=cur) when Armijo fails; accept when both
        # Wolfe hold; -> ZOOM with (lo=cur, hi=prev) on positive slope;
        # otherwise expand.
        def to_zoom_prev_cur(s):
            return s._replace(stage=jnp.int32(_ZOOM), a_hi=s.a,
                              phi_hi=s.phi_a, dphi_hi=s.dphi_a)

        def accept(s):
            return s._replace(stage=jnp.int32(_DONE))

        def to_zoom_cur_prev(s):
            return s._replace(stage=jnp.int32(_ZOOM), a_lo=s.a, phi_lo=s.phi_a,
                              dphi_lo=s.dphi_a, g_lo=s.g_a, a_hi=s.a_lo,
                              phi_hi=s.phi_lo, dphi_hi=s.dphi_lo)

        def expand(s):
            new_a = jnp.minimum(2.0 * s.a, jnp.asarray(max_alpha, dtype))
            phi, dphi, g = evaluate(new_a)
            return s._replace(
                a_lo=s.a, phi_lo=s.phi_a, dphi_lo=s.dphi_a, g_lo=s.g_a,
                a=new_a, phi_a=phi, dphi_a=dphi, g_a=g,
                it=s.it + 1,
            )

        branch = jnp.where(
            armijo_fail, 0, jnp.where(curv_ok, 1, jnp.where(pos_slope, 2, 3))
        )
        return lax.switch(branch, [to_zoom_prev_cur, accept, to_zoom_cur_prev,
                                   expand], s)

    def zoom_step(s: _OracleState) -> _OracleState:
        a_j = _oracle_cubic_min(s.a_lo, s.phi_lo, s.dphi_lo, s.a_hi, s.phi_hi, s.dphi_hi)
        phi, dphi, g = evaluate(a_j)
        s = s._replace(a=a_j, phi_a=phi, dphi_a=dphi, g_a=g, it=s.it + 1)

        armijo_fail = (phi > phi0 + C1 * a_j * dphi0) | (phi >= s.phi_lo)

        def shrink_hi(s):
            return s._replace(a_hi=s.a, phi_hi=s.phi_a, dphi_hi=s.dphi_a)

        def check_curvature(s):
            curv_ok = jnp.abs(s.dphi_a) <= -C2 * dphi0

            def accept(s):
                return s._replace(stage=jnp.int32(_DONE))

            def move_lo(s):
                flip = s.dphi_a * (s.a_hi - s.a_lo) >= 0.0
                s = lax.cond(
                    flip,
                    lambda s: s._replace(a_hi=s.a_lo, phi_hi=s.phi_lo,
                                         dphi_hi=s.dphi_lo),
                    lambda s: s,
                    s,
                )
                return s._replace(a_lo=s.a, phi_lo=s.phi_a, dphi_lo=s.dphi_a,
                                  g_lo=s.g_a)

            return lax.cond(curv_ok, accept, move_lo, s)

        return lax.cond(armijo_fail, shrink_hi, check_curvature, s)

    def body(s: _OracleState) -> _OracleState:
        s = lax.switch(s.stage, [bracket_step, zoom_step,
                                 lambda s: s, lambda s: s], s)
        # Give up when the eval budget is exhausted or the zoom interval
        # collapsed; keep the best sufficient-decrease point seen (a_lo).
        exhausted = (s.it >= MAX_LS_ITER) & (s.stage < _DONE)
        interval_dead = (s.stage == _ZOOM) & (
            jnp.abs(s.a_hi - s.a_lo) <= 1e-14 * jnp.maximum(1.0, jnp.abs(s.a_hi))
        )
        return lax.cond(
            exhausted | interval_dead,
            lambda s: s._replace(stage=jnp.int32(_FAIL)),
            lambda s: s,
            s,
        )

    def cond(s: _OracleState) -> Array:
        return s.stage < _DONE

    a0 = jnp.asarray(init_alpha, dtype)
    phi_i, dphi_i, g_i = evaluate(a0)
    init = _OracleState(
        stage=jnp.int32(_BRACKET),
        it=jnp.int32(1),
        a=a0, phi_a=phi_i, dphi_a=dphi_i, g_a=g_i,
        a_lo=jnp.zeros((), dtype), phi_lo=phi0, dphi_lo=dphi0, g_lo=g0,
        a_hi=jnp.zeros((), dtype), phi_hi=phi0, dphi_hi=dphi0,
    )
    final = lax.while_loop(cond, body, init)

    accepted = final.stage == _DONE
    # On failure fall back to the best point holding sufficient decrease
    # (a_lo; may be 0 => no progress, caller decides what to do).
    fallback_ok = final.phi_lo < phi0
    alpha = jnp.where(accepted, final.a, jnp.where(fallback_ok, final.a_lo, 0.0))
    value = jnp.where(accepted, final.phi_a,
                      jnp.where(fallback_ok, final.phi_lo, phi0))
    grad = jnp.where(accepted, final.g_a,
                     jnp.where(fallback_ok, final.g_lo, g0))
    return LineSearchResult(
        alpha=alpha,
        value=value,
        grad=grad,
        ok=accepted | fallback_ok,
        num_evals=final.it,
    ), final


# --- the battery: families of 1-D restrictions, one parameter each ---------

def _on_the_host(make):
    """phi(a) = f(x - a p g(x)), the parameter being the direction's scale,
    evaluated in numpy behind a callback: the objective then rounds the same
    way wherever the search calls it, batched or not (XLA fuses each call
    site of a traced objective by itself), and every difference left is
    the line search's own."""

    def family(dtype, p):
        f_and_g, x = make(dtype)
        f0, g0 = f_and_g(x)

        def host_phi(a, p):
            d = -g0 * p
            f, g = f_and_g(x + a * d)
            return f, np.vdot(g, d).astype(dtype), g

        shapes = (jax.ShapeDtypeStruct((), dtype),) * 2 + (
            jax.ShapeDtypeStruct(x.shape, dtype),)

        def phi(a):
            return jax.pure_callback(host_phi, shapes, a, p,
                                     vmap_method="sequential")

        return phi, jnp.asarray(f0), -p * jnp.asarray(np.vdot(g0, g0)), \
            jnp.asarray(g0)

    return family


def _logistic(dtype):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(200, 6)) * np.logspace(0, 1, 6)
    y = (rng.random(200) < 1 / (1 + np.exp(-X @ rng.normal(size=6))))
    X, y = X.astype(dtype), y.astype(dtype)

    def f_and_g(w):
        z = X @ w
        f = np.sum(np.logaddexp(0, z) - y * z) + 0.5 * np.sum(w * w)
        return f.astype(dtype), X.T @ (np.exp(-np.logaddexp(0, -z)) - y) + w

    return f_and_g, np.zeros(6, dtype)


def _quadratic(dtype):
    A = np.logspace(0, 2, 6).astype(dtype)
    return (lambda w: (0.5 * np.sum(A * w * w), A * w)), np.ones(6, dtype)


def _parabola(dtype, p):
    """(p a - 1)^2 / 2: the first trial lands p times as far as the
    minimizer."""
    none = jnp.zeros(1, dtype)
    half = jnp.asarray(0.5, dtype)
    return (lambda a: (half * (p * a - 1) ** 2, p * (p * a - 1), none),
            half, -p, none)


def _kink(dtype, p):
    """|a - p| - p: the slope jumps from -1 to +1 at p, so away from p itself
    the curvature condition never holds and the zoom runs until it gives up."""
    none = jnp.zeros(1, dtype)
    one = jnp.ones((), dtype)
    return (lambda a: (jnp.abs(a - p) - p, jnp.where(a < p, -one, one), none),
            jnp.zeros((), dtype), -one, none)


def _line(dtype, p):
    """-p a, unbounded below: the bracketing never ends by itself."""
    none = jnp.zeros(1, dtype)
    return (lambda a: (-p * a, -p, none), jnp.zeros((), dtype), -p, none)


def _flat(dtype, p):
    """phi(a) == phi(0) = p whatever a: float rounding at an optimum."""
    none = jnp.zeros(1, dtype)
    slope = jnp.asarray(-1e-9, dtype)
    return (lambda a: (p + 0.0 * a, slope, none), p, slope, none)


FAMILIES = {
    "logistic": (_on_the_host(_logistic), {}),
    "quadratic": (_on_the_host(_quadratic), {}),
    "parabola": (_parabola, {}),
    "kink": (_kink, {}),
    "line": (_line, {}),
    "line-capped": (_line, {"max_alpha": 4.0}),
    "flat": (_flat, {}),
}

EXITS = ("accept_at_once", "expand", "zoom_from_armijo",
         "zoom_from_positive_slope", "exhausted", "collapsed_interval")

# (family, dtype, parameter, the exit the two-stage form takes there)
CASES = [
    ("logistic", "float32", 1e-4, "accept_at_once"),
    ("logistic", "float32", 1e-7, "expand"),
    ("logistic", "float32", 1e-2, "zoom_from_armijo"),
    ("logistic", "float32", 1.0, "zoom_from_armijo"),
    ("logistic", "float32", 1.95, "zoom_from_armijo"),
    ("logistic", "float32", 30.0, "zoom_from_armijo"),
    ("logistic", "float64", 1e-7, "expand"),
    ("logistic", "float64", 30.0, "zoom_from_armijo"),
    ("quadratic", "float32", 1e-2, "accept_at_once"),
    ("quadratic", "float32", 1e-4, "expand"),
    ("quadratic", "float32", 1e-7, "expand"),
    ("parabola", "float32", 1.95, "zoom_from_positive_slope"),
    ("parabola", "float32", 1.0, "accept_at_once"),
    ("parabola", "float32", 30.0, "zoom_from_armijo"),
    ("parabola", "float64", 1.95, "zoom_from_positive_slope"),
    ("parabola", "float64", 1e-3, "expand"),
    ("quadratic", "float32", 3.0, "zoom_from_armijo"),
    ("quadratic", "float32", 30.0, "zoom_from_armijo"),
    ("quadratic", "float64", 30.0, "zoom_from_armijo"),
    ("kink", "float32", 3.0, "collapsed_interval"),
    ("kink", "float32", 0.3, "collapsed_interval"),
    ("kink", "float32", 1e-3, "exhausted"),
    ("kink", "float64", 0.3, "exhausted"),
    ("line", "float32", 1.0, "exhausted"),
    ("line", "float64", 0.5, "exhausted"),
    ("line-capped", "float32", 1.0, "collapsed_interval"),
    ("line-capped", "float64", 2.0, "collapsed_interval"),
    ("flat", "float32", 5.0, "exhausted"),
    ("flat", "float64", 5.0, "exhausted"),
]


def _lanes(family, dtype):
    return [p for f, dt, p, _ in CASES if (f, dt) == (family, dtype)]


def _search(family, dtype, which):
    make, kw = FAMILIES[family]

    def search(p):
        phi, phi0, dphi0, g0 = make(jnp.dtype(dtype), p)
        if which == "oracle":
            return _oracle_strong_wolfe(phi, phi0, dphi0, g0, **kw)
        return strong_wolfe(phi, phi0, dphi0, g0, **kw)

    return search


@functools.lru_cache(maxsize=None)
def _solo(family, dtype, p, which):
    out = jax.jit(_search(family, dtype, which))(jnp.asarray(p, dtype))
    return jax.tree_util.tree_map(np.asarray, out)


@functools.lru_cache(maxsize=None)
def _batched(family, dtype, which):
    lanes = jnp.asarray(_lanes(family, dtype), dtype)
    out = jax.jit(jax.vmap(_search(family, dtype, which)))(lanes)
    return jax.tree_util.tree_map(np.asarray, out)


def _lane(tree, lane):
    return jax.tree_util.tree_map(lambda v: v[lane], tree)


def _exit_of(family, dtype, p):
    """Which way the two-stage form left, from its final state and its
    own tests on the first trial."""
    _, final = _solo(family, dtype, p, "oracle")
    if final.stage == _FAIL:
        return "exhausted" if final.it >= MAX_LS_ITER \
            else "collapsed_interval"
    assert final.stage == _DONE
    if final.it == 1:
        return "accept_at_once"
    make, _ = FAMILIES[family]
    phi, phi0, dphi0, _ = make(jnp.dtype(dtype), jnp.asarray(p, dtype))
    a0 = jnp.ones((), dtype)
    phi1, dphi1, _ = phi(a0)
    if (phi1 > phi0 + C1 * a0 * dphi0) | (phi1 >= phi0):
        return "zoom_from_armijo"
    assert jnp.abs(dphi1) > -C2 * dphi0
    return "zoom_from_positive_slope" if dphi1 >= 0.0 else "expand"


def _ulps_apart(got, want):
    """Representable numbers between two floats of one dtype and sign."""
    assert got.dtype == want.dtype
    bits = {4: np.int32, 8: np.int64}[got.dtype.itemsize]
    return abs(int(np.asarray(got).view(bits))
               - int(np.asarray(want).view(bits)))


def _assert_same_search(got: LineSearchResult, want: LineSearchResult):
    assert int(got.num_evals) == int(want.num_evals)
    assert bool(got.ok) == bool(want.ok)
    assert _ulps_apart(got.alpha, want.alpha) <= 4, (got.alpha, want.alpha)
    assert _ulps_apart(got.value, want.value) <= 4, (got.value, want.value)
    scale = max(1.0, float(np.max(np.abs(want.grad))))
    np.testing.assert_allclose(got.grad, want.grad, rtol=0, atol=1e-5 * scale)


def test_the_battery_takes_every_exit():
    assert {c[3] for c in CASES} == set(EXITS)


@pytest.mark.parametrize(
    "family,dtype,p,exit_", CASES,
    ids=[f"{f}-{dt}-{p:g}-{e}" for f, dt, p, e in CASES])
def test_one_evaluation_a_pass_makes_the_two_stage_forms_search(
        family, dtype, p, exit_):
    assert _exit_of(family, dtype, p) == exit_
    want, final = _solo(family, dtype, p, "oracle")
    _assert_same_search(_solo(family, dtype, p, "new"), want)
    # under vmap against the oracle under vmap: batching re-orders the
    # objective's own sums, so a lane is held to the lane beside it
    lane = _lanes(family, dtype).index(p)
    _assert_same_search(_lane(_batched(family, dtype, "new"), lane),
                        _lane(_batched(family, dtype, "oracle")[0], lane))
    if exit_ in ("exhausted", "collapsed_interval"):
        # a failed search falls back to the best point that held sufficient
        # decrease, a_lo, or to no step at all
        got = _solo(family, dtype, p, "new")
        make, _ = FAMILIES[family]
        phi0 = np.asarray(make(jnp.dtype(dtype), jnp.asarray(p, dtype))[1])
        held = final.phi_lo < phi0
        assert got.alpha == (final.a_lo if held else 0.0)
        assert got.value == (final.phi_lo if held else phi0)
        assert bool(got.ok) == bool(held)
        assert (int(got.num_evals) == MAX_LS_ITER) == (exit_ == "exhausted")


# --- what the single-evaluation form is for ---------------------------------

_PASSES = {"ran": 0}


def _count_a_pass():
    _PASSES["ran"] += 1


def _counted_search(p, search=strong_wolfe):
    """A logistic search whose objective bumps a host counter every time
    it really runs: once per execution of the site, however many lanes
    that execution carries."""
    phi, phi0, dphi0, g0 = FAMILIES["logistic"][0](jnp.dtype("float32"), p)

    def counted_phi(a):
        jax.debug.callback(_count_a_pass)
        return phi(a)

    return search(counted_phi, phi0, dphi0, g0)


def test_under_vmap_the_objective_runs_once_a_pass_for_the_slowest_lane():
    scales = [p for f, dt, p, _ in CASES if (f, dt) == ("logistic", "float32")]
    _PASSES["ran"] = 0
    got = jax.jit(jax.vmap(_counted_search))(jnp.asarray(scales, jnp.float32))
    jax.effects_barrier()
    got = jax.tree_util.tree_map(np.asarray, got)
    needed = [int(n) for n in got.num_evals]
    assert len(set(needed)) >= 4  # the lanes do need unequal trials
    # the two-stage form ran 2 * (max + 1 transition) here: both stages'
    # evaluations in every pass, and one before the loop
    assert _PASSES["ran"] == max(needed)
    for lane, p in enumerate(scales):
        _assert_same_search(_lane(got, lane),
                            _solo("logistic", "float32", p, "new"))
        _PASSES["ran"] = 0
        solo = jax.jit(_counted_search)(jnp.float32(p))
        jax.effects_barrier()
        assert _PASSES["ran"] == int(solo.num_evals) == needed[lane]


def _sites(jaxpr, name, inside=()):
    """[(enclosing primitives, outermost first)] of every equation called
    ``name`` in a jaxpr, sub-jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(inside)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _sites(sub, name, inside + (eqn.primitive.name,))
    return found


@pytest.mark.parametrize("batched", (False, True), ids=("solo", "vmap"))
def test_the_loop_body_holds_the_objective_once_under_no_conditional(batched):
    def jaxpr_of(search):
        one = functools.partial(_counted_search, search=search)
        if batched:
            return jax.make_jaxpr(jax.vmap(one))(jnp.ones(3, jnp.float32)).jaxpr
        return jax.make_jaxpr(one)(jnp.float32(1.0)).jaxpr

    # one call site in the whole search, straight in the while's body: no
    # evaluation before the loop, none under a cond (a switch is one too),
    # where vmap would run it for every branch
    jaxpr = jaxpr_of(strong_wolfe)
    assert _sites(jaxpr, "debug_callback") == [("while",)]
    assert _sites(jaxpr, "cond") == []
    # the two-stage form held it three times, two of them under its switch;
    # vmap turns a cond on a batched index into selects over every branch
    assert sorted(_sites(jaxpr_of(_oracle_strong_wolfe), "debug_callback")) \
        == ([(), ("while",), ("while",)] if batched
            else [(), ("while", "cond"), ("while", "cond", "cond")])
