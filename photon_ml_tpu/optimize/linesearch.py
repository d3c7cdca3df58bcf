"""Strong-Wolfe line search as a jit-safe state machine.

The reference delegates line search to Breeze's ``StrongWolfeLineSearch``
(via BreezeLBFGS — reference optimization/LBFGS.scala:100-112). Breeze uses a
bracket-then-zoom scheme (Nocedal & Wright Alg. 3.5/3.6) with cubic
interpolation; we implement the same scheme as a single ``lax.while_loop``
whose carry holds the stage (BRACKET -> ZOOM) and the next trial step, so it
compiles once and runs entirely on device. Wolfe constants match
Breeze/Nocedal defaults: c1=1e-4, c2=0.9.

The search works on the 1-D restriction phi(a) = f(x + a d): each trial
evaluates the full (value, gradient) so the accepted point's gradient is
returned for free — one objective evaluation per trial, exactly like the
reference's calculate-per-line-search-step.

Every pass of the loop makes exactly one evaluation, at the carried trial
step and under no ``lax.switch``/``lax.cond``; the stage's rules then judge
it and pick the next trial by selects on scalars. So a search makes
``num_evals`` passes, batched or not: under ``jax.vmap`` (the per-entity
solves) a conditional with a batched index would run every branch for every
lane, and an evaluation inside one would be paid once per branch. Here the
batched loop evaluates max-over-lanes(``num_evals``) times and no more.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax.numpy as jnp
from jax import lax

Array = jnp.ndarray

C1 = 1e-4
C2 = 0.9
MAX_LS_ITER = 20
_BRACKET, _ZOOM, _DONE, _FAIL = 0, 1, 2, 3


class LineSearchResult(NamedTuple):
    alpha: Array  # accepted step length (0 on failure)
    value: Array  # f(x + alpha d)
    grad: Array  # grad f(x + alpha d)
    ok: Array  # bool: Wolfe conditions satisfied
    num_evals: Array


class _LSState(NamedTuple):
    stage: Array
    it: Array  # evaluations made so far
    # the next trial while searching; the last one evaluated once stopped
    a: Array
    # value and gradient at the last trial evaluated
    phi_a: Array
    g_a: Array
    # previous trial (bracketing) / zoom interval lo and hi
    a_lo: Array
    phi_lo: Array
    dphi_lo: Array
    g_lo: Array
    a_hi: Array
    phi_hi: Array
    dphi_hi: Array


def _cubic_min(a, fa, dfa, b, fb, dfb):
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


def strong_wolfe(
    value_and_grad_1d: Callable[[Array], tuple[Array, Array, Array]],
    phi0: Array,
    dphi0: Array,
    g0: Array,
    init_alpha: Array | float = 1.0,
    max_alpha: float = 1e10,
) -> LineSearchResult:
    """Find a step satisfying the strong Wolfe conditions.

    ``value_and_grad_1d(a)`` must return ``(phi(a), dphi(a), grad(x + a d))``.
    ``phi0``/``dphi0``/``g0`` are the values at a=0 (already computed by the
    caller, so a failed search costs nothing extra).
    """
    dtype = phi0.dtype

    def body(s: _LSState) -> _LSState:
        a = s.a
        phi, dphi, g = value_and_grad_1d(a)
        it = s.it + 1
        bracketing = s.stage == _BRACKET

        # Both stages put the same three questions to a fresh trial, in
        # this order. Sufficient decrease fails: the trial becomes hi (which
        # ends the bracketing: lo is the previous trial). Both Wolfe
        # conditions hold: accept. Otherwise the trial becomes lo, and hi
        # takes the old lo when the slope there points away from hi — while
        # bracketing that is a positive slope (which ends the bracketing),
        # and no flip means expand. (N&W Alg. 3.5 asks phi >= phi_lo of
        # the bracketing only from its second trial; at the first, phi_lo
        # is phi(0), and the two-stage form asked it there too.) The
        # bracketing stage spends its last evaluation unjudged: the budget
        # runs out first.
        judged = ~(bracketing & (it >= MAX_LS_ITER))
        armijo_fail = (phi > phi0 + C1 * a * dphi0) | (phi >= s.phi_lo)
        curv_ok = jnp.abs(dphi) <= -C2 * dphi0
        flip = jnp.where(bracketing, dphi >= 0.0,
                         dphi * (s.a_hi - s.a_lo) >= 0.0)
        shrink_hi = judged & armijo_fail
        accept = judged & ~armijo_fail & curv_ok
        move_lo = judged & ~armijo_fail & ~curv_ok
        flip_hi = move_lo & flip

        def pick_hi(cur, lo, hi):
            return jnp.where(shrink_hi, cur, jnp.where(flip_hi, lo, hi))

        a_hi = pick_hi(a, s.a_lo, s.a_hi)
        phi_hi = pick_hi(phi, s.phi_lo, s.phi_hi)
        dphi_hi = pick_hi(dphi, s.dphi_lo, s.dphi_hi)
        a_lo = jnp.where(move_lo, a, s.a_lo)
        phi_lo = jnp.where(move_lo, phi, s.phi_lo)
        dphi_lo = jnp.where(move_lo, dphi, s.dphi_lo)
        g_lo = jnp.where(move_lo, g, s.g_lo)
        stage = jnp.where(
            accept, _DONE,
            jnp.where(bracketing & ~(shrink_hi | flip_hi), _BRACKET, _ZOOM))

        # Give up when the eval budget is exhausted or the zoom interval
        # collapsed; keep the best sufficient-decrease point seen (a_lo).
        exhausted = (it >= MAX_LS_ITER) & (stage < _DONE)
        interval_dead = (stage == _ZOOM) & (
            jnp.abs(a_hi - a_lo) <= 1e-14 * jnp.maximum(1.0, jnp.abs(a_hi))
        )
        stage = jnp.where(exhausted | interval_dead, _FAIL, stage)

        # The next trial: twice the step while bracketing, the cubic's
        # minimizer over (lo, hi) once zooming; a stopped search keeps its
        # last trial, which is the accepted step.
        a_next = jnp.where(
            stage == _BRACKET,
            jnp.minimum(2.0 * a, jnp.asarray(max_alpha, dtype)),
            jnp.where(
                stage == _ZOOM,
                _cubic_min(a_lo, phi_lo, dphi_lo, a_hi, phi_hi, dphi_hi),
                a))
        return _LSState(
            stage=stage.astype(jnp.int32), it=it, a=a_next, phi_a=phi, g_a=g,
            a_lo=a_lo, phi_lo=phi_lo, dphi_lo=dphi_lo, g_lo=g_lo,
            a_hi=a_hi, phi_hi=phi_hi, dphi_hi=dphi_hi,
        )

    def cond(s: _LSState) -> Array:
        return s.stage < _DONE

    init = _LSState(
        stage=jnp.int32(_BRACKET),
        it=jnp.int32(0),
        a=jnp.asarray(init_alpha, dtype), phi_a=phi0, g_a=g0,
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
    )
