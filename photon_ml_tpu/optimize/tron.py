"""TRON: trust-region Newton with truncated conjugate gradient.

TPU-native re-design of the reference's LIBLINEAR-derived TRON
(reference: photon-ml/src/main/scala/com/linkedin/photon/ml/optimization/
TRON.scala:84-341; Lin & More / the LIBLINEAR logistic paper, Algorithm 2).
Semantics preserved:

- hyper-parameters (eta0, eta1, eta2) = (1e-4, 0.25, 0.75),
  (sigma1, sigma2, sigma3) = (0.25, 0.5, 4.0)  (TRON.scala:103-104)
- trust region initialized to ||g0||; shrunk to min(delta, ||step||) after
  the first objective evaluation (TRON.scala:195-198)
- inner truncated CG: <= 20 iterations, tolerance 0.1 ||g||, boundary
  intersection when the step leaves the trust region (TRON.scala:281-341)
- up to 5 improvement failures with a shrinking region before giving up
  (maxNumImprovementFailures, TRON.scala:260)
- defaults maxIter=15, tol=1e-5 (TRON.scala:260-262)

The reference pays one Spark treeAggregate per CG iteration (Hessian-vector);
here each Hv is a fused on-device kernel, and the entire outer/inner loop nest
is one compiled XLA program.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optimize.common import (
    BoxConstraints,
    RunHistory,
    finite_step,
    project_box,
    should_continue,
)
from photon_ml_tpu.optimize.lbfgs import axis_dot, axis_norm

Array = jnp.ndarray

DEFAULT_MAX_ITER = 15
DEFAULT_TOLERANCE = 1e-5
DEFAULT_MAX_FAILURES = 5
MAX_CG_ITERATIONS = 20

_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0


class _CGState(NamedTuple):
    it: Array
    done: Array
    step: Array
    residual: Array
    direction: Array
    r_tr: Array


def _truncated_cg(hvp, gradient: Array, delta: Array,
                  axis_name: Optional[str] = None,
                  collective_quant: str = "none"
                  ) -> tuple[Array, Array, Array]:
    """Approximately solve H s = -g within ||s|| <= delta.

    Returns (cg_iterations, step, residual). ``hvp(v)`` computes H v.
    With ``axis_name`` set, gradient/step are per-replica shards and every
    inner product is psum'd (see lbfgs.axis_dot).
    """
    vdot = axis_dot(axis_name, collective_quant)
    vnorm = axis_norm(axis_name, collective_quant)
    tol = 0.1 * vnorm(gradient)
    r0 = -gradient

    init = _CGState(
        it=jnp.int32(0), done=jnp.bool_(False),
        step=jnp.zeros_like(gradient), residual=r0, direction=r0,
        r_tr=vdot(r0, r0),
    )

    def cond(s: _CGState) -> Array:
        return (s.it < MAX_CG_ITERATIONS) & ~s.done

    def body(s: _CGState) -> _CGState:
        converged = vnorm(s.residual) <= tol

        def advance(s: _CGState) -> _CGState:
            hd = hvp(s.direction)
            alpha = s.r_tr / vdot(s.direction, hd)
            step = s.step + alpha * s.direction
            outside = vnorm(step) > delta

            def hit_boundary(_):
                # Back up to the region boundary: solve ||step0 + t d|| = delta
                step0 = s.step
                std = vdot(step0, s.direction)
                sts = vdot(step0, step0)
                dtd = vdot(s.direction, s.direction)
                dsq = delta * delta
                rad = jnp.sqrt(std * std + dtd * (dsq - sts))
                t = jnp.where(std >= 0.0, (dsq - sts) / (std + rad),
                              (rad - std) / dtd)
                new_step = step0 + t * s.direction
                new_residual = s.residual - t * hd
                return s._replace(it=s.it + 1, done=jnp.bool_(True),
                                  step=new_step, residual=new_residual)

            def interior(_):
                residual = s.residual - alpha * hd
                r_new = vdot(residual, residual)
                beta = r_new / s.r_tr
                direction = residual + beta * s.direction
                return s._replace(it=s.it + 1, step=step, residual=residual,
                                  direction=direction, r_tr=r_new)

            return lax.cond(outside, hit_boundary, interior, None)

        return lax.cond(converged,
                        lambda s: s._replace(done=jnp.bool_(True)),
                        advance, s)

    final = lax.while_loop(cond, body, init)
    return final.it, final.step, final.residual


class _TRONCarry(NamedTuple):
    it: Array
    x: Array
    f: Array
    g: Array
    prev_f: Array
    delta: Array
    failures: Array  # consecutive improvement failures at the current iterate
    made_progress: Array
    values: Array
    grad_norms: Array
    evaluations: Array  # [max_iter+1] int32 (RunHistory.evaluations)
    hvps: Array  # [max_iter+1] int32 (RunHistory.hvps)
    iterates: Optional[Array]  # [max_iter+1, d] when tracking, else None


class TRONResume(NamedTuple):
    """Chunk-restart carry for TRON (see lbfgs.LBFGSResume): live iterate
    state, the trust-region radius and failure count, the previous
    objective, and the ORIGINAL f₀/‖g₀‖ anchors — a resumed chunk then
    runs exactly the iterations the uninterrupted solve would have."""

    x: Array
    f: Array
    g: Array
    prev_f: Array
    delta: Array
    failures: Array
    f0: Array
    g0n: Array


@partial(jax.jit, static_argnums=(0, 1, 4, 5, 6, 8, 10, 11, 12))
def _minimize_tron_impl(
    value_and_grad_fn,
    hvp_fn,
    x0: Array,
    data,
    max_iter: int,
    tolerance: float,
    max_failures: int,
    box: Optional[BoxConstraints] = None,
    track_iterates: bool = False,
    resume: Optional[TRONResume] = None,
    return_carry: bool = False,
    update_axis_name: Optional[str] = None,
    collective_quant: str = "none",
):
    # Sharded weight update (see lbfgs): x0/g are per-replica shards, CG
    # and region arithmetic psum every d-vector reduction. hvp_fn must
    # accept/return shards (the caller's wrapper all-gathers v).
    if update_axis_name is not None and (box is not None or track_iterates):
        raise ValueError(
            "sharded weight update supports neither box constraints nor "
            "track_iterates")
    vdot = axis_dot(update_axis_name, collective_quant)
    vnorm = axis_norm(update_axis_name, collective_quant)
    dtype = x0.dtype
    if resume is None:
        f_start, g_start = value_and_grad_fn(x0, data)
        anchor_f0 = f_start
        anchor_g0n = vnorm(g_start)
        x_start = x0
        prev_f0 = f_start + jnp.asarray(jnp.inf, dtype)
        delta0 = anchor_g0n
        failures0 = jnp.int32(0)
    else:
        x_start, f_start, g_start = resume.x, resume.f, resume.g
        prev_f0 = resume.prev_f
        delta0, failures0 = resume.delta, resume.failures
        anchor_f0, anchor_g0n = resume.f0, resume.g0n

    values = jnp.full(max_iter + 1, jnp.nan, dtype).at[0].set(f_start)
    grad_norms = jnp.full(max_iter + 1, jnp.nan, dtype).at[0].set(
        vnorm(g_start))
    # the start's evaluation above; a resumed chunk made none
    evaluations = jnp.zeros(max_iter + 1, jnp.int32).at[0].set(
        1 if resume is None else 0)
    iterates0 = (jnp.zeros((max_iter + 1,) + x_start.shape, dtype)
                 .at[0].set(x_start) if track_iterates else None)

    init = _TRONCarry(
        it=jnp.int32(0), x=x_start, f=f_start, g=g_start,
        prev_f=prev_f0,
        delta=delta0, failures=failures0, made_progress=jnp.bool_(True),
        values=values, grad_norms=grad_norms, evaluations=evaluations,
        hvps=jnp.zeros(max_iter + 1, jnp.int32), iterates=iterates0,
    )

    def cond(c: _TRONCarry) -> Array:
        return should_continue(
            c.it, c.f, c.prev_f, vnorm(c.g),
            anchor_f0, anchor_g0n,
            max_iter, tolerance, c.made_progress,
            resumed=resume is not None,
        ) & (c.failures < max_failures)

    def body(c: _TRONCarry) -> _TRONCarry:
        # CG's iteration count IS its Hessian-vector count: only the
        # ``advance`` branch, which makes the product, advances it
        with jax.named_scope("tron.cg"):
            cg_iters, step, residual = _truncated_cg(
                lambda v: hvp_fn(c.x, v, data), c.g, c.delta,
                update_axis_name, collective_quant)

        x_try = c.x + step
        gs = vdot(c.g, step)
        predicted = -0.5 * (gs - vdot(step, residual))
        f_try, g_try = value_and_grad_fn(x_try, data)
        # A non-finite trial objective is "infinitely bad" for the region
        # arithmetic: every where-comparison on a NaN is False, which
        # would otherwise leak a NaN alpha into delta and wedge the solve
        # permanently — +inf instead drives the shrink branch, TRON's
        # documented rejection remedy, until the step re-enters the
        # finite region.
        f_arith = jnp.where(jnp.isfinite(f_try), f_try,
                            jnp.asarray(jnp.inf, dtype))
        actual = c.f - f_arith
        step_norm = vnorm(step)

        # First iteration: tighten the initial region to the step scale.
        # A chunk-resumed solve carries its live region — never re-tighten.
        if resume is None:
            delta = jnp.where(c.it == 0,
                              jnp.minimum(c.delta, step_norm), c.delta)
        else:
            delta = c.delta

        # Step-scale prediction alpha (TRON.scala:201-206).
        denom = f_arith - c.f - gs
        alpha = jnp.where(denom <= 0.0, _SIGMA3,
                          jnp.maximum(_SIGMA1, -0.5 * (gs / denom)))

        # Region update by actual/predicted ratio (TRON.scala:208-217).
        delta = jnp.where(
            actual < _ETA0 * predicted,
            jnp.minimum(jnp.maximum(alpha, _SIGMA1) * step_norm, _SIGMA2 * delta),
            jnp.where(
                actual < _ETA1 * predicted,
                jnp.maximum(_SIGMA1 * delta,
                            jnp.minimum(alpha * step_norm, _SIGMA2 * delta)),
                jnp.where(
                    actual < _ETA2 * predicted,
                    jnp.maximum(_SIGMA1 * delta,
                                jnp.minimum(alpha * step_norm, _SIGMA3 * delta)),
                    jnp.maximum(delta,
                                jnp.minimum(alpha * step_norm, _SIGMA3 * delta)),
                ),
            ),
        )

        # Non-finite trial values count as an improvement failure (the NaN
        # comparison already rejects f_try; the explicit guard also keeps a
        # NaN gradient out of the accepted state).
        improved = finite_step(actual > _ETA0 * predicted, f_try, g_try,
                               update_axis_name)
        x_new = jnp.where(improved, project_box(x_try, box) if box is not None
                          else x_try, c.x)
        evals = jnp.int32(1)  # the trial point
        if box is not None:
            # Projected point may differ from x_try; refresh (f, g) there.
            changed = improved & jnp.any(x_new != x_try)
            f_try, g_try = lax.cond(
                changed, lambda: value_and_grad_fn(x_new, data),
                lambda: (f_try, g_try))
            evals = evals + changed.astype(jnp.int32)

        it_new = jnp.where(improved, c.it + 1, c.it)
        f_new = jnp.where(improved, f_try, c.f)
        g_new = jnp.where(improved, g_try, c.g)

        values = jnp.where(
            improved, c.values.at[c.it + 1].set(f_try), c.values)
        grad_norms = jnp.where(
            improved,
            c.grad_norms.at[c.it + 1].set(vnorm(g_try)), c.grad_norms)
        # unconditional write: when not improved, x_new == c.x and it does
        # not advance, so the slot is overwritten by the next accepted step
        # or sliced off by from_history — no whole-buffer select needed
        iterates = (c.iterates.at[c.it + 1].set(x_new)
                    if track_iterates else None)
        # a rejected trial leaves ``it`` where it was: its passes add up in
        # the slot of the iteration being tried for
        # (selects, not ``.at[].add``: under ``vmap`` that is a scatter over
        # every lane, and these arrays are here to be cheap)
        trying = jnp.arange(max_iter + 1) == c.it + 1
        evaluations = c.evaluations + jnp.where(trying, evals, 0)
        hvps = c.hvps + jnp.where(trying, cg_iters, 0)

        return _TRONCarry(
            it=it_new, x=x_new, f=f_new, g=g_new,
            prev_f=jnp.where(improved, c.f, c.prev_f),
            delta=delta,
            failures=jnp.where(improved, 0, c.failures + 1),
            made_progress=improved | (c.failures + 1 < max_failures),
            values=values, grad_norms=grad_norms, evaluations=evaluations,
            hvps=hvps, iterates=iterates,
        )

    final = lax.while_loop(cond, body, init)
    history = RunHistory(values=final.values, grad_norms=final.grad_norms,
                         num_iterations=final.it, iterates=final.iterates,
                         evaluations=final.evaluations, hvps=final.hvps)
    if return_carry:
        carry = TRONResume(
            x=final.x, f=final.f, g=final.g, prev_f=final.prev_f,
            delta=final.delta, failures=final.failures,
            f0=anchor_f0, g0n=anchor_g0n)
        return final.x, history, final.made_progress, carry
    return final.x, history, final.made_progress


def minimize_tron(
    value_and_grad_fn: Callable[[Array, object], tuple[Array, Array]],
    hvp_fn: Callable[[Array, Array, object], Array],
    x0: Array,
    data=None,
    max_iter: int = DEFAULT_MAX_ITER,
    tolerance: float = DEFAULT_TOLERANCE,
    max_failures: int = DEFAULT_MAX_FAILURES,
    box: Optional[BoxConstraints] = None,
    track_iterates: bool = False,
    resume: Optional[TRONResume] = None,
    return_carry: bool = False,
    update_axis_name: Optional[str] = None,
    collective_quant: str = "none",
):
    """Trust-region Newton; returns (x, RunHistory, made_progress).

    ``hvp_fn(x, v, data)`` computes the (Gauss-Newton) Hessian-vector product.
    Requires a twice-differentiable objective — the smoothed-hinge loss has no
    usable Hessian, so the problem factory refuses TRON for it exactly as the
    reference's OptimizerFactory does (OptimizerFactory.scala:78-79).
    ``resume``/``return_carry`` continue a chunked solve bit-identically
    (see :class:`TRONResume`).
    """
    from photon_ml_tpu.obs import compile as obs_compile

    return obs_compile.call(
        "optimizer.tron", _minimize_tron_impl,
        (value_and_grad_fn, hvp_fn, x0, data, max_iter, tolerance,
         max_failures, box, track_iterates, resume, return_carry,
         update_axis_name, collective_quant),
        static_argnums=(0, 1, 4, 5, 6, 8, 10, 11, 12),
        arg_names=("value_and_grad_fn", "hvp_fn", "x0", "data", "max_iter",
                   "tolerance", "max_failures", "box", "track_iterates",
                   "resume", "return_carry", "update_axis_name",
                   "collective_quant"))
