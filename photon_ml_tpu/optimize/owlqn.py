"""OWL-QN (Orthant-Wise Limited-memory Quasi-Newton) for L1 objectives.

TPU-native replacement for the reference's Breeze-backed OWLQN
(reference: photon-ml/src/main/scala/com/linkedin/photon/ml/optimization/
OWLQN.scala:43-90 — extends LBFGS, delegating to ``BreezeOWLQN`` with a
mutable L1 weight for the warm-started lambda grid). Implements Andrew & Gao
(2007) as one jitted ``lax.while_loop``:

- pseudo-gradient of F(x) = f(x) + l1 ||x||_1 (subgradient selection at 0)
- L-BFGS two-loop direction from *smooth* gradient history, projected onto
  the orthant of the negative pseudo-gradient
- backtracking line search on points projected onto the current orthant
- history pairs from smooth gradients only

``l1`` may be a scalar or a per-coordinate vector (e.g. zero for the
intercept), covering the reference's elastic-net split where lambda1 = alpha *
lambda goes to OWL-QN and lambda2 stays in the smooth L2 mixin
(RegularizationContext.scala:35-90).
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
from photon_ml_tpu.optimize.lbfgs import (
    LBFGSResume,
    axis_dot,
    axis_norm,
    check_history_layout,
    empty_history,
    push_pair,
    record,
    two_loop_direction,
)
from photon_ml_tpu.parallel.quantized_collectives import qpsum

Array = jnp.ndarray

DEFAULT_MAX_ITER = 100
DEFAULT_M = 10
DEFAULT_TOLERANCE = 1e-7
_LS_MAX_STEPS = 30
_LS_C1 = 1e-4


def pseudo_gradient(x: Array, g: Array, l1: Array) -> Array:
    """Subgradient selection for F = f + l1 ||x||_1 (Andrew & Gao eq. 4)."""
    right = g + l1  # derivative approaching from x_j > 0
    left = g - l1  # from x_j < 0
    at_zero = jnp.where(right < 0.0, right, jnp.where(left > 0.0, left, 0.0))
    return jnp.where(x > 0.0, right, jnp.where(x < 0.0, left, at_zero))


class _OWLQNCarry(NamedTuple):
    it: Array
    x: Array
    f: Array  # F = f + l1 |x|  (the tracked objective)
    g: Array  # smooth gradient
    prev_f: Array
    S: Array
    Y: Array
    rho: Array
    valid: Array
    head: Optional[Array]  # None: newest-first history (see lbfgs)
    made_progress: Array
    values: Array
    grad_norms: Array  # pseudo-gradient norms
    evaluations: Array  # [max_iter+1] int32 (RunHistory.evaluations)
    iterates: Optional[Array]  # [max_iter+1, d] when tracking, else None


@partial(jax.jit, static_argnums=(0, 3, 4, 5, 8, 10, 11, 12, 13))
def _minimize_owlqn_impl(
    value_and_grad_fn,
    x0: Array,
    data,
    max_iter: int,
    m: int,
    tolerance: float,
    l1: Array = 0.0,
    box: Optional[BoxConstraints] = None,
    track_iterates: bool = False,
    resume: Optional[LBFGSResume] = None,
    return_carry: bool = False,
    update_axis_name: Optional[str] = None,
    collective_quant: str = "none",
    newest_first: bool = False,
):
    # Sharded weight update (see lbfgs): x0/g/l1 are per-replica shards,
    # every d-vector reduction (including the L1 penalty sum) is psum'd.
    # Orthant projections and the pseudo-gradient stay elementwise.
    if update_axis_name is not None and (box is not None or track_iterates):
        raise ValueError(
            "sharded weight update supports neither box constraints nor "
            "track_iterates")
    vdot = axis_dot(update_axis_name, collective_quant)
    vnorm = axis_norm(update_axis_name, collective_quant)
    d = x0.shape[0]
    dtype = x0.dtype
    l1 = jnp.broadcast_to(jnp.asarray(l1, dtype), (d,))

    def full_objective(x):
        f, g = value_and_grad_fn(x, data)
        # L1 penalty sums d tiny per-coordinate terms: accumulate in at
        # least f32 so bf16/f16 iterates don't lose the penalty entirely.
        penalty = jnp.sum(l1 * jnp.abs(x),
                          dtype=jnp.promote_types(dtype, jnp.float32))
        if update_axis_name is not None:
            penalty = qpsum(penalty, update_axis_name,
                            mode=collective_quant)
        return f + penalty, g

    # ``resume`` continues a previous chunk's solve verbatim: carry
    # (iterate, SMOOTH-gradient curvature pairs, prev F) plus the ORIGINAL
    # F₀/‖pg₀‖ anchors, so chunked restarts never re-anchor the relative
    # tolerances (see lbfgs.LBFGSResume — the carry shape is shared, and
    # so are the two history layouts ``newest_first`` chooses between).
    if resume is None:
        f_start, g_start = full_objective(x0)
        anchor_f0 = f_start
        anchor_g0n = vnorm(pseudo_gradient(x0, g_start, l1))
        x_start = x0
        prev_f0 = f_start + jnp.asarray(jnp.inf, dtype)
        S0, Y0, rho0, valid0, head0 = empty_history(m, d, dtype,
                                                    newest_first)
    else:
        check_history_layout(resume, newest_first)
        x_start, f_start, g_start = resume.x, resume.f, resume.g
        prev_f0 = resume.prev_f
        S0, Y0, rho0 = resume.S, resume.Y, resume.rho
        valid0, head0 = resume.valid, resume.head
        anchor_f0, anchor_g0n = resume.f0, resume.g0n

    pg_start = pseudo_gradient(x_start, g_start, l1)
    values = jnp.full(max_iter + 1, jnp.nan, dtype).at[0].set(f_start)
    grad_norms = jnp.full(max_iter + 1, jnp.nan, dtype).at[0].set(
        vnorm(pg_start))
    # the start's evaluation above; a resumed chunk made none
    evaluations = jnp.zeros(max_iter + 1, jnp.int32).at[0].set(
        1 if resume is None else 0)
    iterates0 = (jnp.zeros((max_iter + 1, d), dtype).at[0].set(x_start)
                 if track_iterates else None)

    init = _OWLQNCarry(
        it=jnp.int32(0), x=x_start, f=f_start, g=g_start,
        prev_f=prev_f0,
        S=S0, Y=Y0, rho=rho0, valid=valid0,
        head=head0, made_progress=jnp.bool_(True),
        values=values, grad_norms=grad_norms, evaluations=evaluations,
        iterates=iterates0,
    )

    def cond(c: _OWLQNCarry) -> Array:
        pg = pseudo_gradient(c.x, c.g, l1)
        return should_continue(
            c.it, c.f, c.prev_f, vnorm(pg),
            anchor_f0, anchor_g0n,
            max_iter, tolerance, c.made_progress,
            resumed=resume is not None,
        )

    def body(c: _OWLQNCarry) -> _OWLQNCarry:
        pg = pseudo_gradient(c.x, c.g, l1)
        with jax.named_scope("owlqn.direction"):
            direction = two_loop_direction(
                pg, c.S, c.Y, c.rho, c.valid, c.head,
                update_axis_name, collective_quant)
            # Project direction onto the orthant of -pg (keep only
            # components that actually descend along the pseudo-gradient).
            direction = jnp.where(direction * pg < 0.0, direction, 0.0)

        # Orthant for this step: sign(x_j), or sign(-pg_j) where x_j == 0.
        xi = jnp.where(c.x != 0.0, jnp.sign(c.x), jnp.sign(-pg))

        def project_trial(x_new):
            x_new = jnp.where(x_new * xi > 0.0, x_new, 0.0)
            # Box projection after the orthant projection, mirroring the
            # reference where OWLQN inherits LBFGS's per-iterate hypercube
            # projection (optimization/LBFGS.scala:42-150).
            if box is not None:
                x_new = project_box(x_new, box)
            return x_new

        # Chunk-resumed solves are past their true first iteration, so
        # the 1/||d|| first-step convention must not re-fire at restart.
        if resume is None:
            init_alpha = jnp.where(
                c.it == 0,
                1.0 / jnp.maximum(vnorm(direction), 1.0),
                jnp.asarray(1.0, dtype),
            )
        else:
            init_alpha = jnp.asarray(1.0, dtype)

        # Backtracking: accept F(pi(x + a d)) <= F(x) + c1 * pg . (x_new - x).
        def ls_cond(state):
            a, f_a, g_a, x_a, k, accepted = state
            return (~accepted) & (k < _LS_MAX_STEPS)

        def ls_body(state):
            a, _, _, _, k, _ = state
            x_a = project_trial(c.x + a * direction)
            f_a, g_a = full_objective(x_a)
            accepted = f_a <= c.f + _LS_C1 * vdot(pg, x_a - c.x)
            a_next = jnp.where(accepted, a, a * 0.5)
            return a_next, f_a, g_a, x_a, k + 1, accepted

        # one evaluation per trial: the trial count IS the iteration's
        # evaluation count
        with jax.named_scope("owlqn.linesearch"):
            a, f_new, g_new, x_new, evals, accepted = lax.while_loop(
                ls_cond, ls_body,
                (init_alpha, c.f, c.g, c.x, jnp.int32(0),
                 jnp.bool_(False)),
            )
        # Non-finite trial values never enter the carry (divergence guard).
        accepted = finite_step(accepted, f_new, g_new, update_axis_name)

        with jax.named_scope("owlqn.update"):
            s = x_new - c.x
            y = g_new - c.g  # smooth gradient difference
            sy = vdot(s, y)
            store = accepted & (sy > 1e-10)

            S, Y, rho, valid, head = push_pair(
                c.S, c.Y, c.rho, c.valid, c.head, s, y, sy, store)

            it_new = c.it + 1
            pg_new = pseudo_gradient(x_new, g_new, l1)
            values = record(c.values, it_new,
                            jnp.where(accepted, f_new, c.f), newest_first)
            grad_norms = record(
                c.grad_norms, it_new,
                vnorm(jnp.where(accepted, pg_new, pg)), newest_first)
            # always by select: this array is here to be cheap
            evaluations = record(c.evaluations, it_new, evals, True)
            x_acc = jnp.where(accepted, x_new, c.x)
            iterates = (c.iterates.at[it_new].set(x_acc)
                        if track_iterates else None)

        return _OWLQNCarry(
            it=it_new,
            x=x_acc,
            f=jnp.where(accepted, f_new, c.f),
            g=jnp.where(accepted, g_new, c.g),
            prev_f=c.f,
            S=S, Y=Y, rho=rho, valid=valid, head=head,
            made_progress=accepted,
            values=values, grad_norms=grad_norms, evaluations=evaluations,
            iterates=iterates,
        )

    final = lax.while_loop(cond, body, init)
    history = RunHistory(values=final.values, grad_norms=final.grad_norms,
                         num_iterations=final.it, iterates=final.iterates,
                         evaluations=final.evaluations)
    if return_carry:
        carry = LBFGSResume(
            x=final.x, f=final.f, g=final.g, prev_f=final.prev_f,
            S=final.S, Y=final.Y, rho=final.rho, valid=final.valid,
            head=final.head, f0=anchor_f0, g0n=anchor_g0n)
        return final.x, history, final.made_progress, carry
    return final.x, history, final.made_progress


def minimize_owlqn(
    value_and_grad_fn: Callable[[Array, object], tuple[Array, Array]],
    x0: Array,
    data=None,
    l1: float | Array = 0.0,
    max_iter: int = DEFAULT_MAX_ITER,
    m: int = DEFAULT_M,
    tolerance: float = DEFAULT_TOLERANCE,
    box: Optional[BoxConstraints] = None,
    track_iterates: bool = False,
    resume: Optional[LBFGSResume] = None,
    return_carry: bool = False,
    update_axis_name: Optional[str] = None,
    collective_quant: str = "none",
    newest_first: bool = False,
):
    """Minimize f(x, data) + l1 ||x||_1; returns (x, RunHistory, made_progress).

    ``value_and_grad_fn`` returns the SMOOTH part's (value, gradient); the L1
    term is handled here. ``l1`` may be scalar or per-coordinate (length d).
    ``resume``/``return_carry`` continue a chunked solve bit-identically
    (see :func:`minimize_lbfgs` — the carry shape is shared), and
    ``newest_first`` is the history layout for a solve under ``vmap``, as
    there.
    """
    from photon_ml_tpu.obs import compile as obs_compile

    return obs_compile.call(
        "optimizer.owlqn", _minimize_owlqn_impl,
        (value_and_grad_fn, x0, data, max_iter, m, tolerance, l1, box,
         track_iterates, resume, return_carry, update_axis_name,
         collective_quant, newest_first),
        static_argnums=(0, 3, 4, 5, 8, 10, 11, 12, 13),
        arg_names=("value_and_grad_fn", "x0", "data", "max_iter", "m",
                   "tolerance", "l1", "box", "track_iterates", "resume",
                   "return_carry", "update_axis_name", "collective_quant",
                   "newest_first"))
