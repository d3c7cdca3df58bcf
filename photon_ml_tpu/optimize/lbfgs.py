"""L-BFGS as a single jitted ``lax.while_loop`` kernel.

TPU-native replacement for the reference's Breeze-backed LBFGS
(reference: photon-ml/src/main/scala/com/linkedin/photon/ml/optimization/
LBFGS.scala:42-156 — wraps ``breeze.optimize.LBFGS.iterations`` and projects
each iterate onto box constraints; defaults maxIter=100, m=10, tol=1e-7).

Design: the two-loop recursion runs over a fixed-size circular history held in
``[m, d]`` device arrays with per-slot validity masks, so the whole solve is
one XLA computation — no host round-trips per iteration (the reference pays a
Spark broadcast + treeAggregate per function evaluation; here a sharded
objective's all-reduce is fused into the loop body).

Convergence checks mirror Optimizer.scala:156-170 (see optimize/common.py).
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
from photon_ml_tpu.optimize.linesearch import strong_wolfe
from photon_ml_tpu.parallel.quantized_collectives import qpsum

Array = jnp.ndarray

DEFAULT_MAX_ITER = 100
DEFAULT_M = 10
DEFAULT_TOLERANCE = 1e-7


class _LBFGSCarry(NamedTuple):
    it: Array
    x: Array
    f: Array
    g: Array
    prev_f: Array
    S: Array  # [m, d] position differences
    Y: Array  # [m, d] gradient differences
    rho: Array  # [m]
    valid: Array  # [m] bool
    head: Array  # next write slot
    made_progress: Array  # bool: last line search succeeded
    values: Array
    grad_norms: Array
    evaluations: Array  # [max_iter+1] int32 (RunHistory.evaluations)
    iterates: Optional[Array]  # [max_iter+1, d] when tracking, else None


class LBFGSResume(NamedTuple):
    """Everything a chunked warm restart needs to continue THIS solve as
    if it had never stopped: the live iterate state, the full two-loop
    curvature history, the previous objective value (so the restart's
    first convergence check is the uninterrupted loop's check, not a
    sentinel-forced continue), and the ORIGINAL dispatch's f₀/‖g₀‖
    anchors (the relative tolerances |Δf| ≤ tol·|f₀| and ‖g‖ ≤ tol·‖g₀‖
    must never re-anchor at a chunk boundary). Produced by
    ``return_carry=True``; under ``vmap`` every leaf grows a lane axis,
    which is what lets the lane-compaction driver gather only the
    still-active lanes' carries between chunks."""

    x: Array
    f: Array
    g: Array
    prev_f: Array
    S: Array
    Y: Array
    rho: Array
    valid: Array
    head: Array
    f0: Array  # original-dispatch anchor f₀
    g0n: Array  # original-dispatch anchor ‖g₀‖


def axis_dot(axis_name: Optional[str], collective_quant: str = "none"):
    """d-vector dot product, all-reduced over ``axis_name`` when the
    vectors are shards of a mesh-partitioned weight update (arXiv
    2004.13336): each replica holds a slice of x/g/S/Y, so every inner
    product in the solver must psum its local partial. Routed through
    ``qpsum`` so the solver's collective sites share the
    ``--collective-quant`` wire format — the payload here is a scalar,
    which qpsum always ships uncompressed (a 4-byte partial cannot
    compress; quantizing it would only add error)."""
    if axis_name is None:
        return jnp.dot
    return lambda a, b: qpsum(jnp.dot(a, b), axis_name,
                              mode=collective_quant)


def axis_norm(axis_name: Optional[str], collective_quant: str = "none"):
    """d-vector 2-norm, all-reduced over ``axis_name`` (see axis_dot)."""
    if axis_name is None:
        return jnp.linalg.norm
    return lambda a: jnp.sqrt(qpsum(jnp.sum(a * a), axis_name,
                                    mode=collective_quant))


def two_loop_direction(g: Array, S: Array, Y: Array, rho: Array, valid: Array,
                       head: Array,
                       axis_name: Optional[str] = None,
                       collective_quant: str = "none") -> Array:
    """Two-loop recursion over a masked circular history buffer.

    With ``axis_name`` set, g/S/Y are per-replica shards and every inner
    product is psum'd — the recursion then produces this replica's shard
    of the exact full-dimension direction."""
    m = S.shape[0]
    vdot = axis_dot(axis_name, collective_quant)

    # Order slots newest -> oldest: head-1, head-2, ...
    idx = (head - 1 - jnp.arange(m)) % m

    def first_loop(carry, i):
        q = carry
        a_i = jnp.where(valid[i], rho[i] * vdot(S[i], q), 0.0)
        q = q - a_i * Y[i]
        return q, a_i

    q, alphas = lax.scan(first_loop, g, idx)

    # Initial Hessian scaling gamma = s.y / y.y from the newest valid pair.
    newest = (head - 1) % m
    sy = vdot(S[newest], Y[newest])
    yy = vdot(Y[newest], Y[newest])
    gamma = jnp.where(valid[newest] & (yy > 0), sy / jnp.maximum(yy, 1e-300), 1.0)
    r = gamma * q

    def second_loop(carry, ia):
        r = carry
        i, a_i = ia
        beta = jnp.where(valid[i], rho[i] * vdot(Y[i], r), 0.0)
        r = r + S[i] * (a_i - beta)
        return r, None

    # reverse order: oldest -> newest
    r, _ = lax.scan(second_loop, r, (idx[::-1], alphas[::-1]))
    return -r


@partial(jax.jit, static_argnums=(0, 3, 4, 5, 7, 9, 10, 11))
def _minimize_lbfgs_impl(
    value_and_grad_fn,
    x0: Array,
    data,
    max_iter: int,
    m: int,
    tolerance: float,
    box: Optional[BoxConstraints] = None,
    track_iterates: bool = False,
    resume: Optional[LBFGSResume] = None,
    return_carry: bool = False,
    update_axis_name: Optional[str] = None,
    collective_quant: str = "none",
):
    # ``data`` is a traced pytree (the batch): one compiled kernel per
    # function object serves every batch of the same shape — critical for the
    # GAME workload where thousands of per-entity solves reuse this kernel.
    # ``box=None`` vs a BoxConstraints pytree changes trace structure, so the
    # unconstrained path compiles with no projection code at all.
    # ``resume`` continues a previous chunk's solve: the carry (iterate,
    # curvature pairs, prev_f) and the ORIGINAL dispatch's f₀/‖g₀‖
    # anchors come back verbatim, so every convergence check and line
    # search is bit-identical to the uninterrupted loop's at the same
    # global iteration (only ``it``/the history buffer restart at 0 —
    # they are chunk-local bookkeeping).
    # ``update_axis_name``: x0/g are per-replica shards of the weight
    # vector; every d-vector reduction is psum'd so the sharded solve is
    # the exact full-dimension recursion (arXiv 2004.13336). Box
    # projection and iterate tracking would need full vectors per step —
    # unsupported in sharded-update mode (callers fall back).
    if update_axis_name is not None and (box is not None or track_iterates):
        raise ValueError(
            "sharded weight update supports neither box constraints nor "
            "track_iterates")
    vdot = axis_dot(update_axis_name, collective_quant)
    vnorm = axis_norm(update_axis_name, collective_quant)
    d = x0.shape[0]
    dtype = x0.dtype
    if resume is None:
        f_start, g_start = value_and_grad_fn(x0, data)
        anchor_f0 = f_start
        anchor_g0n = vnorm(g_start)
        x_start = x0
        prev_f0 = f_start + jnp.asarray(jnp.inf, dtype)
        S0 = jnp.zeros((m, d), dtype)
        Y0 = jnp.zeros((m, d), dtype)
        rho0 = jnp.zeros(m, dtype)
        valid0 = jnp.zeros(m, bool)
        head0 = jnp.int32(0)
    else:
        x_start, f_start, g_start = resume.x, resume.f, resume.g
        prev_f0 = resume.prev_f
        S0, Y0, rho0 = resume.S, resume.Y, resume.rho
        valid0, head0 = resume.valid, resume.head
        anchor_f0, anchor_g0n = resume.f0, resume.g0n

    values = jnp.full(max_iter + 1, jnp.nan, dtype)
    grad_norms = jnp.full(max_iter + 1, jnp.nan, dtype)
    values = values.at[0].set(f_start)
    grad_norms = grad_norms.at[0].set(vnorm(g_start))
    # the start's evaluation above; a resumed chunk made none
    evaluations = jnp.zeros(max_iter + 1, jnp.int32).at[0].set(
        1 if resume is None else 0)
    iterates0 = (jnp.zeros((max_iter + 1, d), dtype).at[0].set(x_start)
                 if track_iterates else None)

    init = _LBFGSCarry(
        it=jnp.int32(0), x=x_start, f=f_start, g=g_start,
        prev_f=prev_f0,
        S=S0, Y=Y0, rho=rho0, valid=valid0,
        head=head0, made_progress=jnp.bool_(True),
        values=values, grad_norms=grad_norms, evaluations=evaluations,
        iterates=iterates0,
    )

    def cond(c: _LBFGSCarry) -> Array:
        return should_continue(
            c.it, c.f, c.prev_f, vnorm(c.g),
            anchor_f0, anchor_g0n,
            max_iter, tolerance, c.made_progress,
            resumed=resume is not None,
        )

    def body(c: _LBFGSCarry) -> _LBFGSCarry:
        with jax.named_scope("lbfgs.direction"):
            direction = two_loop_direction(
                c.g, c.S, c.Y, c.rho, c.valid, c.head,
                update_axis_name, collective_quant)
            dphi0 = vdot(c.g, direction)
            # Safeguard: fall back to steepest descent if not a descent
            # direction.
            bad = dphi0 >= 0.0
            direction = jnp.where(bad, -c.g, direction)
            dphi0 = jnp.where(bad, -vdot(c.g, c.g), dphi0)

        def phi(a):
            x_a = c.x + a * direction
            f_a, g_a = value_and_grad_fn(x_a, data)
            return f_a, vdot(g_a, direction), g_a

        # Breeze convention: first iteration starts at 1/||d||, then 1.0.
        # A chunk-resumed solve is never at its true first iteration —
        # its local it=0 is some global iteration > 0, so alpha stays 1.0.
        if resume is None:
            init_alpha = jnp.where(
                c.it == 0,
                1.0 / jnp.maximum(vnorm(direction), 1.0),
                jnp.asarray(1.0, dtype),
            )
        else:
            init_alpha = jnp.asarray(1.0, dtype)
        with jax.named_scope("lbfgs.linesearch"):
            ls = strong_wolfe(phi, c.f, dphi0, c.g, init_alpha=init_alpha)

        x_new = c.x + ls.alpha * direction
        f_new, g_new = ls.value, ls.grad
        evals = ls.num_evals
        if box is not None:
            x_proj = project_box(x_new, box)
            changed = jnp.any(x_proj != x_new)
            f_new, g_new = lax.cond(
                changed, lambda: value_and_grad_fn(x_proj, data),
                lambda: (f_new, g_new)
            )
            x_new = x_proj
            evals = evals + changed.astype(jnp.int32)

        # A step into a non-finite region is never accepted: the solver
        # stops at the last good iterate (ObjectiveNotImproving).
        ok = finite_step(ls.ok, f_new, g_new, update_axis_name)

        with jax.named_scope("lbfgs.update"):
            s = x_new - c.x
            y = g_new - c.g
            sy = vdot(s, y)
            store = ok & (sy > 1e-10)

            S = jnp.where(store, c.S.at[c.head].set(s), c.S)
            Y = jnp.where(store, c.Y.at[c.head].set(y), c.Y)
            rho = jnp.where(
                store, c.rho.at[c.head].set(1.0 / jnp.maximum(sy, 1e-300)),
                c.rho)
            valid = jnp.where(store, c.valid.at[c.head].set(True), c.valid)
            head = jnp.where(store, (c.head + 1) % m, c.head)

            it_new = c.it + 1
            values = c.values.at[it_new].set(jnp.where(ok, f_new, c.f))
            grad_norms = c.grad_norms.at[it_new].set(
                vnorm(jnp.where(ok, g_new, c.g)))
            # a select, not ``.at[].set``: under ``vmap`` that is a scatter
            # over every lane, and this array is here to be cheap
            evaluations = jnp.where(
                jnp.arange(max_iter + 1) == it_new, evals, c.evaluations)
            x_acc = jnp.where(ok, x_new, c.x)
            iterates = (c.iterates.at[it_new].set(x_acc)
                        if track_iterates else None)

        return _LBFGSCarry(
            it=it_new,
            x=x_acc,
            f=jnp.where(ok, f_new, c.f),
            g=jnp.where(ok, g_new, c.g),
            prev_f=c.f,
            S=S, Y=Y, rho=rho, valid=valid, head=head,
            made_progress=ok,
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


def minimize_lbfgs(
    value_and_grad_fn: Callable[[Array, object], tuple[Array, Array]],
    x0: Array,
    data=None,
    max_iter: int = DEFAULT_MAX_ITER,
    m: int = DEFAULT_M,
    tolerance: float = DEFAULT_TOLERANCE,
    box: Optional[BoxConstraints] = None,
    track_iterates: bool = False,
    resume: Optional[LBFGSResume] = None,
    return_carry: bool = False,
    update_axis_name: Optional[str] = None,
    collective_quant: str = "none",
):
    """Minimize ``f(x, data)`` from ``x0``; returns (x, RunHistory, made_progress).

    ``value_and_grad_fn(x, data)`` must be jit-traceable. Pass the batch via
    ``data`` (a pytree), NOT by closing over it: the function object is a
    static jit argument, so reusing one function across many batches hits the
    compile cache, while a fresh closure per batch would retrace and pin the
    captured arrays in the cache. ``track_iterates`` records per-iteration
    coefficient snapshots into the history (ModelTracker analog).

    ``return_carry=True`` appends a :class:`LBFGSResume` to the return
    tuple; passing it back via ``resume`` continues the solve EXACTLY
    where it stopped (original f₀/‖g₀‖ anchors, curvature history,
    previous objective) — the lane-compaction driver's chunk restarts
    use this to stay bit-identical to a single dispatch.
    """
    from photon_ml_tpu.obs import compile as obs_compile

    return obs_compile.call(
        "optimizer.lbfgs", _minimize_lbfgs_impl,
        (value_and_grad_fn, x0, data, max_iter, m, tolerance, box,
         track_iterates, resume, return_carry, update_axis_name,
         collective_quant),
        static_argnums=(0, 3, 4, 5, 7, 9, 10, 11),
        arg_names=("value_and_grad_fn", "x0", "data", "max_iter", "m",
                   "tolerance", "box", "track_iterates", "resume",
                   "return_carry", "update_axis_name", "collective_quant"))
