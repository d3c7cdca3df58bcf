"""L-BFGS as a single jitted ``lax.while_loop`` kernel.

TPU-native replacement for the reference's Breeze-backed LBFGS
(reference: photon-ml/src/main/scala/com/linkedin/photon/ml/optimization/
LBFGS.scala:42-156 — wraps ``breeze.optimize.LBFGS.iterations`` and projects
each iterate onto box constraints; defaults maxIter=100, m=10, tol=1e-7).

Design: the two-loop recursion runs over a fixed-size history held in
``[m, d]`` device arrays with per-slot validity masks, so the whole solve is
one XLA computation — no host round-trips per iteration (the reference pays a
Spark broadcast + treeAggregate per function evaluation; here a sharded
objective's all-reduce is fused into the loop body).

The history has two layouts, and the call site names the one it needs
(``newest_first``); no option a user can set does:

- circular (the default, every unbatched solve: a fixed effect, a grid fit,
  the factored refit): ``head`` is the next slot to write and a new pair is
  one row written in place. A ``[10, 29.9M]`` history cannot afford more.
- newest-first (the per-entity solves, which run under ``vmap``): slot 0 is
  the newest pair, there is no ``head``, a new pair shifts the rest down.
  Under ``vmap`` the batched ``while_loop`` predicate batches the whole
  carry, so ``head`` would be one index a lane and every ``S[i]`` a gather
  of one row a lane, every ``.at[head].set`` a scatter; a static slot order
  needs neither. Per lane the operations and their order are the circular
  form's: newest to oldest, invalid slots skipped by the same ``where``.

The line search has two forms, and the same call site names the one it
needs (``line_fn``):

- a trial is a full evaluation (the default, every unbatched solve): the
  accepted trial's value and gradient are the next iterate's, so a search
  that accepts its first trial costs one pass over the rows. Those solves
  make 1.2-1.9 evaluations an iteration.
- trials on carried margins (the per-entity solves): one pass forms the
  margins at the iterate and the direction's margins (``line_fn``, scope
  ``objective.line``), every trial is elementwise work on them, and the
  accepted point is evaluated in full once. Under ``vmap`` every lane pays
  for the slowest lane's trials, about 13 an iteration where a lane needs
  5.9, and a full trial reads an ``[E, N, D]`` block twice.

Convergence checks mirror Optimizer.scala:156-170 (see optimize/common.py).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.data.batch import DenseBatch, EllBatch, ProductSumBatch
from photon_ml_tpu.obs.metrics import REGISTRY
from photon_ml_tpu.ops.aggregators import GLMObjective, fused_kernels_for
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
    head: Optional[Array]  # next write slot; None: newest-first history
    made_progress: Array  # bool: last line search succeeded
    values: Array
    grad_norms: Array
    evaluations: Array  # [max_iter+1] int32 (RunHistory.evaluations)
    iterates: Optional[Array]  # [max_iter+1, d] when tracking, else None
    # [max_iter+1] int32 (RunHistory.line_trials); None: full trials
    line_trials: Optional[Array]


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
    still-active lanes' carries between chunks.

    The history is in the layout of the solve that made it (module
    docstring) and goes back into a solve of the same layout: circular
    with its ``head``, or newest-first with ``head=None`` (the per-entity
    solves' carry, an empty pytree leaf that gathers and shards as
    nothing)."""

    x: Array
    f: Array
    g: Array
    prev_f: Array
    S: Array
    Y: Array
    rho: Array
    valid: Array
    head: Optional[Array]
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
                       head: Optional[Array],
                       axis_name: Optional[str] = None,
                       collective_quant: str = "none") -> Array:
    """Two-loop recursion over a masked history buffer: circular with
    ``head`` the next slot to write, or newest-first (slot 0 the newest
    pair) with ``head=None``. Both walk the pairs newest to oldest and back
    and skip invalid slots by the same ``where``; they differ in how a slot
    is found. The circular form indexes with ``head``, which under ``vmap``
    is a gather of one row a lane a step. The newest-first form scans the
    history itself: the scan's counter is no part of any carry, so it stays
    unbatched under ``vmap`` and slices every lane's slot ``i`` at once.

    With ``axis_name`` set, g/S/Y are per-replica shards and every inner
    product is psum'd — the recursion then produces this replica's shard
    of the exact full-dimension direction."""
    vdot = axis_dot(axis_name, collective_quant)
    if head is None:
        return _two_loop_newest_first(g, S, Y, rho, valid, vdot)
    m = S.shape[0]

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


def _two_loop_newest_first(g, S, Y, rho, valid, vdot) -> Array:
    """:func:`two_loop_direction` over a newest-first history: the slots
    are the scans' ``xs``, forward then in reverse."""

    def first_loop(q, slot):
        s_i, y_i, rho_i, valid_i = slot
        a_i = jnp.where(valid_i, rho_i * vdot(s_i, q), 0.0)
        return q - a_i * y_i, a_i

    q, alphas = lax.scan(first_loop, g, (S, Y, rho, valid))

    sy = vdot(S[0], Y[0])
    yy = vdot(Y[0], Y[0])
    gamma = jnp.where(valid[0] & (yy > 0), sy / jnp.maximum(yy, 1e-300), 1.0)
    r = gamma * q

    def second_loop(r, slot):
        s_i, y_i, rho_i, valid_i, a_i = slot
        beta = jnp.where(valid_i, rho_i * vdot(y_i, r), 0.0)
        return r + s_i * (a_i - beta), None

    r, _ = lax.scan(second_loop, r, (S, Y, rho, valid, alphas), reverse=True)
    return -r


def empty_history(m: int, d: int, dtype, newest_first: bool):
    """(S, Y, rho, valid, head) of a solve that has stored no pair yet."""
    return (jnp.zeros((m, d), dtype), jnp.zeros((m, d), dtype),
            jnp.zeros(m, dtype), jnp.zeros(m, bool),
            None if newest_first else jnp.int32(0))


def check_history_layout(resume: LBFGSResume, newest_first: bool) -> None:
    """A carry read in the other layout would solve on, wrongly and in
    silence: slot 0 is the newest pair in one and any pair in the other."""
    if (resume.head is None) != newest_first:
        raise ValueError(
            "a resumed solve takes the history layout of the solve that "
            f"made its carry: newest_first={newest_first} but the carry "
            f"has {'no' if resume.head is None else 'a'} head")


def push_pair(S: Array, Y: Array, rho: Array, valid: Array,
              head: Optional[Array], s: Array, y: Array, sy: Array,
              store: Array):
    """The history after an iteration, (S, Y, rho, valid, head): with the
    pair ``(s, y)`` in it where ``store``, as it was where not. Once ``m``
    pairs are held a new one replaces the oldest. Circular (``head`` an
    index): one row written in place at ``head``, which moves on.
    Newest-first (``head=None``): the pair enters at slot 0 and the rest
    shift down a slot, a whole copy but no index."""
    if head is None:
        def push(row, rows):
            return jnp.where(store, jnp.concatenate([row[None], rows[:-1]]),
                             rows)

        return (push(s, S), push(y, Y),
                push(1.0 / jnp.maximum(sy, 1e-300), rho),
                push(jnp.bool_(True), valid), None)
    m = S.shape[0]
    return (jnp.where(store, S.at[head].set(s), S),
            jnp.where(store, Y.at[head].set(y), Y),
            jnp.where(store,
                      rho.at[head].set(1.0 / jnp.maximum(sy, 1e-300)), rho),
            jnp.where(store, valid.at[head].set(True), valid),
            jnp.where(store, (head + 1) % m, head))


def record(trail: Array, at: Array, value: Array, by_select: bool) -> Array:
    """``trail`` (a RunHistory array) with ``value`` at iteration ``at``.
    ``by_select`` is for a solve under ``vmap``, where ``at`` is one index
    a lane and ``.at[].set`` a scatter over every lane."""
    if by_select:
        return jnp.where(jnp.arange(trail.shape[0]) == at, value, trail)
    return trail.at[at].set(value)


def start_form(data) -> str:
    """The form of a fresh solve's start for its ``data``, read from the
    batch's layout type and the fused kernel's gate, as no option can:
    ``in_loop`` where the data holds an ``EllBatch``; ``product_sum`` where
    it holds a ``DenseBatch`` that the gate (``fused_kernels_for``, at the
    objective's ``axis_name``) sends to the two-pass form on a TPU (on
    another backend that form's einsums are float32 products and sums
    already); ``direct`` for every other batch (the fused kernel's dense
    batches, the factored refit's)."""
    def layout_or_objective(node):
        return isinstance(node, (EllBatch, DenseBatch, GLMObjective))

    nodes = jax.tree.leaves(data, is_leaf=layout_or_objective)
    if any(isinstance(node, EllBatch) for node in nodes):
        return "in_loop"
    axis_name = next((node.axis_name for node in nodes
                      if isinstance(node, GLMObjective)), None)
    if jax.default_backend() == "tpu" and any(
            isinstance(node, DenseBatch)
            and fused_kernels_for("value_and_grad", node, axis_name) is None
            for node in nodes):
        return "product_sum"
    return "direct"


def start_evaluation(value_and_grad_fn, x0: Array, data):
    """The value and gradient at ``x0`` that a fresh solve starts from, in
    the form the batch's layout needs (:func:`start_form`). Made at the top
    level of the program, a row-sparse batch's (``EllBatch``) gather reads
    its table, its indices and its output from HBM, at twice a line-search
    trial's time a slot (PERF.md, section 5). The line search evaluates
    inside a loop, on the table ``x + a*d`` its body makes, and there the
    compiler places all three in VMEM. So on that layout the start is
    evaluated the same way (``in_loop``): in a loop, on a table its body
    makes by a select, which keeps every bit of ``x0`` (``x0 + 0*x0`` would
    turn an infinite entry into NaN). The loop stops on a flag it sets, not
    on a counted trip, which the compiler would see through and inline.

    A dense batch's two-pass start (``product_sum``) is made on the same
    leaves as a ``ProductSumBatch``: float32 products and sums, the
    arithmetic the line search's trials get inside the loop. At the top
    level the TPU compiler may make the einsums MXU convolutions over
    operands cut to bfloat16 (it does for the per-entity solves under
    ``vmap``), exact from a zero start (``X 0 = 0``) but not from a warm
    one, where the search would test float32 trials against a start of
    another precision (PERF.md, section 6). The fused kernel's starts
    (``direct``) already run at its loop's precision and stay the direct
    call. Booked on ``solver_start_lowerings{site, form}`` at trace time,
    as ``objective_lowerings`` is."""
    form = start_form(data)
    REGISTRY.counter("solver_start_lowerings").inc(
        site="optimizer.lbfgs", form=form)
    if form == "direct":
        return value_and_grad_fn(x0, data)
    if form == "product_sum":
        with jax.named_scope("lbfgs.start"):
            return value_and_grad_fn(x0, jax.tree.map(
                lambda node: (ProductSumBatch(*node)
                              if isinstance(node, DenseBatch) else node),
                data, is_leaf=lambda node: isinstance(node, DenseBatch)))

    def body(c):
        done, _, _ = c
        return (jnp.bool_(True),
                *value_and_grad_fn(jnp.where(done, jnp.zeros_like(x0), x0),
                                   data))

    with jax.named_scope("lbfgs.start"):
        _, f, g = lax.while_loop(
            lambda c: ~c[0], body,
            (jnp.bool_(False), jnp.zeros((), x0.dtype), jnp.zeros_like(x0)))
    return f, g


@partial(jax.jit, static_argnums=(0, 3, 4, 5, 7, 9, 10, 11, 12, 13))
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
    newest_first: bool = False,
    line_fn=None,
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
    # ``newest_first``: the history layout of a solve under ``vmap`` (module
    # docstring); its RunHistory is written by select for the same reason.
    # ``line_fn(x, d, data)``: the line search's trials on carried margins
    # (module docstring); it returns ``phi(a) -> (f, grad . d)`` at
    # ``x + a d``.
    if update_axis_name is not None and (box is not None or track_iterates):
        raise ValueError(
            "sharded weight update supports neither box constraints nor "
            "track_iterates")
    if line_fn is not None and box is not None:
        raise ValueError("trials on carried margins take no box constraints")
    vdot = axis_dot(update_axis_name, collective_quant)
    vnorm = axis_norm(update_axis_name, collective_quant)
    d = x0.shape[0]
    dtype = x0.dtype
    if resume is None:
        f_start, g_start = start_evaluation(value_and_grad_fn, x0, data)
        anchor_f0 = f_start
        anchor_g0n = vnorm(g_start)
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
        line_trials=(None if line_fn is None
                     else jnp.zeros(max_iter + 1, jnp.int32)),
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

        if line_fn is None:
            g_search = c.g

            def phi(a):
                x_a = c.x + a * direction
                f_a, g_a = value_and_grad_fn(x_a, data)
                return f_a, vdot(g_a, direction), g_a
        else:
            # the search carries no gradient: the accepted point's is made
            # below, once
            g_search = jnp.zeros((), dtype)
            phi_line = line_fn(c.x, direction, data)

            def phi(a):
                return (*phi_line(a), g_search)

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
            ls = strong_wolfe(phi, c.f, dphi0, g_search,
                              init_alpha=init_alpha)

        x_new = c.x + ls.alpha * direction
        if line_fn is None:
            f_new, g_new = ls.value, ls.grad
            evals = ls.num_evals
        else:
            # made whether or not the search found a step (under ``vmap``
            # a conditional would run it for every lane anyway); kept only
            # where it did, by ``ok`` below
            f_new, g_new = value_and_grad_fn(x_new, data)
            evals = jnp.int32(1)
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

            S, Y, rho, valid, head = push_pair(
                c.S, c.Y, c.rho, c.valid, c.head, s, y, sy, store)

            it_new = c.it + 1
            values = record(c.values, it_new, jnp.where(ok, f_new, c.f),
                            newest_first)
            grad_norms = record(c.grad_norms, it_new,
                                vnorm(jnp.where(ok, g_new, c.g)),
                                newest_first)
            # always by select: this array is here to be cheap
            evaluations = record(c.evaluations, it_new, evals, True)
            line_trials = (None if line_fn is None else record(
                c.line_trials, it_new, ls.num_evals, True))
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
            iterates=iterates, line_trials=line_trials,
        )

    final = lax.while_loop(cond, body, init)
    history = RunHistory(values=final.values, grad_norms=final.grad_norms,
                         num_iterations=final.it, iterates=final.iterates,
                         evaluations=final.evaluations,
                         line_trials=final.line_trials)
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
    newest_first: bool = False,
    line_fn=None,
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

    ``newest_first`` is for the caller that runs this solve under ``vmap``
    (the per-entity solves): the curvature history, and the carry, in the
    layout that needs no per-lane index (module docstring). Every other
    caller leaves it off and keeps the in-place circular history.

    ``line_fn(x, d, data)``, a function as static as ``value_and_grad_fn``
    (``GLMObjective.line`` behind the same payload), is for that caller
    too: the line search's trials on margins carried from one pass an
    iteration, the accepted point evaluated in full (module docstring).
    ``RunHistory.evaluations`` then counts the full evaluations (the start
    and one an iteration) and ``RunHistory.line_trials`` the trials.
    """
    from photon_ml_tpu.obs import compile as obs_compile

    return obs_compile.call(
        "optimizer.lbfgs", _minimize_lbfgs_impl,
        (value_and_grad_fn, x0, data, max_iter, m, tolerance, box,
         track_iterates, resume, return_carry, update_axis_name,
         collective_quant, newest_first, line_fn),
        static_argnums=(0, 3, 4, 5, 7, 9, 10, 11, 12, 13),
        arg_names=("value_and_grad_fn", "x0", "data", "max_iter", "m",
                   "tolerance", "box", "track_iterates", "resume",
                   "return_carry", "update_axis_name", "collective_quant",
                   "newest_first", "line_fn"))
