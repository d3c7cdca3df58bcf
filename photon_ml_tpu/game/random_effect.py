"""Random-effect solver: vmapped local optimizers over entity blocks.

TPU-native replacement for the reference's per-entity solve
(reference: photon-ml/src/main/scala/com/linkedin/photon/ml/algorithm/
RandomEffectCoordinate.scala:104-113 — a 3-way join of activeData ⋈ problems ⋈
models followed by ``mapValues(localProblem.run)``, i.e. one Breeze L-BFGS per
entity running data-local on a Spark executor).

Here every entity's subproblem lives in one padded tensor
``[E, N_max, D_red]`` and the *same* jitted solver kernels
(optimize/lbfgs.py, owlqn.py, tron.py) are ``vmap``ped over the entity
axis — XLA batches the two-loop recursion / line search / trust-region CG
across entities, so thousands of tiny solves become large MXU matmuls. Sharding the entity axis over the mesh
(``pjit``) reproduces Spark's embarrassing parallelism with zero communication
in the hot loop (SURVEY §2.2, §5.8).

Heterogeneous convergence (SURVEY §7 hard part 2) is handled by the batched
``lax.while_loop``: lanes that converged keep their state via the per-lane
convergence predicate in ``should_continue`` — the loop runs until every lane
is done, converged lanes' updates are masked out by the line-search failure
path costing only wasted FLOPs, never wrong results.
"""

from __future__ import annotations

import dataclasses
import logging
from functools import lru_cache, partial
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from photon_ml_tpu.data.batch import DenseBatch
from photon_ml_tpu.game.dataset import RandomEffectDataset
from photon_ml_tpu.obs import compile as obs_compile
from photon_ml_tpu.obs import trace
from photon_ml_tpu.obs.metrics import REGISTRY
from photon_ml_tpu.ops.aggregators import GLMObjective
from photon_ml_tpu.ops.losses import get_loss
from photon_ml_tpu.optimize.common import (
    LaneCompactionState,
    padded_lane_count,
    solver_x0,
)
from photon_ml_tpu.optimize.config import (
    GLMOptimizationConfiguration,
    OptimizerType,
    TASK_LOSS_NAME,
    TaskType,
)
from photon_ml_tpu.optimize.lbfgs import minimize_lbfgs
from photon_ml_tpu.optimize.owlqn import minimize_owlqn
from photon_ml_tpu.optimize.tron import minimize_tron
from photon_ml_tpu.parallel.mesh import ENTITY_AXIS, get_default_mesh
from photon_ml_tpu.utils.faults import fault_point

Array = jnp.ndarray

logger = logging.getLogger(__name__)

# Per-entity convergence codes (RandomEffectOptimizationTracker.
# countsByConvergence analog; names match ConvergenceReason values).
CONV_MAX_ITERATIONS = 0
CONV_FUNCTION_VALUES = 1
CONV_GRADIENT = 2
CONV_NOT_PROGRESSED = 3
CONVERGENCE_CODE_NAMES = {
    CONV_MAX_ITERATIONS: "MaxIterations",
    CONV_FUNCTION_VALUES: "FunctionValuesConverged",
    CONV_GRADIENT: "GradientConverged",
    CONV_NOT_PROGRESSED: "ObjectiveNotImproving",
}


class LaneCounts(NamedTuple):
    """Objective evaluations of one coordinate's batched solves: what each
    lane needed against what the batched loops ran (see
    :func:`_fit_blocks_impl` for what ``rounds`` can and cannot see). The
    fields are ``RandomEffectTracker``'s, which takes them as they are."""

    evaluations: Array  # [E] int32: each lane's own count
    # per dispatched program (a bucket, a compaction chunk, a shard):
    evaluation_rounds: Array  # [B] int32: the rounds it ran
    bucket_lanes: np.ndarray  # [B] host: its lanes, pads included
    site: str  # the obs/compile.py site the programs dispatched through
    line_trials: Array  # [E] int32: each lane's line-search trials


def _vg(w, payload):
    obj, batch = payload
    return obj.calculate(w, batch)


def _line(w, d, payload):
    obj, batch = payload
    return obj.line(w, d, batch)


def _hvp(w, v, payload):
    obj, batch = payload
    return obj.hessian_vector(w, v, batch)


# Per-solve schedule counts, the seam the compaction and sharding tests
# observe the per-entity solve through: ``lane_counts`` is the still-active
# lane count entering each compacted chunk. The ``shard_*`` keys account
# the mesh-sharded path: real vs power-of-two padded lanes per sharded
# dispatch and a rolling window of per-shard active-lane counts (the
# load-balance signal). The intervals themselves are the ``re.solve`` /
# ``re.compact_chunk`` spans.
SOLVE_STATS = {"dispatches": 0, "chunks": 0, "lane_counts": [],
               "shard_real_lanes": 0, "shard_padded_lanes": 0,
               "shard_lane_counts": []}


def reset_solve_stats() -> None:
    SOLVE_STATS.update({"dispatches": 0, "chunks": 0, "lane_counts": [],
                        "shard_real_lanes": 0, "shard_padded_lanes": 0,
                        "shard_lane_counts": []})


#: ``lane_compaction_chunk`` sentinel (driver flag value ``auto``): the
#: chunk size is chosen — and re-tuned between solves — by
#: :class:`ChunkAutoTuner` from the observed per-chunk active-lane decay.
AUTO_COMPACTION_CHUNK = -1

#: ``--re-entity-shards`` sentinel (flag value ``auto``): put EVERY local
#: device on the mesh entity axis (the driver resolves this to the device
#: count before building the mesh; kept an int so run-manifest flags stay
#: scalar).
AUTO_ENTITY_SHARDS = -1


def _pow2_at_most(x: int) -> int:
    return 1 << max(int(x).bit_length() - 1, 0)


class ChunkAutoTuner:
    """Feedback controller for the lane-compaction chunk size.

    The data source is the per-chunk active-lane sequence each compacted
    solve produces (the same counts the ``re_chunk_active_lanes``
    histogram aggregates — the ROADMAP item's promised signal): the
    fraction of lanes still active after a solve's FIRST chunk says
    whether the chunk budget was matched to the convergence profile.

    - survival > 0.75: the chunk is shedding too few lanes to pay for
      its per-chunk host fetch + re-pack → double it;
    - survival < 0.25: most lanes idled through the tail of the chunk
      before compaction could shed them → halve it;
    - in between: keep it.

    One tuner per coordinate problem (created lazily by
    :class:`RandomEffectOptimizationProblem` — the problem instance
    lives across sweeps, so feedback accumulates, while two coordinates
    with IDENTICAL configs but opposite convergence profiles still tune
    independently instead of ping-ponging one shared entry). State is
    keyed per (solver, max_iterations) within the instance and clamped
    to [4, max_iterations); a probe chunk of ``~max_iterations / 4``
    (power of two, for compile-shape reuse) seeds each key. Chunk sizes
    stay powers of two so re-tuning between sweeps revisits previously
    compiled shapes instead of growing the jit cache without bound.
    """

    MIN_CHUNK = 4

    def __init__(self):
        self._chunks: dict = {}

    def chunk_for(self, solver: str, max_iterations: int) -> int:
        if max_iterations <= self.MIN_CHUNK:
            return 0  # nothing to chunk: single dispatch
        key = (solver, max_iterations)
        c = self._chunks.get(key)
        if c is None:
            c = max(self.MIN_CHUNK, _pow2_at_most(max_iterations // 4))
            self._chunks[key] = c
        return c

    def update(self, solver: str, max_iterations: int,
               lane_counts: list) -> None:
        """Feed one solve's per-chunk active-lane sequence back."""
        if max_iterations <= self.MIN_CHUNK or not lane_counts:
            return
        key = (solver, max_iterations)
        c = self._chunks.get(key)
        if c is None or lane_counts[0] <= 0:
            return
        if len(lane_counts) == 1:
            # everything converged inside one chunk: the budget was
            # bigger than the straggler tail needed
            survival = 0.0
        else:
            survival = lane_counts[1] / lane_counts[0]
        if survival > 0.75:
            c *= 2
        elif survival < 0.25:
            c //= 2
        self._chunks[key] = min(max(c, self.MIN_CHUNK),
                                _pow2_at_most(max_iterations - 1))


def _fit_blocks_impl(
    X: Array,
    labels: Array,
    offsets: Array,
    weights: Array,
    initial: Array,
    obj: GLMObjective,
    l1: Array,
    solver: str,
    max_iter: int,
    tolerance: float,
    boundary_convergence: bool = False,
    resume=None,
    return_carry: bool = False,
):
    """vmapped solve over entity blocks; returns (coefs [E,D], iters [E],
    final loss values [E], convergence codes [E] int8 — see
    CONVERGENCE_CODE_NAMES — evaluations [E] int32, line trials [E] int32,
    rounds [1] int32), plus a per-lane solver carry when
    ``return_carry``. ``solver`` is "lbfgs"/"owlqn"/"tron".

    ``evaluations`` is each lane's own count of objective evaluations
    (the sum of its ``RunHistory.evaluations``: what its solo solve would
    make); ``line trials`` its ``RunHistory.line_trials`` (L-BFGS, whose
    search tries its steps on carried margins here: below), 0 for the other
    solvers. ``rounds`` = Σ_k max over lanes of ``evaluations[k]``: the
    rounds of evaluation the batched loop ran for this dispatch, as far as
    the lanes themselves can tell. The solvers' loops evaluate once a pass
    and under no conditional (a batched ``lax.switch``/``lax.cond`` runs
    every branch for every lane, and the two-stage line search paid two
    evaluations a pass that way: it no longer does), so while every lane is
    still solving, ``rounds`` IS what the device executed: the batched
    outer loop lines the lanes' iterations up, and in each the inner loop
    makes as many passes as its slowest lane needs. What still escapes it:
    a finished lane rides every later iteration, replaying the step after
    its last, and the trials it makes there are discarded, so they are not
    visible from inside ``vmap``; where such a lane needs more trials than
    any lane still solving, the device ran more than ``rounds``. (And an
    evaluation that does sit under a ``lax.cond``, the box projection's in
    ``lbfgs.py``, runs for every lane once any lane needs it.) Shape [1],
    so a sharded dispatch concatenates one per shard.

    ``boundary_convergence`` is set by the lane-compaction driver on
    NON-final chunks: a lane that satisfies a convergence criterion on
    exactly its last budgeted iteration then reports that criterion
    instead of MaxIterations, so it leaves the active set with its true
    reason rather than being re-dispatched from its optimum. The
    default preserves the host-ordering classification
    (Optimizer.scala:156-170): max-iterations wins.

    ``resume`` is the previous chunk's per-lane carry (lane-compacted by
    the caller): the solvers continue their loop state verbatim and every
    convergence check stays anchored to the ORIGINAL dispatch's f₀/‖g₀‖,
    so a chunked solve is bit-identical to the single dispatch.

    L-BFGS and OWL-QN are asked for their newest-first curvature history
    here (``newest_first=True``, optimize/lbfgs.py): this is the one call
    site that runs them under ``vmap``, where the circular history's
    ``head`` would be one index a lane and every slot read a gather of one
    row a lane. All four fit paths (this dispatch, the compacted one, and
    both sharded ones) trace this function, so all carry that layout, and
    the ``LBFGSResume`` they pass between chunks has ``head=None``.

    L-BFGS is asked for trials on carried margins too (``line_fn``,
    optimize/lbfgs.py), for the same reason: under ``vmap`` every lane pays
    for the slowest lane's trials, and a full trial reads the ``[E, N, D]``
    block twice where a trial on the margins reads ``[E, N]`` arrays. An
    L-BFGS lane then makes its start and one full evaluation an iteration,
    so ``rounds`` is 1 + the most iterations any lane made; the trials are
    in no round, and the one pass an iteration that forms the margins in no
    count. The carry stays an ``LBFGSResume`` (the margins are formed again
    from its iterate)."""

    def solve_one(Xe, ye, oe, we, x0, res):
        batch = DenseBatch(X=Xe, labels=ye, offsets=oe, weights=we)
        if solver == "owlqn":
            out = minimize_owlqn(
                _vg, x0, (obj, batch), l1=l1,
                max_iter=max_iter, tolerance=tolerance,
                resume=res, return_carry=return_carry, newest_first=True)
        elif solver == "tron":
            out = minimize_tron(
                _vg, _hvp, x0, (obj, batch),
                max_iter=max_iter, tolerance=tolerance,
                resume=res, return_carry=return_carry)
        else:
            out = minimize_lbfgs(
                _vg, x0, (obj, batch),
                max_iter=max_iter, tolerance=tolerance,
                resume=res, return_carry=return_carry, newest_first=True,
                line_fn=_line)
        x, hist, progressed = out[:3]
        carry = out[3] if return_carry else None
        k = hist.num_iterations
        final_value = hist.values[k]
        # Per-lane convergence classification mirroring the HOST ordering
        # of Optimizer.getConvergenceReason (Optimizer.scala:156-170 port,
        # optimize/common._convergence_reason): max-iterations, then
        # not-progressed, then function values, then gradient; the
        # total-function fallback is FunctionValuesConverged like the host.
        # A lane that stalls with an unchanged objective therefore reports
        # ObjectiveNotImproving, keeping tracker counts aligned with the
        # reference's countsByConvergence. On a resumed chunk the
        # thresholds anchor to the ORIGINAL dispatch's f₀/‖g₀‖ and a
        # k==0 exit compares against the pre-boundary value — the checks
        # the uninterrupted loop would have run.
        if res is None:
            f0_anchor = hist.values[0]
            g0n_anchor = hist.grad_norms[0]
            prev_value = hist.values[jnp.maximum(k - 1, 0)]
            fv_gate = k >= 1
        else:
            f0_anchor = res.f0
            g0n_anchor = res.g0n
            prev_value = jnp.where(k >= 1,
                                   hist.values[jnp.maximum(k - 1, 0)],
                                   res.prev_f)
            fv_gate = True
        fv = fv_gate & (jnp.abs(final_value - prev_value)
                        <= tolerance * jnp.abs(f0_anchor))
        gv = hist.grad_norms[k] <= tolerance * g0n_anchor
        converged = jnp.where(~progressed, CONV_NOT_PROGRESSED,
                              jnp.where(fv, CONV_FUNCTION_VALUES,
                                        jnp.where(gv, CONV_GRADIENT,
                                                  CONV_FUNCTION_VALUES)))
        if boundary_convergence:
            # chunk boundary: an exhausted budget only means MaxIterations
            # when no criterion fired on the final iteration
            exhausted = jnp.where(
                ~progressed, CONV_NOT_PROGRESSED,
                jnp.where(fv, CONV_FUNCTION_VALUES,
                          jnp.where(gv, CONV_GRADIENT,
                                    CONV_MAX_ITERATIONS)))
        else:
            exhausted = CONV_MAX_ITERATIONS
        code = jnp.where(k >= max_iter, exhausted, converged)
        trials = (jnp.zeros_like(hist.evaluations)
                  if hist.line_trials is None else hist.line_trials)
        return (x, k, final_value, code.astype(jnp.int8), hist.evaluations,
                trials, carry)

    with jax.named_scope("re.solve"):
        if resume is None:
            out = jax.vmap(
                lambda Xe, ye, oe, we, x0: solve_one(Xe, ye, oe, we, x0,
                                                     None)
            )(X, labels, offsets, weights, initial)
        else:
            out = jax.vmap(solve_one)(X, labels, offsets, weights, initial,
                                      resume)
    # [E, max_iter+1] each
    x, k, final_value, code, evals_by_iter, trials_by_iter, carry = out
    counted = (x, k, final_value, code, jnp.sum(evals_by_iter, axis=1),
               jnp.sum(trials_by_iter, axis=1),
               jnp.sum(jnp.max(evals_by_iter, axis=0), keepdims=True))
    return counted + (carry,) if return_carry else counted


_STATIC = ("solver", "max_iter", "tolerance", "boundary_convergence",
           "return_carry")
_fit_blocks = partial(jax.jit, static_argnames=_STATIC)(_fit_blocks_impl)
# Donating variants, only engaged off-CPU (the CPU runtime can't alias and
# would warn per call) and only for callers that own the buffers:
# - offsets (arg 2) is rebuilt per update from the CD score vector, so the
#   coordinate-update path may always hand its buffer to XLA as scratch;
# - initial/x0 (arg 4) is donated ONLY by the compacted re-dispatch path,
#   whose x0 is a gather this module just created. The plain path's x0 can
#   BE the caller's live array (solver_x0 returns a matching-dtype warm
#   start unchanged — i.e. coordinate descent's states[cid] last-good
#   state, which retries/quarantine/checkpointing must still read), so
#   donating it there would delete state out from under the CD loop.
_fit_blocks_donate_offsets = partial(
    jax.jit, static_argnames=_STATIC, donate_argnums=(2,),
)(_fit_blocks_impl)
_fit_blocks_donate_offsets_x0 = partial(
    jax.jit, static_argnames=_STATIC, donate_argnums=(2, 4),
)(_fit_blocks_impl)


# (variant, shapes, dtypes, statics) signatures already dispatched: a key
# not seen before is about to pay an XLA trace+compile (the in-process jit
# cache misses exactly there), so the ``retraces{site="re.dispatch"}``
# counter tracks bucketed-dispatch compile pressure — host-side bookkeeping
# only, no device work.
_SEEN_DISPATCH_KEYS: set = set()


def _dispatch_fit(X, labels, offsets, weights, initial, obj, l1, solver,
                  max_iter, tolerance, donate: bool,
                  donate_x0: bool = False,
                  boundary_convergence: bool = False,
                  resume=None, return_carry: bool = False):
    SOLVE_STATS["dispatches"] += 1
    fn = _fit_blocks
    if donate and jax.default_backend() != "cpu":
        # the resumed-chunk path passes the gathered carry's x as BOTH
        # the x0 arg and a resume leaf — never donate x0 there (aliasing
        # a donated buffer with a live arg is a runtime error)
        fn = (_fit_blocks_donate_offsets_x0
              if donate_x0 and resume is None
              else _fit_blocks_donate_offsets)
    key = (id(fn), tuple(X.shape), str(X.dtype), tuple(initial.shape),
           str(initial.dtype), solver, max_iter, float(tolerance),
           boundary_convergence, resume is not None, return_carry)
    if key not in _SEEN_DISPATCH_KEYS:
        _SEEN_DISPATCH_KEYS.add(key)
        REGISTRY.counter("retraces").inc(site="re.dispatch")
    # statics by position in _fit_blocks_impl's signature (the _STATIC
    # names): solver=7, max_iter=8, tolerance=9, boundary_convergence=10,
    # return_carry=12 — obs.compile strips them for the AOT fastpath
    return obs_compile.call(
        "re.fit_blocks", fn,
        (X, labels, offsets, weights, initial, obj, l1, solver,
         max_iter, tolerance, boundary_convergence, resume, return_carry),
        static_argnums=(7, 8, 9, 10, 12),
        arg_names=("X", "labels", "offsets", "weights", "initial", "obj",
                   "l1", "solver", "max_iter", "tolerance",
                   "boundary_convergence", "resume", "return_carry"))


def _fit_blocks_compacted(X, labels, offsets, weights, x0, obj, l1,
                          solver, max_iter, tolerance, chunk: int,
                          donate: bool,
                          lane_seq: Optional[list] = None):
    """Chunked solve with active-lane compaction (Snap ML-style: don't pay
    straggler cost for converged subproblems).

    Runs the batched solver ``chunk`` iterations at a time; after each
    chunk the lanes that converged keep their results and only the
    still-active lanes are gathered into a dense (power-of-two padded)
    block and re-dispatched. A bucket where 90% of entities converge in 5
    iterations then costs ~10% of the lanes for the straggler tail instead
    of running every lane to the slowest lane's count. Each chunk costs
    one small device→host fetch (the unconverged mask).

    Restarts are EXACT: each non-final chunk also returns the solvers'
    per-lane carry (iterate, curvature history / trust region, previous
    objective, ORIGINAL f₀/‖g₀‖ anchors — LBFGSResume/TRONResume), which
    is gathered down to the still-active lanes and resumed, so the
    chunked solve runs bit-identically to the single dispatch instead of
    re-anchoring its relative tolerances at every boundary."""
    state = LaneCompactionState.initial(x0, x0.dtype)
    idx: Optional[np.ndarray] = None
    carry = None  # previous chunk's per-lane solver carry (device)
    cur = (X, labels, offsets, weights, x0)
    rounds, lanes = [], []  # per chunk dispatch (LaneCounts)
    spent = 0
    chunk_index = 0
    while True:
        budget = min(chunk, max_iter - spent)
        final_chunk = spent + budget >= max_iter
        # span per chunk, labeled with the REAL active-lane count entering
        # it (not the power-of-two padded dispatch width): the shrinking
        # sequence IS the iteration histogram the ROADMAP chunk-size
        # auto-tuner needs, and the ``re_chunk_active_lanes`` histogram
        # aggregates it across the run
        active_lanes = int(X.shape[0]) if idx is None else int(len(idx))
        if lane_seq is not None:  # the auto-tuner's feedback signal
            lane_seq.append(active_lanes)
        with trace.span("re.compact_chunk", chunk=chunk_index,
                        active_lanes=active_lanes, budget=budget):
            # chunk 1 runs the caller's buffers (which later compactions
            # re-gather from: never donate them); compacted chunks run
            # gathered copies this loop owns outright — but x0 doubles as
            # the carry's live iterate on resumed chunks, so only the
            # offsets buffer is donated there. Non-final chunks classify
            # boundary convergence so a lane converging on its last
            # budgeted iteration leaves with its true reason instead of
            # a re-dispatch from its optimum.
            donate_chunk = donate and idx is not None
            out = _dispatch_fit(*cur, obj, l1, solver, budget,
                                tolerance, donate=donate_chunk,
                                donate_x0=donate_chunk,
                                boundary_convergence=not final_chunk,
                                resume=carry,
                                return_carry=not final_chunk)
            c, it, v, k, ev, tr, chunk_rounds = out[:7]
            new_carry = None if final_chunk else out[7]
            rounds.append(chunk_rounds)
            lanes.append(int(c.shape[0]))
            still, still_local = state.absorb(idx, c, it, ev, v, k,
                                              CONV_MAX_ITERATIONS, tr)
        REGISTRY.histogram("re_chunk_active_lanes").observe(active_lanes)
        SOLVE_STATS["chunks"] += 1
        chunk_index += 1
        spent += budget
        if spent >= max_iter or len(still) == 0:
            break
        idx = still
        pad = padded_lane_count(len(still))
        idx_padded = np.concatenate(
            [still, np.full(pad - len(still), still[0], np.int32)])
        g = jax.device_put(idx_padded)
        # data tensors gather by GLOBAL lane id; the carry gathers by the
        # lanes' LOCAL positions within the chunk that produced it
        local_padded = np.concatenate(
            [still_local,
             np.full(pad - len(still_local), still_local[0], np.int32)])
        gl = jax.device_put(local_padded)
        carry = jax.tree_util.tree_map(
            lambda leaf: jnp.take(leaf, gl, axis=0), new_carry)
        cur = (jnp.take(X, g, axis=0), jnp.take(labels, g, axis=0),
               jnp.take(offsets, g, axis=0), jnp.take(weights, g, axis=0),
               carry.x)
        # bounded telemetry: long training runs append per compaction and
        # only tests ever reset, so keep a rolling window
        SOLVE_STATS["lane_counts"] = (
            SOLVE_STATS["lane_counts"][-63:] + [int(len(still))])
    return _with_counts(state.results(), rounds, lanes)


def _with_counts(out, rounds: list, lanes: list,
                 site: str = "re.fit_blocks"):
    """(coefs, iters, values, codes, evaluations, line trials) of one
    block plus the rounds and lane counts of the programs that solved it
    -> the 5-tuple :meth:`RandomEffectOptimizationProblem._fit` returns."""
    return tuple(out[:4]) + (LaneCounts(
        out[4], jnp.concatenate(rounds), np.asarray(lanes, np.int64),
        site, out[5]),)


# ---------------------------------------------------------------------------
# Mesh-sharded dispatch: the entity axis of a bucket is split over the mesh
# ENTITY_AXIS (parallel/mesh.py) via shard_map — every device runs the SAME
# vmapped solver kernel on its local lane slice, with ZERO collectives inside
# the solve loop (entity subproblems are independent; the reference's Spark
# embarrassing parallelism made explicit). Only the score exchange reduces
# across shards, with an on-device psum (see _sharded_score_fn).
# ---------------------------------------------------------------------------


@lru_cache(maxsize=64)
def _sharded_fit_fn(mesh, solver, max_iter, tolerance,
                    boundary_convergence, return_carry):
    """shard_map + jit of the block solve for a FULL (unpadded) dispatch:
    lane-leading arrays split over the entity axis, obj/l1 replicated.
    Cached per (mesh, statics) so repeat dispatches reuse the executable
    instead of re-tracing a fresh closure per call."""
    from photon_ml_tpu.parallel.distributed import _shard_map

    lane = P(ENTITY_AXIS)

    def impl(X, labels, offsets, weights, initial, obj, l1):
        return _fit_blocks_impl(X, labels, offsets, weights, initial, obj,
                                l1, solver, max_iter, tolerance,
                                boundary_convergence, None, return_carry)

    fit = _shard_map(impl, mesh,
                     in_specs=(lane, lane, lane, lane, lane, P(), P()),
                     out_specs=tuple([lane] * (8 if return_carry else 7)))
    return jax.jit(fit)


@lru_cache(maxsize=64)
def _sharded_resume_fit_fn(mesh, solver, max_iter, tolerance,
                           boundary_convergence, return_carry):
    """shard_map + jit of a RESUMED compacted dispatch. The still-active
    lane gather happens ON DEVICE inside the sharded program: each shard
    receives its ``[1, L]`` row of local data ids / carry positions,
    gathers its own lanes from its resident slice of the full block and
    the previous chunk's carry, and resumes — the host never re-packs
    data tensors, it only computes the tiny id arrays from the one
    unconverged-mask fetch per chunk."""
    from photon_ml_tpu.parallel.distributed import _shard_map

    lane = P(ENTITY_AXIS)

    def impl(X, labels, offsets, weights, idx_data, idx_carry, obj, l1,
             carry):
        idx_d = idx_data.reshape(-1)
        idx_c = idx_carry.reshape(-1)
        res = jax.tree_util.tree_map(
            lambda leaf: jnp.take(leaf, idx_c, axis=0), carry)
        return _fit_blocks_impl(
            jnp.take(X, idx_d, axis=0), jnp.take(labels, idx_d, axis=0),
            jnp.take(offsets, idx_d, axis=0),
            jnp.take(weights, idx_d, axis=0),
            res.x, obj, l1, solver, max_iter, tolerance,
            boundary_convergence, res, return_carry)

    fit = _shard_map(
        impl, mesh,
        in_specs=(lane, lane, lane, lane, lane, lane, P(), P(), lane),
        out_specs=tuple([lane] * (8 if return_carry else 7)))
    return jax.jit(fit)


def _note_shard_dispatch(kind: str, fn, X, extra=()) -> None:
    SOLVE_STATS["dispatches"] += 1
    key = (kind, id(fn), tuple(X.shape), str(X.dtype)) + tuple(extra)
    if key not in _SEEN_DISPATCH_KEYS:
        _SEEN_DISPATCH_KEYS.add(key)
        REGISTRY.counter("retraces").inc(site="re.shard_dispatch")


def _dispatch_fit_sharded(mesh, X, labels, offsets, weights, initial, obj,
                          l1, solver, max_iter, tolerance,
                          boundary_convergence: bool = False,
                          return_carry: bool = False):
    fn = _sharded_fit_fn(mesh, solver, max_iter, float(tolerance),
                         boundary_convergence, return_carry)
    _note_shard_dispatch("shard", fn, X)
    # a full dispatch has no pad lanes: real == padded
    SOLVE_STATS["shard_real_lanes"] += int(X.shape[0])
    SOLVE_STATS["shard_padded_lanes"] += int(X.shape[0])
    return obs_compile.call(
        "re.shard_fit_blocks", fn,
        (X, labels, offsets, weights, initial, obj, l1),
        arg_names=("X", "labels", "offsets", "weights", "initial", "obj",
                   "l1"))


def _dispatch_fit_sharded_resume(mesh, X, labels, offsets, weights,
                                 idx_data, idx_carry, obj, l1, carry,
                                 solver, max_iter, tolerance,
                                 boundary_convergence: bool,
                                 return_carry: bool):
    fn = _sharded_resume_fit_fn(mesh, solver, max_iter, float(tolerance),
                                boundary_convergence, return_carry)
    _note_shard_dispatch("shard_resume", fn, X,
                         extra=(tuple(idx_data.shape),))
    return obs_compile.call(
        "re.shard_fit_blocks", fn,
        (X, labels, offsets, weights, idx_data, idx_carry, obj, l1, carry),
        arg_names=("X", "labels", "offsets", "weights", "idx_data",
                   "idx_carry", "obj", "l1", "carry"))


def _fit_blocks_compacted_sharded(mesh, shards: int, X, labels, offsets,
                                  weights, x0, obj, l1, solver,
                                  max_iter, tolerance, chunk: int,
                                  lane_seq: Optional[list] = None):
    """Sharded variant of :func:`_fit_blocks_compacted`: lane compaction
    with PER-SHARD power-of-two padding. A lane's home shard never changes
    (global id // lanes_per_shard), so after each chunk the host partitions
    the still-active ids by owner, pads every shard's list to one shared
    power-of-two width L (a ragged per-shard width would be a different
    program shape per shard), and dispatches ``[K, L]`` local-id arrays —
    the data/carry gathers run on device inside the sharded program.

    Pad slots duplicate one of the shard's own carried lanes; a shard with
    NO active lanes re-resolves one of its converged lanes, which is an
    exact no-op (resuming a converged carry fails the loop predicate
    immediately and writes back the value it already holds). Results are
    folded with :meth:`LaneCompactionState.absorb_padded`, which masks pad
    slots out of the iteration scatter-add. Host cost per chunk is
    unchanged from the unsharded loop: ONE unconverged-mask fetch."""
    K = shards
    e = int(X.shape[0])
    e_shard = e // K
    state = LaneCompactionState.initial(x0, x0.dtype)
    idx: Optional[np.ndarray] = None  # flat [K*L] global ids (host)
    mask: Optional[np.ndarray] = None  # flat [K*L] real-slot flags (host)
    carry = None
    cur_idx = None  # ([K, L] local data ids, [K, L] carry positions)
    prev_width = e_shard  # lanes-per-shard width of the previous dispatch
    prev_global = np.arange(e, dtype=np.int32).reshape(K, e_shard)
    rounds, lanes = [], []  # per chunk, one entry per shard (LaneCounts)
    spent = 0
    chunk_index = 0
    while True:
        budget = min(chunk, max_iter - spent)
        final_chunk = spent + budget >= max_iter
        active_lanes = e if idx is None else int(mask.sum())
        if lane_seq is not None:
            lane_seq.append(active_lanes)
        with trace.span("re.shard_chunk", chunk=chunk_index,
                        active_lanes=active_lanes, budget=budget,
                        shards=K):
            if idx is None:
                out = _dispatch_fit_sharded(
                    mesh, X, labels, offsets, weights, x0, obj, l1,
                    solver, budget, tolerance,
                    boundary_convergence=not final_chunk,
                    return_carry=not final_chunk)
            else:
                out = _dispatch_fit_sharded_resume(
                    mesh, X, labels, offsets, weights, cur_idx[0],
                    cur_idx[1], obj, l1, carry, solver, budget, tolerance,
                    boundary_convergence=not final_chunk,
                    return_carry=not final_chunk)
            c, it, v, k, ev, tr, chunk_rounds = out[:7]  # rounds: [K]
            new_carry = None if final_chunk else out[7]
            rounds.append(chunk_rounds)
            lanes.extend([int(c.shape[0]) // K] * K)
            if idx is None:
                still, still_local = state.absorb(None, c, it, ev, v, k,
                                                  CONV_MAX_ITERATIONS, tr)
            else:
                still, still_local = state.absorb_padded(
                    idx, mask, c, it, ev, v, k, CONV_MAX_ITERATIONS, tr)
        REGISTRY.histogram("re_chunk_active_lanes").observe(active_lanes)
        SOLVE_STATS["chunks"] += 1
        chunk_index += 1
        spent += budget
        if spent >= max_iter or len(still) == 0:
            break
        carry = new_carry
        owner = still_local // prev_width
        counts = np.bincount(owner, minlength=K)
        L = padded_lane_count(int(counts.max()), floor=min(8, e_shard))
        rows_global = np.empty((K, L), np.int32)
        rows_carry = np.zeros((K, L), np.int32)
        rows_mask = np.zeros((K, L), bool)
        for s in range(K):
            sel = owner == s
            g_ids = still[sel]
            l_pos = (still_local[sel] % prev_width).astype(np.int32)
            n = len(g_ids)
            if n:
                fill_g, fill_c = g_ids[0], l_pos[0]
            else:
                fill_g, fill_c = prev_global[s, 0], 0
            rows_global[s] = fill_g
            rows_carry[s] = fill_c
            rows_global[s, :n] = g_ids
            rows_carry[s, :n] = l_pos
            rows_mask[s, :n] = True
        idx = rows_global.reshape(-1)
        mask = rows_mask.reshape(-1)
        cur_idx = (rows_global
                   - np.arange(K, dtype=np.int32)[:, None] * e_shard,
                   rows_carry)
        prev_global = rows_global
        prev_width = L
        SOLVE_STATS["shard_real_lanes"] += int(counts.sum())
        SOLVE_STATS["shard_padded_lanes"] += K * L
        SOLVE_STATS["shard_lane_counts"] = (
            SOLVE_STATS["shard_lane_counts"][-15:] + [counts.tolist()])
        SOLVE_STATS["lane_counts"] = (
            SOLVE_STATS["lane_counts"][-63:] + [int(len(still))])
    return _with_counts(state.results(), rounds, lanes,
                        "re.shard_fit_blocks")


#: fallback reasons already logged (one warning per distinct cause, not
#: one per sweep — the sharded path is hit every CD sweep)
_SHARD_FALLBACK_WARNED: set = set()


def _resolve_entity_shards(entity_shards: int, num_lanes: int):
    """(mesh, K) when the mesh-sharded path engages for a block of
    ``num_lanes`` entity lanes, else (None, 1) — with one logged warning
    per distinct fallback cause. K is the DEFAULT mesh's entity-axis
    extent (the driver sizes both from the same flag; a mesh granted
    fewer shards than requested already warned in setup_default_mesh)."""
    if entity_shards <= 1:
        return None, 1
    mesh = get_default_mesh()
    K = int(mesh.shape.get(ENTITY_AXIS, 1)) if mesh is not None else 1
    if K <= 1:
        reason = ("no-mesh", entity_shards)
        if reason not in _SHARD_FALLBACK_WARNED:
            _SHARD_FALLBACK_WARNED.add(reason)
            logger.warning(
                "re-entity-shards=%d requested but no default mesh with an "
                "entity axis > 1 is installed; running unsharded",
                entity_shards)
        return None, 1
    if num_lanes % K != 0:
        reason = ("ragged", num_lanes, K)
        if reason not in _SHARD_FALLBACK_WARNED:
            _SHARD_FALLBACK_WARNED.add(reason)
            logger.warning(
                "entity block of %d lanes does not divide %d entity "
                "shards; running this block unsharded (build the dataset "
                "with entity_axis_size=%d to pad it)", num_lanes, K, K)
        return None, 1
    return mesh, K


@lru_cache(maxsize=64)
def _sharded_score_fn(mesh, num_samples, collective_quant="none"):
    """shard_map + jit of the active-score exchange: each shard scores its
    resident entity lanes and scatters into a full-length sample-axis
    partial, reduced ON DEVICE with a psum over the entity axis — the
    replicated result feeds the CD fused epilogue directly, no host-side
    assemble and no new device→host syncs. ``collective_quant`` selects
    the psum wire format (int8 ships blockwise-quantized partials and
    dequant-accumulates in f32); it is part of the cache key, so the two
    wire modes compile as distinct programs and never cross-hit."""
    from photon_ml_tpu.parallel.distributed import _shard_map
    from photon_ml_tpu.parallel.quantized_collectives import qpsum

    lane = P(ENTITY_AXIS)

    def impl(X, coefs, row_ids, weights):
        margins = _block_margins(X, coefs)
        margins = jnp.where(weights > 0, margins, 0.0)
        flat = jax.ops.segment_sum(
            margins.reshape(-1), row_ids.reshape(-1).astype(jnp.int32),
            num_segments=num_samples + 1)
        return qpsum(flat[:num_samples], ENTITY_AXIS,
                     mode=collective_quant)

    fit = _shard_map(impl, mesh, in_specs=(lane, lane, lane, lane),
                     out_specs=P())
    return jax.jit(fit)


@dataclasses.dataclass(frozen=True)
class RandomEffectOptimizationProblem:
    """Per-entity GLM problems for one random-effect coordinate.

    Reference: optimization/game/RandomEffectOptimizationProblem.scala:41-130
    builds an RDD of SingleNodeOptimizationProblems co-partitioned with the
    data; here one config applies to all entities and the per-entity state is
    just the coefficient block.
    """

    config: GLMOptimizationConfiguration
    task: TaskType
    # > 0 engages chunked solving with active-lane compaction: the batched
    # solver runs ``lane_compaction_chunk`` iterations at a time and only
    # still-unconverged lanes re-dispatch (see _fit_blocks_compacted).
    # 0 keeps the single-dispatch all-lanes-to-max-lane-count behavior.
    # AUTO_COMPACTION_CHUNK (-1, driver flag value "auto") lets this
    # problem's own ChunkAutoTuner pick — and re-tune between solves —
    # from the observed per-chunk active-lane decay.
    lane_compaction_chunk: int = 0
    # > 1 engages the mesh-sharded dispatch (driver flag
    # --re-entity-shards): entity lanes split over the default mesh's
    # ENTITY_AXIS via shard_map, per-shard lane compaction, on-device
    # psum score exchange. Engages only when a default mesh with a
    # matching entity axis is installed AND the block's lane count
    # divides it (build_random_effect_dataset(entity_axis_size=K) pads
    # for this); otherwise one logged warning and the unsharded path.
    # 1 (the default) IS the unsharded path. A sharded solve is another
    # XLA program: it agrees with the unsharded one to f64 machine
    # epsilon (rtol 1e-10, tests/test_re_sharding.py), not bit for bit.
    entity_shards: int = 1
    # per-coordinate controller state (the problem instance lives
    # across sweeps, so auto-mode feedback persists; identical configs
    # on different coordinates still tune independently)
    chunk_tuner: ChunkAutoTuner = dataclasses.field(
        default_factory=ChunkAutoTuner, compare=False, repr=False)
    # Wire format of the sharded score exchange's entity-axis psum
    # ("none" | "int8", driver --collective-quant). The per-entity
    # solves themselves have no collectives — entities are independent —
    # so this only affects the score path.
    collective_quant: str = "none"

    def objective(self) -> GLMObjective:
        cfg = self.config
        l2 = cfg.regularization_context.l2_weight(cfg.regularization_weight)
        return GLMObjective(
            loss=get_loss(TASK_LOSS_NAME[self.task]),
            l2_lambda=l2,
            has_hessian=self.task != TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
        )

    def _fit(self, X, labels, offsets, weights, x0, obj, l1_arr,
             solver: str, donate: bool, fault_tag: Optional[str] = None):
        """One entity block through the solver — compacted in iteration
        chunks when ``lane_compaction_chunk`` engages (auto-tuned when
        it is AUTO_COMPACTION_CHUNK), one dispatch otherwise. With
        ``entity_shards`` > 1 and a matching default mesh, the block
        dispatches mesh-sharded instead (``donate`` is ignored there:
        the sharded program gathers on device from the caller's
        buffers, which therefore stay live)."""
        cfg = self.config
        chunk = self.lane_compaction_chunk
        auto = chunk == AUTO_COMPACTION_CHUNK
        if auto:
            chunk = self.chunk_tuner.chunk_for(solver, cfg.max_iterations)
        mesh, shards = _resolve_entity_shards(self.entity_shards,
                                              int(X.shape[0]))
        if shards > 1:
            e = int(X.shape[0])
            with trace.span("re.shard_solve", solver=solver, shards=shards,
                            lanes=e):
                if 0 < chunk < cfg.max_iterations and e // shards > 1:
                    lane_seq = [] if auto else None
                    out = _fit_blocks_compacted_sharded(
                        mesh, shards, X, labels, offsets, weights, x0,
                        obj, l1_arr, solver, cfg.max_iterations,
                        float(cfg.tolerance), chunk, lane_seq=lane_seq)
                    if auto:
                        self.chunk_tuner.update(solver, cfg.max_iterations,
                                                lane_seq)
                else:
                    out = _dispatch_fit_sharded(
                        mesh, X, labels, offsets, weights, x0, obj,
                        l1_arr, solver, cfg.max_iterations,
                        float(cfg.tolerance))
                    out = _with_counts(out[:6], [out[6]],
                                       [e // shards] * shards,
                                       "re.shard_fit_blocks")
            # host-level chaos site (never traced): a drill here proves a
            # fault INSIDE a sharded solve rides the existing CD recovery
            # ladder — see utils/faults.FAULT_POINTS["re.shard_dispatch"]
            poisoned = fault_point("re.shard_dispatch", tag=fault_tag,
                                   arrays=out[0])
            return (poisoned,) + tuple(out[1:])
        if 0 < chunk < cfg.max_iterations and int(X.shape[0]) > 1:
            lane_seq: Optional[list] = [] if auto else None
            out = _fit_blocks_compacted(
                X, labels, offsets, weights, x0, obj, l1_arr, solver,
                cfg.max_iterations, float(cfg.tolerance), chunk, donate,
                lane_seq=lane_seq)
            if auto:
                self.chunk_tuner.update(solver, cfg.max_iterations,
                                        lane_seq)
            return out
        out = _dispatch_fit(
            X, labels, offsets, weights, x0, obj, l1_arr, solver,
            cfg.max_iterations, float(cfg.tolerance), donate)
        return _with_counts(out[:6], [out[6]], [int(X.shape[0])])

    def run(
        self,
        dataset: RandomEffectDataset,
        offsets: Array,
        initial: Optional[Array] = None,
        donate: bool = False,
    ) -> tuple[Array, Array, Array, Array, LaneCounts]:
        """Fit all entities; returns (coefficients [E, D_red], iterations [E],
        final losses [E], convergence codes [E] — CONVERGENCE_CODE_NAMES —
        and the evaluation counts, :class:`LaneCounts`).

        ``offsets`` is the entity-major offset block (base offsets + other
        coordinates' scores). All three solvers run batched under ``vmap``:
        TRON's trust-region/CG loop nest is the same ``lax.while_loop``
        program per entity lane (OptimizerFactory.scala:69-77 allows TRON
        for single-node problems; TRON.scala:84-341). As in the reference,
        TRON requires a twice-differentiable loss, so smoothed-hinge + TRON
        is rejected (OptimizerFactory.scala:78-79).
        """
        cfg = self.config
        l1 = cfg.regularization_context.l1_weight(cfg.regularization_weight)
        if cfg.optimizer_type == OptimizerType.TRON:
            if self.task == TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM:
                raise ValueError(
                    "TRON requires a twice-differentiable loss; the smoothed "
                    "hinge (linear SVM) task has no usable Hessian "
                    "(OptimizerFactory.scala:78-79). Use LBFGS instead.")
            solver = "tron"
        elif l1 > 0.0:
            solver = "owlqn"
        else:
            solver = "lbfgs"

        if dataset.buckets is not None:
            with trace.span("re.solve", solver=solver, bucketed=True,
                            entities=int(dataset.num_entities)):
                return self._run_bucketed(dataset, offsets, initial,
                                          solver, l1, donate)

        e, _, d = dataset.X.shape
        acc = jnp.promote_types(dataset.X.dtype, jnp.float32)
        x0 = solver_x0(acc, (e, d), initial)
        # solver state policy: blocks are f32, solver state >= f32; a
        # wider offset vector (e.g. f64 scores) must not poison the
        # jitted solver's carry dtypes
        offsets = jnp.asarray(offsets, acc)
        with trace.span("re.solve", solver=solver, bucketed=False,
                        entities=int(e)):
            return self._fit(
                dataset.X, dataset.labels, offsets, dataset.weights, x0,
                self.objective(), jnp.full(d, l1, x0.dtype), solver,
                donate and offsets is not dataset.base_offsets,
                fault_tag="0")

    def _run_bucketed(self, dataset, offsets, initial, solver: str,
                      l1: float, donate: bool = False):
        """Per-bucket vmapped solves assembled into one compact global
        block ``[num_entities, reduced_dim]`` (entity order is bucket-major;
        pad lanes never leave the bucket). With compaction off (the
        default) all buckets are DISPATCHED before any result is
        assembled and no blocking read happens here at all (the trackers
        fetch lazily, the CD epilogue fetches once); the compact global
        block is built with one concatenate per output instead of a
        per-bucket ``.at[].set`` copy chain over a presized zero block.
        With ``lane_compaction_chunk`` set, each bucket's chunked solve
        blocks on its small per-chunk unconverged-mask fetches before the
        next bucket dispatches — compaction trades that serialization for
        shedding converged lanes.

        Compile-cost note: each distinct bucket shape (E_b, N_b, D_b)
        compiles its own ``_fit_blocks`` trace, so the first sweep pays one
        compile per bucket. The DP bucket plan is deterministic for a given
        dataset, so shapes are stable across sweeps/processes and the
        in-process jit cache plus the persistent XLA compile cache
        (utils/compile_cache.py) absorb every later sweep; keep bucket
        counts small (3-4) so the one-time cost stays bounded."""
        d_red = dataset.reduced_dim
        acc = jnp.promote_types(dataset.buckets[0].X.dtype, jnp.float32)
        obj = self.objective()
        # solver state policy: blocks are f32, solver state >= f32
        # (optimize/common.solver_x0); the warm-start conversion is hoisted
        # out of the bucket loop (it used to re-convert per bucket/sweep)
        initial_acc = None if initial is None else jnp.asarray(initial, acc)
        outs = []
        for bi, (bucket, off_b) in enumerate(zip(dataset.buckets, offsets)):
            e_b, _, d_b = bucket.X.shape
            nr, start = bucket.num_real, bucket.entity_start
            off_b = jnp.asarray(off_b, acc)
            if initial_acc is None:
                x0_b = jnp.zeros((e_b, d_b), acc)
            else:
                # pad rows/columns in one op instead of zeros + .at[].set
                x0_b = jnp.pad(initial_acc[start:start + nr, :d_b],
                               ((0, e_b - nr), (0, 0)))
            outs.append(self._fit(
                bucket.X, bucket.labels, off_b, bucket.weights, x0_b,
                obj, jnp.full(d_b, l1, acc), solver, donate,
                fault_tag=str(bi)))
        # bucket-major concatenation IS the global entity order; pad each
        # bucket's D_b out to the global reduced_dim
        coefs = jnp.concatenate([
            jnp.pad(c[:b.num_real],
                    ((0, 0), (0, d_red - int(c.shape[1])))).astype(acc)
            for b, (c, _, _, _, _) in zip(dataset.buckets, outs)])
        iters = jnp.concatenate([
            it[:b.num_real]
            for b, (_, it, _, _, _) in zip(dataset.buckets, outs)])
        values = jnp.concatenate([
            v[:b.num_real].astype(acc)
            for b, (_, _, v, _, _) in zip(dataset.buckets, outs)])
        codes = jnp.concatenate([
            k[:b.num_real]
            for b, (_, _, _, k, _) in zip(dataset.buckets, outs)])
        counts = LaneCounts(
            jnp.concatenate([n.evaluations[:b.num_real]
                             for b, (*_, n) in zip(dataset.buckets, outs)]),
            jnp.concatenate([n.evaluation_rounds for *_, n in outs]),
            np.concatenate([n.bucket_lanes for *_, n in outs]),
            # one label a coordinate: a bucket too ragged for the mesh
            # falls back alone, and is booked with its coordinate's rest
            outs[0][4].site,
            jnp.concatenate([n.line_trials[:b.num_real]
                             for b, (*_, n) in zip(dataset.buckets, outs)]))
        return coefs, iters, values, codes, counts

    def regularization_value_device(self, coefs: Array):
        """Σ over entities of the per-entity penalty as a device scalar
        (no host sync — feeds the CD fused epilogue's per-coordinate reg
        cache). Python ``0.0`` when the config has no penalty."""
        cfg = self.config
        l1 = cfg.regularization_context.l1_weight(cfg.regularization_weight)
        l2 = cfg.regularization_context.l2_weight(cfg.regularization_weight)
        val = 0.0
        if l1 > 0:
            val = val + l1 * jnp.sum(jnp.abs(coefs))
        if l2 > 0:
            val = val + 0.5 * l2 * jnp.sum(coefs * coefs)
        return val

    def regularization_value(self, coefs: Array) -> float:
        """Σ over entities of the per-entity penalty
        (RandomEffectOptimizationProblem.getRegularizationTermValue)."""
        val = self.regularization_value_device(coefs)
        # photonlint: allow-W101(this IS the host-scalar accessor: one guarded scalar sync per sweep-end objective, annotated -> float)
        return val if isinstance(val, float) else float(val)


def _block_margins(dataset_X: Array, coefs: Array) -> Array:
    """margins[e, n] = X[e, n] . coefs[e], as a product and a sum (what
    ``score_passive`` does for its rows) and not an einsum: alone in its
    program, XLA:TPU makes the einsum of a bucket 64 columns wide or more
    an MXU convolution over the coefficients rounded to bfloat16, so that
    the scores the sweep's objective is summed from are not those of the
    model it publishes (PERF.md, PR 36). The sum reads X once, as the
    convolution did."""
    return jnp.sum(dataset_X * coefs[:, None, :], axis=-1).astype(jnp.float32)


@partial(jax.jit, static_argnames=("num_samples",))
def score_active(dataset_X: Array, coefs: Array, row_ids: Array,
                 weights: Array, num_samples: int) -> Array:
    """Scatter per-entity active-row margins back to the sample axis.

    margins[e, n] = X[e, n] . coefs[e]; padded rows (weight 0) scatter to the
    discard slot ``num_samples``. This is the entity→sample resharding half of
    the score exchange (RandomEffectCoordinate.score :137-151 analog).
    """
    with jax.named_scope("re.score"):
        margins = _block_margins(dataset_X, coefs)
        margins = jnp.where(weights > 0, margins, 0.0)
        flat = jax.ops.segment_sum(
            margins.reshape(-1), row_ids.reshape(-1).astype(jnp.int32),
            num_segments=num_samples + 1)
        return flat[:num_samples]


@partial(jax.jit, static_argnames=("num_samples",))
def score_passive(passive_X: Array, passive_entity: Array, coefs: Array,
                  passive_row_ids: Array, num_samples: int) -> Array:
    """Score passive rows with their entity's model (gather + rowwise dot).

    Reference: RandomEffectCoordinate.scala:153-199 collects the relevant
    models into a broadcast map; here it is a gather of coefficient rows.
    """
    with jax.named_scope("re.score"):
        w = coefs[passive_entity]  # [P, D_red]
        margins = jnp.sum(passive_X * w, axis=-1)
        return jax.ops.segment_sum(
            margins, passive_row_ids.astype(jnp.int32),
            num_segments=num_samples + 1)[:num_samples]


@jax.jit
def _active_margins(dataset_X: Array, coefs: Array, weights: Array) -> Array:
    """``score_active``'s margins, left in the block's own ``[E, N]``
    layout (padded rows, weight 0, read 0)."""
    with jax.named_scope("re.score"):
        return jnp.where(weights > 0, _block_margins(dataset_X, coefs), 0.0)


@jax.jit
def _passive_margins(passive_X: Array, passive_entity: Array,
                     coefs: Array) -> Array:
    """``score_passive``'s margins, in the passive rows' own order."""
    with jax.named_scope("re.score"):
        return jnp.sum(passive_X * coefs[passive_entity], axis=-1)


@partial(jax.jit, static_argnames=("spans",))
def _bucket_coefs(coefs: Array, spans: tuple) -> list:
    """The compact global block cut into every bucket's own ``[E_b, D_b]``
    block: bucket ``(start, num_real, e_b, d_b)`` takes rows ``start:start
    + num_real`` and the first ``d_b`` columns, its pad lanes zeros (the
    zeros and ``.at[].set`` a bucket the eager form made, one program a
    dataset: ``jit__bucket_coefs`` in a device trace)."""
    with jax.named_scope("re.score"):
        return [jnp.zeros((e_b, d_b), coefs.dtype).at[:num_real].set(
                    coefs[start:start + num_real, :d_b])
                for start, num_real, e_b, d_b in spans]


@jax.jit
def _gather_scores(margins, positions: Array) -> Array:
    """Each sample's score from its one place among the blocks' margins
    and the passive rows' (``RandomEffectDataset.score_positions``; one
    zero after them for a row this coordinate does not score)."""
    with jax.named_scope("re.score"):
        flat = [m.reshape(-1) for m in margins]
        return jnp.concatenate(flat + [jnp.zeros(1, jnp.float32)])[positions]


def score_random_effect(dataset: RandomEffectDataset, coefs: Array,
                        entity_shards: int = 1,
                        collective_quant: str = "none",
                        coordinate: str = "") -> Array:
    """Full sample-axis score vector (active + passive) for this coordinate.

    ``coefs`` is the compact global block ``[num_entities, reduced_dim]``.
    Shard-count 1 takes every block's margins and the passive rows' as
    they lie and gathers every sample's score from its place among them
    (``_gather_scores``), wherever this process holds every block: the
    TPU's compiler takes 7-16 s for each scatter into a sample-long
    vector, and seconds for the gather. Otherwise (several hosts, or
    ``entity_shards`` > 1) every block scatters its margins by ``row_ids``
    (row sets are disjoint, so the per-bucket scatters sum without
    overlap): with ``entity_shards`` > 1 (and the same engagement
    conditions as the sharded solve), each block's scoring runs
    shard-local and the per-shard partial score vectors reduce with an
    on-device psum over the entity axis — the replicated result feeds the
    CD fused epilogue with zero added host syncs;
    ``collective_quant="int8"`` ships that psum's partials
    blockwise-quantized (parallel/quantized_collectives.py) and counts
    the wire bytes on ``collective_bytes{site="re.score_psum"}``.
    ``coordinate`` labels the host span of the gathering form, ``re.score``
    (the mesh path's blocks have ``re.shard_score``)."""
    from photon_ml_tpu.parallel.quantized_collectives import \
        record_collective_bytes

    def _score_block(X, c_b, row_ids, weights):
        mesh, K = _resolve_entity_shards(entity_shards, int(X.shape[0]))
        if K > 1:
            with trace.span("re.shard_score", shards=K,
                            lanes=int(X.shape[0])):
                out = _sharded_score_fn(mesh, int(dataset.num_samples),
                                        collective_quant)(
                    X, c_b, row_ids, weights)
                record_collective_bytes("re.score_psum", collective_quant,
                                        int(dataset.num_samples))
                return out
        return score_active(X, c_b, row_ids, weights, dataset.num_samples)

    def bucket_coefs():
        return obs_compile.call(
            "re.bucket_coefs", _bucket_coefs,
            (coefs, tuple(
                (b.entity_start, b.num_real, int(b.X.shape[0]),
                 int(b.X.shape[2])) for b in dataset.buckets)),
            static_argnums=(1,), arg_names=("coefs", "spans"))

    positions = dataset.score_positions() if entity_shards <= 1 else None
    if positions is not None:
        with trace.span("re.score", coordinate=coordinate,
                        blocks=dataset.num_blocks):
            if dataset.buckets is not None:
                margins = [_active_margins(b.X, c_b, b.weights)
                           for b, c_b in zip(dataset.buckets,
                                             bucket_coefs())]
            else:
                margins = [_active_margins(dataset.X, coefs,
                                           dataset.weights)]
            if dataset.num_passive:
                margins.append(_passive_margins(
                    dataset.passive_X, dataset.passive_entity, coefs))
            return _gather_scores(margins, positions)
    if dataset.buckets is not None:
        s = jnp.zeros(dataset.num_samples, jnp.float32)
        for bucket, c_b in zip(dataset.buckets, bucket_coefs()):
            s = s + _score_block(bucket.X, c_b,
                                 bucket.row_ids, bucket.weights)
    else:
        s = _score_block(dataset.X, coefs, dataset.row_ids, dataset.weights)
    if dataset.num_passive:
        s = s + score_passive(dataset.passive_X, dataset.passive_entity,
                              coefs, dataset.passive_row_ids,
                              dataset.num_samples)
    return s
