"""Coordinate descent: the GAME outer loop.

TPU-native re-design of the reference's CoordinateDescent
(reference: photon-ml/src/main/scala/com/linkedin/photon/ml/algorithm/
CoordinateDescent.scala:50-263): initialize per-coordinate models and score
vectors; per (iteration, coordinate in updating sequence) — sum the *other*
coordinates' scores and inject them as offsets (:143-151), re-optimize the
coordinate, re-score it, log the global objective
``trainingLossEvaluator(Σ scores) + Σ regularization`` (:199-205), optionally
evaluate on validation data and keep the best full model by the first
validation evaluator (:245-255).

The reference's per-step RDD joins/unpersists become array adds and gathers;
all score vectors are sample-major ``[N]`` device arrays.

Hot-loop sync discipline: one coordinate update costs AT MOST one device
round-trip. The update, its score, the changed coordinate's regularization
scalar, and the fused epilogue (:func:`make_update_epilogue`) dispatch
asynchronously; the single blocking read is a ``jax.device_get`` of the
epilogue's small scalar pytree. Everything sample-sized — the canonical
score total included — stays device-resident between updates, and the
per-coordinate trackers/optimizer histories materialize lazily at
log/metrics/checkpoint time. ``tests/test_sync_discipline.py`` enforces
this under ``jax.transfer_guard("disallow")``.

Two sweep-level optimizations attack the dispatch critical path that the
one-fetch-per-update work exposed:

- **Double-buffered updates** (``pipeline_depth=1``, the default): the
  next coordinate's solve is DISPATCHED against the previous epilogue's
  device-resident outputs (its corrected total and new score — the very
  arrays the previous commit will install) before the previous fetch
  blocks, so host dispatch work overlaps device compute. The committed
  floats are bit-identical to the sequential sweep — only host ordering
  changes — and the recovery/quarantine ladder tolerates acting one
  update late: a divergence discovered at fetch time rolls the
  speculative dispatch back (RNG stream positions included) and replays
  from last-good state.
- **Block-parallel sweeps** (``block_size=B``): B coordinates solve
  concurrently against the SAME stale score total, then ONE fused
  correction epilogue re-canonicalizes the ids-order total with all B
  new scores substituted — one fetch per block (1/B amortized
  syncs/update). Block updates use stale partial scores, so trajectories
  match the sequential sweep within tolerance, not bitwise; block
  boundaries are commit barriers, so checkpoint bit-exactness and
  ``tools/crash_resume_drill.py`` semantics are preserved (a snapshot
  never lands mid-block).

The pipeline-depth discipline (an epilogue fetch is consumed at most ONE
dispatch later) is structural: photonlint W105 flags a deferred handle
that survives two dispatches.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.game.coordinate import Coordinate, Tracker
from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.game.models import GameModel
from photon_ml_tpu.ops.losses import get_loss
from photon_ml_tpu.optimize.config import TASK_LOSS_NAME, TaskType
from photon_ml_tpu.obs import compile as obs_compile
from photon_ml_tpu.obs import devicemem, trace
from photon_ml_tpu.obs.metrics import REGISTRY
from photon_ml_tpu.utils.events import (
    CoordinateQuarantinedEvent,
    EventEmitter,
    FaultEvent,
    RecoveryEvent,
)
from photon_ml_tpu.utils.faults import InjectedFault, fault_point
from photon_ml_tpu.utils.preempt import PreemptionRequested
from photon_ml_tpu.utils.sync_telemetry import record_host_fetch

Array = jnp.ndarray


class CoordinateDivergenceError(RuntimeError):
    """A coordinate update produced a non-finite state or objective."""


# Hot-loop schedule counts, the seam the transfer-guard and pipelining
# tests observe the sweep through: the one-round-trip contract says every
# non-validation coordinate update performs AT MOST ONE blocking
# device→host fetch (the fused epilogue's small scalar pytree; a block of
# B updates shares ONE fetch, so the amortized rate is 1/B).
# ``max_inflight`` is the most dispatched-but-unfetched updates alive at
# once (2 with double-buffering at block size 1) and
# ``pipelined_resolves`` counts fetches that happened AFTER a later
# dispatch had already been issued. The intervals themselves are the
# ``cd.dispatch`` / ``cd.pipeline_wait`` / ``cd.epilogue_fetch`` spans.
HOT_LOOP_STATS = {"updates": 0, "epilogue_fetches": 0,
                  "max_inflight": 0, "pipelined_resolves": 0}


def reset_hot_loop_stats() -> None:
    HOT_LOOP_STATS.update({"updates": 0, "epilogue_fetches": 0,
                           "max_inflight": 0, "pipelined_resolves": 0})


def _sample_live_bytes(sweep: int) -> None:
    """Sample Σ nbytes over ``jax.live_arrays()`` into the
    ``hbm_live_bytes`` gauge and a ``cd.hbm_sample`` span at the
    sweep-boundary drain, so pipeline depth and the drain policy can be
    tuned from a trace (are deferred buffers accumulating between
    drains?). Metadata-only — enumerating live arrays never syncs the
    device — and run only where an observed run asked for it
    (``--trace-dir``: ``obs/run.py`` switches ``devicemem.watch_sweeps``):
    the enumeration is two O(#arrays) walks of host work that neither the
    untraced hot path nor a process that is merely armed (it records
    spans, as every benchmark run does) must pay."""
    # --device-telemetry: the sweep's per-coordinate commit watermarks go
    # out at the same boundary (a switch of their own: no-op unless armed)
    devicemem.drain_coordinate_watermarks(sweep)
    if not devicemem.sweeps_watched():
        return
    try:
        total_bytes = sum(int(getattr(a, "nbytes", 0) or 0)
                          for a in jax.live_arrays())
    except Exception:  # pragma: no cover - backend without live_arrays
        return
    REGISTRY.gauge("hbm_live_bytes").set(total_bytes, site="cd.sweep_drain")
    # mesh-sharded runs: attribute live bytes to each DEVICE holding a
    # shard (addressable_shards metadata — still no device sync), so a
    # lopsided entity partition shows up as a lopsided per-shard gauge
    try:
        per_device: dict = {}
        for a in jax.live_arrays():
            shards = getattr(a, "addressable_shards", None) or []
            if len(shards) > 1:
                for s in shards:
                    d = s.device.id
                    per_device[d] = (per_device.get(d, 0)
                                     + int(getattr(s.data, "nbytes", 0)
                                           or 0))
        for d, b in sorted(per_device.items()):
            REGISTRY.gauge("re_shard_hbm_live_bytes").set(b, shard=str(d))
    except Exception:  # pragma: no cover - backend without shard metadata
        pass
    with trace.span("cd.hbm_sample", sweep=sweep, live_bytes=total_bytes):
        pass


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unfetched block of coordinate updates: the
    fused epilogue's device handles plus everything the host needs to
    commit the block at fetch time — or discard it (``update_counts_
    before`` restores the RNG stream positions ``coord.update`` advanced,
    so a rolled-back speculative dispatch leaves no trace in a
    down-sampling coordinate's key sequence)."""

    it: int
    block: list  # [(ci, cid), ...] in dispatch order
    attempt: int
    cands: dict
    trackers: dict
    new_scores: dict
    new_regs: dict
    new_total: object  # device [N]: the re-canonicalized score total
    objective_d: object
    train_loss_d: object
    finite_d: object
    state_finite_d: object
    update_counts_before: dict
    snapshot_due: bool
    # resume point of the enclosing RAW block ("about to run this
    # coordinate"): quarantine-filtered members still count toward the
    # boundary, or a resumed run would re-partition the sweep's blocks
    snapshot_next_ci: int
    t_wall: float
    # serial of the dispatch within this run: the ``update`` label that
    # joins the block's cd.dispatch span to its later cd.pipeline_wait /
    # cd.epilogue_fetch (a pipelined block is fetched one dispatch later)
    update: int = 0
    pipelined: bool = False  # a later dispatch was issued before this fetch


def _canonical_sum(score_list, num_samples: int):
    """Σ scores in updating-sequence order from zero — the ONE summation
    order used everywhere (init, resume, and INSIDE the fused epilogue), so
    a resumed run reproduces the uninterrupted run's floats exactly."""
    t = jnp.zeros(num_samples)
    for s in score_list:
        t = t + s
    return t


@functools.lru_cache(maxsize=32)
def _canonical_total_jit(num_samples: int):
    """Jitted canonical summation, cached per sample count so repeated
    runs reuse the executable."""
    return jax.jit(lambda score_list: _canonical_sum(score_list,
                                                     num_samples))


@functools.lru_cache(maxsize=32)
def make_update_epilogue(task: TaskType, num_samples: int):
    """Build the fused, jitted update epilogue (cached per task/sample
    count: repeated runs share one compiled executable per shape).

    One compiled call computes everything the host needs after a candidate
    coordinate update, replacing what used to be O(K) blocking syncs per
    update (a ``float()`` per coordinate's regularization term, a
    ``bool()`` per state leaf for the finiteness guard, a ``float()`` for
    the objective) with a single device program whose small outputs are
    fetched as ONE pytree:

    - the canonical ids-order score total (kept ON DEVICE — it feeds the
      next update's partial-score offsets without a round-trip); summation
      order is preserved inside the fused op so crash/resume stays
      bit-exact,
    - the training loss Σᵢ wᵢ·l(totalᵢ + offsetᵢ, yᵢ) (:199-205),
    - Σ regularization from the per-coordinate reg-scalar cache (updated
      only for the changed coordinate, summed in ids order),
    - the global objective (training loss + Σ reg),
    - one all-leaves finiteness flag over the candidate state + objective.

    ``score_list``/``reg_list`` arrive in updating-sequence order with the
    changed coordinates' entries already substituted — ONE changed entry
    for a sequential update, B entries for a block-parallel update (the
    canonical re-summation then IS the block's staleness-correction step:
    every member solved against the stale block-start total, and this op
    rebuilds the ids-order total with all members' new scores in one
    fused program). ``state_leaves`` concatenates every changed
    coordinate's state leaves, so the finiteness flag covers the whole
    block.
    """
    # this body runs only on an lru_cache MISS — i.e. a new (task, N)
    # shape is about to pay an XLA compile; the counter makes retrace
    # regressions visible in metrics.jsonl
    REGISTRY.counter("retraces").inc(site="cd.epilogue")
    loss = get_loss(TASK_LOSS_NAME[task])

    @jax.jit
    def epilogue(score_list, reg_list, state_leaves, labels, weights,
                 offsets):
        with jax.named_scope("cd.epilogue"):
            total = _canonical_sum(score_list, num_samples)
            l, _ = loss.loss_and_d1(total + offsets, labels)
            train_loss = jnp.sum(weights * l)
            reg_total = 0.0
            for r in reg_list:  # ids order (python floats stay op-free)
                reg_total = reg_total + r
            objective = train_loss + reg_total
            state_finite = jnp.asarray(True)
            for leaf in state_leaves:
                state_finite = state_finite & jnp.all(jnp.isfinite(leaf))
            finite = state_finite & jnp.isfinite(objective)
        return total, objective, train_loss, reg_total, finite, state_finite

    return epilogue


@dataclasses.dataclass(frozen=True)
class RecoveryPolicy:
    """What to do when a coordinate update diverges (non-finite state or
    objective) or raises an injected fault.

    The reference never needed this — Spark re-ran lost lineage for free
    but had no answer to numeric divergence either (SURVEY §5.4); here
    both are handled by one policy:

    - retry the update up to ``max_retries`` times from the last-good
      state, damping the accepted step by ``damping**attempt``. Damping
      rescues transient faults and finite-state overflows (an Inf
      objective from an over-long step); a DETERMINISTIC NaN solve will
      reproduce itself and exhaust the retries — the skip/abort action
      below is what bounds that cost;
    - when retries are exhausted, either ``skip`` the coordinate for this
      sweep (keep the last-good state, continue degraded) or ``abort``;
    - abort anyway after ``max_consecutive_failures`` consecutive skipped
      updates — a run that skips every sweep is not making progress.

    ``quarantine_after`` adds a PER-COORDINATE failure budget on top of
    the global policy: when > 0, a coordinate whose retries exhaust is
    skipped for the sweep (degraded, regardless of ``on_exhausted``)
    until it has accumulated ``quarantine_after`` exhausted updates
    across the run, at which point it is QUARANTINED — frozen at its
    last-good state, announced with a
    :class:`~photon_ml_tpu.utils.events.CoordinateQuarantinedEvent`, and
    excluded from further sweeps while the rest of the descent continues.
    One chronically-diverging coordinate then costs its own bounded
    budget instead of burning the global retry/consecutive-failure
    budgets or aborting the whole run.

    Under double-buffering the policy acts ONE UPDATE LATE: a divergence
    surfaces at the fetch, after the next update has already been
    dispatched against the diverged outputs. The ladder then rolls the
    speculative dispatch back (its device work is never fetched, its RNG
    stream positions are restored) and re-runs it from the re-committed
    last-good state, so every retry/skip/quarantine decision is made
    against exactly the states the sequential sweep would have used.
    """

    max_retries: int = 2
    on_exhausted: str = "abort"  # "skip" | "abort"
    damping: float = 0.5
    max_consecutive_failures: int = 3
    quarantine_after: int = 0  # 0 = per-coordinate budget disabled

    def __post_init__(self):
        if self.on_exhausted not in ("skip", "abort"):
            raise ValueError(
                f"on_exhausted must be 'skip' or 'abort', "
                f"got {self.on_exhausted!r}")
        if self.quarantine_after < 0:
            raise ValueError(
                f"quarantine_after must be >= 0, "
                f"got {self.quarantine_after}")


def _state_leaves(state):
    return state if isinstance(state, tuple) else (state,)


def _state_is_finite(state) -> bool:
    # device-side reduction: one scalar comes back per leaf instead of a
    # full state copy (per-entity matrices can be millions of rows);
    # all leaves' flags return in a single instrumented fetch
    flags = jax.device_get(tuple(
        jnp.all(jnp.isfinite(jnp.asarray(leaf)))
        for leaf in _state_leaves(state)))
    record_host_fetch(site="cd.state_finite")
    return all(bool(f) for f in flags)


def _damp_toward(good, candidate, factor: float):
    """last_good + factor * (candidate - last_good), leaf-wise."""
    def blend(g, c):
        return g + factor * (jnp.asarray(c) - g)
    if isinstance(candidate, tuple):
        return tuple(blend(g, c) for g, c in zip(good, candidate))
    return blend(jnp.asarray(good), candidate)


def training_loss_evaluator(task: TaskType, labels: Array, weights: Array,
                            offsets: Array) -> Callable[[Array], float]:
    """Σ_i w_i l(score_i + offset_i, y_i) over the training data
    (prepareTrainingLossEvaluator, cli/game/training/Driver.scala:191)."""
    loss = get_loss(TASK_LOSS_NAME[task])

    def evaluate(scores: Array) -> float:
        l, _ = loss.loss_and_d1(scores + offsets, labels)
        value = jax.device_get(jnp.sum(weights * l))
        record_host_fetch(site="cd.training_loss")
        return float(value)

    return evaluate


@dataclasses.dataclass
class CoordinateDescentState:
    """Per-iteration record (OptimizationStatesTracker + CD logging analog)."""

    iteration: int
    coordinate_id: str
    objective: float
    seconds: float
    tracker: Tracker
    validation_metrics: Optional[dict[str, float]] = None


@dataclasses.dataclass
class CoordinateDescentResult:
    model: GameModel
    states: list[CoordinateDescentState]
    best_model: Optional[GameModel] = None
    best_metric: Optional[float] = None
    # Coordinates frozen at last-good state by the per-coordinate failure
    # budget (RecoveryPolicy.quarantine_after) — surfaced in the driver
    # summary and metrics.json.
    quarantined: list[str] = dataclasses.field(default_factory=list)


def _to_jnp_states(d: dict) -> dict:
    return {cid: (tuple(jnp.asarray(s) for s in v)
                  if isinstance(v, tuple) else jnp.asarray(v))
            for cid, v in d.items()}


def _checkpoint_save_contained(manager, step: int, snapshot: dict,
                               log, emit) -> bool:
    """Save a snapshot, CONTAINING a persistently-unwritable disk
    (CheckpointWriteError after the write-side retries): training state
    is intact and the next cadence point tries again, so a full
    checkpoint volume degrades durability instead of killing a
    multi-hour run. The failure is logged, counted
    (``ckpt_save_failures``), and announced as a FaultEvent."""
    from photon_ml_tpu.utils.checkpoint import CheckpointWriteError

    try:
        manager.save(step, snapshot)
        return True
    except CheckpointWriteError as e:
        REGISTRY.counter("ckpt_save_failures").inc()
        emit(FaultEvent(point="ckpt.write_bytes", message=str(e)))
        log(lambda: f"checkpoint step {step} NOT saved (degraded, "
            f"training continues): {e}")
        return False


def run_coordinate_descent(
    coordinates: dict[str, Coordinate],
    num_iterations: int,
    task: TaskType,
    labels: Array,
    weights: Array,
    offsets: Array,
    validation_data: Optional[GameDataset] = None,
    validation_evaluator: Optional[Callable[[Array], dict[str, float]]] = None,
    validation_metric: Optional[str] = None,
    higher_is_better: bool = True,
    initial_states: Optional[dict] = None,
    logger: Optional[Callable[[str], None]] = None,
    checkpoint_manager=None,
    start_iteration: int = 0,
    initial_best: Optional[tuple] = None,
    recovery: Optional[RecoveryPolicy] = None,
    events: Optional[EventEmitter] = None,
    checkpoint_every_coordinates: int = 0,
    start_coordinate: int = 0,
    resume_snapshot: Optional[dict] = None,
    block_size: int = 1,
    pipeline_depth: int = 1,
    stop=None,
) -> CoordinateDescentResult:
    """Run GAME coordinate descent over ``coordinates`` in dict order.

    ``coordinates`` iteration order IS the updating sequence
    (cli/game/training/Params updatingSequence). ``labels/weights/offsets``
    describe the training samples (sample-major). Single-coordinate runs skip
    the partial-score machinery exactly like CoordinateDescent.scala:82-120's
    special case.

    With a :class:`RecoveryPolicy`, every coordinate update is guarded for
    non-finite states/objectives and injected faults; detected faults emit
    :class:`FaultEvent`/:class:`RecoveryEvent` on ``events`` and follow the
    policy (retry damped / skip degraded / abort, plus per-coordinate
    quarantine when ``quarantine_after`` is set). Without one, behavior
    is the legacy fail-through (a NaN propagates to the caller).

    ``pipeline_depth=1`` (the default) DOUBLE-BUFFERS the sweep: the next
    block's solve dispatches against the previous epilogue's
    device-resident outputs before the previous fetch blocks, overlapping
    host dispatch with device compute. The committed floats are
    bit-identical to ``pipeline_depth=0`` (the epilogue consumes the same
    device arrays either way); a divergence discovered at the late fetch
    rolls the speculative dispatch back and replays it from last-good
    state. Depth > 1 is refused — an epilogue fetch must never age more
    than one dispatch (photonlint W105's structural contract).
    Pipelining turns itself off when a validation evaluator runs per
    update (validation needs the committed model) and pauses across
    checkpoint-cadence points (a snapshot is a commit barrier).

    ``block_size=B`` partitions each sweep into disjoint blocks of B
    coordinates solved CONCURRENTLY against the stale block-start score
    total, followed by one fused correction epilogue that
    re-canonicalizes the ids-order total with all B new scores — one
    fetch per block. Trajectories match the sequential sweep within
    tolerance (stale partials), and block boundaries are commit/snapshot
    barriers so crash→resume stays bit-exact for a given block size.
    B=1 is exactly today's sequential semantics.

    Checkpointing: with a ``checkpoint_manager`` a snapshot lands after
    every completed sweep, and — when ``checkpoint_every_coordinates``
    = N > 0 — additionally after every Nth coordinate update, so a crash
    inside a long sweep replays at most N updates instead of the whole
    sweep (with blocks, at the enclosing block boundary). A snapshot
    carries everything a BIT-EXACT resume needs: ``(sweep,
    coordinate_index, per-coordinate states AND scores, RNG stream
    positions, recovery counters, the quarantine set, the running
    best)``. Resume by passing the restored dict as ``resume_snapshot``
    (preferred — it repopulates all of the above; the legacy
    ``initial_states``/``start_iteration``/``initial_best`` trio still
    works for sweep-boundary snapshots). The score total is recomputed
    canonically (ids order, from zero) after every update rather than
    maintained incrementally, so a resumed run sees float-identical
    partial scores to the uninterrupted one.

    Graceful stop: ``stop`` is any object with a ``should_stop() ->
    str | None`` method (a :class:`~photon_ml_tpu.utils.preempt.
    StopController` in the drivers). It is polled ONLY at raw block
    boundaries — the existing commit/snapshot barriers — so a stop can
    never tear a block or race the pipeline. When it returns a reason,
    the in-flight pipelined handle is resolved first (the same settle-
    before-snapshot rule the checkpoint barrier follows), a final
    snapshot lands at the barrier (when checkpointing is on), and
    :class:`~photon_ml_tpu.utils.preempt.PreemptionRequested` is raised
    carrying the exact resume position. Resuming from that snapshot is
    bit-exact vs the uninterrupted run, exactly like crash resume.
    """
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    if pipeline_depth not in (0, 1):
        raise ValueError(
            f"pipeline_depth must be 0 (sequential) or 1 (double-"
            f"buffered), got {pipeline_depth}: a deeper pipeline would "
            f"let an epilogue fetch age more than one dispatch "
            f"(photonlint W105's structural contract)")

    def log(fn: Callable[[], str]):
        # Lazy formatting: log lines materialize lazy trackers (a device
        # fetch), so a run without a logger must never even BUILD them.
        if logger is not None:
            logger(fn())

    emit = events.send_event if events is not None else (lambda e: None)
    ids = list(coordinates)
    n = {cid: coordinates[cid].num_samples for cid in ids}
    num_samples = next(iter(n.values()))
    assert all(v == num_samples for v in n.values()), \
        "all coordinates must cover the same sample axis"

    epilogue = make_update_epilogue(task, num_samples)
    # The canonical total is computed by the SAME jitted summation the
    # epilogue runs, so the init/resume total is bit-identical to the
    # fused op's (XLA executes the identical add sequence).
    canonical_total_fn = _canonical_total_jit(num_samples)

    consecutive_failures = 0
    coordinate_failures: dict[str, int] = {}
    quarantined: set[str] = set()
    restored_scores = None
    if resume_snapshot is not None:
        snap = resume_snapshot
        initial_states = _to_jnp_states(snap["states"])
        start_iteration = int(snap.get("sweep", snap.get("iteration", 0)))
        start_coordinate = int(snap.get("coordinate_index", 0))
        if snap.get("best_states") is not None:
            initial_best = (snap.get("best_metric"),
                            _to_jnp_states(snap["best_states"]))
        if snap.get("scores") is not None:
            restored_scores = {cid: jnp.asarray(v)
                               for cid, v in snap["scores"].items()}
        # RNG stream positions: a down-sampling coordinate's PRNG key is
        # seed + update count, so the counter IS the key state
        for cid, cnt in (snap.get("update_counts") or {}).items():
            if cid in coordinates and hasattr(coordinates[cid],
                                              "_update_count"):
                coordinates[cid]._update_count = int(cnt)
        consecutive_failures = int(snap.get("consecutive_failures", 0))
        coordinate_failures = {k: int(v) for k, v in
                               (snap.get("coordinate_failures")
                                or {}).items()}
        quarantined = set(snap.get("quarantined") or [])

    # a coordinate that labels its spans learns the id it runs under
    for cid in ids:
        if hasattr(coordinates[cid], "coordinate_id"):
            coordinates[cid].coordinate_id = cid

    # Init: zero states, zero scores (CoordinateDescent.scala:93-101).
    states = dict(initial_states or {})
    resumed = set(states)
    for cid in ids:
        if cid not in states:
            states[cid] = coordinates[cid].initial_state()

    def canonical_total(score_map):
        """Σ scores in ids order from zero — the ONE summation order used
        everywhere (shared with the fused epilogue), so a resume that
        rebuilds the total from restored scores reproduces the
        uninterrupted run's floats exactly."""
        return obs_compile.call(
            "cd.canonical_total", canonical_total_fn,
            (tuple(score_map[c] for c in ids),),
            arg_names=("score_list",))

    if restored_scores is not None:
        # Mid-sweep resume: scores come back verbatim from the snapshot
        # (recomputing them from states would be wrong for coordinates
        # that have never been updated — score(initial_state) need not be
        # zero under normalization shifts).
        scores = {cid: (restored_scores[cid] if cid in restored_scores
                        else jnp.zeros(num_samples)) for cid in ids}
    else:
        # Restored coordinates must contribute their scores from the
        # start — zeros would make the first resumed sweep optimize
        # against offsets that pretend the other coordinates' models
        # don't exist.
        scores = {cid: (coordinates[cid].score(states[cid])
                        if cid in resumed else jnp.zeros(num_samples))
                  for cid in ids}
    total = canonical_total(scores)

    # Device-resident per-coordinate regularization scalar cache: the fused
    # epilogue sums these in ids order; only the CHANGED coordinates'
    # entries are recomputed per update (the old path re-evaluated all K
    # penalties with a blocking float() each — O(K²) syncs per sweep).
    # Deterministic on resume: recomputed from the restored states by the
    # same ops.
    def _reg_device(cid, state):
        coord = coordinates[cid]
        fn = getattr(coord, "regularization_value_device",
                     coord.regularization_value)
        return fn(state)

    reg_cache = {cid: _reg_device(cid, states[cid]) for cid in ids}

    history: list[CoordinateDescentState] = []
    best_model = None
    best_metric = None
    best_states = None
    if initial_best is not None:
        best_metric, restored_states = initial_best
        best_states = dict(restored_states)
        best_model = publish_game_model(coordinates, best_states)

    # Per-update validation needs the committed model after EVERY update,
    # so it forces the sequential resolve order (no overlap to exploit).
    validate = (validation_data is not None
                and validation_evaluator is not None)
    use_pipeline = pipeline_depth > 0 and not validate

    last_saved_step = None

    def save_snapshot(sweep, next_ci):
        """Persist the full resume state as of 'about to run coordinate
        ``next_ci`` of ``sweep``'; a completed sweep normalizes to the
        next sweep's coordinate 0. Step number = global update count, so
        mid-sweep and sweep-end snapshots share one monotone sequence."""
        nonlocal last_saved_step
        if next_ci >= len(ids):
            sweep, next_ci = sweep + 1, 0
        step = sweep * len(ids) + next_ci
        if step == last_saved_step:
            return
        # THE fetch point: the whole snapshot (per-coordinate states AND
        # scores, still device-resident from the hot loop) comes back in
        # one explicit jax.device_get of the payload pytree instead of a
        # per-leaf np.asarray chain.
        payload = jax.device_get({
            "states": states,
            "scores": {cid: scores[cid] for cid in ids},
            "best_states": best_states,
        })
        record_host_fetch(site="ckpt.snapshot")
        saved = _checkpoint_save_contained(checkpoint_manager, step, {
            "sweep": sweep,
            "coordinate_index": next_ci,
            # legacy field: completed sweeps (pre-mid-sweep readers)
            "iteration": sweep,
            "states": payload["states"],
            "scores": payload["scores"],
            "best_metric": (None if best_metric is None
                            else float(best_metric)),
            "best_states": payload["best_states"],
            "update_counts": {
                cid: int(getattr(coordinates[cid], "_update_count"))
                for cid in ids
                if hasattr(coordinates[cid], "_update_count")},
            "consecutive_failures": int(consecutive_failures),
            "coordinate_failures": dict(coordinate_failures),
            "quarantined": sorted(quarantined),
        }, log=log, emit=emit)
        if saved:  # a failed save retries at the next cadence point
            last_saved_step = step

    def snapshot_cadence_due(block, it):
        """Does this (raw) block cross a ``checkpoint_every_coordinates``
        cadence point? ONE definition — the success path and every
        fault-replay path must snapshot on the same schedule."""
        return (checkpoint_manager is not None
                and checkpoint_every_coordinates > 0
                and any((it * len(ids) + ci + 1)
                        % checkpoint_every_coordinates == 0
                        for ci, _ in block))

    update_serial = itertools.count()

    def dispatch_update(block, it, attempt, base_total, overlay,
                        snapshot_due=False, snapshot_next_ci=0):
        """Dispatch one block of candidate updates + ONE fused epilogue
        WITHOUT blocking; returns the :class:`_InFlight` handle whose
        single device→host read happens in ``fetch_update`` — possibly
        one block later (double-buffering).

        ``base_total``/``overlay`` carry the still-uncommitted previous
        block's device outputs (its corrected total and per-coordinate
        new scores/regs), so a pipelined dispatch optimistically sees
        EXACTLY the arrays the previous commit will install — which is
        why the block-size-1 pipelined sweep is bit-identical to the
        sequential one. Block members all read ``base_total`` (the stale
        block-start total); the epilogue's canonical re-summation is the
        correction step.

        A fault raised MID-DISPATCH of a multi-member block restores
        every member's RNG stream position before propagating: the
        block replay re-runs each member as its own fresh attempt 0, so
        members dispatched before the fault must not stay advanced (a
        down-sampling coordinate would draw a different key than the
        sequential ladder's). A SINGLETON dispatch keeps its advance —
        the seeded ladder treats it as attempt 0, exactly like the
        sequential retry loop."""
        t_wall = time.time()
        counts_before = {
            cid: getattr(coordinates[cid], "_update_count", None)
            for _, cid in block}
        cands: dict = {}
        trackers: dict = {}
        new_scores: dict = {}
        new_regs: dict = {}
        cids = ",".join(cid for _, cid in block)
        update = next(update_serial)
        try:
            with trace.span("cd.dispatch", sweep=it, size=len(block),
                            coordinates=cids, update=update):
                for ci, cid in block:
                    coord = coordinates[cid]
                    partial = base_total - (
                        overlay[cid][0] if cid in overlay else scores[cid]
                    )  # Σ other coordinates (:143-151)
                    cand, tracker = coord.update(states[cid], partial)
                    # the solver_* counters' second label, beside site
                    tracker.for_coordinate(cid)
                    cand = fault_point("cd.update", tag=f"{it}.{ci}",
                                       arrays=cand)
                    if attempt > 0:
                        cand = _damp_toward(states[cid], cand,
                                            recovery.damping ** attempt)
                    cands[cid] = cand
                    trackers[cid] = tracker
                    new_scores[cid] = coord.score(cand)
                    new_regs[cid] = _reg_device(cid, cand)
                score_list = tuple(
                    new_scores[c] if c in new_scores
                    else (overlay[c][0] if c in overlay else scores[c])
                    for c in ids)
                reg_list = tuple(
                    new_regs[c] if c in new_regs
                    else (overlay[c][1] if c in overlay else reg_cache[c])
                    for c in ids)
                leaves = tuple(jnp.asarray(leaf) for _, cid in block
                               for leaf in _state_leaves(cands[cid]))
                (new_total, objective_d, train_loss_d, _reg_total_d,
                 finite_d, state_finite_d) = obs_compile.call(
                    "cd.epilogue", epilogue,
                    (score_list, reg_list, leaves, labels, weights,
                     offsets),
                    arg_names=("score_list", "reg_list", "state_leaves",
                               "labels", "weights",
                               "offsets"))  # (:199-205)
        except Exception:
            if len(block) > 1:
                for _, cid in block:
                    before = counts_before.get(cid)
                    if before is not None:
                        coordinates[cid]._update_count = before
            raise
        return _InFlight(
            it=it, block=list(block), attempt=attempt, cands=cands,
            trackers=trackers, new_scores=new_scores, new_regs=new_regs,
            new_total=new_total, objective_d=objective_d,
            train_loss_d=train_loss_d, finite_d=finite_d,
            state_finite_d=state_finite_d,
            update_counts_before=counts_before,
            snapshot_due=snapshot_due,
            snapshot_next_ci=snapshot_next_ci,
            t_wall=t_wall, update=update)

    def _set_update_counts(block, counts):
        for _, cid in block:
            v = counts.get(cid)
            if v is not None:
                coordinates[cid]._update_count = v

    def _snap_update_counts(block):
        return {cid: getattr(coordinates[cid], "_update_count", None)
                for _, cid in block}

    def rollback_update(p):
        """Discard a speculative dispatch: its device work is simply
        never fetched; the only HOST state it mutated is the
        down-sampling RNG stream position, which is restored here so the
        re-dispatch draws the keys the sequential sweep would have."""
        _set_update_counts(p.block, p.update_counts_before)

    def fetch_update(p):
        """THE blocking read: one ``jax.device_get`` of the fused
        epilogue's scalar pytree for the whole block. Raises
        :class:`CoordinateDivergenceError` (recovery mode only) when the
        block's states/objective are non-finite."""
        if p.pipelined:
            HOT_LOOP_STATS["pipelined_resolves"] += 1
        span_labels = {"sweep": p.it, "update": p.update}
        if len(p.block) == 1:
            span_labels["coordinate"] = p.block[0][1]
        else:
            span_labels["coordinates"] = ",".join(
                cid for _, cid in p.block)
        with contextlib.ExitStack() as stack:
            if p.pipelined:
                # the residual wait AFTER the overlap window — the part
                # of the epilogue latency double-buffering couldn't hide
                stack.enter_context(
                    trace.span("cd.pipeline_wait", **span_labels))
            stack.enter_context(
                trace.span("cd.epilogue_fetch", **span_labels))
            objective, train_loss, finite, state_finite = jax.device_get(
                (p.objective_d, p.train_loss_d, p.finite_d,
                 p.state_finite_d))
        record_host_fetch(site="cd.epilogue")
        HOT_LOOP_STATS["epilogue_fetches"] += 1
        HOT_LOOP_STATS["updates"] += len(p.block)
        objective = float(objective)
        if recovery is not None and not bool(finite):
            what = "state" if not bool(state_finite) else "objective"
            if len(p.block) == 1:
                raise CoordinateDivergenceError(
                    f"iter {p.it} coordinate {p.block[0][1]}: non-finite "
                    f"{what} (attempt {p.attempt})")
            raise CoordinateDivergenceError(
                f"iter {p.it} block "
                f"{[cid for _, cid in p.block]}: non-finite {what}")
        return objective, float(train_loss)

    def commit_update(p, objective, train_loss, seconds=None,
                      recovered_attempts=0, allow_snapshot=True):
        """Install an accepted block: states/scores/regs + the corrected
        canonical total, then the per-member bookkeeping (objective log,
        optional validation, history, checkpoint cadence).
        ``allow_snapshot=False`` defers the cadence snapshot to the
        caller — a multi-member block replaying its members one at a
        time must snapshot once at the BLOCK boundary, never after an
        individual member (a mid-block snapshot would re-partition the
        sweep's blocks on resume)."""
        nonlocal total, consecutive_failures
        nonlocal best_metric, best_model, best_states
        if recovered_attempts > 0:
            cid0 = p.block[0][1]
            emit(RecoveryEvent(action="recovered", coordinate_id=cid0,
                               iteration=p.it,
                               attempts=recovered_attempts))
            log(lambda: f"iter {p.it} coordinate {cid0}: recovered "
                f"after {recovered_attempts} retry(ies)")
        consecutive_failures = 0
        for _, cid in p.block:
            states[cid] = p.cands[cid]
            scores[cid] = p.new_scores[cid]
            reg_cache[cid] = p.new_regs[cid]
            # --device-telemetry: per-coordinate HBM watermark at the
            # moment this coordinate's buffers land (no-op unless armed;
            # metadata-only — never a device sync)
            devicemem.note_coordinate(cid)
        # canonical (ids order from zero), computed INSIDE the fused
        # epilogue — never incrementally drifted: resume parity
        total = p.new_total
        dt = seconds if seconds is not None else time.time() - p.t_wall
        per = dt / len(p.block)
        for _, cid in p.block:
            log(lambda cid=cid: f"iter {p.it} coordinate {cid}: "
                f"objective={objective:.6f} "
                f"({per:.2f}s) — {p.trackers[cid].summary()}")

        metrics = None
        if validate:
            with trace.span("cd.validation", sweep=p.it,
                            coordinates=",".join(c for _, c in p.block)):
                model = publish_game_model(coordinates, states)
                val_scores = model.score(validation_data)
                metrics = validation_evaluator(val_scores)
            log(lambda: f"iter {p.it} block "
                f"{[cid for _, cid in p.block]}: validation {metrics}")
            if validation_metric is not None:
                m = metrics[validation_metric]
                better = (best_metric is None
                          or (m > best_metric if higher_is_better
                              else m < best_metric))
                if better:  # (:245-255)
                    best_metric, best_model = m, model
                    best_states = dict(states)

        for _, cid in p.block:
            history.append(CoordinateDescentState(
                iteration=p.it, coordinate_id=cid, objective=objective,
                seconds=per, tracker=p.trackers[cid],
                validation_metrics=metrics))

        if p.snapshot_due and allow_snapshot:
            # snapshot at the RAW block boundary (quarantine-filtered
            # members included): state is committed through the block,
            # and resume re-partitions the sweep identically
            save_snapshot(p.it, p.snapshot_next_ci)

    def run_member(ci, cid, it, first_error=None, allow_snapshots=True,
                   snapshot_due=None, snapshot_next_ci=None):
        """One guarded coordinate update: the sequential retry / skip /
        quarantine ladder (dispatch + fetch inline, no overlap).
        ``first_error`` seeds the ladder with an attempt-0 failure
        already caught by the pipelined path — the ladder then proceeds
        exactly as if it had run that attempt itself.
        ``allow_snapshots=False`` marks a member replayed INSIDE a
        multi-coordinate block: snapshots (cadence and quarantine alike)
        are deferred to the enclosing block's boundary, preserving the
        never-mid-block invariant a blocked resume depends on.
        ``snapshot_due``/``snapshot_next_ci`` carry the enclosing RAW
        block's cadence flag and boundary (defaults: this member alone
        IS the block)."""
        nonlocal consecutive_failures
        if snapshot_due is None:
            snapshot_due = snapshot_cadence_due([(ci, cid)], it)
        if snapshot_next_ci is None:
            snapshot_next_ci = ci + 1
        with trace.span("cd.update", coordinate=cid, sweep=it):
            t0 = time.time()
            attempt = 0
            skipped = False
            budgeted_skip = False
            quarantine_now = False
            outcome = None
            error = first_error
            while True:
                if error is None:
                    try:
                        p = dispatch_update(
                            [(ci, cid)], it, attempt, total, {},
                            snapshot_due=snapshot_due,
                            snapshot_next_ci=snapshot_next_ci)
                        objective, train_loss = fetch_update(p)
                        outcome = (p, objective, train_loss)
                        break
                    except (InjectedFault, CoordinateDivergenceError,
                            FloatingPointError) as e:
                        if recovery is None:
                            raise
                        error = e
                        continue
                e, error = error, None
                # an InjectedFault knows its origin site (e.g.
                # "optimizer.gradient"); label divergence detected
                # here as cd.update
                emit(FaultEvent(point=getattr(e, "point", "cd.update"),
                                coordinate_id=cid,
                                iteration=it, message=str(e)))
                log(lambda: f"iter {it} coordinate {cid}: FAULT "
                    f"(attempt {attempt}): {e}")
                attempt += 1
                if attempt <= recovery.max_retries:
                    emit(RecoveryEvent(action="retried",
                                       coordinate_id=cid, iteration=it,
                                       attempts=attempt))
                    continue
                if recovery.quarantine_after > 0:
                    # per-coordinate budget: skip degraded until THIS
                    # coordinate's own budget exhausts, then freeze it
                    # (the global on_exhausted action never fires for
                    # budgeted coordinates — that is the point, and
                    # budgeted skips don't count toward the global
                    # consecutive-failure abort either)
                    coordinate_failures[cid] = (
                        coordinate_failures.get(cid, 0) + 1)
                    if (coordinate_failures[cid]
                            >= recovery.quarantine_after):
                        quarantine_now = True
                    else:
                        skipped = True
                        budgeted_skip = True
                    break
                if recovery.on_exhausted == "skip":
                    skipped = True
                    break
                raise RuntimeError(
                    f"coordinate descent aborted: coordinate {cid} "
                    f"failed {attempt} attempt(s) at iteration {it} "
                    f"(RecoveryPolicy on_exhausted='abort')") from e
            dt = time.time() - t0
            if quarantine_now:
                quarantined.add(cid)
                emit(CoordinateQuarantinedEvent(
                    coordinate_id=cid, iteration=it,
                    failures=coordinate_failures[cid],
                    message=(f"{coordinate_failures[cid]} exhausted "
                             f"update(s); frozen at last-good state")))
                log(lambda: f"iter {it} coordinate {cid}: QUARANTINED "
                    f"after {coordinate_failures[cid]} exhausted "
                    f"update(s) — frozen at last-good state, descent "
                    f"continues ({dt:.2f}s)")
                if checkpoint_manager is not None and allow_snapshots:
                    save_snapshot(it, snapshot_next_ci)
                return
            if skipped:
                # Keep the last-good state and its score; continue
                # degraded (the reference's closest analog: a failed
                # Spark stage retried elsewhere — here the coordinate
                # just sits out). A BUDGETED skip is bounded by the
                # coordinate's own quarantine budget, so it must not
                # also burn the global consecutive-failure budget (it
                # would abort the run before the quarantine ever
                # triggered).
                if not budgeted_skip:
                    consecutive_failures += 1
                emit(RecoveryEvent(action="skipped", coordinate_id=cid,
                                   iteration=it, attempts=attempt))
                log(lambda: f"iter {it} coordinate {cid}: SKIPPED after "
                    f"{attempt} failed attempt(s) — keeping last-good "
                    f"state ({dt:.2f}s)")
                if (not budgeted_skip and consecutive_failures
                        >= recovery.max_consecutive_failures):
                    emit(RecoveryEvent(action="aborted",
                                       coordinate_id=cid,
                                       iteration=it, attempts=attempt))
                    raise RuntimeError(
                        f"coordinate descent aborted: "
                        f"{consecutive_failures} consecutive coordinate "
                        f"updates failed (RecoveryPolicy "
                        f"max_consecutive_failures="
                        f"{recovery.max_consecutive_failures})")
                return
            p, objective, train_loss = outcome
            commit_update(p, objective, train_loss, seconds=dt,
                          recovered_attempts=attempt,
                          allow_snapshot=allow_snapshots)

    def replay_block_members(block, it, due_snapshot, next_ci):
        """Walk each block member through its own sequential ladder with
        snapshots DEFERRED, then save once at the RAW block boundary if
        the block crossed a cadence point — or if the replay quarantined
        a member (the sequential path persists quarantines promptly; the
        blocked path does so at its boundary). A mid-replay snapshot
        would land inside the block and re-partition the sweep on
        resume."""
        q_before = len(quarantined)
        for ci, cid in block:
            if cid not in quarantined:
                run_member(ci, cid, it, allow_snapshots=False)
        if (checkpoint_manager is not None
                and (due_snapshot or len(quarantined) > q_before)):
            save_snapshot(it, next_ci)

    def resolve_update(p, speculative=None):
        """Resolve one in-flight block: fetch its fused epilogue and
        commit — or, on divergence/fault, drop into the sequential
        recovery ladder from the last-good committed state. Returns True
        iff the block committed exactly as dispatched (the pipelined
        loop's signal that a speculative successor dispatch is still
        valid). ``speculative`` is that successor: on failure it is
        rolled back FIRST, before the ladder runs — the ladder's
        quarantine/cadence snapshots must never persist the speculative
        dispatch's advanced RNG stream positions (state the live run is
        about to discard)."""
        try:
            if len(p.block) == 1:
                with trace.span("cd.update", coordinate=p.block[0][1],
                                sweep=p.it):
                    objective, train_loss = fetch_update(p)
                    commit_update(p, objective, train_loss)
            else:
                with trace.span("cd.block", sweep=p.it,
                                size=len(p.block),
                                coordinates=",".join(
                                    cid for _, cid in p.block)):
                    objective, train_loss = fetch_update(p)
                    commit_update(p, objective, train_loss)
            return True
        except (CoordinateDivergenceError, FloatingPointError) as e:
            if recovery is None:
                raise
            if speculative is not None:
                rollback_update(speculative)
            if len(p.block) == 1:
                # the failed fetch WAS this coordinate's attempt 0: seed
                # the ladder with it (no rollback of p itself —
                # sequential retries advance the RNG stream per attempt,
                # and so must we)
                ci, cid = p.block[0]
                run_member(ci, cid, p.it, first_error=e,
                           snapshot_due=p.snapshot_due,
                           snapshot_next_ci=p.snapshot_next_ci)
            else:
                # the epilogue's finiteness flag covers the whole block:
                # discard the block (restoring RNG positions) and replay
                # members one at a time from the committed state —
                # innocents commit cleanly, the culprit walks its ladder
                emit(FaultEvent(point="cd.block", iteration=p.it,
                                message=str(e)))
                log(lambda: f"iter {p.it}: block "
                    f"{[cid for _, cid in p.block]} FAULT — replaying "
                    f"members sequentially: {e}")
                rollback_update(p)
                replay_block_members(p.block, p.it, p.snapshot_due,
                                     p.snapshot_next_ci)
            return False

    def run_block(raw_block, it, first_error=None):
        """Process one RAW block sequentially (dispatch + resolve
        inline): the unpipelined path, and the fallback every pipelined
        failure drops into. ``first_error`` carries a dispatch-time
        failure the pipelined loop already caught. Quarantined members
        are filtered here, but the snapshot boundary and cadence stay
        those of the RAW block — resume must re-partition the sweep
        identically."""
        block = [(ci, cid) for ci, cid in raw_block
                 if cid not in quarantined]
        if not block:
            return
        due = snapshot_cadence_due(raw_block, it)
        next_ci = raw_block[-1][0] + 1
        if first_error is None:
            try:
                p = dispatch_update(block, it, 0, total, {},
                                    snapshot_due=due,
                                    snapshot_next_ci=next_ci)
            except (InjectedFault, FloatingPointError) as e:
                if recovery is None:
                    raise
                first_error = e
            else:
                resolve_update(p)
                return
        # dispatch-time failure: straight to the ladder
        if len(block) > 1:
            emit(FaultEvent(point="cd.block", iteration=it,
                            message=str(first_error)))
            log(lambda: f"iter {it}: block "
                f"{[cid for _, cid in block]} FAULT at dispatch — "
                f"replaying members sequentially: {first_error}")
            replay_block_members(block, it, due, next_ci)
        else:
            run_member(block[0][0], block[0][1], it,
                       first_error=first_error,
                       snapshot_due=due, snapshot_next_ci=next_ci)

    for it in range(start_iteration, num_iterations):
        with trace.span("cd.sweep", sweep=it):
            fault_point("cd.sweep", tag=str(it))
            sweep_history_start = len(history)
            eligible = [(ci, cid) for ci, cid in enumerate(ids)
                        if not (it == start_iteration
                                and ci < start_coordinate)]
            blocks = [eligible[i:i + block_size]
                      for i in range(0, len(eligible), block_size)]

            pending: Optional[_InFlight] = None
            for raw_block in blocks:
                if stop is not None:
                    reason = stop.should_stop()
                    if reason is not None:
                        # Commit barrier: settle the in-flight pipelined
                        # handle first (the snapshot must read committed
                        # state, same rule as the checkpoint barrier),
                        # write the final "about to run this block"
                        # snapshot, and hand the exact resume position
                        # to the driver. Never tears a block.
                        if pending is not None:
                            resolve_update(pending)
                            pending = None
                        if checkpoint_manager is not None:
                            save_snapshot(it, raw_block[0][0])
                        raise PreemptionRequested(reason, it,
                                                  raw_block[0][0])
                block = [(ci, cid) for ci, cid in raw_block
                         if cid not in quarantined]
                if not block:
                    continue
                if not use_pipeline:
                    run_block(raw_block, it)
                    continue
                if pending is not None and pending.snapshot_due:
                    # checkpoint barrier: the pending block snapshots
                    # when it resolves, and a snapshot must never race a
                    # speculative in-flight successor — settle first
                    resolve_update(pending)
                    pending = None
                if pending is not None:
                    base_total = pending.new_total
                    overlay = {cid: (pending.new_scores[cid],
                                     pending.new_regs[cid])
                               for _, cid in pending.block}
                else:
                    base_total, overlay = total, {}
                counts0 = _snap_update_counts(block)
                try:
                    cur = dispatch_update(
                        block, it, 0, base_total, overlay,
                        snapshot_due=snapshot_cadence_due(raw_block, it),
                        snapshot_next_ci=raw_block[-1][0] + 1)
                except (InjectedFault, CoordinateDivergenceError,
                        FloatingPointError) as e:
                    # the dispatch itself failed (injected fault): settle
                    # the pending block first — its events and commit
                    # precede this block's ladder, as in the sequential
                    # order — then walk this block through the ladder
                    if pending is not None:
                        pending.pipelined = True
                        # pending's ladder may snapshot; a snapshot's
                        # "about to run this block" must carry PRE-
                        # dispatch RNG positions (what a sequential
                        # run's snapshot would hold), while the seeded
                        # ladder below still owns the failed dispatch's
                        # advance as its attempt 0 — swap the counters
                        # around the resolution
                        counts_adv = _snap_update_counts(block)
                        _set_update_counts(block, counts0)
                        resolve_update(pending)
                        pending = None
                        _set_update_counts(block, counts_adv)
                    if recovery is None:
                        raise
                    run_block(raw_block, it, first_error=e)
                    continue
                inflight = len(cur.block) + (
                    len(pending.block) if pending is not None else 0)
                REGISTRY.gauge("cd_inflight_updates").set(inflight)
                if inflight > HOT_LOOP_STATS["max_inflight"]:
                    HOT_LOOP_STATS["max_inflight"] = inflight
                if pending is not None:
                    pending.pipelined = True
                    ok = resolve_update(pending, speculative=cur)
                    pending = None
                    if not ok:
                        # the commit diverged from the overlay this
                        # dispatch speculated on (retry/skip/quarantine
                        # changed the state): resolve_update already
                        # rolled it back (BEFORE the ladder could
                        # snapshot its speculative RNG positions) — just
                        # re-run from the re-committed last-good state
                        run_block(raw_block, it)
                        continue
                pending = cur
            if pending is not None:
                # sweep drain: the last block resolves before the
                # tracker drain / sweep snapshot read committed state
                resolve_update(pending)
                pending = None

            # Sweep boundary: drain this sweep's lazy trackers (one
            # batched explicit fetch each, amortized over the whole
            # sweep) so their device-resident per-entity arrays and
            # solver histories don't accumulate in HBM across a long
            # run. The per-update hot path stays at one fetch per block;
            # this drain is the off-hot-path counterpart, like the
            # checkpoint below.
            with trace.span("cd.tracker_drain", sweep=it):
                for h in history[sweep_history_start:]:
                    mat = getattr(h.tracker, "materialize", None)
                    if mat is not None:
                        mat()
            # live-buffer watermark AFTER the drain: the signal that
            # tunes pipeline depth and the drain policy from a trace
            _sample_live_bytes(it)

            if checkpoint_manager is not None:
                save_snapshot(it, len(ids))

    final = publish_game_model(coordinates, states)
    return CoordinateDescentResult(model=final, states=history,
                                   best_model=best_model,
                                   best_metric=best_metric,
                                   quarantined=sorted(quarantined))


def publish_game_model(coordinates: dict[str, Coordinate], states: dict
                       ) -> GameModel:
    return GameModel({cid: coordinates[cid].publish(states[cid])
                      for cid in coordinates})
