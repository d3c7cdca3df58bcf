"""Shared optimizer structures: convergence reasons, results, box projection.

TPU-native re-design of the reference's ``Optimizer`` state machine
(reference: photon-ml/src/main/scala/com/linkedin/photon/ml/optimization/
Optimizer.scala:39-245). The reference mutates driver-side state per
iteration; here each solver is one jitted ``lax.while_loop`` whose carry holds
(x, value, gradient, history) in device arrays, and convergence reasons are
re-derived from the recorded history exactly as Optimizer.scala:156-170 does:

- MaxIterations:            iter >= max_iter
- ObjectiveNotImproving:    the last iteration failed to produce a new state
- FunctionValuesConverged:  |f_k - f_{k-1}| <= tol * f_0
- GradientConverged:        ||g_k||_2 <= tol * ||g_0||_2
"""

from __future__ import annotations

import dataclasses
import enum
from typing import NamedTuple, Optional

import jax.numpy as jnp
import numpy as np
from jax import lax

from photon_ml_tpu.obs.metrics import REGISTRY

Array = jnp.ndarray


class ConvergenceReason(enum.Enum):
    MAX_ITERATIONS = "MaxIterations"
    OBJECTIVE_NOT_IMPROVING = "ObjectiveNotImproving"
    FUNCTION_VALUES_CONVERGED = "FunctionValuesConverged"
    GRADIENT_CONVERGED = "GradientConverged"


class BoxConstraints(NamedTuple):
    """Elementwise [lower, upper] bounds; +-inf for unconstrained coords.

    Replaces OptimizationUtils.projectCoefficientsToHypercube — the reference
    projects iterates onto the hypercube after each optimizer step
    (optimization/LBFGS.scala:42-150, TRON.scala accept branch).
    """

    lower: Array
    upper: Array

    @staticmethod
    def from_map(dim: int, constraint_map: Optional[dict[int, tuple[float, float]]]):
        if not constraint_map:
            return None
        lower = np.full(dim, -np.inf)
        upper = np.full(dim, np.inf)
        for idx, (lo, hi) in constraint_map.items():
            lower[idx], upper[idx] = lo, hi
        # Full-precision bounds; project_box casts to the iterate dtype.
        return BoxConstraints(jnp.asarray(lower), jnp.asarray(upper))


def solver_x0(acc_dtype, shape, initial: Optional[Array]) -> Array:
    """Initial solver state under the mixed-precision invariant: at least
    ``acc_dtype`` (f32 over low-precision data), and a warm start can only
    UPCAST — a bf16 initial promotes, an f64 initial keeps the whole solve
    in f64 (x64 callers rely on that). ONE definition for every solve
    entry point (single-chip, shard_map, per-entity vmapped)."""
    if initial is None:
        return jnp.zeros(shape, acc_dtype)
    initial = jnp.asarray(initial)
    return initial.astype(jnp.promote_types(acc_dtype, initial.dtype))


def finite_step(accepted: Array, f: Array, g: Array,
                axis_name: Optional[str] = None) -> Array:
    """Combine a step-acceptance flag with a non-finite guard.

    A NaN/Inf objective or gradient must never enter the accepted solver
    state: divergence then surfaces as ObjectiveNotImproving at the last
    good iterate instead of poisoning the whole carry (and, under vmap,
    every entity lane reduced with it). Every solver body routes its
    accept flag through here.

    ``axis_name``: when the weight update is sharded over a mesh axis,
    ``g`` is a shard and the finite verdict must be replica-uniform (one
    replica's while_loop stopping early while another continues would
    desynchronize the collectives inside the loop body) — the local
    verdict is all-reduced over the axis.
    """
    fin = jnp.isfinite(f) & jnp.all(jnp.isfinite(g))
    if axis_name is not None:
        fin = lax.psum(jnp.int32(~fin), axis_name) == 0
    return accepted & fin


def project_box(x: Array, box: Optional[BoxConstraints]) -> Array:
    if box is None:
        return x
    return jnp.clip(x, box.lower.astype(x.dtype), box.upper.astype(x.dtype))


class RunHistory(NamedTuple):
    """Fixed-shape device-side record of the optimization trajectory.

    ``values[k]`` / ``grad_norms[k]`` hold f and ||g|| *after* iteration k
    (k=0 is the initial state); slots beyond ``num_iterations`` are NaN.
    Feeds OptimizationStatesTracker (ring buffer of at most 100 states,
    reference OptimizationStatesTracker.scala:31-98) host-side.
    """

    values: Array  # [max_iter + 1]
    grad_norms: Array  # [max_iter + 1]
    num_iterations: Array  # scalar int32: last completed iteration index
    # Per-iteration coefficient snapshots [max_iter + 1, d], recorded only
    # when the solver runs with track_iterates=True (the reference's
    # ModelTracker.models, Optimizer.scala state tracking) — None otherwise
    # so the untracked compile carries no [k, d] buffer.
    iterates: Optional[Array] = None
    # int32 [max_iter + 1]: calls of ``value_and_grad_fn`` (each one pass
    # over the rows). Slot 0 holds those made before the first iteration
    # (1 from a fresh start, 0 on ``resume``), slot k those spent on
    # iteration k: the line search's trials plus the box projection's
    # re-evaluation. TRON's rejected trial steps are booked to the slot of
    # the iteration they were tried for, so the total is the sum over ALL
    # slots, not only the first ``num_iterations + 1``. An L-BFGS solve
    # whose line search tries its steps on carried margins (``line_fn``)
    # books its start and one evaluation an iteration, at the accepted
    # point: its trials are ``line_trials``.
    evaluations: Optional[Array] = None
    # TRON only: Hessian-vector products per iteration (one more pass over
    # the rows each), booked like ``evaluations``.
    hvps: Optional[Array] = None
    # L-BFGS with ``line_fn`` only: the line search's trials per iteration,
    # each elementwise work over the rows' carried margins and no pass
    # over the rows (the one pass an iteration that forms the margins is
    # in neither count).
    line_trials: Optional[Array] = None


@dataclasses.dataclass(frozen=True)
class OptimizationResult:
    """Host-side summary of one solver run."""

    coefficients: Array
    value: float
    grad_norm: float
    iterations: int
    convergence_reason: ConvergenceReason
    values: np.ndarray  # trajectory f_0..f_k
    grad_norms: np.ndarray  # trajectory ||g_0||..||g_k||
    iterates: Optional[np.ndarray] = None  # [k+1, d] when tracked
    # totals of RunHistory.evaluations / .hvps (None where the history
    # carries none, e.g. one assembled from per-shard solves)
    evaluations: Optional[int] = None
    hvps: Optional[int] = None

    @staticmethod
    def from_history(
        coefficients: Array,
        history: RunHistory,
        max_iter: int,
        tolerance: float,
        made_progress_last_iter: bool = True,
        site: Optional[str] = None,
        coordinate: Optional[str] = None,
    ) -> "OptimizationResult":
        """``site`` (an ``obs/compile.py`` site name, e.g.
        ``optimizer.lbfgs``) books the solve on the ``solver_*`` counters,
        under ``coordinate`` too where the solve was a GAME coordinate's:
        pass it where a solve's history reaches the host ONCE. A history
        still on the device comes over in one explicit fetch of the whole
        pytree (it used to cost one blocking read per field)."""
        if not isinstance(history.values, np.ndarray):
            import jax

            from photon_ml_tpu.utils.sync_telemetry import record_host_fetch

            history = jax.device_get(history)
            record_host_fetch(site="optimizer.history")
        k = int(history.num_iterations)
        evaluations = (None if history.evaluations is None
                       else int(np.sum(history.evaluations)))
        hvps = None if history.hvps is None else int(np.sum(history.hvps))
        if site is not None:
            record_solve(site, k, evaluations, hvps, coordinate=coordinate,
                         line_trials=(None if history.line_trials is None
                                      else int(np.sum(history.line_trials))))
        values = np.asarray(history.values)[: k + 1]
        grad_norms = np.asarray(history.grad_norms)[: k + 1]
        reason = _convergence_reason(
            k, values, grad_norms, max_iter, tolerance, made_progress_last_iter
        )
        return OptimizationResult(
            coefficients=coefficients,
            value=float(values[-1]),
            grad_norm=float(grad_norms[-1]),
            iterations=k,
            convergence_reason=reason,
            values=values,
            grad_norms=grad_norms,
            iterates=(None if history.iterates is None
                      else np.asarray(history.iterates)[: k + 1]),
            evaluations=evaluations,
            hvps=hvps,
        )


def record_solve(site: str, iterations: int, evaluations: Optional[int],
                 hvps: Optional[int] = None,
                 lane_evaluations: Optional[int] = None,
                 coordinate: Optional[str] = None,
                 line_trials: Optional[int] = None) -> None:
    """Book solves whose counts just reached the host on the
    ``solver_*{site}`` counters. A solve with no evaluation count books
    nothing: a ratio of the counters must never mix counted and uncounted
    solves. ``lane_evaluations`` is what a batched loop executed (lanes x
    rounds, pad lanes included); a single solve executes what it needs.
    ``line_trials`` (``RunHistory.line_trials``) goes on
    ``solver_line_trials``, 0 for solves whose trials are full evaluations.
    ``coordinate`` (the id of the GAME coordinate whose update made the
    solve, in the updating sequence) is a second label beside ``site``: a
    sweep's coordinates share their sites, and a reader that filters on
    ``site`` alone still reads the totals."""
    if evaluations is None:
        return
    labels = {"site": site}
    if coordinate is not None:
        labels["coordinate"] = coordinate
    REGISTRY.counter("solver_iterations").inc(iterations, **labels)
    REGISTRY.counter("solver_evaluations").inc(evaluations, **labels)
    REGISTRY.counter("solver_lane_evaluations").inc(
        evaluations if lane_evaluations is None else lane_evaluations,
        **labels)
    REGISTRY.counter("solver_line_trials").inc(line_trials or 0, **labels)
    if hvps is not None:
        REGISTRY.counter("solver_hvps").inc(hvps, **labels)


class DeferredOptimizationResult:
    """:class:`OptimizationResult` facade whose history stays device-resident.

    ``coefficients`` is available immediately as a device array (the CD hot
    loop threads it straight into the next jitted op with no sync); every
    scalar field (value/grad_norm/iterations/convergence_reason/...)
    materializes lazily, with ONE explicit ``jax.device_get`` of the whole
    history pytree on first touch. This is what makes the fixed-effect
    coordinate update free of blocking device→host reads: the eager
    ``OptimizationResult.from_history`` paid an ``int()`` + two
    ``np.asarray`` syncs per solve before the epilogue even ran.
    """

    def __init__(self, coefficients: Array, history: RunHistory,
                 progressed, max_iter: int, tolerance: float,
                 site: Optional[str] = None):
        self.coefficients = coefficients
        self._history = history
        self._progressed = progressed
        self._max_iter = max_iter
        self._tolerance = tolerance
        self._site = site
        # the GAME coordinate whose update this solve was, set by its
        # tracker before the history is forced (record_solve's label)
        self.coordinate: Optional[str] = None
        self._result: Optional[OptimizationResult] = None

    def _force(self) -> OptimizationResult:
        if self._result is None:
            import jax

            from photon_ml_tpu.utils.sync_telemetry import record_host_fetch

            history, progressed = jax.device_get(
                (self._history, self._progressed))
            record_host_fetch(site="optimizer.history")
            self._result = OptimizationResult.from_history(
                self.coefficients, history,
                self._max_iter, self._tolerance, bool(progressed),
                site=self._site, coordinate=self.coordinate)
            self._history = self._progressed = None
        return self._result

    @property
    def value(self) -> float:
        return self._force().value

    @property
    def grad_norm(self) -> float:
        return self._force().grad_norm

    @property
    def iterations(self) -> int:
        return self._force().iterations

    @property
    def convergence_reason(self) -> ConvergenceReason:
        return self._force().convergence_reason

    @property
    def values(self) -> np.ndarray:
        return self._force().values

    @property
    def grad_norms(self) -> np.ndarray:
        return self._force().grad_norms

    @property
    def iterates(self) -> Optional[np.ndarray]:
        return self._force().iterates

    @property
    def evaluations(self) -> Optional[int]:
        return self._force().evaluations

    @property
    def hvps(self) -> Optional[int]:
        return self._force().hvps


@dataclasses.dataclass
class LaneCompactionState:
    """Chunk-resumable state for a batched (vmapped) solve over lanes.

    The batched solver runs every lane to the SLOWEST lane's iteration
    count; when per-lane convergence is heterogeneous (90% of entities done
    in 5 iterations, a few stragglers needing 50) that is almost all wasted
    FLOPs. The compacted driver instead solves in iteration chunks: after
    each chunk the still-active lanes are gathered into a dense block and
    only those re-dispatch. This object owns the global result buffers
    (device-resident) and the host-side active-lane bookkeeping between
    chunks; ``absorb`` folds one chunk's output back in and reports which
    lanes remain.

    Chunk restarts carry the FULL per-lane solver state (the solvers'
    ``LBFGSResume``/``TRONResume`` carries: iterate, curvature history /
    trust region, previous objective) plus the ORIGINAL dispatch's
    f₀/‖g₀‖ anchors, so the relative convergence thresholds
    (|Δf| ≤ tol·|f₀|, ‖g‖ ≤ tol·‖g₀‖) never re-anchor and a chunked
    solve runs exactly the iterations the single dispatch would — the
    parity contract is bit-identical coefficients, not just tolerance
    agreement (tests/test_sync_discipline.py).
    """

    coefs: Array  # [E, D] device
    iterations: Array  # [E] int32 device (accumulated across chunks)
    evaluations: Array  # [E] int32 device (accumulated like iterations)
    line_trials: Array  # [E] int32 device (accumulated like iterations)
    values: Array  # [E] device (last chunk's final value per lane)
    codes: Array  # [E] int8 device (last chunk's convergence code)
    active: np.ndarray  # host int32 global lane ids still unconverged

    @staticmethod
    def initial(x0: Array, value_dtype) -> "LaneCompactionState":
        e = int(x0.shape[0])
        return LaneCompactionState(
            coefs=x0,
            iterations=jnp.zeros(e, jnp.int32),
            evaluations=jnp.zeros(e, jnp.int32),
            line_trials=jnp.zeros(e, jnp.int32),
            values=jnp.zeros(e, value_dtype),
            codes=jnp.zeros(e, jnp.int8),
            active=np.arange(e, dtype=np.int32),
        )

    def absorb(self, idx, c: Array, it: Array, ev: Array, v: Array,
               k: Array, max_iterations_code: int,
               tr: Array) -> tuple[np.ndarray, np.ndarray]:
        """Fold one chunk's output (lane-compacted when ``idx`` is not
        None; ``tr`` its lanes' line trials) into the global buffers;
        returns ``(global_ids,
        local_positions)`` of lanes the chunk did NOT converge (they hit
        the chunk's iteration budget) — the local positions index this
        chunk's dispatch lanes, which is what the carry-based restart
        gathers the per-lane solver state with. The unconverged mask is
        the ONE blocking device→host fetch of the chunk — everything
        else stays on device."""
        import jax

        from photon_ml_tpu.utils.sync_telemetry import record_host_fetch

        if idx is None:  # first chunk: all lanes ran, in global order
            self.coefs, self.values, self.codes = c, v, k
            self.iterations, self.evaluations, self.line_trials = it, ev, tr
            unconverged = np.asarray(
                jax.device_get(k == max_iterations_code))
            record_host_fetch(site="re.compact_mask")
            local = np.nonzero(unconverged)[0].astype(np.int32)
            return self.active[unconverged], local
        n_real = len(idx)
        idx_dev = jax.device_put(idx)
        self.coefs = self.coefs.at[idx_dev].set(c[:n_real])
        self.iterations = self.iterations.at[idx_dev].add(it[:n_real])
        self.evaluations = self.evaluations.at[idx_dev].add(ev[:n_real])
        self.line_trials = self.line_trials.at[idx_dev].add(tr[:n_real])
        self.values = self.values.at[idx_dev].set(v[:n_real])
        self.codes = self.codes.at[idx_dev].set(k[:n_real])
        unconverged = np.asarray(
            jax.device_get(k[:n_real] == max_iterations_code))
        record_host_fetch(site="re.compact_mask")
        local = np.nonzero(unconverged)[0].astype(np.int32)
        return idx[unconverged], local

    def absorb_padded(self, idx: np.ndarray, mask: np.ndarray, c: Array,
                      it: Array, ev: Array, v: Array, k: Array,
                      max_iterations_code: int, tr: Array
                      ) -> tuple[np.ndarray, np.ndarray]:
        """Mesh-sharded-chunk variant of :meth:`absorb`: the dispatch lanes
        arrive in per-shard padded layout (flat ``[K * L]``), where a pad
        slot duplicates a real lane of the SAME shard — identical data,
        carry and anchors mean an identical solve, so the duplicate
        ``.set`` writes are value-equal and benign. ``idx`` maps every
        flat slot to its global lane id and ``mask`` flags the real
        slots; iteration and evaluation counts from pad slots are zeroed before the
        scatter-add so duplicates never double-count. Returns
        ``(global_ids, flat_positions)`` of the real lanes that hit the
        budget, exactly like :meth:`absorb`. Still exactly ONE blocking
        device→host fetch (the unconverged mask)."""
        import jax

        from photon_ml_tpu.utils.sync_telemetry import record_host_fetch

        idx_dev = jax.device_put(idx)
        mask_dev = jax.device_put(mask)
        self.coefs = self.coefs.at[idx_dev].set(c)
        self.iterations = self.iterations.at[idx_dev].add(
            jnp.where(mask_dev, it, 0))
        self.evaluations = self.evaluations.at[idx_dev].add(
            jnp.where(mask_dev, ev, 0))
        self.line_trials = self.line_trials.at[idx_dev].add(
            jnp.where(mask_dev, tr, 0))
        self.values = self.values.at[idx_dev].set(v)
        self.codes = self.codes.at[idx_dev].set(k)
        unconverged = np.asarray(
            jax.device_get(k == max_iterations_code))
        record_host_fetch(site="re.compact_mask")
        real = mask & unconverged
        local = np.nonzero(real)[0].astype(np.int32)
        return idx[real], local

    def results(self) -> tuple[Array, Array, Array, Array, Array, Array]:
        return (self.coefs, self.iterations, self.values, self.codes,
                self.evaluations, self.line_trials)


def padded_lane_count(n: int, floor: int = 8) -> int:
    """Round an active-lane count up to a power of two (≥ ``floor``) so
    re-dispatched chunk shapes repeat and the jit cache absorbs them —
    without padding, every distinct straggler count would compile a fresh
    solver executable."""
    n = max(int(n), 1)
    p = floor
    while p < n:
        p *= 2
    return p


def _convergence_reason(
    k: int,
    values: np.ndarray,
    grad_norms: np.ndarray,
    max_iter: int,
    tolerance: float,
    made_progress_last_iter: bool,
) -> ConvergenceReason:
    """Port of Optimizer.getConvergenceReason (Optimizer.scala:156-170)."""
    if k >= max_iter:
        return ConvergenceReason.MAX_ITERATIONS
    if not made_progress_last_iter:
        return ConvergenceReason.OBJECTIVE_NOT_IMPROVING
    if k >= 1 and abs(values[-1] - values[-2]) <= tolerance * abs(values[0]):
        return ConvergenceReason.FUNCTION_VALUES_CONVERGED
    if grad_norms[-1] <= tolerance * grad_norms[0]:
        return ConvergenceReason.GRADIENT_CONVERGED
    # Loop exited without tripping a criterion (shouldn't happen, but keep a
    # total function): classify by the strongest signal available.
    return ConvergenceReason.FUNCTION_VALUES_CONVERGED


def should_continue(
    it: Array,
    value: Array,
    prev_value: Array,
    grad_norm: Array,
    init_value: Array,
    init_grad_norm: Array,
    max_iter: int,
    tolerance: float,
    made_progress: Array,
    resumed: bool = False,
) -> Array:
    """jit-side mirror of the host convergence check (Optimizer.scala:156-170).

    Iteration 0 (prev_value == init_value sentinel) always continues —
    EXCEPT on a chunk-resumed solve (``resumed=True``), where
    ``prev_value`` is the real objective from one iteration before the
    restart point and ``init_value``/``init_grad_norm`` are the ORIGINAL
    dispatch's anchors: the restart's first check must then be exactly
    the check the uninterrupted loop would have run at that global
    iteration, not an unconditional continue.
    """
    not_done = (
        (it < max_iter)
        & made_progress
        & (jnp.abs(value - prev_value) > tolerance * jnp.abs(init_value))
        & (grad_norm > tolerance * init_grad_norm)
    )
    if resumed:
        return not_done
    # Iteration 0 runs unless already at a stationary point (zero initial
    # gradient) — a warm start at the optimum must report GradientConverged,
    # not burn a degenerate line search.
    return (it == 0) & made_progress & (init_grad_norm > 0.0) | not_done
