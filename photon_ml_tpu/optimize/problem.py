"""Unified GLM optimization problem: objective x optimizer x regularization.

TPU-native merge of the reference's problem hierarchy
(reference: photon-ml/src/main/scala/com/linkedin/photon/ml/optimization/
GeneralizedLinearOptimizationProblem.scala:39-174,
DistributedOptimizationProblem.scala:41-193,
SingleNodeOptimizationProblem.scala:37-140). The distributed/single-node split
disappears: one jitted solve serves a replicated single-chip batch, a
mesh-sharded fixed-effect batch, and (vmapped) per-entity random-effect
blocks.

Carried semantics:
- optimizer dispatch per OptimizerFactory.scala:40-85 (LBFGS+L1 -> OWL-QN,
  TRON+L1 -> error, smoothed hinge -> no TRON)
- elastic-net split: lambda1 to OWL-QN, lambda2 into the smooth objective
- zero-model initialization + warm starts
  (GeneralizedLinearOptimizationProblem.initializeZeroModel / ModelTraining
  warm-start fold)
- variance approximation var_j = 1 / (H_jj + eps)
  (DistributedOptimizationProblem.scala:41-193)
- model creation de-normalizes coefficients back to the raw feature space
  (NormalizationContext.transformModelCoefficients)
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from photon_ml_tpu.data.batch import Batch, rows_in_layout_order
from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.obs import trace
from photon_ml_tpu.ops.aggregators import GLMObjective
from photon_ml_tpu.ops.losses import get_loss
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.optimize.common import (
    BoxConstraints,
    DeferredOptimizationResult,
    OptimizationResult,
    solver_x0,
)
from photon_ml_tpu.optimize.config import (
    GLMOptimizationConfiguration,
    OptimizerType,
    RegularizationType,
    TASK_LOSS_NAME,
    TaskType,
)
from photon_ml_tpu.optimize.lbfgs import minimize_lbfgs
from photon_ml_tpu.optimize.owlqn import minimize_owlqn
from photon_ml_tpu.optimize.tron import minimize_tron

Array = jnp.ndarray

VARIANCE_EPSILON = 1e-12


def _objective_vg(w, payload):
    obj, batch = payload
    return obj.calculate(w, batch)


def _objective_hvp(w, v, payload):
    obj, batch = payload
    return obj.hessian_vector(w, v, batch)


@dataclasses.dataclass(frozen=True)
class GLMOptimizationProblem:
    """A ready-to-run GLM training problem for one coordinate/shard."""

    config: GLMOptimizationConfiguration
    task: TaskType
    normalization: NormalizationContext = NormalizationContext()
    box: Optional[BoxConstraints] = None
    compute_variances: bool = False
    # L1 exemption mask applied to the intercept by callers who add one.
    l1_mask: Optional[Array] = None
    # Record per-iteration coefficient snapshots in the result (the
    # reference's ModelTracker.models, consumed by --validate-per-iteration).
    track_iterates: bool = False
    # Shard the optimizer state + coefficient update over the mesh data
    # axis (arXiv 2004.13336): each replica updates only its coefficient
    # shard and all-gathers the result, instead of every replica running
    # the full-dimension update redundantly. Only engages on the
    # shard_map backend with a >1 data axis; incompatible with box
    # constraints and track_iterates (falls back to the replicated
    # update there).
    shard_weight_update: bool = False
    # Wire format of the mesh collectives this problem's sharded solve
    # emits ("none" | "int8", parallel/quantized_collectives.py —
    # driver --collective-quant). Irrelevant on the local backend.
    collective_quant: str = "none"

    def __post_init__(self):
        from photon_ml_tpu.parallel.quantized_collectives import \
            check_quant_mode
        check_quant_mode(self.collective_quant)
        if (self.task == TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM
                and self.config.optimizer_type == OptimizerType.TRON):
            # function/svm has no Hessian: DiffFunction only
            # (DistributedSmoothedHingeLossFunction.scala:131).
            raise ValueError("TRON requires a twice-differentiable loss; "
                             "smoothed hinge SVM supports LBFGS/OWLQN only")

    # -- objective construction ---------------------------------------------

    def objective(self) -> GLMObjective:
        cfg = self.config
        l2 = cfg.regularization_context.l2_weight(cfg.regularization_weight)
        return GLMObjective(
            loss=get_loss(TASK_LOSS_NAME[self.task]),
            norm=self.normalization,
            l2_lambda=l2,
            has_hessian=self.task != TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
            collective_quant=self.collective_quant,
        )

    # -- solve ---------------------------------------------------------------

    def solver_site(self) -> str:
        """The solver this configuration dispatches to, by the name its
        ``obs/compile.py`` site and its ``solver_*`` counters carry."""
        cfg = self.config
        if cfg.optimizer_type == OptimizerType.LBFGS:
            l1 = cfg.regularization_context.l1_weight(
                cfg.regularization_weight)
            return "optimizer.owlqn" if l1 > 0.0 else "optimizer.lbfgs"
        if cfg.optimizer_type == OptimizerType.TRON:
            return "optimizer.tron"
        raise ValueError(f"unknown optimizer {cfg.optimizer_type}")

    def solve(self, obj: GLMObjective, batch: Batch, x0: Array,
              update_axis_name: Optional[str] = None,
              vg_fn=None, hvp_fn=None, l1_mask: Optional[Array] = None):
        """Optimizer dispatch → (x, RunHistory, progressed). Pure-jax: safe
        to call under jit/shard_map (parallel/distributed.py wraps it with
        a per-shard batch and a psum-ing objective).

        ``update_axis_name``/``vg_fn``/``hvp_fn``/``l1_mask``: the sharded
        weight-update backend (parallel/distributed._sharded callers)
        passes a per-replica ``x0`` shard, gather/slice-wrapped objective
        callables, and a pre-sliced L1 mask; every d-vector reduction
        inside the solver then psums over the axis."""
        cfg = self.config
        # a solve reads the rows through sums alone: a layout that holds
        # them in an order of its own hands the row vectors over in that
        # order here, once, and no evaluation permutes anything
        payload = (obj, rows_in_layout_order(batch))
        vg = _objective_vg if vg_fn is None else vg_fn
        hvp = _objective_hvp if hvp_fn is None else hvp_fn
        mask = self.l1_mask if l1_mask is None else l1_mask
        dim = x0.shape[-1]
        site = self.solver_site()

        if site == "optimizer.owlqn":
            l1 = cfg.regularization_context.l1_weight(
                cfg.regularization_weight)
            l1_arr = jnp.full(dim, l1, x0.dtype)
            if mask is not None:
                l1_arr = l1_arr * mask.astype(x0.dtype)
            return minimize_owlqn(
                vg, x0, payload, l1=l1_arr,
                max_iter=cfg.max_iterations, tolerance=cfg.tolerance,
                box=self.box, track_iterates=self.track_iterates,
                update_axis_name=update_axis_name,
                collective_quant=self.collective_quant)
        if site == "optimizer.lbfgs":
            return minimize_lbfgs(
                vg, x0, payload,
                max_iter=cfg.max_iterations, tolerance=cfg.tolerance,
                box=self.box, track_iterates=self.track_iterates,
                update_axis_name=update_axis_name,
                collective_quant=self.collective_quant)
        return minimize_tron(
            vg, hvp, x0, payload,
            max_iter=cfg.max_iterations, tolerance=cfg.tolerance,
            box=self.box, track_iterates=self.track_iterates,
            update_axis_name=update_axis_name,
            collective_quant=self.collective_quant)

    def publish(self, x: Array, history, progressed,
                obj: Optional[GLMObjective] = None,
                batch: Optional[Batch] = None
                ) -> tuple[GeneralizedLinearModel, OptimizationResult]:
        """Solver output → (raw-space model, result record): optional
        variance approximation, then coefficient de-normalization
        (createModel analog)."""
        cfg = self.config
        result = OptimizationResult.from_history(
            x, history, cfg.max_iterations, cfg.tolerance, bool(progressed),
            site=self.solver_site())

        variances = None
        if self.compute_variances and obj is not None and batch is not None:
            diag = obj.hessian_diagonal(x, batch)
            variances = 1.0 / (diag + VARIANCE_EPSILON)

        # De-normalize into raw feature space for the published model
        # (training stays in normalized space; createModel analog).
        means = self.normalization.transform_model_coefficients(x)
        model = GeneralizedLinearModel(
            Coefficients(means=means, variances=variances), self.task)
        return model, result

    def run(self, batch: Batch, initial: Optional[Array] = None
            ) -> tuple[GeneralizedLinearModel, OptimizationResult]:
        """Train on a device batch; returns (model in RAW feature space,
        optimization result with trajectory + convergence reason).

        When the process has a default mesh with a >1 data axis
        (parallel/mesh.setup_default_mesh — the drivers' bootstrap), the
        solve routes through the explicit shard_map+psum backend: rows are
        sharded, each device runs the solver loop locally, and per-shard
        shapes stay local so the fused Pallas kernel engages on every chip
        (a pallas_call has no GSPMD partitioning rule, so the auto-sharded
        path would silently fall back to the two-pass XLA form on a pod).
        """
        from photon_ml_tpu.parallel.mesh import DATA_AXIS, get_default_mesh
        from photon_ml_tpu.utils.faults import fault_point

        mesh = get_default_mesh()
        if mesh is not None and mesh.shape.get(DATA_AXIS, 1) > 1:
            from photon_ml_tpu.parallel.distributed import run_glm_shard_map

            with trace.span("optimizer.solve", backend="shard_map",
                            optimizer=self.config.optimizer_type.name):
                model, result = run_glm_shard_map(self, batch, mesh,
                                                  initial=initial)
        else:
            dim = batch.num_features
            x0 = solver_x0(batch.acc_dtype, dim, initial)
            obj = self.objective()
            with trace.span("optimizer.solve", backend="local",
                            optimizer=self.config.optimizer_type.name):
                x, history, progressed = self.solve(obj, batch, x0)
            # the solve above only DISPATCHED: this span holds the wait
            # for it and the history's fetch, i.e. where the device runs
            # dry between two solves of a grid
            with trace.span("optimizer.publish",
                            optimizer=self.config.optimizer_type.name):
                model, result = self.publish(x, history, progressed, obj,
                                             batch)
        # Host-level fault site (never inside the jitted solve, where an
        # injection would bake into the compile cache): a nan-mode fault
        # here simulates a diverged solve for the recovery-policy tests.
        poisoned = fault_point("optimizer.gradient",
                               arrays=result.coefficients)
        if poisoned is not result.coefficients:
            result = dataclasses.replace(result, coefficients=poisoned)
            model = GeneralizedLinearModel(
                Coefficients(means=self.normalization
                             .transform_model_coefficients(poisoned),
                             variances=model.coefficients.variances),
                self.task)
        return model, result

    def run_lazy(self, batch: Batch, initial: Optional[Array] = None):
        """Like :meth:`run` but device-resident: returns only a result whose
        ``coefficients`` is an on-device array and whose history/scalars
        materialize lazily (:class:`DeferredOptimizationResult`) — no
        blocking device→host read happens here. The CD hot loop uses this
        so a fixed-effect update contributes zero syncs outside the fused
        epilogue fetch. The multi-device shard_map path keeps its eager
        result (its collectives already fence).

        MULTI-IN-FLIGHT: each call returns an independent deferred
        result owning its own device history buffers — the pipelined /
        block-parallel CD sweep keeps several unmaterialized results
        alive at once (the next update dispatches before the previous
        tracker ever forces) and forces them in any order at the
        sweep-boundary drain. Nothing here is shared across calls except
        the jit cache, and a discarded result (a rolled-back speculative
        dispatch) is simply never forced — its buffers free with the
        last reference, no cleanup hook needed."""
        from photon_ml_tpu.parallel.mesh import DATA_AXIS, get_default_mesh
        from photon_ml_tpu.utils.faults import fault_point

        mesh = get_default_mesh()
        if mesh is not None and mesh.shape.get(DATA_AXIS, 1) > 1:
            _, result = self.run(batch, initial=initial)
            return result
        dim = batch.num_features
        x0 = solver_x0(batch.acc_dtype, dim, initial)
        obj = self.objective()
        # the solve DISPATCHES here (async); the span measures host-side
        # dispatch time, the deferred result's fetch is a separate site
        with trace.span("optimizer.solve", backend="lazy",
                        optimizer=self.config.optimizer_type.name):
            x, history, progressed = self.solve(obj, batch, x0)
        x = fault_point("optimizer.gradient", arrays=x)
        cfg = self.config
        return DeferredOptimizationResult(
            x, history, progressed, cfg.max_iterations, cfg.tolerance,
            site=self.solver_site())

    def regularization_value_device(self, coef_normalized: Array):
        """lambda-weighted penalty as a device scalar (no host sync) —
        the CD fused epilogue keeps a per-coordinate cache of these and
        sums them on device. Returns the Python float ``0.0`` when the
        config has no penalty, so unregularized configs stay op-free."""
        cfg = self.config
        l1 = cfg.regularization_context.l1_weight(cfg.regularization_weight)
        l2 = cfg.regularization_context.l2_weight(cfg.regularization_weight)
        val = 0.0
        if l1 > 0:
            val = val + l1 * jnp.sum(jnp.abs(coef_normalized))
        if l2 > 0:
            val = val + 0.5 * l2 * jnp.dot(coef_normalized, coef_normalized)
        return val

    def regularization_value(self, coef_normalized: Array) -> float:
        """lambda-weighted penalty of a (normalized-space) coefficient vector,
        used by coordinate descent's global objective
        (GeneralizedLinearOptimizationProblem.getRegularizationTermValue)."""
        val = self.regularization_value_device(coef_normalized)
        # photonlint: allow-W101(this IS the host-scalar accessor: one guarded scalar sync per objective evaluation, annotated -> float)
        return val if isinstance(val, float) else float(val)
