"""Fused GLM objective kernels: value+gradient, Hessian-vector, Hessian-diag.

TPU-native re-design of the reference's aggregator trio
(reference: photon-ml/src/main/scala/com/linkedin/photon/ml/function/
ValueAndGradientAggregator.scala:34-274, HessianVectorAggregator.scala:37-163,
HessianDiagonalAggregator.scala:97). The reference accumulates per-datum
contributions in a Spark ``treeAggregate`` (seqOp ``add`` / combOp ``merge``);
here each pass is a single fused matmul + reduction over the columnar batch.
On a dense batch at a real size and width on a TPU a value+gradient evaluation
and a Hessian-vector product are each ONE Pallas kernel call that reads X once
(ops/pallas_kernels.py, one gate: ``_fused_kernels``); everywhere else, and as
the semantics the kernel is tested against, they are the two-pass XLA bodies
below. ``objective_lowerings{scope, form}`` counts which form each traced
call site took.
When the batch is sharded over a mesh data axis, XLA's GSPMD inserts the
all-reduce that replaces ``treeAggregate`` (SURVEY §3.4, §5.8); an explicit
``axis_name`` is accepted for use under ``shard_map``.

Normalization algebra (carried over verbatim from the reference, see
ops/normalization.py): margins use effective coefficients; gradients are
reconstructed from raw-feature sums via factors/shifts — the data itself is
never transformed.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.batch import Batch, DenseBatch
from photon_ml_tpu.obs.metrics import REGISTRY
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.parallel.quantized_collectives import qpsum

Array = jnp.ndarray


def fused_kernels_for(form: str, batch, axis_name: Optional[str]):
    """The one gate of both fused forms: ops/pallas_kernels (imported here
    alone, so only a dense batch ever loads Pallas) where the pass takes
    the fused ``form`` ("value_and_grad" or "hvp"), a dense batch at a real
    size and at a width the form wins at on a TPU (``pallas_supported``),
    else None. Books nothing: the solver's start reads the same rule
    (optimize/lbfgs.py:start_form)."""
    if isinstance(batch, DenseBatch) and batch.X.ndim == 2:
        from photon_ml_tpu.ops import pallas_kernels

        n, d = batch.X.shape
        # axis_name set => the caller runs us under shard_map (manual
        # partitioning, per-shard shapes): safe on any device count.
        if pallas_kernels.pallas_supported(
                form, n, d, batch.X.dtype,
                inside_shard_map=axis_name is not None):
            return pallas_kernels
    return None


def _fused_kernels(form: str, batch, axis_name: Optional[str]):
    """:func:`fused_kernels_for`, booking the form the pass is traced in on
    ``objective_lowerings{scope, form}``: trace time is when the form is
    decided, so the count can never add a device sync."""
    kernels = fused_kernels_for(form, batch, axis_name)
    REGISTRY.counter("objective_lowerings").inc(
        scope="objective." + form,
        form="two_pass" if kernels is None else "fused")
    return kernels


def _maybe_psum(x, axis_name: Optional[str], quant: str = "none"):
    # qpsum is the identity on axis_name=None and a plain lax.psum for
    # mode "none" and sub-block payloads (every scalar here); int8 mode
    # compresses only the d-vector sums, which dominate the traffic.
    return qpsum(x, axis_name, mode=quant)


def value_and_gradient(
    loss: PointwiseLoss,
    norm: NormalizationContext,
    coef: Array,
    batch: Batch,
    axis_name: Optional[str] = None,
    collective_quant: str = "none",
) -> tuple[Array, Array]:
    """Weighted loss value and gradient in normalized coefficient space.

    Mirrors ValueAndGradientAggregator.calculateValueAndGradient (:235-274):
      value        = sum_i w_i l(z_i, y_i)
      vectorSum    = sum_i w_i l'(z_i) x_i
      prefactorSum = sum_i w_i l'(z_i)
      grad_j       = factors_j (vectorSum_j - shifts_j prefactorSum)
    """
    # the scope names one pass over the rows in a device trace, whichever
    # form (fused kernel, two-pass XLA) makes it
    with jax.named_scope("objective.value_and_grad"):
        w_eff, margin_shift = norm.effective_coefficients(coef)
        kernels = _fused_kernels("value_and_grad", batch, axis_name)
        if kernels is not None:
            value, vector_sum, prefactor_sum = (
                kernels.fused_value_gradient_sums(
                    loss, False, batch.X, batch.labels, batch.offsets,
                    batch.weights, w_eff, margin_shift))
        else:
            z = batch.margins(w_eff, margin_shift)
            l, d1 = loss.loss_and_d1(z, batch.labels)
            value = jnp.sum(batch.weights * l)
            r = batch.weights * d1
            vector_sum = batch.weighted_feature_sum(r)
            prefactor_sum = jnp.sum(r)
        value = _maybe_psum(value, axis_name, collective_quant)
        vector_sum = _maybe_psum(vector_sum, axis_name, collective_quant)
        prefactor_sum = _maybe_psum(prefactor_sum, axis_name,
                                    collective_quant)
        return value, norm.reconstruct_gradient(vector_sum, prefactor_sum)


def hessian_vector(
    loss: PointwiseLoss,
    norm: NormalizationContext,
    coef: Array,
    vector: Array,
    batch: Batch,
    axis_name: Optional[str] = None,
    collective_quant: str = "none",
) -> Array:
    """Gauss-Newton Hessian-vector product H v.

    Mirrors HessianVectorAggregator (:37-163): with v_eff = v * factors and
    zv_i = x_i . v_eff - v_eff . shifts,
      (Hv)_j = factors_j (sum_i w_i l''(z_i) zv_i x_ij
                          - shifts_j sum_i w_i l''(z_i) zv_i)
    """
    with jax.named_scope("objective.hvp"):
        w_eff, margin_shift = norm.effective_coefficients(coef)
        v_eff, v_shift = norm.effective_coefficients(vector)
        kernels = _fused_kernels("hvp", batch, axis_name)
        if kernels is not None:
            vector_sum, prefactor_sum = kernels.fused_hessian_vector_sums(
                loss, False, batch.X, batch.labels, batch.offsets,
                batch.weights, w_eff, margin_shift, v_eff, v_shift)
        else:
            z = batch.margins(w_eff, margin_shift)
            # zv: margin of v without data offsets (constant in w).
            zv = batch.margins(v_eff, v_shift) - batch.offsets
            r = batch.weights * loss.d2(z, batch.labels) * zv
            vector_sum = batch.weighted_feature_sum(r)
            prefactor_sum = jnp.sum(r)
        vector_sum = _maybe_psum(vector_sum, axis_name, collective_quant)
        prefactor_sum = _maybe_psum(prefactor_sum, axis_name,
                                    collective_quant)
        return norm.reconstruct_gradient(vector_sum, prefactor_sum)


def line_loss(
    loss: PointwiseLoss,
    norm: NormalizationContext,
    coef: Array,
    direction: Array,
    batch: DenseBatch,
    axis_name: Optional[str] = None,
    collective_quant: str = "none",
):
    """The weighted loss along a line, a -> (sum_i w_i l(z_i(a)), its
    slope), from one pass over the rows. The margins are affine in the
    coefficients, z(coef + a d) = z + a zv with zv the direction's margins
    without offsets (as in :func:`hessian_vector`), so after the pass that
    forms z and zv a point of the line is elementwise work over the rows:
      value(a) = sum_i w_i l(z_i + a zv_i)
      slope(a) = sum_i w_i l'(z_i + a zv_i) zv_i  = grad(coef + a d) . d
    (the gradient's reconstruction from raw-feature sums, dotted with d, is
    exactly this sum). A dense batch only: the per-entity blocks."""
    with jax.named_scope("objective.line"):
        w_eff, margin_shift = norm.effective_coefficients(coef)
        v_eff, v_shift = norm.effective_coefficients(direction)
        z, zv = batch.margin_pair(w_eff, margin_shift, v_eff, v_shift)

    def at(a):
        l, d1 = loss.loss_and_d1(z + a * zv, batch.labels)
        value = _maybe_psum(jnp.sum(batch.weights * l), axis_name,
                            collective_quant)
        slope = _maybe_psum(jnp.sum(batch.weights * d1 * zv), axis_name,
                            collective_quant)
        return value, slope

    return at


def hessian_diagonal(
    loss: PointwiseLoss,
    norm: NormalizationContext,
    coef: Array,
    batch: Batch,
    axis_name: Optional[str] = None,
    collective_quant: str = "none",
) -> Array:
    """Diagonal of the Gauss-Newton Hessian (for variance approximation).

    Mirrors HessianDiagonalAggregator.scala:97. In normalized space
      H_jj = factors_j^2 sum_i w_i l''(z_i) (x_ij - shifts_j)^2
    expanded into three raw-feature sums so data stays untouched.
    """
    with jax.named_scope("objective.hessian_diag"):
        w_eff, margin_shift = norm.effective_coefficients(coef)
        z = batch.margins(w_eff, margin_shift)
        r = batch.weights * loss.d2(z, batch.labels)
        sq_sum = _maybe_psum(batch.hadamard_square_sum(r), axis_name,
                             collective_quant)
        if norm.shifts is None:
            diag = sq_sum
        else:
            lin_sum = _maybe_psum(batch.weighted_feature_sum(r), axis_name,
                                  collective_quant)
            scalar_sum = _maybe_psum(jnp.sum(r), axis_name, collective_quant)
            diag = (sq_sum - 2.0 * norm.shifts * lin_sum
                    + norm.shifts**2 * scalar_sum)
        if norm.factors is not None:
            diag = diag * norm.factors**2
        return diag


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GLMObjective:
    """Twice-differentiable GLM objective over a device batch.

    Plays the role of DistributedGLMLossFunction / SingleNodeGLMLossFunction
    (reference function/glm/DistributedGLMLossFunction.scala:48-167,
    SingleNodeGLMLossFunction.scala): the distributed/local split disappears
    on TPU — the same jitted kernel runs on one core or a sharded mesh.

    ``l2_lambda`` folds in the L2Regularization mixin
    (function/L2Regularization.scala:25-180): + lambda/2 ||w||^2 on the value,
    + lambda w on the gradient, + lambda v on Hv, + lambda on the diagonal.
    L1 is NOT part of the smooth objective — it lives in OWL-QN's pseudo-
    gradient, as in the reference (RegularizationContext splits elastic net
    into lambda1 for OWLQN and lambda2 for the L2 mixin).
    """

    # Pytree layout: ``norm`` and ``l2_lambda`` are traced leaves (the lambda
    # grid reuses one compiled solver kernel across lambda values — the
    # reference builds a new objective per lambda the same way,
    # GLMOptimizationConfiguration + warm starts, ModelTraining.scala:182-208);
    # ``loss``/``axis_name``/``has_hessian`` are static metadata.
    loss: PointwiseLoss = dataclasses.field(metadata=dict(static=True))
    norm: NormalizationContext = NormalizationContext()
    l2_lambda: float = 0.0
    axis_name: Optional[str] = dataclasses.field(default=None,
                                                 metadata=dict(static=True))
    has_hessian: bool = dataclasses.field(default=True,
                                          metadata=dict(static=True))
    # Wire format of the axis_name collectives ("none" | "int8",
    # parallel/quantized_collectives.py). Static: it selects which
    # collective ops get traced, exactly like axis_name itself.
    collective_quant: str = dataclasses.field(default="none",
                                              metadata=dict(static=True))

    def value(self, coef: Array, batch: Batch) -> Array:
        return self.calculate(coef, batch)[0]

    def gradient(self, coef: Array, batch: Batch) -> Array:
        return self.calculate(coef, batch)[1]

    def calculate(self, coef: Array, batch: Batch) -> tuple[Array, Array]:
        value, grad = value_and_gradient(
            self.loss, self.norm, coef, batch, self.axis_name,
            self.collective_quant,
        )
        # Unconditional arithmetic: l2_lambda may be a tracer inside jit.
        value = value + 0.5 * self.l2_lambda * jnp.dot(coef, coef)
        grad = grad + self.l2_lambda * coef
        return value, grad

    def line(self, coef: Array, direction: Array, batch: DenseBatch):
        """The objective along ``coef + a * direction``: makes one pass over
        the rows (:func:`line_loss`) and returns ``phi(a) -> (f(coef + a
        direction), grad(coef + a direction) . direction)``, which makes
        none."""
        loss_at = line_loss(self.loss, self.norm, coef, direction, batch,
                            self.axis_name, self.collective_quant)

        def phi(a):
            value, slope = loss_at(a)
            x_a = coef + a * direction
            return (value + 0.5 * self.l2_lambda * jnp.dot(x_a, x_a),
                    slope + self.l2_lambda * jnp.dot(x_a, direction))

        return phi

    def hessian_vector(self, coef: Array, vector: Array, batch: Batch) -> Array:
        hv = hessian_vector(self.loss, self.norm, coef, vector, batch,
                            self.axis_name, self.collective_quant)
        return hv + self.l2_lambda * vector

    def hessian_diagonal(self, coef: Array, batch: Batch) -> Array:
        d = hessian_diagonal(self.loss, self.norm, coef, batch,
                             self.axis_name, self.collective_quant)
        return d + self.l2_lambda

    def with_l2(self, l2_lambda: float) -> "GLMObjective":
        return dataclasses.replace(self, l2_lambda=l2_lambda)
