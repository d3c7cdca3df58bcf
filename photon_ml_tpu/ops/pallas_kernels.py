"""Pallas TPU kernels: single-pass fused GLM value+gradient and
Hessian-vector product.

The hot op of every solver is a pass over the rows (reference:
photon-ml/src/main/scala/com/linkedin/photon/ml/function/
ValueAndGradientAggregator.scala:235-274 and
HessianVectorAggregator.scala:37-163 — the treeAggregate over per-datum
``add``). The XLA formulation reads the design matrix twice per pass: once
for the margin matmul ``z = X @ w`` and once for the feature-sum matmul
``X^T r``. At GLM scale a pass is HBM-bandwidth-bound, so the X re-read is
the dominant cost.

The kernel streams each row tile of X through VMEM ONCE, computing margins,
the pointwise loss derivatives, and the running (X^T r, sum r) accumulators
in the same pass — the Pallas analog of the reference's fused per-datum
``add`` loop. It has two forms over one plumbing (``_row_tile_sums``):
``fused_value_gradient_sums`` (r = w l'(z), plus the value) for an objective
evaluation and ``fused_hessian_vector_sums`` (r = w l''(z) zv, both margins
made in the tile pass) for TRON's conjugate-gradient steps.

Grid iterates row tiles sequentially (TPU grid order), accumulating into
shared output blocks — the standard Pallas accumulation pattern. The last
tile's out-of-range rows are masked (rows and weights zeroed), keeping N
free of padding requirements.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.ops.losses import PointwiseLoss

Array = jnp.ndarray

# VMEM budget: a [tile_rows, D] f32 tile must fit comfortably with double
# buffering — target 4 MB per buffer (at D=2048 on a v5e, tile 512: a
# value+gradient pass reads X once at 716 GB/s and a Hessian-vector pass at
# 755 GB/s, where the two-pass XLA form manages 377 GB/s of one read;
# ledger, PR 33, and PERF.md, PR 34).
_TILE_BYTES = 4 * 1024 * 1024
MAX_PALLAS_DIM = 4096


# The fewest columns each fused form engages at. Under them the form loses
# to the two-pass XLA body it replaces: a narrow tile's [T, 1] products at
# "highest", its row vectors' relayout and the loss's transcendentals on
# them cost more than the second read of X they save. Read on a v5e inside
# the solvers' loops, 2.6 GB of f32 X, ms a pass fused / two-pass, squared |
# logistic loss (PERF.md, PR 34; at the sweep cells' 65 columns the
# value+gradient form reads 43.2 / 13.7 | 56.8 / 13.7). A bf16 X has no
# reading of its own and takes the same rule:
MIN_PALLAS_DIM = {
    # 512 columns 6.18 / 6.88 | 8.33 / 6.88; 1,024: 4.33 / 7.15 | 5.24 / 7.16
    "value_and_grad": 1024,
    # 256 columns 6.05 / 6.88 | 10.63 / 10.34; 512: 3.92 / 6.87 | 6.88 / 6.88
    "hvp": 512,
}


# Below this many elements the two-pass XLA form is already cache-resident;
# the kernel's win is HBM traffic, so only engage at real sizes.
MIN_PALLAS_ELEMENTS = 1 << 21


# f32 operands multiply at full precision. Mosaic's default contracts them
# in reduced-precision MXU passes: on a v5e at 262144x2048 that left the
# gradient sum 4e-4 (relative) off the float64 sums where this setting and
# the two-pass XLA form are within 1e-6, and L-BFGS stalled at a 4x larger
# gradient norm; full precision costs ~5% of the kernel's time there
# (PERF.md, PR 22). A bf16 X is already exact in one pass.
_F32_DOT_PRECISION = jax.lax.Precision.HIGHEST


def _tile_rows(d: int, itemsize: int = 4) -> int:
    rows = _TILE_BYTES // (d * itemsize)
    return int(max(256, min(1024, (rows // 8) * 8)))


def pallas_supported(form: str, n: int, d: int, dtype,
                     inside_shard_map: bool = False) -> bool:
    """Gate for the fused kernel's ``form`` (a key of ``MIN_PALLAS_DIM``) on
    an [n, d] block. ``inside_shard_map``: under an explicit
    shard_map the computation is manually partitioned and per-shard shapes
    are local, so the kernel is safe on any device count; OUTSIDE one, a
    pallas_call is opaque to GSPMD (no partitioning rule) and would force a
    full replication of X onto every device — only allow it single-device.

    X may be f32 or bf16: a bf16 design matrix halves the HBM stream (the
    kernel's whole cost) while the MXU multiplies bf16 natively and every
    accumulator stays f32. Storing X in bf16 is the caller's opt-in
    precision choice (build the batch with dtype=bfloat16)."""
    if os.environ.get("PHOTON_DISABLE_PALLAS"):
        return False
    if jax.default_backend() != "tpu":
        return False
    if not inside_shard_map and jax.device_count() > 1:
        return False
    if jnp.dtype(dtype) not in (jnp.dtype("float32"),
                                jnp.dtype("bfloat16")):
        return False
    return (MIN_PALLAS_DIM[form] <= d <= MAX_PALLAS_DIM
            and n * d >= MIN_PALLAS_ELEMENTS)


def _zero_at_first_tile(i, vec_ref, *sum_refs):
    """Zero the accumulators at row tile 0 (the grid walks the tiles in
    order and every tile adds into the same output blocks)."""
    @pl.when(i == 0)
    def _init():
        for ref in sum_refs:
            ref[0, 0] = jnp.float32(0.0)
        vec_ref[...] = jnp.zeros_like(vec_ref)


def _masked_tile(i, n_rows: int, x_ref):
    """Row tile i with the last tile's out-of-range rows zeroed, the {0,1}
    row mask [T] for its weights, and the precision X's dtype multiplies
    at."""
    tile = x_ref.shape[0]
    # Edge-tile masking with f32 multiplies (bool minor-dim broadcasts are
    # unsupported by Mosaic): separate 2D and 1D iotas, mask → {0,1} floats.
    rows_2d = i * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    mask_col = (rows_2d < n_rows).astype(jnp.float32)  # [T, 1]
    rows_1d = i * tile + jax.lax.broadcasted_iota(jnp.int32, (tile,), 0)
    mask_row = (rows_1d < n_rows).astype(jnp.float32)  # [T]

    # Zero padded edge rows by SELECTION, not multiplication — out-of-bounds
    # block rows may be NaN (interpret mode pads with NaN) and 0*NaN = NaN.
    x_dtype = x_ref.dtype
    precision = _F32_DOT_PRECISION if x_dtype == jnp.float32 else None
    X = jnp.where(mask_col > 0.0, x_ref[...], jnp.zeros((), x_dtype))
    return X, mask_row, precision


def _tile_margins(X, coef_ref, k: int, precision):
    """``X @ coef[k]`` -> [T] for row k of the [K, D] f32 coefficient block.
    Mosaic wants 2D operands on both matmuls: [T,D]@[D,1] and [1,T]@[T,D].
    The row is cast to X's dtype so a bf16 X rides the MXU's native bf16
    path. Accumulation is f32 either way."""
    col = jnp.transpose(coef_ref[k:k + 1, :], (1, 0)).astype(X.dtype)
    return jax.lax.dot_general(
        X, col, (((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32).reshape(-1)


def _accumulate(r, X, precision, vec_ref, pre_ref):
    """``prefactor_sum += sum r`` and ``vector_sum += r @ X`` for the
    tile's per-row factors r [T]."""
    pre_ref[0, 0] += jnp.sum(r)
    vec_ref[...] += jax.lax.dot_general(
        r.reshape(1, -1).astype(X.dtype), X, (((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)


def _value_gradient_kernel(loss: PointwiseLoss, n_rows: int,
                           x_ref, y_ref, off_ref, wt_ref, w_ref, shift_ref,
                           val_ref, vec_ref, pre_ref):
    i = pl.program_id(0)
    _zero_at_first_tile(i, vec_ref, val_ref, pre_ref)
    X, mask_row, precision = _masked_tile(i, n_rows, x_ref)
    z = (_tile_margins(X, w_ref, 0, precision)
         + off_ref[...].reshape(-1) + shift_ref[0, 0])
    y = y_ref[...].reshape(-1)
    # masked rows have wt == 0 and finite z (= offset + shift), so their
    # loss terms vanish in the products below.
    wt = wt_ref[...].reshape(-1) * mask_row
    wl = wt * loss.loss(z, y)
    wd = wt * loss.d1(z, y)
    val_ref[0, 0] += jnp.sum(wl)
    _accumulate(wd, X, precision, vec_ref, pre_ref)


def _hvp_kernel(loss: PointwiseLoss, n_rows: int,
                x_ref, y_ref, off_ref, wt_ref, wv_ref, shift_ref,
                vec_ref, pre_ref):
    i = pl.program_id(0)
    _zero_at_first_tile(i, vec_ref, pre_ref)
    X, mask_row, precision = _masked_tile(i, n_rows, x_ref)
    # wv_ref is [w_eff; v_eff]. Two one-column products of the tile held in
    # VMEM, not one [T, D] @ [D, 2]: at 786432x2048 f32 on a v5e the
    # two-column product at "highest" makes the pass 17.2 ms, as long as the
    # two-pass XLA form (17.1), where this reads 8.58, the value+gradient
    # kernel's own time (PERF.md, PR 32).
    z = (_tile_margins(X, wv_ref, 0, precision)
         + off_ref[...].reshape(-1) + shift_ref[0, 0])
    # the margin of v carries no data offsets: they are constant in w
    zv = _tile_margins(X, wv_ref, 1, precision) + shift_ref[0, 1]
    # masked rows: wt == 0 and z, zv finite, as above
    wt = wt_ref[...].reshape(-1) * mask_row
    r = wt * loss.d2(z, y_ref[...].reshape(-1)) * zv
    _accumulate(r, X, precision, vec_ref, pre_ref)


def _xla_sums(loss: PointwiseLoss, X, labels, offsets, weights, w_eff,
              margin_shift):
    """Two-pass XLA formulation of the same three sums — the reference
    semantics the kernel must match, and the differentiable fallback the
    custom VJP linearizes through."""
    z = X @ w_eff + offsets + margin_shift
    l, d1 = loss.loss_and_d1(z, labels)
    r = weights * d1
    return (jnp.sum(weights * l), r @ X, jnp.sum(r))


def _row_tile_sums(kernel, interpret: bool, with_value: bool, X: Array,
                   labels: Array, offsets: Array, weights: Array,
                   coefs: Array, shifts: Array):
    """Run ``kernel`` over the row tiles of X: each [tile_rows, D] tile
    passes through VMEM once beside its labels, offsets and weights, the
    [K, D] coefficient block and the [1, K] shifts (SMEM) stay resident.
    Returns ([value,] vector_sum [D], prefactor_sum)."""
    if jnp.dtype(X.dtype) not in (jnp.dtype("float32"),
                                  jnp.dtype("bfloat16")):
        X = X.astype(jnp.float32)  # f64 callers (x64 tests) compute in f32
    n, d = X.shape
    k = coefs.shape[0]
    tile_rows = _tile_rows(d, jnp.dtype(X.dtype).itemsize)
    num_tiles = pl.cdiv(n, tile_rows)
    n_pad = num_tiles * tile_rows

    def _rows_2d(v: Array) -> Array:
        """Per-row vector → [1, N_pad] (rank-1 operands hit XLA/Mosaic
        layout mismatches; padding N floats is noise next to X)."""
        v = v.astype(jnp.float32)
        if n_pad != n:
            v = jnp.pad(v, (0, n_pad - n))
        return v.reshape(1, n_pad)

    row_spec = pl.BlockSpec((1, tile_rows), lambda i: (0, i))
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)
    scalar_shape = jax.ShapeDtypeStruct((1, 1), jnp.float32)
    values = [scalar_spec] if with_value else []
    *value, vec, pre = pl.pallas_call(
        kernel,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((tile_rows, d), lambda i: (i, 0)),
            row_spec,  # labels
            row_spec,  # offsets
            row_spec,  # weights
            pl.BlockSpec((k, d), lambda i: (0, 0)),  # coefs
            pl.BlockSpec((1, k), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=values + [
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            scalar_spec,
        ],
        out_shape=[scalar_shape] * len(values) + [
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            scalar_shape,
        ],
        interpret=interpret,
    )(
        X,
        _rows_2d(labels),
        _rows_2d(offsets),
        _rows_2d(weights),
        coefs.astype(jnp.float32),
        jnp.asarray(shifts, jnp.float32).reshape(1, k),
    )
    return (*(v[0, 0] for v in value), vec.reshape(d), pre[0, 0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def fused_value_gradient_sums(
        loss: PointwiseLoss,
        interpret: bool,
        X: Array,
        labels: Array,
        offsets: Array,
        weights: Array,
        w_eff: Array,
        margin_shift: Array) -> tuple[Array, Array, Array]:
    """One-pass (value, vector_sum, prefactor_sum) over a dense batch.

    Returns the same three sums the XLA path computes:
      value        = Σ w_i l(z_i, y_i)
      vector_sum   = Σ w_i l'(z_i) x_i
      prefactor    = Σ w_i l'(z_i)

    Differentiable: pallas_call has no autodiff rule, so the custom VJP
    recomputes the backward pass through the XLA formulation (used by
    second-order callers like jax.hessian over the objective value).
    """
    return _row_tile_sums(
        functools.partial(_value_gradient_kernel, loss, X.shape[0]),
        interpret, True, X, labels, offsets, weights,
        w_eff.reshape(1, -1), margin_shift)


def fused_hessian_vector_sums(
        loss: PointwiseLoss,
        interpret: bool,
        X: Array,
        labels: Array,
        offsets: Array,
        weights: Array,
        w_eff: Array,
        margin_shift: Array,
        v_eff: Array,
        v_shift: Array) -> tuple[Array, Array]:
    """One-pass (vector_sum, prefactor_sum) of a Hessian-vector product
    over a dense batch, the margins computed inside the tile pass:

      z_i, zv_i    = x_i . w_eff + o_i + margin_shift,  x_i . v_eff + v_shift
      vector_sum   = Σ w_i l''(z_i) zv_i x_i
      prefactor    = Σ w_i l''(z_i) zv_i

    Not differentiable (pallas_call has no autodiff rule and no caller
    differentiates a product); the semantics are the two-pass body of
    ops/aggregators.hessian_vector.
    """
    return _row_tile_sums(
        functools.partial(_hvp_kernel, loss, X.shape[0]),
        interpret, False, X, labels, offsets, weights,
        jnp.stack([w_eff, v_eff]), jnp.stack([margin_shift, v_shift]))


def _fused_fwd(loss, interpret, X, labels, offsets, weights, w_eff,
               margin_shift):
    out = fused_value_gradient_sums(
        loss, interpret, X, labels, offsets, weights, w_eff, margin_shift)
    return out, (X, labels, offsets, weights, w_eff, margin_shift)


def _fused_bwd(loss, interpret, residuals, cotangents):
    _, vjp = jax.vjp(functools.partial(_xla_sums, loss), *residuals)
    return vjp(cotangents)


fused_value_gradient_sums.defvjp(_fused_fwd, _fused_bwd)
