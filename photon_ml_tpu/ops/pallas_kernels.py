"""Pallas TPU kernels: single-pass fused GLM value+gradient.

The hot op of every solver is the objective evaluation (reference:
photon-ml/src/main/scala/com/linkedin/photon/ml/function/
ValueAndGradientAggregator.scala:235-274 — the treeAggregate over per-datum
``add``). The XLA formulation reads the design matrix twice per evaluation:
once for the margin matmul ``z = X @ w`` and once for the gradient matmul
``X^T r``. At GLM scale the evaluation is HBM-bandwidth-bound, so the X
re-read is the dominant cost.

This kernel streams each row tile of X through VMEM ONCE, computing margin,
pointwise loss/derivative, and the running (value, X^T r, sum r)
accumulators in the same pass — the Pallas analog of the reference's fused
per-datum ``add`` loop, with the MXU doing both matmuls per tile.

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
# buffering — target 4 MB per buffer (measured best at D=2048 on v5-class
# HBM: tile 512 → ~394 GB/s single-pass vs ~270 GB/s for the 2-pass XLA
# form).
_TILE_BYTES = 4 * 1024 * 1024
MAX_PALLAS_DIM = 4096


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


def pallas_supported(n: int, d: int, dtype,
                     inside_shard_map: bool = False) -> bool:
    """Gate for the fused kernel. ``inside_shard_map``: under an explicit
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
    return d <= MAX_PALLAS_DIM and n * d >= MIN_PALLAS_ELEMENTS


def _kernel(loss: PointwiseLoss, n_rows: int,
            x_ref, y_ref, off_ref, wt_ref, w_ref, shift_ref,
            val_ref, vec_ref, pre_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        val_ref[0, 0] = jnp.float32(0.0)
        pre_ref[0, 0] = jnp.float32(0.0)
        vec_ref[...] = jnp.zeros_like(vec_ref)

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
    # Mosaic wants 2D operands on both matmuls: [T,D]@[D,1] and [1,T]@[T,D].
    # w arrives as a [1, D] f32 block; cast to X's dtype so a bf16 X rides
    # the MXU's native bf16 path. Accumulation is f32 either way.
    w_col = jnp.transpose(w_ref[...], (1, 0)).astype(x_dtype)  # [D, 1]
    z = (jax.lax.dot_general(
        X, w_col, (((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32).reshape(-1)
        + off_ref[...].reshape(-1) + shift_ref[0, 0])
    y = y_ref[...].reshape(-1)
    wt = wt_ref[...].reshape(-1) * mask_row
    # masked rows have wt == 0 and finite z (= offset + shift), so their
    # loss terms vanish in the products below.
    wl = wt * loss.loss(z, y)
    wd = wt * loss.d1(z, y)

    val_ref[0, 0] += jnp.sum(wl)
    pre_ref[0, 0] += jnp.sum(wd)
    vec_ref[...] += jax.lax.dot_general(
        wd.reshape(1, -1).astype(x_dtype), X, (((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)


def _xla_sums(loss: PointwiseLoss, X, labels, offsets, weights, w_eff,
              margin_shift):
    """Two-pass XLA formulation of the same three sums — the reference
    semantics the kernel must match, and the differentiable fallback the
    custom VJP linearizes through."""
    z = X @ w_eff + offsets + margin_shift
    l, d1 = loss.loss_and_d1(z, labels)
    r = weights * d1
    return (jnp.sum(weights * l), r @ X, jnp.sum(r))


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
    if jnp.dtype(X.dtype) not in (jnp.dtype("float32"),
                                  jnp.dtype("bfloat16")):
        X = X.astype(jnp.float32)  # f64 callers (x64 tests) compute in f32
    n, d = X.shape
    tile_rows = _tile_rows(d, jnp.dtype(X.dtype).itemsize)
    num_tiles = pl.cdiv(n, tile_rows)
    grid = (num_tiles,)
    n_pad = num_tiles * tile_rows

    def _rows_2d(v: Array) -> Array:
        """Per-row vector → [1, N_pad] (rank-1 operands hit XLA/Mosaic
        layout mismatches; padding N floats is noise next to X)."""
        v = v.astype(jnp.float32)
        if n_pad != n:
            v = jnp.pad(v, (0, n_pad - n))
        return v.reshape(1, n_pad)

    row_spec = pl.BlockSpec((1, tile_rows), lambda i: (0, i))
    kernel = functools.partial(_kernel, loss, n)
    value, vec, pre = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_rows, d), lambda i: (i, 0)),
            row_spec,  # labels
            row_spec,  # offsets
            row_spec,  # weights
            pl.BlockSpec((1, d), lambda i: (0, 0)),  # w_eff
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(
        X,
        _rows_2d(labels),
        _rows_2d(offsets),
        _rows_2d(weights),
        w_eff.astype(jnp.float32).reshape(1, d),
        jnp.asarray(margin_shift, jnp.float32).reshape(1, 1),
    )
    return value[0, 0], vec.reshape(d), pre[0, 0]


def _fused_fwd(loss, interpret, X, labels, offsets, weights, w_eff,
               margin_shift):
    out = fused_value_gradient_sums(
        loss, interpret, X, labels, offsets, weights, w_eff, margin_shift)
    return out, (X, labels, offsets, weights, w_eff, margin_shift)


def _fused_bwd(loss, interpret, residuals, cotangents):
    _, vjp = jax.vjp(functools.partial(_xla_sums, loss), *residuals)
    return vjp(cotangents)


fused_value_gradient_sums.defvjp(_fused_fwd, _fused_bwd)
