"""Explicit-collectives distributed GLM fit: shard_map + psum over the mesh.

The default distributed path lets GSPMD auto-partition the jitted solver
over a row-sharded batch (parallel/mesh.py; SURVEY §5.8). This module is the
*manual* backend — the moral equivalent of the reference's treeAggregate
call sites made explicit (reference: photon-ml/src/main/scala/com/linkedin/
photon/ml/function/ValueAndGradientAggregator.scala:243,
HessianVectorAggregator.scala:146):

- every device runs the SAME L-BFGS/OWL-QN/TRON loop on its row shard;
- each objective evaluation ends in ``lax.psum`` over the ``data`` axis, so
  every device sees the same collective result and the replicated
  coefficient iterates stay bit-identical ACROSS DEVICES (the invariant
  that replaces the reference's coefficient Broadcast);
- per-shard shapes are local, which lets the fused Pallas kernel engage on
  each shard (ops/pallas_kernels.py's shard_map gate).

Use this path when GSPMD's choices need overriding (e.g. to force the
single-pass kernel, or to compose with other manual collectives).

Parity with the local path: psum sums per-shard partials, which reassociates
the floating-point reduction relative to ``GLMOptimizationProblem.run`` on
the full batch. In float64 both paths converge to the same optimum to
machine epsilon; in float32, when the convergence tolerance sits below the
f32 noise floor (~1e-7 relative), the two trajectories stall at points that
differ at the noise-floor scale (~1e-4 coefficient max-abs observed). That
is inherent to distributed summation — the reference's treeAggregate has the
same property vs a sequential fold — and is pinned by
tests/test_mesh_routing.py's paired f64/f32 parity tests.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from photon_ml_tpu.data.batch import (
    Batch,
    deal_rows,
    pad_batch,
    row_partition_specs,
    rows_in_layout_order,
)
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.optimize.common import OptimizationResult, solver_x0
from photon_ml_tpu.optimize.problem import GLMOptimizationProblem
from photon_ml_tpu.parallel.mesh import DATA_AXIS, pad_rows_to_multiple
from photon_ml_tpu.parallel.quantized_collectives import (
    qall_gather,
    record_collective_bytes,
)

Array = jnp.ndarray


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off: every
    caller's outputs are psum-identical across devices, which the checker
    cannot prove through a solver ``while_loop``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def run_glm_shard_map(
        problem: GLMOptimizationProblem,
        batch: Batch,
        mesh,
        initial: Optional[Array] = None,
) -> tuple[GeneralizedLinearModel, OptimizationResult]:
    """Fit ``problem`` on ``batch`` with rows explicitly sharded over the
    mesh ``data`` axis. Works for both batch layouts (DenseBatch, EllBatch:
    ``data/batch.row_partition_specs`` names each leaf's row axis). Rows
    not divisible by the data-axis size are padded with zero-weight rows
    here, and an ELL layout of several blocks of slots is dealt into one
    run of rows a shard (``data/batch.deal_rows``), its row vectors handed
    over in the layout's order before the split: a shard cannot look up a
    global permutation.

    With ``problem.shard_weight_update`` set, the optimizer state and the
    coefficient update are additionally sharded over the SAME data axis
    (arXiv 2004.13336): each replica all-gathers the iterate for the
    objective evaluation, keeps only its gradient/coefficient shard, and
    the converged shard is all-gathered once at the end — instead of
    every replica running the full-dimension two-loop/CG redundantly.
    """
    n_shards = mesh.shape[DATA_AXIS]
    rows = batch.labels.shape[0]
    padded = pad_rows_to_multiple(rows, n_shards)
    if padded != rows:
        batch = pad_batch(batch, padded)

    dim = batch.num_features
    x0 = solver_x0(batch.acc_dtype, dim, initial)
    shards = deal_rows(rows_in_layout_order(batch), n_shards)
    fit, shard_update = sharded_fit(problem, shards, mesh, x0.dtype)
    x, history, progressed = jax.jit(fit)(shards, x0)

    # Host-side collective-traffic ledger (collectives run inside the
    # jitted loop where counting is impossible): one d-vector gradient
    # psum per iteration on every backend, plus the sharded update's
    # per-evaluation iterate all-gather of one shard. Line-search extra
    # evaluations are invisible here — a documented lower bound, applied
    # identically for both wire modes so the ratio is exact.
    iters = int(history.num_iterations)
    itemsize = jnp.dtype(batch.acc_dtype).itemsize
    record_collective_bytes("fe.grad_psum", problem.collective_quant,
                            dim, itemsize=itemsize, rounds=iters)
    if shard_update:
        d_pad = pad_rows_to_multiple(dim, n_shards)
        record_collective_bytes("fe.iterate_gather",
                                problem.collective_quant,
                                d_pad // n_shards, itemsize=itemsize,
                                rounds=iters)

    # Variances/publication run on the full (GSPMD-sharded) batch.
    return problem.publish(x, history, progressed, problem.objective(),
                           batch)


def sharded_fit(problem: GLMOptimizationProblem, batch: Batch, mesh,
                x0_dtype):
    """The shard_map-wrapped per-device solve ``fit(batch, x0) -> (x,
    history, progressed)`` for ``batch``'s layout, and whether the sharded
    weight update engaged. Only the batch's structure and width are read,
    so its leaves may be arrays or ``jax.ShapeDtypeStruct``s (ahead-of-time
    compiles for a described topology, tests/test_tpu_compile.py). Rows
    must already divide the mesh data axis, an ELL layout of several
    blocks be dealt over it and in its own row order."""
    n_shards = mesh.shape[DATA_AXIS]
    dim = batch.num_features
    # psum-ing objective: every reduction crosses the data axis.
    obj = dataclasses.replace(problem.objective(), axis_name=DATA_AXIS)
    row_specs = row_partition_specs(batch, DATA_AXIS)

    shard_update = problem.shard_weight_update
    if shard_update and (problem.box is not None or problem.track_iterates):
        logging.getLogger(__name__).warning(
            "shard_weight_update is incompatible with box constraints / "
            "track_iterates; falling back to the replicated update")
        shard_update = False

    if shard_update:
        local_fit = _sharded_update_local_fit(problem, obj, dim, n_shards,
                                              x0_dtype)
    else:
        def local_fit(shard, x0_rep):
            x, history, progressed = problem.solve(obj, shard, x0_rep)
            return x, history, progressed

    # grads are psum-identical on every device, but the replication checker
    # can't prove it through the while_loop — checking is disabled.
    fit = _shard_map(
        local_fit, mesh,
        in_specs=(row_specs, P()),
        out_specs=(P(), P(), P()),
    )
    return fit, shard_update


def _sharded_update_local_fit(problem: GLMOptimizationProblem, obj,
                              dim: int, n_shards: int, dtype):
    """Build the per-replica body of a weight-update-sharded GLM fit.

    The coefficient vector is zero-padded to a multiple of ``n_shards``
    and split evenly; padded coordinates provably stay 0 (their gradient
    is identically 0, and OWL-QN's pseudo-gradient at x=0, g=0, l1>=0 is
    0), so padding never perturbs the solve. The solver itself runs with
    ``update_axis_name`` set, psum-ing every d-vector reduction, which
    makes the sharded recursion exactly the full-dimension one up to
    reduction order.
    """
    d_pad = pad_rows_to_multiple(dim, n_shards)
    shard_d = d_pad // n_shards
    quant = problem.collective_quant

    def gather_full(x_shard):
        # the per-evaluation iterate/gradient gather — the compressible
        # wire traffic of the sharded update (every replica dequantizes
        # the same bytes, so iterates stay replica-identical)
        return qall_gather(x_shard, DATA_AXIS, mode=quant)[:dim]

    def slice_own(full_vec):
        start = lax.axis_index(DATA_AXIS) * shard_d
        return lax.dynamic_slice(jnp.pad(full_vec, (0, d_pad - dim)),
                                 (start,), (shard_d,))

    def vg(x_shard, payload):
        obj_p, data = payload
        f, g = obj_p.calculate(gather_full(x_shard), data)
        return f, slice_own(g)

    def hvp(x_shard, v_shard, payload):
        obj_p, data = payload
        hv = obj_p.hessian_vector(gather_full(x_shard),
                                  gather_full(v_shard), data)
        return slice_own(hv)

    full_mask = (jnp.asarray(problem.l1_mask).astype(dtype)
                 if problem.l1_mask is not None else None)

    def local_fit(shard, x0_rep):
        l1_mask = slice_own(full_mask) if full_mask is not None else None
        x_shard, history, progressed = problem.solve(
            obj, shard, slice_own(x0_rep),
            update_axis_name=DATA_AXIS, vg_fn=vg, hvp_fn=hvp,
            l1_mask=l1_mask)
        # the paper's step: all-gather the updated shard once per solve,
        # not per iteration — the full vector only rematerializes here.
        return gather_full(x_shard), history, progressed

    return local_fit
