"""Ahead-of-time compiles for a DESCRIBED TPU v5e:2x2 (no chip attached).

The chip's compiler is installed with jaxlib/libtpu and compiles for a
topology that is described, not attached — so what Mosaic or XLA:TPU
would refuse on the chip (a misaligned kernel slice, too much VMEM, an
unusable donation, a kernel that cannot live inside ``shard_map``) is
refused here, at real widths, at no chip time. These are the programs
``chip_smoke.py`` runs: the fused value+gradient kernel at the smoke's
shapes, an L-BFGS solve with the kernel inside its ``while_loop``, the
buffer-donating per-entity fits at a GLMix bucket shape, and the sharded
fixed-effect / per-entity steps on a 2x2 mesh of the described devices.

A compile that passes is not a chip run: nothing executes here, so this
file says nothing about results or times.

This is the ONLY file that describes the chip, and it does so inside a
module-scoped, non-autouse fixture: one process at a time may load the
TPU's library, every xdist worker imports every test file, and only the
worker that is handed this file may reach that call. Code that asks
``jax.default_backend()`` would take its CPU branch here; the tests steer
it (monkeypatch), the program has no option for it.

When this file may skip: where ``libtpu`` cannot be imported (no TPU
compiler is installed). Every other failure to describe the topology is
an error, not a skip. The reading behind that (PR 31; the 12 cases ran on
all three): on the CPU-only machine and on the chip's machine with the
chip idle the description succeeds at once; while a ``benchmark/run.py``
cell holds the chip it fails with ``ABORTED: The TPU is already in use by
process with pid N. Not attempting to load libtpu.so in this process``
(libtpu's lock file), and the same call in the same process succeeds with
``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` set, the cell beside it unharmed. So
the fixture tries plainly, then once more with that variable set for the
one call (a description opens no chip: the lock guards nothing here), and
fails if that fails too. Until PR 31 any exception was a skip, and 12
tests could leave the count in silence.
"""

from __future__ import annotations

import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from photon_ml_tpu.data.batch import DenseBatch
from photon_ml_tpu.ops.aggregators import GLMObjective
from photon_ml_tpu.ops.losses import get_loss
from photon_ml_tpu.ops.pallas_kernels import (
    MAX_PALLAS_DIM,
    MIN_PALLAS_DIM,
    fused_hessian_vector_sums,
    fused_value_gradient_sums,
)
from photon_ml_tpu.optimize.config import (
    GLMOptimizationConfiguration,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
    TaskType,
)
from photon_ml_tpu.optimize.problem import GLMOptimizationProblem
from photon_ml_tpu.parallel.mesh import DATA_AXIS, ENTITY_AXIS, make_mesh

# chip_smoke.py's shapes (kept in step by tests/test_chip_smoke.py)
GLM_SHAPE = (262144, 2048)
GLMIX_ROWS, GLMIX_FIXED_DIM = 1_000_209, 65  # 64 global features + intercept
GLMIX_BUCKET = (675, 128, 128)  # largest of the four (E, N, D) buckets
MESH_ROWS = 262144

MOSAIC_CALL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # The one skip: no TPU compiler is installed. Every other failure to
    # describe the topology is an error (module docstring).
    if (importlib.util.find_spec("libtpu") is None
            and not os.environ.get("TPU_LIBRARY_PATH")):
        pytest.skip("libtpu cannot be imported: no TPU compiler here")
    from jax.experimental import topologies

    def describe():
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")

    try:
        return describe()
    except Exception as first:
        # Another process holds libtpu's lock (a benchmark cell on the
        # chip, a sibling test process). Describing a topology opens no
        # chip, so the lock guards nothing this file does: load beside
        # the holder, for this one call.
        with pytest.MonkeyPatch.context() as env:
            env.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
            try:
                return describe()
            except Exception as second:
                pytest.fail(
                    f"libtpu is installed but no v5e:2x2 topology can be "
                    f"described: {first!r}; beside the lock's holder: "
                    f"{second!r}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return make_mesh(num_data=2, num_entity=2, devices=list(topo.devices))


@pytest.fixture(autouse=True)
def _as_the_program_runs():
    """x64 off, as every entry point runs (tests/conftest.py turns it on
    for finite-difference checks; under it a kernel's index maps come out
    i64, which Mosaic refuses). And no persistent cache: a
    described-topology executable would be written to it but cannot be
    read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was = jax.config.jax_enable_compilation_cache
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_x64", x64_was)
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_on_tpu_mesh(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _dense(n, d, sharding, dtype=jnp.float32) -> DenseBatch:
    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    return DenseBatch(X=sds((n, d), dtype), labels=sds((n,)),
                      offsets=sds((n,)), weights=sds((n,)))


def _l2_problem(max_iter, tolerance, lam,
                optimizer=OptimizerType.LBFGS,
                task=TaskType.LOGISTIC_REGRESSION,
                **kw) -> GLMOptimizationProblem:
    return GLMOptimizationProblem(
        config=GLMOptimizationConfiguration(
            max_iterations=max_iter, tolerance=tolerance,
            regularization_weight=lam, optimizer_type=optimizer,
            regularization_context=RegularizationContext(
                RegularizationType.L2)),
        task=task, **kw)


KERNEL_SHAPES = pytest.mark.parametrize("n,d,dtype", [
    GLM_SHAPE + ("float32",),
    GLM_SHAPE + ("bfloat16",),
    (GLMIX_ROWS, GLMIX_FIXED_DIM, "float32"),  # ragged last tile, odd width
    (65536, MAX_PALLAS_DIM, "float32"),
])


@KERNEL_SHAPES
def test_fused_kernel_compiles(one_chip, n, d, dtype):
    loss = get_loss("logistic")
    b = _dense(n, d, one_chip, jnp.dtype(dtype))
    w = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    shift = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda X, y, o, wt, w, s: fused_value_gradient_sums(
            loss, False, X, y, o, wt, w, s)
    ).lower(b.X, b.labels, b.offsets, b.weights, w, shift).compile()
    assert MOSAIC_CALL in compiled.as_text()


@KERNEL_SHAPES
def test_fused_hvp_compiles(one_chip, n, d, dtype):
    """The Hessian-vector form at the value+gradient form's shapes (the
    program engages each from its ``MIN_PALLAS_DIM`` columns on; the
    kernels themselves take the 65-wide block too)."""
    loss = get_loss("logistic")
    b = _dense(n, d, one_chip, jnp.dtype(dtype))
    vec = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    shift = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda X, y, o, wt, w, s, v, vs: fused_hessian_vector_sums(
            loss, False, X, y, o, wt, w, s, v, vs)
    ).lower(b.X, b.labels, b.offsets, b.weights, vec, shift, vec,
            shift).compile()
    assert MOSAIC_CALL in compiled.as_text()


def test_lbfgs_solve_compiles_with_kernel_in_loop(one_chip, as_on_one_tpu):
    """chip_smoke phase 1's program: train_glm_grid's solve at 262144x2048."""
    n, d = GLM_SHAPE
    problem = _l2_problem(80, 1e-6, 10.0)
    x0 = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(problem.solve).lower(
        problem.objective(), _dense(n, d, one_chip), x0).compile()
    text = compiled.as_text()
    assert MOSAIC_CALL in text and "while" in text


@pytest.mark.parametrize("n", [10_000_054, 5_046_676])
def test_narrow_fixed_effect_solve_compiles_two_pass(one_chip, as_on_one_tpu,
                                                     n):
    """The sweep cells' fixed-effect solve at the cells' own shapes
    (benchmark/configs/glmix-ml10m.json, game-ml20m.json: 64 global
    features + intercept): under ``MIN_PALLAS_DIM`` columns the gate keeps
    both loops off the kernel, so
    no Mosaic call, the two halves of a two-pass evaluation under their
    scopes, and no more temporary memory than the row-major, lane-padded
    copy of X that the loops carry in either form (the fused form's
    temporaries read 5,242,291,200 bytes at 10,000,054 rows, this form's
    5,121,770,496: PERF.md, PR 34)."""
    d = GLMIX_FIXED_DIM
    assert d < MIN_PALLAS_DIM["value_and_grad"]
    problem = _l2_problem(6, 1e-30, 10.0)
    x0 = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(problem.solve).lower(
        problem.objective(), _dense(n, d, one_chip), x0).compile()
    text = compiled.as_text()
    assert MOSAIC_CALL not in text and "while" in text
    assert "objective.margins" in text and "objective.feature_sum" in text
    padded_copy = n * 128 * 4
    assert (compiled.memory_analysis().temp_size_in_bytes
            < 1.005 * padded_copy < 5_242_291_200 * n / 10_000_054)


def test_tron_solve_compiles_with_kernel_in_cg_loop(one_chip, as_on_one_tpu):
    """The TRON cell's program at chip_smoke's shape: the fused product is
    a Mosaic call inside the conjugate-gradient ``while`` inside the
    trust-region ``while``, beside the value+gradient form's calls, and no
    pass over X is left in plain XLA."""
    n, d = GLM_SHAPE
    problem = _l2_problem(80, 1e-6, 10.0, optimizer=OptimizerType.TRON,
                          task=TaskType.LINEAR_REGRESSION)
    x0 = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(problem.solve).lower(
        problem.objective(), _dense(n, d, one_chip), x0).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if MOSAIC_CALL in line]
    assert any("tron.cg" in line and "objective.hvp" in line
               for line in calls)
    assert any("objective.value_and_grad" in line for line in calls)
    # the two halves of a two-pass product carry these scopes
    assert "objective.margins" not in text
    assert "objective.feature_sum" not in text


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("variant", [
    "_fit_blocks_donate_offsets",
    "_fit_blocks_donate_offsets_x0",
])
def test_donating_random_effect_fit_compiles(one_chip, variant):
    """The variants game/random_effect._dispatch_fit takes off the CPU. At
    a GLMix bucket N == D, so a donated [E, N] offsets (and [E, D] x0)
    buffer has an output of its own shape to alias."""
    from photon_ml_tpu.game import random_effect

    e, n, d = GLMIX_BUCKET

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    obj = GLMObjective(loss=get_loss("logistic"), l2_lambda=1.0)
    compiled = getattr(random_effect, variant).lower(
        sds(e, n, d), sds(e, n), sds(e, n), sds(e, n), sds(e, d), obj,
        sds(d), solver="lbfgs", max_iter=20, tolerance=1e-7).compile()
    aliased = compiled.memory_analysis().alias_size_in_bytes
    # one [E, N] f32 buffer (tile-padded) is all either variant can alias:
    # the fit has a single [E, D] output, the coefficients. The second
    # donation of the _x0 variant is unusable and JAX says so.
    assert e * n * 4 <= aliased < 2 * e * n * 4, (variant, aliased)


# "%gather.3 = f32[675,128]{1,0:T(8,128)} gather(": the instruction, with
# the scopes it was traced under in its metadata
_INDEXED_BY_LANE = re.compile(r" (gather|scatter)\(.*op_name=\"([^\"]*)\"")


@pytest.mark.parametrize("solver", ["lbfgs", "owlqn"])
def test_per_entity_solve_indexes_no_history_by_lane(one_chip, solver):
    """The per-entity solves keep the curvature history newest-first
    (optimize/lbfgs.py): under ``vmap`` a circular history's ``head`` is one
    index a lane, and the optimised program of the parent held 11 gathers
    under ``lbfgs.direction`` (one 128-float row a lane a step) and 6
    scatters under ``lbfgs.update`` (PERF.md, PR 36). Now neither scope
    holds either, for both solvers that share the recursion; the scans
    slice every lane's slot at once, and the compiler lays the
    ``[E, 10, D]`` history out slot-major, its 10 rows unpadded."""
    from photon_ml_tpu.game import random_effect

    e, n, d = GLMIX_BUCKET

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    obj = GLMObjective(loss=get_loss("logistic"), l2_lambda=1.0)
    compiled = random_effect._fit_blocks.lower(
        sds(e, n, d), sds(e, n), sds(e, n), sds(e, n), sds(e, d), obj,
        sds(d), solver=solver, max_iter=20, tolerance=1e-7).compile()
    text = compiled.as_text()
    for scope in ("direction", "linesearch", "update"):
        assert f"{solver}.{scope}" in text  # the scopes are there to read
    by_lane = [(op, name) for op, name in _INDEXED_BY_LANE.findall(text)
               if f"{solver}.direction" in name or f"{solver}.update" in name]
    assert by_lane == []
    assert f"f32[{e},10,{d}]{{2,0,1" in text  # slot-major: 10 rows, not 16
    # the bound: less than one [E, 10, D] array, so no copy of the history
    # is among the temporaries (1,345,024 bytes for L-BFGS and 1,657,344
    # for OWL-QN, what the parent's programs read here too; at the cell's
    # [31492, 128, 128] bucket 1,068,955,136 against the parent's
    # 1,404,802,560: PERF.md, PR 36)
    assert compiled.memory_analysis().temp_size_in_bytes < e * 10 * d * 4


def _block_readers(text, block, scope):
    """[(instruction, scope path)] of every instruction traced under
    ``scope`` that takes an operand of shape ``block`` (``f32[E,N,D]``): in
    any computation, fused ones included, an operand's shape is its
    defining line's in the same computation."""
    found = []
    for _, body in _HLO_COMPUTATION.findall(text):
        lines = body.splitlines()
        shapes = dict(re.findall(r"^\s*(?:ROOT )?%(\S+) = (\S+)", body,
                                 re.MULTILINE))
        for line in lines:
            path = re.search(r'op_name="([^"]*)"', line)
            if path is None or scope not in path.group(1):
                continue
            defined, rest = line.split(" = ", 1)
            operands = re.findall(r"%([\w.\-]+)", rest.split("metadata=")[0])
            if any(shapes.get(o, "").startswith(block) for o in operands):
                found.append((defined.strip(), path.group(1)))
    return found


def test_per_entity_line_search_reads_no_block(one_chip):
    """The per-entity L-BFGS tries its steps on margins it carries
    (optimize/lbfgs.py, ``line_fn``): one pass an iteration under
    ``objective.line`` forms them, and no instruction of the line search
    takes the ``[E, N, D]`` block, where the same solve with full trials,
    vmapped at the same shape, reads it in every trial (twice: PERF.md,
    section 6)."""
    from photon_ml_tpu.game import random_effect
    from photon_ml_tpu.optimize.lbfgs import _minimize_lbfgs_impl

    e, n, d = GLMIX_BUCKET
    block = f"f32[{e},{n},{d}]"

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    obj = GLMObjective(loss=get_loss("logistic"), l2_lambda=1.0)
    blocks = (sds(e, n, d), sds(e, n), sds(e, n), sds(e, n), sds(e, d))
    text = random_effect._fit_blocks.lower(
        *blocks, obj, sds(d), solver="lbfgs", max_iter=8,
        tolerance=1e-30).compile().as_text()
    assert "lbfgs.linesearch" in text and "objective.line/" in text
    assert _block_readers(text, block, "lbfgs.linesearch") == []
    assert _block_readers(text, block, "objective.line/")

    def full_trials(X, y, o, w, x0):
        return _minimize_lbfgs_impl(
            random_effect._vg, x0,
            (obj, DenseBatch(X=X, labels=y, offsets=o, weights=w)),
            8, 10, 1e-30, newest_first=True)[0]

    control = jax.jit(jax.vmap(full_trials)).lower(*blocks).compile()
    assert len(_block_readers(control.as_text(), block,
                              "lbfgs.linesearch")) >= 2


@pytest.mark.parametrize("n,d", [(GLMIX_ROWS, GLMIX_FIXED_DIM), GLM_SHAPE])
def test_unbatched_lbfgs_tries_its_steps_in_full(one_chip, as_on_one_tpu,
                                                  n, d):
    """Every unbatched L-BFGS solve (a fixed effect at 65 columns, a dense
    GLM at 2,048) keeps the trial-on-full-evaluation form: its program
    holds no ``objective.line``."""
    problem = _l2_problem(6, 1e-30, 10.0)
    x0 = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    text = jax.jit(problem.solve).lower(
        problem.objective(), _dense(n, d, one_chip), x0).compile().as_text()
    assert "lbfgs.linesearch" in text
    assert "objective.line" not in text


@pytest.mark.parametrize("d", [GLMIX_FIXED_DIM, GLM_SHAPE[1]])
def test_sharded_fixed_effect_step_compiles(mesh, as_on_tpu_mesh, d):
    """``chip_smoke.py --chips 4``'s fixed-effect update: the solver inside
    ``shard_map`` over the data axis of a 2x2 mesh, the weight update
    sharded (what --re-entity-shards sets). The gate's width rule holds per
    shard: at the smoke's 65 columns each shard's passes are two-pass XLA,
    at the dense width the fused kernel runs on each shard."""
    from photon_ml_tpu.parallel.distributed import sharded_fit

    problem = _l2_problem(40, 1e-7, 10.0, shard_weight_update=True)
    batch = _dense(MESH_ROWS, d, NamedSharding(mesh, P(DATA_AXIS)))
    x0 = jax.ShapeDtypeStruct((d,), jnp.float32,
                              sharding=NamedSharding(mesh, P()))
    fit, shard_update = sharded_fit(problem, batch, mesh, jnp.float32)
    assert shard_update
    compiled = jax.jit(fit).lower(batch, x0).compile()
    text = compiled.as_text()
    fused = d >= MIN_PALLAS_DIM["value_and_grad"]
    assert (MOSAIC_CALL in text) == fused
    assert ("objective.margins" in text) == (not fused)
    assert "all-reduce" in text
    per_device = compiled.memory_analysis().argument_size_in_bytes
    # rows split over the data axis: each device holds half of X
    assert per_device < 0.6 * MESH_ROWS * d * 4 * 1.1


def test_sharded_random_effect_fit_compiles(mesh):
    """The entity-sharded per-entity solve: lanes split over the mesh
    entity axis, no collective inside the solve."""
    from photon_ml_tpu.game.random_effect import _sharded_fit_fn

    e, n, d = 676, GLMIX_BUCKET[1], GLMIX_BUCKET[2]  # lanes divide entity=2
    lane = NamedSharding(mesh, P(ENTITY_AXIS))
    rep = NamedSharding(mesh, P())

    def sds(shape, sharding):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    obj = GLMObjective(loss=get_loss("logistic"), l2_lambda=1.0)
    compiled = _sharded_fit_fn(mesh, "lbfgs", 20, 1e-7, False, False).lower(
        sds((e, n, d), lane), sds((e, n), lane), sds((e, n), lane),
        sds((e, n), lane), sds((e, d), lane), obj, sds((d,), rep)).compile()
    text = compiled.as_text()
    assert "all-reduce" not in text
    assert (compiled.memory_analysis().argument_size_in_bytes
            < 0.6 * e * n * d * 4 * 1.1)


# the wide-sparse cell's shape (benchmark/configs/glm-sparse-criteo.json)
CRITEO_ROWS, CRITEO_SLOTS, CRITEO_DIM = 11_468_800, 39, 1_000_000
V5E_HBM_BYTES = 16e9


def _ell(rows, slots, dim, row_sharding, plane_sharding=None):
    from photon_ml_tpu.data.batch import EllBatch

    plane_sharding = plane_sharding or row_sharding

    def sds(shape, dt, sharding):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    row = [sds((rows,), jnp.float32, row_sharding) for _ in range(3)]
    return EllBatch(sds((slots, rows), jnp.int32, plane_sharding),
                    sds((slots, rows), jnp.float32, plane_sharding), *row,
                    dim=dim)


def _criteo_solve(one_chip):
    """The Criteo-shaped cell's program, compiled: L-BFGS over the
    slot-major ELL batch, 447M stored slots and a 1M-wide solve."""
    problem = _l2_problem(6, 1e-30, 10.0)
    x0 = jax.ShapeDtypeStruct((CRITEO_DIM,), jnp.float32, sharding=one_chip)
    return jax.jit(problem.solve).lower(
        problem.objective(),
        _ell(CRITEO_ROWS, CRITEO_SLOTS, CRITEO_DIM, one_chip), x0).compile()


def test_wide_sparse_solve_fits_one_chip_slot_major(one_chip):
    """The [39, N] planes pad to 40 sublanes (2.6%); held [N, 39] the same
    solve asks the compiler for 21.5 GB (PERF.md, PR 29)."""
    compiled = _criteo_solve(one_chip)
    memory = compiled.memory_analysis()
    planes = 2 * CRITEO_ROWS * CRITEO_SLOTS * 4
    vectors = 3 * CRITEO_ROWS * 4 + CRITEO_DIM * 4
    assert planes + vectors < memory.argument_size_in_bytes < (
        1.03 * planes + 1.01 * vectors)  # 39 slots in 40 sublanes
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 0.6 * V5E_HBM_BYTES)
    text = compiled.as_text()
    assert "objective.margins" in text and "objective.feature_sum" in text


# "%name = f32[39,11468800]{layout} opcode(": an instruction that makes one
# array (a tuple's shape starts with a bracket and is skipped)
_HLO_ARRAY = re.compile(
    r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(", re.MULTILINE)


def test_wide_sparse_solve_makes_no_plane_sized_temporary(one_chip):
    """The pass walks the slots with an [N] or [D] accumulator: between the
    argument planes and the results no instruction of the optimised program
    makes an array of K x N elements (the parent's gather wrote a flat
    f32[447283200] and its temporaries were 3.85 GB; PERF.md, PR 30). The
    planes themselves only pass through the loops' tuples."""
    compiled = _criteo_solve(one_chip)
    plane = CRITEO_ROWS * CRITEO_SLOTS
    made = [(dims, opcode)
            for dims, opcode in _HLO_ARRAY.findall(compiled.as_text())
            if math.prod(int(d) for d in dims.split(",") if d) >= plane
            and opcode not in ("parameter", "get-tuple-element")]
    assert made == []
    assert compiled.memory_analysis().temp_size_in_bytes < plane * 4


# "%fused_computation.3 (param_0.1: f32[1000000], ...) -> f32[11468800] {":
# a computation's header, then its instructions, then "}"
_HLO_COMPUTATION = re.compile(
    r"^(?:ENTRY )?%(\S+) \([^\n]*\) -> [^\n]* \{$\n(.*?)^\}$",
    re.MULTILINE | re.DOTALL)
_HLO_SHAPE = re.compile(r"= (\w+\[[\d,]*\]\S*) ")
VMEM = "S(1)"


def _fusions_of(text, opcode, scope):
    """[(output shape, parameters' shapes, scope path)] of every fusion
    whose computation holds an ``opcode`` under ``scope``, the path that
    instruction's. A parameter of a fused computation carries the memory
    space its operand is read from."""
    bodies = dict(_HLO_COMPUTATION.findall(text))
    callers = {}
    for body in bodies.values():
        for line in body.splitlines():
            called = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
            if called:
                callers[called.group(1)] = line
    found = []
    for name, body in bodies.items():
        ops = [line for line in body.splitlines()
               if f" {opcode}(" in line and f"/{scope}/" in line]
        if ops and name in callers:
            params = [_HLO_SHAPE.search(line).group(1)
                      for line in body.splitlines() if " parameter(" in line]
            path = re.search(r'op_name="([^"]*)"', ops[0]).group(1)
            found.append((_HLO_SHAPE.search(callers[name]).group(1), params,
                          path))
    return found


def test_the_row_sparse_solve_keeps_its_gathers_and_scatters_in_vmem(
        one_chip):
    """Every gather of the Criteo-shaped solve, the start's and the line
    search's, reads its ``f32[1000000]`` table and its clamped indices and
    writes its output in VMEM (memory space ``S(1)``); every scatter-add
    writes its column sums there. The start is evaluated in a loop of its
    own (``lbfgs.start``): evaluated at the program's top level its gather
    had none of the three in VMEM and ran at 14.2 ns a slot against the
    line search's 7.13 (PERF.md, PR 30 and PR 38)."""
    text = _criteo_solve(one_chip).as_text()
    gathers = _fusions_of(text, "gather", "objective.margins")
    scatters = _fusions_of(text, "scatter", "objective.feature_sum")
    for found in (gathers, scatters):
        assert sorted("lbfgs.start" in path for _, _, path in found) == [
            False, True]  # the start, and the line search's trials
        assert all("lbfgs.start/while/" in path
                   or "lbfgs.linesearch/while/" in path
                   for _, _, path in found)
    for out, params, _ in gathers:
        assert params[0].startswith(f"f32[{CRITEO_DIM}]")
        assert params[1].startswith(f"s32[{CRITEO_ROWS}]")
        assert out.startswith(f"f32[{CRITEO_ROWS}]")
        assert all(VMEM in shape for shape in [out] + params), (out, params)
    for out, _, _ in scatters:
        assert out.startswith(f"f32[{CRITEO_DIM}]") and VMEM in out


def _start_scopes(text):
    """The scope paths above ``objective.value_and_grad`` of the program's
    start evaluation: every one outside the line search."""
    return {name.split("objective.value_and_grad")[0]
            for name in re.findall(r'op_name="([^"]*)"', text)
            if "objective.value_and_grad" in name
            and "lbfgs.linesearch" not in name}


# "%name = f32[675,128]{...} opcode(...), metadata={op_name="..."}": an
# instruction's result type, its opcode and its scope path
_HLO_OP = re.compile(
    r"= (\S+) ([\w-]+)\(.*op_name=\"([^\"]*)\"", re.MULTILINE)


def _per_user_bucket_solve(one_chip):
    """The per-user bucket program at ``GLMIX_BUCKET``: the vmapped L-BFGS
    solves of a GLMix bucket, each making its own start."""
    from photon_ml_tpu.game import random_effect

    e, n, d = GLMIX_BUCKET

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    obj = GLMObjective(loss=get_loss("logistic"), l2_lambda=1.0)
    return random_effect._fit_blocks.lower(
        sds(e, n, d), sds(e, n), sds(e, n), sds(e, n), sds(e, d), obj,
        sds(d), solver="lbfgs", max_iter=8, tolerance=1e-30).compile()


@pytest.mark.parametrize("solve", ["dense-2048", "fixed-effect-65",
                                   "per-user-bucket", "criteo"])
def test_the_start_is_made_in_a_loop_for_a_row_sparse_batch_alone(
        one_chip, as_on_one_tpu, solve):
    """``solver_start_lowerings{site, form}`` books the form once a traced
    L-BFGS program: ``in_loop`` for the ``EllBatch`` solve, whose start
    sits inside ``lbfgs.start``'s ``while``; ``product_sum`` for the dense
    solves the fused kernel's gate sends to the two-pass form (the sweep
    cells' 65-wide fixed effect, a per-user bucket under ``vmap``), whose
    start stays at the program's top level under ``lbfgs.start``, in
    float32 products and sums: no operation there is a ``convolution``
    (the MXU's form of a default-precision einsum at the top level, over
    operands cut to bfloat16) or makes a ``bf16`` array; and ``direct`` for
    the fused kernel's dense solve (the GLM cells' at 2,048 columns), whose
    start is the kernel's call at the top level: its scope path holds no
    ``/while/``."""
    from photon_ml_tpu.obs.metrics import REGISTRY

    counter = REGISTRY.counter("solver_start_lowerings")
    forms = ("in_loop", "product_sum", "direct")
    before = {f: counter.value(site="optimizer.lbfgs", form=f) for f in forms}
    jax.clear_caches()  # the solver's trace is cached across programs
    if solve == "criteo":
        text, form = _criteo_solve(one_chip).as_text(), "in_loop"
    elif solve == "per-user-bucket":
        text = _per_user_bucket_solve(one_chip).as_text()
        form = "product_sum"
    else:
        (n, d), form = {
            "dense-2048": (GLM_SHAPE, "direct"),
            "fixed-effect-65": ((GLMIX_ROWS, GLMIX_FIXED_DIM),
                                "product_sum")}[solve]
        problem = _l2_problem(6, 1e-30, 10.0)
        x0 = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
        text = jax.jit(problem.solve).lower(
            problem.objective(), _dense(n, d, one_chip), x0).compile(
        ).as_text()
    assert {f: counter.value(site="optimizer.lbfgs", form=f) - before[f]
            for f in forms} == {f: int(f == form) for f in forms}
    if form == "product_sum":
        start = [(out, opcode, path)
                 for out, opcode, path in _HLO_OP.findall(text)
                 if "lbfgs.start/" in path]
        paths = {path for _, _, path in start}
        assert any("objective.margins" in p for p in paths)
        assert any("objective.feature_sum" in p for p in paths)
        assert not any("/while/" in p for p in paths), paths
        assert [(out, opcode) for out, opcode, _ in start
                if opcode == "convolution" or out.startswith("bf16")] == []
        return
    scopes = _start_scopes(text)
    assert scopes
    if form == "direct":
        assert not any("/while/" in scope for scope in scopes), scopes
        calls = [re.search(r'op_name="([^"]*)"', line).group(1)
                 for line in text.splitlines()
                 if " custom-call(" in line and "op_name=" in line]
        assert any("objective.value_and_grad" in path
                   and "/while/" not in path for path in calls), calls
        assert "lbfgs.start" not in text
    else:
        assert all(scope.endswith("lbfgs.start/while/body/")
                   for scope in scopes), scopes


def test_sharded_wide_sparse_solve_compiles(mesh, as_on_tpu_mesh):
    """The same solve inside ``shard_map``, the planes split along their
    row (minor) axis over the data axis of the 2x2 mesh."""
    from photon_ml_tpu.data.batch import row_partition_specs
    from photon_ml_tpu.parallel.distributed import sharded_fit

    problem = _l2_problem(6, 1e-30, 10.0)
    by_row = NamedSharding(mesh, P(DATA_AXIS))
    batch = _ell(MESH_ROWS, CRITEO_SLOTS, CRITEO_DIM, by_row,
                 NamedSharding(mesh, P(None, DATA_AXIS)))
    specs = row_partition_specs(batch, DATA_AXIS)
    assert specs.indices == P(None, DATA_AXIS) and specs.labels == P(DATA_AXIS)
    x0 = jax.ShapeDtypeStruct((CRITEO_DIM,), jnp.float32,
                              sharding=NamedSharding(mesh, P()))
    fit, _ = sharded_fit(problem, batch, mesh, jnp.float32)
    compiled = jax.jit(fit).lower(batch, x0).compile()
    assert "all-reduce" in compiled.as_text()
    per_device = compiled.memory_analysis().argument_size_in_bytes
    planes = 2 * MESH_ROWS * CRITEO_SLOTS * 4
    assert per_device < 0.6 * planes * 1.1 + CRITEO_DIM * 4


# the ragged cell's shape (benchmark/configs/glm-ragged-kddb.json): 74 blocks
# of 65,536 rows over 29,890,095 columns; the blocks of slots the layout's
# rule gives the generator's row lengths, [slots, rows] each (PERF.md, PR 35)
KDDB_ROWS, KDDB_DIM = 4_849_664, 29_890_095
KDDB_BLOCKS = ((16, 4_849_664), (8, 4_575_254), (8, 2_666_620),
               (8, 1_360_526), (8, 710_441), (16, 388_101), (24, 132_049),
               (40, 33_388))


def _kddb(row_sharding, plane_sharding, run_sharding, shards=1):
    """The cell's layout as ``shards`` runs of its rows: block 0
    ``[16, shards x N]``, a further block ``[shards, K, n_g]``."""
    from photon_ml_tpu.data.batch import EllBatch

    def sds(shape, dt, sharding):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def planes(shape, sharding):
        return sds(shape, jnp.int32, sharding), sds(shape, jnp.float32,
                                                    sharding)

    (k0, n0), rest = KDDB_BLOCKS[0], KDDB_BLOCKS[1:]
    rows = [sds((shards * n0,), jnp.float32, row_sharding) for _ in range(3)]
    return EllBatch(
        *planes((k0, shards * n0), plane_sharding), *rows,
        tuple(planes((shards, k, n), run_sharding) for k, n in rest), None,
        dim=KDDB_DIM)


def _elastic_net_problem():
    problem = GLMOptimizationProblem(
        config=GLMOptimizationConfiguration(
            max_iterations=5, tolerance=1e-30, regularization_weight=1.0,
            optimizer_type=OptimizerType.LBFGS,
            regularization_context=RegularizationContext(
                RegularizationType.ELASTIC_NET, alpha=0.5)),
        task=TaskType.LOGISTIC_REGRESSION)
    assert problem.solver_site() == "optimizer.owlqn"
    return problem


KDDB_SLOTS = sum(k * n for k, n in KDDB_BLOCKS)
KDDB_HISTORY = 2 * 16 * KDDB_DIM * 4  # S and Y, 10 rows in 16 sublanes


def test_ragged_owlqn_solve_fits_one_chip_and_updates_its_history_in_place(
        one_chip):
    """The KDD Cup 2010-shaped cell's program, compiled: OWL-QN (LBFGS +
    ELASTIC_NET) over the ELL layout in eight blocks of slots, 162,811,664
    slots walked for 142,603,811 stored, a 29.9M-wide solve. The ``[10, D]``
    history is updated one row at a time in place (a ``dynamic-update-
    slice``, no whole copy), so the program stays under half the chip;
    its 10 rows are tiled to 16 sublanes, which is most of the
    temporaries. A further block's leading axis of one run costs nothing:
    no plane is padded or copied."""
    problem = _elastic_net_problem()
    compiled = jax.jit(problem.solve).lower(
        problem.objective(), _kddb(one_chip, one_chip, one_chip),
        jax.ShapeDtypeStruct((KDDB_DIM,), jnp.float32,
                             sharding=one_chip)).compile()
    memory = compiled.memory_analysis()
    assert 8 * KDDB_SLOTS < memory.argument_size_in_bytes < 1.02 * (
        8 * KDDB_SLOTS + 12 * KDDB_ROWS + 4 * KDDB_DIM)
    assert KDDB_HISTORY < memory.temp_size_in_bytes < (
        KDDB_HISTORY + 16 * KDDB_DIM * 4)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 0.5 * V5E_HBM_BYTES)
    text = compiled.as_text()
    whole = [(dims, opcode) for dims, opcode in _HLO_ARRAY.findall(text)
             if dims == f"10,{KDDB_DIM}"]
    assert {opcode for _, opcode in whole} <= {
        "dynamic-update-slice", "broadcast", "parameter",
        "get-tuple-element"}, whole
    assert "owlqn.update" in text and "objective.feature_sum" in text


def test_the_ragged_fit_compiles_row_sharded_over_a_v5e_host(topo):
    """The configuration's stated deployment: the rows data-parallel over
    the four chips of one host, each chip the one-chip cell's share, through
    ``sharded_fit`` on the layout dealt into four runs of rows. A chip holds
    one run of every block of slots and what the one-chip program holds."""
    from photon_ml_tpu.data.batch import row_partition_specs
    from photon_ml_tpu.parallel.distributed import sharded_fit

    mesh = make_mesh(num_data=4, num_entity=1, devices=list(topo.devices))
    batch = _kddb(NamedSharding(mesh, P(DATA_AXIS)),
                  NamedSharding(mesh, P(None, DATA_AXIS)),
                  NamedSharding(mesh, P(DATA_AXIS, None, None)), shards=4)
    specs = row_partition_specs(batch, DATA_AXIS)
    assert specs.indices == P(None, DATA_AXIS) and specs.order is None
    assert all(ix == v == P(DATA_AXIS, None, None) for ix, v in specs.tail)
    fit, _ = sharded_fit(_elastic_net_problem(), batch, mesh, jnp.float32)
    compiled = jax.jit(fit).lower(batch, jax.ShapeDtypeStruct(
        (KDDB_DIM,), jnp.float32,
        sharding=NamedSharding(mesh, P()))).compile()
    assert "all-reduce" in compiled.as_text()
    memory = compiled.memory_analysis()  # of one device
    assert 8 * KDDB_SLOTS < memory.argument_size_in_bytes < 1.02 * (
        8 * KDDB_SLOTS + 12 * KDDB_ROWS + 4 * KDDB_DIM)
    assert KDDB_HISTORY < memory.temp_size_in_bytes < (
        KDDB_HISTORY + 24 * KDDB_DIM * 4)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 0.5 * V5E_HBM_BYTES)


# the long-row cell's shape (benchmark/configs/glm-longrow-webspam.json): 87,500
# rows over 16,609,143 columns, the blocks of slots the layout's rule gives the
# generator's row lengths, [slots, rows] each
WEBSPAM_ROWS, WEBSPAM_DIM = 87_500, 16_609_143
WEBSPAM_BLOCKS = ((1656, 87_500), (1080, 63_955), (1328, 43_131),
                  (1688, 26_443), (2384, 15_072), (3480, 7_327),
                  (6176, 3_001), (14976, 780))
WEBSPAM_SLOTS = sum(k * n for k, n in WEBSPAM_BLOCKS)


def _layout(blocks, dim, one_chip):
    """An ``EllBatch`` of one run of rows in ``blocks``, as the builder
    lays it out: block 0 ``[K, N]``, a further block ``[1, K, n_g]``."""
    from photon_ml_tpu.data.batch import EllBatch

    def planes(shape):
        return (jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip))

    (k0, n0), rest = blocks[0], blocks[1:]
    rows = [jax.ShapeDtypeStruct((n0,), jnp.float32, sharding=one_chip)
            for _ in range(3)]
    return EllBatch(*planes((k0, n0)), *rows,
                    tuple(planes((1, k, n)) for k, n in rest), None, dim=dim)


def _lowered_forms(monkeypatch, lower):
    """``lower()``'s text as the program lowers it and with every walk
    forced to one slot a step, and the forms each booked on
    ``ell_walk_lowerings``."""
    from photon_ml_tpu.data import batch as batch_module
    from photon_ml_tpu.obs.metrics import REGISTRY

    counter = REGISTRY.counter("ell_walk_lowerings")
    out = []
    for rows in (batch_module.ELL_TILE_ROWS, 0):
        with monkeypatch.context() as patch:
            patch.setattr(batch_module, "ELL_TILE_ROWS", rows)
            jax.clear_caches()  # the walk's form is decided at trace time
            before = {f: counter.value(form=f) for f in ("slot", "tile")}
            text = lower().as_text()
            out.append((text, {f: counter.value(form=f) - before[f]
                               for f in ("slot", "tile")}))
    jax.clear_caches()
    return out


@pytest.mark.parametrize("solve", ["criteo", "kddb"])
def test_the_sparse_cells_walk_one_slot_a_step_as_they_did(
        one_chip, monkeypatch, solve):
    """Every block of the Criteo-shaped and KDD Cup 2010-shaped layouts
    holds ``ELL_TILE_ROWS`` rows or more (the smallest, kddb's last, 33,388),
    so their walks keep one slot a step: the lowered solve is the one the
    walk forced to slots lowers to, text for text, and books ``slot``
    alone (checked once against the program before the tiled walk, with
    traceback locations off: PERF.md)."""
    from photon_ml_tpu.data import batch as batch_module

    if solve == "criteo":
        problem, batch = _l2_problem(6, 1e-30, 10.0), _ell(
            CRITEO_ROWS, CRITEO_SLOTS, CRITEO_DIM, one_chip)
        dim = CRITEO_DIM
    else:
        problem, batch = _elastic_net_problem(), _kddb(one_chip, one_chip,
                                                       one_chip)
        dim = KDDB_DIM
    assert min(n for _, n in KDDB_BLOCKS) >= batch_module.ELL_TILE_ROWS
    x0 = jax.ShapeDtypeStruct((dim,), jnp.float32, sharding=one_chip)
    (text, forms), (slots_text, _) = _lowered_forms(
        monkeypatch,
        lambda: jax.jit(problem.solve).lower(problem.objective(), batch, x0))
    assert text == slots_text
    assert forms["tile"] == 0 and forms["slot"] > 0


def test_the_long_row_svm_solve_walks_its_deep_blocks_in_tiles(
        one_chip, monkeypatch):
    """The long-row cell's program, compiled: the smoothed-hinge L-BFGS over
    the layout's eight blocks, the five deepest over fewer than
    ``ELL_TILE_ROWS`` rows walked in tiles (``ell_walk_lowerings{form=
    tile}``), the rest a slot a step; the planes, the rows' vectors and
    the ``[10, D]`` history fit well inside one chip."""
    from photon_ml_tpu.data import batch as batch_module

    problem = _l2_problem(4, 1e-30, 1.0,
                          task=TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM)
    batch = _layout(WEBSPAM_BLOCKS, WEBSPAM_DIM, one_chip)
    x0 = jax.ShapeDtypeStruct((WEBSPAM_DIM,), jnp.float32, sharding=one_chip)
    (text, forms), (slots_text, slot_forms) = _lowered_forms(
        monkeypatch,
        lambda: jax.jit(problem.solve).lower(problem.objective(), batch, x0))
    tiled = sum(n < batch_module.ELL_TILE_ROWS for _, n in WEBSPAM_BLOCKS)
    assert 1 <= tiled < len(WEBSPAM_BLOCKS)
    # each walk of a block books its form: the tiled blocks' share of all
    assert forms["tile"] * len(WEBSPAM_BLOCKS) == tiled * (
        forms["tile"] + forms["slot"])
    assert slot_forms["tile"] == 0 and text != slots_text
    compiled = jax.jit(problem.solve).lower(problem.objective(), batch,
                                            x0).compile()
    memory = compiled.memory_analysis()
    assert 8 * WEBSPAM_SLOTS < memory.argument_size_in_bytes < 1.02 * (
        8 * WEBSPAM_SLOTS + 12 * WEBSPAM_ROWS + 4 * WEBSPAM_DIM)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 0.5 * V5E_HBM_BYTES)


# --- the factored coordinate's projection refit (PR 33) ---------------------

# benchmark/configs/game-ml20m.json: the per-user buckets of one chip's
# quarter of MovieLens-20M, K = 32 latent factors, 26,744 movies
GAME_USER_BUCKETS = ((15781, 128, 128), (4562, 96, 96), (6486, 72, 72),
                     (7795, 48, 48))
GAME_LATENT_DIM, GAME_MOVIES = 32, 26744


def test_factored_refit_fits_one_chip_at_the_cells_size(one_chip):
    """The refit of ``game-ml20m.train`` compiled: six L-BFGS iterations
    over the four per-user buckets in the refit's own layout. The reference
    builds the Kronecker features, here a [3.0M, 855,808] matrix; the layout
    gathers and scatter-adds K-wide columns, so no instruction of the
    optimised program makes an array a tenth that size, and arguments and
    temporaries fit a third of one chip."""
    from photon_ml_tpu.data.batch import ProjectionRefitBatch

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    rows = sum(e * n for e, n, _ in GAME_USER_BUCKETS)
    batch = ProjectionRefitBatch(
        [(sds((e, n, d)), sds((e, d), jnp.int32),
          sds((e, GAME_LATENT_DIM))) for e, n, d in GAME_USER_BUCKETS],
        sds((rows,)), sds((rows,)), sds((rows,)), dim=GAME_MOVIES)
    problem = _l2_problem(6, 1e-30, 1.0)

    def _factored_refit_impl(obj, batch, x0):
        return problem.solve(obj, batch, x0)

    compiled = jax.jit(_factored_refit_impl).lower(
        problem.objective(), batch,
        sds((GAME_LATENT_DIM * GAME_MOVIES,))).compile()
    memory = compiled.memory_analysis()
    blocks = sum(e * n * d for e, n, d in GAME_USER_BUCKETS) * 4
    assert memory.argument_size_in_bytes > blocks
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < V5E_HBM_BYTES / 3)
    text = compiled.as_text()
    assert "jit__factored_refit_impl" in text[:400]
    for scope in ("factored.project", "objective.margins",
                  "objective.feature_sum", "lbfgs.linesearch"):
        assert scope in text, scope
    kronecker = rows * GAME_LATENT_DIM * GAME_MOVIES
    largest = max(math.prod(int(d) for d in dims.split(",") if d)
                  for dims, _ in _HLO_ARRAY.findall(text))
    assert largest < kronecker // 1000


# --- a random-effect coordinate's scores, gathered by position (PR 33) ------

# the per-item side of the same configuration: the skew's other end
GAME_ITEM_BUCKETS = ((3678, 128, 128), (1680, 64, 64), (2668, 24, 24),
                     (12368, 8, 8))
GAME_ROWS, GAME_ITEM_PASSIVE = 5_046_676, 4_469_393


def test_scoring_by_position_compiles_without_a_scatter(one_chip):
    """``score_random_effect`` on one chip: every block's margins as they
    lie, the passive rows', and one gather by each sample's place among
    them. The TPU's compiler took 7-16 s for each of the five scatters
    into a sample-long vector this replaces (ROADMAP S13); none of the six
    optimised programs holds one, and the last gathers the whole score
    vector."""
    from photon_ml_tpu.game.random_effect import (
        _active_margins,
        _gather_scores,
        _passive_margins,
    )

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    entities = sum(e for e, _, _ in GAME_ITEM_BUCKETS)
    programs = [_active_margins.lower(sds((e, n, d)), sds((e, d)),
                                      sds((e, n))).compile()
                for e, n, d in GAME_ITEM_BUCKETS]
    programs.append(_passive_margins.lower(
        sds((GAME_ITEM_PASSIVE, 128)), sds((GAME_ITEM_PASSIVE,), jnp.int32),
        sds((entities, 128))).compile())
    gather = _gather_scores.lower(
        [sds((e, n)) for e, n, _ in GAME_ITEM_BUCKETS]
        + [sds((GAME_ITEM_PASSIVE,))], sds((GAME_ROWS,), jnp.int32)).compile()
    for compiled in programs + [gather]:
        text = compiled.as_text()
        assert not re.search(r"\bscatter\(", text)  # the instruction
        assert "re.score" in text
        # the margins are a product and a sum in float32: as an einsum
        # alone in its program, a bucket 64 columns wide or more became an
        # MXU convolution over coefficients rounded to bfloat16 (PR 36)
        assert "convolution" not in text and "bf16" not in text
    text = gather.as_text()
    assert "jit__gather_scores" in text[:400]
    assert f"f32[{GAME_ROWS}]" in text  # the gathered score vector
    assert gather.memory_analysis().output_size_in_bytes >= GAME_ROWS * 4
    assert sum(c.memory_analysis().temp_size_in_bytes
               for c in programs + [gather]) < V5E_HBM_BYTES / 2


# glmix-ml10m.train's rows and its four per-user buckets (E, N, D)
GLMIX_CELL_ROWS = 10_000_054
GLMIX_CELL_BUCKETS = ((31492, 128, 128), (9268, 96, 96), (13170, 72, 72),
                      (15948, 48, 48))


def test_the_score_exchanges_named_programs_compile_at_the_cells_shapes(
        one_chip):
    """The offset exchange is one program a dataset and the buckets'
    coefficient blocks one more (PR 37): at GLMix's four buckets and ten
    million scores both compile for the chip under the names a device
    trace shows (``jit__block_offsets``, ``jit__bucket_coefs``; a
    ``jit_gather`` a bucket before), their operations under the scopes
    ``re.offsets`` / ``re.score``."""
    from photon_ml_tpu.game import dataset, random_effect

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    offsets = dataset._block_offsets.lower(
        tuple(sds((e, n)) for e, n, _ in GLMIX_CELL_BUCKETS),
        tuple(sds((e, n), jnp.int32) for e, n, _ in GLMIX_CELL_BUCKETS),
        sds((GLMIX_CELL_ROWS,)))
    assert "jit__block_offsets" in offsets.as_text()[:200]
    text = offsets.compile().as_text()
    assert "re.offsets" in text and text.count(" gather(") >= 1
    spans, start = [], 0
    for e, _, d in GLMIX_CELL_BUCKETS:
        spans.append((start, e - 3, e, d))  # three pad lanes a bucket
        start += e - 3
    coefs = random_effect._bucket_coefs.lower(sds((start, 128)),
                                              spans=tuple(spans))
    assert "jit__bucket_coefs" in coefs.as_text()[:200]
    assert "re.score" in coefs.compile().as_text()
