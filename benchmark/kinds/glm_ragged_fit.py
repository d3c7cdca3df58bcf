"""Kind ``glm_ragged_fit``: a step is one fit of a fixed-effect logistic GLM
with an elastic-net penalty on a wide-sparse matrix whose rows differ in
length (tens of millions of columns, a few dozen non-zeros a row, some rows
four times the mean), through ``csr_to_batch`` and ``train_glm_grid``: the
program's normal path, whose LBFGS + ELASTIC_NET is OWL-QN.

The rows are made on the host in row blocks from the configuration's
``data_seed`` (``generators/kddb_rows.py``) and handed to the program's own
builder as one CSR matrix: the layout (how many blocks of slots, which rows
where) and the packing are the program's, timed as ``block_build_s``; this
kind builds no plane. ``--seed`` deals the row blocks in another order, so
every seed fits the same rows and does the same work. As in
``glm_sparse_fit``, every step adds one of a cycle of two small offset
vectors to the margins, in the order the seed deals, and the window closes
on a whole cycle (the harness starts its profiler at a window's second
step, and one fit outlasts ``--seconds``).

No minimiser is affordable at 29.9 million columns, so ``verify`` judges the
solver without one, as ``glm_sparse_fit`` does: ``F`` and the
pseudo-gradient norm the program reports at its own coefficients against
the reference's evaluation there; the reference's ``F`` there against the
reference's after as many iterations of its own textbook OWL-QN; the
reported values, which may never rise; and the share of coefficients that
are exactly zero against the textbook's.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import work_ragged
from benchmark.generators import kddb_rows
from benchmark.kinds import glm_grid_fit as dense
from benchmark.reference import glm_ragged as reference


class State:
    def __init__(self):
        self.mat = self.y = None  # the benchmark's rows, on the host
        self.batch = None  # the program's layout of them, on the device
        self.flat = None  # the reference's flat arrays, made when asked for
        self.settings = None
        self.rows = self.features = self.block = self.nonzeros = 0
        # what glm_grid_fit.jitter reads
        self.steps_made = self.data_seed = 0
        self.jitter, self.cycle = 0.0, None


def build(config: dict, workload: dict, seed: int, phases) -> State:
    # The first import is a gate and nothing else: the layout's rule by its
    # public name, new with the layout. A program that pads every row to the
    # longest (620M slots here, 112 s a fit, 8 minutes a run) has no such
    # name and ends here with an ImportError, before any data.
    from photon_ml_tpu.data.batch import ell_block_bounds  # noqa: F401
    from photon_ml_tpu.game.dataset import csr_to_batch

    state = State()
    state.rows, state.features = int(config["rows"]), int(config["features"])
    state.block = int(config["rows_per_block"])
    state.settings = dict(workload["step"])
    state.data_seed = int(config["data_seed"])
    state.jitter = float(workload["offset_jitter"])
    state.cycle = np.random.default_rng(seed).permutation(
        int(workload["steps_per_cycle"]))
    with phases("data"):
        state.mat, state.y = kddb_rows.make_rows(config, seed)
    state.nonzeros = int(state.mat.nnz)
    with phases("build"):
        state.batch = csr_to_batch(
            state.mat, state.y, np.zeros(state.rows, np.float32),
            np.ones(state.rows, np.float32))
    return state


def describe(state: State) -> list:
    walked = state.batch.walked_slots
    # a block of slots is [K, N] or, past the first, [1, K, n]
    bounds = np.cumsum([ix.shape[-2] for ix, _ in state.batch.blocks])
    return [f"glm_ragged_fit: {state.rows} rows x {state.features} columns, "
            f"{state.nonzeros} non-zeros ({state.nonzeros / state.rows:.2f} "
            f"a row), blocks of slots ending at {bounds.tolist()}: "
            f"{walked} slots walked ({walked / state.nonzeros:.3f}x), "
            f"lambdas {state.settings['lambdas']} alpha "
            f"{state.settings['alpha']}"]


def train(batch, settings: dict):
    """The timed call. Tests break it underneath (see tests/bench_harness)."""
    from photon_ml_tpu.optimize.config import (
        OptimizerType,
        RegularizationContext,
        RegularizationType,
        TaskType,
    )
    from photon_ml_tpu.training import train_glm_grid

    return train_glm_grid(
        batch, TaskType[settings["task"]],
        regularization_weights=list(settings["lambdas"]),
        optimizer_type=OptimizerType[settings["optimizer"]],
        regularization_context=RegularizationContext(
            RegularizationType[settings["regularization"]],
            alpha=float(settings.get("alpha", 0.5))),
        max_iterations=int(settings["max_iterations"]),
        tolerance=float(settings["tolerance"]))


def step(state: State, settings=None) -> dict:
    """One fit from zero; ends in fetched host values (each solve's history
    and its coefficients)."""
    index = state.steps_made
    state.steps_made += 1
    models = train(state.batch._replace(offsets=dense.jitter(state, index)),
                   settings or state.settings)
    results = [m.result for m in models]
    # fetched as they are (float32): the comparison widens them, outside
    # the window
    coefficients = [np.asarray(r.coefficients) for r in results]
    return {
        "index": index,
        "lambdas": [float(m.regularization_weight) for m in models],
        "iterations": [int(r.iterations) for r in results],
        "evaluations": [int(r.evaluations) for r in results],
        "values": [float(r.value) for r in results],
        "grad_norms": [float(r.grad_norm) for r in results],
        "histories": [np.asarray(r.values, np.float64) for r in results],
        "reasons": [r.convergence_reason.name for r in results],
        "nonzeros": [int(np.count_nonzero(w)) for w in coefficients],
        "coefficients": coefficients}


def work(state: State, record: dict) -> dict:
    return work_ragged.ragged_work(state.nonzeros, state.rows,
                                   state.features, record["evaluations"])


def release(state: State) -> None:
    state.batch = None


def penalties(state: State, lam: float) -> tuple:
    """(lambda1, lambda2) of the elastic net at weight ``lam``."""
    alpha = float(state.settings["alpha"])
    return alpha * lam, (1.0 - alpha) * lam


def _data(state: State, index: int):
    """The reference's view of the rows: the flat arrays (made from the
    host's CSR on first use, after the program's planes are released), the
    labels, step ``index``'s offsets, unit weights."""
    import jax.numpy as jnp

    if state.flat is None:
        flat = reference.flat_blocks(state.mat.indptr, state.mat.indices,
                                     state.mat.data, state.block)
        state.flat = tuple(jnp.asarray(a) for a in flat)
    y = jnp.asarray(state.y)
    return (*state.flat, y, dense.jitter(state, index), jnp.ones_like(y))


def control(state: State, index: int = 0) -> dict:
    """The control: the reference put in the program's place, computed in
    bfloat16 (values, coefficients and residuals rounded before every
    product): its own OWL-QN for the step's iteration budget, reporting
    what it computed."""
    data = _data(state, index)
    lambdas = sorted((float(v) for v in state.settings["lambdas"]),
                     reverse=True)
    out = {"index": index, "lambdas": lambdas, "iterations": [],
           "evaluations": [], "values": [], "grad_norms": [],
           "histories": [], "nonzeros": [], "coefficients": []}
    start = np.zeros(state.features)
    for lam in lambdas:
        l1, l2 = penalties(state, lam)
        w, values, gnorm = reference.owlqn(
            lambda w, l2=l2: reference.smooth(*data, w, l2,
                                              low_precision=True),
            l1, start, int(state.settings["max_iterations"]))
        out["iterations"].append(len(values) - 1)
        out["values"].append(values[-1])
        out["grad_norms"].append(gnorm)
        out["histories"].append(np.asarray(values))
        out["nonzeros"].append(int(np.count_nonzero(w)))
        out["coefficients"].append(w)
        start = w
    return out


def fault_state_unchanged(state: State) -> dict:
    """A step that returns its state unchanged: the zero start."""
    out = step(state)
    out["coefficients"] = [np.zeros(state.features) for _ in out["lambdas"]]
    return out


def fault_half_batch(state: State) -> dict:
    """Half of the batch left out, the sum taken over the rest twice: the
    second half of the rows weighs 0 and the first half 2."""
    import jax.numpy as jnp

    whole = state.batch
    half = state.rows // 2
    state.batch = whole._replace(weights=jnp.concatenate([
        jnp.full(half, 2.0, jnp.float32),
        jnp.zeros(state.rows - half, jnp.float32)]))
    try:
        return step(state)
    finally:
        state.batch = whole


@functools.cache
def _drops_last_block_class():
    import jax

    from photon_ml_tpu.data.batch import EllBatch

    @jax.tree_util.register_pytree_node_class
    class DropsLastBlock(EllBatch):
        def _column_sums(self, row_scalars, square):
            whole = EllBatch(*self.tree_flatten()[0], dim=self.dim)
            return whole._replace(tail=self.tail[:-1])._column_sums(
                row_scalars, square)

    return DropsLastBlock


def fault_scatter_drops_a_block(state: State) -> dict:
    """The layout's own fault: the gradient's scatter-add walks one block
    of slots fewer than the margins do, and so leaves out the last block,
    the longest rows' last cells (at the cell's size slots 88 to 127 of the
    0.7% of rows that reach them, 0.4% of the non-zeros); the margins still
    read them."""
    whole = state.batch
    if not whole.tail:
        raise ValueError("the fault needs a layout of several blocks")
    state.batch = _drops_last_block_class()(*whole.tree_flatten()[0],
                                            dim=whole.dim)
    try:
        return step(state)
    finally:
        state.batch = whole


def fault_l1_ignored(state: State) -> dict:
    """The penalty's orthant logic left out: the smooth L-BFGS on the same
    loss with the L2 part alone (lambda2 as the elastic net splits it),
    reported as the elastic-net fit."""
    lambdas = sorted((float(v) for v in state.settings["lambdas"]),
                     reverse=True)
    out = step(state, dict(
        state.settings, regularization="L2",
        lambdas=[penalties(state, lam)[1] for lam in lambdas]))
    out["lambdas"] = lambdas
    return out


FAULTS = {"state_unchanged": fault_state_unchanged,
          "half_batch": fault_half_batch,
          "scatter_drops_a_block": fault_scatter_drops_a_block,
          "l1_ignored": fault_l1_ignored}

CHECKS = ("value_gap", "grad_gap", "step_gap", "trajectory",
          "zero_share_gap")


def verify(state: State, outputs: dict, limits: dict) -> list:
    """The fit the window made against the plain reference, worst lambda
    each. ``value_gap``, ``grad_gap``: ``F`` and the pseudo-gradient norm
    the program reports at its own coefficients against the reference's
    there (the pass and the penalty). ``step_gap``: the reference's ``F``
    there less the reference's after the same number of iterations of its
    own textbook OWL-QN from the same start, over the decrease from that
    start (the solver: under the limit when the program descends as far or
    farther). ``trajectory``: the largest rise between two values the
    solver reports in a row, over the first. ``zero_share_gap``: the share
    of coefficients that are exactly 0.0 in the program's model against the
    textbook's (the orthant logic: a fit that ignores the penalty leaves
    none at zero), absolute."""
    data = _data(state, outputs["index"])
    zero = np.zeros(state.features)
    f0, grad0 = reference.smooth(*data, zero, 0.0)
    gaps = {name: 0.0 for name in CHECKS}
    gaps["step_gap"] = -np.inf
    start, smooth_at_start = zero, (f0, grad0)  # no penalty at 0
    for lam, w, value, gnorm, iterations, history in zip(
            outputs["lambdas"], outputs["coefficients"], outputs["values"],
            outputs["grad_norms"], outputs["iterations"],
            outputs["histories"]):
        w = np.asarray(w, np.float64)
        l1, l2 = penalties(state, lam)
        # the norm every gradient gap is taken over: the pseudo-gradient's
        # at 0, where the smooth gradient is the loss's alone
        g0 = float(np.linalg.norm(reference.pseudo_gradient(zero, grad0,
                                                            l1)))

        def fn(w, l2=l2):
            return reference.smooth(*data, w, l2)

        F_at, _, pg_at = reference.penalised(fn, w, l1)
        at_start = None
        if smooth_at_start is not None:
            f, g = smooth_at_start
            at_start = (f + l1 * float(np.abs(start).sum()), g,
                        reference.pseudo_gradient(start, g, l1))
        w_ref, values_ref, _ = reference.owlqn(fn, l1, start,
                                               int(iterations), at_start)
        gaps["value_gap"] = max(gaps["value_gap"],
                                abs(value - F_at) / abs(F_at))
        gaps["grad_gap"] = max(
            gaps["grad_gap"],
            abs(gnorm - float(np.linalg.norm(pg_at))) / g0)
        decrease = values_ref[0] - values_ref[-1]
        # no iteration reported, nothing to share: as the state unchanged
        gaps["step_gap"] = max(gaps["step_gap"], (
            F_at - values_ref[-1]) / decrease if decrease > 0 else 1.0)
        rises = np.diff(np.asarray(history, np.float64))
        gaps["trajectory"] = max(gaps["trajectory"], float(
            max(rises.max(initial=0.0), 0.0) / abs(history[0])))
        gaps["zero_share_gap"] = max(gaps["zero_share_gap"], abs(
            float(np.mean(w == 0.0)) - float(np.mean(w_ref == 0.0))))
        # the program warm-starts the next lambda from here
        start, smooth_at_start = w, None
    return [(name, float(gaps[name]), float(limits[name]))
            for name in CHECKS]
