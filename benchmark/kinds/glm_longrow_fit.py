"""Kind ``glm_longrow_fit``: a step is one fit of a fixed-effect linear SVM
(Rennie's smoothed hinge, L-BFGS + L2) on a wide-sparse matrix whose rows
run to thousands of non-zeros and differ in length by a factor of a
hundred, through ``csr_to_batch`` and ``train_glm_grid``: the program's
normal path.

The rows are made on the host in row blocks from the configuration's
``data_seed`` (``generators/webspam_rows.py``) and handed to the program's
own builder as one CSR matrix: the layout (the blocks of slots, the tiles
its walks take) and the packing are the program's, timed as
``block_build_s``. ``--seed`` deals the row blocks in another order, so
every seed fits the same rows and does the same work. As in
``glm_ragged_fit``, every step adds one of a cycle of two small offset
vectors to the margins, in the order the seed deals, and the window closes
on a whole cycle.

No minimiser is affordable at 16.6 million columns, so ``verify`` judges the
solver as ``glm_sparse_fit`` does: ``F`` and the gradient norm the program
reports at its own coefficients against the reference's evaluation there;
the reference's ``F`` there against the reference's after as many
iterations of its own textbook L-BFGS; and the reported values, which may
never rise.
"""

from __future__ import annotations

import numpy as np

from benchmark.generators import webspam_rows
from benchmark.kinds import glm_grid_fit as dense
from benchmark.kinds import glm_ragged_fit as ragged
from benchmark.reference import glm_sparse
from benchmark.reference import glm_svm as reference

train = dense.train  # the timed call; tests break it underneath


# the ragged kind's state, work, release and reference view fit this kind
# as they are: a CSR matrix on the host, its layout on the device
State = ragged.State
work = ragged.work
release = ragged.release
_data = ragged._data


def build(config: dict, workload: dict, seed: int, phases) -> State:
    # The first import is a gate and nothing else: the walk's constant by
    # its public name, new with the tiled walk. A program that walks the
    # deepest blocks one slot a step has no such name and ends here with
    # an ImportError, before any data.
    from photon_ml_tpu.data.batch import ELL_TILE_ROWS  # noqa: F401
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
        state.mat, state.y = webspam_rows.make_rows(config, seed)
    state.nonzeros = int(state.mat.nnz)
    with phases("build"):
        state.batch = csr_to_batch(
            state.mat, state.y, np.zeros(state.rows, np.float32),
            np.ones(state.rows, np.float32))
    return state


def describe(state: State) -> list:
    from photon_ml_tpu.data.batch import ell_walk_steps

    walked = state.batch.walked_slots
    # a block of slots is [K, N] or, past the first, [1, K, n]
    shapes = [(ix.shape[-2], ix.shape[-1]) for ix, _ in state.batch.blocks]
    bounds = np.cumsum([k for k, _ in shapes])
    steps = [ell_walk_steps(k, n) for k, n in shapes]
    return [f"glm_longrow_fit: {state.rows} rows x {state.features} columns, "
            f"{state.nonzeros} non-zeros ({state.nonzeros / state.rows:.1f} "
            f"a row), blocks of slots ending at {bounds.tolist()} over "
            f"{[n for _, n in shapes]} rows: {walked} slots walked "
            f"({walked / state.nonzeros:.3f}x) in {sum(steps)} loop steps a "
            f"walk ({steps} by block), lambdas {state.settings['lambdas']}"]


def step(state: State, settings=None) -> dict:
    """One fit from zero; ends in fetched host values (each solve's history
    and its coefficients)."""
    index = state.steps_made
    state.steps_made += 1
    models = train(state.batch._replace(offsets=dense.jitter(state, index)),
                   settings or state.settings)
    results = [m.result for m in models]
    return {
        "index": index,
        "lambdas": [float(m.regularization_weight) for m in models],
        "iterations": [int(r.iterations) for r in results],
        "evaluations": [int(r.evaluations) for r in results],
        "values": [float(r.value) for r in results],
        "grad_norms": [float(r.grad_norm) for r in results],
        "histories": [np.asarray(r.values, np.float64) for r in results],
        "reasons": [r.convergence_reason.name for r in results],
        # fetched as they are (float32): the comparison widens them
        "coefficients": [np.asarray(r.coefficients) for r in results]}


def control(state: State, index: int = 0) -> dict:
    """The control: the reference put in the program's place, computed in
    bfloat16 (values, coefficients and residuals rounded before every
    product): its own L-BFGS for the step's iteration budget, reporting
    what it computed."""
    data = _data(state, index)
    lambdas = sorted((float(v) for v in state.settings["lambdas"]),
                     reverse=True)
    out = {"index": index, "lambdas": lambdas, "iterations": [],
           "evaluations": [], "values": [], "grad_norms": [],
           "histories": [], "coefficients": []}
    start = np.zeros(state.features)
    for lam in lambdas:
        w, values, gnorm = glm_sparse.lbfgs(
            lambda w, lam=lam: reference.objective(*data, w, lam,
                                                   low_precision=True),
            start, int(state.settings["max_iterations"]))
        out["iterations"].append(len(values) - 1)
        out["values"].append(values[-1])
        out["grad_norms"].append(gnorm)
        out["histories"].append(np.asarray(values))
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


def fault_scatter_drops_a_block(state: State) -> dict:
    """The layout's own fault: the gradient's scatter-add leaves out the
    last block of slots, the longest rows' last cells, which the tiled walk
    takes many slots a step; the margins still read them."""
    whole = state.batch
    if not whole.tail:
        raise ValueError("the fault needs a layout of several blocks")
    state.batch = ragged._drops_last_block_class()(
        *whole.tree_flatten()[0], dim=whole.dim)
    try:
        return step(state)
    finally:
        state.batch = whole


def fault_logistic_loss(state: State) -> dict:
    """The loss's own fault: the logistic loss fitted in the smoothed
    hinge's place, reported as the SVM's fit."""
    return step(state, dict(state.settings, task="LOGISTIC_REGRESSION"))


FAULTS = {"state_unchanged": fault_state_unchanged,
          "half_batch": fault_half_batch,
          "scatter_drops_a_block": fault_scatter_drops_a_block,
          "logistic_loss": fault_logistic_loss}

CHECKS = ("value_gap", "grad_gap", "step_gap", "trajectory")


def verify(state: State, outputs: dict, limits: dict) -> list:
    """The fit the window made against the plain reference, worst lambda
    each. ``value_gap``, ``grad_gap``: ``F`` and the gradient norm the
    program reports at its own coefficients against the reference's there
    (the pass and the loss), the latter over the gradient norm at 0.
    ``step_gap``: the reference's ``F`` there less the reference's after
    the same number of iterations of its own textbook L-BFGS from the same
    start, over the decrease from that start (the solver: under the limit
    when the program descends as far or farther). ``trajectory``: the
    largest rise between two values the solver reports in a row, over the
    first."""
    data = _data(state, outputs["index"])
    at_zero = reference.objective(*data, np.zeros(state.features), 0.0)
    g0 = float(np.linalg.norm(at_zero[1]))
    gaps = {name: 0.0 for name in CHECKS}
    gaps["step_gap"] = -np.inf
    start, at_start = np.zeros(state.features), at_zero  # no penalty at 0
    for lam, w, value, gnorm, iterations, history in zip(
            outputs["lambdas"], outputs["coefficients"], outputs["values"],
            outputs["grad_norms"], outputs["iterations"],
            outputs["histories"]):
        def fn(w, lam=lam):
            return reference.objective(*data, w, lam)

        f_at, g_at = fn(np.asarray(w, np.float64))
        _, values_ref, _ = glm_sparse.lbfgs(fn, start, int(iterations),
                                            at_start)
        gaps["value_gap"] = max(gaps["value_gap"],
                                abs(value - f_at) / abs(f_at))
        gaps["grad_gap"] = max(
            gaps["grad_gap"], abs(gnorm - float(np.linalg.norm(g_at))) / g0)
        decrease = values_ref[0] - values_ref[-1]
        # no iteration reported, nothing to share: as the state unchanged
        gaps["step_gap"] = max(gaps["step_gap"], (
            f_at - values_ref[-1]) / decrease if decrease > 0 else 1.0)
        rises = np.diff(np.asarray(history, np.float64))
        gaps["trajectory"] = max(gaps["trajectory"], float(
            max(rises.max(initial=0.0), 0.0) / abs(history[0])))
        # the program warm-starts the next lambda from here
        start, at_start = w, None
    return [(name, float(gaps[name]), float(limits[name]))
            for name in CHECKS]
