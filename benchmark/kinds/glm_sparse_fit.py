"""Kind ``glm_sparse_fit``: a step is one fit of a fixed-effect logistic GLM
on a wide-sparse batch (a million hashed columns, a few dozen non-zeros a
row), through ``train_glm_grid`` on the program's ``EllBatch``.

The rows are made on the device in row blocks from the configuration's
``data_seed`` (``generators/criteo_rows.py``); ``--seed`` deals the blocks
in another order, so every seed fits the same rows and does the same work.
As in ``glm_grid_fit``, every step adds one of a fixed cycle of small offset
vectors to the margins, in the order the seed deals, and the window closes
on a whole cycle: every window holds the same fits (here two: the harness
starts its profiler at a window's second step, and one fit outlasts
``--seconds``, so a window of one step would leave a traced run no trace).
The planes come out in the layout the program holds them in, and the
program's batch is a view of them (``ell_batch`` copies nothing): the data
is on the device once, for the program and for the reference.

No minimiser is affordable at a million columns, so ``verify`` judges the
solver without one: the objective and the gradient norm the program reports
at its own coefficients against the reference's evaluation there; the
reference's objective there against the reference's objective after as many
iterations of its own textbook L-BFGS; and the reported values, which may
never rise.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import work_sparse
from benchmark.generators import criteo_rows
from benchmark.kinds import glm_grid_fit as dense
from benchmark.reference import glm_sparse as reference

train = dense.train  # the timed call; tests break it underneath


class State:
    def __init__(self):
        self.ids = self.vals = self.y = None  # the benchmark's planes
        self.batch = None  # the program's view of them
        self.settings = None
        self.rows = self.slots = self.features = self.block = 0
        # what glm_grid_fit.jitter reads
        self.steps_made = self.data_seed = 0
        self.jitter, self.cycle = 0.0, None


def build(config: dict, workload: dict, seed: int, phases) -> State:
    from photon_ml_tpu.data.batch import ell_batch

    state = State()
    state.rows, state.features = int(config["rows"]), int(config["features"])
    state.slots = int(config["nonzeros_per_row"])
    state.block = int(config["rows_per_block"])
    state.settings = dict(workload["step"])
    state.data_seed = int(config["data_seed"])
    state.jitter = float(workload["offset_jitter"])
    state.cycle = np.random.default_rng(seed).permutation(
        int(workload["steps_per_cycle"]))
    with phases("data"):
        state.ids, state.vals, state.y = criteo_rows.make_rows(config, seed)
    with phases("build"):
        state.batch = ell_batch(state.ids, state.vals, state.y,
                                dim=state.features)
    return state


def describe(state: State) -> list:
    return [f"glm_sparse_fit: {state.rows} rows x {state.features} columns, "
            f"{state.slots} non-zeros a row, planes {tuple(state.ids.shape)} "
            f"{state.ids.dtype}/{state.vals.dtype}, lambdas "
            f"{state.settings['lambdas']}"]


def step(state: State) -> dict:
    """One fit from zero; ends in fetched host values (each solve's history
    and its coefficients)."""
    index = state.steps_made
    state.steps_made += 1
    models = train(state.batch._replace(offsets=dense.jitter(state, index)),
                   state.settings)
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
        "coefficients": [np.asarray(r.coefficients, np.float64)
                         for r in results]}


def work(state: State, record: dict) -> dict:
    return work_sparse.sparse_work(state.rows, state.slots, state.features,
                                   record["evaluations"])


def release(state: State) -> None:
    state.batch = None


def _data(state: State, index: int):
    import jax.numpy as jnp

    return (state.ids, state.vals, state.y, dense.jitter(state, index),
            jnp.ones_like(state.y))


def control(state: State, index: int = 0) -> dict:
    """The control: the reference put in the program's place, computed in
    bfloat16 (values, coefficients and residuals rounded before every
    product): its own L-BFGS for the step's iteration budget, reporting what
    it computed."""
    data = _data(state, index)
    lambdas = sorted((float(v) for v in state.settings["lambdas"]),
                     reverse=True)
    out = {"index": index, "lambdas": lambdas, "iterations": [],
           "evaluations": [], "values": [], "grad_norms": [],
           "histories": [], "coefficients": []}
    start = np.zeros(state.features)
    for lam in lambdas:
        w, values, gnorm = reference.lbfgs(
            lambda w, lam=lam: reference.objective(
                *data, w, lam, block=state.block, low_precision=True),
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


@functools.cache
def _drops_last_slot_class():
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import EllBatch

    @jax.tree_util.register_pytree_node_class
    class DropsLastSlot(EllBatch):
        def _replace(self, **kw):  # the step's offsets keep the fault
            plain = EllBatch._replace(self, **kw)
            return DropsLastSlot(*plain.tree_flatten()[0], dim=plain.dim)

        def weighted_feature_sum(self, row_scalars):
            kept = jnp.arange(self.values.shape[0]) < self.values.shape[0] - 1
            return EllBatch.weighted_feature_sum(
                self._replace(values=self.values * kept[:, None]),
                row_scalars)

    return DropsLastSlot


def fault_scatter_drops_a_slot(state: State) -> dict:
    """The mechanism's own fault: the gradient's scatter-add leaves out the
    last slot (38) of every row; the margins still read it."""
    whole = state.batch
    state.batch = _drops_last_slot_class()(
        whole.indices, whole.values, whole.labels, whole.offsets,
        whole.weights, dim=whole.dim)
    try:
        return step(state)
    finally:
        state.batch = whole


FAULTS = {"state_unchanged": fault_state_unchanged,
          "half_batch": fault_half_batch,
          "scatter_drops_a_slot": fault_scatter_drops_a_slot}


def verify(state: State, outputs: dict, limits: dict) -> list:
    """The fit the window made against the plain reference, worst lambda
    each. ``value_gap``, ``grad_gap``: the objective and the gradient norm
    the program reports at its own coefficients against the reference's
    there (the pass). ``step_gap``: the reference's objective there less the
    reference's after the same number of iterations of its own textbook
    L-BFGS from the same start, over the decrease from that start (the
    solver: under the limit when the program descends as far or farther).
    ``trajectory``: the largest rise between two values the solver reports
    in a row, over the first."""
    data = _data(state, outputs["index"])
    at_zero = reference.objective(*data, np.zeros(state.features), 0.0,
                                  block=state.block)
    g0 = float(np.linalg.norm(at_zero[1]))
    gaps = {"value_gap": 0.0, "grad_gap": 0.0, "step_gap": -np.inf,
            "trajectory": 0.0}
    start, at_start = np.zeros(state.features), at_zero  # no penalty at 0
    for lam, w, value, gnorm, iterations, history in zip(
            outputs["lambdas"], outputs["coefficients"], outputs["values"],
            outputs["grad_norms"], outputs["iterations"],
            outputs["histories"]):
        def fn(w, lam=lam):
            return reference.objective(*data, w, lam, block=state.block)

        f_at, g_at = fn(w)
        _, values_ref, _ = reference.lbfgs(fn, start, int(iterations),
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
            for name in ("value_gap", "grad_gap", "step_gap", "trajectory")]
