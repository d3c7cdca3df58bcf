"""Kind ``glm_grid_fit``: a step is one warm-started lambda-grid fit of a
fixed-effect GLM on a dense batch, through ``train_glm_grid``.

The data is made on the device in one jitted call from the configuration's
``data_seed``. Every step adds small offsets to the margins: L-BFGS ends a
solve an iteration or two sooner or later on rounding alone, so a window of
identical fits would repeat one draw of that, and fresh offsets from every
seed would make the seed change the work (a fit's time spreads by several
percent). So the workload fixes a cycle of ``steps_per_cycle`` offset
vectors, ``--seed`` deals them in another order, and the harness closes the
window on a whole cycle: every window holds the same fits in the same
numbers (PERF.md, Findings). The generator is
``chip_smoke.py:phase_glm``'s recipe (standard-normal X, a planted
coefficient vector, labels drawn through it), changed to run on the device
in row blocks and to give the columns unequal scales.
"""

from __future__ import annotations

from functools import partial

import numpy as np

from benchmark import work as work_fns
from benchmark.reference import glm as reference


class State:
    def __init__(self):
        self.X = self.y = None  # the benchmark's data, on the device
        self.batch = None  # the program's view of it
        self.settings = None
        self.rows = self.cols = 0


def column_scales(cols: int, decades: float, data_seed: int) -> np.ndarray:
    """Column j is scaled by 10**(-decades * u_j), the u_j evenly spaced
    on [0, 1] and dealt to the columns by the data seed."""
    rng = np.random.default_rng([data_seed, 1])
    u = rng.permutation(np.linspace(0.0, 1.0, cols))
    return (10.0 ** (-decades * u)).astype(np.float32)


def planted(cols: int, scales: np.ndarray, data_seed: int) -> np.ndarray:
    """Planted coefficients: every column adds as much to the margin, whose
    variance is 1."""
    rng = np.random.default_rng([data_seed, 2])
    return (rng.normal(size=cols) / (np.sqrt(cols) * scales)).astype(
        np.float32)


def make_data(rows: int, cols: int, rows_per_block: int, data_seed: int,
              decades: float):
    """(X [rows, cols] f32, y [rows] f32) on the default device."""
    import jax
    import jax.numpy as jnp

    if rows % rows_per_block:
        raise ValueError("rows must be a multiple of rows_per_block")
    scales = column_scales(cols, decades, data_seed)
    w_true = planted(cols, scales, data_seed)

    @partial(jax.jit, static_argnames=("rows_per_block", "cols"))
    def make(key, scales, w_true, rows_per_block, cols):
        def block(block_id):
            kx, ku = jax.random.split(jax.random.fold_in(key, block_id))
            Xb = jax.random.normal(
                kx, (rows_per_block, cols), jnp.float32) * scales
            z = jnp.matmul(Xb, w_true,
                           precision=jax.lax.Precision.HIGHEST)
            _, p, _ = reference.logistic_terms(z, jnp.float32(0.0))
            u = jax.random.uniform(ku, (rows_per_block,), jnp.float32)
            return Xb, (u < p).astype(jnp.float32)

        X, y = jax.lax.map(
            block, jnp.arange(rows // rows_per_block, dtype=jnp.int32))
        return X.reshape(-1, cols), y.reshape(-1)

    X, y = make(jax.random.key(data_seed), jnp.asarray(scales),
                jnp.asarray(w_true), rows_per_block, cols)
    return jax.block_until_ready(X), y


def jitter(state: "State", index: int):
    """Step ``index``'s offsets: the cycle's vector that ``--seed`` dealt to
    that place, ``offset_jitter`` times a standard normal."""
    import jax
    import jax.numpy as jnp

    which = int(state.cycle[index % len(state.cycle)])
    key = jax.random.fold_in(jax.random.key(state.data_seed), 1000 + which)
    return jnp.float32(state.jitter) * jax.random.normal(
        key, (state.rows,), jnp.float32)


def build(config: dict, workload: dict, seed: int, phases) -> State:
    from photon_ml_tpu.data.batch import dense_batch

    state = State()
    state.rows, state.cols = int(config["rows"]), int(config["features"])
    state.settings = dict(workload["step"])
    state.block = int(config["rows_per_block"])
    state.steps_made, state.data_seed = 0, int(config["data_seed"])
    state.jitter = float(workload["offset_jitter"])
    state.cycle = np.random.default_rng(seed).permutation(
        int(workload["steps_per_cycle"]))
    with phases("data"):
        state.X, state.y = make_data(
            state.rows, state.cols, state.block, state.data_seed,
            float(config["column_scale_decades"]))
    with phases("build"):
        state.batch = dense_batch(state.X, state.y)
    return state


def describe(state: State) -> list:
    return [f"glm_grid_fit: batch {state.rows} x {state.cols} "
            f"{state.X.dtype}, lambdas {state.settings['lambdas']}"]


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
            RegularizationType[settings["regularization"]]),
        max_iterations=int(settings["max_iterations"]),
        tolerance=float(settings["tolerance"]))


def step(state: State) -> dict:
    """One grid fit from zero; ends in fetched host values (each solve's
    history and the last coefficients)."""
    index = state.steps_made
    state.steps_made += 1
    models = train(state.batch._replace(offsets=jitter(state, index)),
                   state.settings)
    return {
        "index": index,
        "lambdas": [float(m.regularization_weight) for m in models],
        "iterations": [int(m.result.iterations) for m in models],
        "values": [float(m.result.value) for m in models],
        "grad_norms": [float(m.result.grad_norm) for m in models],
        "reasons": [m.result.convergence_reason.name for m in models],
        "coefficients": [np.asarray(m.result.coefficients, np.float64)
                         for m in models]}


def work(state: State, record: dict) -> dict:
    return work_fns.block_work(state.rows, state.cols,
                               state.X.dtype.itemsize, record["iterations"])


def release(state: State) -> None:
    state.batch = None


def reference_fit(state: State, lambdas, offsets,
                  low_precision: bool = False) -> list:
    """The reference's minimiser for each lambda, warm-started down the
    grid: [(lambda, w, value, gradient norm)]."""
    import jax.numpy as jnp

    data = (state.X, state.y, offsets, jnp.ones_like(state.y))
    out, start = [], None
    for lam in lambdas:
        w, _ = reference.newton(*data, lam, start, block=state.block,
                                low_precision=low_precision)
        value, grad = reference.objective(*data, w, lam, block=state.block,
                                          low_precision=low_precision)
        out.append((lam, w, value, float(np.linalg.norm(grad))))
        start = w
    return out


def control(state: State, index: int = 0) -> dict:
    """The control: the reference put in the program's place, computed in
    bfloat16 (X and coefficients rounded before every product)."""
    lambdas = sorted((float(v) for v in state.settings["lambdas"]),
                     reverse=True)
    fits = reference_fit(state, lambdas, jitter(state, index),
                         low_precision=True)
    return {"index": index, "lambdas": lambdas,
            "coefficients": [f[1] for f in fits],
            "values": [f[2] for f in fits],
            "grad_norms": [f[3] for f in fits]}


def fault_state_unchanged(state: State) -> dict:
    """A step that returns its state unchanged: the zero start."""
    out = step(state)
    out["coefficients"] = [np.zeros(state.cols) for _ in out["lambdas"]]
    return out


def fault_half_batch(state: State) -> dict:
    """Half of the batch left out, the mean taken over the rest: the second
    half of the rows weighs 0 and the first half twice."""
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


FAULTS = {"state_unchanged": fault_state_unchanged,
          "half_batch": fault_half_batch}


def verify(state: State, outputs: dict, limits: dict) -> list:
    """The fit the window made against the plain reference: the objective
    and the gradient the program reports at its own coefficients against
    the reference's evaluation there (the kernel), and its coefficients
    against the reference's minimiser (the solver). Worst lambda each."""
    import jax.numpy as jnp

    offsets = jitter(state, outputs["index"])
    data = (state.X, state.y, offsets, jnp.ones_like(state.y))
    _, grad0 = reference.objective(
        *data, np.zeros(state.cols), 0.0, block=state.block)
    g0 = float(np.linalg.norm(grad0))
    fits = reference_fit(state, outputs["lambdas"], offsets)
    gaps = {"value_gap": 0.0, "grad_gap": 0.0, "coef_gap": 0.0}
    for (lam, w_ref, _, _), w, value, gnorm in zip(
            fits, outputs["coefficients"], outputs["values"],
            outputs["grad_norms"]):
        f_at, g_at = reference.objective(*data, w, lam, block=state.block)
        gaps["value_gap"] = max(gaps["value_gap"],
                                abs(value - f_at) / abs(f_at))
        gaps["grad_gap"] = max(
            gaps["grad_gap"], abs(gnorm - float(np.linalg.norm(g_at))) / g0)
        gaps["coef_gap"] = max(
            gaps["coef_gap"],
            float(np.linalg.norm(w - w_ref) / np.linalg.norm(w_ref)))
    return [(name, float(gaps[name]), float(limits[name]))
            for name in ("value_gap", "grad_gap", "coef_gap")]
