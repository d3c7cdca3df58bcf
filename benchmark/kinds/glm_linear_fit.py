"""Kind ``glm_linear_fit``: a step is one fit of a fixed-effect linear
(squared-loss) GLM on the dense configuration's own batch, through
``train_glm_grid`` with TRON: every iteration's conjugate-gradient steps are
Hessian-vector products in plain XLA, which bypass the fused value+gradient
kernel that the L-BFGS cell of the same configuration times.

The data, the cycle of offset vectors that ``--seed`` deals and the timed
call are ``glm_grid_fit``'s. The step's record also carries the evaluations
and the Hessian-vector products the solver counted, which ``work`` credits.
``verify`` is this kind's own: squared loss has a closed-form minimiser
(``reference/glm_linear.py``).
"""

from __future__ import annotations

import numpy as np

from benchmark import work_sparse
from benchmark.kinds import glm_grid_fit as dense
from benchmark.reference import glm_linear as reference

build = dense.build
release = dense.release
train = dense.train  # the timed call; tests break it underneath


def describe(state) -> list:
    return [f"glm_linear_fit: batch {state.rows} x {state.cols} "
            f"{state.X.dtype}, {state.settings['optimizer']} "
            f"{state.settings['task']}, lambdas {state.settings['lambdas']}"]


def step(state) -> dict:
    """One fit from zero; ends in fetched host values (each solve's history
    and the coefficients)."""
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
        "hvps": [int(r.hvps) for r in results],
        "values": [float(r.value) for r in results],
        "grad_norms": [float(r.grad_norm) for r in results],
        "reasons": [r.convergence_reason.name for r in results],
        "coefficients": [np.asarray(r.coefficients, np.float64)
                         for r in results]}


def work(state, record: dict) -> dict:
    return work_sparse.dense_work(
        state.rows, state.cols, state.X.dtype.itemsize,
        record["evaluations"], record["hvps"])


def _data(state, index: int):
    import jax.numpy as jnp

    return (state.X, state.y, dense.jitter(state, index),
            jnp.ones_like(state.y))


def control(state, index: int = 0) -> dict:
    """The control: the reference put in the program's place, computed in
    bfloat16 (X, coefficients and residuals rounded before every product)."""
    data = _data(state, index)
    lambdas = sorted((float(v) for v in state.settings["lambdas"]),
                     reverse=True)
    out = {"index": index, "lambdas": lambdas, "coefficients": [],
           "values": [], "grad_norms": []}
    for lam in lambdas:
        w = reference.minimiser(*data, lam, block=state.block,
                                low_precision=True)
        value, grad = reference.objective(*data, w, lam, block=state.block,
                                          low_precision=True)
        out["coefficients"].append(w)
        out["values"].append(value)
        out["grad_norms"].append(float(np.linalg.norm(grad)))
    return out


# the faults are planted under the timed call, whatever the record carries
FAULTS = dense.FAULTS


def verify(state, outputs: dict, limits: dict) -> list:
    """The fit the window made against the plain reference, worst lambda
    each: the objective and the gradient norm the program reports at its own
    coefficients against the reference's evaluation there (the passes), and
    its coefficients against the closed form (the solver)."""
    data = _data(state, outputs["index"])
    _, grad0 = reference.objective(*data, np.zeros(state.cols), 0.0,
                                   block=state.block)
    g0 = float(np.linalg.norm(grad0))
    gaps = {"value_gap": 0.0, "grad_gap": 0.0, "coef_gap": 0.0}
    for lam, w, value, gnorm in zip(
            outputs["lambdas"], outputs["coefficients"], outputs["values"],
            outputs["grad_norms"]):
        w_ref = reference.minimiser(*data, lam, block=state.block)
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
