"""Kind ``cd_train``: a step is one GAME coordinate-descent training from
zero state (``run_coordinate_descent``) over a fixed-effect coordinate and a
per-user random-effect coordinate, through the program's own block build.

The in-memory path is ``bench.py:bench_glmix``'s (copied; changed: the
generator's laws and chunks, an intercept column, settings from the
workload's file): a ``GameDataset`` from host arrays, then
``build_fixed_effect_dataset`` and ``build_random_effect_dataset``. It
enters below Avro ingest, which is host-only work and is not measured here.
"""

from __future__ import annotations

import numpy as np

from benchmark import work as work_fns
from benchmark.generators import glmix_rows
from benchmark.reference import glmix as reference


class State:
    def __init__(self):
        self.rows = None  # the benchmark's data, on the host
        self.coords = self.vectors = None  # the program's objects
        self.config = self.settings = None
        self.shapes = {}


def _l2_config(lam: float, iterations: int, tolerance: float):
    from photon_ml_tpu.optimize.config import (
        GLMOptimizationConfiguration,
        OptimizerType,
        RegularizationContext,
        RegularizationType,
    )

    return GLMOptimizationConfiguration(
        max_iterations=iterations, tolerance=tolerance,
        regularization_weight=lam, optimizer_type=OptimizerType.LBFGS,
        regularization_context=RegularizationContext(RegularizationType.L2))


def build_blocks(rows, config: dict):
    """The program's block build: host rows -> (fixed-effect dataset,
    random-effect dataset)."""
    import scipy.sparse as sp

    from photon_ml_tpu.game.dataset import (
        GameDataset,
        RandomEffectDataConfiguration,
        build_fixed_effect_dataset,
        build_random_effect_dataset,
    )

    n = len(rows.y)
    one_hot = sp.csr_matrix(
        (np.ones(n, np.float32), rows.movie, np.arange(n + 1)),
        shape=(n, int(config["movies"])))
    data = GameDataset(responses=rows.y, feature_shards={
        "global": sp.csr_matrix(rows.X), "per_user": one_hot})
    data.encode_ids("userId", rows.user)
    fixed = build_fixed_effect_dataset(data, "global")
    user = build_random_effect_dataset(
        data, RandomEffectDataConfiguration(
            random_effect_type="userId", feature_shard_id="per_user",
            num_partitions=1,
            num_active_data_points_upper_bound=int(config["active_rows_cap"]),
            num_features_to_keep_upper_bound=int(config["features_cap"])),
        seed=int(config["active_rows_sample_seed"]),
        num_buckets=int(config["buckets"]))
    return data, fixed, user


def build(config: dict, workload: dict, seed: int, phases) -> State:
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.coordinate import (
        FixedEffectCoordinate,
        RandomEffectCoordinate,
    )
    from photon_ml_tpu.game.random_effect import (
        RandomEffectOptimizationProblem,
    )
    from photon_ml_tpu.optimize.config import TaskType
    from photon_ml_tpu.optimize.problem import GLMOptimizationProblem

    state = State()
    state.config, state.settings = config, dict(workload["step"])
    s = state.settings
    task = TaskType[s["task"]]
    with phases("data"):
        state.rows = glmix_rows.make_rows(config, seed)
    with phases("build"):
        data, fixed, user = build_blocks(state.rows, config)
        state.vectors = tuple(
            jnp.asarray(v, jnp.float32)
            for v in (data.responses, data.weights, data.offsets))
        jax.block_until_ready((fixed.batch.X, state.vectors))
    state.coords = {
        "fixed": FixedEffectCoordinate(
            dataset=fixed, problem=GLMOptimizationProblem(
                config=_l2_config(float(s["fixed"]["l2"]),
                                  int(s["fixed"]["max_iterations"]),
                                  float(s["tolerance"])), task=task)),
        "per-user": RandomEffectCoordinate(
            dataset=user, problem=RandomEffectOptimizationProblem(
                config=_l2_config(float(s["per_user"]["l2"]),
                                  int(s["per_user"]["max_iterations"]),
                                  float(s["tolerance"])), task=task)),
    }
    state.task = task
    buckets = user.buckets if user.buckets is not None else [user]
    state.shapes = {
        "fixed": [int(v) for v in fixed.batch.X.shape],
        "itemsize": int(fixed.batch.X.dtype.itemsize),
        "buckets": [[int(v) for v in b.X.shape] for b in buckets],
        "entity_codes": np.asarray(user.entity_codes),
        "passive_rows": int(user.num_passive)}
    counts = np.bincount(state.rows.user)
    state.active = np.minimum(counts, int(config["active_rows_cap"]))
    return state


def describe(state: State) -> list:
    sh = state.shapes
    capped = int(np.sum(
        state.active >= int(state.config["active_rows_cap"])))
    return [f"cd_train: fixed-effect block {sh['fixed']}, per-user buckets "
            f"[E, N, D] {sh['buckets']}, passive rows {sh['passive_rows']}, "
            f"users {len(sh['entity_codes'])}, users at the cap {capped}"]


def train(coords: dict, sweeps: int, task, vectors):
    """The timed call. Tests break it underneath (see tests/bench_harness)."""
    from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent

    labels, weights, offsets = vectors
    return run_coordinate_descent(coords, num_iterations=sweeps, task=task,
                                  labels=labels, weights=weights,
                                  offsets=offsets)


def step(state: State) -> dict:
    """One training from zero; ends in the fetched model and histories."""
    result = train(state.coords, int(state.settings["sweeps"]), state.task,
                   state.vectors)
    user_model = result.model.get("per-user")
    fixed_iterations, user_iterations, first_fixed = [], [], None
    for st in result.states:
        tracker = st.tracker.materialize()
        if st.coordinate_id == "fixed":
            fixed_iterations.append(int(tracker.result.iterations))
            if first_fixed is None:  # solved against no user scores yet
                first_fixed = (
                    np.asarray(tracker.result.coefficients, np.float64),
                    float(tracker.result.value),
                    float(tracker.result.grad_norm))
        else:
            user_iterations.append(np.asarray(tracker.iterations))
    return {
        "objectives": [float(st.objective) for st in result.states],
        "first_fixed": first_fixed,
        "fixed_iterations": fixed_iterations,
        "user_iterations": user_iterations,
        "iterations": fixed_iterations + [int(u.max())
                                          for u in user_iterations],
        "w_fixed": np.asarray(
            result.model.get("fixed").coefficients.means, np.float64),
        "user_coef": np.asarray(user_model.coefficients_projected),
        "user_codes": np.asarray(user_model.entity_codes),
        "user_columns": np.asarray(user_model.projectors.raw_indices),
        "raw_dim": int(user_model.projectors.raw_dim)}


def work(state: State, record: dict) -> dict:
    """The fixed-effect block once per reported iteration (+ the start),
    and every user's own dense block (its active rows by as many features,
    which is the most its one-hot rows can touch) once per iteration that
    user's solver reports."""
    rows, cols = state.shapes["fixed"]
    itemsize = state.shapes["itemsize"]
    parts = [work_fns.block_work(rows, cols, itemsize,
                                 record["fixed_iterations"])]
    active = state.active[state.shapes["entity_codes"]].astype(np.int64)
    cap = int(state.config["features_cap"])
    area = active * np.minimum(active, cap)
    for iterations in record["user_iterations"]:
        passes = np.asarray(iterations, np.int64) + 1
        cells = int(np.sum(passes * area))
        parts.append({"flops": 4 * cells, "bytes": itemsize * cells})
    return work_fns.add_work(*parts)


def release(state: State) -> None:
    state.coords = state.vectors = None


def pair_coefficients(out: dict, movies: int):
    """The program's per-user coefficients as (sorted pair keys, values)."""
    valid = out["user_columns"] < out["raw_dim"]
    users = np.broadcast_to(out["user_codes"][:, None].astype(np.int64),
                            valid.shape)
    keys = users[valid] * movies + out["user_columns"][valid]
    values = np.asarray(out["user_coef"], np.float64)[valid]
    order = np.argsort(keys)
    return keys[order], values[order]


def reference_fit(state: State, low_precision: bool = False, **planted):
    c, s = state.config, state.settings
    return reference.fit(
        state.rows, int(c["movies"]), int(c["active_rows_cap"]),
        int(c["active_rows_sample_seed"]), int(s["sweeps"]),
        float(s["fixed"]["l2"]), float(s["per_user"]["l2"]),
        block=int(c["reference_rows_per_block"]),
        low_precision=low_precision, **planted)


def as_outputs(fit, movies: int) -> dict:
    """A reference fit in the shape of a step's outputs, to stand in the
    program's place (the control, and the faults planted in it)."""
    users, columns = np.divmod(fit.pair_key, movies)
    return {"objectives": list(fit.objectives), "w_fixed": fit.w_fixed,
            "first_fixed": fit.first_fixed,
            "user_coef": fit.pair_coef[:, None],
            "user_codes": users, "user_columns": columns[:, None],
            "raw_dim": movies}


def control(state: State) -> dict:
    """The control: the reference in the program's place, in bfloat16."""
    return as_outputs(reference_fit(state, low_precision=True),
                      int(state.config["movies"]))


def fault_state_unchanged(state: State) -> dict:
    """A training that returns its zero state: the objective stays at the
    start's, the coefficients at 0."""
    n = len(state.rows.y)
    updates = 2 * int(state.settings["sweeps"])
    zero = np.zeros(state.rows.X.shape[1])
    return {"objectives": [n * float(np.log(2.0))] * updates,
            "w_fixed": zero, "first_fixed": (zero, n * float(np.log(2.0)),
                                            0.0),
            "user_coef": np.zeros((1, 1)), "user_codes": np.zeros(1, int),
            "user_columns": np.zeros((1, 1), int),
            "raw_dim": int(state.config["movies"])}


def fault_half_batch(state: State) -> dict:
    """Half of the rows left out of both solves, the rest weighed twice."""
    n = len(state.rows.y)
    weight = np.where(np.arange(n) < n // 2, 2.0, 0.0)
    return as_outputs(reference_fit(state, row_weight=weight),
                      int(state.config["movies"]))


def fault_no_exchange(state: State) -> dict:
    """The exchange of scores between the coordinates left out: each solve
    sees none of the other's scores."""
    return as_outputs(reference_fit(state, exchange=False),
                      int(state.config["movies"]))


FAULTS = {"state_unchanged": fault_state_unchanged,
          "half_batch": fault_half_batch,
          "no_exchange": fault_no_exchange}


def compare(ref, out: dict, movies: int) -> dict:
    """The gaps between a training's outputs and the reference's."""
    objective_gap = max(
        abs(got - want) / abs(want)
        for got, want in zip(out["objectives"], ref.objectives))
    fixed_gap = float(np.linalg.norm(out["w_fixed"] - ref.w_fixed)
                      / np.linalg.norm(ref.w_fixed))
    keys, values = pair_coefficients(out, movies)
    # a pair only one side has counts with 0 on the other
    all_keys = np.union1d(keys, ref.pair_key)
    mine = np.zeros(len(all_keys))
    mine[np.searchsorted(all_keys, keys)] = values
    theirs = np.zeros(len(all_keys))
    theirs[np.searchsorted(all_keys, ref.pair_key)] = ref.pair_coef
    # users over the cap train on a sample of their rows, weighed up: the
    # only users whose sampling and weights matter, compared apart as well
    capped = np.zeros(len(all_keys), bool)
    capped[np.searchsorted(all_keys, ref.pair_key)] = ref.pair_weight > 1.0
    norm = float(np.linalg.norm(theirs))
    _, _, grad_norm = out["first_fixed"]
    _, grad_at, grad_zero = ref.probe
    return {
        "fixed_grad_gap": abs(grad_norm - grad_at) / grad_zero,
        "objective_gap": float(objective_gap),
        "fixed_coef_gap": fixed_gap,
        "user_coef_gap": float(np.linalg.norm(mine - theirs) / norm),
        "user_norm_gap": abs(float(np.linalg.norm(mine)) - norm) / norm,
        "capped_coef_gap": float(
            np.linalg.norm((mine - theirs)[capped])
            / np.linalg.norm(theirs[capped])) if capped.any() else 0.0}


def verify(state: State, outputs: dict, limits: dict) -> list:
    """A training the window made against exact coordinate descent: the
    objective after every update (block build, both solves and the score
    exchange between them), both coordinates' final coefficients, and the
    gradient the first fixed-effect solve reports at its own coefficients
    against the reference's evaluation there (the kernel)."""
    gaps = compare(
        reference_fit(state, probe_fixed=outputs["first_fixed"][0]),
        outputs, int(state.config["movies"]))
    return [(name, gaps[name], float(limits[name])) for name in limits]
