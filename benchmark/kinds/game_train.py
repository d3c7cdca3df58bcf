"""Kind ``game_train``: a step is one GAME coordinate-descent sweep from zero
state (``run_coordinate_descent``) over the updating sequence fixed effect
-> per-user random effect -> per-item random effect -> factored random
effect (``mf``: per-user latent coefficients and a shared projection over
the movies, on the per-user coordinate's own dataset), through the
program's own block build.

As ``cd_train``: a ``GameDataset`` from host arrays, then
``build_fixed_effect_dataset`` and ``build_random_effect_dataset`` (twice:
by user over one-hot movies, by movie over one-hot users). It enters below
Avro ingest. The factored coordinate starts from the projection the
benchmark draws (``generators/game_rows.starting_projection``), handed in
through ``initial_states``, so that the plain reference starts from the
same one.
"""

from __future__ import annotations

import numpy as np

from benchmark import work as work_fns
from benchmark import work_game, work_sparse
from benchmark.generators import game_rows
from benchmark.kinds.cd_train import _l2_config
from benchmark.reference import game as reference

SEQUENCE = ("fixed", "per-user", "per-item", "mf")


class State:
    def __init__(self):
        self.rows = None  # the benchmark's data, on the host
        self.coords = self.vectors = self.initial = None  # the program's
        self.config = self.settings = None
        self.B0 = None
        self.shapes = {}


def build_blocks(rows, config: dict):
    """The program's block build: host rows -> (data, fixed-effect dataset,
    per-user dataset, per-item dataset)."""
    import scipy.sparse as sp

    from photon_ml_tpu.game.dataset import (
        GameDataset,
        RandomEffectDataConfiguration,
        build_fixed_effect_dataset,
        build_random_effect_dataset,
    )

    n = len(rows.y)
    ones, at = np.ones(n, np.float32), np.arange(n + 1)
    data = GameDataset(responses=rows.y, feature_shards={
        "global": sp.csr_matrix(rows.X),
        "per_user": sp.csr_matrix((ones, rows.movie_feature, at),
                                  shape=(n, int(config["movies"]))),
        "per_item": sp.csr_matrix((ones, rows.user_feature, at),
                                  shape=(n, int(config["users"])))})
    data.encode_ids("userId", rows.user)
    data.encode_ids("movieId", rows.movie)
    fixed = build_fixed_effect_dataset(data, "global")

    def side(id_type, shard):
        return build_random_effect_dataset(
            data, RandomEffectDataConfiguration(
                random_effect_type=id_type, feature_shard_id=shard,
                num_partitions=1,
                num_active_data_points_upper_bound=int(
                    config["active_rows_cap"]),
                num_features_to_keep_upper_bound=int(
                    config["features_cap"])),
            seed=int(config["active_rows_sample_seed"]),
            num_buckets=int(config["buckets"]))

    return data, fixed, side("userId", "per_user"), side("movieId",
                                                         "per_item")


def _side_shapes(dataset, vocab) -> dict:
    buckets = dataset.buckets if dataset.buckets is not None else [dataset]
    return {"buckets": [[int(v) for v in b.X.shape] for b in buckets],
            "ids": np.asarray(vocab)[np.asarray(dataset.entity_codes)],
            "columns": np.asarray(dataset.projectors.raw_indices),
            "raw_dim": int(dataset.projectors.raw_dim),
            "passive_rows": int(dataset.num_passive)}


def build(config: dict, workload: dict, seed: int, phases) -> State:
    import jax
    import jax.numpy as jnp

    # the parent of the PR that brought this kind ends here, at once: its
    # factored coordinate refuses these blocks only after minutes of data
    # and block build
    from photon_ml_tpu.data.batch import ProjectionRefitBatch  # noqa: F401
    from photon_ml_tpu.game.coordinate import (
        FactoredRandomEffectCoordinate,
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
    tolerance = float(s["tolerance"])

    def l2_config(part):
        return _l2_config(float(part["l2"]), int(part["max_iterations"]),
                          tolerance)

    def entity_problem(part):
        return RandomEffectOptimizationProblem(config=l2_config(part),
                                               task=task)

    with phases("data"):
        state.rows = game_rows.make_rows(config, seed)
        state.B0 = game_rows.starting_projection(config)
    with phases("build"):
        data, fixed, user, item = build_blocks(state.rows, config)
        state.vectors = tuple(
            jnp.asarray(v, jnp.float32)
            for v in (data.responses, data.weights, data.offsets))
        jax.block_until_ready((fixed.batch.X, state.vectors))
    mf = s["mf"]
    state.coords = {
        "fixed": FixedEffectCoordinate(
            dataset=fixed, problem=GLMOptimizationProblem(
                config=l2_config(s["fixed"]), task=task)),
        "per-user": RandomEffectCoordinate(
            dataset=user, problem=entity_problem(s["per_user"])),
        "per-item": RandomEffectCoordinate(
            dataset=item, problem=entity_problem(s["per_item"])),
        # the per-user coordinate's own dataset, not a second build
        "mf": FactoredRandomEffectCoordinate(
            dataset=user, problem=entity_problem(mf["latent"]),
            latent_problem=GLMOptimizationProblem(
                config=l2_config(mf["refit"]), task=task),
            latent_dim=int(config["latent_dim"]),
            num_inner_iterations=int(mf["inner_iterations"])),
    }
    assert tuple(state.coords) == SEQUENCE
    state.initial = {"mf": (
        jnp.zeros((user.num_entities, int(config["latent_dim"])),
                  jnp.float32), jnp.asarray(state.B0))}
    state.task = task

    cap, keep = int(config["active_rows_cap"]), int(config["features_cap"])
    by_user = np.bincount(state.rows.user, minlength=int(config["users"]))
    by_movie = np.bincount(state.rows.movie,
                           minlength=int(config["movies"]))
    sides = {"per-user": _side_shapes(user, data.id_vocabs["userId"]),
             "per-item": _side_shapes(item, data.id_vocabs["movieId"])}
    for name, counts in (("per-user", by_user), ("per-item", by_movie)):
        active = np.minimum(counts[sides[name]["ids"]], cap).astype(np.int64)
        sides[name]["active"] = active
        # one-hot rows: an entity's block is its rows by as many columns
        sides[name]["cells"] = active * np.minimum(active, keep)
    u = sides["per-user"]
    slots = int(np.sum(np.minimum(u["active"], keep)))
    state.shapes = {
        "fixed": [int(v) for v in fixed.batch.X.shape],
        "itemsize": int(fixed.batch.X.dtype.itemsize), "sides": sides,
        "refit": {"cells": int(u["cells"].sum()), "slots": slots,
                  "rows": int(u["active"].sum()),
                  "columns": int(np.sum(np.bincount(
                      u["columns"].ravel(),
                      minlength=u["raw_dim"] + 1)[:u["raw_dim"]] > 0)),
                  "latent_dim": int(config["latent_dim"])},
        "report": game_rows.describe_rows(state.rows, config)}
    return state


def describe(state: State) -> list:
    sh = state.shapes
    return [f"game_train: fixed-effect block {sh['fixed']}; " + "; ".join(
        f"{name} buckets [E, N, D] {side['buckets']}, passive rows "
        f"{side['passive_rows']}" for name, side in sh["sides"].items())
        + f"; mf refit {sh['refit']}",
        "data_report: " + repr(sh["report"])]


def train(coords: dict, sweeps: int, task, vectors, initial: dict):
    """The timed call. Tests break it underneath (see tests/bench_harness)."""
    from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent

    labels, weights, offsets = vectors
    return run_coordinate_descent(coords, num_iterations=sweeps, task=task,
                                  labels=labels, weights=weights,
                                  offsets=offsets, initial_states=initial)


def step(state: State) -> dict:
    """One sweep from zero state and ``B0``; ends in the fetched model and
    the solves' own counts."""
    result = train(state.coords, int(state.settings["sweeps"]), state.task,
                   state.vectors, state.initial)
    record = {"objectives": [float(st.objective) for st in result.states],
              "evaluations": {}, "iterations": []}
    for st in result.states:
        tracker, cid = st.tracker.materialize(), st.coordinate_id
        if cid == "fixed":
            found = tracker.result
            record["first_fixed"] = (
                np.asarray(found.coefficients, np.float64),
                float(found.value), float(found.grad_norm))
            record["evaluations"][cid] = int(found.evaluations)
            record["iterations"].append(int(found.iterations))
        elif cid == "mf":
            (latent, refit), = tracker.inner
            record["evaluations"]["mf.latent"] = np.asarray(
                latent.evaluations)
            record["evaluations"]["mf.refit"] = int(refit.result.evaluations)
            record["iterations"] += [int(latent.iterations.max()),
                                     int(refit.result.iterations)]
        else:
            record["evaluations"][cid] = np.asarray(tracker.evaluations)
            record["iterations"].append(int(tracker.iterations.max()))
    record["w_fixed"] = np.asarray(
        result.model.get("fixed").coefficients.means, np.float64)
    for cid in ("per-user", "per-item"):
        record[cid] = np.asarray(
            result.model.get(cid).coefficients_projected)
    factored = result.model.get("mf")
    record["latent"] = np.asarray(factored.coefficients_latent)
    record["projection"] = np.asarray(factored.projection)
    record["mf_refit"] = work_game.refit_work(
        itemsize=state.shapes["itemsize"],
        evaluations=record["evaluations"]["mf.refit"],
        **state.shapes["refit"])
    return record


def work(state: State, record: dict) -> dict:
    """Every coordinate's passes, from the solvers' own counts: the
    fixed-effect block once an evaluation, every entity's own dense block
    (its training rows by the columns they touch; in the latent stage by
    K) once an evaluation of its solve, the refit's pass once an
    evaluation."""
    sh, ev = state.shapes, record["evaluations"]
    rows, cols = sh["fixed"]
    itemsize = sh["itemsize"]
    users = sh["sides"]["per-user"]
    return work_fns.add_work(
        work_sparse.dense_work(rows, cols, itemsize, ev["fixed"], 0),
        work_game.entity_work(users["cells"], ev["per-user"], itemsize),
        work_game.entity_work(sh["sides"]["per-item"]["cells"],
                              ev["per-item"], itemsize),
        work_game.entity_work(users["active"] * sh["refit"]["latent_dim"],
                              ev["mf.latent"], itemsize),
        record["mf_refit"])


def release(state: State) -> None:
    state.coords = state.vectors = state.initial = None


# --- the comparison ---------------------------------------------------------


def reference_fit(state: State, low_precision: bool = False, **planted):
    c, s = state.config, state.settings
    mf = s["mf"]
    return reference.fit(
        state.rows, int(c["users"]), int(c["movies"]),
        int(c["active_rows_cap"]), int(c["active_rows_sample_seed"]),
        {"fixed": float(s["fixed"]["l2"]),
         "per_user": float(s["per_user"]["l2"]),
         "per_item": float(s["per_item"]["l2"]),
         "latent": float(mf["latent"]["l2"]),
         "projection": float(mf["refit"]["l2"])},
        state.B0, int(mf["refit"]["max_iterations"]),
        block=int(c["reference_rows_per_block"]),
        low_precision=low_precision, **planted)


def _pairs(side: dict, coef: np.ndarray, columns: int):
    """One side's coefficients as (sorted pair keys, values)."""
    valid = side["columns"] < side["raw_dim"]
    ids = np.broadcast_to(side["ids"][:, None].astype(np.int64),
                          valid.shape)
    keys = ids[valid] * columns + side["columns"][valid]
    values = np.asarray(coef, np.float64)[valid]
    order = np.argsort(keys)
    return keys[order], values[order]


def as_outputs(fit) -> dict:
    """A reference fit in the shape ``compare`` reads a step's outputs in,
    to stand in the program's place (the control, and the planted
    faults)."""
    return {"objectives": list(fit.objectives), "w_fixed": fit.w_fixed,
            "first_fixed": fit.first_fixed,
            "pairs": {"per-user": (fit.user_key, fit.user_coef),
                      "per-item": (fit.item_key, fit.item_coef)},
            "latent_by_user": fit.latent, "projection": fit.projection}


def outputs_of(state: State, record: dict) -> dict:
    """A step's record in that same shape."""
    c, sides = state.config, state.shapes["sides"]
    latent = np.zeros((int(c["users"]), int(c["latent_dim"])))
    latent[sides["per-user"]["ids"]] = record["latent"]
    return {"objectives": record["objectives"],
            "w_fixed": record["w_fixed"],
            "first_fixed": record["first_fixed"],
            "pairs": {
                "per-user": _pairs(sides["per-user"], record["per-user"],
                                   int(c["movies"])),
                "per-item": _pairs(sides["per-item"], record["per-item"],
                                   int(c["users"]))},
            "latent_by_user": latent, "projection": record["projection"]}


def control(state: State) -> dict:
    """The control: the reference in the program's place, in bfloat16."""
    return as_outputs(reference_fit(state, low_precision=True))


def fault_state_unchanged(state: State) -> dict:
    """A sweep that returns its starting state: the objective stays at the
    start's, every coefficient at 0, the projection at ``B0``."""
    c = state.config
    n = len(state.rows.y)
    start = n * float(np.log(2.0)) + 0.5 * float(
        state.settings["mf"]["refit"]["l2"]) * float(
            np.sum(state.B0.astype(np.float64) ** 2))
    zero = np.zeros(state.rows.X.shape[1])
    none = (np.zeros(0, np.int64), np.zeros(0))
    return {"objectives": [start] * len(SEQUENCE), "w_fixed": zero,
            "first_fixed": (zero, n * float(np.log(2.0)), 0.0),
            "pairs": {"per-user": none, "per-item": none},
            "latent_by_user": np.zeros((int(c["users"]),
                                        int(c["latent_dim"]))),
            "projection": np.asarray(state.B0, np.float64)}


def fault_no_item_exchange(state: State) -> dict:
    """The per-item coordinate's scores left out of the offsets the
    factored coordinate solves against."""
    return as_outputs(reference_fit(state, no_item_exchange=True))


def fault_refit_drops_factor(state: State) -> dict:
    """The refit's scatter-add without the last latent factor: row K - 1 of
    the projection's gradient holds its penalty alone."""
    return as_outputs(reference_fit(state, refit_drops_factor=True))


def fault_stale_projection(state: State) -> dict:
    """The refit's result dropped: the coordinate's state and its scores
    hold the projection the latent stage was solved against."""
    return as_outputs(reference_fit(state, stale_projection=True))


def fault_half_batch(state: State) -> dict:
    """Half of the rows left out of every solve, the rest weighed twice."""
    n = len(state.rows.y)
    weight = np.where(np.arange(n) < n // 2, 2.0, 0.0)
    return as_outputs(reference_fit(state, row_weight=weight))


FAULTS = {"state_unchanged": fault_state_unchanged,
          "half_batch": fault_half_batch,
          "no_item_exchange": fault_no_item_exchange,
          "refit_drops_factor": fault_refit_drops_factor,
          "stale_projection": fault_stale_projection}


def _side_gaps(mine: tuple, keys, coef, weight) -> tuple:
    """(gap over every pair, gap over the pairs of entities at the cap):
    distance over norm; a pair only one side has counts with 0 on the
    other."""
    all_keys = np.union1d(mine[0], keys)
    a = np.zeros(len(all_keys))
    a[np.searchsorted(all_keys, mine[0])] = mine[1]
    b = np.zeros(len(all_keys))
    at = np.searchsorted(all_keys, keys)
    b[at] = coef
    capped = np.zeros(len(all_keys), bool)
    capped[at] = weight > 1.0
    whole = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    return whole, (float(np.linalg.norm((a - b)[capped])
                         / np.linalg.norm(b[capped]))
                   if capped.any() else 0.0)


def compare(ref, out: dict, B0: np.ndarray) -> dict:
    """The gaps between a sweep's outputs and the reference's."""
    _, _, grad_norm = out["first_fixed"]
    _, grad_at, grad_zero = ref.probe
    user, user_capped = _side_gaps(out["pairs"]["per-user"], ref.user_key,
                                   ref.user_coef, ref.user_weight)
    item, item_capped = _side_gaps(out["pairs"]["per-item"], ref.item_key,
                                   ref.item_coef, ref.item_weight)
    latent, capped = out["latent_by_user"], ref.user_weight_of > 1.0
    moved = ref.projection - B0  # what the refit did to the projection
    mine = np.asarray(out["projection"], np.float64) - B0
    by_factor = np.linalg.norm(mine - moved, axis=1) / np.linalg.norm(
        moved, axis=1)
    return {
        "fixed_grad_gap": abs(grad_norm - grad_at) / grad_zero,
        # the three coordinates with one minimiser each: against it
        "objective_gap": float(max(
            abs(got - want) / abs(want)
            for got, want in zip(out["objectives"][:3], ref.objectives))),
        # the sweep's last objective against the equations at the sweep's
        # own model: scoring, the exchange and the penalties of all four
        # coordinates, whatever path the budgeted solves took
        "final_objective_gap": abs(out["objectives"][3]
                                   - ref.probe_objective)
        / abs(ref.probe_objective),
        "fixed_coef_gap": float(np.linalg.norm(out["w_fixed"] - ref.w_fixed)
                                / np.linalg.norm(ref.w_fixed)),
        "user_coef_gap": user, "user_capped_coef_gap": user_capped,
        "item_coef_gap": item, "item_capped_coef_gap": item_capped,
        "latent_coef_gap": float(np.linalg.norm(latent - ref.latent)
                                 / np.linalg.norm(ref.latent)),
        "latent_capped_coef_gap": float(
            np.linalg.norm((latent - ref.latent)[capped])
            / np.linalg.norm(ref.latent[capped])) if capped.any() else 0.0,
        "projection_gap": float(np.linalg.norm(mine - moved)
                                / np.linalg.norm(moved)),
        "projection_factor_gap": float(by_factor.max())}


def verify(state: State, outputs: dict, limits: dict) -> list:
    """A sweep the window made against the plain reference's: the objective
    after every update (block build, four solves and the score exchange
    between them; the last at the sweep's own factored state), every
    coordinate's coefficients, and the gradient the fixed-effect solve
    reports at its own coefficients against the reference's evaluation
    there. ``outputs`` is a step's record, or the
    stand-in a control or a fault returns."""
    if "pairs" not in outputs:
        outputs = outputs_of(state, outputs)
    gaps = compare(
        reference_fit(state, probe_fixed=outputs["first_fixed"][0],
                      probe_model=outputs),
        outputs, np.asarray(state.B0, np.float64))
    return [(name, gaps[name], float(limits[name])) for name in limits]
