"""GAME coordinates: per-coordinate update/score units for coordinate descent.

TPU-native re-design of the reference's coordinate family
(reference paths under photon-ml/src/main/scala/com/linkedin/photon/ml/
algorithm/):

- ``Coordinate`` (Coordinate.scala:26-82): updateModel(model, partialScore →
  offsets), score(model), regularization value.
- ``FixedEffectCoordinate`` (FixedEffectCoordinate.scala:34-165): optimize a
  GLM on the offset-adjusted full batch via
  DistributedOptimizationProblem.runWithSampling (down-sampling per update).
- ``RandomEffectCoordinate`` (RandomEffectCoordinate.scala:99-199): per-entity
  local solves (here: the vmapped block solver) + active/passive scoring.
- ``RandomEffectCoordinateInProjectedSpace``
  (RandomEffectCoordinateInProjectedSpace.scala:25-149): models live in
  projected space — here that is the *native* representation; raw-space
  conversion happens when the model is published.
- ``FactoredRandomEffectCoordinate`` (FactoredRandomEffectCoordinate.scala:
  39-257): alternate per-entity latent fits with a distributed fit of the
  latent→raw projection on Kronecker-product features (:228-271) — the
  Kronecker expansion is one einsum on TPU.

Every coordinate's state is (model arrays, sample-axis score vector); the
partial-score offset injection is a gather along the stored row ids.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Union

import numpy as np

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.batch import (
    ProjectionRefitBatch,
    gather_projection,
    projection_table,
)
from photon_ml_tpu.game.dataset import (
    FixedEffectDataset,
    RandomEffectDataset,
)
from photon_ml_tpu.game.models import (
    FactoredRandomEffectModel,
    FixedEffectModel,
    RandomEffectModelInProjectedSpace,
)
from photon_ml_tpu.parallel.mesh import ensure_addressable
from photon_ml_tpu.game.random_effect import (
    RandomEffectOptimizationProblem,
    score_random_effect,
)
from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.obs import compile as obs_compile
from photon_ml_tpu.obs import trace
from photon_ml_tpu.optimize.common import (
    DeferredOptimizationResult,
    OptimizationResult,
    record_solve,
)
from photon_ml_tpu.optimize.config import TaskType
from photon_ml_tpu.optimize.problem import GLMOptimizationProblem
from photon_ml_tpu.sampler.samplers import down_sample

Array = jnp.ndarray

_CLASSIFICATION_TASKS = (
    TaskType.LOGISTIC_REGRESSION,
    TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
)


@dataclasses.dataclass
class FixedEffectTracker:
    """optimization/game/FixedEffectOptimizationTracker analog."""

    result: OptimizationResult

    def for_coordinate(self, coordinate: str) -> "FixedEffectTracker":
        """Name the coordinate whose update this was: the label the
        solve's counts are booked under when its history is forced."""
        # (under a mesh with a data axis ``run_lazy`` hands back an eager
        # result: its counts reached the host inside the solve and stand
        # under ``site`` alone)
        if isinstance(self.result, DeferredOptimizationResult):
            self.result.coordinate = coordinate
        return self

    def materialize(self) -> "FixedEffectTracker":
        """Force a deferred result's device-resident history host-side
        (one batched fetch) — the CD loop drains trackers at sweep
        boundaries so device buffers don't accumulate across the run."""
        force = getattr(self.result, "_force", None)
        if force is not None:
            force()
        return self

    def summary(self) -> str:
        return (f"fixed effect: {self.result.convergence_reason.name}, "
                f"{self.result.iterations} iterations")


@dataclasses.dataclass
class RandomEffectTracker:
    """optimization/game/RandomEffectOptimizationTracker analog: iteration
    counts + per-entity convergence-reason counts across the vmapped
    solves (countsByConvergence — the operator's only view into thousands
    of per-entity fits).

    LAZY: construction accepts device arrays and performs no host fetch —
    the CD hot loop creates one of these per update without blocking. The
    per-entity arrays materialize with a SINGLE ``jax.device_get`` of the
    whole tuple on first use (``summary()``/``counts_by_convergence()``,
    i.e. log or metrics time), where they are also sliced to ``num_real``
    entities (the single-block solver returns entity-axis pad lanes).

    The same fetch brings the evaluation counts (``LaneCounts``, None from
    a caller that has none), and the first materialization books them on
    the ``solver_*{site}`` counters: a training that never looks at a
    tracker counts nothing for it."""

    iterations: np.ndarray  # [E] (device array until materialized)
    final_values: np.ndarray  # [E]
    convergence_codes: Optional[np.ndarray] = None  # [E] int8
    # lazy slice bound: real entity count (None = already compact)
    num_real: Optional[int] = None
    # game/random_effect.LaneCounts, field for field
    evaluations: Optional[np.ndarray] = None  # [E] each entity's own
    evaluation_rounds: Optional[np.ndarray] = None  # [B]
    bucket_lanes: Optional[np.ndarray] = None  # [B], pad lanes included
    site: str = "re.fit_blocks"
    line_trials: Optional[np.ndarray] = None  # [E] each entity's own
    coordinate: Optional[str] = None  # the counters' second label

    def for_coordinate(self, coordinate: str) -> "RandomEffectTracker":
        self.coordinate = coordinate
        return self

    def materialize(self) -> "RandomEffectTracker":
        """Fetch the per-entity arrays host-side (one explicit
        ``jax.device_get`` of the tuple, multi-host safe) — idempotent."""
        if not isinstance(self.iterations, np.ndarray):
            from photon_ml_tpu.utils.sync_telemetry import record_host_fetch

            it, v, c, ev, rounds, tr = jax.device_get(tuple(
                None if a is None else ensure_addressable(a)
                for a in (self.iterations, self.final_values,
                          self.convergence_codes, self.evaluations,
                          self.evaluation_rounds, self.line_trials)))
            record_host_fetch(site="tracker.materialize")
            nr = self.num_real
            if nr is not None:
                it, v = it[:nr], v[:nr]
                c = None if c is None else c[:nr]
                ev = None if ev is None else ev[:nr]
                tr = None if tr is None else tr[:nr]
            self.iterations, self.final_values = np.asarray(it), np.asarray(v)
            self.convergence_codes = None if c is None else np.asarray(c)
            self.num_real = None
            if ev is not None:
                self.evaluations = np.asarray(ev)
                self.evaluation_rounds = np.asarray(rounds)
                self.line_trials = None if tr is None else np.asarray(tr)
                record_solve(
                    self.site, int(self.iterations.sum()),
                    int(self.evaluations.sum()),
                    lane_evaluations=self._lane_evaluations(),
                    coordinate=self.coordinate,
                    line_trials=(None if tr is None
                                 else int(self.line_trials.sum())))
        return self

    def _lane_evaluations(self) -> int:
        """What the batched loops ran: per program, lanes x rounds."""
        return int(np.dot(self.bucket_lanes, self.evaluation_rounds))

    def lane_fill(self) -> Optional[float]:
        """Evaluations the entities needed over the lane-evaluations the
        batched loops ran for them (pad lanes included): at most 1. The
        executed fill while every lane is still solving; above it only by
        what finished lanes ride along for (``rounds`` cannot see that, see
        ``game/random_effect._fit_blocks_impl``)."""
        self.materialize()
        if self.evaluations is None:
            return None
        ran = self._lane_evaluations()
        return float(self.evaluations.sum()) / ran if ran else None

    def counts_by_convergence(self) -> dict[str, int]:
        """reason name -> entity count
        (RandomEffectOptimizationTracker.countsByConvergence)."""
        from photon_ml_tpu.game.random_effect import CONVERGENCE_CODE_NAMES

        self.materialize()
        if self.convergence_codes is None:
            return {}
        codes, counts = np.unique(self.convergence_codes,
                                  return_counts=True)
        return {CONVERGENCE_CODE_NAMES[int(c)]: int(n)
                for c, n in zip(codes, counts)}

    def summary(self) -> str:
        it = self.materialize().iterations
        base = (f"random effect: {len(it)} entities, iterations "
                f"min/mean/max = {it.min()}/{it.mean():.1f}/{it.max()}")
        counts = self.counts_by_convergence()
        if counts:
            base += ", convergence " + "/".join(
                f"{k}={v}" for k, v in sorted(counts.items()))
        return base


@dataclasses.dataclass
class FactoredRandomEffectTracker:
    inner: list[tuple[RandomEffectTracker, FixedEffectTracker]]

    def for_coordinate(self, coordinate: str
                       ) -> "FactoredRandomEffectTracker":
        for re_tracker, fe_tracker in self.inner:
            re_tracker.for_coordinate(coordinate)
            fe_tracker.for_coordinate(coordinate)
        return self

    def materialize(self) -> "FactoredRandomEffectTracker":
        for re_tracker, fe_tracker in self.inner:
            re_tracker.materialize()
            fe_tracker.materialize()
        return self

    def summary(self) -> str:
        return (f"factored random effect: {len(self.inner)} inner iterations")


Tracker = Union[FixedEffectTracker, RandomEffectTracker,
                FactoredRandomEffectTracker]


# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FixedEffectCoordinate:
    """Global GLM coordinate over the mesh-sharded sample batch."""

    dataset: FixedEffectDataset
    problem: GLMOptimizationProblem
    seed: int = 0
    _update_count: int = 0

    @property
    def num_samples(self) -> int:
        return self.dataset.num_samples

    def initial_state(self) -> Array:
        """Zero coefficients in normalized space."""
        return jnp.zeros(self.dataset.batch.num_features)

    def update(self, coefs: Optional[Array], extra_scores: Array
               ) -> tuple[Array, Tracker]:
        """Re-optimize on the offset-adjusted batch
        (FixedEffectCoordinate.updateModel :137-148 + runWithSampling).
        Device-resident: ``run_lazy`` keeps the solve history on device, so
        the returned coefficients/tracker carry no blocking host read — the
        CD fused epilogue owns the update's single device→host fetch."""
        batch = self.dataset.with_offsets(extra_scores)
        rate = self.problem.config.down_sampling_rate
        if rate < 1.0:
            key = jax.random.PRNGKey(self.seed + self._update_count)
            batch = down_sample(
                batch, rate, key,
                is_classification=self.problem.task in _CLASSIFICATION_TASKS)
        self._update_count += 1
        result = self.problem.run_lazy(batch, initial=coefs)
        return result.coefficients, FixedEffectTracker(result)

    def score(self, coefs: Array) -> Array:
        """Sample-axis margins x.w (normalized-space coefficients are scored
        through the normalization's effective-coefficient algebra)."""
        w_eff, shift = self.problem.normalization.effective_coefficients(coefs)
        zero_off = self.dataset.batch._replace(
            offsets=jnp.zeros_like(self.dataset.base_offsets))
        return zero_off.margins(w_eff, shift)

    def regularization_value(self, coefs: Array) -> float:
        return self.problem.regularization_value(coefs)

    def regularization_value_device(self, coefs: Array):
        """Penalty as a device scalar (no sync) for the CD epilogue."""
        return self.problem.regularization_value_device(coefs)

    def publish(self, coefs: Array) -> FixedEffectModel:
        means = self.problem.normalization.transform_model_coefficients(coefs)
        model = GeneralizedLinearModel(Coefficients(means=means),
                                       self.problem.task)
        return FixedEffectModel(model=model,
                                feature_shard_id=self.dataset.shard_id)


# ---------------------------------------------------------------------------


def _exchange_offsets(coordinate, extra_scores: Array):
    """The offset half of a random-effect coordinate's score exchange,
    under the host span that says where it was dispatched."""
    ds = coordinate.dataset
    with trace.span("re.offsets", coordinate=coordinate.coordinate_id,
                    blocks=ds.num_blocks):
        return ds.offsets_with(extra_scores)


@dataclasses.dataclass
class RandomEffectCoordinate:
    """Per-entity GLM coordinate, vmapped over the entity axis.

    Combines the reference's RandomEffectCoordinate and its projected-space
    wrapper: the dataset is already in each entity's reduced space, so the
    coordinate state (``[E, D_red]``) is the projected model
    (RandomEffectCoordinateInProjectedSpace.scala:25-149).
    """

    dataset: RandomEffectDataset
    problem: RandomEffectOptimizationProblem
    # the id the coordinate-descent loop runs it under (it says so at its
    # start): the ``coordinate`` label of the exchange's spans, spelled as
    # ``cd.update`` spells it
    coordinate_id: str = ""

    @property
    def num_samples(self) -> int:
        return self.dataset.num_samples

    def initial_state(self) -> Array:
        return jnp.zeros((self.dataset.num_entities, self.dataset.reduced_dim))

    def update(self, coefs: Optional[Array], extra_scores: Array
               ) -> tuple[Array, Tracker]:
        offsets = _exchange_offsets(self, extra_scores)
        # ``donate=True``: the per-update offset block is rebuilt from the
        # CD score vector every update, so the solver may reuse its device
        # buffer in place (no-op on CPU; ``coefs`` — the CD loop's live
        # last-good state — is never donated, see _dispatch_fit)
        new_coefs, iters, values, codes, counts = self.problem.run(
            self.dataset, offsets, initial=coefs, donate=True)
        # lazy tracker: arrays stay on device until log/metrics time; the
        # num_real bound trims the single-block path's entity-axis PAD
        # lanes at materialization (the bucketed path is already compact)
        tracker = RandomEffectTracker(
            iters, values, codes, num_real=len(self.dataset.entity_codes),
            **counts._asdict())
        return new_coefs, tracker

    def score(self, coefs: Array) -> Array:
        return score_random_effect(
            self.dataset, coefs,
            entity_shards=self.problem.entity_shards,
            collective_quant=self.problem.collective_quant,
            coordinate=self.coordinate_id)

    def regularization_value(self, coefs: Array) -> float:
        return self.problem.regularization_value(coefs)

    def regularization_value_device(self, coefs: Array):
        """Penalty as a device scalar (no sync) for the CD epilogue."""
        return self.problem.regularization_value_device(coefs)

    def publish(self, coefs: Array) -> RandomEffectModelInProjectedSpace:
        return RandomEffectModelInProjectedSpace(
            random_effect_type=self.dataset.config.random_effect_type,
            feature_shard_id=self.dataset.config.feature_shard_id,
            entity_codes=self.dataset.entity_codes,
            coefficients_projected=coefs,
            projectors=self.dataset.projectors,
            random_projector=self.dataset.random_projector,
        )


# ---------------------------------------------------------------------------


def _refit_batch(data, spans, single_block: bool, raw_dim: int,
                 coefs: Array, offsets) -> ProjectionRefitBatch:
    """The projection refit's batch: ``data`` is what no update changes
    (the blocks, their column maps, the flat labels and weights), ``spans``
    every block's (first global entity, real entities, lanes), ``coefs``
    the compact global latent block, cut here into the blocks' own lane
    counts (pad lanes zero), ``offsets`` the per-block training offsets
    (None: zeros)."""
    Xs, columns, labels, weights = data
    if offsets is None:
        flat = jnp.zeros_like(labels)
    else:
        flat = jnp.concatenate([o.reshape(-1) for o in (
            [offsets] if single_block else offsets)])
    return ProjectionRefitBatch(
        blocks=[(X, cols, jnp.pad(coefs[start:start + num_real],
                                  ((0, e_b - num_real), (0, 0))))
                for X, cols, (start, num_real, e_b) in zip(Xs, columns,
                                                           spans)],
        labels=labels, offsets=flat, weights=weights, dim=raw_dim)


@dataclasses.dataclass
class FactoredRandomEffectCoordinate:
    """Alternating latent-space random effect + projection-matrix fit.

    For entity ``e`` with latent coefficients ``c_e`` in R^K and the shared
    projection ``B`` in R^{K x D} (D the raw feature space), a row scores
    ``c_e^T B x``. The dataset is any ``RandomEffectDataset`` in raw space:
    index-map projected (each entity's block holds only its own columns
    ``P_e = projectors.raw_indices[e]``, so ``B x = B[:, P_e] x~``),
    bucketed or one block; identity projection is the case ``P_e =
    arange(D)``. The per-entity random-effect coordinate's own dataset may
    be handed in as it is. Each update runs ``num_inner_iterations`` of:

    1. project the blocks into the current latent space
       (``X_lat = einsum(X~, B[:, P_e])``) and solve per-entity latent
       coefficients with the vmapped block solver
       (FactoredRandomEffectCoordinate.scala:228-257's random-effect step);
    2. refit B with a single GLM whose coefficient vector is vec(B) over
       the Kronecker features ``c_e (x) x``
       (kroneckerProductFeaturesAndCoefficients :271), which are never
       built: the refit's batch layout gathers and scatter-adds each
       entity's columns of B (data/batch.ProjectionRefitBatch).
    """

    dataset: RandomEffectDataset  # raw space: index-map or identity
    problem: RandomEffectOptimizationProblem  # latent per-entity fits
    latent_problem: GLMOptimizationProblem  # projection-matrix fit
    latent_dim: int
    num_inner_iterations: int = 2
    seed: int = 0
    coordinate_id: str = ""  # as RandomEffectCoordinate's

    def __post_init__(self):
        ds = self.dataset
        if ds.random_projector is not None:
            raise ValueError(
                "factored coordinate needs a dataset in raw feature space "
                "(index-map or identity projection): a random projection "
                "has no per-entity columns of B to gather")
        self.raw_dim = (ds.reduced_dim if ds.projectors is None
                        else int(ds.projectors.raw_dim))
        # (first global entity, real entities, lanes) of every block: its
        # buckets, or the dataset itself where it is one block (whose pad
        # lanes are part of the coefficient block)
        blocks = [ds] if ds.buckets is None else ds.buckets
        self._spans = tuple(
            (0, int(ds.X.shape[0]), int(ds.X.shape[0]))
            if ds.buckets is None
            else (b.entity_start, b.num_real, int(b.X.shape[0]))
            for b in blocks)
        # every block's column map [E_b, D_b] into B's columns; raw_dim
        # marks an unused slot, which gathers zeros and scatters nowhere
        columns = []
        for (start, num_real, e_b), block in zip(self._spans, blocks):
            d_b = int(block.X.shape[2])
            if ds.projectors is None:
                cols = np.broadcast_to(np.arange(d_b, dtype=np.int32),
                                       (e_b, d_b))
            else:
                cols = np.full((e_b, d_b), self.raw_dim, np.int32)
                own = ds.projectors.raw_indices[start:start + num_real,
                                                :d_b]
                cols[:len(own)] = own
            columns.append(jnp.asarray(cols))
        # what of the refit's batch no update changes: the blocks, their
        # column maps, the flat labels and weights. Handed to the jitted
        # stages as arguments (a closed-over array would be baked into the
        # executable as a constant)
        self._data = (
            tuple(b.X for b in blocks), tuple(columns),
            jnp.concatenate([b.labels.reshape(-1) for b in blocks]),
            jnp.concatenate([b.weights.reshape(-1) for b in blocks]))
        # The three jitted stages close over small static facts only (never
        # over ``self``: a cycle through the coordinate would keep its
        # device blocks alive until the cycle collector runs).
        problem, spans, raw_dim = self.latent_problem, self._spans, self.raw_dim
        single_block, d_red = ds.buckets is None, ds.reduced_dim

        # the refit runs as an XLA module of its own name (the fixed
        # effect's solve is jit__minimize_lbfgs_impl too, inlined here)
        def _factored_refit_impl(obj, data, coefs, offsets, x0):
            return problem.solve(obj, _refit_batch(
                data, spans, single_block, raw_dim, coefs, offsets), x0)

        def _factored_latent_blocks(data, B):
            """Every block's rows in the latent space, ``B[:, P_e] x~``:
            [E_b, N_b, K] each."""
            table = projection_table(B)
            return tuple(
                jnp.einsum("end,edk->enk", X, gather_projection(table, cols),
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=B.dtype)
                for X, cols in zip(data[0], data[1]))

        def _factored_entity_coefficients(data, coefs, B):
            """``w_e = B[:, P_e]^T c_e`` of every entity: the compact
            global [num_entities, reduced_dim] block."""
            batch = _refit_batch(data, spans, single_block, raw_dim, coefs,
                                 None)
            parts = [
                jnp.pad(w[:num_real], ((0, 0), (0, d_red - w.shape[1])))
                for w, (_, num_real, _) in zip(
                    batch.entity_coefficients(B.reshape(-1)), spans)]
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

        self._refit = jax.jit(_factored_refit_impl)
        self._latent_blocks = jax.jit(_factored_latent_blocks)
        self._entity_coefficients = jax.jit(_factored_entity_coefficients)

    @property
    def num_samples(self) -> int:
        return self.dataset.num_samples

    def initial_state(self) -> tuple[Array, Array]:
        k = self.latent_dim
        e = self.dataset.num_entities
        # Random projection init (MFOptimizationConfiguration analog).
        # Explicit f32: under x64 the default dtype would draw DIFFERENT
        # random bits, and the bilinear alternation amplifies an init
        # difference into a different local optimum — the init must not
        # depend on the precision mode (blocks are f32 regardless).
        b0 = jax.random.normal(jax.random.PRNGKey(self.seed),
                               (k, self.raw_dim),
                               dtype=jnp.float32) / jnp.sqrt(k)
        return jnp.zeros((e, k), jnp.float32), b0

    def _latent_dataset(self, B: Array) -> RandomEffectDataset:
        """The dataset with every block's rows projected into the latent
        space: what the per-entity solver sees."""
        ds = self.dataset
        lat = self._latent_blocks(self._data, B)
        if ds.buckets is None:
            return dataclasses.replace(ds, X=lat[0], projectors=None)
        return dataclasses.replace(
            ds, projectors=None, _reduced_dim=self.latent_dim,
            buckets=[dataclasses.replace(b, X=x)
                     for b, x in zip(ds.buckets, lat)])

    def update(self, state: Optional[tuple[Array, Array]],
               extra_scores: Array) -> tuple[tuple[Array, Array], Tracker]:
        coefs, B = state if state is not None else self.initial_state()
        ds = self.dataset
        offsets = _exchange_offsets(self, extra_scores)
        # The init is drawn in f32 so its BITS don't depend on the x64
        # mode; the running state then promotes to the ambient dtype (x64
        # runs keep solving in f64, with the identical starting values).
        acc = jnp.promote_types(
            jnp.promote_types(coefs.dtype, jnp.float32),
            (offsets if ds.buckets is None else offsets[0]).dtype)
        coefs, B = coefs.astype(acc), B.astype(acc)
        k = self.latent_dim
        obj = self.latent_problem.objective()
        cfg = self.latent_problem.config
        inner: list = []
        for _ in range(self.num_inner_iterations):
            # (1) latent-space per-entity fits on projected blocks.
            with trace.span("factored.latent_solve", latent_dim=k):
                # donate=False: ``offsets`` is reused across inner
                # iterations and by the refit below
                coefs, iters, values, codes, counts = self.problem.run(
                    self._latent_dataset(B), offsets, initial=coefs,
                    donate=False)
            re_tracker = RandomEffectTracker(
                iters, values, codes, num_real=len(ds.entity_codes),
                **counts._asdict())
            # (2) projection-matrix fit over the Kronecker features.
            with trace.span("factored.refit", latent_dim=k,
                            raw_dim=self.raw_dim):
                x, history, progressed = obs_compile.call(
                    "factored.refit", self._refit,
                    (obj, self._data, coefs, offsets, B.reshape(-1)),
                    arg_names=("obj", "data", "coefs", "offsets", "x0"))
            B = x.reshape(k, self.raw_dim)
            inner.append((re_tracker, FixedEffectTracker(
                DeferredOptimizationResult(
                    x, history, progressed, cfg.max_iterations,
                    cfg.tolerance,
                    site=self.latent_problem.solver_site()))))
        return (coefs, B), FactoredRandomEffectTracker(inner)

    def entity_coefficients(self, state: tuple[Array, Array]) -> Array:
        """Every entity's coefficients in its own reduced space, ``w_e =
        B[:, P_e]^T c_e``: the compact global ``[num_entities,
        reduced_dim]`` block a random-effect coordinate of this dataset
        would hold."""
        coefs, B = state
        return self._entity_coefficients(self._data, coefs,
                                         B.astype(coefs.dtype))

    def score(self, state: tuple[Array, Array]) -> Array:
        """``c_e^T B x`` for every row: the random-effect score of the
        entities' own coefficients ``w_e``, so active and passive rows
        alike go through their own entity's columns of B."""
        return score_random_effect(
            self.dataset, self.entity_coefficients(state),
            entity_shards=self.problem.entity_shards,
            collective_quant=self.problem.collective_quant,
            coordinate=self.coordinate_id)

    def regularization_value(self, state: tuple[Array, Array]) -> float:
        coefs, B = state
        return (self.problem.regularization_value(coefs)
                + self.latent_problem.regularization_value(B.reshape(-1)))

    def regularization_value_device(self, state: tuple[Array, Array]):
        """Penalty as a device scalar (no sync) for the CD epilogue."""
        coefs, B = state
        return (self.problem.regularization_value_device(coefs)
                + self.latent_problem.regularization_value_device(
                    B.reshape(-1)))

    def publish(self, state: tuple[Array, Array]) -> FactoredRandomEffectModel:
        coefs, B = state
        return FactoredRandomEffectModel(
            random_effect_type=self.dataset.config.random_effect_type,
            feature_shard_id=self.dataset.config.feature_shard_id,
            entity_codes=self.dataset.entity_codes,
            coefficients_latent=coefs,
            projection=B,
        )


Coordinate = Union[FixedEffectCoordinate, RandomEffectCoordinate,
                   FactoredRandomEffectCoordinate]
