"""GAME training driver: datasets → coordinates → CD grid → best model.

Re-design of the reference's GAME training pipeline (reference:
photon-ml/src/main/scala/com/linkedin/photon/ml/cli/game/training/
Driver.scala:66-757 + Params.scala:38-426 + cli/game/GAMEDriver.scala):

    prepareFeatureMaps → prepareGameDataSet → prepareTrainingDataSet →
    prepare evaluators → train (grid of coordinate-descent runs) →
    selectBestModel → saveModelToHDFS

Flag names and composite string formats match the reference CLI:
- ``--fixed-effect-data-configurations``: ``coordId:shardId,minPartitions``
  per coordinate, ``|``-separated.
- ``--random-effect-data-configurations``: ``coordId:<reConfig>`` with the
  reference's 7-field config string (data/RandomEffectDataConfiguration
  .scala:80).
- ``--fixed/random-effect-optimization-configurations``: grid points
  separated by ``;``, coordinates by ``|``, each
  ``coordId:maxIter,tol,lambda,downSamplingRate,OPTIMIZER,REG``
  (optimization/GLMOptimizationConfiguration.scala:41-87).
- ``--factored-random-effect-optimization-configurations``:
  ``coordId:reCfg:latentCfg:mfCfg`` with mfCfg = ``maxIters,numFactors``.
- ``--feature-shard-id-to-feature-section-keys-map``:
  ``shardId:sec1,sec2|shard2:...``; intercept map likewise with booleans.

Training runs every grid combination of fixed/random opt configs and keeps
the model that wins the first validation evaluator (Driver.scala:557-592
selectBestModel), then saves ALL/BEST/NONE per ``--model-output-mode``.
"""

from __future__ import annotations

import argparse
import itertools
import json
import math
import os
import sys
from typing import Optional, Sequence

import numpy as np

import jax.numpy as jnp

from photon_ml_tpu.evaluation.evaluators import (
    EvaluatorSpec,
    evaluate_many,
    resolve_entity_ids,
)
from photon_ml_tpu.game.coordinate import (
    FactoredRandomEffectCoordinate,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.coordinate_descent import (
    CoordinateDescentResult,
    run_coordinate_descent,
)
from photon_ml_tpu.game.dataset import (
    FixedEffectDataConfiguration,
    GameDataset,
    RandomEffectDataConfiguration,
    build_fixed_effect_dataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.game.random_effect import (
    AUTO_COMPACTION_CHUNK,
    AUTO_ENTITY_SHARDS,
    RandomEffectOptimizationProblem,
)
from photon_ml_tpu.io.data_format import (
    NameAndTermFeatureSets,
    load_game_dataset_avro,
)
from photon_ml_tpu.io.index_map import IndexMap
from photon_ml_tpu.io.model_io import save_game_model
from photon_ml_tpu.optimize.config import (
    GLMOptimizationConfiguration,
    MFOptimizationConfiguration,
    TaskType,
)
from photon_ml_tpu.optimize.problem import GLMOptimizationProblem
from photon_ml_tpu.utils import parse_flag
from photon_ml_tpu.utils.logging import PhotonLogger, timed_phase
from photon_ml_tpu.utils.compile_cache import (
    enable_persistent_compile_cache,
)

from photon_ml_tpu.cli.args import (
    add_precision_flags,
    check_telemetry_flags,
    parse_key_value_map,
    parse_section_keys_map,
    precision_dtype,
)


class ModelOutputMode:
    """io/ModelOutputMode.scala: ALL / BEST / NONE."""

    ALL = "ALL"
    BEST = "BEST"
    NONE = "NONE"


# The composite-flag grammars are shared CLI surface (the scoring
# driver and the serving entrypoint speak the same dialect); they live
# in cli/args.py now. The old private names stay importable.
_parse_key_value_map = parse_key_value_map
_parse_section_keys_map = parse_section_keys_map


def _parse_opt_config_grid(s: str) -> list[dict[str,
                                               GLMOptimizationConfiguration]]:
    """``;``-separated grid points of ``|``-separated ``coord:cfg``."""
    grid = []
    for point in s.split(";"):
        if not point.strip():
            continue
        grid.append({k: GLMOptimizationConfiguration.parse(v)
                     for k, v in _parse_key_value_map(point).items()})
    return grid


def _parse_factored_grid(s: str) -> list[dict]:
    """``coordId:reCfg:latentCfg:mfCfg`` per coordinate."""
    grid = []
    for point in s.split(";"):
        if not point.strip():
            continue
        configs = {}
        for line in point.split("|"):
            if not line.strip():
                continue
            parts = [p.strip() for p in line.split(":")]
            if len(parts) != 4:
                raise ValueError(
                    f"factored config needs coordId:reCfg:latentCfg:mfCfg, "
                    f"got {line!r}")
            key, s1, s2, s3 = parts
            configs[key] = (GLMOptimizationConfiguration.parse(s1),
                            GLMOptimizationConfiguration.parse(s2),
                            MFOptimizationConfiguration.parse(s3))
        grid.append(configs)
    return grid


def _parse_compaction_chunk(s: str) -> int:
    """``--re-lane-compaction-chunk`` value: an int, or ``auto`` → the
    ChunkAutoTuner sentinel (kept an int so the run-manifest flags stay
    scalar)."""
    if s.strip().lower() == "auto":
        return AUTO_COMPACTION_CHUNK
    return int(s)


def _parse_entity_shards(s: str) -> int:
    """``--re-entity-shards`` value: an int, or ``auto`` → every local
    device on the entity axis (kept an int so the run-manifest flags stay
    scalar)."""
    if s.strip().lower() == "auto":
        return AUTO_ENTITY_SHARDS
    return int(s)


def parse_args(argv: Sequence[str]) -> argparse.Namespace:
    p = argparse.ArgumentParser(prog="game-training",
                                description="GAME training on TPU")
    p.add_argument("--train-input-dirs", required=True)
    p.add_argument("--train-date-range",
                   help="yyyyMMdd-yyyyMMdd over <dir>/daily/yyyy/MM/dd")
    p.add_argument("--train-date-range-days-ago",
                   help="start-end days-ago pair (alternative to "
                        "--train-date-range)")
    p.add_argument("--validate-input-dirs")
    p.add_argument("--validate-date-range")
    p.add_argument("--validate-date-range-days-ago")
    p.add_argument("--output-dir", required=True)
    p.add_argument("--task-type", required=True,
                   choices=[t.name for t in TaskType])
    p.add_argument("--feature-name-and-term-set-path")
    p.add_argument("--feature-shard-id-to-feature-section-keys-map",
                   required=True)
    p.add_argument("--feature-shard-id-to-intercept-map", default="")
    p.add_argument("--updating-sequence", required=True)
    p.add_argument("--num-iterations", type=int, default=1)
    p.add_argument("--fixed-effect-data-configurations", default="")
    p.add_argument("--fixed-effect-optimization-configurations", default="")
    p.add_argument("--random-effect-data-configurations", default="")
    p.add_argument("--random-effect-optimization-configurations", default="")
    p.add_argument("--factored-random-effect-optimization-configurations",
                   default="")
    p.add_argument("--random-effect-block-buckets", type=int, default=1,
                   help="(N, D) size buckets for random-effect entity "
                        "blocks: >1 pads each size bucket only to its own "
                        "(rows, dims), cutting FLOPs/HBM on skewed entity "
                        "sizes (SURVEY hard part 1; factored coordinates "
                        "take the same buckets)")
    p.add_argument("--re-lane-compaction-chunk",
                   type=_parse_compaction_chunk, default=0,
                   help="solve random-effect entity blocks in iteration "
                        "chunks of this size, compacting still-active "
                        "lanes between chunks so converged entities stop "
                        "paying for the slowest lane's iteration count "
                        "(0 = one dispatch to max_iterations; costs one "
                        "small device fetch per chunk). 'auto' lets the "
                        "chunk-size controller pick and re-tune between "
                        "solves from the observed per-chunk active-lane "
                        "decay (the re_chunk_active_lanes signal)")
    p.add_argument("--re-entity-shards",
                   type=_parse_entity_shards, default=1,
                   help="partition random-effect entity blocks over this "
                        "many mesh entity shards (shard_map over the mesh "
                        "entity axis: per-shard lane compaction, on-device "
                        "psum score exchange) and shard the fixed-effect "
                        "weight update across the remaining data-axis "
                        "replicas. 'auto' = all local devices. Counts "
                        "that do not divide the device count fall back to "
                        "the largest divisor (logged); 1 (default) is the "
                        "unsharded path, bit-identical to before")
    add_precision_flags(p)
    p.add_argument("--cd-block-size", type=int, default=1,
                   help="solve this many coordinates per sweep "
                        "CONCURRENTLY against a stale device-resident "
                        "score total, then apply one fused correction "
                        "epilogue that re-canonicalizes the ids-order "
                        "total (one device fetch per block, 1/B "
                        "amortized syncs/update). 1 (default) = the "
                        "sequential sweep. Block updates use stale "
                        "partial scores, so trajectories match the "
                        "sequential sweep within tolerance — do not "
                        "raise this when coordinates' scores are "
                        "strongly coupled (see README 'Performance')")
    # default None (resolved to 1 single-process): multi-host must tell
    # an explicit pipeline-depth request apart from the argparse default
    # (its gang-synchronous worker has no pipeline to configure)
    p.add_argument("--cd-pipeline-depth", type=int, default=None,
                   choices=[0, 1],
                   help="1 (default): double-buffer coordinate updates "
                        "— dispatch the next solve against the previous "
                        "fused epilogue's device-resident outputs before "
                        "blocking on its fetch, overlapping host "
                        "dispatch with device compute (bit-identical "
                        "floats to the sequential sweep; recovery acts "
                        "one update late, rolling the speculative "
                        "dispatch back on divergence). 0: sequential "
                        "dispatch-then-fetch")
    p.add_argument("--random-effect-blocks-dir", default=None,
                   help="build random-effect entity blocks through the "
                        "STREAMED builder with np.memmap destinations "
                        "under this directory (one subdir per "
                        "coordinate): peak host RAM stays one part plus "
                        "O(N) scalar columns instead of CSR + all padded "
                        "blocks; blocks page to device per solve")
    p.add_argument("--max-shard-loss-frac", type=float, default=0.0,
                   help="degraded-mode ingest budget: a corrupt, "
                        "truncated, or persistently unreadable Avro "
                        "shard is QUARANTINED (skipped with a "
                        "ShardQuarantinedEvent and a recorded "
                        "data-coverage fraction) and training continues "
                        "on the surviving shards, as long as the lost "
                        "fraction stays within this budget; past it the "
                        "run aborts cleanly (exit code 3). 0 (default) "
                        "= strict: the first lost shard aborts")
    p.add_argument("--evaluator-type", default="")
    # default None (resolved to ALL single-process): multi-host must tell
    # an explicit model-output request apart from the argparse default
    p.add_argument("--model-output-mode", default=None,
                   choices=[ModelOutputMode.ALL, ModelOutputMode.BEST,
                            ModelOutputMode.NONE])
    p.add_argument("--num-output-files-for-random-effect-model", type=int,
                   default=1)
    p.add_argument("--compute-variance", default="false")
    p.add_argument("--delete-output-dir-if-exists", default="false")
    p.add_argument("--application-name", default="game-training")
    p.add_argument("--offheap-indexmap-dir",
                   help="pre-built off-heap feature index store "
                        "(one namespace per feature shard); skips scanning "
                        "the data for features")
    p.add_argument("--offheap-indexmap-num-partitions", type=int,
                   default=None,
                   help="must match the partition count the store was built "
                        "with (validated against the store's meta)")
    p.add_argument("--checkpoint-dir",
                   help="snapshot coordinate states after each CD sweep "
                        "(plus mid-sweep at the --checkpoint-every-"
                        "coordinates cadence) and auto-resume from the "
                        "latest INTACT snapshot (integrity-verified; "
                        "single-grid-point runs only). In multi-host mode "
                        "process 0 owns the snapshots and broadcasts the "
                        "restored state to the re-formed gang, so a "
                        "supervisor restart resumes training instead of "
                        "restarting it")
    p.add_argument("--checkpoint-every-coordinates", type=int, default=0,
                   help="with --checkpoint-dir: additionally snapshot "
                        "after every Nth coordinate update, so a crash "
                        "inside a long sweep replays at most N updates "
                        "instead of the whole sweep (0 = sweep-end only)")
    # Divergence recovery (game/coordinate_descent.RecoveryPolicy): guard
    # every coordinate update for non-finite states/objectives.
    p.add_argument("--recovery-policy", default="none",
                   choices=["none", "abort", "skip"],
                   help="divergence handling per coordinate update: none "
                        "(legacy fail-through), abort (retry then stop), "
                        "skip (retry then keep last-good state and "
                        "continue degraded)")
    p.add_argument("--recovery-max-retries", type=int, default=2,
                   help="damped retries from last-good state before the "
                        "exhausted action applies")
    p.add_argument("--recovery-damping", type=float, default=0.5,
                   help="per-retry step damping factor toward the "
                        "last-good state")
    p.add_argument("--recovery-max-consecutive-failures", type=int,
                   default=3,
                   help="abort after this many consecutive skipped "
                        "coordinate updates")
    p.add_argument("--recovery-quarantine-after", type=int, default=0,
                   help="per-coordinate failure budget: a coordinate "
                        "whose retries exhaust this many times is "
                        "QUARANTINED (frozen at last-good state, descent "
                        "continues without it) instead of burning the "
                        "global budget; 0 disables")
    # Cooperative preemption (utils/preempt.py): SIGTERM/SIGINT, a
    # wall-clock budget, and an external stop file all request the same
    # graceful stop — the CD loop finishes its current block, snapshots
    # at the commit barrier, and exits PREEMPTED_EXIT (75) for a
    # supervisor to relaunch with resume.
    p.add_argument("--max-train-seconds", type=float, default=0.0,
                   help="wall-clock budget measured from driver startup "
                        "(ingest + compile included, like a scheduler "
                        "quota); past it the run stops at the next "
                        "commit barrier, snapshots, and exits 75 "
                        "(preempted) for a clean requeue; 0 disables")
    p.add_argument("--stop-file", default=None,
                   help="cooperative external stop: when this path "
                        "exists the run stops at the next commit "
                        "barrier exactly like a SIGTERM (polled at "
                        "most every 0.25s)")
    # Worker supervision (multi-host only): relaunch this host's crashed
    # worker process with bounded exponential backoff + jitter.
    p.add_argument("--max-worker-restarts", type=int, default=0,
                   help="with --num-processes > 1: relaunch this host's "
                        "crashed worker up to N times (0 = unsupervised)")
    p.add_argument("--worker-backoff-base", type=float, default=1.0,
                   help="supervisor backoff base seconds (doubles per "
                        "restart)")
    p.add_argument("--worker-backoff-max", type=float, default=30.0,
                   help="supervisor backoff ceiling seconds")
    # Multi-host (multi-controller jax.distributed) execution: launch this
    # same driver once per host; each process ingests only its own share
    # of the avro part files (cli/game/training/Driver.scala:642-726 — the
    # driver IS the cluster program).
    p.add_argument("--num-processes", type=int, default=1,
                   help="total multi-host processes (1 = single-process)")
    p.add_argument("--process-id", type=int, default=0)
    p.add_argument("--coordinator", default=None,
                   help="host:port of process 0's jax.distributed "
                        "coordination service (required when "
                        "--num-processes > 1)")
    p.add_argument("--coordinator-timeout", type=int, default=60,
                   help="seconds to wait for the cluster to form before "
                        "failing fast (jax.distributed initialization "
                        "timeout)")
    p.add_argument("--heartbeat-timeout", type=int, default=100,
                   help="seconds without a peer heartbeat before the "
                        "cluster declares that process dead and errors "
                        "pending collectives")
    # Observability (photon_ml_tpu/obs): span tracing + metrics + run
    # manifest + stall heartbeat, all scoped to this run.
    p.add_argument("--trace-dir",
                   help="enable span tracing/metrics for this run and "
                        "write trace.json (Chrome trace events, "
                        "Perfetto-loadable), spans.jsonl, metrics.jsonl "
                        "(live heartbeat + final counters) and "
                        "run_manifest.json here; multi-host processes "
                        "write trace.<process_index>.json etc.")
    p.add_argument("--trace-heartbeat-seconds", type=float, default=10.0,
                   help="with --trace-dir: append a progress record to "
                        "metrics.jsonl every N seconds (<= 0 disables "
                        "the heartbeat thread)")
    p.add_argument("--trace-stall-seconds", type=float, default=120.0,
                   help="with --trace-dir: flag the run STALLED when no "
                        "span closes within this window (logged, counted "
                        "on the 'stalls' metric, marked in the heartbeat "
                        "records)")
    p.add_argument("--telemetry-endpoint",
                   help="with --trace-dir: stream span/heartbeat/"
                        "run-end records live as line-delimited JSON to "
                        "this consumer — host:port (TCP), "
                        "unix:/path.sock, or file:/path.jsonl; when a "
                        "socket consumer is absent or slow, records "
                        "fall back to <trace-dir>/telemetry.jsonl or "
                        "are dropped (counted on telemetry_dropped) — "
                        "the hot loop never blocks on telemetry. "
                        "tools/photon_status.py is the bundled consumer")
    p.add_argument("--device-telemetry", action="store_true",
                   help="with --trace-dir: arm the DEVICE plane — "
                        "xla.compile spans with cost_analysis flops/"
                        "bytes, retrace-cause records (which argument "
                        "changed shape/dtype/static value), heartbeat-"
                        "cadence hbm_bytes{device,kind} gauges, per-"
                        "coordinate HBM watermarks at the sweep drain, "
                        "and peak_hbm_bytes on the run_end record")
    ns = p.parse_args(argv)
    _check_telemetry_flags(p, ns)
    return ns


# Shared parse-time validation (cli/args.py); old private name kept.
_check_telemetry_flags = check_telemetry_flags


class GameTrainingDriver:
    """cli/game/training/Driver.scala analog."""

    def __init__(self, ns: argparse.Namespace,
                 logger: Optional[PhotonLogger] = None):
        self.ns = ns
        self.task = TaskType[ns.task_type]
        self.logger = logger or PhotonLogger(
            os.path.join(ns.output_dir, "game-training.log"), echo=False)
        self.section_keys = _parse_section_keys_map(
            ns.feature_shard_id_to_feature_section_keys_map)
        self.intercept_map = {
            k: parse_flag(v)
            for k, v in _parse_key_value_map(
                ns.feature_shard_id_to_intercept_map).items()}
        self.updating_sequence = [
            x.strip() for x in ns.updating_sequence.split(",") if x.strip()]
        self.fixed_data_configs = {
            k: FixedEffectDataConfiguration.parse(v)
            for k, v in _parse_key_value_map(
                ns.fixed_effect_data_configurations).items()}
        self.random_data_configs = {
            k: RandomEffectDataConfiguration.parse(v)
            for k, v in _parse_key_value_map(
                ns.random_effect_data_configurations).items()}
        self.fixed_opt_grid = _parse_opt_config_grid(
            ns.fixed_effect_optimization_configurations) or [{}]
        self.random_opt_grid = _parse_opt_config_grid(
            ns.random_effect_optimization_configurations) or [{}]
        self.factored_grid = _parse_factored_grid(
            ns.factored_random_effect_optimization_configurations) or [{}]
        self.evaluators = [EvaluatorSpec.parse(x)
                           for x in ns.evaluator_type.split(",") if x.strip()]

        self.index_maps: dict[str, IndexMap] = {}
        self.train_data: Optional[GameDataset] = None
        self.validate_data: Optional[GameDataset] = None
        self.train_ingest = None  # IngestPolicy of the training load
        self.validate_ingest = None
        self._events = None  # driver-wide event bus, built on first use
        # resolved --re-entity-shards: the GRANTED mesh entity-axis size
        # (run() resolves 'auto'/non-dividing counts against the devices)
        self._entity_shards = 1

    # -- pipeline ----------------------------------------------------------

    def prepare_feature_maps(self) -> None:
        """GAMEDriver.prepareFeatureMaps: per-shard index maps — off-heap
        store when --offheap-indexmap-dir is given (GAMEDriver.scala:90-97
        prepareFeatureMapsPalDB), else built from the feature name-and-term
        sets (default in-heap path)."""
        if getattr(self.ns, "offheap_indexmap_dir", None):
            from photon_ml_tpu.io.feature_index_job import load_feature_index

            # offheap=True, not autodetect: the flag explicitly requests the
            # off-heap store, so a dir without one fails loudly instead of
            # silently loading the JSON index into RAM (and skipping the
            # partition-count validation the flag exists to enforce)
            self.index_maps.update(load_feature_index(
                self.ns.offheap_indexmap_dir, sorted(self.section_keys),
                offheap=True,
                expected_partitions=getattr(
                    self.ns, "offheap_indexmap_num_partitions", None)))
            self.logger.info(
                f"off-heap feature maps: "
                f"{ {k: len(v) for k, v in self.index_maps.items()} }")
            return
        all_sections = sorted({s for secs in self.section_keys.values()
                               for s in secs})
        if self.ns.feature_name_and_term_set_path:
            sets = NameAndTermFeatureSets.load(
                self.ns.feature_name_and_term_set_path, all_sections)
        else:
            from photon_ml_tpu.utils.date_range import resolve_input_paths

            paths = resolve_input_paths(
                self.ns.train_input_dirs, self.ns.train_date_range,
                self.ns.train_date_range_days_ago)
            sets = NameAndTermFeatureSets.from_paths(
                paths, all_sections, policy=self._ingest_policy())
        for shard, sections in self.section_keys.items():
            self.index_maps[shard] = sets.index_map(
                sections, add_intercept=self.intercept_map.get(shard, True))
        self.logger.info(
            f"feature maps: "
            f"{ {k: len(v) for k, v in self.index_maps.items()} }")

    def _lane_chunk(self) -> int:
        c = int(self.ns.re_lane_compaction_chunk)
        return c if c == AUTO_COMPACTION_CHUNK else max(0, c)

    def _event_bus(self):
        """The driver-wide event bus: fault/recovery/quarantine AND
        shard-quarantine events all land in the warn log and (via the
        bridge) in the metrics stream. One emitter for the whole run so
        ingest and coordinate descent share listeners."""
        if self._events is None:
            from photon_ml_tpu.cli import build_event_bus

            self._events = build_event_bus(self.logger.warn)
        return self._events

    def _ingest_policy(self):
        from photon_ml_tpu.cli import build_ingest_policy

        return build_ingest_policy(self.ns.max_shard_loss_frac,
                                   events=self._event_bus(),
                                   warn=self.logger.warn)

    def _id_types(self) -> list[str]:
        id_types = {cfg.random_effect_type
                    for cfg in self.random_data_configs.values()}
        id_types |= {e.id_type for e in self.evaluators if e.id_type}
        return sorted(id_types)

    def prepare_game_dataset(self) -> None:
        from photon_ml_tpu.utils.date_range import resolve_input_paths

        train_paths = resolve_input_paths(
            self.ns.train_input_dirs, self.ns.train_date_range,
            self.ns.train_date_range_days_ago)
        self.train_ingest = self._ingest_policy()
        self.train_data = load_game_dataset_avro(
            train_paths, self.section_keys, self.index_maps,
            id_types=self._id_types(), response_required=True,
            policy=self.train_ingest)
        self.train_ingest.finish(log=self.logger.warn)
        self.logger.info(
            f"train dataset: {self.train_data.num_samples} samples "
            f"from {len(train_paths)} path(s), data coverage "
            f"{self.train_ingest.coverage_fraction:.1%}")
        if self.ns.validate_input_dirs:
            validate_paths = resolve_input_paths(
                self.ns.validate_input_dirs, self.ns.validate_date_range,
                self.ns.validate_date_range_days_ago)
            self.validate_ingest = self._ingest_policy()
            self.validate_data = load_game_dataset_avro(
                validate_paths, self.section_keys,
                self.index_maps, id_types=self._id_types(),
                response_required=True, policy=self.validate_ingest)
            self.validate_ingest.finish(log=self.logger.warn)

    def _build_coordinates(self, fixed_cfgs, random_cfgs, factored_cfgs
                           ) -> dict:
        """Driver.train :352-533: one coordinate per updating-sequence entry
        with this grid point's optimization configs."""
        coords = {}
        compute_variance = (
            parse_flag(self.ns.compute_variance))
        dtype = precision_dtype(getattr(self.ns, "precision", "f32"))
        quant = getattr(self.ns, "collective_quant", "none")
        for cid in self.updating_sequence:
            if cid in self.fixed_data_configs:
                data_cfg = self.fixed_data_configs[cid]
                opt_cfg = fixed_cfgs.get(
                    cid, GLMOptimizationConfiguration())
                ds = build_fixed_effect_dataset(
                    self.train_data, data_cfg.feature_shard_id,
                    dtype=dtype)
                coords[cid] = FixedEffectCoordinate(
                    dataset=ds,
                    problem=GLMOptimizationProblem(
                        config=opt_cfg, task=self.task,
                        compute_variances=compute_variance,
                        # with entity sharding on, the data-axis replicas
                        # also split the optimizer state / weight update
                        # (engages only when the data axis is > 1)
                        shard_weight_update=self._entity_shards > 1,
                        collective_quant=quant))
            elif cid in self.random_data_configs and cid in factored_cfgs:
                data_cfg = self.random_data_configs[cid]
                re_cfg, latent_cfg, mf_cfg = factored_cfgs[cid]
                ds = build_random_effect_dataset(
                    self.train_data, data_cfg, dtype=dtype,
                    num_buckets=max(
                        1, int(self.ns.random_effect_block_buckets)))
                coords[cid] = FactoredRandomEffectCoordinate(
                    dataset=ds,
                    problem=RandomEffectOptimizationProblem(
                        config=re_cfg, task=self.task,
                        lane_compaction_chunk=self._lane_chunk(),
                        collective_quant=quant),
                    latent_problem=GLMOptimizationProblem(
                        config=latent_cfg, task=self.task,
                        collective_quant=quant),
                    latent_dim=mf_cfg.num_factors,
                    num_inner_iterations=mf_cfg.max_number_iterations)
            elif cid in self.random_data_configs:
                data_cfg = self.random_data_configs[cid]
                opt_cfg = random_cfgs.get(
                    cid, GLMOptimizationConfiguration())
                num_buckets = max(
                    1, int(self.ns.random_effect_block_buckets))
                if getattr(self.ns, "random_effect_blocks_dir", None):
                    from photon_ml_tpu.game.dataset import (
                        build_random_effect_dataset_streamed,
                        dataset_row_stream,
                    )

                    ds = build_random_effect_dataset_streamed(
                        dataset_row_stream(self.train_data, data_cfg),
                        data_cfg,
                        raw_dim=self.train_data.shard_dim(
                            data_cfg.feature_shard_id),
                        num_buckets=num_buckets,
                        entity_axis_size=self._entity_shards,
                        blocks_dir=os.path.join(
                            self.ns.random_effect_blocks_dir, cid),
                        dtype=dtype)
                else:
                    ds = build_random_effect_dataset(
                        self.train_data, data_cfg,
                        num_buckets=num_buckets,
                        entity_axis_size=self._entity_shards,
                        dtype=dtype)
                coords[cid] = RandomEffectCoordinate(
                    dataset=ds,
                    problem=RandomEffectOptimizationProblem(
                        config=opt_cfg, task=self.task,
                        lane_compaction_chunk=self._lane_chunk(),
                        entity_shards=self._entity_shards,
                        collective_quant=quant))
            else:
                raise ValueError(
                    f"coordinate {cid!r} in updating sequence has no data "
                    f"configuration")
        return coords

    def _validation_evaluator(self):
        if self.validate_data is None or not self.evaluators:
            return None, None
        vd = self.validate_data
        labels = jnp.asarray(vd.responses)
        weights = jnp.asarray(vd.weights)

        # Entity-id columns resolved once; every validation pass then
        # computes ALL metrics with a single instrumented fetch
        # (evaluate_many), not one hidden sync per metric.
        ids_by_type, num_by_type = resolve_entity_ids(
            self.evaluators, vd.id_columns, vd.id_vocabs)

        def evaluator(scores):
            return evaluate_many(
                self.evaluators, scores, labels, weights,
                entity_ids_by_type=ids_by_type,
                num_entities_by_type=num_by_type)

        return evaluator, self.evaluators[0]

    def train(self) -> tuple:
        """Grid over opt-config combinations; each runs coordinate descent
        (Driver.train :324-350)."""
        evaluator, first_spec = self._validation_evaluator()
        if evaluator is not None:
            # Random-guess baseline per evaluator before training
            # (Driver.scala:307-311) — the floor every model must beat.
            rand = jnp.asarray(np.random.default_rng(0).uniform(
                size=self.validate_data.num_samples))
            for name, value in evaluator(rand).items():
                self.logger.info(
                    f"Random guessing based baseline evaluation metric for "
                    f"{name}: {value:.6f}")
        best = None  # (metric, result, combo_desc)
        results = []
        combos = list(itertools.product(
            self.fixed_opt_grid, self.random_opt_grid, self.factored_grid))
        ckpt_mgr = None
        resume_snapshot = None
        if self.ns.checkpoint_dir:
            from photon_ml_tpu.utils.checkpoint import CheckpointManager

            if len(combos) > 1:
                raise ValueError(
                    "--checkpoint-dir supports single-grid-point runs only "
                    f"(got {len(combos)} grid combinations)")
            ckpt_mgr = CheckpointManager(self.ns.checkpoint_dir)
            # integrity-verified: restore() falls back past truncated/
            # corrupt/partial step dirs to the newest intact snapshot; a
            # dir with steps but NO intact one raises (data loss must not
            # silently retrain from scratch), only an empty dir is fresh
            try:
                resume_snapshot = ckpt_mgr.restore()
            except FileNotFoundError:
                resume_snapshot = None
            if resume_snapshot is not None:
                self.logger.info(
                    f"resuming from checkpoint at sweep "
                    f"{resume_snapshot.get('sweep', resume_snapshot.get('iteration', 0))} "
                    f"coordinate "
                    f"{resume_snapshot.get('coordinate_index', 0)}")
        recovery = None
        events = None
        if self.ns.recovery_policy != "none":
            from photon_ml_tpu.game.coordinate_descent import RecoveryPolicy

            recovery = RecoveryPolicy(
                max_retries=self.ns.recovery_max_retries,
                on_exhausted=self.ns.recovery_policy,
                damping=self.ns.recovery_damping,
                max_consecutive_failures=(
                    self.ns.recovery_max_consecutive_failures),
                quarantine_after=self.ns.recovery_quarantine_after)
            # the shared driver bus: fault/recovery/quarantine counts
            # land in metrics.jsonl via the event-bus → metrics bridge
            events = self._event_bus()
        for gi, (f_cfgs, r_cfgs, fac_cfgs) in enumerate(combos):
            desc = (f"grid[{gi}]: fixed={ {k: v.render() for k, v in f_cfgs.items()} } "
                    f"random={ {k: v.render() for k, v in r_cfgs.items()} }")
            self.logger.info(desc)
            with timed_phase(f"train {desc}", self.logger):
                coords = self._build_coordinates(f_cfgs, r_cfgs, fac_cfgs)
                result = run_coordinate_descent(
                    coords, self.ns.num_iterations, self.task,
                    jnp.asarray(self.train_data.responses),
                    jnp.asarray(self.train_data.weights),
                    jnp.asarray(self.train_data.offsets),
                    validation_data=self.validate_data,
                    validation_evaluator=evaluator,
                    validation_metric=(first_spec.name if first_spec
                                       else None),
                    higher_is_better=(first_spec.better_than(1.0, 0.0)
                                      if first_spec else True),
                    logger=self.logger,
                    checkpoint_manager=ckpt_mgr,
                    checkpoint_every_coordinates=(
                        self.ns.checkpoint_every_coordinates),
                    resume_snapshot=resume_snapshot,
                    recovery=recovery,
                    events=events,
                    block_size=max(1, int(self.ns.cd_block_size)),
                    pipeline_depth=(1 if self.ns.cd_pipeline_depth is None
                                    else int(self.ns.cd_pipeline_depth)),
                    stop=getattr(self, "stop", None))
            if result.quarantined:
                self.logger.warn(
                    f"{desc}: quarantined coordinates (frozen at "
                    f"last-good state): {result.quarantined}")
            results.append((desc, result))
            metric = result.best_metric
            if metric is not None:
                if best is None or (first_spec.better_than(metric, best[0])):
                    best = (metric, result, desc)
        if best is None and results:
            # no validation: lowest training objective wins; a run resumed
            # past its last iteration has no new states — treat as neutral
            best_result = min(
                results,
                key=lambda dr: (dr[1].states[-1].objective
                                if dr[1].states else float("inf")))
            best = (None, best_result[1], best_result[0])
        return best, results

    def run(self) -> CoordinateDescentResult:
        from photon_ml_tpu.parallel.mesh import setup_default_mesh

        ns = self.ns
        if os.path.isdir(ns.output_dir) and os.listdir(ns.output_dir):
            if parse_flag(ns.delete_output_dir_if_exists):
                import shutil
                shutil.rmtree(ns.output_dir)
            elif os.path.exists(os.path.join(ns.output_dir, "best")):
                raise FileExistsError(
                    f"output dir {ns.output_dir} is not empty")
        os.makedirs(ns.output_dir, exist_ok=True)
        # Multi-chip: --re-entity-shards devices on the entity axis (auto =
        # all of them), the rest on the data axis; fixed-effect solves go
        # through the shard_map backend (see GLMOptimizationProblem.run),
        # random-effect blocks shard over the entity axis.
        import jax as _jax

        requested = int(getattr(ns, "re_entity_shards", 1))
        if requested == AUTO_ENTITY_SHARDS:
            requested = max(1, len(_jax.devices()))
        mesh = setup_default_mesh(num_entity=requested)
        from photon_ml_tpu.parallel.mesh import ENTITY_AXIS

        self._entity_shards = (int(mesh.shape.get(ENTITY_AXIS, 1))
                               if mesh is not None else 1)
        from photon_ml_tpu.obs.metrics import REGISTRY

        REGISTRY.gauge("re_entity_shards").set(self._entity_shards)
        if self._entity_shards > 1:
            self.logger.info(
                f"mesh-sharded GAME: {self._entity_shards} entity shards "
                f"(requested {requested})")
        with timed_phase("prepareFeatureMaps", self.logger):
            self.prepare_feature_maps()
        with timed_phase("prepareGameDataSet", self.logger):
            self.prepare_game_dataset()
        best, results = self.train()
        _, best_result, best_desc = best
        self.logger.info(f"best model: {best_desc}")
        quarantined_all = sorted({cid for _, r in results
                                  for cid in r.quarantined})
        if quarantined_all:
            self.logger.warn(
                f"run summary: {len(quarantined_all)} coordinate(s) "
                f"quarantined (frozen at last-good state): "
                f"{quarantined_all}")

        # Persist the training/validation record per grid point (the GAME
        # analog of the legacy driver's metrics.json; the reference only
        # logs these — cli/game/training/Driver.scala:557-592).
        def _finite(x):
            # strict-JSON artifact: a diverged grid point's NaN objective
            # must serialize as null, not the bare NaN token
            x = None if x is None else float(x)
            return x if x is not None and math.isfinite(x) else None

        record = {
            "best": {"description": best_desc,
                     "metric": _finite(best_result.best_metric)},
            "quarantined": quarantined_all,
            # degraded-ingest record: the surviving-shard fraction and
            # which shards were lost (the chaos campaign's coverage
            # assertion reads these)
            "data_coverage": (self.train_ingest.coverage_fraction
                              if self.train_ingest is not None else 1.0),
            "ingest": {
                "train": (self.train_ingest.summary()
                          if self.train_ingest is not None else None),
                "validate": (self.validate_ingest.summary()
                             if self.validate_ingest is not None
                             else None),
            },
            "grid": [
                {"description": desc,
                 "quarantined": result.quarantined,
                 "states": [
                     {"iteration": s.iteration,
                      "coordinate": s.coordinate_id,
                      "objective": _finite(s.objective),
                      "seconds": round(float(s.seconds), 3),
                      # per-entity convergence-reason counts for RE sweeps
                      # (RandomEffectOptimizationTracker.countsByConvergence)
                      "convergence_counts": (
                          s.tracker.counts_by_convergence()
                          if hasattr(s.tracker, "counts_by_convergence")
                          else None),
                      "validation_metrics": (
                          None if s.validation_metrics is None else
                          {k: _finite(v)
                           for k, v in s.validation_metrics.items()})}
                     for s in result.states]}
                for desc, result in results],
        }
        with open(os.path.join(ns.output_dir, "metrics.json"), "w") as fh:
            json.dump(record, fh, indent=1)

        output_mode = ns.model_output_mode or ModelOutputMode.ALL
        if output_mode != ModelOutputMode.NONE:
            entity_vocabs = dict(self.train_data.id_vocabs)
            model = (best_result.best_model if best_result.best_model
                     is not None else best_result.model)
            save_game_model(
                model, os.path.join(ns.output_dir, "best"),
                self.index_maps, entity_vocabs=entity_vocabs,
                num_output_files=ns.num_output_files_for_random_effect_model,
                task=self.task)
            if output_mode == ModelOutputMode.ALL:
                for gi, (_, result) in enumerate(results):
                    save_game_model(
                        result.model,
                        os.path.join(ns.output_dir, "output", f"grid-{gi}"),
                        self.index_maps, entity_vocabs=entity_vocabs,
                        num_output_files=(
                            ns.num_output_files_for_random_effect_model),
                        task=self.task)
        return best_result


def _check_multihost_args(ns: argparse.Namespace) -> None:
    """Multi-host config validation, run BEFORE any worker (or supervisor)
    starts: a deterministic config error must fail in under a second with
    the real message, not burn a supervisor's restart budget. Fails fast
    on flags the multi-host path does not implement — silently ignoring
    them would hand a user expecting the single-process driver's outputs
    (saved avro models, validation metrics, divergence recovery) nothing
    at all. --checkpoint-dir IS supported: process 0 owns the snapshots
    and the restored state is broadcast to the re-formed gang."""
    if not ns.coordinator:
        raise ValueError(
            "--coordinator host:port is required with --num-processes > 1")
    if not (ns.feature_name_and_term_set_path
            or getattr(ns, "offheap_indexmap_dir", None)):
        raise ValueError(
            "multi-host mode needs pre-built feature maps: pass "
            "--feature-name-and-term-set-path or --offheap-indexmap-dir "
            "(every process must hold identical maps)")
    unsupported = []
    # the argparse default (None) is not a request for model output; only
    # an EXPLICIT ALL/BEST is rejected
    if ns.model_output_mode not in (None, ModelOutputMode.NONE):
        unsupported.append(
            f"--model-output-mode {ns.model_output_mode} (only NONE: "
            f"results are written as multihost_result.p<i>.npz, not avro "
            f"model dirs)")
    if ns.validate_input_dirs:
        unsupported.append("--validate-input-dirs")
    if ns.evaluator_type.strip():
        unsupported.append("--evaluator-type")
    if ns.recovery_policy != "none":
        unsupported.append(
            "--recovery-policy (divergence recovery is wired into the "
            "single-process coordinate-descent loop only)")
    if ns.re_lane_compaction_chunk != 0:  # 0 is "off"; auto (-1) counts
        unsupported.append(
            "--re-lane-compaction-chunk (lane compaction gathers active "
            "lanes with per-chunk host round-trips; the multi-host solve "
            "keeps its entity axis mesh-sharded and runs the "
            "single-dispatch path)")
    if getattr(ns, "re_entity_shards", 1) != 1:  # 1 is "off"; auto counts
        unsupported.append(
            "--re-entity-shards (the multi-host worker already shards its "
            "entity axis over the global mesh via GSPMD; the explicit "
            "shard_map path is wired into the single-process driver only)")
    if ns.cd_block_size != 1:
        unsupported.append(
            "--cd-block-size (the multi-host worker runs its own "
            "gang-synchronous CD loop; block-parallel sweeps are wired "
            "into the single-process coordinate-descent loop only)")
    # the argparse default (None) passes; only an EXPLICIT depth request
    # is rejected — the multi-host worker has no pipeline to configure,
    # so accepting 0 or 1 would promise behavior that doesn't exist
    if ns.cd_pipeline_depth is not None:
        unsupported.append(
            "--cd-pipeline-depth (the multi-host worker runs its own "
            "gang-synchronous CD loop; there is no per-coordinate "
            "dispatch pipeline to configure there)")
    if ns.max_shard_loss_frac > 0:
        unsupported.append(
            "--max-shard-loss-frac (shard quarantine is wired into the "
            "single-process ingest; the multi-host workers must all "
            "agree on the surviving row set, which needs a gang-level "
            "coverage consensus that does not exist yet)")
    if unsupported:
        raise ValueError(
            "multi-host mode (--num-processes > 1) does not support: "
            + "; ".join(unsupported))
    if ns.checkpoint_dir and ns.process_id == 0 \
            and os.path.isdir(ns.checkpoint_dir):
        # An all-corrupt checkpoint dir is a TERMINAL condition: surface
        # it here, before any worker or supervisor starts, instead of
        # letting each restart burn a heartbeat timeout on the same
        # CheckpointCorruptionError inside the gang (only process 0 can
        # check — the other hosts need not share the filesystem).
        from photon_ml_tpu.utils.checkpoint import CheckpointManager

        CheckpointManager(ns.checkpoint_dir).raise_if_all_corrupt()


def _run_multihost(ns: argparse.Namespace) -> None:
    """Multi-host GAME training: route to the jax.distributed worker.

    Every process runs this same CLI with its own ``--process-id``; part
    files are round-robin split across processes so no process ever reads
    another's rows. Feature maps must be PRE-BUILT
    (--feature-name-and-term-set-path or --offheap-indexmap-dir) so all
    processes hold identical maps — the reference does the same with its
    standalone FeatureIndexingJob for large feature spaces.
    """
    from photon_ml_tpu.cli import clean_abort, preempted_exit
    from photon_ml_tpu.parallel.multihost import run_game_worker
    from photon_ml_tpu.utils.date_range import resolve_input_paths
    from photon_ml_tpu.utils.preempt import (
        PreemptionRequested,
        StopController,
    )

    # config was validated by _check_multihost_args in main() — the single
    # validation site, BEFORE any supervisor starts
    os.makedirs(ns.output_dir, exist_ok=True)
    driver = GameTrainingDriver(ns, logger=PhotonLogger(
        os.path.join(ns.output_dir,
                     f"game-training.p{ns.process_id}.log"), echo=False))
    # graceful stop, gang-consistent: any member's local flag (signal,
    # deadline, stop file) is allgathered at the worker's gang-
    # synchronous safe points, so ALL members stop at the same
    # coordinate and the collective snapshot stays coherent
    stop = StopController(max_train_seconds=ns.max_train_seconds,
                          stop_file=ns.stop_file)
    stop.install_signal_handlers()
    # per-process observability: each gang member writes its own
    # trace.<process_index>.json / metrics.<process_index>.jsonl; a
    # supervisor-relaunched worker preserves the crashed incarnation's
    # heartbeat/span evidence instead of truncating it
    from photon_ml_tpu.obs.run import start_observed_run_from_flags

    obs_run = start_observed_run_from_flags(
        ns, process_index=ns.process_id, num_processes=ns.num_processes,
        warn=driver.logger.warn,
        preserve_existing=bool(os.environ.get(_SUPERVISED_ENV)))
    try:
        driver.prepare_feature_maps()
        fixed_ids = [c for c in driver.updating_sequence
                     if c in driver.fixed_data_configs]
        re_ids = [c for c in driver.updating_sequence
                  if c in driver.random_data_configs]
        if len(fixed_ids) != 1 or not re_ids:
            raise ValueError(
                "multi-host mode needs exactly one fixed coordinate and "
                "at least one random-effect coordinate (plain or "
                "factored)")
        if (len(driver.fixed_opt_grid) > 1 or len(driver.random_opt_grid) > 1
                or len(driver.factored_grid) > 1):
            raise ValueError("multi-host mode supports a single grid point")
        f_cid = fixed_ids[0]
        extra_factored = set(driver.factored_grid[0]) - set(re_ids)
        if extra_factored:
            raise ValueError(
                f"factored configs for unknown coordinates: "
                f"{sorted(extra_factored)}")
        f_opt = driver.fixed_opt_grid[0].get(
            f_cid, GLMOptimizationConfiguration())
        random_coordinates = [
            (cid, driver.random_data_configs[cid],
             driver.random_opt_grid[0].get(
                 cid, GLMOptimizationConfiguration()),
             driver.factored_grid[0].get(cid))
            for cid in re_ids]

        # expand dirs to part files, then round-robin by process id
        from photon_ml_tpu.io.avro import expand_part_paths

        if not 0 <= ns.process_id < ns.num_processes:
            raise ValueError(
                f"--process-id {ns.process_id} out of range for "
                f"--num-processes {ns.num_processes}")
        paths = resolve_input_paths(
            ns.train_input_dirs, ns.train_date_range,
            ns.train_date_range_days_ago)
        files = expand_part_paths(paths)
        local_files = files[ns.process_id::ns.num_processes]
        if not local_files:
            raise ValueError(
                f"process {ns.process_id} received no part files "
                f"({len(files)} file(s) across {ns.num_processes} "
                "processes)")
        driver.logger.info(
            f"process {ns.process_id}/{ns.num_processes}: "
            f"{len(local_files)} of {len(files)} part file(s)")

        result = run_game_worker(
            ns.process_id, ns.num_processes, ns.coordinator, local_files,
            driver.section_keys, driver.index_maps,
            (f_cid, driver.fixed_data_configs[f_cid], f_opt),
            random_coordinates,
            driver.task, num_iterations=ns.num_iterations,
            num_buckets=max(1, int(ns.random_effect_block_buckets)),
            initialization_timeout=ns.coordinator_timeout,
            heartbeat_timeout=ns.heartbeat_timeout,
            # process 0 owns the snapshots; the restored state is
            # broadcast to the whole (re-formed) gang on startup
            checkpoint_dir=ns.checkpoint_dir,
            checkpoint_every_coordinates=ns.checkpoint_every_coordinates,
            # per-process subdir: two processes must not write the same
            # memmap files (the worker appends one subdir per coordinate)
            blocks_dir=(os.path.join(ns.random_effect_blocks_dir,
                                     f"p{ns.process_id}")
                        if ns.random_effect_blocks_dir else None),
            precision=getattr(ns, "precision", "f32"),
            collective_quant=getattr(ns, "collective_quant", "none"),
            stop=stop)

        # one npz per process: fixed coefficients + per-coordinate tables
        arrays = {
            "fixed": result["fixed"][f_cid],
            "objective": np.asarray(result["objective"]),
            "re_coordinate_ids": np.asarray(
                sorted(result["random_effect"])),
        }
        for cid, table in result["random_effect"].items():
            ids = sorted(table)
            arrays[f"re_ids__{cid}"] = np.asarray(ids)
            arrays[f"re_coefs__{cid}"] = (
                np.stack([table[i] for i in ids])
                if ids else np.zeros((0, 0)))
        np.savez(
            os.path.join(ns.output_dir,
                         f"multihost_result.p{ns.process_id}.npz"),
            **arrays)
        print(f"MULTIHOST_GAME_OK process={ns.process_id} "
              f"of={ns.num_processes} devices={result['global_devices']} "
              f"re_entity_axis={result['re_entity_axis_devices']} "
              f"re_coordinates={','.join(sorted(result['random_effect']))} "
              f"rows={result['rows_global']} "
              f"objective={result['objective']:.6f}", flush=True)
    except PreemptionRequested as e:
        # gang-consensus stop: every member raises at the same safe
        # point after the collective snapshot; each exits 75 so the
        # per-host supervisors requeue the whole gang
        if obs_run is not None:
            obs_run.set_exit_status("preempted",
                                    reason=f"{e.reason} step={e.step}")
        raise preempted_exit(e, log=driver.logger.warn) from None
    except KeyboardInterrupt:
        if obs_run is not None:
            obs_run.set_exit_status("abort", reason="KeyboardInterrupt")
        raise clean_abort(KeyboardInterrupt("interrupted by operator"),
                          log=driver.logger.error) from None
    except Exception as e:
        driver.logger.error(f"multi-host GAME training failed: {e}")
        if obs_run is not None:
            obs_run.set_exit_status("error",
                                    reason=f"{type(e).__name__}: {e}")
        raise
    finally:
        if obs_run is not None:
            obs_run.finish()
        driver.logger.close()


_SUPERVISED_ENV = "PHOTON_GAME_SUPERVISED"


def _run_supervised(ns: argparse.Namespace, argv: Sequence[str]) -> None:
    """Supervise this host's multi-host worker: re-exec the driver as a
    child process and relaunch it with bounded exponential backoff +
    jitter when it crashes (peer death included — the survivors error out
    within the heartbeat bound and every host's supervisor re-forms the
    gang on the coordinator). Restart counts land in the driver log and
    on stdout (``SUPERVISOR_OK worker=<pid> restarts=<n>``)."""
    import subprocess

    from photon_ml_tpu.parallel.multihost import (
        SupervisorExhaustedError,
        WorkerSupervisor,
    )

    os.makedirs(ns.output_dir, exist_ok=True)
    logger = PhotonLogger(
        os.path.join(ns.output_dir,
                     f"supervisor.p{ns.process_id}.log"), echo=False)
    name = f"worker p{ns.process_id}"

    def spawn(attempt: int):
        env = dict(os.environ)
        env[_SUPERVISED_ENV] = "1"
        logger.info(f"{name}: launch attempt {attempt}")
        return subprocess.Popen(
            [sys.executable, "-m",
             "photon_ml_tpu.cli.game_training_driver", *argv], env=env)

    sup = WorkerSupervisor(
        spawn, max_restarts=ns.max_worker_restarts,
        backoff_base_seconds=ns.worker_backoff_base,
        backoff_max_seconds=ns.worker_backoff_max,
        name=name, log=logger.warn)
    try:
        restarts = sup.run()
    except SupervisorExhaustedError as e:
        logger.error(f"{name}: {e}")
        logger.close()
        raise SystemExit(
            f"multi-host worker process {ns.process_id} failed permanently "
            f"after {e.restarts} restart(s); see the per-process driver "
            f"log under {ns.output_dir}") from e
    logger.info(f"{name}: completed with {restarts} restart(s)")
    logger.close()
    print(f"SUPERVISOR_OK worker=p{ns.process_id} restarts={restarts}",
          flush=True)


def main(argv: Optional[Sequence[str]] = None) -> None:
    enable_persistent_compile_cache()
    argv = list(argv) if argv is not None else sys.argv[1:]
    ns = parse_args(argv)
    if ns.num_processes > 1:
        _check_multihost_args(ns)
        if ns.max_worker_restarts > 0 and not os.environ.get(
                _SUPERVISED_ENV):
            return _run_supervised(ns, argv)
        return _run_multihost(ns)
    driver = GameTrainingDriver(ns)
    from photon_ml_tpu.cli import (
        clean_abort,
        clean_abort_types,
        preempted_exit,
    )
    from photon_ml_tpu.obs.run import start_observed_run_from_flags
    from photon_ml_tpu.utils.preempt import (
        PreemptionRequested,
        StopController,
    )

    # graceful stop: SIGTERM/SIGINT latch the flag (a second delivery
    # forces), --max-train-seconds starts counting NOW (ingest + compile
    # are inside the budget), --stop-file is polled at commit barriers
    stop = StopController(max_train_seconds=ns.max_train_seconds,
                          stop_file=ns.stop_file)
    stop.install_signal_handlers()
    driver.stop = stop
    # resolve --re-entity-shards before the manifest is written so it
    # records the GRANTED entity-axis size, not the 'auto' sentinel;
    # run() re-derives the same value when it builds the mesh
    from photon_ml_tpu.parallel.mesh import largest_entity_divisor
    import jax as _jax

    _ndev = len(_jax.devices())
    _req = int(getattr(ns, "re_entity_shards", 1))
    if _req == AUTO_ENTITY_SHARDS:
        _req = max(1, _ndev)
    ns.re_entity_shards = largest_entity_divisor(_ndev, _req)
    # under a supervisor (tools/photon_supervise.py or the multi-host
    # re-exec), a relaunched incarnation rotates the previous one's
    # telemetry to .prev instead of truncating the evidence
    obs_run = start_observed_run_from_flags(
        ns, warn=driver.logger.warn,
        preserve_existing=bool(os.environ.get(_SUPERVISED_ENV)))
    try:
        driver.run()
    except clean_abort_types() as e:
        # documented terminal conditions (shard loss over budget,
        # all-corrupt checkpoints, I/O down through its retries, an
        # unrecovered injected fault) end with the PHOTON_ABORT line and
        # exit code 3 — never a stack trace
        if obs_run is not None:  # the run_end record says WHY it ended
            obs_run.set_exit_status("abort",
                                    reason=f"{type(e).__name__}: {e}")
        raise clean_abort(e, log=driver.logger.error) from None
    except PreemptionRequested as e:
        # graceful stop honored at a commit barrier: the final snapshot
        # is already on disk; drain telemetry with status "preempted"
        # and exit 75 so a supervisor requeues us
        if obs_run is not None:
            obs_run.set_exit_status("preempted",
                                    reason=f"{e.reason} step={e.step}")
        raise preempted_exit(e, log=driver.logger.warn) from None
    except KeyboardInterrupt:
        # a forced interrupt (second Ctrl-C, or one delivered outside
        # the graceful-stop window) still ends with the clean-abort
        # discipline: run_end emitted, telemetry drained, no traceback
        if obs_run is not None:
            obs_run.set_exit_status("abort", reason="KeyboardInterrupt")
        raise clean_abort(KeyboardInterrupt("interrupted by operator"),
                          log=driver.logger.error) from None
    except Exception as e:
        driver.logger.error(f"GAME training failed: {e}")
        if obs_run is not None:
            obs_run.set_exit_status("error",
                                    reason=f"{type(e).__name__}: {e}")
        raise
    finally:
        if obs_run is not None:
            obs_run.finish()
        driver.logger.close()


if __name__ == "__main__":
    main()
