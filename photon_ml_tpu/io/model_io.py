"""GAME / GLM model serialization — the reference's on-disk model contract.

Re-design of the reference's model (de)serialization stack
(reference paths under photon-ml/src/main/scala/com/linkedin/photon/ml/):

- ``ModelProcessingUtils.saveGameModelsToHDFS`` / ``loadGameModelFromHDFS``
  (avro/model/ModelProcessingUtils.scala:44-106) — directory layout::

      <dir>/fixed-effect/<name>/id-info                  (1 line: featureShardId)
      <dir>/fixed-effect/<name>/coefficients/part-00000.avro
      <dir>/random-effect/<name>/id-info                 (2 lines: reType, shardId)
      <dir>/random-effect/<name>/coefficients/part-*.avro

  Coefficient files hold ``BayesianLinearModelAvro`` records: one per fixed
  effect (modelId = "fixed-effect"), one per entity for random effects
  (modelId = raw entityId), with sparse (name, term, value) means and
  optional variances (avro/AvroUtils.scala:172-194).
- ``modelClass`` interop: the reference stores the JVM class name and
  reflectively instantiates it (avro/AvroUtils.scala:208,231); we map those
  exact strings to :class:`TaskType` both ways.
- Matrix factorization: ``<dir>/<rowEffectType>/part-*.avro`` +
  ``<dir>/<colEffectType>/part-*.avro`` of ``LatentFactorAvro``
  (ModelProcessingUtils.scala:375-430).
- Scored items: ``ScoringResultAvro`` (avro/data/ScoreProcessingUtils.scala).
- Legacy text models: TSV ``name\\tterm\\tvalue\\tlambda`` sorted by value
  descending (util/IOUtils.scala:207-247 writeModelsInText).
"""

from __future__ import annotations

import logging
import os
from typing import Iterable, Optional

import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.io import schemas
from photon_ml_tpu.io.avro import (
    read_directory,
    read_records,
    write_container,
)
from photon_ml_tpu.io.index_map import IndexMap, feature_key, split_feature_key
from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.optimize.config import TaskType

logger = logging.getLogger(__name__)

# Directory-layout constants (reference avro/Constants.scala:22-25).
ID_INFO = "id-info"
COEFFICIENTS = "coefficients"
FIXED_EFFECT = "fixed-effect"
RANDOM_EFFECT = "random-effect"
DEFAULT_AVRO_FILE_NAME = "part-00000.avro"

# JVM class-name interop (avro/AvroUtils.scala:208 setModelClass /
# :231 Class.forName) — written verbatim so reference tooling can reload
# models we save, and vice versa.
_MODEL_CLASS_BY_TASK = {
    TaskType.LOGISTIC_REGRESSION:
        "com.linkedin.photon.ml.supervised.classification."
        "LogisticRegressionModel",
    TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM:
        "com.linkedin.photon.ml.supervised.classification."
        "SmoothedHingeLossLinearSVMModel",
    TaskType.LINEAR_REGRESSION:
        "com.linkedin.photon.ml.supervised.regression.LinearRegressionModel",
    TaskType.POISSON_REGRESSION:
        "com.linkedin.photon.ml.supervised.regression.PoissonRegressionModel",
}
_TASK_BY_MODEL_CLASS = {v: k for k, v in _MODEL_CLASS_BY_TASK.items()}


# ---------------------------------------------------------------------------
# GLM <-> BayesianLinearModelAvro record
# ---------------------------------------------------------------------------


def _vector_to_name_term_values(vec: np.ndarray, index_map: IndexMap
                                ) -> list[dict]:
    """Sparse (name, term, value) entries for the nonzeros of ``vec``
    (avro/AvroUtils.scala convertVectorAsArrayOfNameTermValueAvros)."""
    out = []
    for idx in np.flatnonzero(vec):
        key = index_map.key_of(int(idx))
        if key is None:
            continue
        name, term = split_feature_key(key)
        out.append({"name": name, "term": term, "value": float(vec[idx])})
    return out


def glm_to_record(model_id: str, model: GeneralizedLinearModel,
                  index_map: IndexMap) -> dict:
    """BayesianLinearModelAvro dict for one GLM
    (avro/AvroUtils.scala:172-194)."""
    means = np.asarray(model.coefficients.means, dtype=np.float64)
    record = {
        "modelId": model_id,
        "modelClass": _MODEL_CLASS_BY_TASK[model.task],
        "means": _vector_to_name_term_values(means, index_map),
        "variances": None,
        "lossFunction": "",
    }
    if model.coefficients.variances is not None:
        variances = np.asarray(model.coefficients.variances, np.float64)
        record["variances"] = _vector_to_name_term_values(variances, index_map)
    return record


def record_to_glm(record: dict, index_map: Optional[IndexMap] = None,
                  load_variances: bool = False,
                  default_task: TaskType = TaskType.LINEAR_REGRESSION
                  ) -> tuple[GeneralizedLinearModel, IndexMap]:
    """Rebuild a GLM from a BayesianLinearModelAvro dict
    (avro/AvroUtils.scala:203-241). Without an index map, a compact one is
    built from the record's own features (ModelProcessingUtils.scala:106-118
    load-without-index contract)."""
    if index_map is None:
        keys = [feature_key(f["name"], f["term"]) for f in record["means"]]
        keys += [feature_key(f["name"], f["term"])
                 for f in record.get("variances") or []]
        index_map = IndexMap.from_keys(keys)
    means = np.zeros(len(index_map))
    for f in record["means"]:
        key = feature_key(f["name"], f["term"])
        if key in index_map:
            means[index_map.index_of(key)] = f["value"]
    variances = None
    if load_variances and record.get("variances"):
        variances = np.zeros(len(index_map))
        for f in record["variances"]:
            key = feature_key(f["name"], f["term"])
            if key in index_map:
                variances[index_map.index_of(key)] = f["value"]
    task = _TASK_BY_MODEL_CLASS.get(record.get("modelClass") or "",
                                    default_task)
    coefficients = Coefficients(
        means=jnp.asarray(means, jnp.float32),
        variances=(None if variances is None
                   else jnp.asarray(variances, jnp.float32)))
    return GeneralizedLinearModel(coefficients, task), index_map


# ---------------------------------------------------------------------------
# GAME model directory save/load
# ---------------------------------------------------------------------------


def _write_id_info(path: str, lines: list[str]) -> None:
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")


def _read_id_info(path: str) -> list[str]:
    with open(path) as fh:
        return [ln for ln in fh.read().splitlines() if ln]


def save_game_model(model, output_dir: str,
                    index_maps: dict[str, IndexMap],
                    entity_vocabs: Optional[dict[str, np.ndarray]] = None,
                    num_output_files: int = 1,
                    task: TaskType = TaskType.LINEAR_REGRESSION) -> None:
    """Write a GameModel as the reference's directory layout
    (ModelProcessingUtils.scala:44-90; see module docstring for the tree).

    ``entity_vocabs[reType]`` maps entity codes → raw ids for random-effect
    coordinates whose models still reference dataset codes; coordinates that
    carry ``entity_ids`` need no vocab.
    """
    # Local imports: game.models imports nothing from here (no cycle), but
    # keep io importable without the game stack resolved at module load.
    from photon_ml_tpu.game.models import (
        FactoredRandomEffectModel,
        FixedEffectModel,
        MatrixFactorizationModel,
        RandomEffectModel,
        RandomEffectModelInProjectedSpace,
    )

    for name, sub in model.models.items():
        if isinstance(sub, (RandomEffectModelInProjectedSpace,
                            FactoredRandomEffectModel)):
            sub = sub.to_raw()
        if isinstance(sub, FixedEffectModel):
            out = os.path.join(output_dir, FIXED_EFFECT, name)
            os.makedirs(os.path.join(out, COEFFICIENTS), exist_ok=True)
            _write_id_info(os.path.join(out, ID_INFO), [sub.feature_shard_id])
            glm = sub.model
            record = glm_to_record(FIXED_EFFECT, glm,
                                   index_maps[sub.feature_shard_id])
            write_container(
                os.path.join(out, COEFFICIENTS, DEFAULT_AVRO_FILE_NAME),
                schemas.BAYESIAN_LINEAR_MODEL, [record])
        elif isinstance(sub, RandomEffectModel):
            out = os.path.join(output_dir, RANDOM_EFFECT, name)
            os.makedirs(os.path.join(out, COEFFICIENTS), exist_ok=True)
            _write_id_info(os.path.join(out, ID_INFO),
                           [sub.random_effect_type, sub.feature_shard_id])
            index_map = index_maps[sub.feature_shard_id]
            coefs = np.asarray(sub.coefficients)
            if sub.entity_ids is not None:
                raw_ids = np.asarray(sub.entity_ids)
            else:
                vocab = (entity_vocabs or {}).get(sub.random_effect_type)
                if vocab is None:
                    raise ValueError(
                        f"random effect '{name}' has no entity_ids and no "
                        f"vocab for '{sub.random_effect_type}' was passed")
                raw_ids = np.asarray(vocab)[np.asarray(sub.entity_codes)]
            records = []
            for e in range(coefs.shape[0]):
                # a host row: writing a file needs no device (this used to
                # be one host->device->host round trip per entity)
                glm = GeneralizedLinearModel(Coefficients(coefs[e]), task)
                records.append(glm_to_record(str(raw_ids[e]), glm, index_map))
            # Partitioned output (numberOfOutputFilesForRandomEffectModel).
            chunks = np.array_split(np.arange(len(records)),
                                    max(1, num_output_files))
            for part, idxs in enumerate(chunks):
                if len(chunks) > 1 and len(idxs) == 0:
                    continue
                write_container(
                    os.path.join(out, COEFFICIENTS, f"part-{part:05d}.avro"),
                    schemas.BAYESIAN_LINEAR_MODEL,
                    [records[i] for i in idxs])
        elif isinstance(sub, MatrixFactorizationModel):
            # The reference's saveGameModelsToHDFS handles only fixed/random
            # coordinates (ModelProcessingUtils.scala:53-88 match) — MF has
            # its own save path with no id-info marker, so a GAME-directory
            # load could not find it again. Refuse rather than lose it.
            raise TypeError(
                f"coordinate '{name}': MatrixFactorizationModel is saved "
                f"separately via save_matrix_factorization_model(), not in "
                f"the GAME model directory")
        else:
            raise TypeError(f"cannot serialize coordinate model {type(sub)}")


def load_game_model(input_dir: str,
                    index_maps: Optional[dict[str, IndexMap]] = None,
                    task: TaskType = TaskType.LINEAR_REGRESSION):
    """Load a GameModel directory (ModelProcessingUtils.scala:106-170).
    Returns ``(GameModel, {shardId: IndexMap})`` — index maps are rebuilt
    compactly from the model files when not provided, matching the
    reference's load-without-index contract."""
    from photon_ml_tpu.game.models import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )

    index_maps = dict(index_maps or {})
    models: dict = {}

    fixed_dir = os.path.join(input_dir, FIXED_EFFECT)
    if os.path.isdir(fixed_dir):
        for name in sorted(os.listdir(fixed_dir)):
            inner = os.path.join(fixed_dir, name)
            (shard_id,) = _read_id_info(os.path.join(inner, ID_INFO))
            _, records = read_directory(os.path.join(inner, COEFFICIENTS))
            glm, imap = record_to_glm(records[0], index_maps.get(shard_id),
                                      load_variances=True,
                                      default_task=task)
            index_maps.setdefault(shard_id, imap)
            models[name] = FixedEffectModel(glm, shard_id)

    re_dir = os.path.join(input_dir, RANDOM_EFFECT)
    empty_shards: dict = {}  # shard_id -> first empty coordinate seen
    if os.path.isdir(re_dir):
        for name in sorted(os.listdir(re_dir)):
            inner = os.path.join(re_dir, name)
            re_type, shard_id = _read_id_info(os.path.join(inner, ID_INFO))
            # A random-effect coordinate with no coefficients dir is a valid
            # empty model: the reference's RDD load over a pathless glob
            # yields zero per-entity GLMs — the checked-in
            # GameIntegTest/gameModel fixture ships exactly this layout
            # (random-effect/<name>/ holding only id-info). read_directory
            # itself handles a dir with no avro files.
            coeff_dir = os.path.join(inner, COEFFICIENTS)
            records = (read_directory(coeff_dir)[1]
                       if os.path.isdir(coeff_dir) else [])
            imap = index_maps.get(shard_id)
            if imap is None:
                # Union of all per-entity features → one compact map. An
                # EMPTY coordinate registers nothing: a zero-length map in
                # the returned dict would silently zero out that shard for
                # any dataset later built against these maps.
                keys = sorted({feature_key(f["name"], f["term"])
                               for r in records for f in r["means"]})
                imap = IndexMap.from_keys(keys)
                if records:
                    index_maps[shard_id] = imap
                else:
                    empty_shards.setdefault(shard_id, name)
            # Per-entity variances are discarded on load, matching the
            # reference (ModelProcessingUtils.scala:342 TODO: "only the
            # means of the coefficients are loaded").
            ids, rows = [], []
            for r in records:
                glm, _ = record_to_glm(r, imap, default_task=task)
                ids.append(r["modelId"])
                rows.append(np.asarray(glm.coefficients.means))
            coefs = (np.stack(rows) if rows
                     else np.zeros((0, len(imap)), np.float32))
            models[name] = RandomEffectModel(
                random_effect_type=re_type,
                feature_shard_id=shard_id,
                entity_codes=np.arange(len(ids)),
                coefficients=jnp.asarray(coefs),
                entity_ids=np.asarray(ids, dtype=object))

    # Warn only for shards that REMAIN unserved: another (non-empty)
    # coordinate sharing the feature shard may have registered a map.
    for shard_id, name in empty_shards.items():
        if shard_id not in index_maps:
            logger.warning(
                "random-effect coordinate %r is empty and no index map was "
                "supplied for feature shard %r; the shard is omitted from "
                "the returned index maps — building a dataset against these "
                "maps will not serve shard %r", name, shard_id, shard_id)

    if not models:
        raise FileNotFoundError(f"no models under {input_dir}")
    return GameModel(models), index_maps


# ---------------------------------------------------------------------------
# Matrix factorization (LatentFactorAvro)
# ---------------------------------------------------------------------------


def save_matrix_factorization_model(
        model, output_dir: str,
        entity_vocabs: Optional[dict[str, np.ndarray]] = None,
        num_output_files: int = 1) -> None:
    """``<dir>/<rowEffectType>/part-*.avro`` etc. of LatentFactorAvro
    (ModelProcessingUtils.scala:375-400)."""
    for effect_type, factors, ids in (
            (model.row_effect_type, model.row_factors, model.row_ids),
            (model.col_effect_type, model.col_factors, model.col_ids)):
        out = os.path.join(output_dir, effect_type)
        os.makedirs(out, exist_ok=True)
        arr = np.asarray(factors, np.float64)
        if ids is None:
            vocab = (entity_vocabs or {}).get(effect_type)
            if vocab is not None and len(vocab) < len(arr):
                raise ValueError(
                    f"entity vocab for '{effect_type}' has {len(vocab)} "
                    f"entries but the factor table has {len(arr)} rows")
            ids = (np.asarray(vocab)[:len(arr)] if vocab is not None
                   else np.arange(len(arr)))
        records = [{"effectId": str(ids[i]),
                    "latentFactor": [float(v) for v in arr[i]]}
                   for i in range(len(arr))]
        chunks = np.array_split(np.arange(len(records)),
                                max(1, num_output_files))
        for part, idxs in enumerate(chunks):
            write_container(os.path.join(out, f"part-{part:05d}.avro"),
                            schemas.LATENT_FACTOR,
                            [records[i] for i in idxs])


def load_matrix_factorization_model(input_dir: str, row_effect_type: str,
                                    col_effect_type: str):
    """ModelProcessingUtils.scala:413-430 analog."""
    from photon_ml_tpu.game.models import MatrixFactorizationModel

    tables = {}
    for effect_type in (row_effect_type, col_effect_type):
        _, records = read_directory(os.path.join(input_dir, effect_type))
        ids = np.asarray([r["effectId"] for r in records], dtype=object)
        factors = (np.asarray([r["latentFactor"] for r in records],
                              np.float32)
                   if records else np.zeros((0, 0), np.float32))
        tables[effect_type] = (ids, factors)
    (row_ids, row_factors) = tables[row_effect_type]
    (col_ids, col_factors) = tables[col_effect_type]
    return MatrixFactorizationModel(
        row_effect_type=row_effect_type, col_effect_type=col_effect_type,
        row_factors=jnp.asarray(row_factors),
        col_factors=jnp.asarray(col_factors),
        row_ids=row_ids, col_ids=col_ids)


# ---------------------------------------------------------------------------
# Scored items (ScoringResultAvro — avro/data/ScoreProcessingUtils.scala)
# ---------------------------------------------------------------------------


def save_scored_items(path: str, scores: np.ndarray, model_id: str,
                      uids: Optional[Iterable] = None,
                      labels: Optional[np.ndarray] = None,
                      weights: Optional[np.ndarray] = None) -> None:
    """ScoringResultAvro output (ScoreProcessingUtils analog). Record
    bytes encode natively (native/score_encoder.cpp) when available —
    scoring output is a per-record hot path at the 20M-row target — with
    the dict-record writer as fallback and semantic reference."""
    scores = np.asarray(scores, np.float64)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)

    from photon_ml_tpu.io.avro import DEFAULT_SYNC_INTERVAL
    from photon_ml_tpu.io.native_loader import encode_scores_native

    n = len(scores)
    uid_arr = None if uids is None else np.asarray(list(uids), dtype=object)
    blocks: Optional[list] = []
    # write_container's block granularity: bounded memory per block and
    # sync markers splittable readers can seek to
    for lo in range(0, n, DEFAULT_SYNC_INTERVAL):
        hi = min(lo + DEFAULT_SYNC_INTERVAL, n)
        raw = encode_scores_native(
            scores[lo:hi], model_id,
            uids=None if uid_arr is None else uid_arr[lo:hi],
            labels=None if labels is None else labels[lo:hi],
            weights=None if weights is None else weights[lo:hi])
        if raw is None:
            blocks = None
            break
        blocks.append((hi - lo, raw))
    if blocks is not None and n > 0:
        _write_container_raw(path, schemas.SCORING_RESULT, blocks)
        return
    if blocks is not None:  # n == 0: empty container, no blocks
        _write_container_raw(path, schemas.SCORING_RESULT, [])
        return

    uid_list = None if uids is None else [str(u) for u in uids]
    records = []
    for i in range(len(scores)):
        records.append({
            "uid": None if uid_list is None else uid_list[i],
            "label": None if labels is None else float(labels[i]),
            "modelId": model_id,
            "predictionScore": float(scores[i]),
            "weight": None if weights is None else float(weights[i]),
            "metadataMap": None,
        })
    write_container(path, schemas.SCORING_RESULT, records)


def _write_container_raw(path: str, schema,
                         blocks: list) -> None:
    """Container framing around already-encoded record streams, one Avro
    block per (count, record_bytes) entry — the same header/codec/sync
    layout and block granularity write_container produces."""
    import io as _io
    import zlib as _zlib

    from photon_ml_tpu.io.avro import (
        SYNC_SIZE,
        BinaryEncoder,
        parse_schema,
        write_container_header,
    )

    schema = parse_schema(schema)
    sync = os.urandom(SYNC_SIZE)
    with open(path, "wb") as fh:
        write_container_header(fh, schema, "deflate", sync)
        for count, record_bytes in blocks:
            if not count:
                continue
            packed = _zlib.compress(record_bytes)[2:-1]  # raw deflate
            head = _io.BytesIO()
            henc = BinaryEncoder(head)
            henc.write_long(count)
            henc.write_long(len(packed))
            fh.write(head.getvalue())
            fh.write(packed)
            fh.write(sync)


def load_scored_items(path: str) -> list[dict]:
    return read_records(path)


# ---------------------------------------------------------------------------
# Legacy text model IO (util/IOUtils.scala:207-247)
# ---------------------------------------------------------------------------


def write_models_text(output_dir: str,
                      models: Iterable[tuple[float, GeneralizedLinearModel]],
                      index_map: IndexMap) -> None:
    """One ``<lambda>.txt`` per model: ``name\\tterm\\tvalue\\tlambda`` rows
    sorted by coefficient value descending."""
    os.makedirs(output_dir, exist_ok=True)
    for part, (reg_weight, model) in enumerate(models):
        means = np.asarray(model.coefficients.means, np.float64)
        order = np.argsort(-means, kind="stable")
        lines = []
        for idx in order:
            key = index_map.key_of(int(idx))
            if key is None:
                continue
            name, term = split_feature_key(key)
            lines.append(f"{name}\t{term}\t{means[idx]}\t{reg_weight}")
        with open(os.path.join(output_dir, f"part-{part:05d}.txt"),
                  "w") as fh:
            fh.write("\n".join(lines) + "\n")


def read_models_text(input_dir: str, index_map: Optional[IndexMap] = None,
                     task: TaskType = TaskType.LINEAR_REGRESSION
                     ) -> list[tuple[float, GeneralizedLinearModel]]:
    out = []
    for fname in sorted(os.listdir(input_dir)):
        if not fname.endswith(".txt"):
            continue
        entries = []
        with open(os.path.join(input_dir, fname)) as fh:
            for line in fh:
                if not line.strip():
                    continue
                name, term, value, lam = line.rstrip("\n").split("\t")
                entries.append((name, term, float(value), float(lam)))
        if not entries:
            continue
        imap = index_map or IndexMap.from_keys(
            [feature_key(n, t) for n, t, _, _ in entries])
        means = np.zeros(len(imap))
        for name, term, value, _ in entries:
            key = feature_key(name, term)
            if key in imap:
                means[imap.index_of(key)] = value
        out.append((entries[0][3], GeneralizedLinearModel(
            Coefficients(jnp.asarray(means, jnp.float32)), task)))
    return out
