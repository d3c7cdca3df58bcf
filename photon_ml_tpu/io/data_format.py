"""Input data formats: Avro / LibSVM → columnar datasets, GAME ingestion.

Re-design of the reference's ingestion stack (reference paths under
photon-ml/src/main/scala/com/linkedin/photon/ml/):

- ``InputDataFormat`` family (io/InputDataFormat.scala:26-50,
  io/InputFormatFactory.scala:24-40): pluggable AVRO vs LIBSVM loaders for
  the legacy single-GLM path. Output here is columnar (CSR features +
  label/offset/weight arrays) instead of an RDD of LabeledPoint — the TPU
  batch layouts in data/batch.py consume these directly.
- ``GLMSuite`` (io/GLMSuite.scala:98-260): avro → LabeledPoint with default
  index-map build, selected-features filter, intercept injection, and the
  JSON box-constraint map (wildcard semantics, :207-260).
- ``FieldNames`` (avro/FieldNames.scala:23-29): TRAINING_EXAMPLE uses
  "label" (avro/TrainingExampleFieldNames.scala:26),
  RESPONSE_PREDICTION uses "response" (avro/ResponsePredictionFieldNames
  .scala:26) — selected by the legacy ``--format`` flag.
- GAME ingestion (avro/data/DataProcessingUtils.scala:57-215): per record,
  one sparse vector per feature *shard* (a union of feature *sections* =
  record fields), response/offset/weight, id columns read from top-level
  fields or metadataMap, intercept appended when the shard's index map
  carries the intercept key.
- ``NameAndTermFeatureSetContainer`` (avro/data/NameAndTermFeatureSet
  Container.scala:38-127): per-section (name, term) sets → index maps;
  text-file save/load (``name\\tterm`` lines).
"""

from __future__ import annotations

import dataclasses
import enum
import json
import logging
import os
from typing import Iterable, Optional, Sequence

import numpy as np
import scipy.sparse as sp

from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.io.avro import read_records as _read_records
from photon_ml_tpu.io.avro import read_shard as _read_shard
from photon_ml_tpu.io.index_map import (
    DELIMITER,
    INTERCEPT_KEY,
    IndexMap,
    feature_key,
)
from photon_ml_tpu.utils.faults import fault_point
from photon_ml_tpu.utils.retry import RetryExhaustedError, call_with_retry

WILDCARD = "*"  # io/GLMSuite.scala:377

# Avro field names (avro/AvroFieldNames.scala:21-28).
NAME, TERM, VALUE = "name", "term", "value"
RESPONSE, OFFSET, WEIGHT, UID = "response", "offset", "weight", "uid"
META_DATA_MAP = "metadataMap"


class InputFormatType(enum.Enum):
    """io/InputFormatType.scala analog."""

    AVRO = "AVRO"
    LIBSVM = "LIBSVM"


@dataclasses.dataclass(frozen=True)
class FieldNames:
    """avro/FieldNames.scala:23-29 analog."""

    features: str = "features"
    response: str = "label"
    offset: str = "offset"
    weight: str = "weight"


TRAINING_EXAMPLE_FIELD_NAMES = FieldNames(response="label")
RESPONSE_PREDICTION_FIELD_NAMES = FieldNames(response="response")


@dataclasses.dataclass
class LabeledData:
    """Columnar legacy dataset (the RDD[LabeledPoint] analog)."""

    features: sp.csr_matrix  # [N, D]
    labels: np.ndarray  # [N]
    offsets: np.ndarray  # [N]
    weights: np.ndarray  # [N]
    index_map: IndexMap

    @property
    def num_samples(self) -> int:
        return self.features.shape[0]

    @property
    def dim(self) -> int:
        return self.features.shape[1]


# ---------------------------------------------------------------------------
# Legacy Avro → LabeledData (GLMSuite analog)
# ---------------------------------------------------------------------------


def load_selected_features(path: str) -> set[str]:
    """Selected-features avro file → set of feature keys
    (io/GLMSuite.scala:141-149)."""
    return {feature_key(r[NAME], r.get(TERM) or "")
            for r in _read_records(path)}


def build_index_map_from_records(
        records: Iterable[dict],
        field_names: FieldNames = TRAINING_EXAMPLE_FIELD_NAMES,
        selected_features: Optional[set[str]] = None,
        add_intercept: bool = True) -> IndexMap:
    """Default index-map build: distinct feature keys in appearance-sorted
    order + optional intercept (io/GLMSuite.scala:159-205)."""
    keys: set[str] = set()
    for rec in records:
        for f in rec.get(field_names.features) or []:
            key = feature_key(f[NAME], f.get(TERM) or "")
            # None = no filtering; an empty SET means "select nothing"
            if selected_features is None or key in selected_features:
                keys.add(key)
    return IndexMap.from_keys(sorted(keys), add_intercept=add_intercept)


def _columnar_part_paths(path: str) -> list[str]:
    """Part files of a file-or-directory input (same set as
    read_directory)."""
    if os.path.isdir(path):
        from photon_ml_tpu.io.avro import list_avro_parts

        return list_avro_parts(path)
    return [path]


def _iter_columnar_parts(paths):
    """Yield per-part columnar reads ONE AT A TIME so ingestion memory is
    bounded by the largest part, not the input (the reference streams
    partitioned HDFS parts the same way, RandomEffectDataSet.scala:169-206).
    Yields None when a part can't take the native path — the caller must
    abandon the stream and fall back."""
    from photon_ml_tpu.io.native_avro import read_columnar

    for p in paths:
        yield read_columnar(p)


#: Sentinel: "this shard was quarantined — skip it, keep the fast path"
#: (distinct from None = "unsupported shape — fall back whole-input").
_QUARANTINED = object()


def _columnar_part_or_quarantine(path: str, policy):
    """``read_columnar`` under the degraded-ingest protocol: returns the
    columnar part, ``None`` for a shape the native decoder doesn't cover
    (caller falls back to the interpreted whole-input path), or
    :data:`_QUARANTINED` when the shard was lost to the policy.

    The native decoder DECLINES corrupt framing with ``None`` instead of
    raising (the interpreted reader owns the diagnostics), so on a None
    with a policy active the container FRAMING is probed once — no
    record decode — to tell a corrupt shard (quarantine it, keep the
    fast path for the rest) from a genuinely unsupported schema (fall
    back)."""
    from photon_ml_tpu.io.avro import check_container_framing
    from photon_ml_tpu.io.native_avro import read_columnar

    def attempt():
        fault_point("io.avro_read", tag=os.path.basename(path), path=path)
        return read_columnar(path)

    try:
        part = call_with_retry(attempt, site="io.avro_read")
    except (RetryExhaustedError, ValueError, FileNotFoundError) as e:
        if policy is None:
            raise
        policy.quarantine(path, stage=("decode" if isinstance(e, ValueError)
                                       else "open"), error=e)
        return _QUARANTINED
    if part is None and policy is not None:
        # the probe re-opens the file, so it gets the SAME retry
        # protocol as every other open: a transient EIO mid-probe must
        # not quarantine a healthy-but-unsupported shard
        try:
            call_with_retry(lambda: check_container_framing(path),
                            site="io.shard_open")
        except (RetryExhaustedError, ValueError, FileNotFoundError) as e:
            policy.quarantine(path,
                              stage=("decode" if isinstance(e, ValueError)
                                     else "open"), error=e)
            return _QUARANTINED
        return None
    if part is not None and policy is not None:
        policy.record_ok(path)
    return part


def _feature_col_ok(col) -> bool:
    """A feature array column usable by :func:`_feature_triples`: record
    items with STRING name/term (interned codes) and a numeric value."""
    from photon_ml_tpu.io.native_avro import OP_STRING as _OP_STRING

    if col is None or "subs" not in col:
        return False
    subs = col["subs"]
    if any(k not in subs for k in (NAME, TERM, VALUE)):
        return False
    if any(subs[k].get("op") != _OP_STRING for k in (NAME, TERM)):
        return False
    return subs[VALUE].get("op") != _OP_STRING


def _unique_name_terms(subs, with_inverse: bool = True):
    """Interned name/term sub-columns → (per-entry unique-pair ids,
    unique (name, term) pair list) — ONE encode/decode of the pair trick
    shared by the loaders and the feature-map scan. ``with_inverse=False``
    (the scan) skips the per-entry inverse array entirely."""
    name_codes = subs[NAME]["codes"].astype(np.int64)
    name_uniq = subs[NAME]["uniq"]
    term_codes = subs[TERM]["codes"]
    term_uniq = subs[TERM]["uniq"]
    nt = max(len(term_uniq), 1)
    pair = name_codes * nt + term_codes
    if with_inverse:
        upair, inv_p = np.unique(pair, return_inverse=True)
    else:
        upair, inv_p = np.unique(pair), None
    upairs = [(str(name_uniq[p // nt]), str(term_uniq[p % nt]))
              for p in upair]
    return inv_p, upairs


def _feature_triples(col, num_prior_rows_total: int):
    """array<record> feature column → (row_of_entry, key_of_entry arrays).

    Names/terms arrive INTERNED from the native decoder (int32 codes +
    unique tables), so keys are composed once per unique (name, term)
    pair; the per-entry work is integer arithmetic only."""
    lengths = col["lengths"]
    values = col["subs"][VALUE]["values"]
    rows = np.repeat(
        np.arange(len(lengths), dtype=np.int64) + num_prior_rows_total,
        lengths)
    inv_p, upairs = _unique_name_terms(col["subs"])
    ukeys = [feature_key(n, t) for n, t in upairs]
    return rows, inv_p, ukeys, values


def _columnar_labeled_points(
        path: str,
        field_names: FieldNames,
        index_map: Optional[IndexMap],
        selected: Optional[set],
        add_intercept: bool) -> Optional[LabeledData]:
    """Vectorized assembly from native columnar reads, streamed part by
    part (each part's columns are released before the next loads); None →
    caller falls back to the per-record interpreted path."""
    lab_parts, off_parts, wt_parts = [], [], []
    all_rows, all_keyid, all_vals = [], [], []
    key_tables = []
    keys_before = 0
    base = 0
    got_any = False
    for part in _iter_columnar_parts(_columnar_part_paths(path)):
        if part is None:
            return None
        got_any = True
        _, count, cols = part
        r = cols.get(field_names.response)
        if r is None or "values" not in r:
            return None
        if r.get("nulls") is not None and r["nulls"].any():
            # interpreted path raises on a null response — keep that
            return None
        if not _feature_col_ok(cols.get(field_names.features)):
            return None
        for aux in (field_names.offset, field_names.weight):
            c = cols.get(aux)
            if c is not None and "values" not in c:
                # e.g. a string-typed offset the interpreted path parses —
                # silent 0/1 defaults would be wrong; fall back
                return None

        lab_parts.append(np.asarray(r["values"], dtype=float))
        off = cols.get(field_names.offset)
        off_parts.append(
            np.asarray(off["values"], dtype=float)  # nulls decode as 0
            if off is not None and "values" in off else np.zeros(count))
        wt = cols.get(field_names.weight)
        wt_parts.append(
            np.where(wt["nulls"] == 1, 1.0, wt["values"])
            if wt is not None and "values" in wt else np.ones(count))
        rows, keyid, ukeys, values = _feature_triples(
            cols[field_names.features], base)
        all_rows.append(rows)
        all_keyid.append(keyid + keys_before)
        all_vals.append(values)
        key_tables.append(ukeys)
        keys_before += len(ukeys)
        base += count
    if not got_any:
        return None

    n = base
    labels = np.concatenate(lab_parts) if lab_parts else np.zeros(0)
    offsets = np.concatenate(off_parts) if off_parts else np.zeros(0)
    weights = np.concatenate(wt_parts) if wt_parts else np.ones(0)
    rows = np.concatenate(all_rows) if all_rows else np.zeros(0, np.int64)
    keyid = np.concatenate(all_keyid) if all_keyid else np.zeros(0, np.int64)
    vals = np.concatenate(all_vals) if all_vals else np.zeros(0)
    ukeys: list[str] = [k for t in key_tables for k in t]

    if selected is not None:
        kept = np.asarray([k in selected for k in ukeys])
    else:
        kept = np.ones(len(ukeys), bool)
    if index_map is None:
        index_map = IndexMap.from_keys(
            [k for k, keep in zip(ukeys, kept) if keep],
            add_intercept=add_intercept)
    ucol = np.asarray([index_map.index_of(k) if keep else -1
                       for k, keep in zip(ukeys, kept)], np.int64)
    cols_of = ucol[keyid]
    ok = cols_of >= 0
    rows, cols_of, vals = rows[ok], cols_of[ok], vals[ok]

    d = len(index_map)
    rc = rows * np.int64(d) + cols_of
    if len(np.unique(rc)) != len(rc):
        raise ValueError("Duplicate feature in a record (same name+term "
                         "appears twice)")
    intercept_idx = index_map.intercept_index
    if intercept_idx is not None:
        rows = np.concatenate([rows, np.arange(n, dtype=np.int64)])
        cols_of = np.concatenate(
            [cols_of, np.full(n, intercept_idx, np.int64)])
        vals = np.concatenate([vals, np.ones(n)])
    features = sp.csr_matrix((vals, (rows, cols_of)), shape=(n, d))
    return LabeledData(features, labels, offsets, weights, index_map)


def load_labeled_points_avro(
        path: str,
        field_names: FieldNames = TRAINING_EXAMPLE_FIELD_NAMES,
        index_map: Optional[IndexMap] = None,
        selected_features_file: Optional[str] = None,
        add_intercept: bool = True) -> LabeledData:
    """Legacy avro ingestion (io/GLMSuite.scala:98-137 + toLabeledPoints):
    per record sparse features via the index map, intercept column set to 1
    when the map carries the intercept key, offset/weight defaults 0/1.

    Dispatches to the native columnar decoder (native/avro_columnar.cpp,
    ~20x at ingestion scale) and falls back to the interpreted per-record
    path when the library or schema shape is unavailable."""
    selected_early = (load_selected_features(selected_features_file)
                      if selected_features_file else None)
    fast = _columnar_labeled_points(path, field_names, index_map,
                                    selected_early, add_intercept)
    if fast is not None:
        return fast
    records = _read_records(path)
    selected = selected_early
    if index_map is None:
        index_map = build_index_map_from_records(
            records, field_names, selected, add_intercept)

    n, d = len(records), len(index_map)
    labels = np.zeros(n)
    offsets = np.zeros(n)
    weights = np.ones(n)
    rows, cols, vals = [], [], []
    intercept_idx = index_map.intercept_index
    for i, rec in enumerate(records):
        labels[i] = float(rec[field_names.response])
        if rec.get(field_names.offset) is not None:
            offsets[i] = float(rec[field_names.offset])
        if rec.get(field_names.weight) is not None:
            weights[i] = float(rec[field_names.weight])
        seen = set()
        for f in rec.get(field_names.features) or []:
            key = feature_key(f[NAME], f.get(TERM) or "")
            # selected-features filter applies even with a caller-provided
            # index map (GLMSuite's selected-feature semantics)
            if selected is not None and key not in selected:
                continue
            j = index_map.index_of(key)
            if j < 0:
                continue
            if j in seen:
                raise ValueError(f"Duplicate feature {key!r} in record {i}")
            seen.add(j)
            rows.append(i)
            cols.append(j)
            # a nullable numeric value decodes as 0.0, matching the native
            # columnar path (reference schemas are non-null)
            vals.append(0.0 if f[VALUE] is None else float(f[VALUE]))
        if intercept_idx is not None:
            rows.append(i)
            cols.append(intercept_idx)
            vals.append(1.0)
    features = sp.csr_matrix(
        (np.asarray(vals), (np.asarray(rows, np.int64),
                            np.asarray(cols, np.int64))),
        shape=(n, d))
    return LabeledData(features, labels, offsets, weights, index_map)


# ---------------------------------------------------------------------------
# LibSVM (io/LibSVMInputDataFormat.scala:31-77)
# ---------------------------------------------------------------------------


def load_libsvm(path: str, feature_dimension: int,
                use_intercept: bool = True, zero_based: bool = False,
                delim: str = " ", idx_value_delim: str = ":",
                binarize_labels: bool = True) -> LabeledData:
    """LibSVM text → LabeledData. Labels are binarized (>0 → 1) like the
    reference (``binarize_labels=False`` keeps the raw values, for format
    conversion of regression data); the intercept occupies the LAST column
    when enabled (IdentityIndexMapLoader semantics).

    Parsing dispatches to the native C++ parser (io/native_loader.py,
    mmap + multithreaded) when available and custom delimiters aren't
    requested; the Python row loop below is the fallback and the semantic
    reference."""
    true_dim = feature_dimension + 1 if use_intercept else feature_dimension
    # Skip hidden/underscore-prefixed files (_SUCCESS, .crc checksums) the
    # way the avro directory reader filters to *.avro.
    paths = ([os.path.join(path, p) for p in sorted(os.listdir(path))
              if not p.startswith((".", "_"))]
             if os.path.isdir(path) else [path])

    if delim == " " and idx_value_delim == ":":
        native = _load_libsvm_native(paths, feature_dimension,
                                     use_intercept, zero_based,
                                     binarize_labels)
        if native is not None:
            return native

    labels_list: list[float] = []
    rows, cols, vals = [], [], []
    i = 0
    for p in paths:
        with open(p) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                # Default delimiter = ANY run of whitespace, matching the
                # native parser exactly (tab-separated files parse the same
                # whether or not a compiler is present); custom delimiters
                # keep literal splitting.
                ts = line.split() if delim == " " else line.split(delim)
                label = float(ts[0])
                labels_list.append((1.0 if label > 0 else 0.0)
                                   if binarize_labels else label)
                for item in ts[1:]:
                    item = item.strip()
                    if not item:
                        continue
                    idx_s, val_s = item.split(idx_value_delim)
                    idx = int(idx_s) - (0 if zero_based else 1)
                    if not 0 <= idx < feature_dimension:
                        raise ValueError(
                            f"feature index {idx_s} out of range for "
                            f"feature_dimension={feature_dimension} "
                            f"(zero_based={zero_based})")
                    rows.append(i)
                    cols.append(idx)
                    vals.append(float(val_s))
                if use_intercept:
                    rows.append(i)
                    cols.append(true_dim - 1)
                    vals.append(1.0)
                i += 1
    n = len(labels_list)
    features = sp.csr_matrix(
        (np.asarray(vals), (np.asarray(rows, np.int64),
                            np.asarray(cols, np.int64))),
        shape=(n, true_dim))
    return _libsvm_labeled_data(features, np.asarray(labels_list),
                                feature_dimension, use_intercept)


def _libsvm_labeled_data(features: sp.csr_matrix, labels: np.ndarray,
                         feature_dimension: int,
                         use_intercept: bool) -> LabeledData:
    """LabeledData with the IdentityIndexMapLoader map (intercept LAST when
    enabled) — shared by the Python and native parse paths."""
    if use_intercept:
        keys = {str(i): i for i in range(feature_dimension)}
        keys[INTERCEPT_KEY] = feature_dimension
        index_map = IndexMap(keys)
    else:
        index_map = IndexMap.identity(feature_dimension)
    n = features.shape[0]
    return LabeledData(features, labels, np.zeros(n), np.ones(n), index_map)


def _load_libsvm_native(paths, feature_dimension: int, use_intercept: bool,
                        zero_based: bool,
                        binarize_labels: bool = True
                        ) -> Optional[LabeledData]:
    """Native-parser path of :func:`load_libsvm`; None → use Python loop."""
    from photon_ml_tpu.io.native_loader import parse_libsvm_native

    if not paths:
        return None  # empty-directory case: Python loop builds 0-row data
    parts = []
    for p in paths:
        out = parse_libsvm_native(p, zero_based)
        if out is None:
            return None
        parts.append(out)
    mats, labels_all = [], []
    for raw_labels, mat, dim in parts:
        if dim > feature_dimension:
            raise ValueError(
                f"feature index {dim - 1 + (0 if zero_based else 1)} out of "
                f"range for feature_dimension={feature_dimension} "
                f"(zero_based={zero_based})")
        n = mat.shape[0]
        mat = sp.csr_matrix((mat.data, mat.indices, mat.indptr),
                            shape=(n, feature_dimension))
        if use_intercept:
            mat = sp.hstack([mat, np.ones((n, 1))], format="csr")
        mats.append(mat)
        labels_all.append((raw_labels > 0).astype(np.float64)
                          if binarize_labels
                          else np.asarray(raw_labels, np.float64))
    features = sp.vstack(mats, format="csr") if len(mats) > 1 else mats[0]
    return _libsvm_labeled_data(features, np.concatenate(labels_all),
                                feature_dimension, use_intercept)


# ---------------------------------------------------------------------------
# Box-constraint map (io/GLMSuite.scala:207-260)
# ---------------------------------------------------------------------------


def parse_constraint_map(constraint_string: Optional[str],
                         index_map: IndexMap
                         ) -> Optional[dict[int, tuple[float, float]]]:
    """JSON list of {name, term, lowerBound?, upperBound?} → per-index box
    bounds with the reference's wildcard rules: (*,*) applies to every
    non-intercept feature and must be the sole entry; (name,*) applies to
    all terms of ``name``; no wildcard names with concrete terms."""
    if not constraint_string:
        return None
    parsed = json.loads(constraint_string)
    out: dict[int, tuple[float, float]] = {}
    for entry in parsed:
        name = entry["name"]
        term = entry["term"]
        lo = float(entry.get("lowerBound", -np.inf))
        hi = float(entry.get("upperBound", np.inf))
        if not (np.isfinite(lo) or np.isfinite(hi)):
            raise ValueError(
                f"constraint for ({name}, {term}) has -Inf/+Inf bounds")
        if lo >= hi:
            raise ValueError(
                f"lower bound {lo} >= upper bound {hi} for ({name}, {term})")
        if name == WILDCARD:
            if term != WILDCARD:
                raise ValueError(
                    "wildcard name requires wildcard term")
            if out:
                raise ValueError(
                    "(*, *) constraint must be the only constraint")
            for key, idx in index_map.items():
                if key != INTERCEPT_KEY:
                    out[idx] = (lo, hi)
        elif term == WILDCARD:
            prefix = name + DELIMITER
            for key, idx in index_map.items():
                if key.startswith(prefix):
                    if idx in out:
                        raise ValueError(
                            f"conflicting bounds for feature {key!r}")
                    out[idx] = (lo, hi)
        else:
            key = feature_key(name, term)
            if key in index_map:
                idx = index_map.index_of(key)
                if idx in out:
                    raise ValueError(
                        f"conflicting bounds for feature {key!r}")
                out[idx] = (lo, hi)
    return out or None


# ---------------------------------------------------------------------------
# GAME ingestion (avro/data/DataProcessingUtils.scala:57-215)
# ---------------------------------------------------------------------------


def _id_from_record(rec: dict, id_type: str) -> str:
    """Top-level field first, then metadataMap
    (DataProcessingUtils.scala:91-115)."""
    v = rec.get(id_type)
    if v is None or v == "":
        meta = rec.get(META_DATA_MAP) or {}
        v = meta.get(id_type)
        if v is None:
            raise ValueError(
                f"Cannot find id in either record field {id_type!r} or in "
                f"metadataMap with key {id_type!r}")
    return str(v)


def _columnar_game_dataset(
        paths: Sequence[str],
        feature_shard_sections: dict[str, Sequence[str]],
        index_maps: dict[str, IndexMap],
        id_types: Sequence[str],
        response_required: bool,
        policy=None) -> Optional[GameDataset]:
    """Vectorized GAME assembly from native columnar reads (the 20M-row
    ingestion path), streamed part by part so peak memory is bounded by
    the largest part plus the assembled CSR (the reference streams
    partitioned HDFS parts through executors the same way,
    avro/data/DataProcessingUtils.scala per-partition map); None →
    interpreted fallback. Per-part feature keys are mapped through the
    index maps inside the stream, so string key tables never accumulate."""
    from photon_ml_tpu.io.native_avro import OP_LONG as _OP_LONG
    from photon_ml_tpu.io.native_avro import arena_strings

    sections_needed = sorted({s for secs in feature_shard_sections.values()
                              for s in secs})
    resp_parts, off_parts, wt_parts, uids_parts = [], [], [], []
    have_uid = False
    ids_parts: dict[str, list] = {t: [] for t in id_types}
    # per shard: filtered (rows, cols, vals) triples, index-mapped per part
    shard_acc: dict[str, list] = {s: [] for s in feature_shard_sections}
    base = 0
    part_files = [f for p in paths for f in _columnar_part_paths(p)]
    if policy is not None:
        policy.begin(len(part_files))
    for pf in part_files:
        part = _columnar_part_or_quarantine(pf, policy)
        if part is _QUARANTINED:
            continue  # shard lost; survivors keep streaming
        if part is None:
            return None
        schema, count, cols = part
        # --- structural validation (fall back on any mismatch) ---------
        field_types = {f["name"]: f["type"]
                       for f in (schema.get("fields", [])
                                 if isinstance(schema, dict) else [])}
        for sec in sections_needed:
            if not _feature_col_ok(cols.get(sec)):
                return None
            if isinstance(field_types.get(sec), list):
                # nullable section: the interpreted path raises a
                # per-record error for null sections — keep that contract
                return None
        u = cols.get(UID)
        if u is not None and "arena" not in u:
            # numeric uid: the interpreted path stringifies it — fall back
            return None
        for aux in (OFFSET, WEIGHT):
            c = cols.get(aux)
            if c is not None and "values" not in c:
                return None
        # top-level id fields: strings, or integer columns (str(int)
        # matches the interpreted path's str(v) exactly); float ids keep
        # the interpreted path
        for t in id_types:
            c = cols.get(t)
            if (c is not None and "arena" not in c
                    and c.get("op") != _OP_LONG):
                return None
        if response_required and (RESPONSE not in cols
                                  or "values" not in cols[RESPONSE]):
            return None

        # --- consume this part -----------------------------------------
        r = cols.get(RESPONSE)
        if r is not None and "values" in r:
            vals = r["values"].copy()
            null_mask = r["nulls"] == 1
            if response_required and null_mask.any():
                raise ValueError(
                    f"record {base + int(np.argmax(null_mask))} has no "
                    f"response field")
            vals[null_mask] = np.nan
            resp_parts.append(np.asarray(vals, dtype=float))
        elif response_required:
            raise ValueError(f"record {base} has no response field")
        else:
            resp_parts.append(np.full(count, np.nan))
        off = cols.get(OFFSET)
        off_parts.append(np.asarray(off["values"], dtype=float)
                         if off is not None and "values" in off
                         else np.zeros(count))
        wt = cols.get(WEIGHT)
        wt_parts.append(np.where(wt["nulls"] == 1, 1.0, wt["values"])
                        if wt is not None and "values" in wt
                        else np.ones(count))
        u = cols.get(UID)
        if u is not None and "arena" in u:
            s = arena_strings(u["arena"], u["offsets"], dedup=False)
            if (u["nulls"] == 0).any():
                have_uid = True
            s[u["nulls"] == 1] = ""
            uids_parts.append(s)
        else:
            uids_parts.append(np.full(count, "", dtype=object))

        ids_local = {t: np.full(count, None, dtype=object)
                     for t in id_types}
        for t in id_types:
            c = cols.get(t)
            if c is None:
                continue
            if "arena" in c:
                s = arena_strings(c["arena"], c["offsets"])
                ok = (c["nulls"] == 0) & (s != "")
                ids_local[t][ok] = s[ok]
            elif "values" in c:
                iv = c["values"].astype(np.int64)
                uniq, inv = np.unique(iv, return_inverse=True)
                s = np.asarray([str(int(u)) for u in uniq],
                               dtype=object)[inv]
                ok = c["nulls"] == 0
                ids_local[t][ok] = s[ok]
        m = cols.get(META_DATA_MAP)
        if m is not None and "key_codes" in m:
            pair_rows = np.repeat(
                np.arange(count, dtype=np.int64), m["lengths"])
            key_uniq = m["key_uniq"]
            for t in id_types:
                matches = np.flatnonzero(key_uniq == t)
                if len(matches) == 0:
                    continue
                hit = m["key_codes"] == matches[0]
                if hit.any():
                    rows_t = pair_rows[hit]
                    vals_t = m["val_uniq"][m["val_codes"][hit]]
                    still = np.asarray(
                        [ids_local[t][rr] is None for rr in rows_t])
                    # later map entries win like dict construction did
                    ids_local[t][rows_t[still]] = vals_t[still]
        for t in id_types:
            ids_parts[t].append(ids_local[t])

        for shard, sections in feature_shard_sections.items():
            imap = index_maps[shard]
            for sec in sections:
                rows, keyid, ukeys, values = _feature_triples(
                    cols[sec], base)
                ucol = np.asarray([imap.index_of(k) for k in ukeys],
                                  np.int64)
                c = ucol[keyid]
                ok = c >= 0
                shard_acc[shard].append((rows[ok], c[ok], values[ok]))
        base += count
    if base == 0 and not part_files:
        return None

    n = base
    responses = (np.concatenate(resp_parts) if resp_parts
                 else np.full(0, np.nan))
    offsets = np.concatenate(off_parts) if off_parts else np.zeros(0)
    weights = np.concatenate(wt_parts) if wt_parts else np.ones(0)
    ids_obj = {t: (np.concatenate(ids_parts[t]) if ids_parts[t]
                   else np.zeros(0, dtype=object)) for t in id_types}
    for t in id_types:
        missing = np.asarray([v is None for v in ids_obj[t]])
        if missing.any():
            raise ValueError(
                f"Cannot find id in either record field {t!r} or in "
                f"metadataMap with key {t!r}")

    shards = {}
    for shard, acc in shard_acc.items():
        imap = index_maps[shard]
        rows = (np.concatenate([a[0] for a in acc]) if acc
                else np.zeros(0, np.int64))
        cvec = (np.concatenate([a[1] for a in acc]) if acc
                else np.zeros(0, np.int64))
        vals = np.concatenate([a[2] for a in acc]) if acc else np.zeros(0)
        d = len(imap)
        rc = rows * np.int64(d) + cvec
        if len(np.unique(rc)) != len(rc):
            raise ValueError(
                f"Duplicate feature in a record for shard {shard!r}")
        intercept_idx = imap.intercept_index
        if intercept_idx is not None:
            rows = np.concatenate([rows, np.arange(n, dtype=np.int64)])
            cvec = np.concatenate(
                [cvec, np.full(n, intercept_idx, np.int64)])
            vals = np.concatenate([vals, np.ones(n)])
        shards[shard] = sp.csr_matrix((vals, (rows, cvec)), shape=(n, d))

    ds = GameDataset(responses=responses, feature_shards=shards,
                     offsets=offsets, weights=weights)
    for t in id_types:
        ds.encode_ids(t, np.asarray([str(v) for v in ids_obj[t]],
                                    dtype=object))
    if have_uid:
        ds.uids = np.concatenate(uids_parts).astype(object)
    return ds


def game_dataset_from_records(
        records: Sequence[dict],
        feature_shard_sections: dict[str, Sequence[str]],
        index_maps: dict[str, IndexMap],
        id_types: Sequence[str] = (),
        response_required: bool = True) -> GameDataset:
    """Decoded GAME records (dicts in the Avro record shape) →
    :class:`GameDataset`.

    This IS the interpreted assembly loop of
    :func:`load_game_dataset_avro`, shared verbatim with the serving
    request path (``photon_ml_tpu/serve``): a scoring request's NDJSON
    rows go through the same feature-key probing, duplicate detection,
    intercept append, and CSR canonicalization as an Avro part file —
    so service scores and batch-driver scores agree bit for bit by
    construction, not by test luck."""
    n = len(records)
    responses = np.full(n, np.nan)
    offsets = np.zeros(n)
    weights = np.ones(n)
    uids: Optional[list] = [] if any(
        r.get(UID) is not None for r in records) else None

    shard_builders = {
        shard: ([], [], []) for shard in feature_shard_sections}
    id_values: dict[str, list] = {t: [] for t in id_types}

    # index_of probes on an OffHeapIndexMap cost a hash + memmap search
    # each: features pay one probe per occurrence (not `in` + index_of),
    # and the per-shard intercept index is cached outside the record loop
    intercepts = {shard: index_maps[shard].intercept_index
                  for shard in feature_shard_sections}
    for i, rec in enumerate(records):
        if rec.get(RESPONSE) is not None:
            responses[i] = float(rec[RESPONSE])
        elif response_required:
            raise ValueError(f"record {i} has no response field")
        if rec.get(OFFSET) is not None:
            offsets[i] = float(rec[OFFSET])
        if rec.get(WEIGHT) is not None:
            weights[i] = float(rec[WEIGHT])
        if uids is not None:
            uids.append("" if rec.get(UID) is None else str(rec[UID]))
        for t in id_types:
            id_values[t].append(_id_from_record(rec, t))
        for shard, sections in feature_shard_sections.items():
            imap = index_maps[shard]
            rows, cols, vals = shard_builders[shard]
            seen = set()
            for section in sections:
                entries = rec.get(section)
                if entries is None:
                    raise ValueError(
                        f"record {i}: feature section {section!r} is not a "
                        f"list (or is null)")
                for f in entries:
                    key = feature_key(f[NAME], f.get(TERM) or "")
                    j = imap.index_of(key)
                    if j < 0:
                        continue
                    if j in seen:
                        raise ValueError(
                            f"Duplicate feature {key!r} in record {i} for "
                            f"shard {shard!r}")
                    seen.add(j)
                    rows.append(i)
                    cols.append(j)
                    vals.append(
                        0.0 if f[VALUE] is None else float(f[VALUE]))
            if intercepts[shard] is not None:
                rows.append(i)
                cols.append(intercepts[shard])
                vals.append(1.0)

    shards = {}
    for shard, (rows, cols, vals) in shard_builders.items():
        d = len(index_maps[shard])
        shards[shard] = sp.csr_matrix(
            (np.asarray(vals), (np.asarray(rows, np.int64),
                                np.asarray(cols, np.int64))),
            shape=(n, d))

    ds = GameDataset(responses=responses, feature_shards=shards,
                     offsets=offsets, weights=weights)
    for t in id_types:
        ds.encode_ids(t, np.asarray(id_values[t], dtype=object))
    if uids is not None:
        ds.uids = np.asarray(uids, dtype=object)
    return ds


def load_game_dataset_avro(
        path: str | Sequence[str],
        feature_shard_sections: dict[str, Sequence[str]],
        index_maps: dict[str, IndexMap],
        id_types: Sequence[str] = (),
        response_required: bool = True,
        policy=None) -> GameDataset:
    """Avro records → columnar :class:`GameDataset`: one CSR per feature
    shard (union of that shard's sections, intercept appended when the
    shard's index map has the intercept key), response/offset/weight
    columns, dictionary-encoded id columns, uids kept when present.

    ``path`` may be a single file/directory or a list of them (the dated
    daily-partition layout resolves to several directories). Dispatches to
    the native columnar decoder when available (falls back per schema
    shape).

    ``policy`` (an :class:`~photon_ml_tpu.data.ingest.IngestPolicy`)
    engages shard-level quarantine on BOTH decode paths: a corrupt,
    truncated, or persistently unreadable part file is skipped (with a
    ``ShardQuarantinedEvent`` and a recorded coverage fraction) instead
    of killing the load; past the policy's loss budget the load aborts
    cleanly with ``ShardLossExceededError``."""
    paths = [path] if isinstance(path, str) else list(path)
    fast = _columnar_game_dataset(paths, feature_shard_sections,
                                  index_maps, id_types, response_required,
                                  policy=policy)
    if fast is not None:
        return fast
    # said out loud: the interpreted reader is orders of magnitude slower,
    # and a missing toolchain or a stale library would otherwise only show
    # as a slow ingest
    logging.getLogger(__name__).warning(
        "native columnar Avro decoder unavailable or declined this input; "
        "using the interpreted Avro reader for %d path(s)", len(paths))
    if policy is not None:
        # shard-granular interpreted fallback: quarantine per part file
        part_files = [f for p in paths for f in _columnar_part_paths(p)]
        policy.begin(len(part_files))
        records = []
        for pf in part_files:
            out = _read_shard(pf, policy=policy)
            if out is not None:
                records.extend(out[1])
    elif isinstance(path, str):
        records = _read_records(path)
    else:
        records = [r for p in path for r in _read_records(p)]
    return game_dataset_from_records(
        records, feature_shard_sections, index_maps,
        id_types=id_types, response_required=response_required)


# ---------------------------------------------------------------------------
# NameAndTermFeatureSetContainer
# ---------------------------------------------------------------------------


class NameAndTermFeatureSets:
    """Per-section (name, term) sets → index maps; text save/load
    (avro/data/NameAndTermFeatureSetContainer.scala:38-127)."""

    def __init__(self, sets: dict[str, set[tuple[str, str]]]):
        self.sets = sets

    @staticmethod
    def from_records(records: Iterable[dict],
                     section_keys: Sequence[str]) -> "NameAndTermFeatureSets":
        sets: dict[str, set[tuple[str, str]]] = {
            k: set() for k in section_keys}
        for rec in records:
            for k in section_keys:
                for f in rec.get(k) or []:
                    sets[k].add((f[NAME], f.get(TERM) or ""))
        return NameAndTermFeatureSets(sets)

    @staticmethod
    def from_paths(paths: Sequence[str], section_keys: Sequence[str],
                   policy=None) -> "NameAndTermFeatureSets":
        """Feature-map scan over data files: columnar fast path when the
        native decoder handles every part (the unique name/term tables ARE
        the name-term sets — the scan never touches per-entry data), else
        the per-record loop (GAMEDriver.prepareFeatureMapsDefault's
        distinct() scan). ``policy`` quarantines corrupt/unreadable parts
        instead of failing the scan (same degraded-ingest protocol as the
        dataset load that follows it)."""
        # one FILE decoded at a time (directories expand to their part
        # files): the scan only keeps the (tiny) name-term sets, never a
        # whole decoded dataset
        from photon_ml_tpu.io.avro import list_avro_parts

        files: list[str] = []
        for p in paths:
            files.extend(list_avro_parts(p) if os.path.isdir(p) else [p])
        sets: dict[str, set[tuple[str, str]]] = {
            k: set() for k in section_keys}
        if policy is not None:
            policy.begin(len(files))
        ok = True
        for f in files:
            part = _columnar_part_or_quarantine(f, policy)
            if part is _QUARANTINED:
                continue
            if part is None:
                ok = False
                break
            _, _, cols = part
            for k in section_keys:
                if not _feature_col_ok(cols.get(k)):
                    ok = False
                    break
                _, upairs = _unique_name_terms(cols[k]["subs"],
                                               with_inverse=False)
                sets[k].update(upairs)
            if not ok:
                break
        if ok and files:
            return NameAndTermFeatureSets(sets)
        from photon_ml_tpu.io.avro import read_records as _rr

        if policy is not None:
            policy.begin(len(files))
        return NameAndTermFeatureSets.from_records(
            (r for p in paths for r in _rr(p, policy=policy)),
            section_keys)

    def index_map(self, section_keys: Sequence[str],
                  add_intercept: bool) -> IndexMap:
        """Union of the sections' features → one map
        (getFeatureNameAndTermToIndexMap :46-58)."""
        pairs = set()
        for k in section_keys:
            pairs |= self.sets.get(k, set())
        return IndexMap.from_name_terms(sorted(pairs),
                                        add_intercept=add_intercept)

    def save(self, directory: str) -> None:
        os.makedirs(directory, exist_ok=True)
        for section, pairs in self.sets.items():
            with open(os.path.join(directory, section), "w") as fh:
                for name, term in sorted(pairs):
                    fh.write(f"{name}\t{term}\n")

    @staticmethod
    def load(directory: str,
             section_keys: Sequence[str]) -> "NameAndTermFeatureSets":
        # feature maps are REQUIRED state — no quarantine here, but the
        # read retries transient I/O (drillable at io.index_map) and a
        # persistent failure surfaces as RetryExhaustedError, which the
        # drivers map to a clean abort
        def attempt():
            fault_point("io.index_map", tag=os.path.basename(directory))
            return NameAndTermFeatureSets._load_once(directory,
                                                     section_keys)

        return call_with_retry(attempt, site="io.index_map")

    @staticmethod
    def _load_once(directory: str,
                   section_keys: Sequence[str]) -> "NameAndTermFeatureSets":
        sets: dict[str, set[tuple[str, str]]] = {}
        for section in section_keys:
            pairs = set()
            with open(os.path.join(directory, section)) as fh:
                for line in fh:
                    line = line.rstrip("\n")
                    if not line:
                        continue
                    parts = line.split("\t")
                    if len(parts) == 1:
                        pairs.add((parts[0], ""))
                    elif len(parts) == 2:
                        pairs.add((parts[0], parts[1]))
                    else:
                        raise ValueError(
                            f"Unexpected entry {line!r}: expected 1 or 2 "
                            f"tab-separated tokens, found {len(parts)}")
            sets[section] = pairs
        return NameAndTermFeatureSets(sets)
