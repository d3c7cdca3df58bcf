"""GAME data layer: columnar dataset, per-coordinate views, score exchange.

TPU-native re-design of the reference's GAME data structures
(reference paths under photon-ml/src/main/scala/com/linkedin/photon/ml/):

- ``GameDatum`` (data/GameDatum.scala:33-54) — one row with response/offset/
  weight, per-feature-shard sparse vectors, and an idType→entityId map. Here
  the whole dataset is **columnar**: response/offset/weight arrays, one CSR
  matrix per feature shard, and integer entity-code columns per id type.
- ``FixedEffectDataSet`` (data/FixedEffectDataSet.scala:29-103) — an RDD of
  rows for one shard. Here: a device batch (dense or ELL) whose rows ARE the
  sample axis, sharded over the mesh ``data`` axis.
- ``RandomEffectDataSet`` (data/RandomEffectDataSet.scala:40-317) — active
  data grouped per entity (reservoir-capped), passive overflow, projection.
  Here: padded entity-major blocks ``[E, N_max, D_red]`` plus sample-major
  passive arrays; the sample↔entity layout exchange is a gather/scatter by
  row id (the Spark-shuffle analog, SURVEY §5.7).
- ``KeyValueScore`` (data/KeyValueScore.scala:32-95) — score vector keyed by
  unique sample id. Here: a plain ``[N]`` array aligned to row order; the
  outer-join ``+``/``-`` becomes elementwise add/sub.

Ragged→static design (SURVEY §7 hard part 1): active rows per entity are
capped (reservoir), entity blocks are padded to one ``N_max`` and reduced
feature spaces padded to one ``D_red``; padded rows carry weight 0 and row id
``N`` (scores scattered there land in a discard slot).
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Optional

import numpy as np
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.batch import (
    Batch,
    DenseBatch,
    canonicalized_csr,
    ell_from_csr,
)
from photon_ml_tpu.io.native_loader import pack_projected_rows_native
from photon_ml_tpu.obs import compile as obs_compile
from photon_ml_tpu.obs import trace
from photon_ml_tpu.obs.metrics import REGISTRY
from photon_ml_tpu.projector.projectors import (
    IndexMapProjectors,
    ProjectorConfig,
    ProjectorType,
    RandomProjector,
    build_random_projector,
)

Array = jnp.ndarray

# Densify a shard below this width; ELL above (mirrors the reference's
# representation switch around 200k features, cli/game/training/Driver.scala:
# 357-363 — ours trades dense MXU matmuls against gather/scatter cost).
DENSE_FEATURE_THRESHOLD = 4096


# ---------------------------------------------------------------------------
# Columnar GAME dataset (host side)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GameDataset:
    """Columnar GAME dataset: the host-resident source of per-coordinate views.

    ``feature_shards[shard]`` is a scipy CSR ``[N, D_shard]``;
    ``id_columns[id_type]`` holds integer entity codes (`0..V-1`) with the
    original ids in ``id_vocabs[id_type]`` (GameDatum.scala:33-54's
    idTypeToValueMap, dictionary-encoded).
    """

    responses: np.ndarray  # [N] float
    feature_shards: dict[str, sp.csr_matrix]
    offsets: Optional[np.ndarray] = None  # [N]
    weights: Optional[np.ndarray] = None  # [N]
    id_columns: dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    id_vocabs: dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    uids: Optional[np.ndarray] = None  # [N] raw uid strings when present

    def __post_init__(self):
        n = len(self.responses)
        self.responses = np.asarray(self.responses, dtype=np.float64)
        if self.offsets is None:
            self.offsets = np.zeros(n)
        if self.weights is None:
            self.weights = np.ones(n)
        for name, mat in list(self.feature_shards.items()):
            if not sp.issparse(mat):
                mat = sp.csr_matrix(np.asarray(mat))
            else:
                mat = mat.tocsr()
            # downstream block fills scatter `mat.data` by (row, col) —
            # duplicate entries must be pre-summed or the scatter keeps
            # only the last write
            self.feature_shards[name] = canonicalized_csr(mat)

    @property
    def num_samples(self) -> int:
        return len(self.responses)

    def shard_dim(self, shard: str) -> int:
        return self.feature_shards[shard].shape[1]

    def encode_ids(self, id_type: str, raw_ids: np.ndarray) -> None:
        """Dictionary-encode a raw id column (strings or ints) into codes."""
        vocab, codes = np.unique(np.asarray(raw_ids), return_inverse=True)
        self.id_columns[id_type] = codes.astype(np.int64)
        self.id_vocabs[id_type] = vocab


# ---------------------------------------------------------------------------
# Scores (KeyValueScore analog)
# ---------------------------------------------------------------------------


def zero_scores(n: int) -> np.ndarray:
    return np.zeros(n)


class _BuildStages:
    """The stages of one block build, one after another: entering a stage
    closes the one before it, so the stages tile the build and their
    seconds add up to it. Each is a ``dataset.<stage>`` span (the
    timeline) and, at its close, its seconds on the always-on counter
    ``block_build_secs{stage, coordinate}`` (what the benchmark's
    ``build_*_s`` read), as ``lower_secs{site}`` is booked beside
    ``xla.lower``. Host clocks only: no stage waits for the device."""

    def __init__(self, coordinate: str):
        self._coordinate = coordinate
        self._stage = self._span = None
        self._t0 = 0.0

    def enter(self, stage: str, **labels) -> None:
        if stage == self._stage and self._span is not None:
            return  # still in it
        self.close()
        self._stage = stage
        self._span = trace.span(f"dataset.{stage}",
                                coordinate=self._coordinate, **labels)
        self._span.__enter__()
        # the counter's clock runs inside the span: both read one interval
        self._t0 = time.perf_counter()

    def close(self) -> None:
        if self._span is None:
            return
        REGISTRY.counter("block_build_secs").inc(
            time.perf_counter() - self._t0, stage=self._stage,
            coordinate=self._coordinate)
        span, self._span = self._span, None
        span.__exit__(None, None, None)

    def __enter__(self) -> "_BuildStages":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


# ---------------------------------------------------------------------------
# Fixed-effect view
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FixedEffectDataset:
    """Device batch over the full sample axis for one feature shard.

    Reference: data/FixedEffectDataSet.scala:29-103. ``batch`` rows align
    with GameDataset row order, so coordinate-descent offset injection
    (addScoresToOffsets, :55-74 analog) is a plain array swap — see
    ``with_offsets``.
    """

    shard_id: str
    batch: Batch
    base_offsets: Array  # original data offsets (before CD score injection)

    @property
    def num_samples(self) -> int:
        return int(self.batch.labels.shape[0])

    def with_offsets(self, extra_scores: Array) -> Batch:
        """Batch whose offsets = data offsets + other coordinates' scores."""
        return self.batch._replace(offsets=self.base_offsets + extra_scores)


def csr_to_batch(
    mat: sp.csr_matrix,
    labels: np.ndarray,
    offsets: np.ndarray,
    weights: np.ndarray,
    dtype=jnp.float32,
    dense_threshold: int = DENSE_FEATURE_THRESHOLD,
) -> Batch:
    if mat.shape[1] <= dense_threshold:
        return DenseBatch(
            X=jnp.asarray(mat.toarray(), dtype),
            labels=jnp.asarray(labels, jnp.float32),
            offsets=jnp.asarray(offsets, jnp.float32),
            weights=jnp.asarray(weights, jnp.float32),
        )
    # the ELL layout would split a duplicated cell across slots and
    # corrupt Hessian-diagonal terms (sum(x^2) vs (sum x)^2); toarray()
    # above sums implicitly so only this branch needs the canonical form
    return ell_from_csr(canonicalized_csr(mat), labels, offsets, weights,
                        dtype=dtype)


def build_fixed_effect_dataset(
    data: GameDataset,
    shard_id: str,
    dtype=jnp.float32,
    dense_threshold: int = DENSE_FEATURE_THRESHOLD,
) -> FixedEffectDataset:
    mat = data.feature_shards[shard_id]
    with _BuildStages(shard_id) as stages:
        stages.enter("fixed", rows=int(mat.shape[0]), cols=int(mat.shape[1]))
        batch = csr_to_batch(mat, data.responses, data.offsets,
                             data.weights, dtype=dtype,
                             dense_threshold=dense_threshold)
        return FixedEffectDataset(shard_id=shard_id, batch=batch,
                                  base_offsets=batch.offsets)


# ---------------------------------------------------------------------------
# Load-balanced entity partitioning
# ---------------------------------------------------------------------------


def balanced_entity_order(counts: np.ndarray, num_bins: int,
                          capacity: int = 10000) -> np.ndarray:
    """Greedy bin-pack entities by sample count; return a permutation whose
    contiguous ``num_bins`` slices are load-balanced.

    Mirrors data/RandomEffectDataSetPartitioner.scala:31-108: the heaviest
    ``capacity`` entities are placed greedily onto the lightest bin (min-heap
    by assigned samples); the long tail is hashed. Two changes for the mesh
    layout: bins become contiguous index ranges (sharding = slicing), and bin
    cardinality is capped at ⌈E/num_bins⌉ so equal-size slices line up with
    the bins (padded entity blocks all cost the same compute anyway — load
    balance here equalizes *active sample mass* per shard for build/IO).
    """
    import heapq

    e = len(counts)
    if e == 0:
        return np.zeros(0, dtype=np.int64)
    order = np.argsort(-counts, kind="stable")
    heavy = order[: min(capacity, e)]
    tail = order[min(capacity, e):]
    cap = -(-e // num_bins)
    bins: list[list[int]] = [[] for _ in range(num_bins)]
    heap = [(0, b) for b in range(num_bins)]
    heapq.heapify(heap)
    for ent in heavy:
        spill = []
        while True:
            load, b = heapq.heappop(heap)
            if len(bins[b]) < cap:
                break
            spill.append((load, b))
        bins[b].append(int(ent))
        heapq.heappush(heap, (load + int(counts[ent]), b))
        for item in spill:
            heapq.heappush(heap, item)
    for ent in tail:
        b = int(ent) % num_bins
        if len(bins[b]) >= cap:
            b = min(range(num_bins), key=lambda i: len(bins[i]))
        bins[b].append(int(ent))
    return np.concatenate([np.asarray(b, dtype=np.int64) for b in bins])


# ---------------------------------------------------------------------------
# Random-effect view
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RandomEffectDataConfiguration:
    """Per-coordinate data knobs (data/RandomEffectDataConfiguration.scala:80).

    String format (parity with the reference's CLI):
    ``idType,featureShardId,numPartitions[,activeBound[,passiveBound
    [,numFeaturesToKeep[,projector]]]]`` with ``-`` / ``none`` meaning unset.
    """

    random_effect_type: str
    feature_shard_id: str
    num_partitions: int = 1
    num_active_data_points_upper_bound: Optional[int] = None
    num_passive_data_points_lower_bound: Optional[int] = None
    # CLI field 5 is a features-to-samples RATIO (double) in the reference
    # (RandomEffectDataConfiguration.scala:104-109); the per-entity keep
    # count is ceil(ratio * num_entity_samples) (RandomEffectDataSet.scala:
    # 384-390). The absolute count is a direct-API knob, not CLI-parsed.
    num_features_to_samples_ratio_upper_bound: Optional[float] = None
    num_features_to_keep_upper_bound: Optional[int] = None
    projector: ProjectorConfig = ProjectorConfig(ProjectorType.INDEX_MAP)

    def features_to_keep(self, num_entity_samples: int) -> Optional[int]:
        """Per-entity feature cap: the absolute bound if set, else
        ceil(ratio * samples) (RandomEffectDataSet.scala:386)."""
        if self.num_features_to_keep_upper_bound is not None:
            return self.num_features_to_keep_upper_bound
        if self.num_features_to_samples_ratio_upper_bound is not None:
            return int(math.ceil(
                self.num_features_to_samples_ratio_upper_bound
                * num_entity_samples))
        return None

    @staticmethod
    def parse(s: str) -> "RandomEffectDataConfiguration":
        parts = [p.strip() for p in s.split(",")]
        if len(parts) < 3:
            raise ValueError(
                f"random-effect data config needs at least idType,shard,"
                f"numPartitions: {s!r}")

        def _unset(i):
            return i >= len(parts) or parts[i] in ("", "-", "none", "None")

        def opt_int(i):
            # Negative raw values mean "no bound" (the reference maps them
            # to Int.MaxValue, RandomEffectDataConfiguration.scala:92-95).
            if _unset(i):
                return None
            v = int(parts[i])
            return None if v < 0 else v

        def opt_ratio(i):
            # Field 5 is a double (features-to-samples ratio); negative
            # means unbounded (RandomEffectDataConfiguration.scala:104-109).
            if _unset(i):
                return None
            v = float(parts[i])
            return None if v < 0 else v

        proj = ProjectorConfig(ProjectorType.INDEX_MAP)
        if len(parts) > 6 and parts[6] not in ("", "-", "none"):
            proj = ProjectorConfig.parse(parts[6])
        return RandomEffectDataConfiguration(
            random_effect_type=parts[0],
            feature_shard_id=parts[1],
            num_partitions=int(parts[2]),
            num_active_data_points_upper_bound=opt_int(3),
            num_passive_data_points_lower_bound=opt_int(4),
            num_features_to_samples_ratio_upper_bound=opt_ratio(5),
            projector=proj,
        )


@dataclasses.dataclass(frozen=True)
class FixedEffectDataConfiguration:
    """data/FixedEffectDataConfiguration.scala:23 — ``shardId[,minPartitions]``."""

    feature_shard_id: str
    min_num_partitions: int = 1

    @staticmethod
    def parse(s: str) -> "FixedEffectDataConfiguration":
        parts = [p.strip() for p in s.split(",")]
        return FixedEffectDataConfiguration(
            feature_shard_id=parts[0],
            min_num_partitions=int(parts[1]) if len(parts) > 1 else 1,
        )


@dataclasses.dataclass
class EntityBucket:
    """One (N, D)-homogeneous slice of the entity axis.

    SURVEY §7 hard part 1: padding every entity to a single global
    (N_max, D_red) wastes FLOPs and HBM when entity sizes are skewed (the
    MovieLens per-user block pads the median user ~20x). Entities are
    grouped into a few size buckets; each bucket is padded only to ITS
    (N_b, D_b), and the vmapped solver runs per bucket. Reference analog:
    exactly-sized per-entity local datasets (data/LocalDataSet.scala:34-155).

    ``entity_start``: first global (compact) entity index of this bucket;
    bucket row ``i < num_real`` is global entity ``entity_start + i``; rows
    beyond ``num_real`` are padding lanes for even mesh sharding.
    """

    entity_start: int
    num_real: int
    X: Array  # [E_b, N_b, D_b]
    labels: Array  # [E_b, N_b]
    base_offsets: Array  # [E_b, N_b]
    weights: Array  # [E_b, N_b] (0 = padding)
    row_ids: Array  # [E_b, N_b] int32 (num_samples = discard)
    # When built with entity_shard=(k, K): arrays hold only rows
    # [local_entity_offset, local_entity_offset + E_b/K) of the bucket's
    # padded entity axis; 0 for full builds.
    local_entity_offset: int = 0


@dataclasses.dataclass
class RandomEffectDataset:
    """Entity-major active blocks + sample-major passive rows for one coordinate.

    Active data (trained on): padded dense blocks in each entity's reduced
    feature space —
      ``X [E, N_max, D_red]``, ``labels/offsets/weights [E, N_max]``,
      ``row_ids [E, N_max]`` int32 (pad → ``num_samples``: scatters to a
      discard slot).
    Passive data (scored only, RandomEffectDataSet.scala:328+):
      ``passive_X [P, D_red]`` already projected per its entity,
      ``passive_entity [P]`` local entity index, ``passive_row_ids [P]``.

    ``entity_codes`` maps local entity index → dataset entity code;
    ``projectors`` maps reduced columns back to raw feature ids.

    When built with ``num_buckets > 1`` the single global block is replaced
    by ``buckets`` (each padded to its own (N_b, D_b) — see EntityBucket)
    and ``X/labels/base_offsets/weights/row_ids`` are ``None``; global
    coefficient blocks stay compact ``[num_entities, reduced_dim]`` with
    entity order bucket-major.
    """

    config: RandomEffectDataConfiguration
    entity_codes: np.ndarray  # [E] codes into GameDataset vocab
    X: Optional[Array]  # [E, N_max, D_red] (None when bucketed)
    labels: Optional[Array]  # [E, N_max]
    base_offsets: Optional[Array]  # [E, N_max]
    weights: Optional[Array]  # [E, N_max] (0 = padding)
    row_ids: Optional[Array]  # [E, N_max] int32 (num_samples = discard)
    num_samples: int  # N of the parent GameDataset
    projectors: Optional[IndexMapProjectors] = None
    random_projector: Optional[RandomProjector] = None
    # passive side (may be empty)
    passive_X: Optional[Array] = None  # [P, D_red]
    passive_entity: Optional[Array] = None  # [P] int32
    passive_row_ids: Optional[Array] = None  # [P] int32
    passive_offsets: Optional[Array] = None  # [P]
    # (N, D)-bucketed active blocks (replaces X... when present)
    buckets: Optional[list[EntityBucket]] = None
    _reduced_dim: Optional[int] = None  # set when bucketed
    _score_positions: Optional[Array] = None  # made by score_positions()

    @property
    def num_entities(self) -> int:
        if self.buckets is not None:
            return sum(b.num_real for b in self.buckets)
        return int(self.X.shape[0])

    @property
    def max_rows_per_entity(self) -> int:
        if self.buckets is not None:
            return max(int(b.X.shape[1]) for b in self.buckets)
        return int(self.X.shape[1])

    @property
    def reduced_dim(self) -> int:
        if self.buckets is not None:
            return int(self._reduced_dim)
        return int(self.X.shape[2])

    @property
    def num_passive(self) -> int:
        return 0 if self.passive_X is None else int(self.passive_X.shape[0])

    def offsets_with(self, extra_scores: Array):
        """Per-block training offsets (base + other coordinates' scores):
        one ``[E, N_max]`` array, or a list per bucket when bucketed. The
        offset half of the score exchange (CD offset injection — the
        all-to-all resharding analog of
        RandomEffectDataSet.addScoresToOffsets :55-74): one device program
        a dataset, ``_block_offsets``."""
        blocks = self.buckets if self.buckets is not None else [self]
        out = obs_compile.call(
            "re.offsets", _block_offsets,
            (tuple(b.base_offsets for b in blocks),
             tuple(b.row_ids for b in blocks), extra_scores),
            arg_names=("base_offsets", "row_ids", "extra_scores"))
        return out if self.buckets is not None else out[0]

    @property
    def num_blocks(self) -> int:
        return 1 if self.buckets is None else len(self.buckets)

    def score_positions(self) -> Array:
        """``[num_samples]`` int32: each row's place in the concatenation
        of the active blocks' flattened margins (bucket-major), then the
        passive rows' margins, then one zero (the place of a row this
        coordinate does not score). A row is in one block, so scoring
        gathers by this table what it would otherwise scatter by
        ``row_ids``: the same numbers, and the TPU's compiler takes
        seconds for the gather where it takes 7-16 s for every scatter
        into a sample-long vector. Made on the host at the first call and
        kept (``dataclasses.replace`` carries it with the ``row_ids`` it
        was made from). None where this process cannot read every block's
        ``row_ids`` (a dataset sharded over several hosts): the table
        would need the other hosts' rows, and scoring scatters there."""
        if self._score_positions is None:
            blocks = self.buckets if self.buckets is not None else [self]
            ids = [b.row_ids for b in blocks]
            if self.num_passive:
                ids.append(self.passive_row_ids)
            if not all(getattr(a, "is_fully_addressable", True)
                       for a in ids):
                return None
            ids = np.concatenate([np.asarray(a).reshape(-1) for a in ids])
            real = np.flatnonzero(ids < self.num_samples)
            positions = np.full(self.num_samples, len(ids), np.int32)
            positions[ids[real]] = real
            if np.count_nonzero(positions < len(ids)) != len(real):
                raise ValueError(
                    "a row is in two blocks of this random-effect dataset: "
                    "its score cannot be gathered from one position")
            self._score_positions = jnp.asarray(positions)
        return self._score_positions


@jax.jit
def _block_offsets(base_offsets, row_ids, extra_scores: Array) -> list:
    """Every block's ``base_offsets + extra_scores[row_ids]`` (a padded
    row, id ``num_samples``, reads the one zero appended): what the eager
    ``padded[b.row_ids]`` a block did, as one program a dataset under a
    name of the program's own (``jit__block_offsets`` in a device trace;
    one dispatch an update where the eager form made three a block)."""
    with jax.named_scope("re.offsets"):
        padded = jnp.concatenate(
            [extra_scores, jnp.zeros(1, extra_scores.dtype)])
        return [base + padded[ids]
                for base, ids in zip(base_offsets, row_ids)]


def _topk_per_segment(seg: np.ndarray, score: np.ndarray,
                      limit: np.ndarray) -> np.ndarray:
    """Boolean mask keeping the ``limit[seg]`` highest-``score`` items of
    each segment (stable; vectorized — no per-segment loop)."""
    order = np.lexsort((-score, seg))
    seg_sorted = seg[order]
    # rank within segment along the sorted layout
    boundaries = np.flatnonzero(np.diff(seg_sorted)) + 1
    starts = np.concatenate([[0], boundaries])
    seg_sizes = np.diff(np.concatenate([starts, [len(seg)]]))
    rank = np.arange(len(seg)) - np.repeat(starts, seg_sizes)
    keep_sorted = rank < limit[seg_sorted]
    mask = np.zeros(len(seg), dtype=bool)
    mask[order] = keep_sorted
    return mask


def _densify_chunked(sub: sp.csr_matrix, chunk: int = 1 << 16) -> np.ndarray:
    """``sub.toarray()`` in bounded-memory row chunks (identity projection
    on a wide shard would otherwise materialize one giant temporary on top
    of the destination block)."""
    r, d = sub.shape
    out = np.zeros((r, d), dtype=np.float32)
    for lo in range(0, r, chunk):
        out[lo:lo + chunk] = sub[lo:lo + chunk].toarray()
    return out


def _project_nnz(sub: sp.csr_matrix, entity_of_row: np.ndarray,
                 projectors: IndexMapProjectors
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Reduced column of every stored element of ``sub``, batched.

    Row ``r`` of ``sub`` belongs to entity ``entity_of_row[r]``; each nnz's
    raw column is looked up in that entity's sorted index map with ONE
    ``searchsorted`` over a flattened (entity, raw_col) key table — the
    vectorized inverse of ``IndexMapProjectors.project_row``. Returns
    ``(row_of_nnz, reduced_col, valid)``; invalid elements (features the
    entity's map dropped) must be discarded by the caller.
    """
    lens = np.diff(sub.indptr)
    row_of = np.repeat(np.arange(sub.shape[0]), lens)
    ent = np.asarray(entity_of_row, dtype=np.int64)[row_of]
    d_red = projectors.max_reduced_dim
    stride = projectors.raw_dim + 1
    e = projectors.num_entities
    table = (np.arange(e, dtype=np.int64)[:, None] * stride
             + projectors.raw_indices.astype(np.int64)).ravel()
    keys = ent * stride + sub.indices
    pos = np.searchsorted(table, keys)
    pos_clip = np.minimum(pos, len(table) - 1)
    valid = table[pos_clip] == keys
    j = pos_clip - ent * d_red
    return row_of, j, valid


class _PairStatsAccumulator:
    """Streaming per-(entity, feature) moment accumulation for projector
    construction. Feed any number of active-row chunks through ``add``; the
    running state is the sorted unique (entity, feature) key set with summed
    moments (s1=Σx, s2=Σx², sxy=Σxy) plus per-entity label sums — memory is
    bounded by the number of DISTINCT pairs (the eventual projector table),
    never the total nnz, which is what lets the entity-block build stream
    past host RAM (RandomEffectDataSet.scala:169-206's shuffle-side
    combine)."""

    def __init__(self, raw_dim: int, e_real: int, with_moments: bool):
        self.raw_dim = raw_dim
        self.e_real = e_real
        self.with_moments = with_moments
        self.keys = np.zeros(0, np.int64)
        self.s1 = np.zeros(0)
        self.s2 = np.zeros(0)
        self.sxy = np.zeros(0)
        self.sy1 = np.zeros(e_real)
        self.sy2 = np.zeros(e_real)

    def add(self, sub: sp.csr_matrix, entity_of_row: np.ndarray,
            labels: np.ndarray) -> None:
        """Absorb one chunk of ACTIVE rows (CSR + their entity indices +
        labels)."""
        lens = np.diff(sub.indptr)
        row_of = np.repeat(np.arange(sub.shape[0]), lens)
        ent = np.asarray(entity_of_row, dtype=np.int64)[row_of]
        keys = ent * self.raw_dim + sub.indices
        pairs, inv = np.unique(keys, return_inverse=True)
        if self.with_moments:
            v = sub.data.astype(np.float64)
            y = np.asarray(labels, dtype=np.float64)
            # bincount-with-weights, not np.add.at: the buffered ufunc.at
            # path is ~10-30x slower on the 80M-element ingest bench.
            s1 = np.bincount(inv, weights=v, minlength=len(pairs))
            s2 = np.bincount(inv, weights=v * v, minlength=len(pairs))
            sxy = np.bincount(inv, weights=v * y[row_of],
                              minlength=len(pairs))
            ent_rows = np.asarray(entity_of_row, dtype=np.int64)
            self.sy1 += np.bincount(ent_rows, weights=y,
                                    minlength=self.e_real)
            self.sy2 += np.bincount(ent_rows, weights=y * y,
                                    minlength=self.e_real)
        else:
            s1 = s2 = sxy = np.zeros(len(pairs))
        # merge-compact into the running sorted key set
        if len(self.keys):
            merged, minv = np.unique(np.concatenate([self.keys, pairs]),
                                     return_inverse=True)
            ms1 = np.bincount(minv, weights=np.concatenate([self.s1, s1]),
                              minlength=len(merged))
            ms2 = np.bincount(minv, weights=np.concatenate([self.s2, s2]),
                              minlength=len(merged))
            msxy = np.bincount(minv, weights=np.concatenate([self.sxy, sxy]),
                               minlength=len(merged))
            self.keys, self.s1, self.s2, self.sxy = merged, ms1, ms2, msxy
        else:
            self.keys, self.s1, self.s2, self.sxy = pairs, s1, s2, sxy

    def finalize(self, act_counts: np.ndarray,
                 config: RandomEffectDataConfiguration,
                 pad_to_multiple: int = 8) -> IndexMapProjectors:
        """Per-entity feature unions + optional |Pearson| top-k selection
        (LocalDataSet.scala:202-248) over the accumulated pair stats."""
        e_real = self.e_real
        raw_dim = self.raw_dim
        pair_ent = (self.keys // raw_dim).astype(np.int64)
        pair_col = (self.keys % raw_dim).astype(np.int32)

        # Per-entity keep limits (None -> no cap anywhere).
        if config.num_features_to_keep_upper_bound is not None:
            limits = np.full(e_real,
                             config.num_features_to_keep_upper_bound,
                             dtype=np.int64)
        elif config.num_features_to_samples_ratio_upper_bound is not None:
            limits = np.ceil(
                config.num_features_to_samples_ratio_upper_bound
                * act_counts).astype(np.int64)
        else:
            limits = None

        if limits is not None:
            # |Pearson(feature, label)| per pair from the sparse moments:
            # cov = E[xy] - E[x]E[y], var = E[x^2] - E[x]^2 (zeros
            # contribute only through the entity's row count).
            k_e = np.maximum(act_counts, 1).astype(np.float64)
            ym = self.sy1 / k_e
            y_sd = np.sqrt(np.maximum(self.sy2 / k_e - ym * ym, 0.0))
            ke_p = k_e[pair_ent]
            xm = self.s1 / ke_p
            cov = self.sxy / ke_p - xm * ym[pair_ent]
            var_x = np.maximum(self.s2 / ke_p - xm * xm, 0.0)
            denom = np.sqrt(var_x) * y_sd[pair_ent]
            corr = np.where(denom > 0,
                            np.abs(cov) / np.where(denom > 0, denom, 1.0),
                            0.0)
            keep = _topk_per_segment(pair_ent, corr, limits)
            pair_ent, pair_col = pair_ent[keep], pair_col[keep]
            # restore (entity, column) order after the ranked selection
            reorder = np.lexsort((pair_col, pair_ent))
            pair_ent, pair_col = pair_ent[reorder], pair_col[reorder]

        reduced_dims = np.bincount(pair_ent,
                                   minlength=e_real).astype(np.int32)
        d_red = int(reduced_dims.max()) if e_real else 1
        d_red = max(1, -(-max(d_red, 1) // pad_to_multiple)
                    * pad_to_multiple)
        raw_indices = np.full((e_real, d_red), raw_dim, dtype=np.int32)
        starts = np.concatenate([[0], np.cumsum(reduced_dims)[:-1]])
        slot = np.arange(len(pair_ent)) - starts[pair_ent]
        raw_indices[pair_ent, slot] = pair_col
        return IndexMapProjectors(raw_indices, reduced_dims, raw_dim)


def _build_projectors_from_active(
    sub: sp.csr_matrix,
    entity_of_row: np.ndarray,
    act_counts: np.ndarray,
    labels: np.ndarray,
    raw_dim: int,
    config: RandomEffectDataConfiguration,
    pad_to_multiple: int = 8,
) -> IndexMapProjectors:
    """One-shot (single-chunk) projector build — the in-RAM entry to the
    same accumulate+finalize path the streamed builder uses chunk-wise."""
    need_moments = (
        config.num_features_to_keep_upper_bound is not None
        or config.num_features_to_samples_ratio_upper_bound is not None)
    acc = _PairStatsAccumulator(raw_dim, len(act_counts), need_moments)
    acc.add(sub, entity_of_row, labels)
    return acc.finalize(act_counts, config, pad_to_multiple)


def _bucket_plan(counts: np.ndarray, num_buckets: int, multiple: int
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Optimal (N-threshold) bucketing of entities by active-row count.

    Quantizes counts up to ``multiple`` (rows are padded to that multiple
    anyway), then a small exact DP over the distinct quantized sizes picks
    ≤ ``num_buckets`` contiguous groups minimizing the padded area
    Σ_b E_b · N_b — the FLOP/HBM cost of the vmapped solve. Returns
    ``(bucket_n_max desc [K], bucket_of [E])``.
    """
    counts = np.asarray(counts, dtype=np.int64)
    q = np.maximum(multiple, -(-counts // multiple) * multiple)
    uniq, w = np.unique(q, return_counts=True)
    uniq, w = uniq[::-1], w[::-1].astype(np.int64)  # descending sizes
    m = len(uniq)
    k = min(num_buckets, m)
    if k >= m:
        n_max = uniq
        bucket_of = np.searchsorted(-uniq, -q)
        return n_max, bucket_of
    prefix = np.concatenate([[0], np.cumsum(w)])
    inf = np.iinfo(np.int64).max // 4
    # f[j, t] = min padded area covering the j largest sizes with t buckets
    f = np.full((m + 1, k + 1), inf, dtype=np.int64)
    arg = np.zeros((m + 1, k + 1), dtype=np.int64)
    f[0, 0] = 0
    for t in range(1, k + 1):
        for j in range(t, m + 1):
            # bucket (i..j] has N = uniq[i] (largest member)
            cand = f[:j, t - 1] + uniq[:j] * (prefix[j] - prefix[:j])
            i = int(np.argmin(cand))
            f[j, t], arg[j, t] = cand[i], i
    cuts = []
    j = m
    for t in range(k, 0, -1):
        i = int(arg[j, t])
        cuts.append(i)
        j = i
    cuts = cuts[::-1]  # ascending segment starts into uniq
    n_max = uniq[np.asarray(cuts)]
    # entity -> bucket: the segment its quantized size falls in
    seg_of_size = np.zeros(m, dtype=np.int64)
    for b, start in enumerate(cuts):
        seg_of_size[start:] = b
    size_rank = np.searchsorted(-uniq, -q)
    return n_max, seg_of_size[size_rank]


def _fill_feature_rows(
    sub: sp.csr_matrix,
    out: np.ndarray,
    flat_pos: np.ndarray,
    projectors: Optional[IndexMapProjectors],
    random_projector: Optional[RandomProjector],
    table_ent: Optional[np.ndarray] = None,
    global_ent: Optional[np.ndarray] = None,
    raw_indices: Optional[np.ndarray] = None,
) -> None:
    """ONE per-block feature fill shared by the single-block, bucketed, and
    passive builders: native pack (block_packer.cpp), numpy ``_project_nnz``
    scatter fallback, random-projector matmul, or chunked densify.

    ``out`` is a zeroed C-contiguous f32 array whose flat row view receives
    row ``r`` of ``sub`` at ``flat_pos[r]``. For index-map projection,
    ``table_ent[r]`` indexes ``raw_indices`` (which may be a bucket slice of
    the global table) and ``global_ent[r]`` is the row's GLOBAL entity index
    for the numpy fallback's searchsorted over the full projector table.
    """
    flat = out.reshape(-1, out.shape[-1])
    if projectors is not None:
        if not pack_projected_rows_native(sub, table_ent, flat_pos,
                                          raw_indices, out):
            nnz_row, nnz_j, nnz_ok = _project_nnz(sub, global_ent,
                                                  projectors)
            flat[flat_pos[nnz_row[nnz_ok]],
                 nnz_j[nnz_ok]] = sub.data[nnz_ok]
    elif random_projector is not None:
        flat[flat_pos] = (sub @ random_projector.matrix).astype(np.float32)
    else:
        flat[flat_pos] = _densify_chunked(sub)


def _pack_entity_buckets(
    sub: sp.csr_matrix,
    ent_of_act: np.ndarray,
    slot_of_act: np.ndarray,
    act_labels: np.ndarray,
    act_offsets: np.ndarray,
    act_weights: np.ndarray,
    rows_act: np.ndarray,
    n_samples: int,
    bucket_sizes: np.ndarray,
    bucket_n_max: np.ndarray,
    entity_axis_size: int,
    projectors: Optional[IndexMapProjectors],
    random_projector: Optional[RandomProjector],
    d_red: int,
    dtype,
    stages: _BuildStages,
    pad_dim_multiple: int = 8,
) -> list[EntityBucket]:
    """Pack active rows into per-bucket (N_b, D_b) blocks (stage ``pack``)
    and hand each to the device as it is done (stage ``transfer``), so the
    host holds one bucket's rows at a time.

    ``ent_of_act`` are GLOBAL compact entity indices (bucket-major order);
    bucket b owns entities [starts[b], starts[b] + bucket_sizes[b]). Each
    bucket's D_b is the max per-entity reduced dim within it (index-map
    projection narrows tall-entity buckets too — that is the D half of the
    (N, D) bucketing), padded for lane alignment.
    """
    stages.enter("pack")
    starts = np.concatenate([[0], np.cumsum(bucket_sizes)])
    bucket_of_act = np.searchsorted(starts, ent_of_act, side="right") - 1
    buckets: list[EntityBucket] = []
    for b in range(len(bucket_sizes)):
        stages.enter("pack")
        nr = int(bucket_sizes[b])
        start = int(starts[b])
        n_b = int(bucket_n_max[b])
        if projectors is not None:
            d_b = int(projectors.reduced_dims[start:start + nr].max())
            d_b = max(1, -(-max(d_b, 1) // pad_dim_multiple)
                      * pad_dim_multiple)
            d_b = min(d_b, d_red)
        else:
            d_b = d_red
        e_b = max(1, -(-nr // entity_axis_size) * entity_axis_size)

        mask = bucket_of_act == b
        loc = ent_of_act[mask] - start
        slots = slot_of_act[mask]
        X = np.zeros((e_b, n_b, d_b), dtype=np.float32)
        labels = np.zeros((e_b, n_b), dtype=np.float32)
        offsets = np.zeros((e_b, n_b), dtype=np.float32)
        weights = np.zeros((e_b, n_b), dtype=np.float32)
        row_ids = np.full((e_b, n_b), n_samples, dtype=np.int32)
        labels[loc, slots] = act_labels[mask]
        offsets[loc, slots] = act_offsets[mask]
        weights[loc, slots] = act_weights[mask]
        row_ids[loc, slots] = rows_act[mask]

        # Per-bucket table slice: every entity's valid columns sit in the
        # first reduced_dims[e] <= D_b positions, so truncating to D_b only
        # drops pad sentinels.
        _fill_feature_rows(
            sub[mask], X, loc * n_b + slots,
            projectors, random_projector,
            table_ent=loc, global_ent=ent_of_act[mask],
            raw_indices=None if projectors is None
            else projectors.raw_indices[start:start + nr, :d_b])

        stages.enter("transfer")
        buckets.append(EntityBucket(
            entity_start=start, num_real=nr,
            X=jnp.asarray(X, dtype),
            labels=jnp.asarray(labels),
            base_offsets=jnp.asarray(offsets),
            weights=jnp.asarray(weights),
            row_ids=jnp.asarray(row_ids),
        ))
    return buckets


def build_random_effect_dataset(
    data: GameDataset,
    config: RandomEffectDataConfiguration,
    seed: int = 0,
    pad_rows_multiple: int = 8,
    dtype=jnp.float32,
    entity_axis_size: int = 1,
    num_buckets: int = 1,
) -> RandomEffectDataset:
    """Group rows per entity, cap/split, project, pad into device blocks.

    ``entity_axis_size``: the entity mesh-axis extent — E is padded to a
    multiple so the blocks shard evenly; entities are pre-permuted by the
    greedy load balancer (balanced_entity_order) so contiguous shards carry
    similar sample mass.

    ``num_buckets > 1`` activates (N, D) size bucketing (SURVEY §7 hard
    part 1): entities are grouped by active-row count into at most that
    many buckets, each padded only to its own (N_b, D_b) — see
    EntityBucket. Entity order becomes bucket-major (balanced within each
    bucket) and the returned dataset carries ``buckets`` instead of one
    global block.

    One ``dataset.build{coordinate, rows, entities}`` span (``coordinate``
    is the id type), tiled by its stages (:class:`_BuildStages`): ``group``
    (the lexsort and reservoir split, the bucket plan, the balanced
    order), ``project`` (the CSR row gather and the per-entity feature
    spaces), ``pack`` (rows into padded blocks), ``passive`` (densify and
    project the passive rows), ``transfer`` (the host's part of every copy
    to the device: nothing here waits for the device, the rest of a copy
    shows where its array is first used or waited for).
    """
    id_type = config.random_effect_type
    if id_type not in data.id_columns:
        raise KeyError(f"id type {id_type!r} not in dataset (have "
                       f"{list(data.id_columns)})")
    with trace.span("dataset.build", coordinate=id_type) as build_span, \
            _BuildStages(id_type) as stages:
        out = _build_random_effect_dataset(
            data, config, seed, pad_rows_multiple, dtype, entity_axis_size,
            num_buckets, stages)
        build_span.label(rows=out.num_samples, entities=out.num_entities)
        return out


def _build_random_effect_dataset(data, config, seed, pad_rows_multiple,
                                 dtype, entity_axis_size, num_buckets,
                                 stages: _BuildStages
                                 ) -> RandomEffectDataset:
    stages.enter("group")
    codes = np.asarray(data.id_columns[config.random_effect_type])
    mat = data.feature_shards[config.feature_shard_id].tocsr()
    n, raw_dim = mat.shape
    rng = np.random.default_rng(seed)

    # --- group + reservoir split in one lexsort: rows ordered by
    # (entity, random key), so the first `cap` rows of each group ARE a
    # uniform sample (RandomEffectDataSet.scala:254-317's reservoir,
    # vectorized). No per-entity Python loop anywhere below.
    order = np.lexsort((rng.random(n), codes))
    sorted_codes = codes[order]
    uniq, starts, group_sizes = np.unique(
        sorted_codes, return_index=True, return_counts=True)
    e_real = len(uniq)
    grp_of_sorted = np.repeat(np.arange(e_real), group_sizes)
    pos_in_group = np.arange(n) - starts[grp_of_sorted]

    cap = config.num_active_data_points_upper_bound
    if cap is None:
        active_mask = np.ones(n, dtype=bool)
        act_counts = group_sizes
    else:
        active_mask = pos_in_group < cap
        act_counts = np.minimum(group_sizes, cap)
    # weight rescale count/cap preserves expected total weight per entity
    group_scale = group_sizes / np.maximum(act_counts, 1)

    lo = config.num_passive_data_points_lower_bound
    pas_counts = group_sizes - act_counts
    keep_passive_group = (pas_counts > 0 if lo is None
                          else pas_counts >= lo)
    passive_mask = ~active_mask & keep_passive_group[grp_of_sorted]

    # --- load-balanced entity ordering for contiguous sharding. With
    # bucketing the order is bucket-major (balanced within each bucket:
    # members are within one padding quantum of each other, so contiguous
    # entity-axis shards stay balanced).
    bucket_sizes = bucket_n_max = None
    if num_buckets > 1 and e_real > 1:
        bucket_n_max, bucket_of = _bucket_plan(
            act_counts, num_buckets, pad_rows_multiple)
        parts = []
        for b in range(len(bucket_n_max)):
            idx = np.flatnonzero(bucket_of == b)
            parts.append(idx[balanced_entity_order(
                act_counts[idx], num_bins=max(1, entity_axis_size))])
        kept = [(n, p) for n, p in zip(bucket_n_max, parts) if len(p)]
        bucket_n_max = np.array([n for n, _ in kept], dtype=np.int64)
        parts = [p for _, p in kept]
        perm = np.concatenate(parts)
        bucket_sizes = np.array([len(p) for p in parts], dtype=np.int64)
    else:
        perm = balanced_entity_order(act_counts,
                                     num_bins=max(1, entity_axis_size))
    ent_codes = uniq[perm].astype(np.int64)
    inv_perm = np.empty(e_real, dtype=np.int64)
    inv_perm[perm] = np.arange(e_real)

    rows_act = order[active_mask]  # dataset row ids of active rows
    ent_of_act = inv_perm[grp_of_sorted[active_mask]]  # local entity index
    slot_of_act = pos_in_group[active_mask]
    counts = act_counts[perm]  # active rows per local entity

    # --- per-entity feature space (projection).
    stages.enter("project")
    proj_cfg = config.projector
    projectors = None
    random_projector = None
    sub = mat[rows_act]  # one bulk CSR row gather, row r <-> active row r
    if proj_cfg.kind == ProjectorType.INDEX_MAP:
        projectors = _build_projectors_from_active(
            sub, ent_of_act, counts, data.responses[rows_act], raw_dim,
            config)
        d_red = projectors.max_reduced_dim
    elif proj_cfg.kind == ProjectorType.RANDOM:
        random_projector = build_random_projector(
            raw_dim, proj_cfg.projected_dim, seed=proj_cfg.seed)
        d_red = proj_cfg.projected_dim
    else:  # IDENTITY
        d_red = raw_dim

    act_weights = (data.weights[rows_act]
                   * group_scale[grp_of_sorted[active_mask]])

    if bucket_sizes is not None:
        buckets = _pack_entity_buckets(
            sub, ent_of_act, slot_of_act,
            act_labels=data.responses[rows_act],
            act_offsets=data.offsets[rows_act],
            act_weights=act_weights,
            rows_act=rows_act, n_samples=n,
            bucket_sizes=bucket_sizes, bucket_n_max=bucket_n_max,
            entity_axis_size=entity_axis_size,
            projectors=projectors, random_projector=random_projector,
            d_red=d_red, dtype=dtype, stages=stages)
        X = None
    else:
        stages.enter("pack")
        buckets = None
        # --- pad E to the entity axis and N to a stable multiple.
        e_pad = max(1,
                    -(-max(e_real, 1) // entity_axis_size) * entity_axis_size)
        n_max = int(counts.max()) if e_real else 1
        n_max = max(1, -(-n_max // pad_rows_multiple) * pad_rows_multiple)

        X = np.zeros((e_pad, n_max, d_red), dtype=np.float32)
        labels = np.zeros((e_pad, n_max), dtype=np.float32)
        offsets = np.zeros((e_pad, n_max), dtype=np.float32)
        weights = np.zeros((e_pad, n_max), dtype=np.float32)
        row_ids = np.full((e_pad, n_max), n, dtype=np.int32)

        labels[ent_of_act, slot_of_act] = data.responses[rows_act]
        offsets[ent_of_act, slot_of_act] = data.offsets[rows_act]
        weights[ent_of_act, slot_of_act] = act_weights
        row_ids[ent_of_act, slot_of_act] = rows_act

        _fill_feature_rows(
            sub, X, ent_of_act * n_max + slot_of_act,
            projectors, random_projector,
            table_ent=ent_of_act, global_ent=ent_of_act,
            raw_indices=None if projectors is None
            else projectors.raw_indices)

    # --- passive side (sample-major, already projected per entity).
    stages.enter("passive")
    p_X = p_ent = p_rows = p_off = None
    if passive_mask.any():
        pr = order[passive_mask]
        local = inv_perm[grp_of_sorted[passive_mask]].astype(np.int32)
        sub_p = mat[pr]
        dense = np.zeros((len(pr), d_red), dtype=np.float32)
        _fill_feature_rows(
            sub_p, dense, np.arange(len(pr), dtype=np.int64),
            projectors, random_projector,
            table_ent=local.astype(np.int64), global_ent=local,
            raw_indices=None if projectors is None
            else projectors.raw_indices)
        p_rows = pr.astype(np.int32)
        p_off = data.offsets[pr].astype(np.float32)
        stages.enter("transfer")
        p_X = jnp.asarray(dense)
        p_ent = jnp.asarray(local)
        p_rows = jnp.asarray(p_rows)
        p_off = jnp.asarray(p_off)

    stages.enter("transfer")
    return RandomEffectDataset(
        config=config,
        entity_codes=ent_codes,
        X=None if buckets is not None else jnp.asarray(X, dtype),
        labels=None if buckets is not None else jnp.asarray(labels),
        base_offsets=None if buckets is not None else jnp.asarray(offsets),
        weights=None if buckets is not None else jnp.asarray(weights),
        row_ids=None if buckets is not None else jnp.asarray(row_ids),
        num_samples=n,
        projectors=projectors,
        random_projector=random_projector,
        passive_X=p_X,
        passive_entity=p_ent,
        passive_row_ids=p_rows,
        passive_offsets=p_off,
        buckets=buckets,
        _reduced_dim=d_red if buckets is not None else None,
    )


def _alloc_rows(shape, blocks_dir: Optional[str], name: str) -> np.ndarray:
    """Zeroed f32 destination: RAM array, or a disk-backed ``np.memmap``
    under ``blocks_dir`` (never resident all at once — the OS pages it)."""
    if blocks_dir is None:
        return np.zeros(shape, dtype=np.float32)
    import os

    os.makedirs(blocks_dir, exist_ok=True)
    return np.memmap(os.path.join(blocks_dir, name + ".f32"),
                     dtype=np.float32, mode="w+", shape=shape)


def build_random_effect_dataset_streamed(
    stream_factory,
    config: RandomEffectDataConfiguration,
    raw_dim: int,
    seed: int = 0,
    pad_rows_multiple: int = 8,
    entity_axis_size: int = 1,
    num_buckets: int = 1,
    blocks_dir: Optional[str] = None,
    pad_dim_multiple: int = 8,
    keep_host_blocks: bool = False,
    entity_shard: Optional[tuple[int, int]] = None,
    dtype=jnp.float32,
) -> RandomEffectDataset:
    """Random-effect blocks from STREAMED parts, optionally memmap-backed.

    The in-RAM builder (``build_random_effect_dataset``) holds the full
    feature CSR plus every padded block simultaneously; the reference
    instead streams partitioned parts through a distributed shuffle into
    entity-major layout (data/RandomEffectDataSet.scala:169-206) and never
    materializes the whole dataset on one host. This builder is that
    shuffle's single-host analog:

    - ``stream_factory()`` returns a FRESH iterator over parts, each part
      ``(csr_chunk [M, raw_dim], entity_codes [M], labels [M], offsets [M],
      weights [M])`` in a deterministic order (the iterator is consumed 2-3
      times; identical content each time).
    - Pass 1 holds only O(N) scalar columns (codes/labels/offsets/weights)
      — never features — and computes the reservoir split, the
      load-balanced entity order, and the (N, D) bucket plan.
    - For INDEX_MAP projection a stats pass accumulates per-(entity,
      feature) moments bounded by the projector-table size
      (``_PairStatsAccumulator``).
    - Pass 2 scatters each part's active/passive rows straight into their
      destination blocks; with ``blocks_dir`` those are ``np.memmap`` files
      (bucket blocks + passive rows), so peak RSS is one part + the scalar
      columns, not CSR + all blocks.

    Always returns the bucketed representation (``num_buckets=1`` → one
    bucket). Host-side staging is always float32; ``dtype`` applies at
    the device commit (the --precision bf16 storage mode), matching the
    in-RAM builder. With ``blocks_dir`` the blocks are f32 numpy memmaps
    that JAX copies to device per-bucket at solve time — the memmap
    files themselves stay f32 regardless of ``dtype`` (the on-disk
    format is the spill contract, and the paging path converts on
    device commit) — and the caller owns the directory's lifetime. ``keep_host_blocks=True`` keeps
    RAM-built blocks as plain numpy too (no device commit) — for callers
    that re-shard them onto a global mesh themselves (the multi-host
    worker must not materialize the full block set on one device first).

    ``entity_shard=(k, K)`` builds ONLY the k-th of K contiguous
    entity-axis slices of every bucket (the grouping/plan stays global,
    computed from the O(N) scalar columns): bucket arrays come back with
    leading dim ``E_b/K`` and ``EntityBucket.local_entity_offset`` set to
    the slice start, so a multi-host worker allocates and fills just its
    own entity range — no host ever holds another host's blocks, the
    per-host-sharded analog of RandomEffectDataSet.scala:169-206's
    partitioned shuffle output. Requires ``entity_axis_size`` divisible
    by K (every bucket's padded E_b then splits evenly). Passive arrays
    remain global.

    The same ``dataset.build`` span and stages as the in-RAM builder's:
    ``group`` is pass 1, ``project`` the stats pass, ``pack`` pass 2 (which
    scatters a part's active and passive rows together, so this builder
    books no ``passive``), ``transfer`` the device commit where there is
    one.
    """
    with trace.span("dataset.build",
                    coordinate=config.random_effect_type) as build_span, \
            _BuildStages(config.random_effect_type) as stages:
        out = _build_random_effect_dataset_streamed(
            stream_factory, config, raw_dim, seed, pad_rows_multiple,
            entity_axis_size, num_buckets, blocks_dir, pad_dim_multiple,
            keep_host_blocks, entity_shard, dtype, stages)
        build_span.label(rows=out.num_samples, entities=out.num_entities,
                         streamed=True)
        return out


def _build_random_effect_dataset_streamed(
        stream_factory, config, raw_dim, seed, pad_rows_multiple,
        entity_axis_size, num_buckets, blocks_dir, pad_dim_multiple,
        keep_host_blocks, entity_shard, dtype, stages: _BuildStages
) -> RandomEffectDataset:
    # ---- pass 1: scalar columns only ------------------------------------
    stages.enter("group")
    codes_parts, y_parts, off_parts, wt_parts = [], [], [], []
    for chunk in stream_factory():
        _, c, y, o, w = chunk
        codes_parts.append(np.asarray(c, np.int64))
        y_parts.append(np.asarray(y, np.float64))
        off_parts.append(np.asarray(o, np.float32))
        # f64 so the reservoir rescale product below is bit-identical to
        # the in-RAM builder's (f64 weights x f64 scale, then one f32 cast)
        wt_parts.append(np.asarray(w, np.float64))
    if not codes_parts:
        raise ValueError("empty random-effect stream")
    codes = np.concatenate(codes_parts)
    resp = np.concatenate(y_parts)
    offs = np.concatenate(off_parts)
    wts = np.concatenate(wt_parts)
    del codes_parts, y_parts, off_parts, wt_parts
    n = len(codes)
    rng = np.random.default_rng(seed)

    # identical reservoir/grouping math to the in-RAM builder (same seed →
    # identical active sets, so the two paths are parity-testable)
    order = np.lexsort((rng.random(n), codes))
    sorted_codes = codes[order]
    uniq, starts, group_sizes = np.unique(
        sorted_codes, return_index=True, return_counts=True)
    e_real = len(uniq)
    grp_of_sorted = np.repeat(np.arange(e_real), group_sizes)
    pos_in_group = np.arange(n) - starts[grp_of_sorted]

    cap = config.num_active_data_points_upper_bound
    if cap is None:
        active_mask = np.ones(n, dtype=bool)
        act_counts = group_sizes
    else:
        active_mask = pos_in_group < cap
        act_counts = np.minimum(group_sizes, cap)
    group_scale = group_sizes / np.maximum(act_counts, 1)

    lo_b = config.num_passive_data_points_lower_bound
    pas_counts = group_sizes - act_counts
    keep_passive_group = (pas_counts > 0 if lo_b is None
                          else pas_counts >= lo_b)
    passive_mask = ~active_mask & keep_passive_group[grp_of_sorted]

    # bucket plan + bucket-major balanced entity order
    bucket_n_max, bucket_of = _bucket_plan(
        act_counts, max(1, num_buckets), pad_rows_multiple)
    parts = []
    for b in range(len(bucket_n_max)):
        idx = np.flatnonzero(bucket_of == b)
        parts.append(idx[balanced_entity_order(
            act_counts[idx], num_bins=max(1, entity_axis_size))])
    kept = [(nm, p) for nm, p in zip(bucket_n_max, parts) if len(p)]
    bucket_n_max = np.array([nm for nm, _ in kept], dtype=np.int64)
    parts = [p for _, p in kept]
    perm = np.concatenate(parts)
    bucket_sizes = np.array([len(p) for p in parts], dtype=np.int64)
    ent_codes = uniq[perm].astype(np.int64)
    inv_perm = np.empty(e_real, dtype=np.int64)
    inv_perm[perm] = np.arange(e_real)
    counts = act_counts[perm]

    # per-dataset-row assignments (row-indexed views of the sorted layout)
    row_ent = np.empty(n, np.int64)
    row_ent[order] = inv_perm[grp_of_sorted]
    row_slot = np.empty(n, np.int32)
    row_slot[order] = pos_in_group.astype(np.int32)
    row_active = np.empty(n, bool)
    row_active[order] = active_mask
    row_passive = np.empty(n, bool)
    row_passive[order] = passive_mask
    n_passive = int(passive_mask.sum())
    ppos = np.full(n, -1, np.int64)
    ppos[order[passive_mask]] = np.arange(n_passive)
    group_scale_perm = group_scale[perm]
    del (order, sorted_codes, grp_of_sorted, pos_in_group, active_mask,
         passive_mask, codes)

    # ---- projector (streamed stats pass for INDEX_MAP) -------------------
    stages.enter("project")
    proj_cfg = config.projector
    projectors = None
    random_projector = None
    if proj_cfg.kind == ProjectorType.INDEX_MAP:
        need_moments = (
            config.num_features_to_keep_upper_bound is not None
            or config.num_features_to_samples_ratio_upper_bound is not None)
        acc = _PairStatsAccumulator(raw_dim, e_real, need_moments)
        lo = 0
        for chunk in stream_factory():
            mat_c = chunk[0].tocsr()
            m = mat_c.shape[0]
            a = row_active[lo:lo + m]
            acc.add(mat_c[a], row_ent[lo:lo + m][a], resp[lo:lo + m][a])
            lo += m
        projectors = acc.finalize(counts, config, pad_dim_multiple)
        d_red = projectors.max_reduced_dim
    elif proj_cfg.kind == ProjectorType.RANDOM:
        random_projector = build_random_projector(
            raw_dim, proj_cfg.projected_dim, seed=proj_cfg.seed)
        d_red = proj_cfg.projected_dim
    else:  # IDENTITY
        d_red = raw_dim

    # ---- allocate destination blocks ------------------------------------
    stages.enter("pack")
    if entity_shard is not None:
        shard_k, shard_count = entity_shard
        if not 0 <= shard_k < shard_count:
            raise ValueError(
                f"entity_shard index {shard_k} out of range for "
                f"{shard_count} shards")
        if entity_axis_size % shard_count != 0:
            raise ValueError(
                f"entity_shard needs entity_axis_size divisible by "
                f"{shard_count}, got {entity_axis_size}")
    else:
        shard_k, shard_count = 0, 1
    b_starts = np.concatenate([[0], np.cumsum(bucket_sizes)])
    Xs, labs, offsb, wtsb, rids, dims = [], [], [], [], [], []
    # local (this shard's) entity range of each bucket: [sl_lo, sl_hi)
    slice_lo, slice_hi = [], []
    for b in range(len(bucket_sizes)):
        nr, n_b = int(bucket_sizes[b]), int(bucket_n_max[b])
        start = int(b_starts[b])
        if projectors is not None:
            d_b = int(projectors.reduced_dims[start:start + nr].max())
            d_b = max(1, -(-max(d_b, 1) // pad_dim_multiple)
                      * pad_dim_multiple)
            d_b = min(d_b, d_red)
        else:
            d_b = d_red
        e_b = max(1, -(-nr // entity_axis_size) * entity_axis_size)
        e_loc = e_b // shard_count
        slice_lo.append(shard_k * e_loc)
        slice_hi.append((shard_k + 1) * e_loc)
        Xs.append(_alloc_rows((e_loc, n_b, d_b), blocks_dir,
                              f"bucket{b}_X"))
        labs.append(np.zeros((e_loc, n_b), np.float32))
        offsb.append(np.zeros((e_loc, n_b), np.float32))
        wtsb.append(np.zeros((e_loc, n_b), np.float32))
        rids.append(np.full((e_loc, n_b), n, np.int32))
        dims.append(d_b)
    p_X = (_alloc_rows((n_passive, d_red), blocks_dir, "passive_X")
           if n_passive else None)
    p_ent = np.zeros(n_passive, np.int32)
    p_rows = np.zeros(n_passive, np.int32)
    p_off = np.zeros(n_passive, np.float32)

    # ---- pass 2: scatter each part into its blocks -----------------------
    lo = 0
    for chunk in stream_factory():
        mat_c = chunk[0].tocsr()
        m = mat_c.shape[0]
        hi = lo + m
        a = np.flatnonzero(row_active[lo:hi])
        if len(a):
            rows_g = (lo + a).astype(np.int64)
            ent = row_ent[lo:hi][a]
            slot = row_slot[lo:hi][a]
            b_of = np.searchsorted(b_starts, ent, side="right") - 1
            sub_a = mat_c[a]
            for b in np.unique(b_of):
                mask = b_of == b
                start = int(b_starts[b])
                nr = int(bucket_sizes[b])
                if shard_count > 1:
                    # only this shard's entity range of the bucket
                    loc_all = ent - start
                    mask &= ((loc_all >= slice_lo[b])
                             & (loc_all < slice_hi[b]))
                    if not mask.any():
                        continue
                loc = ent[mask] - start - slice_lo[b]
                sl = slot[mask]
                n_b = int(bucket_n_max[b])
                # projector-table slice aligned with the slice-local loc
                # (real entities only: rows past nr are pure padding)
                tbl_lo = start + slice_lo[b]
                tbl_hi = start + min(nr, slice_hi[b])
                _fill_feature_rows(
                    sub_a[mask], Xs[b], loc * n_b + sl,
                    projectors, random_projector,
                    table_ent=loc, global_ent=ent[mask],
                    raw_indices=None if projectors is None
                    else projectors.raw_indices[tbl_lo:tbl_hi, :dims[b]])
                labs[b][loc, sl] = resp[rows_g[mask]].astype(np.float32)
                offsb[b][loc, sl] = offs[rows_g[mask]]
                wtsb[b][loc, sl] = (wts[rows_g[mask]]
                                    * group_scale_perm[ent[mask]]
                                    ).astype(np.float32)
                rids[b][loc, sl] = rows_g[mask].astype(np.int32)
        p = np.flatnonzero(row_passive[lo:hi])
        if len(p):
            rows_g = (lo + p).astype(np.int64)
            pp = ppos[rows_g]
            ent_p = row_ent[lo:hi][p]
            _fill_feature_rows(
                mat_c[p], p_X, pp,
                projectors, random_projector,
                table_ent=ent_p, global_ent=ent_p,
                raw_indices=None if projectors is None
                else projectors.raw_indices)
            p_ent[pp] = ent_p.astype(np.int32)
            p_rows[pp] = rows_g.astype(np.int32)
            p_off[pp] = offs[rows_g]
        lo = hi

    stages.enter("transfer")
    host_blocks = blocks_dir is not None or keep_host_blocks
    buckets = []
    for b in range(len(bucket_sizes)):
        if host_blocks and hasattr(Xs[b], "flush"):
            Xs[b].flush()
        buckets.append(EntityBucket(
            entity_start=int(b_starts[b]), num_real=int(bucket_sizes[b]),
            X=Xs[b] if host_blocks else jnp.asarray(Xs[b], dtype),
            labels=labs[b] if host_blocks else jnp.asarray(labs[b]),
            base_offsets=offsb[b] if host_blocks else jnp.asarray(offsb[b]),
            weights=wtsb[b] if host_blocks else jnp.asarray(wtsb[b]),
            row_ids=rids[b] if host_blocks else jnp.asarray(rids[b]),
            local_entity_offset=int(slice_lo[b]),
        ))
    if p_X is not None and host_blocks and hasattr(p_X, "flush"):
        p_X.flush()
    return RandomEffectDataset(
        config=config,
        entity_codes=ent_codes,
        X=None, labels=None, base_offsets=None, weights=None, row_ids=None,
        num_samples=n,
        projectors=projectors,
        random_projector=random_projector,
        passive_X=(None if p_X is None
                   else (p_X if host_blocks else jnp.asarray(p_X, dtype))),
        passive_entity=(None if p_X is None
                        else (p_ent if host_blocks else jnp.asarray(p_ent))),
        passive_row_ids=(None if p_X is None
                         else (p_rows if host_blocks else jnp.asarray(p_rows))),
        passive_offsets=(None if p_X is None
                         else (p_off if host_blocks else jnp.asarray(p_off))),
        buckets=buckets,
        _reduced_dim=d_red,
    )


def dataset_row_stream(data: GameDataset, config:
                       RandomEffectDataConfiguration,
                       chunk_rows: int = 500_000):
    """Stream factory over an in-RAM GameDataset (row chunks) — lets the
    streamed/memmap builder run on datasets that already fit in RAM, and
    defines the part contract for loaders that stream from disk."""
    id_type = config.random_effect_type
    if id_type not in data.id_columns:
        raise KeyError(f"id type {id_type!r} not in dataset (have "
                       f"{list(data.id_columns)})")

    def factory():
        mat = data.feature_shards[config.feature_shard_id].tocsr()
        codes = np.asarray(data.id_columns[id_type])
        for lo in range(0, data.num_samples, chunk_rows):
            hi = min(lo + chunk_rows, data.num_samples)
            yield (mat[lo:hi], codes[lo:hi], data.responses[lo:hi],
                   data.offsets[lo:hi], data.weights[lo:hi])

    return factory
