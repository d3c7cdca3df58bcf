"""Device-resident batch representations of labeled GLM data.

TPU-native replacement for the reference's ``LabeledPoint`` rows
(reference: photon-ml/src/main/scala/com/linkedin/photon/ml/data/
LabeledPoint.scala:29-44 — (label, sparse features, offset, weight) with
``computeMargin = x.w + offset``). Where the reference streams rows through
Spark closures, we hold the whole shard as columnar arrays so the margin is
one matmul on the MXU.

Two layouts:

- :class:`DenseBatch` — features as a dense ``[N, D]`` matrix. Right for
  narrow-to-medium feature spaces (the reference densifies per-entity blocks
  the same way after projection).
- :class:`EllBatch`  — padded row-sparse (ELL) layout, held **slot-major**
  on the device: ``indices``/``values`` of shape ``[K, N]`` with ``K`` = max
  nnz per row, padded entries pointing at a dummy column with value 0.
  A pass walks the slots: margins gather one slot of every row at a time
  into an ``[N]`` accumulator, gradients scatter-add one slot at a time
  into a ``[D]`` one. Right for wide sparse spaces (reference policy switches
  representation around 200k features; SURVEY §7 hard-part 5). Slot-major
  because a TPU tiles a 32-bit array (8, 128) over its two minor
  dimensions: a solver loop re-lays row-major ``[N, 39]`` planes with every
  row padded to 128 lanes, 3.3x their bytes, where ``[39, N]`` pads 39 to
  40 sublanes (PERF.md, PR 29).

- :class:`ProjectionRefitBatch` — the factored random effect's projection
  refit: the rows are those of per-entity blocks ``[E, N, D_b]`` in each
  entity's reduced feature space, the coefficients are ``vec(B)`` of the
  shared ``[K, D]`` projection, and a row's features are the Kronecker
  product ``c_e (x) x`` of its entity's latent coefficients with the row,
  which is never built: margins gather each entity's columns of ``B``,
  gradients scatter-add K-wide columns back into a ``[D, K]`` table.

All carry ``labels``, ``offsets``, ``weights`` (length N) and are registered
pytrees so they cross ``jit``/``pjit`` boundaries; the first two shard over
the mesh data axis (:func:`row_partition_specs` says which axis of each leaf
holds the rows).
"""

from __future__ import annotations

from typing import NamedTuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec

Array = jnp.ndarray


# ``jax.named_scope``s of the two halves of a pass over the rows, in both
# layouts' methods: metadata on the device's operations, under
# ``objective.value_and_grad`` / ``objective.hvp`` (ops/aggregators.py).
MARGINS_SCOPE = "objective.margins"
FEATURE_SUM_SCOPE = "objective.feature_sum"


class DenseBatch(NamedTuple):
    """Columnar dense design matrix plus per-row metadata."""

    X: Array  # [N, D]
    labels: Array  # [N]
    offsets: Array  # [N]
    weights: Array  # [N]  (0 for padded rows => they drop out of every sum)

    @property
    def num_features(self) -> int:
        return self.X.shape[-1]

    @property
    def acc_dtype(self):
        """Solver/accumulator dtype for this batch: at least f32 even over
        a bf16 design matrix (mixed precision keeps parameters and sums
        full-precision; only the X stream is low-precision), never
        downcasting f64."""
        return jnp.promote_types(self.X.dtype, jnp.float32)

    def margins(self, w_eff: Array, margin_shift: Array) -> Array:
        """x_i . w_eff + margin_shift + offset_i, batched on the MXU."""
        with jax.named_scope(MARGINS_SCOPE):
            return (
                jnp.einsum(
                    "nd,d->n", self.X, w_eff,
                    preferred_element_type=self.acc_dtype
                )
                + margin_shift
                + self.offsets
            )

    def weighted_feature_sum(self, row_scalars: Array) -> Array:
        """sum_i row_scalars_i * x_i — the gradient's vector sum (X^T r)."""
        with jax.named_scope(FEATURE_SUM_SCOPE):
            return jnp.einsum(
                "nd,n->d", self.X, row_scalars,
                preferred_element_type=self.acc_dtype
            )

    def hadamard_square_sum(self, row_scalars: Array) -> Array:
        """sum_i row_scalars_i * x_i**2 — Hessian-diagonal inner sum."""
        with jax.named_scope(FEATURE_SUM_SCOPE):
            return jnp.einsum(
                "nd,n->d", self.X * self.X, row_scalars,
                preferred_element_type=self.acc_dtype,
            )


@jax.tree_util.register_pytree_node_class
class EllBatch:
    """Padded row-sparse (ELL) design matrix, slot-major on the device.

    ``indices`` and ``values`` are ``[K, N]``: slot ``k`` of every row lies
    contiguous, the rows on the minor (lane) axis. A TPU tiles a 32-bit
    array (8, 128) over its two minor dimensions. A stored ``[N, K]`` array
    is not padded, but inside a solver's ``while`` loop XLA re-lays such
    planes row-major tiled, K = 39 padded to 128 lanes: the L-BFGS program
    over 11.5M rows asked for 21.5 GB of a 16 GB chip and did not compile,
    where ``[K, N]`` pads 39 to 40 sublanes and runs at a peak of 3.9 GB
    (the chip's readings: PERF.md, PR 29). Every method walks the slots in
    order, one ``fori_loop`` step a slot: row ``k`` of both planes (a
    contiguous ``[N]`` vector in this layout) meets an accumulator of shape
    ``[N]`` (margins) or ``[D]`` (column sums), so no ``[K, N]`` value
    exists between the planes and the result. Written as whole-plane
    expressions the same pass cost a third more at 11.5M rows: XLA
    materialised a flat 447M-element product and summed it in a second
    loop (PERF.md, PR 30). Build one from host data with :func:`ell_from_csr` /
    :func:`ell_from_rows`, or from arrays already in this layout with
    :func:`ell_batch`.

    Padded slots must satisfy ``values == 0`` (their index value is then
    irrelevant for margins; for scatter ops we still route them to a real
    column but the zero value contributes nothing).

    ``dim`` is static pytree aux data (not a leaf): the column sums'
    accumulator needs a concrete length under jit, so crossing a jit/pjit
    boundary must not trace it.
    """

    def __init__(self, indices: Array, values: Array, labels: Array,
                 offsets: Array, weights: Array, dim: int):
        self.indices = indices  # [K, N] int32
        self.values = values  # [K, N]
        self.labels = labels  # [N]
        self.offsets = offsets  # [N]
        self.weights = weights  # [N]
        self.dim = dim  # D, static

    def tree_flatten(self):
        return ((self.indices, self.values, self.labels, self.offsets,
                 self.weights), self.dim)

    @classmethod
    def tree_unflatten(cls, dim, leaves):
        return cls(*leaves, dim=dim)

    def _replace(self, **kw):
        fields = dict(indices=self.indices, values=self.values,
                      labels=self.labels, offsets=self.offsets,
                      weights=self.weights, dim=self.dim)
        fields.update(kw)
        return EllBatch(**fields)

    @property
    def num_features(self) -> int:
        return self.dim

    @property
    def acc_dtype(self):
        """Solver/accumulator dtype (see DenseBatch.acc_dtype)."""
        return jnp.promote_types(self.values.dtype, jnp.float32)

    def margins(self, w_eff: Array, margin_shift: Array) -> Array:
        with jax.named_scope(MARGINS_SCOPE):
            def add_slot(k, z):
                return z + w_eff[self.indices[k]] * self.values[k]

            z = jnp.zeros(self.indices.shape[1:],
                          jnp.result_type(w_eff, self.values))
            return (
                lax.fori_loop(0, self.indices.shape[0], add_slot, z)
                + margin_shift
                + self.offsets
            )

    def _column_sums(self, row_scalars: Array, square: bool) -> Array:
        """sum_i row_scalars_i * values[:, i] (or their squares), each slot
        into its column: the scatter-add over all K x N stored slots."""
        with jax.named_scope(FEATURE_SUM_SCOPE):
            def add_slot(k, sums):
                v = self.values[k]
                return sums.at[self.indices[k]].add(
                    (v * v if square else v) * row_scalars)

            sums = jnp.zeros((self.dim,),
                             jnp.result_type(self.values, row_scalars))
            return lax.fori_loop(0, self.indices.shape[0], add_slot, sums)

    def weighted_feature_sum(self, row_scalars: Array) -> Array:
        return self._column_sums(row_scalars, square=False)

    def hadamard_square_sum(self, row_scalars: Array) -> Array:
        return self._column_sums(row_scalars, square=True)


# ``jax.named_scope`` of the gather of each entity's columns of the shared
# projection, ``B[:, P_e]``: in the refit's margins and in the latent stage
# of the factored coordinate (game/coordinate.py).
PROJECT_SCOPE = "factored.project"


def projection_table(B: Array) -> Array:
    """``[K, D]`` projection -> the ``[D + 1, K]`` table the gathers read:
    a column of ``B`` a row (K lies contiguous, so one gathered index brings
    a whole column), and a zero row at ``D``, where the unused slots of an
    index-map projector point (``raw_indices`` holds ``raw_dim`` there)."""
    return jnp.concatenate([B.T, jnp.zeros((1, B.shape[0]), B.dtype)])


def gather_projection(table: Array, columns: Array) -> Array:
    """Every entity's own columns of the projection, ``B[:, P_e]`` as
    ``[E, D_b, K]``: ``columns`` is ``[E, D_b]`` int32 into ``table``'s rows,
    an unused slot holds ``D`` and gathers zeros."""
    with jax.named_scope(PROJECT_SCOPE):
        return table[columns]


class RefitBlock(NamedTuple):
    """One block of per-entity rows in the refit: a bucket of the
    random-effect data set with its entities' column maps and latent
    coefficients."""

    X: Array  # [E, N, D_b] rows in each entity's reduced space
    columns: Array  # [E, D_b] int32 raw column of each slot (dim = unused)
    latent: Array  # [E, K] the entities' latent coefficients c_e


@jax.tree_util.register_pytree_node_class
class ProjectionRefitBatch:
    """The factored random effect's projection refit as a batch layout.

    For entity ``e`` with latent coefficients ``c_e`` and a row ``x~`` of its
    block, stored over the entity's own columns ``P_e`` of the raw space,
    the margin is ``c_e^T B[:, P_e] x~``: linear in ``vec(B)``, with
    Kronecker features ``c_e (x) x``. The reference materialises them
    (kroneckerProductFeaturesAndCoefficients,
    FactoredRandomEffectCoordinate.scala:271); at K = 32 and 128 columns an
    entity that is a ``[rows, 4096]`` matrix, so here a pass is

    - margins: ``w_e = B[:, P_e]^T c_e`` (a gather of K-wide columns and a
      contraction over K), then ``einsum("end,ed->en", X, w)``;
    - feature sum: ``g_e = X_e^T r_e``, then ``B_grad[:, P_e[d]] += c_e
      g_e[d]``, a scatter-add of K-wide columns into a ``[D + 1, K]`` table
      whose last row takes the unused slots and is dropped.

    The coefficient vector is ``B.reshape(-1)`` (``[K, D]`` row-major), the
    rows are the blocks' rows flattened block after block; ``labels``,
    ``offsets`` and ``weights`` are flat in that order (weight 0 on padding
    rows). ``dim`` (D) is static pytree aux data, as in :class:`EllBatch`.
    Products at "highest": they are a small part of a pass (the gather and
    the scatter-add are the rest) and the refit is one solve over every
    entity's rows, where bf16 passes would round ``w_e`` to three digits.
    """

    def __init__(self, blocks, labels: Array, offsets: Array, weights: Array,
                 dim: int):
        self.blocks = tuple(RefitBlock(*b) for b in blocks)
        self.labels = labels  # [R]
        self.offsets = offsets  # [R]
        self.weights = weights  # [R]
        self.dim = dim  # D, static

    def tree_flatten(self):
        return ((self.blocks, self.labels, self.offsets, self.weights),
                self.dim)

    @classmethod
    def tree_unflatten(cls, dim, leaves):
        return cls(*leaves, dim=dim)

    def _replace(self, **kw):
        fields = dict(blocks=self.blocks, labels=self.labels,
                      offsets=self.offsets, weights=self.weights,
                      dim=self.dim)
        fields.update(kw)
        return ProjectionRefitBatch(**fields)

    @property
    def latent_dim(self) -> int:
        return self.blocks[0].latent.shape[-1]

    @property
    def num_features(self) -> int:
        return self.latent_dim * self.dim

    @property
    def acc_dtype(self):
        """Solver/accumulator dtype (see DenseBatch.acc_dtype)."""
        return jnp.promote_types(self.blocks[0].X.dtype, jnp.float32)

    def entity_coefficients(self, w_eff: Array) -> list:
        """Per block, every entity's coefficients over its own columns,
        ``w_e = B[:, P_e]^T c_e`` as ``[E, D_b]``, for ``w_eff`` =
        ``vec(B)``."""
        table = projection_table(w_eff.reshape(self.latent_dim, self.dim))
        return [jnp.einsum("edk,ek->ed",
                           gather_projection(table, b.columns), b.latent,
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=self.acc_dtype)
                for b in self.blocks]

    def margins(self, w_eff: Array, margin_shift: Array) -> Array:
        with jax.named_scope(MARGINS_SCOPE):
            z = [jnp.einsum("end,ed->en", b.X, w,
                            precision=lax.Precision.HIGHEST,
                            preferred_element_type=self.acc_dtype
                            ).reshape(-1)
                 for b, w in zip(self.blocks,
                                 self.entity_coefficients(w_eff))]
            return jnp.concatenate(z) + margin_shift + self.offsets

    def _column_sums(self, row_scalars: Array, square: bool) -> Array:
        """sum over rows of row_scalars x (c_e (x) x) (or its elementwise
        square), each slot's K-wide column into its raw column."""
        with jax.named_scope(FEATURE_SUM_SCOPE):
            sums = jnp.zeros(
                (self.dim + 1, self.latent_dim),
                jnp.result_type(self.acc_dtype, row_scalars,
                                self.blocks[0].latent))
            at = 0
            for b in self.blocks:
                e, n, _ = b.X.shape
                r = row_scalars[at:at + e * n].reshape(e, n)
                at += e * n
                g = jnp.einsum("end,en->ed", b.X * b.X if square else b.X,
                               r, precision=lax.Precision.HIGHEST,
                               preferred_element_type=sums.dtype)
                c = b.latent * b.latent if square else b.latent
                sums = sums.at[b.columns].add(g[:, :, None] * c[:, None, :])
            return sums[:self.dim].T.reshape(-1)

    def weighted_feature_sum(self, row_scalars: Array) -> Array:
        return self._column_sums(row_scalars, square=False)

    def hadamard_square_sum(self, row_scalars: Array) -> Array:
        return self._column_sums(row_scalars, square=True)


Batch = Union[DenseBatch, EllBatch, ProjectionRefitBatch]


def dense_batch(
    X: np.ndarray,
    labels: np.ndarray,
    offsets: np.ndarray | None = None,
    weights: np.ndarray | None = None,
    dtype=jnp.float32,
) -> DenseBatch:
    n = X.shape[0]
    # Per-row metadata stays exact even for low-precision features: labels,
    # offsets and weights are at least float32 (counts > 256 and cumulative
    # weight sums would corrupt in bf16).
    meta = jnp.promote_types(dtype, jnp.float32)
    return DenseBatch(
        X=jnp.asarray(X, dtype=dtype),
        labels=jnp.asarray(labels, dtype=meta),
        offsets=jnp.zeros(n, meta)
        if offsets is None
        else jnp.asarray(offsets, meta),
        weights=jnp.ones(n, meta)
        if weights is None
        else jnp.asarray(weights, meta),
    )


def ell_batch(
    indices,
    values,
    labels,
    dim: int,
    offsets=None,
    weights=None,
    dtype=jnp.float32,
) -> EllBatch:
    """An ELL batch from planes already in the device layout: ``indices``
    and ``values`` slot-major ``[K, N]`` (host or device arrays; device
    arrays of the right dtype are taken as they are, no copy), ``dim``
    columns. The caller vouches for what :func:`ell_from_csr` arranges: no
    column twice in a row, padded slots of value 0, indices in
    ``[0, dim)``."""
    if indices.ndim != 2 or indices.shape != values.shape:
        raise ValueError(
            f"indices {indices.shape} and values {values.shape} must both "
            "be [K, N]")
    n = indices.shape[1]
    if np.shape(labels) != (n,):
        raise ValueError(
            f"planes {indices.shape} are slot-major [K, N] and hold "
            f"{n} rows, labels {np.shape(labels)} do not")
    meta = jnp.promote_types(dtype, jnp.float32)
    return EllBatch(
        indices=jnp.asarray(indices, jnp.int32),
        values=jnp.asarray(values, dtype),
        labels=jnp.asarray(labels, meta),
        offsets=jnp.zeros(n, meta)
        if offsets is None
        else jnp.asarray(offsets, meta),
        weights=jnp.ones(n, meta)
        if weights is None
        else jnp.asarray(weights, meta),
        dim=dim,
    )


def row_partition_specs(batch: "Batch", axis: str):
    """A pytree shaped like ``batch`` of ``PartitionSpec``s that shard the
    rows over mesh axis ``axis``: the leading axis of every leaf, but the
    minor axis of the ELL planes."""
    by_row = PartitionSpec(axis)
    if isinstance(batch, EllBatch):
        by_slot_row = PartitionSpec(None, axis)
        return EllBatch(by_slot_row, by_slot_row, by_row, by_row, by_row,
                        dim=batch.dim)
    return DenseBatch(by_row, by_row, by_row, by_row)


def canonicalized_csr(mat):
    """CSR with duplicate (row, col) entries summed — the dense toarray()
    behavior every sparse consumer must match. No copy when already
    canonical; copies before mutating otherwise (callers may not own the
    matrix)."""
    if not mat.has_canonical_format:
        mat = mat.copy()
        mat.sum_duplicates()
    return mat


def ell_from_csr(
    mat,
    labels: np.ndarray,
    offsets: np.ndarray | None = None,
    weights: np.ndarray | None = None,
    pad_to_multiple: int = 8,
    dtype=jnp.float32,
) -> EllBatch:
    """Build an ELL batch straight from a scipy CSR matrix, vectorized.

    The (row, slot) coordinate of every stored element is computed in bulk
    from the CSR ``indptr`` — no per-row Python loop — so packing a
     10M-row shard is a handful of NumPy ops (the ingestion-scale analog of
    the reference's distributed build,
    data/RandomEffectDataSet.scala:169-206).
    """
    n, dim = mat.shape
    indptr = np.asarray(mat.indptr)
    lens = np.diff(indptr)
    k = int(lens.max()) if n else 1
    k = max(1, -(-max(k, 1) // pad_to_multiple) * pad_to_multiple)
    meta = jnp.promote_types(dtype, jnp.float32)
    stage = np.float64 if meta == jnp.float64 else np.float32
    indices = np.zeros((n, k), dtype=np.int32)
    values = np.zeros((n, k), dtype=stage)
    if mat.nnz:
        packed = False
        if stage == np.float32:
            from photon_ml_tpu.io.native_loader import pack_ell_native

            packed = pack_ell_native(indptr, mat.indices, mat.data, k,
                                     indices, values)
        if not packed:
            row_of = np.repeat(np.arange(n), lens)
            slot_of = np.arange(mat.nnz) - np.repeat(indptr[:-1], lens)
            indices[row_of, slot_of] = mat.indices
            values[row_of, slot_of] = mat.data
    # host planes are packed row-major (the native packer's form); the
    # device holds them slot-major
    return ell_batch(indices.T, values.T, labels, dim, offsets, weights,
                     dtype=dtype)


def ell_from_rows(
    rows: list[tuple[np.ndarray, np.ndarray]],
    dim: int,
    labels: np.ndarray,
    offsets: np.ndarray | None = None,
    weights: np.ndarray | None = None,
    pad_to_multiple: int = 8,
    dtype=jnp.float32,
) -> EllBatch:
    """Build an ELL batch from per-row (indices, values) sparse rows.

    K is padded up to a multiple of ``pad_to_multiple`` to stabilize compiled
    shapes across similar batches.
    """
    n = len(rows)
    k = max((len(ix) for ix, _ in rows), default=1)
    k = max(1, -(-k // pad_to_multiple) * pad_to_multiple)
    meta = jnp.promote_types(dtype, jnp.float32)
    # Host staging in the narrowest exact container (f64 only when asked).
    stage = np.float64 if meta == jnp.float64 else np.float32
    indices = np.zeros((n, k), dtype=np.int32)
    values = np.zeros((n, k), dtype=stage)
    for i, (ix, v) in enumerate(rows):
        indices[i, : len(ix)] = ix
        values[i, : len(v)] = v
    # host planes are packed row-major (the native packer's form); the
    # device holds them slot-major
    return ell_batch(indices.T, values.T, labels, dim, offsets, weights,
                     dtype=dtype)


def pad_batch(batch: Batch, target_rows: int) -> Batch:
    """Zero-pad a batch to ``target_rows`` rows (weights 0 => no-op rows).

    Used to make shard sizes uniform before placing a batch on a device mesh.
    """
    n = batch.labels.shape[0]
    if n == target_rows:
        return batch
    if n > target_rows:
        raise ValueError(f"batch has {n} rows > target {target_rows}")
    pad = target_rows - n
    meta = dict(
        labels=jnp.pad(batch.labels, (0, pad)),
        offsets=jnp.pad(batch.offsets, (0, pad)),
        weights=jnp.pad(batch.weights, (0, pad)),
    )
    if isinstance(batch, DenseBatch):
        return DenseBatch(X=jnp.pad(batch.X, ((0, pad), (0, 0))), **meta)
    # ELL: padded rows point at column 0 with value 0 — inert in every sum.
    return EllBatch(
        indices=jnp.pad(batch.indices, ((0, 0), (0, pad))),
        values=jnp.pad(batch.values, ((0, 0), (0, pad))),
        dim=batch.dim,
        **meta,
    )
