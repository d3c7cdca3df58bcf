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
  on the device: ``indices``/``values`` of shape ``[K, N]``, padded entries
  pointing at a dummy column with value 0.
  A pass walks the slots: margins gather one slot of every row at a time
  into an ``[N]`` accumulator, gradients scatter-add one slot at a time
  into a ``[D]`` one (a tile of slots at a time over a block of few
  rows). Right for wide sparse spaces (reference policy switches
  representation around 200k features; SURVEY §7 hard-part 5). Slot-major
  because a TPU tiles a 32-bit array (8, 128) over its two minor
  dimensions: a solver loop re-lays row-major ``[N, 39]`` planes with every
  row padded to 128 lanes, 3.3x their bytes, where ``[39, N]`` pads 39 to
  40 sublanes (PERF.md, PR 29). Where the rows differ in length the
  layout holds them longest first, and further blocks of slots over the
  rows long enough to reach them (``[K_1 - K_0, n_1]``, ...), so that a
  pass walks about the stored non-zeros and not rows x longest
  (:func:`ell_block_bounds`); rows of one length give the one block.

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

    def margin_pair(self, w_eff: Array, margin_shift: Array, v_eff: Array,
                    v_shift: Array) -> tuple[Array, Array]:
        """:meth:`margins` at ``w_eff`` and a direction's margins
        ``x_i . v_eff + v_shift`` (no offsets) from one read of X: a float32
        product and sum over both vectors at once, what the TPU compiler
        makes of :meth:`margins` under ``vmap`` (two einsums would be two
        reads)."""
        with jax.named_scope(MARGINS_SCOPE):
            both = jnp.sum(self.X[:, None, :] * jnp.stack([w_eff, v_eff]),
                           axis=-1, dtype=self.acc_dtype)
            return both[:, 0] + margin_shift + self.offsets, both[:, 1] + v_shift

    def hadamard_square_sum(self, row_scalars: Array) -> Array:
        """sum_i row_scalars_i * x_i**2 — Hessian-diagonal inner sum."""
        with jax.named_scope(FEATURE_SUM_SCOPE):
            return jnp.einsum(
                "nd,n->d", self.X * self.X, row_scalars,
                preferred_element_type=self.acc_dtype,
            )


class ProductSumBatch(DenseBatch):
    """A :class:`DenseBatch`, the same leaves, whose two halves of a pass
    are float32 products and sums: the arithmetic a solver's loop gets on
    the TPU, where the compiler makes :meth:`DenseBatch.margins` a float32
    ``multiply_reduce`` (and :meth:`DenseBatch.margin_pair` is one by
    construction). At a program's top level it may make the same einsums
    MXU convolutions over operands cut to bfloat16 (it does for a batch of
    solves under ``vmap``), so a warm-started solve whose start were made
    there would test float32 trials against a start of another precision.
    ``optimize/lbfgs.py:start_evaluation`` makes a two-pass dense start on
    a TPU in this form; the fused kernel's gate still sees a dense batch."""

    __slots__ = ()

    def margins(self, w_eff: Array, margin_shift: Array) -> Array:
        with jax.named_scope(MARGINS_SCOPE):
            return (jnp.sum(self.X * w_eff, axis=-1, dtype=self.acc_dtype)
                    + margin_shift + self.offsets)

    def weighted_feature_sum(self, row_scalars: Array) -> Array:
        with jax.named_scope(FEATURE_SUM_SCOPE):
            return jnp.sum(self.X * row_scalars[:, None], axis=0,
                           dtype=self.acc_dtype)


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

    A step of fewer than ``ELL_TILE_ROWS`` elements costs far more than
    its elements (on a v5e the scatter-add takes another path, 7x the time
    a slot), so a block over fewer rows walks a tile of slots a step
    instead (:func:`ell_tile_slots`): ``[T, n]`` of both planes, summed
    over T into the margins, one scatter-add into the column sums.

    **Rows of uneven length.** A padded slot costs a pass what a stored one
    costs, so where the rows differ in length the layout holds them
    **longest first** and in several blocks of slots: ``indices``/``values``
    are slots ``[0, K_0)`` of all N rows, and ``tail`` holds further
    ``(indices, values)`` blocks ``[1, K_g - K_(g-1), n_g]``, slots
    ``[K_(g-1), K_g)`` of the first ``n_g`` rows, the only ones long enough
    to reach them (``n_1 > n_2 > ...``). A pass walks block after block with
    the same loops, each over its own rows: ``sum_g (K_g - K_(g-1)) n_g``
    slots, which :func:`ell_block_bounds` keeps near the stored non-zeros.
    ``order`` is then the permutation: the row at place ``p`` of the planes
    is the caller's row ``order[p]``. **The caller's order holds at the
    surface**: ``labels``, ``offsets`` and ``weights`` are in it, ``margins``
    returns it and the column sums take row scalars in it, at the price of
    one scatter or gather of N elements a call. A solve reads the rows
    through sums alone, so it asks once for :func:`rows_in_layout_order`
    (the row vectors permuted, ``order`` gone) and pays nothing per
    evaluation. Rows of one length give ``tail == ()`` and ``order is
    None``: the one block, and the program it always was.

    **On a mesh** the rows are split into S equal runs, one a shard
    (:func:`deal_rows`: the rows longest first dealt round-robin, so that
    every run is again longest first and holds its share of every length).
    A tail block is then ``[S, K_g - K_(g-1), m_g]``: its leading axis is
    the run, the axis a mesh shards, and it covers the first ``m_g`` rows of
    every run. One shard of such a batch is the ``S == 1`` batch of its own
    rows, so ``shard_map`` hands every device the single-device program;
    off ``shard_map`` the methods read S from the blocks' shape.

    Padded slots must satisfy ``values == 0`` (their index value is then
    irrelevant for margins; for scatter ops we still route them to a real
    column but the zero value contributes nothing).

    ``dim`` is static pytree aux data (not a leaf): the column sums'
    accumulator needs a concrete length under jit, so crossing a jit/pjit
    boundary must not trace it.
    """

    def __init__(self, indices: Array, values: Array, labels: Array,
                 offsets: Array, weights: Array, tail=(), order=None, *,
                 dim: int):
        self.indices = indices  # [K_0, N] int32
        self.values = values  # [K_0, N]
        self.labels = labels  # [N]
        self.offsets = offsets  # [N]
        self.weights = weights  # [N]
        # ((indices, values) [S, K_g - K_(g-1), m_g], ...), m_g falling
        self.tail = tuple((ix, v) for ix, v in tail)
        self.order = order  # [N] int32: place in the planes -> caller's row
        self.dim = dim  # D, static

    def tree_flatten(self):
        return ((self.indices, self.values, self.labels, self.offsets,
                 self.weights, self.tail, self.order), self.dim)

    @classmethod
    def tree_unflatten(cls, dim, leaves):
        return cls(*leaves, dim=dim)

    def _replace(self, **kw):
        fields = dict(indices=self.indices, values=self.values,
                      labels=self.labels, offsets=self.offsets,
                      weights=self.weights, tail=self.tail,
                      order=self.order, dim=self.dim)
        fields.update(kw)
        return type(self)(**fields)

    @property
    def num_features(self) -> int:
        return self.dim

    @property
    def acc_dtype(self):
        """Solver/accumulator dtype (see DenseBatch.acc_dtype)."""
        return jnp.promote_types(self.values.dtype, jnp.float32)

    @property
    def blocks(self) -> tuple:
        """Every ``(indices, values)`` block of slots, the one over all rows
        first."""
        return ((self.indices, self.values),) + self.tail

    @property
    def walked_slots(self) -> int:
        """Slots a pass walks: every block's, padding included."""
        return sum(int(np.prod(ix.shape)) for ix, _ in self.blocks)

    def _tail_prefixes(self):
        """For every tail block: its planes as ``[K, S * m_g]`` (run after
        run along the rows, as block 0 holds them) and ``take`` / ``put``,
        which read and write the ``S * m_g`` rows it covers, the first
        ``m_g`` of every run, in a vector over all rows in the planes'
        order. One run needs no reshaping: a slice of the first ``m_g``
        (said apart because the TPU compiler otherwise re-lays every
        ``[1, m_g]`` row of a plane to ``[m_g]`` inside the slot loop)."""
        for indices, values in self.tail:
            runs, k, m = indices.shape
            if runs == 1:
                yield (indices[0], values[0], lambda v, m=m: v[:m],
                       lambda v, part, m=m: v.at[:m].set(part))
                continue

            def flat(plane, runs=runs, k=k, m=m):
                return plane.transpose(1, 0, 2).reshape(k, runs * m)

            def take(v, runs=runs, m=m):
                return v.reshape(runs, -1)[:, :m].reshape(runs * m)

            def put(v, part, runs=runs, m=m):
                return lax.dynamic_update_slice(
                    v.reshape(runs, -1), part.reshape(runs, m),
                    (0, 0)).reshape(v.shape)

            yield flat(indices), flat(values), take, put

    def margins(self, w_eff: Array, margin_shift: Array) -> Array:
        with jax.named_scope(MARGINS_SCOPE):
            def walk(indices, values, z):
                tile = _walk_tile(indices)
                if tile > 1:
                    return _walk_tiles(
                        indices, values, tile,
                        lambda ix, v, z: z + jnp.sum(w_eff[ix] * v, axis=0),
                        z)

                def add_slot(k, z):
                    return z + w_eff[indices[k]] * values[k]

                return lax.fori_loop(0, indices.shape[0], add_slot, z)

            z = walk(self.indices, self.values,
                     jnp.zeros(self.indices.shape[1:],
                               jnp.result_type(w_eff, self.values)))
            for indices, values, take, put in self._tail_prefixes():
                z = put(z, walk(indices, values, take(z)))
            if self.order is not None:
                z = jnp.zeros_like(z).at[self.order].set(
                    z, unique_indices=True)
            return z + margin_shift + self.offsets

    def _column_sums(self, row_scalars: Array, square: bool) -> Array:
        """sum_i row_scalars_i * values[:, i] (or their squares), each slot
        into its column: the scatter-add over every block's slots."""
        with jax.named_scope(FEATURE_SUM_SCOPE):
            if self.order is not None:
                row_scalars = row_scalars[self.order]

            def walk(indices, values, r, sums):
                tile = _walk_tile(indices)
                if tile > 1:
                    return _walk_tiles(
                        indices, values, tile,
                        lambda ix, v, sums: sums.at[ix].add(
                            (v * v if square else v) * r[None, :]), sums)

                def add_slot(k, sums):
                    v = values[k]
                    return sums.at[indices[k]].add(
                        (v * v if square else v) * r)

                return lax.fori_loop(0, indices.shape[0], add_slot, sums)

            sums = walk(self.indices, self.values, row_scalars,
                        jnp.zeros((self.dim,),
                                  jnp.result_type(self.values, row_scalars)))
            for indices, values, take, _ in self._tail_prefixes():
                sums = walk(indices, values, take(row_scalars), sums)
            return sums

    def weighted_feature_sum(self, row_scalars: Array) -> Array:
        return self._column_sums(row_scalars, square=False)

    def hadamard_square_sum(self, row_scalars: Array) -> Array:
        return self._column_sums(row_scalars, square=True)


def rows_in_layout_order(batch: "Batch") -> "Batch":
    """``batch`` for a consumer that only sums over its rows (a solve): an
    ELL layout that holds the rows in an order of its own hands over
    ``labels``, ``offsets`` and ``weights`` in that order, with no ``order``
    left, once, so that no evaluation permutes anything (the margins of the
    result are in that order too); every other batch as it is."""
    if not isinstance(batch, EllBatch) or batch.order is None:
        return batch
    return batch._replace(labels=batch.labels[batch.order],
                          offsets=batch.offsets[batch.order],
                          weights=batch.weights[batch.order], order=None)


def deal_rows(batch: "Batch", shards: int) -> "Batch":
    """``batch`` with its rows in ``shards`` equal runs, for a mesh that
    gives every shard one run (:func:`row_partition_specs`). Only an ELL
    layout of several blocks of slots has anything to do: its rows lie
    longest first, and place ``p`` goes to run ``p % shards``, so every
    run is again longest first, a tail block covers the first
    ``ceil(n_g / shards)`` rows of every run, and the runs' work is equal
    to within a row a block. The caller's order stays at the surface
    (``order`` is dealt with the planes; a batch already in layout order
    has its row vectors dealt). ``shards`` must divide the rows
    (:func:`pad_batch` first)."""
    if not isinstance(batch, EllBatch) or not batch.tail:
        return batch
    have = batch.tail[0][0].shape[0]
    if have == shards:
        return batch
    n = batch.labels.shape[0]
    if have != 1 or n % shards:
        raise ValueError(
            f"an ELL batch of {n} rows in {have} run(s) cannot be dealt "
            f"into {shards}: deal a batch of one run whose rows {shards} "
            "divides (pad_batch first)")

    def deal(plane):  # rows on the last axis: place p -> run p % shards
        lead = plane.shape[:-1]
        return plane.reshape(lead + (-1, shards)).swapaxes(-1, -2).reshape(
            lead + (-1,))

    def deal_tail(plane):  # [1, K, n_g] -> [shards, K, ceil(n_g / shards)]
        _, k, n_g = plane.shape
        plane = jnp.pad(plane[0], ((0, 0), (0, -n_g % shards)))
        return plane.reshape(k, -1, shards).transpose(2, 0, 1)

    rows = {}
    if batch.order is None:
        rows = dict(labels=deal(batch.labels), offsets=deal(batch.offsets),
                    weights=deal(batch.weights))
    return batch._replace(
        indices=deal(batch.indices), values=deal(batch.values),
        tail=tuple((deal_tail(ix), deal_tail(v)) for ix, v in batch.tail),
        order=None if batch.order is None else deal(batch.order), **rows)


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
    minor axis of the ELL planes; of an ELL layout's further blocks of
    slots the leading axis too, which is the run (:func:`deal_rows` makes
    as many runs as the axis has shards)."""
    by_row = PartitionSpec(axis)
    if isinstance(batch, EllBatch):
        by_slot_row = PartitionSpec(None, axis)
        by_run = PartitionSpec(axis, None, None)  # a tail block: S first
        return EllBatch(by_slot_row, by_slot_row, by_row, by_row, by_row,
                        tail=((by_run, by_run),) * len(batch.tail),
                        order=None if batch.order is None else by_row,
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


# An ELL layout's blocks of slots end at multiples of ``_SUBLANES`` (a 32-bit
# plane is tiled (8, 128), so 8 slots are stored whether used or not), and
# there are at most ``_MAX_BLOCKS`` of them. Read on a v5e over the KDD-2010
# rows (142.6M non-zeros, one value+gradient pass; PERF.md, PR 35): 2 blocks
# 7.51 s, 4 4.85 s, 6 4.24 s, 8 4.02 s, 12 3.93 s, 16 3.92 s. A block has no
# fixed cost the chip shows (the pass follows the slots down to 16 blocks);
# the cap is there for the program's size, two loops a block a pass, and
# eight blocks are within 2.5% of sixteen.
_SUBLANES = 8
_MAX_BLOCKS = 8

# Below ``ELL_TILE_ROWS`` elements a step a walk pays for the step, not for
# its elements: a block over fewer rows walks a tile of slots a step
# (:func:`ell_tile_slots`). Read on a v5e into a 16.6M-column table (PERF.md,
# the long-row cell). Jitted alone, one slot a step of n rows: the scatter-add 72-75 ns a
# slot up to 8,192 rows, 8.3-9.1 from 16,384 on. In the L-BFGS solve, tiles
# from 16,384: the scatter-add 10.4 / 11.0 / 12.4 / 15.2 ns a slot in blocks
# of 87,500 / 63,955 / 43,131 / 26,443 rows, 16.4 and 19.1 in tiles of
# 24,008 and 18,720 elements (about 190 us a step beside 8.4 ns an element);
# tiles from 32,768: 8.3-9.4 ns in every block. The gather 6.6-7.1 in both.
ELL_TILE_ROWS = 32768


def ell_tile_slots(slots: int, rows: int) -> int:
    """Slots one step of a walk takes over a block of ``slots`` x ``rows``:
    one where the rows are ``ELL_TILE_ROWS`` or more, else the fewest whole
    groups of ``_SUBLANES`` slots that give a step ``ELL_TILE_ROWS``
    elements or more, and never more than the block's depth."""
    if rows >= ELL_TILE_ROWS:
        return 1
    tile = _SUBLANES * -(-ELL_TILE_ROWS // (_SUBLANES * max(rows, 1)))
    return min(tile, max(slots, 1))


def ell_walk_steps(slots: int, rows: int) -> int:
    """Loop steps a walk makes over a block of ``slots`` x ``rows``, the
    last step shorter where the tile does not divide the depth."""
    return -(-slots // ell_tile_slots(slots, rows))


def _walk_tile(indices) -> int:
    """The tile a walk over the ``[K, n]`` plane ``indices`` takes, booked
    on ``ell_walk_lowerings{form}`` (``slot`` or ``tile``) at trace time,
    when the form is decided."""
    from photon_ml_tpu.obs.metrics import REGISTRY

    tile = ell_tile_slots(*indices.shape)
    REGISTRY.counter("ell_walk_lowerings").inc(
        form="tile" if tile > 1 else "slot")
    return tile


def _walk_tiles(indices, values, tile: int, add, acc):
    """``acc = add(indices, values, acc)`` over the ``[tile, n]`` slices
    of a block's ``[K, n]`` planes, one ``fori_loop`` step a slice, and
    the last ``K mod tile`` slots in one shorter step after the loop."""
    slots = indices.shape[0]
    whole = slots // tile

    def add_tile(k, acc):
        return add(lax.dynamic_slice_in_dim(indices, k * tile, tile),
                   lax.dynamic_slice_in_dim(values, k * tile, tile), acc)

    acc = lax.fori_loop(0, whole, add_tile, acc)
    if slots % tile:
        acc = add(indices[whole * tile:], values[whole * tile:], acc)
    return acc


def ell_block_bounds(lengths: np.ndarray, multiple: int = _SUBLANES) -> list:
    """Where the blocks of slots of an ELL layout end, from the rows'
    lengths alone: ascending multiples of ``multiple``, the last the longest
    row's length rounded up. A row of length l is walked up to the first
    bound at or over l, so the bounds are chosen to make ``sum_rows
    bound(l)`` least over at most ``_MAX_BLOCKS`` blocks: a dynamic
    programme over the histogram of the rounded lengths, the fewest blocks
    among equals. Rows of one length give one bound; a log-normal law of
    mean 29 cut at 128 gives 8 and walks 1.14x its non-zeros where one
    block walks 4.3x (PERF.md, PR 35). Never more slots than one block:
    that is always among the choices."""
    lengths = np.asarray(lengths)
    if not lengths.size:
        return [multiple]
    groups = -(-np.maximum(lengths, 1) // multiple)  # length in multiples
    hist = np.bincount(groups)
    cand = np.nonzero(hist)[0]
    bound = cand * multiple
    rows_upto = np.cumsum(hist)[cand]  # rows no longer than bound[j]
    # best[b, j]: least slots walked by the rows up to bound[j] in b + 1
    # blocks whose last ends there
    blocks = min(_MAX_BLOCKS, cand.size)
    best = np.full((blocks, cand.size), np.inf)
    came_from = np.zeros((blocks, cand.size), np.int64)
    best[0] = bound * rows_upto
    for b in range(1, blocks):
        for j in range(b, cand.size):
            i = np.arange(b - 1, j)
            walked = best[b - 1, i] + bound[j] * (rows_upto[j] - rows_upto[i])
            came_from[b, j] = i[np.argmin(walked)]
            best[b, j] = walked.min()
    b = int(np.argmin(best[:, -1]))
    out, j = [], cand.size - 1
    for b in range(b, -1, -1):
        out.append(int(bound[j]))
        j = came_from[b, j]
    return out[::-1]


def _pack_block(indptr, cols, data, rows, lo: int, hi: int, stage):
    """Slots ``[lo, hi)`` of the CSR rows ``rows`` (``None``: all, in
    order) as row-major ``[len(rows), hi - lo]`` planes, zeros where a row
    ends sooner: the (row, slot) coordinate of every stored element in
    bulk from ``indptr``, no per-row Python loop."""
    starts = indptr[:-1] if rows is None else indptr[rows]
    ends = indptr[1:] if rows is None else indptr[rows + 1]
    count = np.clip(ends - starts - lo, 0, hi - lo)
    indices = np.zeros((len(count), hi - lo), dtype=np.int32)
    values = np.zeros((len(count), hi - lo), dtype=stage)
    total = int(count.sum())
    if total:
        row_of = np.repeat(np.arange(len(count), dtype=np.int32), count)
        slot_of = (np.arange(total, dtype=np.int64)
                   - np.repeat(np.cumsum(count) - count, count))
        source = np.repeat(starts + lo, count) + slot_of
        indices[row_of, slot_of] = cols[source]
        values[row_of, slot_of] = data[source]
    return indices, values


def _ell_from_csr_arrays(indptr, cols, data, dim: int, labels, offsets,
                         weights, pad_to_multiple: int, dtype) -> EllBatch:
    """The layout of a CSR matrix given as its three arrays: the blocks'
    bounds from the rows' lengths (:func:`ell_block_bounds`), the rows
    longest first where there is more than one block, every block packed
    on the host and placed slot-major. Books what a pass will walk against
    what the matrix stores (``ell_walked_slots`` / ``ell_stored_slots``)
    and the loop steps one of its walks makes on one device
    (``ell_walk_steps``: dealt over a mesh, a shard's walk takes its tile
    from its own share of the rows)."""
    from photon_ml_tpu.obs import trace
    from photon_ml_tpu.obs.metrics import REGISTRY

    indptr = np.asarray(indptr, np.int64)
    n = len(indptr) - 1
    lens = np.diff(indptr)
    nnz = int(indptr[-1]) if n else 0
    bounds = ell_block_bounds(lens, pad_to_multiple)
    meta = jnp.promote_types(dtype, jnp.float32)
    # Host staging in the narrowest exact container (f64 only when asked).
    stage = np.float64 if meta == jnp.float64 else np.float32
    # the rows each block covers: those that reach its first slot
    covered = [n if lo == 0 else int(np.count_nonzero(lens > lo))
               for lo in [0] + bounds[:-1]]
    steps = sum(ell_walk_steps(hi - lo, rows) for lo, hi, rows in zip(
        [0] + bounds[:-1], bounds, covered))
    with trace.span("ell.build", rows=n, nonzeros=nnz, blocks=len(bounds),
                    steps=steps):
        order = None
        if len(bounds) > 1:
            order = np.argsort(-lens, kind="stable").astype(np.int32)
        planes, lo = [], 0
        for hi, count in zip(bounds, covered):
            if order is None:  # one block over the rows as they come
                rows = None
            else:  # the rows that reach slot ``lo``, longest first
                rows = order[:count]
            indices = values = None
            if rows is None and nnz and stage == np.float32:
                from photon_ml_tpu.io.native_loader import pack_ell_native

                indices = np.zeros((n, hi), dtype=np.int32)
                values = np.zeros((n, hi), dtype=stage)
                if not pack_ell_native(indptr, cols, data, hi, indices,
                                       values):
                    indices = None
            if indices is None:
                indices, values = _pack_block(indptr, cols, data, rows, lo,
                                              hi, stage)
            # host planes are packed row-major (the native packer's form);
            # the device holds them slot-major
            planes.append((jnp.asarray(indices.T, jnp.int32),
                           jnp.asarray(values.T, dtype)))
            lo = hi
    batch = ell_batch(*planes[0], labels, dim, offsets, weights, dtype=dtype)
    if order is not None:  # the further blocks: one run of rows, [1, K, n]
        batch = batch._replace(
            tail=tuple((ix[None], v[None]) for ix, v in planes[1:]),
            order=jnp.asarray(order))
    REGISTRY.counter("ell_stored_slots").inc(nnz, blocks=len(bounds))
    REGISTRY.counter("ell_walked_slots").inc(batch.walked_slots,
                                             blocks=len(bounds))
    REGISTRY.counter("ell_walk_steps").inc(steps, blocks=len(bounds))
    return batch


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
    data/RandomEffectDataSet.scala:169-206). The rows' lengths decide the
    blocks of slots (:func:`ell_block_bounds`, bounds at multiples of
    ``pad_to_multiple``): rows of one length give the one ``[K, N]``
    block, rows of uneven length several, held longest first.
    """
    return _ell_from_csr_arrays(mat.indptr, np.asarray(mat.indices),
                                np.asarray(mat.data), mat.shape[1], labels,
                                offsets, weights, pad_to_multiple, dtype)


def ell_from_rows(
    rows: list[tuple[np.ndarray, np.ndarray]],
    dim: int,
    labels: np.ndarray,
    offsets: np.ndarray | None = None,
    weights: np.ndarray | None = None,
    pad_to_multiple: int = 8,
    dtype=jnp.float32,
) -> EllBatch:
    """Build an ELL batch from per-row (indices, values) sparse rows: the
    layout :func:`ell_from_csr` gives the same matrix. Block bounds are
    multiples of ``pad_to_multiple``, which stabilizes compiled shapes
    across similar batches.
    """
    indptr = np.zeros(len(rows) + 1, np.int64)
    np.cumsum([len(ix) for ix, _ in rows], out=indptr[1:])
    cols = np.concatenate([np.asarray(ix, np.int32) for ix, _ in rows]
                          + [np.zeros(0, np.int32)])
    data = np.concatenate([np.asarray(v, np.float64) for _, v in rows]
                          + [np.zeros(0, np.float64)])
    return _ell_from_csr_arrays(indptr, cols, data, dim, labels, offsets,
                                weights, pad_to_multiple, dtype)


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
    # They are the shortest rows there can be, so they lie last in the
    # planes, behind every row the further blocks of slots cover: last in
    # the one run of rows, so a batch is padded before it is dealt.
    if batch.tail and batch.tail[0][0].shape[0] != 1:
        raise ValueError(
            f"an ELL batch dealt into {batch.tail[0][0].shape[0]} runs of "
            "rows cannot be padded: pad_batch before deal_rows")
    order = batch.order
    if order is not None:
        order = jnp.concatenate(
            [order, jnp.arange(n, target_rows, dtype=order.dtype)])
    return batch._replace(
        indices=jnp.pad(batch.indices, ((0, 0), (0, pad))),
        values=jnp.pad(batch.values, ((0, 0), (0, pad))),
        order=order,
        **meta,
    )
