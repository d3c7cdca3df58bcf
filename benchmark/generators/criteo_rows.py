"""Criteo-shaped wide-sparse rows, made on the device in row blocks.

The LIBSVM ``criteo`` set (the Criteo Display Advertising Challenge, Kaggle
2014, as the LIBSVM authors preprocessed it) has 39 fields a row (13 binned
numeric, 26 categorical), one active feature a field, every ``field=value``
hashed into 1,000,000 columns, every row scaled to unit length (each of the
39 values 1/sqrt(39)) and a binary label. The rows themselves cannot be
fetched here, so this makes rows of that shape; every choice is listed under
``assumed`` in ``configs/glm-sparse-criteo.json``:

- field f has ``cardinality[f]`` values: the 13 numeric fields'
  cardinalities evenly spaced in the logarithm between the two
  ``numeric_cardinality`` ends (the published bins are not known here), the
  26 categorical fields' as ``categorical_cardinalities`` lists them (the
  challenge's own counts of distinct values a field);
- a row's value in a field has a rank drawn from a Zipf law of exponent
  ``popularity_exponent`` over the field's c values (the density of the rank
  is ``rank ** -exponent``; at 1 the rank is ``floor((c + 1) ** u) - 1``, u
  uniform). The exponent is a guess, so the pass is timed on the chip at
  others too (the configuration's ``data_report``);
- ``(field, rank)`` is hashed (murmur3's 32-bit finaliser) into
  ``[0, features)``; two pairs that collide share a column, as hashing has
  it; a row lists its columns ascending, as a LIBSVM line does, and where
  two of its fields fall on one column the later one takes the next column
  (a row never holds a column twice: a stored cell is one slot);
- labels are Bernoulli through the logistic of planted standard-normal
  coefficients over the row (whose 39 values of 1/sqrt(39) make the margin's
  variance about ``planted_scale ** 2``) plus ``planted_intercept``.

Block ``b`` of ``rows_per_block`` rows is drawn from ``data_seed`` and ``b``
alone. ``--seed`` deals the blocks in another order: every seed gives the
same rows elsewhere on the row axis, and the same work. The planes come out
slot-major (``[slots, rows]``): the layout the device holds them in. It
imports nothing of the program (the logistic is the plain reference's).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.glm import logistic_terms


def cardinalities(config: dict) -> np.ndarray:
    """Values a field can take, one count a field: numeric fields first."""
    lo, hi = config["numeric_cardinality"]
    numeric = np.geomspace(lo, hi, int(config["numeric_fields"]))
    categorical = np.asarray(config["categorical_cardinalities"], np.float64)
    out = np.round(np.concatenate([numeric, categorical])).astype(np.int64)
    if len(out) != int(config["nonzeros_per_row"]):
        raise ValueError("one active feature a field: the fields must add "
                         "up to nonzeros_per_row")
    return out


def block_order(config: dict, seed: int) -> np.ndarray:
    """The order ``--seed`` deals the row blocks in."""
    rows, block = int(config["rows"]), int(config["rows_per_block"])
    if rows % block:
        raise ValueError("rows must be a multiple of rows_per_block")
    return np.random.default_rng(seed).permutation(rows // block)


def planted(config: dict):
    """The planted coefficients, [features] float32 on the device."""
    key = jax.random.fold_in(jax.random.key(int(config["data_seed"])), 1)
    return jnp.float32(config["planted_scale"]) * jax.random.normal(
        key, (int(config["features"]),), jnp.float32)


def _hash(field, rank, features: int):
    """murmur3's finaliser over (field, rank), into [0, features)."""
    u = jnp.uint32
    h = rank.astype(u) * u(0x9E3779B1) + (field.astype(u) + u(1)) * u(
        0x7F4A7C15)
    h = h ^ (h >> 16)
    h = h * u(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * u(0xC2B2AE35)
    h = h ^ (h >> 16)
    return (h % u(features)).astype(jnp.int32)


def _rank(u, log_card, exponent: float):
    """The inverse of the law's distribution function at ``u``: a real rank
    in [1, cardinality + 1) whose density is ``rank ** -exponent``."""
    if exponent == 1.0:
        return jnp.exp(u * log_card)
    top = jnp.exp((1.0 - exponent) * log_card)  # (c + 1) ** (1 - exponent)
    return (1.0 + u * (top - 1.0)) ** (1.0 / (1.0 - exponent))


def _block(key, block_id, log_card, w_true, value, intercept, *, rows: int,
           features: int, exponent: float):
    """One block: (columns [slots, rows] int32 ascending down a row and
    distinct, labels [rows] float32)."""
    slots = log_card.shape[0]
    ku, ky = jax.random.split(jax.random.fold_in(key, block_id))
    u = jax.random.uniform(ku, (slots, rows), jnp.float32)
    card = jnp.exp(log_card)[:, None]  # cardinality + 1
    rank = jnp.clip(jnp.floor(_rank(u, log_card[:, None], exponent)) - 1.0,
                    0.0, card - 2.0).astype(jnp.int32)
    field = jnp.arange(slots, dtype=jnp.int32)[:, None]
    cols = jnp.sort(_hash(field, rank, features), axis=0)
    # strictly ascending down a row: the least majorant with steps >= 1,
    # held under the last column
    k = jnp.arange(slots, dtype=jnp.int32)[:, None]
    cols = k + jnp.minimum(jax.lax.cummax(cols - k, axis=0), features - slots)
    z = value * jnp.sum(w_true[cols], axis=0) + intercept
    _, p, _ = logistic_terms(z, jnp.float32(0.0))
    y = (jax.random.uniform(ky, (rows,), jnp.float32) < p).astype(
        jnp.float32)
    return cols, y


@partial(jax.jit, static_argnames=("rows", "features", "exponent"))
def _make(key, order, log_card, w_true, value, intercept, *, rows: int,
          features: int, exponent: float):
    blocks, slots = order.shape[0], log_card.shape[0]

    def put(j, planes):
        cols, y = planes
        cols_b, y_b = _block(key, order[j], log_card, w_true, value,
                             intercept, rows=rows, features=features,
                             exponent=exponent)
        return (jax.lax.dynamic_update_slice(cols, cols_b, (0, j * rows)),
                jax.lax.dynamic_update_slice(y, y_b, (j * rows,)))

    return jax.lax.fori_loop(0, blocks, put, (
        jnp.zeros((slots, blocks * rows), jnp.int32),
        jnp.zeros((blocks * rows,), jnp.float32)))


def make_rows(config: dict, seed: int):
    """(columns [slots, rows] int32, values [slots, rows] float32, labels
    [rows] float32) on the default device, the blocks in the order ``seed``
    deals."""
    slots = int(config["nonzeros_per_row"])
    order = block_order(config, seed)
    log_card = np.log(cardinalities(config) + 1.0).astype(np.float32)
    key = jax.random.key(int(config["data_seed"]))
    value = jnp.float32(config["value"])
    cols, y = _make(key, jnp.asarray(order, jnp.int32),
                    jnp.asarray(log_card), planted(config), value,
                    jnp.float32(config["planted_intercept"]),
                    rows=int(config["rows_per_block"]),
                    features=int(config["features"]),
                    exponent=float(config["popularity_exponent"]))
    values = jnp.full((slots, int(config["rows"])), value, jnp.float32)
    return jax.block_until_ready(cols), values, y


def describe_rows(cols, y, features: int) -> dict:
    """What the configuration's file reports of the data: the share of the
    columns that any row hits, the heaviest column's share of the
    non-zeros, rows that hold a column twice (must be 0), the positive
    rate."""
    @jax.jit
    def stats(cols, y):
        def count(k, counts):
            return counts.at[cols[k]].add(1)
        counts = jax.lax.fori_loop(0, cols.shape[0], count,
                                   jnp.zeros(features, jnp.int32))
        down = jnp.sort(cols, axis=0)
        twice = jnp.sum(jnp.any(down[1:] == down[:-1], axis=0))
        return (jnp.mean(counts > 0), jnp.max(counts) / cols.size, twice,
                jnp.mean(y))

    hit, heaviest, twice, positive = stats(cols, y)
    return {"columns_hit_share": float(hit),
            "heaviest_column_share_of_nonzeros": float(heaviest),
            "rows_with_a_column_twice": int(twice),
            "positive_rate": float(positive)}
