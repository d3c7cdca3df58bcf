"""Rows of the shape of LIBSVM's ``kdd2010 (bridge to algebra)``, made on the
host in row blocks and handed over as one CSR matrix.

The LIBSVM set (KDD Cup 2010, the winning team's sparse features) has
19,264,097 training rows over 29,890,095 binary features, 566,345,888
non-zeros (29.4 a row, rows of uneven length), every row scaled to unit
length, and a binary label (correct first attempt). The rows themselves
cannot be fetched here, so this makes rows of that shape; every choice is
listed under ``assumed`` in ``configs/glm-ragged-kddb.json``:

- a row holds ``length_floor + round(exp(length_mu + length_sigma z))``
  cells, z standard normal, cut at ``length_cap``; ``length_mu`` is solved
  (:func:`solve_length_mu`) so that the mean is the published one;
- a cell's column has a rank drawn from the law whose density is ``(rank +
  popularity_shift) ** -popularity_exponent`` over all ``features`` columns
  (a few columns in a share of all rows, most in a handful); the column of
  rank r is ``r * COLUMN_STRIDE mod features``, a bijection, so that a
  column's number says nothing of how often it occurs (with the rank as
  the column a row's ascending order would put its rarest columns in its
  last slots, every row alike); a row lists its columns ascending, as a
  LIBSVM line does, and where two of its cells fall on one column the later
  takes the next one (a row never holds a column twice: a stored cell is
  one slot);
- every cell of a row of ``l`` cells has the value ``1 / sqrt(l)``;
- labels are Bernoulli through the logistic of planted standard-normal
  coefficients over the row (the margin's variance about ``planted_scale **
  2``) plus ``planted_intercept``.

Block ``b`` of ``rows_per_block`` rows is drawn from ``data_seed`` and ``b``
alone. ``--seed`` deals the blocks in another order: every seed gives the
same rows elsewhere on the row axis, and the same work. It imports nothing
of the program and builds no plane: the layout is the program's own
(``csr_to_batch``).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp

from benchmark.generators.criteo_rows import block_order  # --seed's dealing

_WORKERS = 8  # blocks made at once: numpy's sorts and draws free the GIL
COLUMN_STRIDE = 15_485_863  # the millionth prime: rank -> column, mod features


def lengths(z: np.ndarray, config: dict, mu=None) -> np.ndarray:
    """Cells a row, for standard-normal draws ``z``."""
    mu = float(config["length_mu"]) if mu is None else mu
    raw = np.round(np.exp(mu + float(config["length_sigma"]) * z))
    return np.minimum(int(config["length_floor"]) + raw,
                      int(config["length_cap"])).astype(np.int64)


def solve_length_mu(config: dict, mean: float, draws: int = 4_000_000,
                    ) -> float:
    """The ``length_mu`` at which the law's mean length is ``mean``, by
    bisection over fixed draws (how the configuration's value was found)."""
    z = np.random.default_rng(0).normal(size=draws)
    lo, hi = 0.0, 6.0
    for _ in range(50):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if lengths(z, config, mid).mean() < mean else (
            lo, mid)
    return 0.5 * (lo + hi)


def planted(config: dict) -> np.ndarray:
    """The planted coefficients, [features] float32."""
    rng = np.random.default_rng([int(config["data_seed"]), 1])
    return (float(config["planted_scale"]) * rng.standard_normal(
        int(config["features"]), np.float32))


def _ranks(u: np.ndarray, config: dict) -> np.ndarray:
    """The inverse of the popularity law's distribution function at ``u``:
    a column in [0, features) whose density is ``(rank + shift) **
    -exponent``."""
    features = int(config["features"])
    shift = float(config["popularity_shift"])
    a = float(config["popularity_exponent"])
    lo, hi = shift, shift + features
    if a == 1.0:
        r = lo * (hi / lo) ** u
    else:
        r = (lo ** (1 - a) + u * (hi ** (1 - a) - lo ** (1 - a))) ** (
            1 / (1 - a))
    return np.clip(np.floor(r - shift), 0, features - 1).astype(np.int64)


def columns_of(ranks: np.ndarray, features: int) -> np.ndarray:
    """The column of every rank: ``rank * COLUMN_STRIDE mod features``, a
    bijection of [0, features) where the stride shares no factor with
    ``features``."""
    if np.gcd(COLUMN_STRIDE, features) != 1:
        raise ValueError(f"{features} columns share a factor with the "
                         f"stride {COLUMN_STRIDE}: ranks would collide")
    return ranks * COLUMN_STRIDE % features


def make_block(config: dict, block_id: int, w_true: np.ndarray):
    """Block ``block_id``: (cells a row [rows] int64, columns [cells] int32
    ascending and distinct within a row, values [cells] float32, labels
    [rows] float32)."""
    rows, features = int(config["rows_per_block"]), int(config["features"])
    rng = np.random.default_rng([int(config["data_seed"]), 2, block_id])
    lens = lengths(rng.standard_normal(rows), config)
    cells = int(lens.sum())
    row_of = np.repeat(np.arange(rows, dtype=np.int64), lens)
    first = np.cumsum(lens) - lens
    slot_of = np.arange(cells, dtype=np.int64) - np.repeat(first, lens)
    # ascending within a row: one sort of (row, column) keys
    key = np.sort(row_of * features
                  + columns_of(_ranks(rng.random(cells), config), features))
    cols = key - row_of * features
    # strictly ascending within a row: the least majorant with steps >= 1
    # (a running maximum of column - slot, which starts anew with every
    # row because a later row's keys are larger), held under the last
    # column
    big = 2 * features
    run = np.maximum.accumulate(cols - slot_of + row_of * big) - row_of * big
    cols = slot_of + np.minimum(run, features - np.repeat(lens, lens))
    value = (1.0 / np.sqrt(lens)).astype(np.float32)
    z = value * np.add.reduceat(w_true[cols], first) + np.float32(
        config["planted_intercept"])
    y = (rng.random(rows) < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)
    return lens, cols.astype(np.int32), np.repeat(value, lens), y


def make_rows(config: dict, seed: int):
    """(the design matrix as scipy CSR [rows, features] float32 with int32
    columns, labels [rows] float32), the blocks in the order ``seed``
    deals."""
    order = block_order(config, seed)
    w_true = planted(config)
    workers = max(1, min(_WORKERS, os.cpu_count() or 1, len(order)))
    with ThreadPoolExecutor(workers) as pool:
        blocks = list(pool.map(
            lambda b: make_block(config, int(b), w_true), order))
    indptr = np.zeros(int(config["rows"]) + 1, np.int64)
    np.cumsum(np.concatenate([b[0] for b in blocks]), out=indptr[1:])
    mat = sp.csr_matrix(
        (np.concatenate([b[2] for b in blocks]),
         np.concatenate([b[1] for b in blocks]), indptr),
        shape=(int(config["rows"]), int(config["features"])))
    return mat, np.concatenate([b[3] for b in blocks])


def describe_rows(mat, y) -> dict:
    """What the configuration's file reports of the data."""
    lens = np.diff(mat.indptr)
    counts = np.bincount(mat.indices, minlength=mat.shape[1])
    falls = np.diff(mat.indices) <= 0  # but for a row's first cell
    firsts = mat.indptr[1:-1]
    falls[firsts[(firsts > 0) & (firsts < mat.nnz)] - 1] = False
    twice = int(falls.sum())
    return {"nonzeros": int(mat.nnz),
            "mean_row_length": float(lens.mean()),
            "median_row_length": float(np.median(lens)),
            "row_length_p99": float(np.percentile(lens, 99)),
            "rows_at_the_cap_share": float(np.mean(lens == lens.max())),
            "columns_hit_share": float(np.mean(counts > 0)),
            "heaviest_column_share_of_nonzeros": float(
                counts.max() / max(mat.nnz, 1)),
            "cells_out_of_order_or_twice_in_a_row": twice,
            "positive_rate": float(np.mean(y))}
