"""Rows of the shape of LIBSVM's ``webspam`` (trigram), made on the host in
row blocks and handed over as one CSR matrix.

The LIBSVM set (the PASCAL Large Scale Learning Challenge 2008's web-spam
pages as byte trigrams) has 350,000 rows over 16,609,143 features,
1,304,697,446 non-zeros (3,727.7 a row, rows of very uneven length), every
row of unit length, and a binary label (spam or not). The rows themselves
are not read, so this makes rows of that shape; every choice is
listed under ``assumed`` in ``configs/glm-longrow-webspam.json``:

- a row holds ``round(exp(length_mu + length_sigma z))`` cells, z standard
  normal, at least one and cut at ``length_cap``; ``length_mu`` is solved
  (:func:`solve_length_mu`) so that the mean is the published one;
- a cell's column has a rank drawn from the law whose density is ``(rank +
  popularity_shift) ** -popularity_exponent`` over all ``features``
  columns, scattered to a column by ``kddb_rows.columns_of``; a row lists
  its columns ascending and distinct, as ``kddb_rows`` makes them (where two
  of a row's cells fall on one column the later takes the next one);
- a cell's value is a count, geometric with success ``count_p``, and every
  row is scaled to unit length;
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
from benchmark.generators.kddb_rows import _ranks, columns_of, planted

_WORKERS = 8  # blocks made at once: numpy's sorts and draws free the GIL


def lengths(z: np.ndarray, config: dict, mu=None) -> np.ndarray:
    """Cells a row, for standard-normal draws ``z``."""
    mu = float(config["length_mu"]) if mu is None else mu
    raw = np.round(np.exp(mu + float(config["length_sigma"]) * z))
    return np.clip(raw, 1, int(config["length_cap"])).astype(np.int64)


def solve_length_mu(config: dict, mean: float, draws: int = 4_000_000,
                    ) -> float:
    """The ``length_mu`` at which the law's mean length is ``mean``, by
    bisection over fixed draws (how the configuration's value was found)."""
    z = np.random.default_rng(0).normal(size=draws)
    lo, hi = 0.0, 12.0
    for _ in range(50):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if lengths(z, config, mid).mean() < mean else (
            lo, mid)
    return 0.5 * (lo + hi)


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
    key = np.sort(row_of * features
                  + columns_of(_ranks(rng.random(cells), config), features))
    cols = key - row_of * features
    del key
    # strictly ascending within a row, as kddb_rows.make_block
    big = 2 * features
    run = np.maximum.accumulate(cols - slot_of + row_of * big) - row_of * big
    cols = slot_of + np.minimum(run, features - np.repeat(lens, lens))
    del run, slot_of, row_of
    counts = rng.geometric(float(config["count_p"]), cells).astype(
        np.float32)
    norms = np.sqrt(np.add.reduceat(counts * counts, first))
    values = counts / np.repeat(norms, lens)
    z = np.add.reduceat(values * w_true[cols], first) + np.float32(
        config["planted_intercept"])
    y = (rng.random(rows) < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)
    return lens, cols.astype(np.int32), values, y


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
    cols = np.concatenate([b[1] for b in blocks])
    values = np.concatenate([b[2] for b in blocks])
    labels = np.concatenate([b[3] for b in blocks])
    del blocks
    mat = sp.csr_matrix((values, cols, indptr),
                        shape=(int(config["rows"]), int(config["features"])))
    return mat, labels
