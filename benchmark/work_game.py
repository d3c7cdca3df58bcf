"""The work a step of kind ``game_train`` needs, from shapes and from the
evaluations the solvers themselves count (as ``work_sparse.py``): every
coordinate's passes are credited, the line search's included, so a share of
a peak computed from these says how near the chip's limit the passes
themselves run.

As in ``work.py``, all of it counts what the algorithm needs and never what
an implementation executed: an entity's own dense block (the rows it trains
on by the columns they touch), not the padded bucket it lies in; the
projection's touched columns once, not once a slot.
"""

from __future__ import annotations

import numpy as np


def entity_work(cells, evaluations, itemsize: int) -> dict:
    """Per-entity solves: entity ``e`` made ``evaluations[e]`` passes over
    its own block of ``cells[e]`` stored values (its rows by its columns),
    two multiply-adds a value a pass."""
    n = int(np.dot(np.asarray(cells, np.int64),
                   np.asarray(evaluations, np.int64)))
    return {"flops": 4 * n, "bytes": int(itemsize) * n}


def refit_pass_flops(cells: int, slots: int, latent_dim: int) -> int:
    """One value+gradient pass of the projection refit: the margin and the
    gradient product over every stored value (2 FLOPs a multiply-add, as
    ``work.pass_flops``), and over every slot (an entity's column) the
    K-wide contraction ``B[:, P_e[d]] . c_e`` and the K-wide update ``c_e
    g_e[d]`` added into the gradient."""
    return 4 * int(cells) + 4 * int(latent_dim) * int(slots)


def refit_pass_bytes(cells: int, slots: int, rows: int, columns: int,
                     latent_dim: int, itemsize: int) -> int:
    """The least one pass must move: every stored value once, every slot's
    index once (4 bytes), every row's label, offset and weight once (12),
    and the touched columns of the projection read once and their gradient
    written once (2 x K x itemsize a column)."""
    return (int(itemsize) * int(cells) + 4 * int(slots) + 12 * int(rows)
            + 2 * int(latent_dim) * int(itemsize) * int(columns))


def refit_work(cells: int, slots: int, rows: int, columns: int,
               latent_dim: int, itemsize: int, evaluations: int) -> dict:
    """FLOPs and bytes of a refit that made ``evaluations`` passes."""
    n = int(evaluations)
    return {"flops": n * refit_pass_flops(cells, slots, latent_dim),
            "bytes": n * refit_pass_bytes(cells, slots, rows, columns,
                                          latent_dim, itemsize)}
