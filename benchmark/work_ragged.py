"""The work a step of kind ``glm_ragged_fit`` needs, from the matrix's
stored non-zeros and from the evaluations the solver itself counts
(``OptimizationResult.evaluations``), as ``work_sparse.py`` credits the
fixed-length sparse kind.

It counts what the algorithm needs and never what an implementation
executed: a pass is credited its **stored non-zeros**, whatever slots a
layout pads them into and walks, so a share of a peak computed from these
reads the same whatever layout or kernel makes the pass, and padding can
only lower it.
"""

from __future__ import annotations

from benchmark.work_sparse import _total


def ragged_pass_flops(nonzeros: int) -> int:
    """One value+gradient pass over ``nonzeros`` stored cells: a
    multiply-add a cell for the margin and one for the gradient."""
    return 4 * int(nonzeros)


def ragged_pass_bytes(nonzeros: int, rows: int, features: int) -> int:
    """The least one pass must move: every stored cell's index and value
    once (8 bytes), every row's label, offset and weight once (12), the
    coefficients read and the gradient written once (2 x 4 a column)."""
    return 8 * int(nonzeros) + 12 * int(rows) + 8 * int(features)


def ragged_work(nonzeros: int, rows: int, features: int,
                evaluations) -> dict:
    """FLOPs and bytes of solves that made ``evaluations`` passes (one
    count, or one a solve)."""
    n = _total(evaluations)
    return {"flops": n * ragged_pass_flops(nonzeros),
            "bytes": n * ragged_pass_bytes(nonzeros, rows, features)}
