"""The work a step of the two kinds of PR 29 needs, from shapes and from the
evaluations the solvers themselves count (``OptimizationResult.evaluations``
and ``.hvps``, PR 27), not from iterations + 1: every pass the line search
or the trust region makes is credited, so a share of a peak computed from
these says how near the chip's limit the passes themselves run.

As in ``work.py``, both count what the algorithm needs and never what an
implementation executed: the same numbers whether a gather and a
scatter-add, a sorted companion layout or a later kernel does the pass.
"""

from __future__ import annotations

from benchmark import work


def _total(counts) -> int:
    """One count, or one a solve."""
    try:
        return sum(int(c) for c in counts)
    except TypeError:
        return int(counts)


def sparse_pass_flops(rows: int, slots: int) -> int:
    """One value+gradient pass over ``rows`` rows of ``slots`` stored cells:
    a multiply-add a cell for the margin and one for the gradient."""
    return 4 * int(rows) * int(slots)


def sparse_pass_bytes(rows: int, slots: int, features: int) -> int:
    """The least one pass must move: every stored cell's index and value
    once (8 bytes), every row's label, offset and weight once (12), the
    coefficients read and the gradient written once (2 x 4 a column)."""
    return 8 * int(rows) * int(slots) + 12 * int(rows) + 8 * int(features)


def sparse_work(rows: int, slots: int, features: int, evaluations) -> dict:
    """FLOPs and bytes of solves that made ``evaluations`` passes (one
    count, or one a solve)."""
    n = _total(evaluations)
    return {"flops": n * sparse_pass_flops(rows, slots),
            "bytes": n * sparse_pass_bytes(rows, slots, features)}


def dense_work(rows: int, cols: int, itemsize: int, evaluations,
               hvps) -> dict:
    """FLOPs and bytes of solves over a dense block that made
    ``evaluations`` value+gradient passes and ``hvps`` Hessian-vector
    products (each one count, or one a solve). A product is credited as one
    dense pass, the least it can be: X v and X^T r with X read once."""
    n = _total(evaluations) + _total(hvps)
    return {"flops": n * work.pass_flops(rows, cols),
            "bytes": n * work.pass_bytes(rows, cols, itemsize)}
