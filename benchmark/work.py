"""The chip's peaks and the work a step needs, from shapes alone.

Both count what the algorithm needs (one pass over the rows for every value
and gradient the solver reports: its iterations, plus the starting point),
never what an implementation executed, so they read the same whether the
fused kernel, the two-pass XLA form or a later kernel does the work. Passes
the line search makes beyond one per iteration are not credited (the
solvers count no evaluations yet: PERF.md, Open questions), so a share of a
peak computed from these can only read low, never over 100%.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The row of ``peaks.json`` for this ``device_kind``; an unknown kind
    is an error, not a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(known: {sorted(table)})")
    return table[device_kind]


def passes(iterations: int) -> int:
    """Value-and-gradient evaluations a solve of ``iterations`` needs."""
    return int(iterations) + 1


def pass_flops(rows: int, cols: int) -> int:
    """One value+gradient pass over a dense [rows, cols] block: the margin
    product X w and the gradient product X^T r, 2 FLOPs a multiply-add."""
    return 4 * int(rows) * int(cols)


def pass_bytes(rows: int, cols: int, itemsize: int) -> int:
    """The least one pass must read: every element of X once."""
    return int(rows) * int(cols) * int(itemsize)


def block_work(rows: int, cols: int, itemsize: int, iterations) -> dict:
    """FLOPs and bytes of the solves over one block; ``iterations`` is one
    count or a sequence (one per solve, or per entity of a bucket)."""
    try:
        n = sum(passes(i) for i in iterations)
    except TypeError:
        n = passes(iterations)
    return {"flops": n * pass_flops(rows, cols),
            "bytes": n * pass_bytes(rows, cols, itemsize)}


def add_work(*parts: dict) -> dict:
    return {"flops": sum(p["flops"] for p in parts),
            "bytes": sum(p["bytes"] for p in parts)}
