"""Tiny copies of the benchmark's cells for the CPU rehearsals: the same
files, kinds and limits (but two), cut in rows only."""

import time

from benchmark import harness

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}

TINY = {
    "glm-dense-2048.lbfgs-logistic": {
        "rows": 8192, "features": 64, "rows_per_block": 1024},
    "glmix-ml10m.train": {
        "rows": 30000, "users": 400, "movies": 300, "rows_per_chunk": 4096,
        "reference_rows_per_block": 4096, "active_rows_cap": 32,
        "features_cap": 32, "max_rows_per_user": 250,
        "movie_popularity_shift": 6.0},
}


# six and eight iterations leave a 30,000-row fixed effect up to 2e-2 and its
# users 6e-3 from their minimisers (2e-5 and 1e-3 at the cell's own size): the
# limits a tiny copy cannot keep
TINY_LIMITS = {"glmix-ml10m.train": {"fixed_coef_gap": 0.08,
                                     "user_coef_gap": 0.05,
                                     "capped_coef_gap": 0.05}}


def spec(cell: str) -> harness.Spec:
    full = harness.load_spec(cell)
    limits = dict(full.workload["limits"], **TINY_LIMITS.get(cell, {}))
    return full._replace(config=dict(full.config, **TINY[cell]),
                         workload=dict(full.workload, limits=limits))


def run(cell: str, seed: int = 7, seconds: float = 0.3, trace: bool = False,
        trace_dir=None) -> dict:
    return harness.run_cell(spec(cell), seed, seconds, trace,
                            time.perf_counter(), DEVICE, trace_dir=trace_dir)
