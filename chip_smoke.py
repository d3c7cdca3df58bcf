"""Chip smoke: the main path, once, on the TPU, through the entry points users call.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the mesh-sharded GAME path on four chips, nothing else

With no arguments it runs, on ONE chip:

1. *GLM fit at full width* — the README quickstart (``dense_batch`` +
   ``train_glm_grid``, logistic, L-BFGS, a short lambda grid) at
   N=262144, D=2048, f32. The fused Pallas kernel must be in the compiled
   objective (Mosaic custom call in its text); its three sums and the
   final coefficients are compared with a plain ``jax.numpy`` float32
   two-pass reference at matmul precision "highest".
2. *GAME train -> score -> serve at GLMix width* — 6040 users, 3706 items,
   64 dense global features, per-user one-hot item features (caps
   128/128), fixed + per-user logistic, L-BFGS/L2, 2 sweeps: Avro part
   files through ``python -m photon_ml_tpu.cli.game_training_driver``
   (with ``--device-telemetry --trace-dir``), held-out rows through
   ``python -m photon_ml_tpu.cli.game_scoring_driver``, then
   ``python -m photon_ml_tpu.serve.service`` answering ``ServeClient.score``
   requests; the three must agree with each other and with a float64
   numpy reference computed from the saved model files.

One process per chip: this parent never initializes a JAX backend. Every
phase that needs the chip is a child, started strictly after the previous
one exited; the device line is a child's own report. All children share
one compile cache — ``JAX_COMPILATION_CACHE_DIR`` where set, else the
program's fixed in-checkout path (photon_ml_tpu/utils/compile_cache.py).
The native readers are built from ``native/*.cpp`` into a fresh directory
of this run, never trusted from a ``native/build`` that came with the tree.

Without a TPU the script says which platform it found and exits non-zero;
any failed phase makes it exit non-zero; it never prints ``"ok": true``
unless every check passed. Everything it writes goes under
``<checkout>/.chip_smoke`` (git-ignored, wiped at start).

``--chips 4`` runs, in ONE process that holds all four chips, the GAME
training driver at the same width on a (data=2 x entity=2) mesh
(``--re-entity-shards 2``) against the same configuration on one device
of the same host, prints the mesh, per-device peak memory and the
objective/coefficient deviation with its bound, and nothing else.

The last stdout line is the contract's:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import logging
import math
import os
import shutil
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".chip_smoke")
if REPO not in sys.path:  # imported (tests) rather than run as a script
    sys.path.insert(0, REPO)

SEED = 22

# Phase 1: the width the kernel's own comment and the benchmark's dense
# configuration (benchmark/configs/glm-dense-2048.json) name.
GLM_ROWS, GLM_DIM = 262144, 2048
GLM_LAMBDAS = (10.0, 1.0, 0.1)

# Phase 2: MovieLens-1M's shape. Widths are never
# cut; ``rows`` is the only size a run may reduce, and never below
# GLMIX_MIN_ROWS (a real size for the fixed effect, also per data shard of
# the four-chip mesh; at its 65 columns every pass is the two-pass XLA
# form: under ops/pallas_kernels.MIN_PALLAS_DIM).
GLMIX_FULL_ROWS = 1_000_209
GLMIX_MIN_ROWS = 131072
GLMIX = dict(users=6040, items=3706, d_global=64, active_cap=128,
             feature_ratio=1.0, buckets=4, sweeps=2,
             fixed_opt="40,1e-7,10,1,LBFGS,L2",
             random_opt="20,1e-7,1,1,LBFGS,L2")
# The pure-Python Avro writer does ~5k of these records a second per core;
# rows are cut only when the host has too few cores to write them all in
# about this long.
FIXTURE_WRITE_BUDGET_SECS = 90.0
WRITER_RECORDS_PER_SEC_PER_CORE = 4500.0
HELDOUT_ROWS = 8192
SERVE_REQUESTS, SERVE_ROWS_PER_REQUEST = 4, 64
# The four-chip run costs four times as much per second and runs the
# training twice: it keeps the widths and takes the floor on rows.
MESH_ROWS = 262144

# Bounds, fixed before the first chip run (PERF.md, PR 22 prediction).
# f32 sums over N rows carry ~sqrt(N)*eps relative rounding (3e-5 at
# N=262144); 1e-4 leaves room for a different summation order only.
SUMS_REL_BOUND = 1e-4
# Two L-BFGS runs that both stop at a relative tolerance of 1e-6 on
# slightly different arithmetic agree to ~1e-3 of the coefficient scale on
# this well-conditioned problem; the objective they reach agrees far closer.
COEF_REL_BOUND = 1e-2
OBJECTIVE_REL_BOUND = 1e-5
# Scores are <= 66-term sums of f32 coefficients times f64 features; the
# scoring driver, the service and the numpy reference may round to f32 at
# different points (2^-24 relative per term).
SCORE_ABS_BOUND = 1e-5
# Mesh vs one device: the same solves with a psum-reassociated gradient
# over per-shard partial sums — the noise-floor parity documented in
# parallel/distributed.py, at GLMix scale.
MESH_OBJECTIVE_REL_BOUND = 1e-4
MESH_COEF_ABS_BOUND = 5e-2

SECTION_MAP = "global:globalFeatures|per_user:userFeatures"

GAME_SCHEMA = {
    "name": "GameRecord", "type": "record", "namespace": "smoke",
    "fields": [
        {"name": "uid", "type": ["null", "string"], "default": None},
        {"name": "response", "type": "double"},
        {"name": "metadataMap",
         "type": ["null", {"type": "map", "values": "string"}],
         "default": None},
        {"name": "globalFeatures", "type": {"type": "array", "items": {
            "name": "FeatureAvro", "type": "record",
            "fields": [{"name": "name", "type": "string"},
                       {"name": "term", "type": "string"},
                       {"name": "value", "type": "double"}]}}},
        {"name": "userFeatures",
         "type": {"type": "array", "items": "FeatureAvro"}},
    ],
}

_T0 = time.perf_counter()


def say(msg: str) -> None:
    print(f"[smoke +{time.perf_counter() - _T0:6.1f}s] {msg}", flush=True)


class SmokeFailure(Exception):
    """A phase's check did not hold."""


# ---------------------------------------------------------------------------
# Data: generated from the seed, in bulk
# ---------------------------------------------------------------------------


def glmix_data(rows: int, seed: int, users: int, items: int,
               d_global: int) -> dict:
    """MovieLens-shaped GLMix rows: power-law users,
    uniform items, dense global features, a one-hot item feature per row."""
    rng = np.random.default_rng(seed)
    user = (rng.zipf(1.3, size=rows) % users).astype(np.int64)
    item = rng.integers(0, items, rows)
    Xg = (rng.normal(size=(rows, d_global)) / np.sqrt(d_global)).astype(
        np.float32)
    wg = rng.normal(size=d_global).astype(np.float32)
    logits = Xg @ wg + 0.5 * rng.normal(size=users)[user].astype(np.float32)
    y = (rng.uniform(size=rows) < 1.0 / (1.0 + np.exp(-logits))).astype(
        np.float64)
    return {"user": user, "item": item, "Xg": Xg, "y": y}


def game_records(data: dict, lo: int, hi: int, uid_prefix: str) -> list:
    names = [f"g{j}" for j in range(data["Xg"].shape[1])]
    out = []
    for i in range(lo, hi):
        row = data["Xg"][i].tolist()
        out.append({
            "uid": f"{uid_prefix}{i}",
            "response": float(data["y"][i]),
            "metadataMap": {"userId": f"u{int(data['user'][i])}"},
            "globalFeatures": [{"name": n, "term": "", "value": v}
                               for n, v in zip(names, row)],
            "userFeatures": [{"name": f"m{int(data['item'][i])}",
                              "term": "", "value": 1.0}],
        })
    return out


def _write_part(job) -> int:
    """Pool worker (spawned: imports nothing of JAX)."""
    path, data, uid_prefix = job
    from photon_ml_tpu.io.avro import write_container

    n = len(data["y"])
    write_container(path, GAME_SCHEMA, game_records(data, 0, n, uid_prefix))
    return n


def write_parts(data: dict, out_dir: str, rows_per_part: int,
                uid_prefix: str, workers: int) -> None:
    """``data`` as Avro part files through the repo's pure-Python writer,
    ``workers`` processes at a time (0 = in this process)."""
    os.makedirs(out_dir, exist_ok=True)
    n = len(data["y"])
    jobs = []
    for k, lo in enumerate(range(0, n, rows_per_part)):
        hi = min(n, lo + rows_per_part)
        part = {key: val[lo:hi] for key, val in data.items()}
        jobs.append((os.path.join(out_dir, f"part-{k:05d}.avro"), part,
                     f"{uid_prefix}{k}_"))
    if workers <= 0:
        done = sum(_write_part(j) for j in jobs)
    else:
        import multiprocessing as mp

        with mp.get_context("spawn").Pool(workers) as pool:
            done = sum(pool.imap_unordered(_write_part, jobs))
    if done != n:
        raise SmokeFailure(f"wrote {done} of {n} fixture rows")


def write_feature_sets(fs_dir: str, d_global: int, items: int) -> None:
    os.makedirs(fs_dir, exist_ok=True)
    with open(os.path.join(fs_dir, "globalFeatures"), "w") as fh:
        fh.writelines(f"g{j}\t\n" for j in range(d_global))
    with open(os.path.join(fs_dir, "userFeatures"), "w") as fh:
        fh.writelines(f"m{j}\t\n" for j in range(items))


def affordable_rows(want: int, workers: int) -> int:
    """Rows the fixture writer gets through in its budget on this host."""
    can = int(FIXTURE_WRITE_BUDGET_SECS * WRITER_RECORDS_PER_SEC_PER_CORE
              * max(workers, 1))
    return max(GLMIX_MIN_ROWS, min(want, can))


def build_fixture(work: str, rows: int, heldout_rows: int, seed: int,
                  workers: int, users: int = GLMIX["users"],
                  items: int = GLMIX["items"],
                  d_global: int = GLMIX["d_global"]) -> dict:
    """Train + held-out Avro part dirs and the feature name-term sets.
    Returns the paths and the held-out rows (for the reference)."""
    data = glmix_data(rows + heldout_rows, seed, users, items, d_global)
    train = {k: v[:rows] for k, v in data.items()}
    held = {k: v[rows:] for k, v in data.items()}
    parts = max(1, workers) * 2
    t0 = time.perf_counter()
    write_parts(train, os.path.join(work, "train"),
                -(-rows // parts), "t", workers)
    if heldout_rows:
        write_parts(held, os.path.join(work, "heldout"), heldout_rows, "h", 0)
    write_feature_sets(os.path.join(work, "feature_sets"), d_global, items)
    say(f"fixture: {rows} train rows in {parts} Avro parts + "
        f"{heldout_rows} held-out rows, {len(np.unique(train['user']))} "
        f"users, {items} items, {d_global} global features, written in "
        f"{time.perf_counter() - t0:.1f}s by {max(workers, 1)} process(es)")
    return {"train_dir": os.path.join(work, "train"),
            "heldout_dir": os.path.join(work, "heldout"),
            "fs_dir": os.path.join(work, "feature_sets"),
            "train": train, "heldout": held}


# ---------------------------------------------------------------------------
# Native readers: built from source, for this run
# ---------------------------------------------------------------------------


def build_native(work: str) -> str:
    """``make -C native`` into a fresh directory; returns the library path.
    ``native/build`` is git-ignored but travels with a copied tree, and the
    loader would trust it by mtime (built with -march=native elsewhere)."""
    import ctypes

    build_dir = os.path.join(work, "native_build")
    t0 = time.perf_counter()
    proc = subprocess.run(
        ["make", "-C", os.path.join(REPO, "native"), f"BUILD={build_dir}"],
        capture_output=True, text=True)
    lib = os.path.join(build_dir, "libphoton_native.so")
    if proc.returncode != 0 or not os.path.exists(lib):
        raise SmokeFailure(
            f"native build failed rc={proc.returncode}:\n{proc.stderr[-2000:]}")
    ctypes.CDLL(lib)  # loads here or raises
    say(f"native readers: built from native/*.cpp in "
        f"{time.perf_counter() - t0:.1f}s and loaded ({lib})")
    return lib


# ---------------------------------------------------------------------------
# Children and their evidence
# ---------------------------------------------------------------------------


def cache_dir() -> str:
    """Where every child of this run keeps its compile cache."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    from photon_ml_tpu.utils.compile_cache import DEFAULT_CACHE_DIR

    return DEFAULT_CACHE_DIR


def cache_entries(path: str) -> int:
    try:
        return sum(1 for f in os.listdir(path) if not f.endswith("-atime"))
    except OSError:
        return 0


def parent_off_chip() -> None:
    """One process per chip: the parent must not have a backend when a
    child that needs the chip starts."""
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is not None and xb.backends_are_initialized():
        raise SmokeFailure("the smoke's parent initialized a JAX backend")


def run_child(name: str, argv: list, env: dict, log_path: str,
              timeout: float = 900.0) -> float:
    """Run one child to its end; returns its wall seconds."""
    parent_off_chip()
    t0 = time.perf_counter()
    with open(log_path, "w") as log:
        proc = subprocess.run(argv, env=env, cwd=REPO, stdout=log,
                              stderr=subprocess.STDOUT, timeout=timeout)
    secs = time.perf_counter() - t0
    if proc.returncode != 0:
        with open(log_path) as fh:
            tail = fh.read()[-3000:]
        raise SmokeFailure(f"{name} exited {proc.returncode}:\n{tail}")
    return secs


def read_jsonl(path: str) -> list:
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def trace_evidence(trace_dir: str, want_platform, want_count: int) -> dict:
    """What a child's ``--trace-dir`` says it ran on and compiled."""
    with open(os.path.join(trace_dir, "run_manifest.json")) as fh:
        manifest = json.load(fh)
    if want_platform is not None and (
            manifest["backend"] != want_platform
            or manifest["device_count"] != want_count):
        raise SmokeFailure(
            f"{trace_dir}: ran on backend={manifest['backend']} "
            f"device_count={manifest['device_count']}, not "
            f"{want_count} x {want_platform}")
    end = [r for r in read_jsonl(os.path.join(trace_dir, "metrics.jsonl"))
           if r.get("kind") == "run_end"]
    if not end or end[-1].get("status") != "ok":
        raise SmokeFailure(f"{trace_dir}: no run_end with status ok")
    totals = end[-1].get("metric_totals", {})
    return {"backend": manifest["backend"],
            "device_count": manifest["device_count"],
            "compile_secs": float(totals.get("compile_secs", 0.0)),
            "compiles": int(totals.get("compiles", 0)),
            "spans": read_jsonl(os.path.join(trace_dir, "spans.jsonl"))}


def training_argv(fx: dict, out_dir: str, trace_dir: str,
                  extra: tuple = ()) -> list:
    g = GLMIX
    return [
        "--train-input-dirs", fx["train_dir"],
        "--output-dir", out_dir,
        "--task-type", "LOGISTIC_REGRESSION",
        "--feature-name-and-term-set-path", fx["fs_dir"],
        "--feature-shard-id-to-feature-section-keys-map", SECTION_MAP,
        "--updating-sequence", "fixed,perUser",
        "--fixed-effect-data-configurations", "fixed:global,1",
        "--random-effect-data-configurations",
        f"perUser:userId,per_user,1,{g['active_cap']},-,{g['feature_ratio']}",
        "--fixed-effect-optimization-configurations",
        f"fixed:{g['fixed_opt']}",
        "--random-effect-optimization-configurations",
        f"perUser:{g['random_opt']}",
        "--random-effect-block-buckets", str(g["buckets"]),
        "--num-iterations", str(g["sweeps"]),
        "--model-output-mode", "BEST",
        "--trace-dir", trace_dir, "--device-telemetry",
        "--trace-heartbeat-seconds", "5",
        *extra,
    ]


def check_training(out_dir: str, spans: list, sweeps: int,
                   expect_donation: bool, logs: tuple) -> dict:
    """The training run's own record: objective finite and lower after the
    last sweep than after the first, nothing compiled or retraced in a warm
    sweep, the donating random-effect fit taken, the native reader used."""
    with open(os.path.join(out_dir, "metrics.json")) as fh:
        states = json.load(fh)["grid"][0]["states"]
    objective = {}
    for s in states:
        if s["objective"] is None or not math.isfinite(s["objective"]):
            raise SmokeFailure(f"non-finite objective in {s}")
        objective[s["iteration"]] = s["objective"]  # last update of a sweep
    if sorted(objective) != list(range(sweeps)):
        raise SmokeFailure(f"expected {sweeps} sweeps, got {sorted(objective)}")
    if not objective[sweeps - 1] < objective[0]:
        raise SmokeFailure(
            f"objective did not fall: sweep 1 {objective[0]!r} -> sweep "
            f"{sweeps} {objective[sweeps - 1]!r}")
    warm = [(s["ts_us"], s["ts_us"] + s["dur_us"]) for s in spans
            if s["name"] == "cd.sweep" and s["labels"]["sweep"] >= 1]
    if len(warm) != sweeps - 1:
        raise SmokeFailure(f"expected {sweeps - 1} warm cd.sweep span(s)")
    in_warm = [s for s in spans if s["name"] in ("xla.compile", "xla.retrace")
               and any(lo <= s["ts_us"] <= hi for lo, hi in warm)]
    if in_warm:
        raise SmokeFailure(
            "the warm sweep compiled: "
            + "; ".join(f"{s['name']} {s['labels']}" for s in in_warm[:4]))
    fits = [s["labels"] for s in spans if s["name"] == "xla.compile"
            and s["labels"]["site"] == "re.fit_blocks"]
    donated = [f.get("alias_bytes") for f in fits]
    if expect_donation and not (fits and all(b and b > 0 for b in donated)):
        raise SmokeFailure(
            "the donating random-effect fit was not taken: alias_bytes of "
            f"the re.fit_blocks executables = {donated}")
    for path in logs:
        with open(path) as fh:
            if "interpreted Avro reader" in fh.read():
                raise SmokeFailure(
                    f"training fell back to the interpreted Avro reader "
                    f"({path})")
    return {"objective_by_sweep": [objective[i] for i in range(sweeps)],
            "re_fit_executables": len(fits), "re_fit_alias_bytes": donated}


# -- model files and scores, read without JAX -------------------------------


def read_model(model_dir: str) -> dict:
    """``{"fixed": {name: value}, "random": {entity: {name: value}}}`` of the
    one fixed and one per-user coordinate, from the Avro model files."""
    from photon_ml_tpu.io.avro import read_directory

    def means(rec):
        return {f["name"]: float(f["value"]) for f in rec["means"]}

    _, fixed = read_directory(os.path.join(
        model_dir, "fixed-effect", "fixed", "coefficients"))
    _, random = read_directory(os.path.join(
        model_dir, "random-effect", "perUser", "coefficients"))
    return {"fixed": means(fixed[0]),
            "random": {r["modelId"]: means(r) for r in random}}


def read_scores(score_dir: str) -> dict:
    from photon_ml_tpu.io.avro import read_directory

    _, recs = read_directory(os.path.join(score_dir, "scores"))
    return {r["uid"]: float(r["predictionScore"]) for r in recs}


def reference_scores(model: dict, held: dict) -> np.ndarray:
    """Plain float64: x_global . w_fixed + w_user[item] + intercepts; a
    user or item the model never saw contributes nothing."""
    icpt = "(INTERCEPT)"
    d = held["Xg"].shape[1]
    w = np.array([model["fixed"].get(f"g{j}", 0.0) for j in range(d)])
    out = held["Xg"].astype(np.float64) @ w + model["fixed"].get(icpt, 0.0)
    for i in range(len(out)):
        mine = model["random"].get(f"u{int(held['user'][i])}")
        if mine is not None:
            out[i] += (mine.get(f"m{int(held['item'][i])}", 0.0)
                       + mine.get(icpt, 0.0))
    return out


def max_abs_dev(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def model_deviation(a: dict, b: dict) -> dict:
    """Largest coefficient difference between two read_model() results."""
    keys = set(a["fixed"]) | set(b["fixed"])
    fixed = max(abs(a["fixed"].get(k, 0.0) - b["fixed"].get(k, 0.0))
                for k in keys)
    random = 0.0
    for ent in set(a["random"]) | set(b["random"]):
        ea, eb = a["random"].get(ent, {}), b["random"].get(ent, {})
        for k in set(ea) | set(eb):
            random = max(random, abs(ea.get(k, 0.0) - eb.get(k, 0.0)))
    return {"fixed": fixed, "random": random}


# ---------------------------------------------------------------------------
# Phase 1 (runs in a child that holds the chip): GLM fit at full width
# ---------------------------------------------------------------------------


def device_report() -> dict:
    import jax

    dev = jax.devices()
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}


def _reference_sums(X, y, w):
    """Plain jax.numpy float32 two-pass logistic sums, matmuls at
    "highest": (sum loss, X^T r, sum r) with r = sigmoid(z) - y."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    z = jnp.matmul(X, w, precision=hi)
    # the textbook stable sigmoid, written out. On the v5e the shorter forms
    # are not accurate enough to referee sum r over 262144 rows, which
    # nearly cancels: jax.nn.sigmoid and 1/(1+exp(-z)) both compile to the
    # logistic op, off by a one-sided ulp (3e-4 of the sum), and
    # exp(-softplus(-z)) is off 4e-3; this form is within 5e-7 (PR 22)
    e = jnp.exp(-jnp.abs(z))
    r = jnp.where(z >= 0, 1.0 / (1.0 + e), e / (1.0 + e)) - y
    return (jnp.sum(jnp.logaddexp(0.0, z) - y * z),
            jnp.matmul(r, X, precision=hi), jnp.sum(r))


def _reference_vg(w, data):
    X, y, lam = data
    value, vec, _ = _reference_sums(X, y, w)
    import jax.numpy as jnp

    return value + 0.5 * lam * jnp.sum(w * w), vec + lam * w


def phase_glm(rows: int, dim: int, lambdas, seed: int) -> dict:
    """README quickstart at (rows, dim) f32 against the reference. Returns
    the measurements and ``failures`` (empty = the phase passed)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import dense_batch
    from photon_ml_tpu.obs import compile as obs_compile
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.ops import pallas_kernels
    from photon_ml_tpu.ops.aggregators import GLMObjective
    from photon_ml_tpu.ops.losses import get_loss
    from photon_ml_tpu.optimize.config import TaskType
    from photon_ml_tpu.optimize.lbfgs import minimize_lbfgs
    from photon_ml_tpu.training import train_glm_grid

    failures = []
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, dim)).astype(np.float32)
    w_true = (rng.normal(size=dim) / np.sqrt(dim)).astype(np.float32)
    y = (rng.uniform(size=rows) < 1.0 / (1.0 + np.exp(-(X @ w_true)))
         ).astype(np.float32)
    w_probe = jnp.asarray(0.01 * rng.normal(size=dim).astype(np.float32))
    batch = dense_batch(X, y)
    jax.block_until_ready(batch.X)
    say(f"glm: batch {rows}x{dim} f32 on {jax.devices()[0].device_kind}")

    # 1. the kernel is in the compiled objective
    loss = get_loss("logistic")
    objective = GLMObjective(loss=loss, l2_lambda=0.0)
    compiled = jax.jit(objective.calculate).lower(w_probe, batch).compile()
    mosaic = "tpu_custom_call" in compiled.as_text()
    supported = pallas_kernels.pallas_supported("value_and_grad", rows, dim,
                                                batch.X.dtype)
    say(f"glm: pallas_supported={supported}, Mosaic custom call in the "
        f"compiled objective: {mosaic}")
    if not (supported and mosaic):
        failures.append("the fused kernel is not in the compiled objective")

    # 2. the fused sums against the reference (l2=0, no normalization: the
    # objective's value and gradient ARE the value and vector sums)
    # (the batch is an ARGUMENT: closed over, X would be baked into the
    # executable as a 2 GB constant)
    fused = jax.jit(lambda b, w: pallas_kernels.fused_value_gradient_sums(
        loss, False, b.X, b.labels, b.offsets, b.weights, w,
        jnp.float32(0.0)))(batch, w_probe)
    through_objective = compiled(w_probe, batch)
    ref = jax.jit(_reference_sums)(batch.X, batch.labels, w_probe)
    sums_dev = {}
    for name, got, want in zip(("value", "vector_sum", "prefactor_sum"),
                               fused, ref):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        sums_dev[name] = float(np.max(np.abs(got - want))
                               / max(1.0, np.max(np.abs(want))))
    sums_dev["objective_value"] = abs(
        float(through_objective[0]) - float(ref[0])) / abs(float(ref[0]))
    sums_dev["objective_gradient"] = max_abs_dev(
        through_objective[1], ref[1]) / float(np.max(np.abs(ref[1])))
    say(f"glm: fused sums vs f32 'highest' two-pass reference, relative "
        f"deviation {sums_dev} (bound {SUMS_REL_BOUND})")
    if not all(np.isfinite(v) and v <= SUMS_REL_BOUND
               for v in sums_dev.values()):
        failures.append(f"fused sums outside {SUMS_REL_BOUND}: {sums_dev}")

    # 3. the fit, compile seconds attributed by the repo's own layer
    obs_compile.arm()
    t0 = time.perf_counter()
    models = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION,
                            regularization_weights=list(lambdas))
    jax.block_until_ready(models[-1].model.coefficients.means)
    fit_secs = time.perf_counter() - t0
    compile_secs = float(REGISTRY.counter("compile_secs").total())
    obs_compile.disarm()
    iters = [int(m.result.iterations) for m in models]
    say(f"glm: train_glm_grid over lambda={[m.regularization_weight for m in models]}"
        f" took {fit_secs:.2f}s, of which compile {compile_secs:.2f}s; "
        f"iterations {iters}, reasons "
        f"{[str(m.result.convergence_reason) for m in models]}")

    # 4. the coefficients against a fit of the reference objective (outside
    # any timed region; same solver driver, independent arithmetic)
    coef_dev, obj_dev, grad_ratio = [], [], []
    x0 = jnp.zeros(dim, jnp.float32)
    g0 = float(jnp.linalg.norm(_reference_vg(x0, (batch.X, batch.labels,
                                                   0.0))[1]))
    for m in models:
        lam = jnp.float32(m.regularization_weight)
        data = (batch.X, batch.labels, lam)
        x_ref, _, _ = minimize_lbfgs(_reference_vg, x0, data, max_iter=80,
                                     tolerance=1e-6)
        x0 = x_ref  # warm start, as the grid does
        w_sys = m.result.coefficients
        f_sys, g_sys = _reference_vg(w_sys, data)
        f_ref, _ = _reference_vg(x_ref, data)
        coef_dev.append(max_abs_dev(w_sys, x_ref)
                        / float(jnp.max(jnp.abs(x_ref))))
        obj_dev.append(abs(float(f_sys) - float(f_ref)) / abs(float(f_ref)))
        grad_ratio.append(float(jnp.linalg.norm(g_sys)) / g0)
        if not np.all(np.isfinite(np.asarray(w_sys))):
            failures.append(f"non-finite coefficients at lambda {lam}")
    say(f"glm: final coefficients vs reference fit, max |dw|/max|w| "
        f"{coef_dev} (bound {COEF_REL_BOUND}); reference objective at them "
        f"off by {obj_dev} (bound {OBJECTIVE_REL_BOUND}); reference "
        f"|grad|/|grad(0)| at them {grad_ratio}")
    if not (max(coef_dev) <= COEF_REL_BOUND
            and max(obj_dev) <= OBJECTIVE_REL_BOUND):
        failures.append(f"coefficients off: {coef_dev}, objective {obj_dev}")

    return {"rows": rows, "dim": dim, "mosaic_call": mosaic,
            "sums_rel_dev": sums_dev, "fit_secs": fit_secs,
            "compile_secs": compile_secs, "iterations": iters,
            "coef_rel_dev": coef_dev, "objective_rel_dev": obj_dev,
            "failures": failures}


def child_glm(ns) -> int:
    """``--phase glm``: report the device first (the parent reads that line
    and stops the run if it is no TPU), then run phase 1."""
    from photon_ml_tpu.utils.compile_cache import (
        enable_persistent_compile_cache,
    )

    enable_persistent_compile_cache()
    device = device_report()
    print(json.dumps({"phase": "device", "device": device}), flush=True)
    if device["platform"] != "tpu":
        return 2
    report = phase_glm(GLM_ROWS, GLM_DIM, GLM_LAMBDAS, ns.seed)
    print(json.dumps({"phase": "glm", **report}), flush=True)
    return 1 if report["failures"] else 0


# ---------------------------------------------------------------------------
# One chip: the parent that never touches the chip
# ---------------------------------------------------------------------------


def start_glm_child(env: dict, seed: int):
    """Start phase 1 and read its device line. Returns (proc, device)."""
    parent_off_chip()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", "glm",
         "--seed", str(seed)],
        env=env, cwd=REPO, stdout=subprocess.PIPE, text=True)
    device = None
    for line in proc.stdout:
        if line.startswith("{"):
            device = json.loads(line).get("device")
            break
        print(line, end="", flush=True)
    if device is None or device.get("platform") != "tpu":
        proc.wait()
        found = device["platform"] if device else "no JAX backend at all"
        raise SmokeFailure(
            f"no TPU: JAX found {found} (child exited {proc.returncode}); "
            "this smoke runs on the chip or not at all")
    return proc, device


def finish_glm_child(proc) -> dict:
    """Relay phase 1's lines; returns its report with ``failures`` filled
    in also when the child died without one."""
    report = None
    for line in proc.stdout:
        if line.startswith("{"):
            report = json.loads(line)
        else:
            print(line, end="", flush=True)
    proc.wait()
    if report is None:
        report = {"failures": ["phase 1 gave no report"]}
    if proc.returncode != 0 and not report["failures"]:
        report["failures"].append(f"phase 1 exited {proc.returncode}")
    return report


def serve_and_score(fx: dict, model_dir: str, env: dict, work: str) -> dict:
    """Start the service, score the first held-out rows in a few requests,
    stop it with its stop file (rc 0). Returns {uid: score}."""
    from photon_ml_tpu.serve.protocol import ServeClient

    parent_off_chip()
    trace_dir = os.path.join(work, "serve_trace")
    stop_file = os.path.join(work, "serve.stop")
    sock = os.path.join(work, "serve.sock")
    log_path = os.path.join(work, "serve.log")
    need = SERVE_REQUESTS * SERVE_ROWS_PER_REQUEST
    records = game_records(fx["heldout"], 0, need, "h0_")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "photon_ml_tpu.serve.service",
             "--game-model-input-dir", model_dir,
             "--listen", f"unix:{sock}",
             "--feature-shard-id-to-feature-section-keys-map", SECTION_MAP,
             "--feature-name-and-term-set-path", fx["fs_dir"],
             "--random-effect-id-set", "userId",
             "--trace-dir", trace_dir, "--device-telemetry",
             "--trace-heartbeat-seconds", "5",
             "--stop-file", stop_file, "--max-serve-seconds", "600"],
            env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=log, text=True)
    scores = {}
    try:
        ready = proc.stdout.readline().strip()
        if "ready endpoint=" not in ready:
            raise SmokeFailure(f"service gave no ready line: {ready!r}")
        with ServeClient(ready.split("endpoint=", 1)[1]) as client:
            for k in range(SERVE_REQUESTS):
                rows = records[k * SERVE_ROWS_PER_REQUEST:
                               (k + 1) * SERVE_ROWS_PER_REQUEST]
                reply = client.score(rows)
                if reply.get("kind") != "scores":
                    raise SmokeFailure(f"service replied {reply}")
                scores.update(zip(reply["uids"], reply["scores"]))
            stats = client.stats()
        open(stop_file, "w").close()
        rc = proc.wait(timeout=120)
        if rc != 0:
            raise SmokeFailure(f"service exited {rc} after its stop file")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    say(f"serve: {len(scores)} rows in {SERVE_REQUESTS} requests, "
        f"p50 {stats.get('p50_ms')} ms, tiers {stats.get('tier_hits')}; "
        f"stopped by stop file, rc 0")
    return scores


def phase_game(work: str, fx: dict, env: dict, platform: str) -> dict:
    """Train -> score -> serve as three children, one after another, each
    checked against its own record; every child must report one
    ``platform`` device. Returns the compile seconds of each."""
    out_dir = os.path.join(work, "train_out")
    train_trace = os.path.join(work, "train_trace")
    train_log = os.path.join(work, "train.log")
    secs = run_child(
        "training driver",
        [sys.executable, "-m", "photon_ml_tpu.cli.game_training_driver",
         *training_argv(fx, out_dir, train_trace)], env, train_log)
    ev = trace_evidence(train_trace, platform, 1)
    train = check_training(out_dir, ev["spans"], GLMIX["sweeps"],
                           platform != "cpu",
                           (train_log,))
    say(f"train ok in {secs:.1f}s on {ev['device_count']} x {ev['backend']}: "
        f"objective by sweep {train['objective_by_sweep']}, compile "
        f"{ev['compile_secs']:.2f}s over {ev['compiles']} executables, "
        f"nothing compiled in the warm sweep, donated bytes per "
        f"re.fit_blocks executable {train['re_fit_alias_bytes']}, native "
        f"Avro reader used")

    model_dir = os.path.join(out_dir, "best")
    score_dir = os.path.join(work, "score_out")
    score_trace = os.path.join(work, "score_trace")
    secs = run_child(
        "scoring driver",
        [sys.executable, "-m", "photon_ml_tpu.cli.game_scoring_driver",
         "--input-data-dirs", fx["heldout_dir"],
         "--game-model-input-dir", model_dir,
         "--output-dir", score_dir,
         "--feature-name-and-term-set-path", fx["fs_dir"],
         "--feature-shard-id-to-feature-section-keys-map", SECTION_MAP,
         "--random-effect-id-set", "userId",
         "--trace-dir", score_trace, "--device-telemetry"],
        env, os.path.join(work, "score.log"))
    score_ev = trace_evidence(score_trace, platform, 1)
    driver_scores = read_scores(score_dir)
    model = read_model(model_dir)
    ref = reference_scores(model, fx["heldout"])
    uids = [f"h0_{i}" for i in range(len(fx["heldout"]["y"]))]
    if sorted(driver_scores) != sorted(uids):
        raise SmokeFailure(
            f"scoring driver wrote {len(driver_scores)} scores for "
            f"{len(uids)} held-out rows")
    got = np.array([driver_scores[u] for u in uids])
    dev_ref = max_abs_dev(got, ref)
    say(f"score ok in {secs:.1f}s on {score_ev['backend']}: {len(got)} "
        f"held-out rows, max |driver - float64 numpy reference| "
        f"{dev_ref:.3e} (bound {SCORE_ABS_BOUND}), compile "
        f"{score_ev['compile_secs']:.2f}s")
    if not (np.all(np.isfinite(got)) and dev_ref <= SCORE_ABS_BOUND):
        raise SmokeFailure(f"scores off the reference by {dev_ref}")

    served = serve_and_score(fx, model_dir, env, work)
    serve_ev = trace_evidence(os.path.join(work, "serve_trace"), platform, 1)
    dev_serve = max(abs(served[u] - driver_scores[u]) for u in served)
    say(f"serve ok on {serve_ev['backend']}: max |served - scoring driver| "
        f"{dev_serve:.3e} over {len(served)} rows (bound {SCORE_ABS_BOUND}),"
        f" compile {serve_ev['compile_secs']:.2f}s")
    if not dev_serve <= SCORE_ABS_BOUND:
        raise SmokeFailure(f"served scores off the driver's by {dev_serve}")

    return {"train": ev["compile_secs"], "score": score_ev["compile_secs"],
            "serve": serve_ev["compile_secs"]}


def run_one_chip(ns) -> dict:
    """Phases 1 and 2 on one chip; returns the device a child reported."""
    env = dict(os.environ)
    cache = cache_dir()
    before = cache_entries(cache)
    home_cache = os.path.join(os.path.expanduser("~"), ".cache",
                              "photon_ml_tpu")
    home_cache_was_there = os.path.exists(home_cache)
    say(f"compile cache: {cache} ({before} entries before this run; "
        f"JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'not set'})")

    glm_proc, device = start_glm_child(env, ns.seed)
    say(f"device (phase 1's own report): {device}")
    try:
        # host-only work while phase 1 has the chip
        env["PHOTON_NATIVE_LIB"] = build_native(WORK)
        workers = max(1, (os.cpu_count() or 2) - 2)
        rows = affordable_rows(GLMIX_FULL_ROWS, workers)
        reduced = []
        if rows < GLMIX_FULL_ROWS:
            reduced.append(
                f"GLMix rows {GLMIX_FULL_ROWS} -> {rows}: the pure-Python "
                f"Avro writer on {workers} core(s) in "
                f"{FIXTURE_WRITE_BUDGET_SECS:.0f}s")
        fx = build_fixture(WORK, rows, HELDOUT_ROWS, ns.seed, workers)
    except BaseException:
        glm_proc.kill()
        glm_proc.wait()
        raise
    glm = finish_glm_child(glm_proc)
    if glm["failures"]:
        # go on: what phase 2 says is worth having either way
        say(f"phase glm FAILED: {glm['failures']}")
    else:
        say(f"phase glm ok: {GLM_ROWS}x{GLM_DIM}, Mosaic call present, "
            f"compile {glm['compile_secs']:.2f}s")

    compiles = phase_game(WORK, fx, env, "tpu")
    if glm["failures"]:
        raise SmokeFailure(f"phase glm: {glm['failures']}")

    parent_off_chip()
    if os.path.exists(home_cache) and not home_cache_was_there:
        raise SmokeFailure(f"something cached under {home_cache}")
    compile_total = glm["compile_secs"] + sum(compiles.values())
    say(f"compile seconds this run (glm + train + score + serve): "
        f"{compile_total:.2f}; cache {cache}: {before} -> "
        f"{cache_entries(cache)} entries")
    say(f"reduced: {reduced if reduced else 'nothing'}")
    return device


# ---------------------------------------------------------------------------
# Four chips: one process, the mesh-sharded GAME path and its comparison
# ---------------------------------------------------------------------------


class _WarningCollector(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


def phase_mesh(work: str, fx: dict, n_devices: int) -> dict:
    """The GAME training driver on a (data x entity=2) mesh over
    ``n_devices`` devices, against the same configuration on one device, in
    this process. Returns the measurements and ``failures``."""
    import jax

    from photon_ml_tpu.cli import game_training_driver as gtd
    from photon_ml_tpu.io.model_io import save_game_model
    from photon_ml_tpu.parallel.mesh import get_default_mesh, set_default_mesh

    failures = []
    if len(jax.devices()) != n_devices:
        raise SmokeFailure(
            f"--chips {n_devices}: this process has {len(jax.devices())} "
            f"{jax.default_backend()} device(s)")
    warnings = _WarningCollector()
    logging.getLogger("photon_ml_tpu").addHandler(warnings)

    # the mesh run, through the driver's main()
    mesh_out = os.path.join(work, "mesh_out")
    mesh_trace = os.path.join(work, "mesh_trace")
    t0 = time.perf_counter()
    gtd.main(training_argv(fx, mesh_out, mesh_trace,
                           extra=("--re-entity-shards", "2")))
    mesh_secs = time.perf_counter() - t0
    mesh = get_default_mesh()
    mesh_shape = dict(mesh.shape) if mesh is not None else None
    say(f"mesh run: {mesh_secs:.1f}s on mesh {mesh_shape} over "
        f"{[str(d) for d in jax.devices()]}")
    if mesh_shape != {"data": n_devices // 2, "entity": 2}:
        failures.append(f"mesh is {mesh_shape}")
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
    say(f"mesh run: per-device peak_bytes_in_use {peaks}")
    ev = trace_evidence(mesh_trace, None, n_devices)
    mesh_check = check_training(mesh_out, ev["spans"], GLMIX["sweeps"],
                                False, ())
    sharded = [s for s in ev["spans"] if s["name"] == "re.shard_solve"]
    if not sharded:
        failures.append("no re.shard_solve span: the entity-sharded "
                        "per-entity solve did not run")

    # the same configuration on one device: the driver's own stages with
    # no mesh installed, so every array lives on jax.devices()[0]
    one_out = os.path.join(work, "one_out")
    ns = gtd.parse_args(training_argv(fx, one_out,
                                      os.path.join(work, "one_trace")))
    os.makedirs(one_out, exist_ok=True)
    driver = gtd.GameTrainingDriver(ns)
    set_default_mesh(None)
    t0 = time.perf_counter()
    try:
        driver.prepare_feature_maps()
        driver.prepare_game_dataset()
        (_, one_result, _), _ = driver.train()
        one_secs = time.perf_counter() - t0
        save_game_model(one_result.model, os.path.join(one_out, "best"),
                        driver.index_maps,
                        entity_vocabs=dict(driver.train_data.id_vocabs),
                        task=driver.task)
    finally:
        driver.logger.close()
        logging.getLogger("photon_ml_tpu").removeHandler(warnings)
    if any("interpreted Avro reader" in m for m in warnings.messages):
        failures.append("training fell back to the interpreted Avro reader")
    one_objective = float(one_result.states[-1].objective)
    mesh_objective = mesh_check["objective_by_sweep"][-1]
    obj_dev = abs(mesh_objective - one_objective) / abs(one_objective)
    coef_dev = model_deviation(read_model(os.path.join(mesh_out, "best")),
                               read_model(os.path.join(one_out, "best")))
    say(f"one device: {one_secs:.1f}s, objective {one_objective!r}; mesh "
        f"objective {mesh_objective!r}: relative deviation {obj_dev:.3e} "
        f"(bound {MESH_OBJECTIVE_REL_BOUND}); largest coefficient "
        f"difference {coef_dev} (bound {MESH_COEF_ABS_BOUND})")
    if not (obj_dev <= MESH_OBJECTIVE_REL_BOUND
            and max(coef_dev.values()) <= MESH_COEF_ABS_BOUND):
        failures.append(f"mesh deviates: objective {obj_dev}, "
                        f"coefficients {coef_dev}")
    return {"mesh": mesh_shape, "peak_bytes": peaks,
            "objective_rel_dev": obj_dev, "coef_abs_dev": coef_dev,
            "failures": failures}


def run_mesh(ns) -> dict:
    """``--chips 4``: everything in this process, which holds the chips.
    The fixture writers are spawned children that never import JAX."""
    from photon_ml_tpu.utils.compile_cache import (
        enable_persistent_compile_cache,
    )

    enable_persistent_compile_cache()
    device = device_report()
    say(f"device: {device}")
    if device["platform"] != "tpu" or device["count"] != ns.chips:
        raise SmokeFailure(
            f"--chips {ns.chips} needs {ns.chips} TPU devices; JAX found "
            f"{device['count']} x {device['platform']}")
    os.environ["PHOTON_NATIVE_LIB"] = build_native(WORK)
    workers = max(1, (os.cpu_count() or 2) - 2)
    fx = build_fixture(WORK, MESH_ROWS, 0, ns.seed, workers)
    say(f"reduced: GLMix rows {GLMIX_FULL_ROWS} -> {MESH_ROWS} (four chips "
        f"cost four times a second and the training runs twice)")
    report = phase_mesh(WORK, fx, ns.chips)
    if any(not p for p in report["peak_bytes"]):
        report["failures"].append(
            f"a device held nothing: peaks {report['peak_bytes']}")
    if report["failures"]:
        raise SmokeFailure("; ".join(report["failures"]))
    return device


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=SEED)
    p.add_argument("--phase", choices=("glm",), help=argparse.SUPPRESS)
    ns = p.parse_args(argv)
    if ns.phase == "glm":
        return child_glm(ns)
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    try:
        device = run_mesh(ns) if ns.chips == 4 else run_one_chip(ns)
    except (SmokeFailure, subprocess.TimeoutExpired) as e:
        say(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
