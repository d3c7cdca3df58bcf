"""Headline benchmarks against BASELINE.md's config list.

Runs on the accelerator JAX finds or fails: there is no CPU fallback, no
interpret-mode stand-in and no carried-over record, and an unknown
``device_kind`` is an error. No record of this file's numbers on today's
code exists — PERF.md says "not measured" until one does; the on-chip
proof that the main path runs is ``chip_smoke.py``. One JSON line out,
naming the device it ran on. Rebuilding this file into cells is ROADMAP S1.

One process per chip: the probes that start children (ingest, serve,
fleet) run FIRST, while this process has not initialized a JAX backend,
and each child exits before the next starts.

- ``logistic_grad_evals_per_sec`` (headline; BASELINE config 1): fused
  value+gradient evaluations/sec of the logistic objective — the innermost
  distributed kernel of every solver in the reference
  (DistributedGLMLossFunction.calculate -> ValueAndGradientAggregator
  treeAggregate, reference photon-ml/src/main/scala/com/linkedin/photon/ml/
  function/ValueAndGradientAggregator.scala:235-250). Before timing, the
  Pallas kernel's three sums are parity-checked on the same device against
  the two-pass XLA form (the aggregator contract, :133-177).
- ``value_gradient_bf16``: the same kernel with X stored bf16 (caller
  opt-in): half the HBM stream, f32 accumulators, parity-gated against the
  f32 two-pass sums at bf16 input-rounding tolerance.
- ``hvp`` (config 2): Gauss-Newton Hessian-vector products/sec
  (HessianVectorAggregator.scala:137-163 — TRON's inner CG op).
- ``owlqn`` (config 3): full OWL-QN elastic-net Poisson solve wall-clock
  (OWLQN.scala:43-90 path).
- ``psum_quant``: A/B of the quantized-collective wire modes
  (--collective-quant none vs int8) over a 4-device mesh — the sharded
  fixed-effect fit and the entity-sharded RE solve+score, with the
  ``collective_bytes{site,mode}`` ledger deltas and convergence parity.
- ``glmix`` (config 4): end-to-end GLMix — fixed effect + per-user random
  effect logistic GAME on a MovieLens-1M-shaped synthetic dataset
  (CoordinateDescent.scala:50-263), reporting dataset-build and train
  wall-clock plus per-CD-sweep seconds.
- ``game_full`` (config 5): full GAME — fixed + per-user + per-item
  coordinates in one CD sweep plus a matrix-factorization scoring pass
  (the MovieLens-20M recipe's structure at 1-core-host-sized rows).
- ``ingest``: 10M-row ELL pack + random-effect block build throughput
  (RandomEffectDataSet.scala:169-206's shuffle analog; the block fill
  runs through the native C++ packer, native/block_packer.cpp).

Roofline: kernel benches report achieved HBM GB/s and % of the chip's peak
(detected from device_kind; override with PHOTON_HBM_PEAK_GBPS) so bandwidth
regressions are visible in the record, not just eval rates.

``vs_baseline`` is the headline rate over a single-process NumPy proxy of
the reference's Breeze-on-CPU per-core inner loop, measured in-run on this
host (the reference publishes no numbers — BASELINE.md); the proxy's
absolute rate is included as ``baseline_evals_per_sec``.
"""

import json
import os
import sys
import time

import numpy as np

_REPO_DIR = os.path.dirname(os.path.abspath(__file__))


def _progress(msg: str) -> None:
    """Stage progress to stderr (stdout stays the single JSON line):
    stages are minutes apart and a silent run is undiagnosable."""
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()

N_ROWS = 1 << 18  # 262144
DIM = 2048

# Public per-chip HBM bandwidth peaks, GB/s (override: PHOTON_HBM_PEAK_GBPS).
_HBM_PEAK_BY_KIND = (
    ("v6", 1638.0),
    ("v5p", 2765.0),
    ("v5 lite", 819.0),
    ("v5e", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
)


def _hbm_peak_gbps() -> float:
    env = os.environ.get("PHOTON_HBM_PEAK_GBPS")
    if env:
        return float(env)
    import jax

    kind = jax.devices()[0].device_kind.lower()
    for token, peak in _HBM_PEAK_BY_KIND:
        if token in kind:
            return peak
    raise RuntimeError(
        f"no HBM peak known for device_kind {kind!r} (platform "
        f"{jax.default_backend()!r}): this bench measures an accelerator "
        f"in the table above, it does not guess and it does not run on "
        f"the CPU")


def _roofline(bytes_per_eval: float, secs_per_eval: float,
              peak: float) -> dict:
    gbps = bytes_per_eval / secs_per_eval / 1e9
    return {"achieved_gbps": round(gbps, 1),
            "pct_hbm_peak": round(100.0 * gbps / peak, 1)}


def _reset_peak_rss() -> None:
    """Reset the kernel's peak-RSS watermark for THIS process. A child
    forked from a large parent inherits the fork-moment RSS in its
    ru_maxrss/VmHWM, so the isolated ingest subprocesses would otherwise
    report the parent bench's ~6 GB peak instead of their own."""
    try:
        with open("/proc/self/clear_refs", "w") as fh:
            fh.write("5")
    except OSError:  # pragma: no cover - non-Linux
        pass


def _peak_rss_mb() -> float:
    """Peak RSS of this process since the last _reset_peak_rss()."""
    try:
        with open("/proc/self/status") as fh:
            for ln in fh:
                if ln.startswith("VmHWM:"):
                    return round(int(ln.split()[1]) / 1024.0, 1)
    except OSError:  # pragma: no cover - non-Linux
        pass
    import resource

    return round(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0, 1)


def _data():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(N_ROWS, DIM)).astype(np.float32)
    w_true = (rng.normal(size=DIM) / np.sqrt(DIM)).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(X @ w_true)))
    y = (rng.uniform(size=N_ROWS) < p).astype(np.float32)
    w = rng.normal(size=DIM).astype(np.float32) * 0.01
    return X, y, w


def bench_numpy(X, y, w, iters=5):
    # Reference-shaped CPU work: margin, pointwise loss derivative, X^T r.
    def eval_once():
        z = X @ w
        p = 1.0 / (1.0 + np.exp(-z))
        val = np.sum(np.logaddexp(0.0, z) - y * z)
        g = X.T @ (p - y)
        return val, g

    eval_once()  # warm the caches
    t0 = time.perf_counter()
    for _ in range(iters):
        v, g = eval_once()
    dt = (time.perf_counter() - t0) / iters
    return 1.0 / dt


def _device_batch(X, y):
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import DenseBatch

    return DenseBatch(
        X=jnp.asarray(X),
        labels=jnp.asarray(y),
        offsets=jnp.zeros(X.shape[0], jnp.float32),
        weights=jnp.ones(X.shape[0], jnp.float32),
    )


def check_pallas_parity(batch, w) -> dict:
    """Parity proof for the fused Pallas kernel: (value, vector_sum,
    prefactor_sum) must match the two-pass XLA form, compiled and run on
    the SAME device the timings below use. Raises on mismatch, and raises
    where the kernel does not engage — a record never stands in an
    interpreter's result for the chip's (interpret-mode parity lives in
    tests/test_pallas.py)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.losses import get_loss
    from photon_ml_tpu.ops.pallas_kernels import (
        _xla_sums,
        fused_value_gradient_sums,
        pallas_supported,
    )

    n, d = batch.X.shape
    if not pallas_supported(n, d, batch.X.dtype):
        raise RuntimeError(
            f"the fused kernel does not engage at {n}x{d} {batch.X.dtype} "
            f"on {jax.default_backend()} x {jax.device_count()}")
    loss = get_loss("logistic")
    wj = jnp.asarray(w)
    shift = jnp.float32(0.0)
    fused = jax.jit(lambda: fused_value_gradient_sums(
        loss, False, batch.X, batch.labels, batch.offsets,
        batch.weights, wj, shift))()
    ref = jax.jit(lambda: _xla_sums(
        loss, batch.X, batch.labels, batch.offsets, batch.weights, wj,
        shift))()
    names = ("value", "vector_sum", "prefactor_sum")
    for name, got, want in zip(names, fused, ref):
        got, want = np.asarray(got), np.asarray(want)
        scale = max(1.0, float(np.abs(want).max()))
        err = float(np.abs(got - want).max()) / scale
        if err > 1e-5:
            raise AssertionError(
                f"Pallas kernel parity FAILED for {name}: rel err "
                f"{err:.3e} (got {got!r}, want {want!r})")
    return {"pallas_parity": "ok"}


def _timed_eval_chain(batch, w, bytes_per_eval, peak, iters=50) -> dict:
    """Shared timing harness for the value+gradient kernels (f32 and bf16
    records MUST be measured identically). Chains each iteration's w on the
    previous gradient (what L-BFGS does), so no evaluation can start before
    the one before it ended; one final VALUE fetch fences the whole chain.
    The 5-step warmup absorbs compile + the backend's first-dispatch
    ramp."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.aggregators import GLMObjective
    from photon_ml_tpu.ops.losses import get_loss

    obj = GLMObjective(loss=get_loss("logistic"), l2_lambda=0.0)
    wj = jnp.asarray(w)
    calc = jax.jit(lambda w, b: obj.calculate(w, b))
    wi = wj
    for _ in range(5):
        v, g = calc(wi, batch)
        wi = wi - 1e-4 * g
    float(v)

    t0 = time.perf_counter()
    wi = wj
    for _ in range(iters):
        v, g = calc(wi, batch)
        wi = wi - 1e-4 * g
    float(v)
    dt = (time.perf_counter() - t0) / iters
    out = {"evals_per_sec": round(1.0 / dt, 2)}
    out.update(_roofline(bytes_per_eval, dt, peak))
    return out


def bench_value_gradient(batch, w, peak, iters=50) -> dict:
    n, d = batch.X.shape
    # Single-pass minimum traffic: one read of X (the fused kernel's goal).
    return _timed_eval_chain(batch, w, 4.0 * n * d, peak, iters)


def bench_value_gradient_bf16(batch, w, peak, iters=50) -> dict:
    """bf16-X variant of the headline kernel: half the HBM stream, f32
    accumulators. Parity-checked on the device against the f32 two-pass
    sums at bf16 input-rounding tolerance before timing; a failure raises."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.aggregators import GLMObjective
    from photon_ml_tpu.ops.losses import get_loss
    from photon_ml_tpu.ops.pallas_kernels import _xla_sums

    n, d = batch.X.shape
    bf = batch._replace(X=batch.X.astype(jnp.bfloat16))
    obj = GLMObjective(loss=get_loss("logistic"), l2_lambda=0.0)
    wj = jnp.asarray(w)
    ref = jax.jit(lambda: _xla_sums(
        obj.loss, batch.X, batch.labels, batch.offsets,
        batch.weights, wj, jnp.float32(0.0)))()
    v0, g0 = jax.jit(lambda w, b: obj.calculate(w, b))(wj, bf)
    rv, rvec, _ = (np.asarray(x) for x in ref)
    if abs(float(v0) - float(rv)) > 2e-2 * abs(float(rv)):
        raise AssertionError(f"bf16 value {float(v0)} vs {float(rv)}")
    scale = max(1.0, float(np.abs(rvec).max()))
    # g0 is the reconstructed gradient == vector_sum with no norm
    if float(np.abs(np.asarray(g0) - rvec).max()) / scale > 5e-2:
        raise AssertionError("bf16 gradient outside 5e-2 of the f32 sums")
    out = {"parity": "ok"}
    out.update(_timed_eval_chain(bf, w, 2.0 * n * d, peak, iters))
    return out


def bench_hvp(batch, w, peak, iters=50) -> dict:
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.aggregators import GLMObjective
    from photon_ml_tpu.ops.losses import get_loss

    obj = GLMObjective(loss=get_loss("logistic"), l2_lambda=0.0)
    wj = jnp.asarray(w)
    hvp = jax.jit(lambda w, v, b: obj.hessian_vector(w, v, b))
    vi = jnp.ones_like(wj)
    for _ in range(5):
        vi = hvp(wj, vi, batch)
        vi = vi / jnp.linalg.norm(vi)  # power-iteration-style chain
    float(vi[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        vi = hvp(wj, vi, batch)
        vi = vi / jnp.linalg.norm(vi)
    float(vi[0])
    dt = (time.perf_counter() - t0) / iters
    n, d = batch.X.shape
    # HVP reads X twice (X v, then X^T s) — two-pass minimum traffic.
    out = {"evals_per_sec": round(1.0 / dt, 2)}
    out.update(_roofline(8.0 * n * d, dt, peak))
    return out


def bench_owlqn(iters=3) -> dict:
    """Config 3: Poisson elastic-net via OWL-QN, full solve wall-clock."""
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import dense_batch
    from photon_ml_tpu.optimize.config import (
        GLMOptimizationConfiguration,
        OptimizerType,
        RegularizationContext,
        RegularizationType,
        TaskType,
    )
    from photon_ml_tpu.optimize.problem import GLMOptimizationProblem

    rng = np.random.default_rng(1)
    n, d = 1 << 16, 512
    X = (rng.normal(size=(n, d)) / np.sqrt(d)).astype(np.float32)
    w_true = np.zeros(d, np.float32)
    w_true[: d // 8] = rng.normal(size=d // 8)  # sparse truth for L1
    lam = X @ w_true
    y = rng.poisson(np.exp(np.clip(lam, -6, 3))).astype(np.float32)
    batch = dense_batch(X, y)
    cfg = GLMOptimizationConfiguration(
        max_iterations=50, tolerance=1e-7, regularization_weight=1.0,
        optimizer_type=OptimizerType.LBFGS,
        regularization_context=RegularizationContext(
            RegularizationType.ELASTIC_NET, alpha=0.5))
    problem = GLMOptimizationProblem(
        config=cfg, task=TaskType.POISSON_REGRESSION)
    model, result = problem.run(batch)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        model, result = problem.run(batch)
    dt = (time.perf_counter() - t0) / iters
    nnz = int(np.sum(np.abs(np.asarray(model.coefficients.means)) > 1e-8))
    return {"solve_ms": round(dt * 1e3, 1),
            "iterations": int(result.iterations),
            "nnz_coefficients": nnz,
            "n": n, "d": d}


def _l2_config(lam, iters):
    """Shared L-BFGS+L2 config for the GAME benches (configs 4 and 5 must
    stay comparable)."""
    from photon_ml_tpu.optimize.config import (
        GLMOptimizationConfiguration,
        OptimizerType,
        RegularizationContext,
        RegularizationType,
    )

    return GLMOptimizationConfiguration(
        max_iterations=iters, tolerance=1e-7, regularization_weight=lam,
        optimizer_type=OptimizerType.LBFGS,
        regularization_context=RegularizationContext(
            RegularizationType.L2))


def bench_psum_quant(n=16_384, d=1024, n_users=256) -> dict:
    """A/B of the quantized-collective wire modes: the SAME sharded
    solves with ``collective_quant`` none vs int8 over a 4-device mesh
    (skipped, and recorded as skipped, on a backend with fewer than four
    devices). Two halves, one per collective-site family:

    - fixed-effect sharded fit (4-way data mesh, shard_weight_update):
      the d-vector gradient psums (``fe.grad_psum``) and the sharded
      iterate all-gather (``fe.iterate_gather``);
    - entity-sharded RE solve + score (4-way entity mesh): the RE score
      psum (``re.score_psum``).

    Each half records warm wall-clock, the convergence evidence
    (objective / max score delta vs the f32 wire), and the
    ``collective_bytes{site,mode}`` ledger deltas whose none/int8 ratio
    IS the wire compression (~3.9x at the 256-element block size)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import DenseBatch
    from photon_ml_tpu.game.dataset import (
        RandomEffectDataConfiguration,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.game.random_effect import (
        RandomEffectOptimizationProblem,
        score_random_effect,
    )
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.optimize.config import TaskType
    from photon_ml_tpu.optimize.problem import GLMOptimizationProblem
    from photon_ml_tpu.parallel.mesh import make_mesh, set_default_mesh

    devs = jax.devices()
    if len(devs) < 4:
        return {"skipped": "<4 devices on the default backend"}
    counter = REGISTRY.counter("collective_bytes")

    def site_delta(before):
        after = counter.items()
        return {f"{dict(k).get('site')}|{dict(k).get('mode')}":
                int(v - before.get(k, 0))
                for k, v in after.items() if v != before.get(k, 0)}

    rng = np.random.default_rng(18)
    X = (rng.normal(size=(n, d)) / np.sqrt(d)).astype(np.float32)
    w_true = rng.normal(size=d).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(X @ w_true)))
    y = (rng.random(n) < p).astype(np.float32)
    batch = DenseBatch(X=jnp.asarray(X), labels=jnp.asarray(y),
                      offsets=jnp.zeros(n, jnp.float32),
                      weights=jnp.ones(n, jnp.float32))
    out = {"fixed_sharded": {}, "re_sharded": {}}

    # ---- half 1: 4-way data-sharded fixed-effect fit --------------------
    from photon_ml_tpu.parallel.distributed import run_glm_shard_map

    mesh = make_mesh(num_data=4, num_entity=1, devices=list(devs[:4]))
    for mode in ("none", "int8"):
        prob = GLMOptimizationProblem(
            config=_l2_config(1.0, 40), task=TaskType.LOGISTIC_REGRESSION,
            shard_weight_update=True, collective_quant=mode)
        run_glm_shard_map(prob, batch, mesh)  # warm/compile
        before = counter.items()
        t0 = time.perf_counter()
        model, result = run_glm_shard_map(prob, batch, mesh)
        jax.block_until_ready(model.coefficients.means)
        out["fixed_sharded"][mode] = {
            "solve_secs": round(time.perf_counter() - t0, 3),
            "iterations": int(result.iterations),
            "objective": float(result.value),
            "collective_bytes": site_delta(before),
        }
    fx = out["fixed_sharded"]
    fx["objective_rel_delta"] = abs(
        fx["int8"]["objective"] - fx["none"]["objective"]) / max(
            abs(fx["none"]["objective"]), 1e-12)

    # ---- half 2: 4-way entity-sharded RE solve + score ------------------
    # capped rows/features per entity: the zipf skew would otherwise hand
    # one entity a giant lane and blow the single-block pad volume
    data = _movielens_data(rng, 20_000, n_users, 128, 16)
    re_cfg = RandomEffectDataConfiguration(
        random_effect_type="userId", feature_shard_id="per_user",
        num_partitions=1, num_active_data_points_upper_bound=128,
        num_features_to_keep_upper_bound=64)
    re_ds = build_random_effect_dataset(data, re_cfg, entity_axis_size=4)
    set_default_mesh(make_mesh(num_data=1, num_entity=4,
                               devices=list(devs[:4])))
    try:
        scores = {}
        re_offs = re_ds.offsets_with(
            jnp.zeros(int(re_ds.num_samples), jnp.float32))
        for mode in ("none", "int8"):
            prob = RandomEffectOptimizationProblem(
                config=_l2_config(1.0, 20),
                task=TaskType.LOGISTIC_REGRESSION, entity_shards=4,
                collective_quant=mode)
            coefs, *_ = prob.run(re_ds, re_offs)  # warm/compile
            score_random_effect(re_ds, coefs, entity_shards=4,
                                collective_quant=mode)
            before = counter.items()
            t0 = time.perf_counter()
            coefs, *_ = prob.run(re_ds, re_offs)
            s = score_random_effect(re_ds, coefs, entity_shards=4,
                                    collective_quant=mode)
            jax.block_until_ready(s)
            scores[mode] = np.asarray(s)
            out["re_sharded"][mode] = {
                "solve_score_secs": round(time.perf_counter() - t0, 3),
                "collective_bytes": site_delta(before),
            }
    finally:
        set_default_mesh(None)
    out["re_sharded"]["score_max_abs_delta"] = float(
        np.abs(scores["int8"] - scores["none"]).max())

    def _site_ratio(rec, site, rounds=(1, 1)):
        # normalize by each mode's round count (the two solves may take
        # different iteration counts) so the ratio is purely the wire
        # format, not convergence-speed noise
        none_b = rec["none"]["collective_bytes"].get(f"{site}|none", 0)
        int8_b = rec["int8"]["collective_bytes"].get(f"{site}|int8", 0)
        none_b /= max(rounds[0], 1)
        int8_b /= max(rounds[1], 1)
        return round(none_b / int8_b, 2) if int8_b else None

    fe_rounds = (fx["none"]["iterations"], fx["int8"]["iterations"])
    out["wire_compression_ratio"] = {
        "fe.grad_psum": _site_ratio(fx, "fe.grad_psum", fe_rounds),
        "fe.iterate_gather": _site_ratio(fx, "fe.iterate_gather",
                                         fe_rounds),
        "re.score_psum": _site_ratio(out["re_sharded"], "re.score_psum"),
    }
    return out


def _movielens_data(rng, n, n_users, n_movies, d_global,
                    with_item_effect=False):
    """MovieLens-shaped synthetic GameDataset: power-law users, uniform
    movies, dense globals, one-hot movie features per user coordinate (and
    one-hot user features per item coordinate when requested). One recipe
    for configs 4 and 5 so their numbers stay comparable."""
    import scipy.sparse as sp

    from photon_ml_tpu.game.dataset import GameDataset

    users = (rng.zipf(1.3, size=n) % n_users).astype(np.int64)
    movies = rng.integers(0, n_movies, n)
    Xg = (rng.normal(size=(n, d_global)) / np.sqrt(d_global)).astype(
        np.float32)
    wg = rng.normal(size=d_global).astype(np.float32)
    logits = Xg @ wg + 0.5 * rng.normal(size=n_users)[users].astype(
        np.float32)
    if with_item_effect:
        logits = logits + 0.4 * rng.normal(size=n_movies)[movies].astype(
            np.float32)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-logits))).astype(np.float64)
    one = np.ones(n, np.float32)
    shards = {
        "global": sp.csr_matrix(Xg),
        "per_user": sp.csr_matrix(
            (one, (np.arange(n), movies)), shape=(n, n_movies)),
    }
    if with_item_effect:
        shards["per_item"] = sp.csr_matrix(
            (one, (np.arange(n), users)), shape=(n, n_users))
    data = GameDataset(responses=y, feature_shards=shards)
    data.encode_ids("userId", users)
    if with_item_effect:
        data.encode_ids("movieId", movies)
    return data


def _instrumented_warm_pass(run_fn) -> dict:
    """The shared glmix/game_full warm-pass probe: one warm (everything
    compiled) training pass with the hot-loop sync telemetry reset around
    it, then the SAME pass again with span tracing enabled. One policy,
    two BENCH records — the probes can't drift apart.

    Returns ``run_fn``'s result plus: ``train_secs_warm``, the hot-loop
    stats dict, ``host_syncs_per_update`` (all instrumented fetch sites /
    updates; steady-state contract 2.0 = 1 hot-loop epilogue + 1
    amortized sweep-boundary drain), ``hot_loop_syncs_per_update``
    (contract ≤ 1.0 — asserted: the pipelined/blocked loop must never
    re-serialize into extra blocking reads), ``cd_pipeline_depth`` (max
    in-flight updates — 2 when double-buffering engages) and
    ``cd_overlap_fraction`` (how much of the epilogue latency the
    overlap hid), the per-site fetch breakdown, the warm pass's retrace
    delta (steady-state contract 0 — a warm retrace is an
    instrumentation/compile-cache regression), and the traced pass's
    ``train_secs_traced`` / ``trace_overhead_pct`` (the smoke test
    asserts < 2% on a repetition-median basis; this single-shot record
    tracks the trend) and the live-telemetry pass's
    ``train_secs_export_live`` / ``trace_export_overhead_pct`` (same
    contract with a connected --telemetry-endpoint consumer)."""
    from photon_ml_tpu.game import coordinate_descent as cd_mod
    from photon_ml_tpu.obs import compile as obs_compile
    from photon_ml_tpu.obs import trace as obs_trace
    from photon_ml_tpu.obs.metrics import REGISTRY as obs_registry
    from photon_ml_tpu.utils import sync_telemetry

    retraces_start = obs_registry.counter("retraces").total()
    # device-plane contract (when the --device-telemetry compile layer is
    # armed, as bench_glmix does for the whole bench): a WARM pass
    # compiles nothing — any compiles-counter delta here is a retrace
    compiles_start = (obs_registry.counter("compiles").total()
                      if obs_compile.is_armed() else None)
    cd_mod.reset_hot_loop_stats()
    sync_telemetry.reset_host_fetches()
    t0 = time.perf_counter()
    result = run_fn()
    train_secs_warm = time.perf_counter() - t0
    # snapshot the warm pass's telemetry BEFORE the traced probe runs the
    # same pass again (it records fetches/retraces of its own)
    hot = dict(cd_mod.HOT_LOOP_STATS)
    host_syncs_per_update = (sync_telemetry.host_fetch_count()
                             / hot["updates"] if hot["updates"] else None)
    hot_loop_syncs_per_update = (hot["epilogue_fetches"] / hot["updates"]
                                 if hot["updates"] else None)
    # pipelined-mode contract: the HOT-LOOP fetch rate is AT MOST 1.0
    # amortized (1 fused-epilogue fetch per update at block size 1, 1/B
    # per block of B) — a regression that re-serializes the loop into
    # extra blocking reads fails the bench loudly, not silently
    if hot_loop_syncs_per_update is not None:
        assert hot_loop_syncs_per_update <= 1.0, (
            f"hot-loop fetch rate {hot_loop_syncs_per_update} > 1.0/update "
            f"({hot['epilogue_fetches']} fetches / {hot['updates']} "
            f"updates): the one-round-trip pipelined contract broke")
    # double-buffering depth + how much of the epilogue latency the
    # overlap actually hid: overlap/(overlap+residual wait)
    cd_pipeline_depth = hot["max_inflight"]
    hidden = hot["overlap_secs"]
    residual = hot["epilogue_wait_secs"]
    cd_overlap_fraction = (hidden / (hidden + residual)
                           if (hidden + residual) > 0 else None)
    host_fetch_sites = sync_telemetry.host_fetches_by_site()
    retraces = int(obs_registry.counter("retraces").total()
                   - retraces_start)
    retrace_count_warm = None
    if compiles_start is not None:
        retrace_count_warm = int(obs_registry.counter("compiles").total()
                                 - compiles_start)
        assert retrace_count_warm == 0, (
            f"warm pass recompiled {retrace_count_warm} instrumented jit "
            f"site(s): the compile-layer signature cache regressed "
            f"(see the xla.retrace records for which argument changed)")

    obs_trace.enable()
    t0 = time.perf_counter()
    run_fn()
    train_secs_traced = time.perf_counter() - t0
    obs_trace.disable()

    # live-telemetry probe: the SAME warm pass with tracing on AND a
    # TelemetrySink connected to a real (discarding) local consumer,
    # spans drained to it on a heartbeat-like cadence — the
    # armed-but-idle cost of --telemetry-endpoint. The smoke test
    # asserts < 2% (the PR 5 tracing-overhead contract, extended to
    # the export plane); this single-shot record tracks the trend.
    import socket
    import threading

    from photon_ml_tpu.obs.export import TelemetrySink

    server = socket.socket()
    server.bind(("127.0.0.1", 0))
    server.listen(1)

    def _discard():
        conn, _ = server.accept()
        while conn.recv(65536):
            pass

    threading.Thread(target=_discard, daemon=True).start()
    sink = TelemetrySink("127.0.0.1:%d" % server.getsockname()[1])
    tracer = obs_trace.enable()
    stop_drain = threading.Event()

    def _drain_loop():
        while not stop_drain.wait(0.2):
            for e in tracer.drain():
                sink.emit({"kind": "span", **e})

    drainer = threading.Thread(target=_drain_loop, daemon=True)
    drainer.start()
    try:
        t0 = time.perf_counter()
        run_fn()
        train_secs_export = time.perf_counter() - t0
    finally:
        stop_drain.set()
        drainer.join(timeout=2.0)
        obs_trace.disable()
        sink.close()
        server.close()

    # fault-free-overhead probe: the SAME warm pass with a fault spec
    # ARMED on the hot-loop point but never firing (flaky p=0 — every
    # cd.update visit evaluates the full spec-matching + deterministic
    # decision path, the chaos machinery's worst no-op case). The smoke
    # test asserts this costs < 1% on the warm glmix path.
    from photon_ml_tpu.utils import faults as faults_mod

    faults_mod.arm("cd.update", "flaky", times=1_000_000_000,
                   probability=0.0)
    try:
        t0 = time.perf_counter()
        run_fn()
        train_secs_chaos = time.perf_counter() - t0
    finally:
        faults_mod.disarm_all()
    return {
        "result": result,
        "train_secs_warm": train_secs_warm,
        "hot": hot,
        "host_syncs_per_update": host_syncs_per_update,
        "hot_loop_syncs_per_update": hot_loop_syncs_per_update,
        "cd_pipeline_depth": cd_pipeline_depth,
        "cd_overlap_fraction": cd_overlap_fraction,
        "host_fetch_sites": host_fetch_sites,
        "retraces": retraces,
        "retrace_count_warm": retrace_count_warm,
        "train_secs_traced": train_secs_traced,
        "trace_overhead_pct": (100.0 * (train_secs_traced - train_secs_warm)
                               / train_secs_warm),
        "train_secs_export_live": train_secs_export,
        "trace_export_overhead_pct": (
            100.0 * (train_secs_export - train_secs_warm)
            / train_secs_warm),
        "train_secs_chaos_armed": train_secs_chaos,
        "chaos_overhead_pct": (100.0 * (train_secs_chaos - train_secs_warm)
                               / train_secs_warm),
    }


def bench_glmix(n=1_000_209, n_users=6040, n_movies=3706, d_global=64,
                active_cap=128, feature_cap=128, num_buckets=4) -> dict:
    """Config 4: fixed + per-user logistic GAME on MovieLens-1M-shaped data,
    end to end (the BASELINE north-star shape: 1M samples, 6040 users,
    3706 movies). Caps keep the padded entity block ~400 MB; host build +
    transfer time is part of the measured budget.

    ``num_buckets`` engages (N, D) entity bucketing (SURVEY §7 hard part 1):
    the record carries the per-bucket shapes, the padded-area ratio vs the
    single global block, and a per-stage (gather/solve/scatter) attribution
    of one steady-state RE update so the dominant cost is visible."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.coordinate import (
        FixedEffectCoordinate,
        RandomEffectCoordinate,
    )
    from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
    from photon_ml_tpu.game.dataset import (
        RandomEffectDataConfiguration,
        build_fixed_effect_dataset,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.game.random_effect import (
        RandomEffectOptimizationProblem,
    )
    from photon_ml_tpu.optimize.config import TaskType
    from photon_ml_tpu.optimize.problem import GLMOptimizationProblem

    rng = np.random.default_rng(7)
    t0 = time.perf_counter()
    data = _movielens_data(rng, n, n_users, n_movies, d_global)
    fixed_ds = build_fixed_effect_dataset(data, "global")
    re_cfg = RandomEffectDataConfiguration(
        random_effect_type="userId", feature_shard_id="per_user",
        num_partitions=1, num_active_data_points_upper_bound=active_cap,
        num_features_to_keep_upper_bound=feature_cap)
    re_ds = build_random_effect_dataset(data, re_cfg,
                                        num_buckets=num_buckets)
    build_secs = time.perf_counter() - t0
    if re_ds.buckets is not None:
        bucket_shapes = [[int(s) for s in b.X.shape] for b in re_ds.buckets]
        area = sum(e * nn * d for e, nn, d in bucket_shapes)
        single_area = (re_ds.num_entities
                       * max(nn for _, nn, _ in bucket_shapes)
                       * re_ds.reduced_dim)
        _progress(f"glmix dataset built in {build_secs:.1f}s "
                  f"(re buckets {bucket_shapes}, "
                  f"{100 * area / single_area:.0f}% of single-block cells)")
    else:
        bucket_shapes = [[int(s) for s in re_ds.X.shape]]
        area = single_area = int(np.prod(re_ds.X.shape))
        _progress(f"glmix dataset built in {build_secs:.1f}s "
                  f"(re block {tuple(int(s) for s in re_ds.X.shape)})")

    coords = {
        "fixed": FixedEffectCoordinate(
            dataset=fixed_ds,
            problem=GLMOptimizationProblem(
                config=_l2_config(10.0, 40),
                task=TaskType.LOGISTIC_REGRESSION)),
        "per-user": RandomEffectCoordinate(
            dataset=re_ds,
            problem=RandomEffectOptimizationProblem(
                config=_l2_config(1.0, 20),
                task=TaskType.LOGISTIC_REGRESSION)),
    }

    labels_j = jnp.asarray(data.responses, jnp.float32)
    weights_j = jnp.asarray(data.weights, jnp.float32)
    offsets_j = jnp.asarray(data.offsets, jnp.float32)
    # arm the --device-telemetry compile layer for the whole glmix bench:
    # the cold pass harvests its per-site lower().compile() bill
    # (compile_secs_cold) and the warm probe asserts the zero-warm-retrace
    # contract against the same compiles counter
    from photon_ml_tpu.obs import compile as obs_compile
    from photon_ml_tpu.obs.metrics import REGISTRY as obs_registry

    obs_compile.arm()
    compile_secs_start = obs_registry.counter("compile_secs").total()
    t0 = time.perf_counter()
    result = run_coordinate_descent(
        coords, num_iterations=2, task=TaskType.LOGISTIC_REGRESSION,
        labels=labels_j, weights=weights_j, offsets=offsets_j)
    train_secs = time.perf_counter() - t0
    compile_secs_cold = float(obs_registry.counter("compile_secs").total()
                              - compile_secs_start)
    sweep_secs = [round(h.seconds, 2) for h in result.states]

    # Compile vs steady-state attribution: re-run the identical training
    # with every kernel already jitted at these shapes. The warm time is
    # the steady-state cost; cold minus warm is (per-bucket-shape) compile
    # overhead, which the persistent compile cache (enabled in main)
    # absorbs on later *processes* too — the
    # warm-start economics of the reference's λ-grid
    # (ModelTraining.scala:182-208). The warm pass also carries the
    # hot-loop sync telemetry: ALL instrumented blocking device→host
    # fetches (epilogue, lazy trackers/histories, compaction masks,
    # snapshots — utils/sync_telemetry.py) per coordinate update
    # (steady-state contract 2.0 = 1 hot-loop epilogue + 1 amortized
    # sweep-boundary drain; the hot-loop-only metric's contract is 1.0 —
    # a lazy-materialization regression pushes either higher), and the
    # dispatch-vs-fetch-wait wall-clock split.
    probe = _instrumented_warm_pass(lambda: run_coordinate_descent(
        coords, num_iterations=2, task=TaskType.LOGISTIC_REGRESSION,
        labels=labels_j, weights=weights_j, offsets=offsets_j))
    result_warm = probe["result"]
    train_secs_warm = probe["train_secs_warm"]
    sweep_secs_warm = [round(h.seconds, 2) for h in result_warm.states]
    hot = probe["hot"]
    host_syncs_per_update = probe["host_syncs_per_update"]
    hot_loop_syncs_per_update = probe["hot_loop_syncs_per_update"]
    host_fetch_sites = probe["host_fetch_sites"]
    retraces = probe["retraces"]
    train_secs_traced = probe["train_secs_traced"]
    trace_overhead_pct = probe["trace_overhead_pct"]
    _progress(f"glmix train cold {train_secs:.1f}s / warm "
              f"{train_secs_warm:.1f}s (compile overhead "
              f"{train_secs - train_secs_warm:.1f}s, "
              f"{host_syncs_per_update} host sync(s)/update incl "
              f"sweep-boundary drains, {retraces} retrace(s))")
    _progress(f"glmix traced warm {train_secs_traced:.1f}s "
              f"(overhead {trace_overhead_pct:+.1f}%)")

    # Block-parallel warm pass on the MAIN glmix config (--cd-block-size
    # 2: both coordinates solve against the stale sweep-start total, one
    # fused correction epilogue per sweep instead of two) — the direct
    # wall-clock comparison point against the sequential warm record.
    run_coordinate_descent(  # compile the block-2 epilogue shape
        coords, num_iterations=2, task=TaskType.LOGISTIC_REGRESSION,
        labels=labels_j, weights=weights_j, offsets=offsets_j,
        block_size=2)
    t0 = time.perf_counter()
    run_coordinate_descent(
        coords, num_iterations=2, task=TaskType.LOGISTIC_REGRESSION,
        labels=labels_j, weights=weights_j, offsets=offsets_j,
        block_size=2)
    train_secs_warm_block2 = time.perf_counter() - t0
    _progress(f"glmix train warm block-2 {train_secs_warm_block2:.1f}s")

    # Preemption-drill probe: deliver a REAL SIGTERM right before the
    # warm pass's second commit barrier, let the graceful-stop path
    # resolve the in-flight handle + snapshot + raise, then resume from
    # that snapshot to completion. Dead time = (interrupted + resumed)
    # wall clock minus one uninterrupted warm pass — the per-preemption
    # cost a scheduler actually pays (snapshot write, restore, replayed
    # dispatch warmup). The resumed objective must equal the warm run's
    # bit for bit, or the probe is measuring a different trajectory.
    import shutil as _shutil
    import signal as _signal
    import tempfile as _tempfile

    from photon_ml_tpu.utils.checkpoint import (
        CheckpointManager as _CkptMgr,
    )
    from photon_ml_tpu.utils.preempt import (
        PreemptionRequested,
        StopController,
    )

    class _SignalAtBarrier:
        """SIGTERM the process at the Nth barrier poll, then delegate
        to the real controller — the probe walks the actual
        signal → latch → barrier path, in process."""

        def __init__(self, controller, at_poll):
            self._controller = controller
            self._at_poll = at_poll
            self._polls = 0

        def should_stop(self):
            self._polls += 1
            if self._polls == self._at_poll:
                os.kill(os.getpid(), _signal.SIGTERM)
            return self._controller.should_stop()

    preempt_ckpt = _tempfile.mkdtemp(prefix="bench_preempt_ckpt_")
    controller = StopController()
    controller.install_signal_handlers(signums=(_signal.SIGTERM,))
    mgr = _CkptMgr(preempt_ckpt)
    preempt_step = None
    t0 = time.perf_counter()
    try:
        run_coordinate_descent(
            coords, num_iterations=2,
            task=TaskType.LOGISTIC_REGRESSION, labels=labels_j,
            weights=weights_j, offsets=offsets_j,
            checkpoint_manager=mgr,
            stop=_SignalAtBarrier(controller, at_poll=2))
    except PreemptionRequested as e:
        preempt_step = e.step
    finally:
        controller.uninstall_signal_handlers()
    preempt_interrupted_secs = time.perf_counter() - t0
    assert preempt_step is not None, (
        "preemption probe never preempted: the SIGTERM-at-barrier "
        "path regressed")
    t0 = time.perf_counter()
    resumed = run_coordinate_descent(
        coords, num_iterations=2, task=TaskType.LOGISTIC_REGRESSION,
        labels=labels_j, weights=weights_j, offsets=offsets_j,
        resume_snapshot=mgr.restore())
    preempt_resumed_secs = time.perf_counter() - t0
    _shutil.rmtree(preempt_ckpt, ignore_errors=True)
    assert (resumed.states[-1].objective
            == result_warm.states[-1].objective), (
        "preempt+resume objective diverged from the warm pass: "
        f"{resumed.states[-1].objective!r} vs "
        f"{result_warm.states[-1].objective!r}")
    preempt_resume_dead_secs = (preempt_interrupted_secs
                                + preempt_resumed_secs
                                - train_secs_warm)
    _progress(f"glmix preempt@{preempt_step} drill: interrupted "
              f"{preempt_interrupted_secs:.1f}s + resumed "
              f"{preempt_resumed_secs:.1f}s vs warm "
              f"{train_secs_warm:.1f}s -> dead "
              f"{preempt_resume_dead_secs:+.1f}s (bit-exact)")

    # Steady-state per-stage attribution of one RE update (everything is
    # already compiled at these shapes): offset gather (sample->entity
    # resharding), vmapped solve, score scatter (entity->sample), plus the
    # fused-epilogue cost amortized over the warm run's updates.
    import dataclasses as _dc

    from photon_ml_tpu.game import random_effect as re_mod
    from photon_ml_tpu.game.random_effect import score_random_effect

    re_prob = coords["per-user"].problem
    scores = jnp.zeros(n, jnp.float32)
    t0 = time.perf_counter()
    offs = re_ds.offsets_with(scores)
    jax.block_until_ready(offs)
    gather_secs = time.perf_counter() - t0
    t0 = time.perf_counter()
    coefs, *_ = re_prob.run(re_ds, offs)
    jax.block_until_ready(coefs)
    solve_secs = time.perf_counter() - t0
    t0 = time.perf_counter()
    s = score_random_effect(re_ds, coefs)
    jax.block_until_ready(s)
    scatter_secs = time.perf_counter() - t0

    # Lane compaction (chunked solve, still-active lanes re-dispatched) on
    # a straggler-heavy variant of the same data: high iteration budget +
    # tight tolerance makes per-entity iteration counts genuinely
    # heterogeneous (the MovieLens zipf skew supplies the size spread), so
    # the batched plain solve runs EVERY lane to the slowest lane's count
    # while the compacted solve sheds converged lanes chunk by chunk.
    # Warm both paths at these shapes first, then time.
    # keep the native tolerance: tightening it would turn EVERY lane into
    # a straggler and leave compaction nothing to shed
    straggler_cfg = _dc.replace(re_prob.config, max_iterations=60)
    plain_prob = _dc.replace(re_prob, config=straggler_cfg)
    compacted_prob = _dc.replace(re_prob, config=straggler_cfg,
                                 lane_compaction_chunk=5)
    plain_prob.run(re_ds, re_ds.offsets_with(scores))
    compacted_prob.run(re_ds, re_ds.offsets_with(scores))
    t0 = time.perf_counter()
    coefs_p, *_ = plain_prob.run(re_ds, re_ds.offsets_with(scores))
    jax.block_until_ready(coefs_p)
    solve_straggler_secs = time.perf_counter() - t0
    re_mod.reset_solve_stats()
    t0 = time.perf_counter()
    coefs_c, *_ = compacted_prob.run(re_ds, re_ds.offsets_with(scores))
    jax.block_until_ready(coefs_c)
    solve_compacted_secs = time.perf_counter() - t0
    compact_stats = {k: (round(v, 3) if isinstance(v, float) else v)
                     for k, v in re_mod.SOLVE_STATS.items()}
    _progress(f"glmix RE straggler solve plain {solve_straggler_secs:.2f}s "
              f"/ lane-compacted {solve_compacted_secs:.2f}s "
              f"(chunks {compact_stats['chunks']}, active lanes "
              f"{compact_stats['lane_counts']})")

    # Mesh-sharded A/B on the same straggler config: partition the entity
    # axis over a 4-device (1 data x 4 entity) mesh, where the backend
    # has four devices, and
    # re-run the compacted straggler solve with per-shard lane
    # compaction. Direct comparison point: solve_straggler_compacted
    # (same config, same zipf skew, one device). The dataset is rebuilt
    # with entity_axis_size=4 so every bucket's lane count divides the
    # mesh; the padding fraction and rolling per-shard lane counts land
    # in the record so shard-imbalance waste is auditable.
    re_solve_secs_sharded = None
    re_shard_padding_frac = None
    re_shard_lane_counts = None
    shard_devs = jax.devices()
    if len(shard_devs) >= 4:
        from photon_ml_tpu.parallel.mesh import make_mesh, set_default_mesh

        re_ds_shard = build_random_effect_dataset(
            data, re_cfg, num_buckets=num_buckets, entity_axis_size=4)
        sharded_prob = _dc.replace(compacted_prob, entity_shards=4)
        set_default_mesh(make_mesh(num_data=1, num_entity=4,
                                   devices=list(shard_devs[:4])))
        try:
            off_s = re_ds_shard.offsets_with(scores)
            coefs_s, *_ = sharded_prob.run(re_ds_shard, off_s)  # warm
            jax.block_until_ready(coefs_s)
            re_mod.reset_solve_stats()
            t0 = time.perf_counter()
            coefs_s, *_ = sharded_prob.run(re_ds_shard, off_s)
            jax.block_until_ready(coefs_s)
            re_solve_secs_sharded = time.perf_counter() - t0
            padded = re_mod.SOLVE_STATS["shard_padded_lanes"]
            if padded:
                re_shard_padding_frac = round(
                    1.0 - re_mod.SOLVE_STATS["shard_real_lanes"] / padded,
                    4)
            re_shard_lane_counts = list(
                re_mod.SOLVE_STATS["shard_lane_counts"])
        finally:
            set_default_mesh(None)
        _progress(f"glmix RE straggler solve mesh-sharded(4) "
                  f"{re_solve_secs_sharded:.2f}s vs single-device "
                  f"compacted {solve_compacted_secs:.2f}s (padding frac "
                  f"{re_shard_padding_frac}, per-shard active lanes "
                  f"{re_shard_lane_counts})")
    else:
        _progress("glmix RE mesh-sharded A/B skipped: <4 devices on the "
                  "default backend (re_solve_secs_sharded stays null)")

    # Block-size ladder on the straggler config: one warm CD sweep per
    # --cd-block-size in (1, 2, 4) over (fixed, straggler per-user). A
    # block solves its coordinates concurrently against the stale
    # block-start total and pays ONE fused correction epilogue, so the
    # ladder shows what block parallelism buys when the RE solve is the
    # long pole (4 clamps to the 2-coordinate sweep width — recorded
    # anyway so the ladder shape is comparable across rounds).
    straggler_coords = {
        "fixed": coords["fixed"],
        "per-user": RandomEffectCoordinate(dataset=re_ds,
                                           problem=compacted_prob),
    }
    ladder = {}
    for bs in (1, 2, 4):
        run_coordinate_descent(  # warm this block shape's epilogue
            straggler_coords, num_iterations=1,
            task=TaskType.LOGISTIC_REGRESSION, labels=labels_j,
            weights=weights_j, offsets=offsets_j, block_size=bs)
        t0 = time.perf_counter()
        run_coordinate_descent(
            straggler_coords, num_iterations=1,
            task=TaskType.LOGISTIC_REGRESSION, labels=labels_j,
            weights=weights_j, offsets=offsets_j, block_size=bs)
        ladder[str(bs)] = round(time.perf_counter() - t0, 2)
    _progress(f"glmix straggler-config block-size ladder: {ladder}")
    obs_compile.disarm()

    return {
        "n_samples": n, "n_users": len(data.id_vocabs["userId"]),
        "d_global": d_global,
        "re_buckets": bucket_shapes,
        "re_padded_cells_vs_single_block": round(area / single_area, 3),
        "dataset_build_secs": round(build_secs, 2),
        "train_secs": round(train_secs, 2),
        "train_secs_warm": round(train_secs_warm, 2),
        # the same warm training pass with --cd-block-size 2 (one fused
        # correction epilogue per sweep instead of two)
        "train_secs_warm_block2": round(train_secs_warm_block2, 2),
        "compile_overhead_secs": round(train_secs - train_secs_warm, 2),
        # the cold pass's device-plane compile bill (sum of the
        # compile_secs{site} counter over the instrumented jit sites) and
        # the warm pass's compiles-counter delta (asserted 0: a warm
        # retrace is a compile-cache regression)
        "compile_secs_cold": round(compile_secs_cold, 2),
        "retrace_count_warm": probe["retrace_count_warm"],
        "per_update_secs": sweep_secs,
        "per_update_secs_warm": sweep_secs_warm,
        # one-round-trip contract telemetry (warm pass): blocking
        # device→host fetches per coordinate update — in-hot-loop (the
        # fused epilogue; the contract value is 1.0) and total including
        # the per-sweep tracker drains (steady state 2.0) — and where the
        # warm wall-clock went (async dispatch vs blocking on the
        # epilogue)
        "host_syncs_per_update": host_syncs_per_update,
        "host_syncs_per_update_hot_loop": hot_loop_syncs_per_update,
        # double-buffering telemetry: max in-flight updates (2 = the
        # pipeline engaged) and the fraction of epilogue latency the
        # dispatch overlap hid (1.0 = fetches always found the result
        # ready; 0.0 = every fetch blocked for the full epilogue)
        "cd_pipeline_depth": probe["cd_pipeline_depth"],
        "cd_overlap_fraction": (
            None if probe["cd_overlap_fraction"] is None
            else round(probe["cd_overlap_fraction"], 3)),
        # one warm CD sweep per --cd-block-size over the straggler
        # config: what block-parallel sweeps buy when the RE solve is
        # the long pole
        "cd_block_ladder_secs": ladder,
        # the SIGTERM-at-barrier drill: wall clock a preemption + resume
        # costs over one uninterrupted warm pass (snapshot write,
        # restore, replayed dispatch warmup), with the resumed
        # trajectory asserted bit-exact
        "preempt_step": preempt_step,
        "preempt_interrupted_secs": round(preempt_interrupted_secs, 2),
        "preempt_resumed_secs": round(preempt_resumed_secs, 2),
        "preempt_resume_dead_secs": round(preempt_resume_dead_secs, 2),
        # per-site breakdown of the warm run's instrumented fetches
        # (labeled host_fetches counter; values sum to the legacy total)
        "host_fetch_sites": host_fetch_sites,
        # compile pressure paid by this bench (epilogue-cache misses +
        # new bucketed-dispatch shapes) and the cost of tracing the warm
        # pass (span instrumentation regression guard)
        "retraces": retraces,
        "trace_overhead_pct": round(trace_overhead_pct, 2),
        "hot_loop_wallclock_split_secs": {
            "update_dispatch": round(hot["update_dispatch_secs"], 3),
            "epilogue_wait": round(hot["epilogue_wait_secs"], 3),
        },
        "re_update_stage_secs": {
            "gather_offsets": round(gather_secs, 3),
            "solve": round(solve_secs, 3),
            # straggler-heavy config (max_iter 60, native tolerance):
            # plain pays every lane to the slowest lane's count, compacted
            # sheds converged lanes per chunk
            "solve_straggler_plain": round(solve_straggler_secs, 3),
            "solve_straggler_compacted": round(solve_compacted_secs, 3),
            # same compacted straggler config over a (1 data x 4 entity)
            # mesh; null when no platform offers 4 devices
            "re_solve_secs_sharded": (
                round(re_solve_secs_sharded, 3)
                if re_solve_secs_sharded is not None else None),
            # pad-slot waste of the per-shard pow2 lane padding
            # (1 - real/padded over every sharded dispatch)
            "re_shard_padding_frac": re_shard_padding_frac,
            # rolling max-over-shards active-lane widths per chunk
            "re_shard_lane_counts": re_shard_lane_counts,
            "scatter_scores": round(scatter_secs, 3),
            # per-update fused-epilogue cost, amortized over the warm run
            "epilogue": (round(hot["epilogue_wait_secs"]
                               / hot["updates"], 3)
                         if hot["updates"] else None),
            # lane-compaction internals: chunked-solve dispatch+mask-wait
            # vs gather/re-pack time, and the shrinking active-lane counts
            "compact": compact_stats["compact_secs"],
            "compact_chunks": compact_stats["chunks"],
            "compact_lane_counts": compact_stats["lane_counts"],
        },
        "final_objective": round(float(result.states[-1].objective), 1),
    }


def bench_game_full(n=400_000, n_users=6040, n_movies=3706, d_global=32,
                    latent_dim=8) -> dict:
    """Config 5: full GAME — fixed + per-user + per-item coordinates in one
    CD sweep plus a matrix-factorization scoring pass (the MovieLens-20M
    recipe at a 1-core-host-sized row count; per-coordinate structure, not
    scale, is what config 5 adds over config 4)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.coordinate import (
        FixedEffectCoordinate,
        RandomEffectCoordinate,
    )
    from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
    from photon_ml_tpu.game.dataset import (
        RandomEffectDataConfiguration,
        build_fixed_effect_dataset,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.game.models import MatrixFactorizationModel
    from photon_ml_tpu.game.random_effect import (
        RandomEffectOptimizationProblem,
    )
    from photon_ml_tpu.optimize.config import TaskType
    from photon_ml_tpu.optimize.problem import GLMOptimizationProblem

    rng = np.random.default_rng(11)
    t0 = time.perf_counter()
    data = _movielens_data(rng, n, n_users, n_movies, d_global,
                           with_item_effect=True)
    users = np.asarray(data.id_columns["userId"])
    movies = np.asarray(data.id_columns["movieId"])

    fixed_ds = build_fixed_effect_dataset(data, "global")
    user_ds = build_random_effect_dataset(data, RandomEffectDataConfiguration(
        "userId", "per_user", 1, num_active_data_points_upper_bound=64,
        num_features_to_keep_upper_bound=64), num_buckets=3)
    item_ds = build_random_effect_dataset(data, RandomEffectDataConfiguration(
        "movieId", "per_item", 1, num_active_data_points_upper_bound=64,
        num_features_to_keep_upper_bound=64), num_buckets=3)
    build_secs = time.perf_counter() - t0

    def _shapes(ds):
        return [[int(x) for x in b.X.shape] for b in ds.buckets] \
            if ds.buckets is not None else [[int(x) for x in ds.X.shape]]

    _progress(f"game-full dataset built in {build_secs:.1f}s (user buckets "
              f"{_shapes(user_ds)}, item buckets {_shapes(item_ds)})")

    task = TaskType.LOGISTIC_REGRESSION
    coords = {
        "fixed": FixedEffectCoordinate(
            dataset=fixed_ds,
            problem=GLMOptimizationProblem(
                config=_l2_config(10.0, 30), task=task)),
        "per-user": RandomEffectCoordinate(
            dataset=user_ds,
            problem=RandomEffectOptimizationProblem(
                config=_l2_config(1.0, 15), task=task)),
        "per-item": RandomEffectCoordinate(
            dataset=item_ds,
            problem=RandomEffectOptimizationProblem(
                config=_l2_config(1.0, 15), task=task)),
    }
    labels_j = jnp.asarray(data.responses, jnp.float32)
    weights_j = jnp.asarray(data.weights, jnp.float32)
    offsets_j = jnp.asarray(data.offsets, jnp.float32)
    t0 = time.perf_counter()
    result = run_coordinate_descent(
        coords, num_iterations=1, task=task,
        labels=labels_j, weights=weights_j, offsets=offsets_j)
    train_secs = time.perf_counter() - t0
    # compile vs steady-state attribution: the shared warm-pass probe
    # carries the hot-loop sync telemetry and the tracing-overhead run
    probe = _instrumented_warm_pass(
        lambda: run_coordinate_descent(coords, num_iterations=1, task=task,
                                       labels=labels_j, weights=weights_j,
                                       offsets=offsets_j))
    train_secs_warm = probe["train_secs_warm"]
    hot = probe["hot"]
    host_syncs_per_update = probe["host_syncs_per_update"]
    hot_loop_syncs_per_update = probe["hot_loop_syncs_per_update"]
    host_fetch_sites = probe["host_fetch_sites"]
    retraces = probe["retraces"]
    train_secs_traced = probe["train_secs_traced"]
    trace_overhead_pct = probe["trace_overhead_pct"]
    _progress(f"game-full traced warm {train_secs_traced:.1f}s "
              f"(overhead {trace_overhead_pct:+.1f}%)")

    # MF scoring pass: replicated factor tables, one jitted gather+dot
    # (MatrixFactorizationModel.scala:50,141's RDD join as a device gather).
    mf = MatrixFactorizationModel(
        row_effect_type="userId", col_effect_type="movieId",
        row_factors=jnp.asarray(rng.normal(
            size=(n_users, latent_dim)).astype(np.float32)),
        col_factors=jnp.asarray(rng.normal(
            size=(n_movies, latent_dim)).astype(np.float32)))
    r = jnp.asarray(users.astype(np.int32))
    c = jnp.asarray(movies.astype(np.int32))

    @jax.jit
    def mf_score(rf, cf, r, c):
        return jnp.sum(rf[r] * cf[c], axis=-1)

    s = mf_score(mf.row_factors, mf.col_factors, r, c)
    float(s[0])  # compile + fence
    t0 = time.perf_counter()
    for _ in range(5):
        s = mf_score(mf.row_factors, mf.col_factors, r, c)
    float(s[0])
    mf_secs = (time.perf_counter() - t0) / 5
    return {
        "n_samples": n, "d_global": d_global,
        "coordinates": ["fixed", "per-user", "per-item"],
        "dataset_build_secs": round(build_secs, 2),
        "cd_sweep_secs": round(train_secs, 2),
        "cd_sweep_secs_warm": round(train_secs_warm, 2),
        "compile_overhead_secs": round(train_secs - train_secs_warm, 2),
        "host_syncs_per_update": host_syncs_per_update,
        "host_syncs_per_update_hot_loop": hot_loop_syncs_per_update,
        "cd_pipeline_depth": probe["cd_pipeline_depth"],
        "cd_overlap_fraction": (
            None if probe["cd_overlap_fraction"] is None
            else round(probe["cd_overlap_fraction"], 3)),
        "host_fetch_sites": host_fetch_sites,
        "retraces": retraces,
        "trace_overhead_pct": round(trace_overhead_pct, 2),
        "hot_loop_wallclock_split_secs": {
            "update_dispatch": round(hot["update_dispatch_secs"], 3),
            "epilogue_wait": round(hot["epilogue_wait_secs"], 3),
        },
        "mf_score_rows_per_sec": round(n / mf_secs, 0),
        "final_objective": round(float(result.states[-1].objective), 1),
    }


def bench_avro_ingest(n=200_000, d=30) -> dict:
    """Avro container → LabeledData through the native columnar decoder
    (native/avro_columnar.cpp; DataProcessingUtils.scala's JVM decode is
    the reference analog)."""
    import tempfile

    from photon_ml_tpu.io import schemas
    from photon_ml_tpu.io.avro import write_container
    from photon_ml_tpu.io.data_format import load_labeled_points_avro

    rng = np.random.default_rng(5)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, 2, n).astype(float)
    recs = [{"uid": f"r{i}", "label": float(y[i]),
             "features": [{"name": f"f{j}", "term": "",
                           "value": float(X[i, j])} for j in range(d)],
             "metadataMap": None, "weight": None, "offset": None}
            for i in range(n)]
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "bench.avro")
        write_container(path, schemas.TRAINING_EXAMPLE, recs)
        del recs
        t0 = time.perf_counter()
        data = load_labeled_points_avro(path)
        dt = time.perf_counter() - t0
    return {"rows": n, "nnz": int(data.features.nnz),
            "records_per_sec": round(n / dt, 0),
            "features_per_sec": round(data.features.nnz / dt, 0)}


def _serve_stage_split(run_dirs) -> dict:
    """Per-stage request-pipeline split from serve run dirs' exit
    metrics snapshots: the ``serve_stage_ms{stage}`` histogram records
    summed across processes (members + router), reduced to
    count/mean/max per stage — the "where did request latency go"
    column BENCH.md tracks next to the end-to-end p99."""
    agg: dict[str, dict] = {}
    for rd in run_dirs:
        try:
            fh = open(os.path.join(rd, "metrics.jsonl"))
        except OSError:
            continue
        with fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if (rec.get("kind") != "histogram"
                        or rec.get("name") != "serve_stage_ms"):
                    continue
                stage = (rec.get("labels") or {}).get("stage")
                if stage is None:
                    continue
                s = agg.setdefault(stage, {"count": 0, "sum": 0.0,
                                           "max": 0.0})
                s["count"] += rec.get("count", 0)
                s["sum"] += rec.get("sum", 0.0)
                s["max"] = max(s["max"], rec.get("max", 0.0))
    return {stage: {"count": int(s["count"]),
                    "mean_ms": (round(s["sum"] / s["count"], 3)
                                if s["count"] else None),
                    "max_ms": round(s["max"], 3)}
            for stage, s in sorted(agg.items())}


def bench_serve(n_users=512, d_g=16, d_u=8, n_clients=4,
                duration_secs=3.0) -> dict:
    """Sustained concurrent-client load against a real photon-serve
    subprocess: NDJSON protocol + micro-batcher + tiered store, end to
    end. The HBM budget holds half the entities so the device tier
    churns under load; the probe reports client-observed rows/sec, the
    service's own SLO gauges, and the per-tier hit split read back from
    the exit metrics snapshot.

    Halfway through, the probe hot-swaps the service to a freshly
    "retrained" model while all clients keep scoring:
    ``swap_blackout_ms`` is the worst client-observed latency in the
    swap window (request admission → flip resolution) — the cost of a
    live generation flip. The probe asserts the swap completes, that
    NOTHING sheds across it, and that the warm loop never retraces
    (the candidate generation reuses the boot generation's compiled
    shapes)."""
    import signal
    import subprocess
    import tempfile
    import threading

    from photon_ml_tpu.game.models import (
        FixedEffectModel, GameModel, RandomEffectModel)
    from photon_ml_tpu.io.index_map import IndexMap
    from photon_ml_tpu.io.model_io import save_game_model
    from photon_ml_tpu.models.glm import (
        Coefficients, GeneralizedLinearModel)
    from photon_ml_tpu.optimize.config import TaskType
    from photon_ml_tpu.serve.protocol import ServeClient

    _require_parent_off_chip("bench_serve")
    rng = np.random.default_rng(17)
    imaps = {
        "global": IndexMap.from_keys([f"g{j}" for j in range(d_g)],
                                     add_intercept=True),
        "user": IndexMap.from_keys([f"u{j}" for j in range(d_u)],
                                   add_intercept=True),
    }
    fixed = FixedEffectModel(GeneralizedLinearModel(
        Coefficients(rng.normal(size=len(imaps["global"])).astype(
            np.float32)),
        TaskType.LINEAR_REGRESSION), "global")
    vocab = np.asarray([f"user{u}" for u in range(n_users)])
    re_model = RandomEffectModel(
        random_effect_type="userId", feature_shard_id="user",
        entity_codes=np.arange(n_users),
        coefficients=rng.normal(
            size=(n_users, len(imaps["user"]))).astype(np.float32))
    records = []
    for i in range(512):
        u = int(rng.integers(0, n_users))
        records.append({
            "uid": f"r{i}", "metadataMap": {"userId": f"user{u}"},
            "globalFeatures": [{"name": f"g{j}", "term": "",
                                "value": float(rng.normal())}
                               for j in range(d_g)],
            "userFeatures": [{"name": f"u{j}", "term": "",
                              "value": float(rng.normal())}
                             for j in range(d_u)],
        })
    # the "retrained" hot-swap candidate: same structure and vocab,
    # freshly drawn coefficients
    fixed_b = FixedEffectModel(GeneralizedLinearModel(
        Coefficients(rng.normal(size=len(imaps["global"])).astype(
            np.float32)),
        TaskType.LINEAR_REGRESSION), "global")
    re_model_b = RandomEffectModel(
        random_effect_type="userId", feature_shard_id="user",
        entity_codes=np.arange(n_users),
        coefficients=rng.normal(
            size=(n_users, len(imaps["user"]))).astype(np.float32))
    row_bytes = len(imaps["user"]) * 4
    budget_mb = (n_users // 2) * row_bytes / (1 << 20)
    rows_scored = [0] * n_clients
    latencies: list[list] = [[] for _ in range(n_clients)]
    swap_window = {}
    with tempfile.TemporaryDirectory() as tmp:
        model_dir = os.path.join(tmp, "model")
        save_game_model(GameModel({"fixed": fixed, "per-user": re_model}),
                        model_dir, imaps, entity_vocabs={"userId": vocab})
        candidate_dir = os.path.join(tmp, "model_retrained")
        save_game_model(
            GameModel({"fixed": fixed_b, "per-user": re_model_b}),
            candidate_dir, imaps, entity_vocabs={"userId": vocab})
        trace = os.path.join(tmp, "trace")
        sock = os.path.join(tmp, "serve.sock")
        # the service is the one process that holds the chip here: this
        # parent has not initialized a backend (checked above)
        env = dict(os.environ)
        proc = subprocess.Popen(
            [sys.executable, "-m", "photon_ml_tpu.serve.service",
             "--game-model-input-dir", model_dir,
             "--listen", f"unix:{sock}",
             "--feature-shard-id-to-feature-section-keys-map",
             "global:globalFeatures|user:userFeatures",
             "--random-effect-id-set", "userId",
             "--max-batch-rows", "256",
             "--serve-hbm-budget-mb", f"{budget_mb:.6f}",
             # the candidate is a genuinely retrained model, so its
             # scores differ by design: open the canary's score-diff
             # gate (the probe measures the flip, not the gate)
             "--swap-canary-threshold-pct", "1e9",
             "--swap-probation-seconds", "0.5",
             "--trace-dir", trace,
             "--trace-heartbeat-seconds", "0.5"],
            env=env, cwd=_REPO_DIR, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)
        ready = proc.stdout.readline().strip()
        if "ready endpoint=" not in ready:
            proc.kill()
            raise RuntimeError(f"serve probe: no ready line: {ready!r}")
        endpoint = ready.split("endpoint=", 1)[1]

        def client_loop(ci):
            # mixed request sizes landing on a handful of pad buckets —
            # the adaptive-batching shape the service is built for
            sizes = (1, 4, 13, 64)
            crng = np.random.default_rng(100 + ci)
            with ServeClient(endpoint) as client:
                deadline = time.perf_counter() + duration_secs
                while time.perf_counter() < deadline:
                    n = int(sizes[crng.integers(0, len(sizes))])
                    lo = int(crng.integers(0, len(records) - n))
                    sent = time.perf_counter()
                    resp = client.score(records[lo:lo + n])
                    done = time.perf_counter()
                    if resp.get("kind") == "scores":
                        rows_scored[ci] += len(resp["scores"])
                        latencies[ci].append(
                            (sent, done, (done - sent) * 1000.0))

        def swap_loop():
            # the live flip, halfway through, under full client load
            time.sleep(duration_secs / 2.0)
            swap_window["start"] = time.perf_counter()
            with ServeClient(endpoint) as client:
                swap_window["result"] = client.swap(
                    candidate_dir, model_id="retrained")
            swap_window["end"] = time.perf_counter()

        threads = [threading.Thread(target=client_loop, args=(ci,))
                   for ci in range(n_clients)]
        threads.append(threading.Thread(target=swap_loop))
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        swap_result = swap_window.get("result") or {}
        assert swap_result.get("outcome") == "ok", (
            f"serve probe: the live hot-swap must complete, got "
            f"{swap_result!r}")
        # worst client-observed latency among requests IN FLIGHT or
        # admitted anywhere in the swap window: the flip's blackout
        s0, s1 = swap_window["start"], swap_window["end"]
        in_window = [ms for lat in latencies for (sent, done, ms) in lat
                     if done >= s0 and sent <= s1]
        swap_blackout_ms = max(in_window) if in_window else 0.0
        with ServeClient(endpoint) as client:
            stats = client.stats()
        assert stats.get("generation") == 2, (
            f"serve probe: post-swap stats must report generation 2, "
            f"got {stats.get('generation')!r}")
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=60)
        # per-tier hit split: the exit snapshot is the only labeled view
        # (heartbeats carry label-summed totals only)
        tier_hits: dict = {}
        shed = 0.0
        with open(os.path.join(trace, "metrics.jsonl")) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                rec = json.loads(line)
                if rec.get("kind") != "counter":
                    continue
                if rec.get("name") == "serve_tier_hits":
                    tier = rec.get("labels", {}).get("tier", "?")
                    tier_hits[tier] = tier_hits.get(tier, 0) \
                        + rec.get("value", 0)
                elif rec.get("name") == "serve_shed":
                    shed += rec.get("value", 0)
        # the flip contract under load: nothing sheds across the swap,
        # and the candidate generation reuses the boot generation's
        # compiled shapes — a warm retrace would be a latency cliff
        assert shed == 0, (
            f"serve probe: {shed:.0f} request(s) shed across the live "
            f"hot-swap — the flip must not drop load")
        retrace_spans = 0
        with open(os.path.join(trace, "spans.jsonl")) as fh:
            for line in fh:
                line = line.strip()
                if line:
                    retrace_spans += (json.loads(line).get("name")
                                      == "xla.retrace")
        assert retrace_spans == 0, (
            f"serve probe: {retrace_spans} warm retrace(s) across the "
            f"hot-swap — the candidate generation must reuse the "
            f"compiled shapes")
        # per-stage latency split of the traced run (queue_wait /
        # batch_form / tier_gather / device_score / reply)
        stage_ms = _serve_stage_split([trace])

        # tracing-overhead A/B: the SAME fixed request sequence against
        # an untraced member and one traced at the DEFAULT sample rate
        # (head sampling + exemplar reservoir armed — the
        # --trace-dir production posture). One member at a time — a
        # chip belongs to one process — so the two sides run back to
        # back, each warmed first. Min-over-3 within 2% plus a 5 ms
        # timer/scheduler-granularity floor — the PR 5 train-side
        # tracing contract applied to the serve plane, asserted HERE
        # because only the bench spawns real traced/untraced members.
        def _timed_member(name, extra):
            ab_sock = os.path.join(tmp, f"{name}.sock")
            ab = subprocess.Popen(
                [sys.executable, "-m", "photon_ml_tpu.serve.service",
                 "--game-model-input-dir", model_dir,
                 "--listen", f"unix:{ab_sock}",
                 "--feature-shard-id-to-feature-section-keys-map",
                 "global:globalFeatures|user:userFeatures",
                 "--random-effect-id-set", "userId",
                 "--max-batch-rows", "256",
                 "--serve-hbm-budget-mb", f"{budget_mb:.6f}"] + extra,
                env=env, cwd=_REPO_DIR, text=True,
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)
            try:
                line = ab.stdout.readline().strip()
                if "ready endpoint=" not in line:
                    raise RuntimeError(
                        f"serve A/B probe: no ready line: {line!r}")

                def timed_pass(client):
                    t0 = time.perf_counter()
                    for lo in range(0, 256, 16):
                        client.score(records[lo:lo + 16])
                    return time.perf_counter() - t0

                with ServeClient(line.split("endpoint=", 1)[1]) as client:
                    for _ in range(2):  # warm tiers + compiles
                        timed_pass(client)
                    return [timed_pass(client) for _ in range(3)]
            finally:
                if ab.poll() is None:
                    ab.send_signal(signal.SIGTERM)
                try:
                    ab.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    ab.kill()
                    ab.wait()

        plain_secs = _timed_member("ab_plain", [])
        traced_secs = _timed_member(
            "ab_traced", ["--trace-dir", os.path.join(tmp, "trace_ab")])
        serve_trace_overhead_pct = (
            100.0 * (min(traced_secs) - min(plain_secs))
            / min(plain_secs))
        assert min(traced_secs) <= min(plain_secs) * 1.02 + 0.005, (
            f"serve tracing overhead too high: {min(plain_secs):.4f}s "
            f"untraced vs {min(traced_secs):.4f}s traced at the "
            f"default sample rate")
    total_rows = int(sum(rows_scored))
    total_hits = sum(tier_hits.values())
    return {
        "clients": n_clients,
        "rows_scored": total_rows,
        "rows_per_sec": round(total_rows / dt, 0),
        "qps": round(float(stats.get("qps") or 0.0), 1),
        "p50_ms": round(float(stats.get("p50_ms") or 0.0), 2),
        "p99_ms": round(float(stats.get("p99_ms") or 0.0), 2),
        "device_tier_hit_rate": round(
            tier_hits.get("device", 0) / total_hits, 3) if total_hits
        else None,
        "tier_hits": {k: int(v) for k, v in sorted(tier_hits.items())},
        "shed": int(shed),
        "swap_blackout_ms": round(swap_blackout_ms, 2),
        "swap_generation": int(stats.get("generation") or 0),
        "swap_outcome": swap_result.get("outcome"),
        # request-pipeline stage split (serve_stage_ms from the traced
        # run's exit snapshot) + the traced-vs-untraced A/B (< 2%
        # asserted above on a min-over-repetitions basis)
        "stage_ms": stage_ms,
        "serve_trace_overhead_pct": round(serve_trace_overhead_pct, 2),
    }


def bench_tier_capacity(n_users=512, d_u=8) -> dict:
    """bf16 device-tier capacity delta: one model and one HBM budget (half
    the entities at f32), both ``--serve-tier-dtype`` values — bf16 halves
    row_bytes, so hot-tier capacity ~doubles (entity-count capped). Holds
    the device itself, so main() runs it after the child-spawning probes."""
    from photon_ml_tpu.game.models import RandomEffectModel
    from photon_ml_tpu.obs.metrics import MetricsRegistry
    from photon_ml_tpu.serve.tiers import TieredCoefficientStore

    rng = np.random.default_rng(17)
    budget_bytes = (n_users // 2) * (d_u + 1) * 4
    probe_model = RandomEffectModel(
        random_effect_type="userId", feature_shard_id="user",
        entity_codes=np.arange(n_users),
        coefficients=rng.normal(size=(n_users, d_u + 1)).astype(np.float32),
        entity_ids=np.asarray([f"user{u}" for u in range(n_users)]))
    tier_caps = {}
    for tier_dt in ("f32", "bf16"):
        store = TieredCoefficientStore(
            "per-user", probe_model, budget_bytes,
            device_dtype=tier_dt, registry=MetricsRegistry())
        tier_caps[tier_dt] = {"device_capacity": store.capacity,
                              "row_bytes": store.row_bytes}
        store.release()
    return {
        **tier_caps,
        "bf16_capacity_ratio": round(
            tier_caps["bf16"]["device_capacity"]
            / max(tier_caps["f32"]["device_capacity"], 1), 2),
    }


def bench_fleet(n_users=512, d_g=16, d_u=8, n_clients=8,
                duration_secs=3.0, fleet_sizes=(1,)) -> dict:
    """Aggregate capacity scaling of the entity-sharded scorer fleet:
    the same concurrent-client load against the fleet router at each
    fleet size. Every member owns a disjoint contiguous slice of the
    keyed-hash entity axis (``serve/fleet.py``), so device-tier budgets
    never overlap and AGGREGATE hot-tier capacity scales linearly with
    members. The probe pins each member's HBM budget to hold exactly
    ``n_users // max(fleet_sizes)`` entities — a lone member can keep
    only that fraction of the axis hot and thrashes, while at the
    largest fleet every member's disjoint slice fits — and records the
    aggregate ``device_tier_hit_rate`` per size as the capacity-scaling
    signal. Rows/sec ``scaling_x`` is recorded alongside with
    ``host_cores`` for context: member scoring is CPU-bound, so the
    throughput dimension can only scale when the host has at least as
    many cores as members. Recorded, not asserted.

    Every member is a process that takes the chip, and nothing assigns a
    member to a chip (tools/photon_supervise.py starts them all alike), so
    a fleet of K > 1 needs K chips AND that assignment, which does not
    exist yet: the default is the one-member fleet behind the router, and
    a larger ``fleet_sizes`` fails at the second member's start on any
    host today (ROADMAP R4). The scaling ratios are None for one size."""
    import signal
    import subprocess
    import tempfile
    import threading

    from photon_ml_tpu.game.models import (
        FixedEffectModel, GameModel, RandomEffectModel)
    from photon_ml_tpu.io.index_map import IndexMap
    from photon_ml_tpu.io.model_io import save_game_model
    from photon_ml_tpu.models.glm import (
        Coefficients, GeneralizedLinearModel)
    from photon_ml_tpu.optimize.config import TaskType
    from photon_ml_tpu.serve.protocol import ServeClient

    _require_parent_off_chip("bench_fleet")
    rng = np.random.default_rng(23)
    imaps = {
        "global": IndexMap.from_keys([f"g{j}" for j in range(d_g)],
                                     add_intercept=True),
        "user": IndexMap.from_keys([f"u{j}" for j in range(d_u)],
                                   add_intercept=True),
    }
    fixed = FixedEffectModel(GeneralizedLinearModel(
        Coefficients(rng.normal(size=len(imaps["global"])).astype(
            np.float32)),
        TaskType.LINEAR_REGRESSION), "global")
    vocab = np.asarray([f"user{u}" for u in range(n_users)])
    re_model = RandomEffectModel(
        random_effect_type="userId", feature_shard_id="user",
        entity_codes=np.arange(n_users),
        coefficients=rng.normal(
            size=(n_users, len(imaps["user"]))).astype(np.float32))
    records = []
    for i in range(512):
        u = int(rng.integers(0, n_users))
        records.append({
            "uid": f"r{i}", "metadataMap": {"userId": f"user{u}"},
            "globalFeatures": [{"name": f"g{j}", "term": "",
                                "value": float(rng.normal())}
                               for j in range(d_g)],
            "userFeatures": [{"name": f"u{j}", "term": "",
                              "value": float(rng.normal())}
                             for j in range(d_u)],
        })
    env = dict(os.environ)
    # one member's hot tier holds its fair share of the entity axis at
    # the LARGEST fleet size (plus headroom for hash-split imbalance) —
    # so a lone member must thrash while a full fleet's disjoint slices
    # all fit
    hot_entities = max(1, int(1.25 * n_users / max(fleet_sizes)))
    budget_mb = hot_entities * (d_u + 1) * 4 / float(1 << 20)

    def _spawn_ready(cmd):
        proc = subprocess.Popen(cmd, env=env, cwd=_REPO_DIR, text=True,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.DEVNULL)
        ready = proc.stdout.readline().strip()
        if "ready endpoint=" not in ready:
            proc.kill()
            raise RuntimeError(f"fleet probe: no ready line: {ready!r}")
        return proc, ready.split("endpoint=", 1)[1]

    per_size: dict[int, dict] = {}
    with tempfile.TemporaryDirectory() as tmp:
        model_dir = os.path.join(tmp, "model")
        save_game_model(GameModel({"fixed": fixed, "per-user": re_model}),
                        model_dir, imaps, entity_vocabs={"userId": vocab})
        for size in fleet_sizes:
            procs = []
            endpoints = []
            try:
                for k in range(size):
                    proc, ep = _spawn_ready(
                        [sys.executable, "-m",
                         "photon_ml_tpu.serve.service",
                         "--game-model-input-dir", model_dir,
                         "--listen",
                         f"unix:{tmp}/f{size}m{k}.sock",
                         "--feature-shard-id-to-feature-"
                         "section-keys-map",
                         "global:globalFeatures|user:userFeatures",
                         "--random-effect-id-set", "userId",
                         "--max-batch-rows", "256",
                         "--serve-hbm-budget-mb", f"{budget_mb:.6f}",
                         "--trace-dir", f"{tmp}/f{size}m{k}"])
                    procs.append(proc)
                    endpoints.append(ep)
                router, endpoint = _spawn_ready(
                    [sys.executable, "-m", "photon_ml_tpu.serve.router",
                     "--listen", f"unix:{tmp}/f{size}router.sock",
                     "--members", ",".join(endpoints),
                     "--route-id", "userId",
                     "--trace-dir", f"{tmp}/f{size}router"])
                procs.append(router)

                def member_tier_hits() -> dict:
                    agg: dict[str, float] = {}
                    for ep in endpoints:
                        with ServeClient(ep) as mc:
                            hits = mc.stats().get("tier_hits") or {}
                        for tier, v in hits.items():
                            agg[tier] = agg.get(tier, 0) + v
                    return agg

                # warm the tiers through the router (two full passes of
                # the entity axis), then difference the members'
                # tier-hit counters across the timed window so the
                # capacity signal is steady-state, not cold-start
                with ServeClient(endpoint) as client:
                    for _ in range(2):
                        for lo in range(0, len(records), 64):
                            client.score(records[lo:lo + 64])
                hits_before = member_tier_hits()
                rows_scored = [0] * n_clients

                def client_loop(ci):
                    sizes = (1, 4, 13, 64)
                    crng = np.random.default_rng(100 + ci)
                    with ServeClient(endpoint) as client:
                        deadline = time.perf_counter() + duration_secs
                        while time.perf_counter() < deadline:
                            n = int(sizes[crng.integers(0, len(sizes))])
                            lo = int(crng.integers(0,
                                                   len(records) - n))
                            resp = client.score(records[lo:lo + n])
                            if resp.get("kind") == "scores":
                                rows_scored[ci] += len(resp["scores"])

                threads = [threading.Thread(target=client_loop,
                                            args=(ci,))
                           for ci in range(n_clients)]
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                dt = time.perf_counter() - t0
                with ServeClient(endpoint) as client:
                    stats = client.stats()
                route = stats.get("route") or {}
                assert not route.get("error") and not route.get("shed"), (
                    f"fleet probe: fault-free load must not shed or "
                    f"error: {route}")
                hits_after = member_tier_hits()
                window = {t: hits_after.get(t, 0) - hits_before.get(t, 0)
                          for t in hits_after}
                total_hits = sum(window.values())
                per_size[size] = {
                    "rows_scored": int(sum(rows_scored)),
                    "rows_per_sec": round(sum(rows_scored) / dt, 0),
                    "p99_ms": round(float(stats.get("p99_ms") or 0.0),
                                    2),
                    "device_tier_hit_rate": round(
                        window.get("device", 0) / total_hits, 3)
                    if total_hits else None,
                }
            finally:
                for proc in procs:
                    if proc.poll() is None:
                        proc.send_signal(signal.SIGTERM)
                for proc in procs:
                    try:
                        proc.wait(timeout=60)
                    except subprocess.TimeoutExpired:
                        proc.kill()
                        proc.wait()
            # per-stage split across the size's members + router exit
            # snapshots (written at SIGTERM drain, so read after the
            # wait loop): member pipeline stages plus the router's
            # route.dispatch / route.member_wait attribution
            per_size[size]["stage_ms"] = _serve_stage_split(
                [f"{tmp}/f{size}m{k}" for k in range(size)]
                + [f"{tmp}/f{size}router"])
    lo, hi = min(fleet_sizes), max(fleet_sizes)
    base = per_size[lo]["rows_per_sec"] or 1.0
    return {
        "clients": n_clients,
        "host_cores": os.cpu_count(),
        "hot_tier_entities_per_member": hot_entities,
        "members": {str(s): per_size[s] for s in fleet_sizes},
        "scaling_x": (round(per_size[hi]["rows_per_sec"] / base, 2)
                      if hi > lo else None),
        "capacity_scaling_x": (
            round(per_size[hi]["device_tier_hit_rate"]
                  / max(per_size[lo]["device_tier_hit_rate"] or 1e-9,
                        1e-9), 2)
            if hi > lo
            and per_size[hi].get("device_tier_hit_rate") is not None
            and per_size[lo].get("device_tier_hit_rate") is not None
            else None),
    }


def bench_ingest(n=10_000_000, d=100_000, nnz_per_row=8,
                 n_entities=50_000) -> dict:
    """10M-row ingestion: vectorized ELL pack + random-effect block build
    (the RandomEffectDataSet.scala:169-206 shuffle analog at the 20M-row
    scale target)."""
    import scipy.sparse as sp

    _reset_peak_rss()

    from photon_ml_tpu.data.batch import ell_from_csr
    from photon_ml_tpu.game.dataset import (
        GameDataset,
        RandomEffectDataConfiguration,
        build_random_effect_dataset,
    )

    rng = np.random.default_rng(3)
    # Direct CSR construction: rows are uniform-width, so indptr is an
    # arange and no 80M-element COO sort is needed. Columns sorted per row
    # (cheap axis-1 sort) so the matrix is canonical up front.
    cols = np.sort(rng.integers(0, d, size=(n, nnz_per_row),
                                dtype=np.int32), axis=1).reshape(-1)
    vals = rng.random(n * nnz_per_row).astype(np.float32)
    indptr = np.arange(0, n * nnz_per_row + 1, nnz_per_row, dtype=np.int64)
    mat = sp.csr_matrix((vals, cols, indptr), shape=(n, d))
    mat.sum_duplicates()  # canonicalize (random cols may repeat in a row)
    y = rng.integers(0, 2, n).astype(np.float64)
    codes = rng.integers(0, n_entities, n).astype(np.int64)

    t0 = time.perf_counter()
    ell = ell_from_csr(mat, y)
    ell_secs = time.perf_counter() - t0

    data = GameDataset(responses=y, feature_shards={"s": mat})
    data.id_columns["u"] = codes
    data.id_vocabs["u"] = np.arange(n_entities)
    cfg = RandomEffectDataConfiguration(
        random_effect_type="u", feature_shard_id="s", num_partitions=1,
        num_active_data_points_upper_bound=32,
        num_features_to_keep_upper_bound=64)
    t0 = time.perf_counter()
    ds = build_random_effect_dataset(data, cfg, entity_axis_size=8)
    re_secs = time.perf_counter() - t0
    del ell
    # peak RSS since the reset above (main() runs this in a subprocess)
    return {
        "rows": n,
        "ell_pack_rows_per_sec": round(n / ell_secs, 0),
        "re_build_rows_per_sec": round(n / re_secs, 0),
        "re_block": [int(s) for s in ds.X.shape],
        "peak_rss_mb": _peak_rss_mb(),
    }


def bench_ingest_streamed(n=10_000_000, d=100_000, nnz_per_row=8,
                          n_entities=50_000, chunk=1_000_000) -> dict:
    """10M-row STREAMED ingestion: the same random-effect block build as
    ``bench_ingest`` but through ``build_random_effect_dataset_streamed``
    with memmap-backed blocks — parts are generated chunk-by-chunk and
    scattered straight into disk-backed blocks, so peak RSS is one chunk
    plus O(N) scalar columns instead of CSR + all padded blocks
    (RandomEffectDataSet.scala:169-206's streamed shuffle, single-host)."""
    import tempfile

    import scipy.sparse as sp

    from photon_ml_tpu.game.dataset import (
        RandomEffectDataConfiguration,
        build_random_effect_dataset_streamed,
    )

    def stream():
        rng = np.random.default_rng(3)
        for lo in range(0, n, chunk):
            m = min(chunk, n - lo)
            cols = np.sort(rng.integers(0, d, size=(m, nnz_per_row),
                                        dtype=np.int32), axis=1).reshape(-1)
            vals = rng.random(m * nnz_per_row).astype(np.float32)
            indptr = np.arange(0, m * nnz_per_row + 1, nnz_per_row,
                               dtype=np.int64)
            mat = sp.csr_matrix((vals, cols, indptr), shape=(m, d))
            mat.sum_duplicates()
            y = rng.integers(0, 2, m).astype(np.float64)
            codes = rng.integers(0, n_entities, m).astype(np.int64)
            yield mat, codes, y, np.zeros(m), np.ones(m)

    cfg = RandomEffectDataConfiguration(
        random_effect_type="u", feature_shard_id="s", num_partitions=1,
        num_active_data_points_upper_bound=32,
        num_features_to_keep_upper_bound=64)
    _reset_peak_rss()
    with tempfile.TemporaryDirectory() as tmp:
        t0 = time.perf_counter()
        ds = build_random_effect_dataset_streamed(
            stream, cfg, raw_dim=d, entity_axis_size=8, blocks_dir=tmp)
        re_secs = time.perf_counter() - t0
        disk_bytes = sum(
            os.path.getsize(os.path.join(tmp, f)) for f in os.listdir(tmp))
        return {
            "rows": n,
            "re_build_rows_per_sec": round(n / re_secs, 0),
            "re_blocks": [[int(s) for s in b.X.shape] for b in ds.buckets],
            "num_passive": ds.num_passive,
            "blocks_on_disk": True,
            "blocks_disk_mb": round(disk_bytes / 2**20, 1),
            "peak_rss_mb": _peak_rss_mb(),
        }


def _require_parent_off_chip(who: str) -> None:
    """One process per chip: ``who`` is about to start a child that takes
    the chip, so this process must not have initialized a JAX backend (a
    parent that has holds the chip, and the child then fails or hangs)."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    if bridge is not None and bridge.backends_are_initialized():
        raise RuntimeError(
            f"{who} starts children that need the accelerator, but this "
            f"process already holds it; run the child-spawning probes "
            f"before anything that touches JAX (see main())")


def _bench_isolated(fn_name: str, timeout: int = 900) -> dict:
    """Run a bench function in a fresh subprocess so its peak-RSS record
    reflects that bench alone. The child takes the accelerator like any
    JAX process and exits before this one goes on; a failure raises."""
    import subprocess

    _require_parent_off_chip(fn_name)
    code = f"import json, bench; print(json.dumps(bench.{fn_name}()))"
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, cwd=_REPO_DIR)
    if proc.returncode != 0:
        raise RuntimeError(
            f"isolated {fn_name} exited {proc.returncode}:\n"
            f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main():
    # Persistent XLA compile cache, shared by this process and every child
    # (JAX_COMPILATION_CACHE_DIR where set, else the in-checkout path).
    from photon_ml_tpu.utils.compile_cache import (
        enable_persistent_compile_cache,
    )

    cache_on = enable_persistent_compile_cache()
    _progress(f"persistent compile cache {'on' if cache_on else 'off'}")

    # One process per chip. Every probe below starts children that take
    # the accelerator, so they all run before this process touches JAX,
    # one child (or one service) at a time.
    _progress("ingest bench")
    ingest = _bench_isolated("bench_ingest")
    _progress("streamed ingest bench")
    ingest_streamed = _bench_isolated("bench_ingest_streamed")
    _progress("serve probe")
    serve = bench_serve()
    _progress("fleet probe")
    fleet = bench_fleet()

    # From here on this process holds the accelerator.
    import jax

    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}
    peak = _hbm_peak_gbps()
    _progress(f"device {device}, HBM peak {peak} GB/s")
    _progress("generating data")
    X, y, w = _data()
    _progress("numpy baseline")
    cpu_evals = bench_numpy(X, y, w)
    batch = _device_batch(X, y)
    _progress("pallas parity check")
    parity = check_pallas_parity(batch, w)
    _progress("value+gradient bench")
    vg = bench_value_gradient(batch, w, peak)
    _progress("value+gradient bf16 bench")
    vg_bf16 = bench_value_gradient_bf16(batch, w, peak)
    _progress("hvp bench")
    hvp = bench_hvp(batch, w, peak)
    del batch
    _progress("owlqn solve bench")
    owlqn = bench_owlqn()
    _progress("quantized-collectives A/B bench")
    psum_quant = bench_psum_quant()
    _progress("glmix end-to-end bench")
    glmix = bench_glmix()
    _progress("full-GAME bench")
    game_full = bench_game_full()
    _progress("avro ingest bench")
    avro_ingest = bench_avro_ingest()
    _progress("serve tier capacity probe")
    serve["tier_capacity"] = bench_tier_capacity()
    _progress("done")

    record = {
        "metric": "logistic_grad_evals_per_sec",
        "value": vg["evals_per_sec"],
        "unit": f"evals/s (N={N_ROWS}, D={DIM}, f32)",
        "vs_baseline": round(vg["evals_per_sec"] / cpu_evals, 2),
        "baseline_evals_per_sec": round(cpu_evals, 2),
        # no JVM exists in this environment, so the Spark-local reference
        # cannot be measured here; the comparison point is a same-host
        # NumPy proxy of the Breeze per-core inner loop (BASELINE.md)
        "baseline_kind": "same-host numpy proxy (no JVM available)",
        "device": device,
        "hbm_peak_gbps": peak,
        **parity,
        "value_gradient": vg,
        "value_gradient_bf16": vg_bf16,
        "hvp": hvp,
        "owlqn": owlqn,
        "psum_quant": psum_quant,
        "glmix": glmix,
        "game_full": game_full,
        "avro_ingest": avro_ingest,
        "serve": serve,
        "fleet": fleet,
        "ingest": ingest,
        "ingest_streamed": ingest_streamed,
    }
    print(json.dumps(record))


if __name__ == "__main__":
    main()
