"""Multi-host execution: ``jax.distributed`` workers over one global mesh.

The reference's runtime is inherently multi-node (Spark executors over
YARN); the TPU-native counterpart is multi-controller JAX: every host
runs this same program, ``jax.distributed.initialize`` forms the global
device set, and the SAME mesh/shard_map code that runs single-host runs
unchanged over hosts — XLA routes the ``psum`` over ICI within a slice
and DCN across slices (SURVEY §5.8: multi-host only for data-loading and
inter-slice reductions).

This module is the ``local[4]``-of-hosts witness
(photon-test/.../SparkTestUtils.scala:55-69 analog, lifted one level):
``run_worker`` is executed by N CPU processes (each with a virtual
multi-device platform), feeds per-process LOCAL data shards into a global
array (the HDFS-partition analog: no process ever holds another's rows),
runs the explicit shard_map+psum fixed-effect fit
(parallel/distributed.run_glm_shard_map), and checks parity against a
process-local single-device solve. tests/test_multihost.py spawns the
workers; a real pod would launch the same worker per host.
"""

from __future__ import annotations

import argparse

import numpy as np

from photon_ml_tpu.obs import trace


def _distributed_initialize(coordinator: str, num_processes: int,
                            process_id: int,
                            initialization_timeout: int = 300,
                            heartbeat_timeout: int = 100) -> None:
    """``jax.distributed.initialize`` inside a ``gang.form`` span.

    Coordinator address, process count and id are always passed
    explicitly, so JAX looks nothing up (no cluster auto-detection, no
    metadata server). ``heartbeat_timeout`` bounds how long a dead gang
    member takes to surface on the survivors — the supervisor's relaunch
    loop sits idle that whole time."""
    import jax

    # gang formation AND re-formation trace here: a supervisor-relaunched
    # worker re-enters this span on its way back into the gang, so the
    # trace shows how long each (re-)join blocked on the coordinator
    with trace.span("gang.form", process=process_id,
                    num_processes=num_processes):
        jax.distributed.initialize(
            coordinator_address=coordinator, num_processes=num_processes,
            process_id=process_id,
            initialization_timeout=initialization_timeout,
            heartbeat_timeout_seconds=heartbeat_timeout)


def _synthetic(rows: int, dim: int, seed: int):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, dim)).astype(np.float32)
    w_true = rng.normal(size=dim).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(X @ w_true)))
    y = (rng.uniform(size=rows) < p).astype(np.float32)
    return X, y


def run_worker(process_id: int, num_processes: int, coordinator: str,
               rows: int = 512, dim: int = 16, seed: int = 11) -> None:
    """One multi-host worker: global-mesh shard_map fit + local parity.

    Every worker generates the same deterministic dataset but contributes
    only ITS row range to the global batch (make_array_from_callback reads
    just the addressable shards), mirroring per-host input partitions.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from photon_ml_tpu.data.batch import DenseBatch
    from photon_ml_tpu.optimize.config import (
        GLMOptimizationConfiguration,
        OptimizerType,
        RegularizationContext,
        RegularizationType,
        TaskType,
    )
    from photon_ml_tpu.optimize.problem import GLMOptimizationProblem
    from photon_ml_tpu.parallel.distributed import run_glm_shard_map
    from photon_ml_tpu.parallel.mesh import DATA_AXIS, make_mesh

    _distributed_initialize(coordinator, num_processes, process_id)
    devs = jax.devices()  # GLOBAL device list across processes
    n_local = len(jax.local_devices())
    assert len(devs) == n_local * num_processes, (len(devs), n_local)
    assert rows % len(devs) == 0, "rows must divide the global device count"
    mesh = make_mesh(num_data=len(devs), num_entity=1, devices=devs)

    X, y = _synthetic(rows, dim, seed)
    host = DenseBatch(
        X=X, labels=y,
        offsets=np.zeros(rows, np.float32),
        weights=np.ones(rows, np.float32),
    )
    sharding = NamedSharding(mesh, P(DATA_AXIS))

    def to_global(leaf):
        # the callback receives per-shard index tuples and returns only
        # the addressable (process-local) row ranges
        return jax.make_array_from_callback(
            leaf.shape, sharding, lambda idx: leaf[idx])

    gbatch = DenseBatch(
        X=to_global(host.X), labels=to_global(host.labels),
        offsets=to_global(host.offsets), weights=to_global(host.weights))

    problem = GLMOptimizationProblem(
        config=GLMOptimizationConfiguration(
            max_iterations=25, tolerance=1e-8, regularization_weight=0.5,
            optimizer_type=OptimizerType.LBFGS,
            regularization_context=RegularizationContext(
                RegularizationType.L2)),
        task=TaskType.LOGISTIC_REGRESSION)

    model, result = run_glm_shard_map(problem, gbatch, mesh)
    w = np.asarray(model.coefficients.means)
    assert np.all(np.isfinite(w))

    # Process-local single-device reference fit on the full dataset.
    local_batch = DenseBatch(
        X=jnp.asarray(X), labels=jnp.asarray(y),
        offsets=jnp.zeros(rows, jnp.float32),
        weights=jnp.ones(rows, jnp.float32))
    local_model, _ = problem.run(local_batch)
    np.testing.assert_allclose(
        w, np.asarray(local_model.coefficients.means),
        rtol=2e-4, atol=2e-4)
    print(f"PARITY_OK process={process_id} devices={len(devs)} "
          f"iters={result.iterations}", flush=True)
    jax.distributed.shutdown()


# ---------------------------------------------------------------------------
# Host-data exchange helpers (the broadcast/shuffle analog for host metadata)
# ---------------------------------------------------------------------------


def allgather_ragged(arr: np.ndarray) -> list[np.ndarray]:
    """All processes exchange a 1-D (or row-major) numeric array of
    process-dependent length; returns the per-process arrays in process
    order. Pads to the global max length and rides two device allgathers
    (jax.experimental.multihost_utils.process_allgather) — the host-side
    analog of the reference's driver↔executor metadata collects."""
    from jax.experimental import multihost_utils as mhu

    arr = np.ascontiguousarray(arr)
    n = np.asarray([arr.shape[0]], dtype=np.int64)
    ns = np.asarray(mhu.process_allgather(n)).reshape(-1)
    cap = int(ns.max()) if len(ns) else 0
    pad = np.zeros((cap,) + arr.shape[1:], arr.dtype)
    pad[: arr.shape[0]] = arr
    g = np.asarray(mhu.process_allgather(pad))
    if g.ndim == pad.ndim:  # single-process: no leading process axis added
        g = g[None]
    return [g[p, : int(ns[p])] for p in range(len(ns))]


def allgather_strings(strings: np.ndarray) -> list[np.ndarray]:
    """Exchange per-process string arrays (object/str dtype) across all
    processes. Each string is length-prefixed — a per-process int64 length
    array rides alongside the concatenated UTF-8 buffer — so ids are
    reconstructed by exact byte offsets and arbitrary content (including
    NUL bytes, which a separator-based framing would mis-split on) round-
    trips intact."""
    encoded = [str(s).encode("utf-8") for s in strings]
    lens = np.asarray([len(b) for b in encoded], dtype=np.int64)
    buf = np.frombuffer(b"".join(encoded), dtype=np.uint8)
    lens_g = allgather_ragged(lens)
    bufs_g = allgather_ragged(buf)
    out = []
    for ln, b in zip(lens_g, bufs_g):
        assert int(ln.sum()) == b.shape[0], (int(ln.sum()), b.shape[0])
        if len(ln) == 0:
            out.append(np.zeros(0, dtype=object))
            continue
        ends = np.cumsum(ln)
        raw = b.tobytes()
        out.append(np.asarray(
            [raw[e - n:e].decode("utf-8")
             for n, e in zip(ln.tolist(), ends.tolist())], dtype=object))
    return out


def allgather_csr(mat) -> list:
    """Exchange per-process CSR row blocks; returns per-process matrices
    (same column dimension) in process order."""
    import scipy.sparse as sp

    lens = np.diff(mat.indptr).astype(np.int64)
    lens_g = allgather_ragged(lens)
    idx_g = allgather_ragged(np.asarray(mat.indices, np.int64))
    dat_g = allgather_ragged(np.asarray(mat.data, np.float64))
    out = []
    for ln, ix, dv in zip(lens_g, idx_g, dat_g):
        indptr = np.concatenate([[0], np.cumsum(ln)])
        out.append(sp.csr_matrix(
            (dv, ix.astype(np.int32), indptr),
            shape=(len(ln), mat.shape[1])))
    return out


# ---------------------------------------------------------------------------
# Multi-host GAME training (fixed + random effect)
# ---------------------------------------------------------------------------

#: Pad-row entity id: never collides with data ids (allgather_strings is
#: length-prefixed, so the value itself is unconstrained); its coefficient
#: row is dropped from results.
_PAD_ENTITY = "\x01__pad__\x01"


def run_game_worker(
    process_id: int,
    num_processes: int,
    coordinator: str,
    train_paths,
    feature_shard_sections: dict,
    index_maps: dict,
    fixed_coordinate: tuple,
    random_coordinates,
    task,
    num_iterations: int = 1,
    num_buckets: int = 1,
    initialization_timeout: int = 60,
    heartbeat_timeout: int = 100,
    blocks_dir=None,
    checkpoint_dir=None,
    checkpoint_every_coordinates: int = 0,
    precision: str = "f32",
    collective_quant: str = "none",
    stop=None,
) -> dict:
    """One multi-host GAME training process: fixed + random effects CD.

    The cluster-program analog of the reference's GAME training driver
    (cli/game/training/Driver.scala:642-726 — the driver IS the cluster
    program): every host runs this same function with ITS OWN avro part
    files (``train_paths``), and the global batch exists only as a mesh-
    sharded array.

    Data movement per axis:
    - **Fixed-effect rows never leave their process.** Each process feeds
      its local (padded) row range into the global mesh via
      ``jax.make_array_from_callback``; the L-BFGS fit runs through the
      shard_map+psum backend over all hosts' devices.
    - **Scalar columns and the (narrow) random-effect shards are
      host-allgathered**, then every process builds its OWN entity slice
      of the padded blocks (per-host-sharded streamed build) and the
      blocks' entity axis is sharded over an all-devices entity mesh:
      each device solves a contiguous slice of entity lanes under the
      jitted vmapped solver (zero comm in the hot loop) — the reference's
      entity-partitioned executors (RandomEffectCoordinate.scala:104-113),
      now across hosts.

    ``fixed_coordinate`` = (coord_id, FixedEffectDataConfiguration,
    GLMOptimizationConfiguration); ``random_coordinates`` is a LIST of
    (coord_id, RandomEffectDataConfiguration,
    GLMOptimizationConfiguration, factored_or_None) updated in order each
    CD iteration — the full GAME shape (e.g. fixed + per-user + per-item)
    runs as one cluster program. ``factored`` entries are
    (re_cfg, latent_cfg, mf_cfg) tuples for factored coordinates. Returns
    a dict with the fixed coefficients, a per-coordinate map of
    per-entity RE coefficients keyed by raw entity id, and the final
    objective — identical on every process.

    With ``checkpoint_dir``, process 0 snapshots the CD state after each
    sweep (plus mid-sweep at the ``checkpoint_every_coordinates``
    cadence) and, on startup, restores the newest intact snapshot and
    BROADCASTS it to the whole gang — so a gang re-formed after a
    supervisor restart resumes training mid-run instead of restarting
    from scratch. Only process 0 ever touches the directory; the other
    hosts need no shared filesystem.

    ``stop`` (any object with ``should_stop() -> str | None``) makes the
    gang preemptable: each member polls its LOCAL flag at the gang-
    synchronous safe points (after each committed coordinate update) and
    the flags are allgathered, so one member's SIGTERM/deadline/stop-file
    stops EVERY member at the same coordinate — the collective snapshot
    fires once, then all members raise
    :class:`~photon_ml_tpu.utils.preempt.PreemptionRequested`.

    ``precision`` / ``collective_quant`` are the mixed-precision flag
    pair (cli/args.py): storage dtype for the design-matrix tiles and
    RE blocks, and the wire format of the mesh collectives. Both shape
    every member's traced collective programs (payload dtypes and
    shapes), so a mismatch would wedge the gang mid-collective — they
    ride the same formation-time signature check as the checkpoint
    cadence and fail fast with the per-process values.
    """
    import os

    import jax

    _distributed_initialize(
        coordinator, num_processes, process_id,
        initialization_timeout=initialization_timeout,
        heartbeat_timeout=heartbeat_timeout)
    # Fault-injection hooks for the committed failure-path tests: a worker
    # that dies mid-run (after joining the cluster, before any collective)
    # must surface as a bounded coordination error on the survivors, not a
    # hang — Spark's task-failure semantics analog (SURVEY §5.3). The
    # registry point ("worker.start", tagged by process id) is the general
    # switchboard (kill/delay/raise via PHOTON_FAULTS); the env hook below
    # is the legacy spelling kept for the original survivor-bound test.
    from photon_ml_tpu.utils.faults import fault_point

    fault_point("worker.start", tag=str(process_id))
    if os.environ.get("PHOTON_MH_TEST_EXIT_AFTER_INIT") == str(process_id):
        os._exit(17)
    try:
        return _game_worker_body(
            process_id, num_processes, train_paths,
            feature_shard_sections, index_maps, fixed_coordinate,
            random_coordinates, task, num_iterations, num_buckets,
            blocks_dir, checkpoint_dir, checkpoint_every_coordinates,
            precision=precision, collective_quant=collective_quant,
            stop=stop)
    finally:
        jax.distributed.shutdown()


def _game_worker_body(
        process_id, num_processes, train_paths, feature_shard_sections,
        index_maps, fixed_coordinate, random_coordinates, task,
        num_iterations, num_buckets, blocks_dir=None, checkpoint_dir=None,
        checkpoint_every_coordinates=0, precision="f32",
        collective_quant="none", stop=None):
    """Post-initialize body of :func:`run_game_worker` (imports deferred
    until the distributed backend is live)."""
    import os

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from photon_ml_tpu.data.batch import DenseBatch
    from photon_ml_tpu.game.dataset import (
        GameDataset,
        build_random_effect_dataset_streamed,
        dataset_row_stream,
    )
    from photon_ml_tpu.game.random_effect import (
        RandomEffectOptimizationProblem,
        score_random_effect,
    )
    from photon_ml_tpu.io.data_format import load_game_dataset_avro
    from photon_ml_tpu.ops.losses import get_loss
    from photon_ml_tpu.optimize.config import TASK_LOSS_NAME
    from photon_ml_tpu.optimize.problem import GLMOptimizationProblem
    from photon_ml_tpu.parallel.distributed import run_glm_shard_map
    from photon_ml_tpu.parallel.mesh import DATA_AXIS, make_mesh

    # Precision / collective-quant shape the TRACED collective programs
    # (payload dtypes, int8 q+scale shapes), so a per-host mismatch would
    # wedge the gang mid-collective with an opaque shape error — validate
    # locally BEFORE any ingestion or collective work, then gang-check
    # the codes alongside the checkpoint cadence below.
    from photon_ml_tpu.cli.args import PRECISION_CHOICES, precision_dtype
    from photon_ml_tpu.parallel.quantized_collectives import (
        QUANT_MODES,
        check_quant_mode,
    )

    if precision not in PRECISION_CHOICES:
        raise ValueError(f"unknown precision {precision!r}; "
                         f"expected one of {PRECISION_CHOICES}")
    check_quant_mode(collective_quant)
    # Host-side staging stays f32 everywhere; the storage dtype applies
    # at the device commit (to_global/to_global_ent), mirroring the
    # single-host builders' device-commit cast.
    store_dtype = np.dtype(precision_dtype(precision))

    devs = jax.devices()
    n_local = len(jax.local_devices())
    mesh = make_mesh(num_data=len(devs), num_entity=1, devices=devs)

    f_cid, f_data_cfg, f_opt_cfg = fixed_coordinate
    id_types = sorted({cfg.random_effect_type
                       for _, cfg, _, _ in random_coordinates})

    # ---- local ingestion: ONLY this process's part files -----------------
    local = load_game_dataset_avro(
        list(train_paths), feature_shard_sections, index_maps,
        id_types=id_types, response_required=True)
    n_loc = local.num_samples
    raw_ids_loc = {t: local.id_vocabs[t][local.id_columns[t]]
                   for t in id_types}

    # ---- padded canonical sample layout ----------------------------------
    # Every process pads its row range to the same L (multiple of the
    # per-process device count) so contiguous data-axis shards of [P*L]
    # rows fall entirely inside one process; pad rows carry weight 0. The
    # layout requires UNIFORM local device counts — verify instead of
    # silently computing mismatched L's and wedging the collectives.
    # -1 = checkpointing off; otherwise the cadence value. Both the flag's
    # PRESENCE and its CADENCE shape the collective schedule (snapshot
    # broadcast + per-save state resharding on every member), so either
    # mismatched across the gang would deadlock it until the heartbeat
    # bound — fail fast with the real reason instead.
    ckpt_sig = (-1 if checkpoint_dir is None
                else int(checkpoint_every_coordinates))
    prec_sig = PRECISION_CHOICES.index(precision)
    quant_sig = QUANT_MODES.index(collective_quant)
    n_all = allgather_ragged(np.asarray(
        [n_loc, n_local, ckpt_sig, prec_sig, quant_sig], np.int64))
    n_per = np.asarray([int(x[0]) for x in n_all])
    dev_per = np.asarray([int(x[1]) for x in n_all])
    if not (dev_per == n_local).all():
        raise RuntimeError(
            f"multi-host GAME needs identical per-process device counts, "
            f"got {dev_per.tolist()}")
    ckpt_per = np.asarray([int(x[2]) for x in n_all])
    if ckpt_per.min() != ckpt_per.max():
        raise RuntimeError(
            f"checkpoint config must be identical on EVERY process of "
            f"the gang (process 0 alone touches --checkpoint-dir, but "
            f"all members issue the snapshot collectives at the same "
            f"--checkpoint-every-coordinates cadence); got per-process "
            f"values {ckpt_per.tolist()} (-1 = checkpointing off)")
    for sig_col, flag, choices in ((3, "--precision", PRECISION_CHOICES),
                                   (4, "--collective-quant", QUANT_MODES)):
        per = np.asarray([int(x[sig_col]) for x in n_all])
        if per.min() != per.max():
            raise RuntimeError(
                f"{flag} must be identical on EVERY process of the gang "
                f"(it shapes the traced collective programs — payload "
                f"dtypes and quantized wire shapes — so a mismatch "
                f"deadlocks the mesh collectives); got per-process "
                f"values {[choices[v] for v in per.tolist()]}")
    L = int(-(-int(n_per.max()) // n_local) * n_local)
    n_pad_total = L * num_processes

    def pad_local(a, fill=0.0, dtype=np.float32):
        out = np.full(L, fill, dtype)
        out[:n_loc] = a
        return out

    resp_loc = pad_local(local.responses)
    off_loc = pad_local(local.offsets)
    wt_loc = pad_local(local.weights)

    # ---- allgather scalar columns + the RE shards ------------------------
    resp_g = np.concatenate(allgather_ragged(resp_loc))
    off_g = np.concatenate(allgather_ragged(off_loc))
    wt_g = np.concatenate(allgather_ragged(wt_loc))
    ids_g = {}
    for t in id_types:
        ids_loc = np.full(L, _PAD_ENTITY, dtype=object)
        ids_loc[:n_loc] = raw_ids_loc[t]
        ids_g[t] = np.concatenate(allgather_strings(ids_loc))
    import scipy.sparse as sp

    shards_g = {}
    for sname in sorted({cfg.feature_shard_id
                         for _, cfg, _, _ in random_coordinates}):
        mat_loc = local.feature_shards[sname].tocsr()
        padded = sp.vstack([
            mat_loc,
            sp.csr_matrix((L - n_loc, mat_loc.shape[1]))]).tocsr()
        shards_g[sname] = sp.vstack(allgather_csr(padded)).tocsr()

    # identical global GameDataset view for the RE coordinates on every
    # process (deterministic build → identical blocks/solves everywhere)
    gdata = GameDataset(
        responses=resp_g, feature_shards=shards_g,
        offsets=off_g.astype(np.float64), weights=wt_g.astype(np.float64))
    for t in id_types:
        gdata.encode_ids(t, ids_g[t])

    # ---- entity-axis sharding over ALL hosts' devices --------------------
    # The blocks are identical on every process (deterministic build);
    # sharding their entity axis over an all-devices entity mesh makes the
    # vmapped solve a real distributed computation — each device solves a
    # contiguous slice of entity lanes with zero comm in the hot loop,
    # the reference's entity-partitioned executors
    # (algorithm/RandomEffectCoordinate.scala:104-113). Blocks were padded
    # to a multiple of the device count (entity_axis_size above).
    from photon_ml_tpu.parallel.mesh import ENTITY_AXIS

    ent_mesh = make_mesh(num_data=1, num_entity=len(devs), devices=devs)

    def to_global_ent(local_arr):
        """Global entity-sharded array from this host's LOCAL slice.

        jax.devices() is process-major, so the entity-axis shard of this
        host's devices is exactly rows [pid*E_loc, (pid+1)*E_loc) of the
        full bucket — the range the sharded build filled; the callback is
        only ever asked for addressable (local) shards.
        """
        arr = np.asarray(local_arr)
        e_loc = arr.shape[0]
        full = (e_loc * num_processes,) + arr.shape[1:]
        lo = process_id * e_loc
        sh = NamedSharding(
            ent_mesh, P(*([ENTITY_AXIS] + [None] * (arr.ndim - 1))))

        def cb(idx):
            # a replicated/size-1 entity axis yields slice(None) — use
            # indices() so the arithmetic survives it
            start, stop, _ = idx[0].indices(full[0])
            return arr[(slice(start - lo, stop - lo),) + tuple(idx[1:])]

        return jax.make_array_from_callback(full, sh, cb)

    _replicate = jax.jit(lambda x: x,
                         out_shardings=NamedSharding(ent_mesh, P()))

    # ---- per-coordinate setup: streamed per-host-sharded block builds ----
    # Every process computes the identical global grouping/plan from the
    # O(N) scalar columns, then allocates and fills ONLY its own
    # contiguous entity slice of every bucket (entity_shard) — no host
    # ever holds another host's blocks, and keep_host_blocks means nothing
    # is committed to a single device before the global-mesh sharding
    # (RandomEffectDataSet.scala:169-206's partitioned shuffle output).
    # Factored coordinates run the latent-refit + Kronecker-fit
    # alternation on the single-block entity-sharded global arrays
    # (FactoredRandomEffectCoordinate.scala:39-257).
    import dataclasses as _dc

    from photon_ml_tpu.game.coordinate import (
        FactoredRandomEffectCoordinate,
    )

    coords = []
    for cid, r_data_cfg, r_opt_cfg, factored in random_coordinates:
        # a factored coordinate always gets a single block (one projection
        # matrix is shared across all entities); plain coordinates keep
        # the requested bucketing — mixing both kinds in one run is fine
        re_ds = build_random_effect_dataset_streamed(
            dataset_row_stream(gdata, r_data_cfg), r_data_cfg,
            raw_dim=gdata.shard_dim(r_data_cfg.feature_shard_id),
            num_buckets=1 if factored is not None else num_buckets,
            entity_axis_size=len(devs),
            blocks_dir=(None if blocks_dir is None
                        else os.path.join(blocks_dir, cid)),
            keep_host_blocks=True,
            entity_shard=(process_id, num_processes))
        for block in re_ds.buckets:
            assert (block.local_entity_offset
                    == process_id * block.X.shape[0])
            for field in ("X", "labels", "base_offsets", "weights",
                          "row_ids"):
                val = getattr(block, field)
                if field == "X":  # design tiles only; scalars stay f32
                    val = np.asarray(val, store_dtype)
                setattr(block, field, to_global_ent(val))
        if re_ds.passive_X is not None:
            # passive rows stay host-side numpy: they enter jitted
            # scoring as replicated constants next to the entity-sharded
            # coefficients
            re_ds.passive_X = np.asarray(re_ds.passive_X)
            re_ds.passive_entity = np.asarray(re_ds.passive_entity)
            re_ds.passive_row_ids = np.asarray(re_ds.passive_row_ids)
            re_ds.passive_offsets = np.asarray(re_ds.passive_offsets)
        fac_coord = None
        if factored is not None:
            fac_re_cfg, fac_latent_cfg, fac_mf_cfg = factored
            b0 = re_ds.buckets[0]
            re_ds = _dc.replace(
                re_ds, X=b0.X, labels=b0.labels,
                base_offsets=b0.base_offsets, weights=b0.weights,
                row_ids=b0.row_ids, buckets=None, _reduced_dim=None)
            fac_coord = FactoredRandomEffectCoordinate(
                dataset=re_ds,
                problem=RandomEffectOptimizationProblem(
                    config=fac_re_cfg, task=task,
                    collective_quant=collective_quant),
                latent_problem=GLMOptimizationProblem(
                    config=fac_latent_cfg, task=task,
                    collective_quant=collective_quant),
                latent_dim=fac_mf_cfg.num_factors,
                num_inner_iterations=fac_mf_cfg.max_number_iterations)
        coords.append({
            "cid": cid,
            "id_type": r_data_cfg.random_effect_type,
            "ds": re_ds,
            "prob": RandomEffectOptimizationProblem(
                config=r_opt_cfg, task=task,
                collective_quant=collective_quant),
            "fac": fac_coord,
        })

    # ---- fixed-effect global batch: local rows only ----------------------
    f_mat = local.feature_shards[f_data_cfg.feature_shard_id].tocsr()
    X_loc = np.zeros((L, f_mat.shape[1]), np.float32)
    X_loc[:n_loc] = f_mat.toarray()
    X_loc = np.asarray(X_loc, store_dtype)
    sharding = NamedSharding(mesh, P(DATA_AXIS))

    def to_global(loc, extra_dims=()):
        shape = (n_pad_total,) + extra_dims

        def cb(idx):
            sl = idx[0]
            lo = sl.start - process_id * L
            return loc[lo:lo + (sl.stop - sl.start)]

        return jax.make_array_from_callback(shape, sharding, cb)

    X_g = to_global(X_loc, (X_loc.shape[1],))
    y_g = to_global(resp_loc)
    w_g = to_global(wt_loc)
    f_problem = GLMOptimizationProblem(config=f_opt_cfg, task=task,
                                       collective_quant=collective_quant)

    def gather_global(x_global):
        """Sharded global [N_pad] vector → replicated numpy on every host."""
        from jax.experimental import multihost_utils as mhu

        shards = sorted(x_global.addressable_shards,
                        key=lambda s: s.index[0].start)
        loc_rows = np.concatenate([np.asarray(s.data) for s in shards])
        return np.asarray(mhu.process_allgather(loc_rows)).reshape(-1)

    @jax.jit
    def fixed_margins(X, w):
        return X @ w

    # ---- checkpoint/resume: process 0 owns the snapshots -----------------
    # Only process 0 reads/writes checkpoint_dir (no shared filesystem
    # needed); the restored snapshot rides a host allgather as one
    # serialized byte buffer, so a gang RE-FORMED after a supervisor
    # restart resumes from the identical mid-run state on every host.
    from photon_ml_tpu.utils.checkpoint import (
        CheckpointManager,
        dumps_state,
        loads_state,
    )
    from photon_ml_tpu.utils.faults import fault_point

    loss = get_loss(TASK_LOSS_NAME[task])
    scores_fixed = np.zeros(n_pad_total, np.float32)
    scores_re = {c["cid"]: np.zeros(n_pad_total, np.float32)
                 for c in coords}
    states = {c["cid"]: None for c in coords}
    regs = {c["cid"]: 0.0 for c in coords}
    w_fixed = None
    objective = None
    update_seq = 1 + len(coords)  # fixed + each RE coordinate, in order
    start_it, start_ci = 0, 0

    ckpt_mgr = None
    if checkpoint_dir is not None:
        snap = None
        if process_id == 0:
            ckpt_mgr = CheckpointManager(checkpoint_dir)
            try:
                snap = ckpt_mgr.restore()
            except FileNotFoundError:
                snap = None
        payload = dumps_state(snap) if snap is not None else b""
        root = allgather_ragged(np.frombuffer(payload, np.uint8))[0]
        if root.size:
            snap = loads_state(root.tobytes())
            start_it = int(snap["sweep"])
            start_ci = int(snap["coordinate_index"])
            if snap["w_fixed"] is not None:
                w_fixed = np.asarray(snap["w_fixed"])
            scores_fixed = np.asarray(snap["scores_fixed"])
            scores_re = {c["cid"]: np.asarray(snap["scores_re"][c["cid"]])
                         for c in coords}
            states = {c["cid"]: snap["re_states"][c["cid"]]
                      for c in coords}
            regs = {c["cid"]: snap["regs"][c["cid"]] for c in coords}
            objective = snap["objective"]
            if process_id == 0:
                print(f"MULTIHOST_RESUME sweep={start_it} "
                      f"coordinate={start_ci}", flush=True)

    def _host_state(v):
        """Coordinate state → replicated host numpy (None passes through;
        factored states are (latent, projection) tuples)."""
        if v is None:
            return None
        if isinstance(v, tuple):
            # photonlint: allow-W103(checkpoint path: replicated-state fetch to host numpy is the point of _host_state)
            return tuple(np.asarray(_replicate(x)) for x in v)
        # photonlint: allow-W103(checkpoint path: replicated-state fetch to host numpy is the point of _host_state)
        return np.asarray(_replicate(v))

    last_saved_step = [None]

    def save_snapshot(sweep, next_ci):
        # EVERY process runs this at the same program points: resharding
        # the entity-sharded global RE states to replicated host copies
        # (_host_state → _replicate) is a collective, so all gang members
        # must participate — only the WRITE below is process 0's alone.
        if checkpoint_dir is None:
            return
        if next_ci >= update_seq:
            sweep, next_ci = sweep + 1, 0
        step = sweep * update_seq + next_ci
        if step == last_saved_step[0]:
            return
        state = {
            "sweep": sweep,
            "coordinate_index": next_ci,
            "w_fixed": None if w_fixed is None else np.asarray(w_fixed),
            "scores_fixed": np.asarray(scores_fixed),
            "scores_re": {cid: np.asarray(s)
                          for cid, s in scores_re.items()},
            "re_states": {cid: _host_state(states[cid]) for cid in states},
            "regs": {cid: float(r) for cid, r in regs.items()},
            "objective": (None if objective is None else float(objective)),
        }
        if ckpt_mgr is not None:
            ckpt_mgr.save(step, state)
        last_saved_step[0] = step

    def maybe_save(sweep, next_ci):
        # sweep-end saves go through save_snapshot directly (after the
        # objective is computed); the cadence only covers mid-sweep points
        if (checkpoint_every_coordinates > 0 and next_ci < update_seq
                and (sweep * update_seq + next_ci)
                % checkpoint_every_coordinates == 0):
            save_snapshot(sweep, next_ci)

    def check_gang_stop(sweep, next_ci):
        # Gang-consensus preemption at a safe point (a committed update,
        # the same places the snapshot cadence fires): every member
        # allgathers its LOCAL stop flag, so one member's SIGTERM/
        # deadline/stop-file stops the WHOLE gang at the same
        # coordinate. The consensus snapshot is a collective (all
        # members reshard; process 0 writes) and dedups against the
        # cadence save that may have just fired at this step.
        if stop is None:
            return
        from photon_ml_tpu.utils.preempt import PreemptionRequested

        local = stop.should_stop()
        flags = allgather_ragged(
            np.asarray([1 if local is not None else 0], np.int32))
        if not any(int(f[0]) for f in flags):
            return
        save_snapshot(sweep, next_ci)
        if next_ci >= update_seq:
            sweep, next_ci = sweep + 1, 0
        raise PreemptionRequested(local or "gang:peer_stop",
                                  sweep, next_ci)

    # ---- coordinate descent: fixed ⇄ random effects ----------------------
    # Offsets for each coordinate = base + Σ other coordinates' scores
    # (CoordinateDescent.scala:143-151's partial-score subtraction).
    for it in range(start_it, num_iterations):
        fault_point("cd.sweep", tag=str(it))
        skip_before = start_ci if it == start_it else 0
        if skip_before <= 0:
            # fixed update (update index 0):
            # offsets = base + Σ RE scores (local slice only)
            re_sum = sum(scores_re.values())
            off_inj = off_loc + re_sum[process_id * L:(process_id + 1) * L]
            batch_g = DenseBatch(X=X_g, labels=y_g,
                                 offsets=to_global(off_inj), weights=w_g)
            model, _ = run_glm_shard_map(
                f_problem, batch_g, mesh,
                initial=None if w_fixed is None else jnp.asarray(w_fixed))
            w_fixed = np.asarray(model.coefficients.means)
            scores_fixed = gather_global(fixed_margins(X_g,
                                                       jnp.asarray(w_fixed)))
            maybe_save(it, 1)
            check_gang_stop(it, 1)

        # random-effect updates in sequence: entity-sharded distributed
        # solves (state stays a global sharded array between iterations)
        for k, c in enumerate(coords):
            ci = k + 1
            if ci < skip_before:
                continue  # mid-sweep resume: already ran before the crash
            cid = c["cid"]
            extra = scores_fixed + sum(
                s for kk, s in scores_re.items() if kk != cid)
            if c["fac"] is not None:
                states[cid], _ = c["fac"].update(states[cid],
                                                 jnp.asarray(extra))
                # photonlint: allow-W103(multi-host CD loop is host-orchestrated: one replicated score fetch per coordinate per sweep by design)
                scores_re[cid] = np.asarray(_replicate(
                    c["fac"].score(states[cid]))).astype(np.float32)
                regs[cid] = c["fac"].regularization_value(states[cid])
            else:
                offs = c["ds"].offsets_with(jnp.asarray(extra))
                states[cid], *_ = c["prob"].run(
                    c["ds"], offs, initial=states[cid])
                # photonlint: allow-W103(multi-host CD loop is host-orchestrated: one replicated score fetch per coordinate per sweep by design)
                scores_re[cid] = np.asarray(_replicate(
                    score_random_effect(c["ds"], states[cid]))).astype(
                        np.float32)
                regs[cid] = c["prob"].regularization_value(states[cid])
            maybe_save(it, ci + 1)
            check_gang_stop(it, ci + 1)

        total = scores_fixed + sum(scores_re.values()) + off_g
        li = loss.loss(jnp.asarray(total), jnp.asarray(resp_g))
        # photonlint: allow-W101(sweep-boundary objective: one scalar sync per sweep, host-orchestrated loop by design)
        objective = float(jnp.sum(jnp.asarray(wt_g) * li))
        objective += float(f_problem.regularization_value(
            jnp.asarray(w_fixed)))
        objective += sum(regs.values())
        save_snapshot(it, update_seq)  # sweep end, objective included

    # drop the pad entity from the returned RE tables
    random_effect = {}
    factored_flags = {}
    for c in coords:
        vocab = gdata.id_vocabs[c["id_type"]]
        codes = c["ds"].entity_codes
        if c["fac"] is not None:
            lat, B = states[c["cid"]]
            # publish in RAW space (latent @ projection), like
            # FactoredRandomEffectModel.to_raw
            # photonlint: allow-W103(end-of-run model publication: final replicated coefficients fetch)
            lat_host = np.asarray(_replicate(lat))
            # photonlint: allow-W103(end-of-run model publication: final replicated coefficients fetch)
            coefs_host = lat_host @ np.asarray(_replicate(B))
        else:
            # photonlint: allow-W103(end-of-run model publication: final replicated coefficients fetch)
            coefs_host = np.asarray(_replicate(states[c["cid"]]))
        random_effect[c["cid"]] = {
            str(vocab[int(code)]): coefs_host[i]
            for i, code in enumerate(codes)
            if vocab[int(code)] != _PAD_ENTITY}
        factored_flags[c["cid"]] = c["fac"] is not None
    return {
        "fixed": {f_cid: w_fixed},
        "random_effect": random_effect,
        "objective": objective,
        "num_processes": num_processes,
        "global_devices": len(devs),
        "rows_global": int(n_per.sum()),
        # witness: the RE entity axis really is sharded over every device
        "re_entity_axis_devices": int(ent_mesh.shape[ENTITY_AXIS]),
        "factored": factored_flags,
    }


# ---------------------------------------------------------------------------
# Worker supervision: relaunch crashed worker processes with bounded backoff
# ---------------------------------------------------------------------------


class SupervisorExhaustedError(RuntimeError):
    """The supervised worker kept failing past its restart budget."""

    def __init__(self, name: str, restarts: int, last_rc: int):
        super().__init__(
            f"{name}: worker failed permanently after {restarts} "
            f"restart(s) (last exit code {last_rc})")
        self.restarts = restarts
        self.last_rc = last_rc


class WorkerSupervisor:
    """Relaunch a crashed worker process with bounded exponential backoff.

    The Spark-driver analog of task retry, lifted to the process level:
    each host runs one supervisor around its worker. When any gang member
    dies, the survivors' collectives error out within the heartbeat bound
    (see TestMultihostFailurePaths), every host's supervisor relaunches
    its own worker, and the gang re-forms on the coordinator — no cross-
    host control plane is needed. Backoff is exponential with
    deterministic per-(name, attempt) jitter so a whole gang restarting
    at once doesn't hammer the coordinator in lockstep.

    ``spawn(attempt)`` must start the worker and return an object with
    ``wait() -> returncode`` (subprocess.Popen fits).
    """

    def __init__(self, spawn, max_restarts: int = 2,
                 backoff_base_seconds: float = 1.0,
                 backoff_max_seconds: float = 30.0,
                 jitter_fraction: float = 0.25,
                 name: str = "worker", log=None):
        self.spawn = spawn
        self.max_restarts = max_restarts
        self.backoff_base_seconds = backoff_base_seconds
        self.backoff_max_seconds = backoff_max_seconds
        self.jitter_fraction = jitter_fraction
        self.name = name
        self.log = log or (lambda s: None)
        self.restart_count = 0

    def backoff_seconds(self, attempt: int) -> float:
        """Exponential backoff for restart ``attempt`` (1-based) with a
        deterministic jitter derived from (name, attempt) — reproducible
        runs, de-synchronized gang members."""
        import zlib

        base = min(self.backoff_base_seconds * (2.0 ** (attempt - 1)),
                   self.backoff_max_seconds)
        seed = zlib.crc32(f"{self.name}:{attempt}".encode()) / 0xFFFFFFFF
        return base * (1.0 + self.jitter_fraction * (2.0 * seed - 1.0))

    def run(self) -> int:
        """Run the worker to successful completion; returns the number of
        restarts it took. Raises SupervisorExhaustedError once
        ``max_restarts`` relaunches have failed."""
        import time

        while True:
            attempt = self.restart_count
            proc = self.spawn(attempt)
            try:
                rc = proc.wait()
            except BaseException:
                # an interrupted/crashed supervisor must not orphan a
                # live worker (it would keep training and hold the
                # coordinator port/gang slot)
                for method in ("terminate", "kill"):
                    try:
                        getattr(proc, method, lambda: None)()
                    except OSError:
                        pass
                if hasattr(proc, "poll"):
                    proc.wait()
                raise
            if rc == 0:
                return self.restart_count
            self.restart_count += 1
            if self.restart_count > self.max_restarts:
                self.log(f"{self.name}: exit code {rc}; restart budget "
                         f"({self.max_restarts}) exhausted")
                raise SupervisorExhaustedError(
                    self.name, self.restart_count - 1, rc)
            delay = self.backoff_seconds(self.restart_count)
            self.log(f"{self.name}: exit code {rc}; restart "
                     f"{self.restart_count}/{self.max_restarts} in "
                     f"{delay:.1f}s")
            time.sleep(delay)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="photon-ml-tpu multi-host shard_map demo worker")
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--coordinator", required=True,
                    help="host:port of process 0's coordination service")
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--dim", type=int, default=16)
    args = ap.parse_args(argv)
    run_worker(args.process_id, args.num_processes, args.coordinator,
               rows=args.rows, dim=args.dim)


if __name__ == "__main__":
    main()
