"""Device mesh + sharding policy: the distributed runtime.

TPU-native replacement for the reference's Spark runtime layer
(reference: Spark 1.6 RDD/Broadcast/treeAggregate; photon-ml's wrappers
RDDLike.scala:30-60, BroadcastLike.scala:25, SparkContextConfiguration.scala:
39-110, and the treeAggregate-depth policy cli/game/training/Driver.scala:
357-363). The mapping (SURVEY §5.8):

- ``treeAggregate(depth)``  ->  XLA all-reduce over the mesh ``data`` axis,
  inserted automatically by GSPMD when a reduction crosses sharded rows.
  The depth-1-vs-2 knob disappears: ICI all-reduce is already tree/ring.
- ``Broadcast[coefficients]`` -> coefficients replicated in HBM; no per-
  iteration host broadcast, no persist/unpersist choreography.
- entity-partitioned RDDs -> arrays sharded over the ``entity`` axis.

One mesh with two logical axes covers the framework:
- ``data``:   shards example rows (fixed-effect aggregation axis)
- ``entity``: shards per-entity blocks (random-effect axis)

On a single chip both axes have size 1 and every sharding below is a no-op;
the same code compiles unchanged for a v5e-16 slice.
"""

from __future__ import annotations

import logging
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.data.batch import (
    DenseBatch,
    EllBatch,
    deal_rows,
    row_partition_specs,
)

DATA_AXIS = "data"
ENTITY_AXIS = "entity"

# Process-wide default mesh: the drivers' distribution context. When set
# with a >1 data axis, GLMOptimizationProblem.run routes fixed-effect
# solves through the explicit shard_map backend so per-shard shapes stay
# local and the fused Pallas kernel engages on every chip (it has no GSPMD
# partitioning rule, so the GSPMD path would disable it on >1 device —
# ops/pallas_kernels.pallas_supported).
_default_mesh: Optional[Mesh] = None


def set_default_mesh(mesh: Optional[Mesh]) -> None:
    global _default_mesh
    _default_mesh = mesh


def get_default_mesh() -> Optional[Mesh]:
    return _default_mesh


def largest_entity_divisor(num_devices: int, requested: int) -> int:
    """Largest divisor of ``num_devices`` that is <= ``requested``.

    The mesh must factor as data x entity over all devices, so an entity
    axis that doesn't divide the device count can't be honored exactly;
    this is the deterministic fallback (always >= 1)."""
    k = max(1, min(int(requested), int(num_devices)))
    while num_devices % k != 0:
        k -= 1
    return k


def setup_default_mesh(num_entity: int = 1) -> Optional[Mesh]:
    """Driver bootstrap: build an all-devices (data x entity) mesh and make
    it the process default. Single-device processes get no mesh (every
    sharding is a no-op there).

    A requested ``num_entity`` that doesn't evenly divide the device count
    falls back to the largest divisor that does (with a logged warning)
    instead of failing the run — the driver's ``--re-entity-shards auto``
    contract."""
    n = len(jax.devices())
    if n <= 1:
        set_default_mesh(None)
        return None
    granted = largest_entity_divisor(n, num_entity)
    if granted != num_entity:
        logging.getLogger(__name__).warning(
            "entity axis %d does not divide %d devices; falling back to "
            "%d entity shards", num_entity, n, granted)
    mesh = make_mesh(num_entity=granted)
    set_default_mesh(mesh)
    return mesh


def make_mesh(
    num_data: Optional[int] = None,
    num_entity: int = 1,
    devices: Optional[list] = None,
) -> Mesh:
    """Build a (data x entity) mesh over the available devices.

    Defaults to all devices on the data axis — the right layout for
    fixed-effect-dominated workloads; GAME drivers pass ``num_entity`` to
    split the mesh (e.g. 4x2 on 8 chips).
    """
    devs = np.asarray(devices if devices is not None else jax.devices())
    n = devs.size
    if num_data is None:
        num_data = n // num_entity
    if num_data * num_entity != n:
        raise ValueError(
            f"mesh {num_data}x{num_entity} != {n} available devices")
    return Mesh(devs.reshape(num_data, num_entity), (DATA_AXIS, ENTITY_AXIS))


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Rows sharded over the data axis (1-D arrays and leading dim of 2-D)."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(batch, mesh: Mesh):
    """Place a batch with rows sharded over the mesh data axis.

    Rows must be a multiple of the data-axis size — callers pad with
    zero-weight rows first (data/batch.pad_batch), the moral equivalent of
    the reference's partition balancing. An ELL layout of several blocks
    of slots is dealt into one run of rows a shard (data/batch.deal_rows).
    """
    n_shards = mesh.shape[DATA_AXIS]
    rows = batch.labels.shape[0]
    if rows % n_shards != 0:
        raise ValueError(
            f"batch rows {rows} not divisible by data axis {n_shards}; "
            "pad with zero-weight rows first")
    if not isinstance(batch, (DenseBatch, EllBatch)):
        raise TypeError(f"unknown batch type {type(batch)}")
    batch = deal_rows(batch, n_shards)
    return jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        batch, row_partition_specs(batch, DATA_AXIS))


def pad_rows_to_multiple(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


import functools


@functools.lru_cache(maxsize=32)
def _replicator(mesh: Mesh):
    return jax.jit(lambda a: a, out_shardings=NamedSharding(mesh, P()))


def ensure_addressable(x):
    """Make a device array fully addressable from this process (replicating
    NON-fully-addressable global arrays over their own mesh) WITHOUT
    fetching it to host. Callers that batch several arrays into one
    ``jax.device_get`` (the lazy trackers' single-fetch materialization)
    route each through here first so the same code runs single-chip,
    multi-chip, and multi-host. The replicating jit is cached per mesh so
    repeated calls don't re-trace."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        x = _replicator(x.sharding.mesh)(x)
    return x


def host_array(x) -> np.ndarray:
    """``np.asarray`` that also handles NON-fully-addressable global
    arrays (multi-controller runs) via :func:`ensure_addressable`. The
    host-side trackers (per-entity iteration/convergence counts) use this
    so the same coordinate code runs single-chip, multi-chip, and
    multi-host."""
    return np.asarray(ensure_addressable(x))
