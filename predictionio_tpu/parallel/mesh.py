"""Device mesh construction + sharding helpers.

This module replaces the reference's entire Spark control plane — the
driver/executor topology, shuffle, and broadcast (reference: Spark
scheduler + netty transport; SURVEY.md §2d) — with the JAX SPMD model:
pick a :class:`jax.sharding.Mesh`, annotate shardings, and let XLA emit
ICI collectives. ``mesh_conf`` blocks in engine.json (the analogue of
the reference's ``sparkConf`` passthrough) resolve here.

Axis conventions used across the framework:

- ``"data"``  — batch / nnz-parallel axis (DP; ALS rating shards,
  two-tower batch shards)
- ``"model"`` — parameter-parallel axis (sharded embedding tables /
  factor matrices when they outgrow one chip's HBM)
- ``"shards"`` — item-parallel retrieval axis: the ANN serving corpus
  (PQ codes + exact-rerank vectors) partitioned item-wise across
  devices (``ann/scorer.ShardedANNScorer``, sharded ``pio
  batchpredict``); queries replicate, shortlists all-gather + merge

Single-process multi-chip and multi-host (``jax.distributed``) both
yield the same mesh; tests force 8 virtual CPU devices (conftest).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np


@dataclass
class MeshConfig:
    """Parsed ``mesh_conf``/``meshConf`` block of engine.json.

    ``{"mesh": {"data": 8}}`` → 1-D 8-way data parallel;
    ``{"mesh": {"data": 4, "model": 2}}`` → 2-D. Empty → all local
    devices on the ``data`` axis.
    """

    axes: Dict[str, int] = field(default_factory=dict)
    # clamp to fewer devices than requested instead of raising; off by
    # default — a mesh that silently shrinks hides a missing device
    # (``"allowSmaller": true`` in the meshConf block opts in)
    allow_smaller: bool = False

    @classmethod
    def from_json(cls, obj: Optional[Dict[str, Any]]) -> "MeshConfig":
        obj = obj or {}
        axes = {str(k): int(v) for k, v in (obj.get("mesh") or {}).items()}
        return cls(axes=axes, allow_smaller=bool(obj.get("allowSmaller", False)))


def make_mesh(config: Optional[MeshConfig] = None, devices: Optional[Sequence[Any]] = None):
    """Build a Mesh per config over the available devices.

    ``PIO_MESH_PLATFORM`` (e.g. ``cpu``) selects which platform's devices
    back the mesh — the CI hook that swaps the TPU slice for the virtual
    8-device CPU platform (SURVEY.md §4).
    """
    import os

    import jax
    from jax.sharding import Mesh

    if devices is None:
        platform = os.environ.get("PIO_MESH_PLATFORM") or None
        devices = jax.devices(platform) if platform else jax.devices()
    devs = list(devices)
    config = config or MeshConfig()
    axes = dict(config.axes)
    if not axes:
        axes = {"data": len(devs)}
    want = int(np.prod(list(axes.values())))
    if want > len(devs):
        if not config.allow_smaller:
            raise ValueError(f"mesh needs {want} devices, have {len(devs)}")
        # clamp the largest axis down to what's available
        biggest = max(axes, key=lambda k: axes[k])
        other = want // axes[biggest]
        axes[biggest] = max(1, len(devs) // other)
        want = int(np.prod(list(axes.values())))
    grid = np.array(devs[:want]).reshape(tuple(axes.values()))
    return Mesh(grid, tuple(axes.keys()))


def shards_mesh(shards: int, devices: Optional[Sequence[Any]] = None):
    """1-D mesh over the ``shards`` axis — the item-parallel layout of
    sharded ANN serving and sharded batchpredict. Honors
    ``PIO_MESH_PLATFORM`` like :func:`make_mesh`; raises when fewer
    than ``shards`` devices are available (an undersized retrieval
    mesh would silently change the serving corpus layout — callers
    that can degrade choose to, this helper never does)."""
    return make_mesh(MeshConfig(axes={"shards": int(shards)}), devices)


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-axes checker off (bodies that
    hold a ``pallas_call``, which has no replication rule)."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def pvary(x, axis: str):
    """Mark ``x`` varying over ``axis`` (vma typing for scan/fori carries
    inside shard_map)."""
    import jax

    return jax.lax.pcast(x, axis, to="varying")


def replicated(mesh) -> Any:
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def shard_batch(mesh, axis: str = "data") -> Any:
    """Sharding for a leading-batch-dim array."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(axis))


def pad_to_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def device_count() -> int:
    import jax

    return jax.device_count()
