"""Production meshes.

Single pod: 16x16 = 256 chips (TPU v5e pod), axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model) — 'pod' is the
cross-pod (DCN-connected) axis; it carries either outer data parallelism
(default) or pipeline stages (PP mode).

Serving meshes use the same two ICI axes with serving semantics
(:mod:`repro.launch.serve_shardings` owns the rule table):

* ``model`` — tensor parallelism for the decode step: attention/MLP/vocab
  weights shard Megatron-style and the paged K/V block pools shard on the
  kv-head axis, so each chip holds ``1/tp`` of the KV memory and walks only
  its local pool slice. Page tables, positions and lengths replicate (they
  are tiny int32 metadata the host scheduler mutates every step).
* ``data`` — replica parallelism across engine instances; a single engine
  runs with ``data = 1`` (continuous batching fills the batch axis, there
  is nothing to split).

Every mesh is built with ``Auto`` axis types: the models place
activations with ``with_sharding_constraint`` and leave the rest to the
partitioner, which jax's default ``Explicit`` axes reject.

Functions, not module constants — importing this module never touches jax
device state (smoke tests must keep seeing 1 device).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count"
            " before importing jax")
    return _auto_mesh(shape, axes, devices[:n])


def make_host_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Small helper meshes for tests/benchmarks (e.g. (8,) 'data')."""
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before "
            "importing jax (a silent [:n] slice would build a mesh of the "
            "wrong size)")
    return _auto_mesh(shape, axes, devices[:n])


def make_serving_mesh(tp: int, *, data: int = 1):
    """(data, model) mesh for one tensor-parallel serving engine.

    ``tp`` chips shard the decode step and the paged KV pools; ``data``
    defaults to 1 — a serving engine is one replica, continuous batching
    (not the mesh) fills its batch axis.
    """
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    return make_host_mesh((data, tp), ("data", "model"))


def make_replica_meshes(replicas: int, tp: int = 1):
    """Carve the device set into ``replicas`` disjoint (1, tp) serving
    meshes — the realized form of the ``data`` axis for multi-replica
    serving.

    A single engine's mesh always has ``data = 1`` (continuous batching
    fills its batch axis); *replica* parallelism is R independent engines
    on disjoint device slices, each with its own params copy, KV pool and
    scheduler, fronted by :class:`repro.serving.router.Router`. Device
    ``r*tp .. (r+1)*tp - 1`` belongs to replica ``r`` — contiguous slices
    so each replica's tp shards stay ICI-adjacent on real hardware.
    Raises (never silently overlaps) when ``replicas * tp`` exceeds the
    device count.
    """
    if replicas < 1:
        raise ValueError(f"replicas must be >= 1, got {replicas}")
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    n = replicas * tp
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"{replicas} replicas x tp={tp} need {n} devices, have "
            f"{len(devices)} — set XLA_FLAGS="
            "--xla_force_host_platform_device_count before importing jax, "
            "or lower --replicas/--tp")
    return [
        _auto_mesh((1, tp), ("data", "model"), devices[r * tp:(r + 1) * tp])
        for r in range(replicas)
    ]
