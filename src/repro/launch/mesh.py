"""Production mesh construction.

A function, not a module-level constant: importing this module must never
touch jax device state (device count is frozen at first backend init, and
smoke tests / benches must see 1 CPU device while the dry-run sees 512).

Axes:
  single-pod : (16, 16)       ('data', 'model')    = 256 chips (v5e pod)
  multi-pod  : (2, 16, 16)    ('pod', 'data', 'model') = 512 chips

'pod' is the cross-pod (DCN) axis: data-parallel by default, pipeline
parallel via ``repro.launch.pipeline``. Scaling to N pods is the same mesh
with shape (N, 16, 16).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh", "make_replica_mesh",
           "batch_axes", "MESH_AXES"]

MESH_AXES = {"single": ("data", "model"), "multi": ("pod", "data", "model")}


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: operands carry their
    sharding, ``jit`` propagates it and ``with mesh:`` scopes the
    partitioner. (``make_mesh`` defaults to ``Explicit`` axes, which type
    every intermediate's sharding and reject ops such as an embedding
    gather whose output sharding is ambiguous.)"""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_replica_mesh(n_replicas: int | None = None, *,
                      axis: str = "replica") -> jax.sharding.Mesh:
    """1-D data-parallel mesh for ``CompiledModel`` replica fan-out (the
    serving tier): ``n_replicas`` devices (default: all local devices)
    along one ``axis``. PointNet++ models are small enough to replicate
    whole — only the request batch shards (``repro.launch.sharding.
    shard_batch``) — so this is the entire mesh story for serving, unlike
    the LM's (data, model) factorization."""
    n = len(jax.devices()) if n_replicas is None else int(n_replicas)
    return _auto_mesh((n,), (axis,))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh for unit tests (requires xla_force_host_platform_device_count
    set in the test's subprocess)."""
    return _auto_mesh(shape, axes)


def batch_axes(mesh: jax.sharding.Mesh):
    """The mesh axes a batch dimension shards over (pod+data when present)."""
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))
