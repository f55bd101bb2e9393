"""Production mesh construction.

Target: TPU v5e, 256 chips/pod. Single-pod mesh is (data=16, model=16);
multi-pod doubles along a leading "pod" axis (2 x 256 = 512 chips). The DWFL
worker axis is ``data`` (16 workers/pod) or ``("pod","data")`` (32 workers)
— each worker is one 16-chip model-parallel group.

Functions only — importing this module never touches jax device state.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto. jax.make_mesh defaults to
    Explicit axes, under which slicing a sharded flat buffer (the padded
    [:, :d] view) is a ShardingTypeError; every engine here places its
    buffers with NamedSharding and lets shard_map/XLA propagate the rest,
    which is the Auto contract."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def worker_axes(multi_pod: bool = False) -> Tuple[str, ...]:
    return ("pod", "data") if multi_pod else ("data",)


def n_workers(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("pod", 1) * sizes["data"]


def model_size(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes["model"]


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh over however many (CPU) devices exist — for tests."""
    return _make_mesh((n_data, n_model), ("data", "model"))


def make_worker_mesh(n_worker_shards: int, n_model: Optional[int] = None,
                     n_replicas: Optional[int] = None):
    """Mesh with a ``workers`` axis for row-sharding the DWFL worker
    population (repro.shard.worker — N beyond one device). Optionally
    composes with the fleet's ``replicas`` axis and/or the flat buffer's
    ``model`` column axis into the full 3-D
    ("replicas", "workers", "model") mesh; the worker-sharded step only
    communicates along ``workers``, leaving the other axes to their own
    engines. Requires the product of the sizes in devices (CPU:
    XLA_FLAGS=--xla_force_host_platform_device_count)."""
    shape, axes = [], []
    if n_replicas is not None:
        shape.append(n_replicas)
        axes.append("replicas")
    shape.append(int(n_worker_shards))
    axes.append("workers")
    if n_model is not None:
        shape.append(n_model)
        axes.append("model")
    return _make_mesh(tuple(shape), tuple(axes))


def make_shard_mesh(n_model: int, n_replicas: Optional[int] = None):
    """Mesh for the model-sharded flat-buffer round (repro.shard):
    1-axis ("model",) for a single network (n_replicas=None), 2-D
    ("replicas", "model") when the fleet's replicate axis composes with it
    — pass n_replicas=1 for a fleet whose replicates all live in one model
    group (the fleet step requires the axis to EXIST, whatever its size).
    Requires max(n_replicas, 1) · n_model devices (CPU: XLA_FLAGS=
    --xla_force_host_platform_device_count)."""
    if n_replicas is not None:
        return _make_mesh((n_replicas, n_model), ("replicas", "model"))
    return _make_mesh((n_model,), ("model",))
