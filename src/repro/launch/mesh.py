"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (one TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis carries
pure data parallelism across pods (gradient all-reduce over DCI), `model`
stays intra-pod where ICI bandwidth lives.

A FUNCTION, not a module constant — importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""

from __future__ import annotations

import os

import jax
from jax.sharding import AxisType

_HOST_COUNT_FLAG = "--xla_force_host_platform_device_count"


def cpu_host_devices(count: int) -> None:
    """Give the CPU platform `count` virtual devices, so a mesh of that
    many shards exists in one process.  Acts only when the run is pinned
    to the CPU (`JAX_PLATFORMS=cpu`) and no count was set yet; must run
    before anything asks jax for its devices.  A chip's devices are never
    padded with host ones: there, `shard_devices` refuses a mesh wider
    than the chips."""
    flags = os.environ.get("XLA_FLAGS", "")
    if (os.environ.get("JAX_PLATFORMS", "") == "cpu"
            and _HOST_COUNT_FLAG not in flags):
        os.environ["XLA_FLAGS"] = f"{_HOST_COUNT_FLAG}={count} {flags}".strip()


def shard_devices(shards: int) -> list:
    """The first `shards` devices of this process, or a clear error."""
    have = jax.device_count()
    if shards > have:
        hint = (f" (on the CPU, set XLA_FLAGS={_HOST_COUNT_FLAG}={shards} "
                f"before jax starts)"
                if jax.default_backend() == "cpu" else "")
        raise ValueError(
            f"a {shards}-shard mesh needs {shards} devices; this "
            f"{jax.default_backend()} process has {have}{hint}")
    return jax.devices()[:shards]


def make_mesh(shape, axes, *, devices=None):
    """The one mesh constructor of the repo: `jax.make_mesh` with every
    axis `AxisType.Auto`.  jax's default is `Explicit`, under which the
    sharded replay's metric reshapes (`core/policy.py`
    `make_replay_from_step`) are refused as sharding-changing; the
    serving steps place their shardings themselves through shard_map, so
    they want the compiler to propagate the rest.  `devices` defaults to
    `jax.devices()` (e.g. `jax.devices()[:4]` for a four-chip mesh)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_shape_dict(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_axes(multi_pod: bool) -> tuple:
    return ("pod", "data") if multi_pod else ("data",)


def make_host_mesh():
    """1-device mesh for CPU tests."""
    return make_mesh((1, 1), ("data", "model"))
