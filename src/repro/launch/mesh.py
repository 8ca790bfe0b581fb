"""Production meshes.

Functions, not module constants — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import; tests see
the default single device).

Physical model (TPU v5e-256 pods):
  single pod:  16 x 16 chips -> mesh (data=16, model=16)
  two pods:    (pod=2, data=16, model=16); the ``pod`` axis crosses DCN.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math

    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices but only {len(devs)} "
            "are visible — the dry-run entry point must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import"
        )
    return jax.make_mesh(shape, axes, devices=devs[:need])


def make_host_mesh(n_devices: int | None = None, axis: str = "data"):
    """1-D mesh over the first ``n_devices`` devices (all by default).

    Raises when fewer devices exist than were asked for: a silently
    smaller mesh would run a different partitioning than requested."""
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise RuntimeError(
            f"make_host_mesh({n}) needs {n} devices but only {len(devs)} "
            f"{devs[0].platform} device(s) are visible; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            "the first jax import"
        )
    return Mesh(np.array(devs[:n]).reshape(n), (axis,))


def dp_axes(mesh) -> tuple:
    """Axes carrying data parallelism (pod x data when multi-pod)."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def flat_axes(mesh) -> tuple:
    """Every mesh axis flattened (GNN node/edge sharding)."""
    return tuple(mesh.axis_names)


def total_devices(mesh) -> int:
    import math

    return math.prod(mesh.devices.shape)
