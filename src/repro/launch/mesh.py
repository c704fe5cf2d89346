"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* the first
jax initialization, and smoke tests/benches must keep seeing 1 device.

Mesh axes:
  single-pod:  (16, 16)      -> ("data", "model")          256 chips
  multi-pod:   (2, 16, 16)   -> ("pod", "data", "model")   512 chips

The axis-order convention follows TPU ICI reality: 'model' is the innermost
(fastest-varying) axis so tensor-parallel collectives ride nearest-neighbour
links; 'pod' is outermost (slowest links, data-parallel only).  Scaling to
1000+ nodes = more pods on the 'pod' axis (pure DP + compressed grad sync)
or a larger per-pod torus — the sharding rules are expressed against logical
axes and never name mesh sizes.
"""
from __future__ import annotations

import math
import warnings

import jax
from jax.sharding import AxisType, Mesh


def _take_devices(devices, n: int, shape, hint: str = ""):
    """Validate + slice the device list for an ``n``-device mesh.

    Under-provision is fatal (a mesh cannot be built).  Over-provision is
    legal but loud: the silent ``devices[:n]`` slice used to strand the
    surplus devices without a trace — a 512-device dry-run pointed at a
    (16, 16) mesh quietly computed on half the machine.
    """
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {tuple(shape)}, "
            f"have {len(devices)}{hint}")
    if len(devices) > n:
        warnings.warn(
            f"mesh {tuple(shape)} uses {n} of {len(devices)} devices; "
            f"the remaining {len(devices) - n} are idle",
            RuntimeWarning, stacklevel=3)
    return devices[:n]


def make_production_mesh(*, multi_pod: bool = False,
                         devices=None) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    if devices is None:
        devices = jax.devices()
    devices = _take_devices(
        devices, n, shape,
        hint="; the dry-run must set XLA_FLAGS="
             "--xla_force_host_platform_device_count=512 "
             "before importing jax")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(shape),
                         devices=devices)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Tiny mesh over whatever devices exist (CPU smoke tests / examples)."""
    shape = (data, model)
    devices = _take_devices(jax.devices(), data * model, shape)
    return jax.make_mesh(shape, ("data", "model"), (AxisType.Auto,) * 2,
                         devices=devices)


def mesh_axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def n_chips(mesh: Mesh) -> int:
    return int(mesh.devices.size)
