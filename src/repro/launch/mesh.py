"""Production mesh construction (assignment-mandated shapes).

Defined as functions — importing this module never touches jax device
state, so library users on 1-device hosts are unaffected.
"""

from __future__ import annotations

import jax

_AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_local_mesh(axes=("data", "model")):
    """All local devices on the first axis (CPU tests / examples)."""
    n = len(jax.devices())
    shape = (n,) + (1,) * (len(axes) - 1)
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def mesh_num_devices(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
