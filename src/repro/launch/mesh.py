"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
jax initialization.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """The one mesh constructor: every axis `Auto`, so the compiler
    partitions whatever the shardings leave open."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: `pod` (DCN, pure data-parallel replicas), `data` (ICI, batch +
    FSDP/ZeRO shards), `model` (ICI, tensor/expert parallel).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False, devices=None):
    """Small-device-count mesh with the same axis names (tests / CI)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if multi_pod:
        if n % 2 or n < 8:
            raise ValueError(f"multi-pod debug mesh needs an even device "
                             f"count >= 8, got {n}")
        shape = (2, n // 4, 2)
        axes = ("pod", "data", "model")
    else:
        if n % 2:
            raise ValueError(
                f"debug mesh needs an even device count, got {n}")
        shape = (n // 2, 2)
        axes = ("data", "model")
    return make_mesh(shape, axes, devices=devices)


def mesh_summary(mesh) -> dict:
    return {"axis_names": list(mesh.axis_names),
            "shape": [int(mesh.shape[a]) for a in mesh.axis_names],
            "n_devices": int(mesh.size)}
