"""Production meshes.

``make_production_mesh`` is a *function* (not a module-level constant) so
importing this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init to obtain placeholder devices.

Topology: TPU v5e pods, 256 chips each, 16x16 (data, model) per pod;
multi-pod adds a leading "pod" axis over DCN: (2, 16, 16).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "mesh_for_devices"]


def _auto(axes: tuple[str, ...]) -> tuple:
    # jax.make_mesh defaults to Explicit axes, which reject the
    # with_sharding_constraint specs of sharding/rules.py
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def mesh_for_devices(n: int | None = None):
    """Small mesh over the actually-available devices (tests / examples):
    (data, model) with model = 1."""
    n = n or len(jax.devices())
    axes = ("data", "model")
    return jax.make_mesh((n, 1), axes, axis_types=_auto(axes))
