"""Production meshes.

Single pod: 16 x 16 = 256 chips (TPU v5e pod), axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — the `pod`
axis carries only data parallelism + ZeRO sharding (cross-pod traffic is
gradient reduce-scatter/all-gather only, which tolerates the slower
inter-pod links).

Defined as functions, not module constants, so importing this module never
touches jax device state (dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(4, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    return _make_mesh(shape, axes)
