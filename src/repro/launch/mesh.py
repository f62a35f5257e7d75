"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state. The dry-run launcher sets XLA_FLAGS host-device-count before any jax
import; everything else sees the real device count.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)}; the "
            "dry-run launcher must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_host_mesh(model: int = 1) -> Mesh:
    """Degenerate mesh over the real local devices (CPU tests/examples)."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[:data * model],
                         axis_types=(AxisType.Auto,) * 2)
