"""Production mesh construction (dry-run target: TPU v5e pods).

A function, not a module-level constant, so importing this module never
touches jax device state.
"""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)}; "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_"
            "count=512 before importing jax")
    return jax.make_mesh(shape, axes, devices=devices[:n])


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over the first local devices. Its axes are Auto:
    arrays carry no sharding in their types, the compiler propagates
    placements through ordinary ops, and only the Pallas calls are split
    by hand (``PagedKVCache.shard``)."""
    import jax
    from jax.sharding import AxisType
    n = int(np.prod(shape))
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:n])
