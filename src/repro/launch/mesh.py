"""Production mesh construction (TPU v5e target).

Functions, not module-level constants — importing this module never
touches jax device state (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any
jax import; smoke tests and benches must keep seeing 1 device).
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh


def _auto_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) > n:       # dry-run forces 512; single-pod uses 256
        import numpy as np
        return Mesh(np.asarray(devices[:n]).reshape(shape), axes)
    return _auto_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Single-device mesh for CPU smoke runs (same axis names)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_clients_mesh(n_devices: int | None = None,
                      axis: str = "clients") -> Mesh:
    """1-D ``clients`` mesh for the runtime engine's shard-mapped round
    (``fed_train --mesh clients:N``): sampled clients live one block per
    shard and aggregation is a single masked collective.  ``None`` takes
    every visible device."""
    devices = jax.devices()
    n = len(devices) if n_devices is None else n_devices
    if n < 1 or n > len(devices):
        raise ValueError(
            f"requested {n_devices} mesh devices but "
            f"{len(devices)} are visible (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N for virtual ones)")
    import numpy as np
    return Mesh(np.asarray(devices[:n]), (axis,))

