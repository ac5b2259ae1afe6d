"""Production mesh construction (a function — importing this module never
touches jax device state)."""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(shape=(1,), axes=("data",)):
    """Small mesh over however many (host) devices exist — tests only."""
    n = 1
    for s in shape:
        n *= s
    if n > len(jax.devices()):
        raise RuntimeError(f"need {n} devices, have {len(jax.devices())}")
    return _make_mesh(shape, axes)
