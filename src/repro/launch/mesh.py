"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run (and only the dry-run) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain placeholder devices.
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


def make_debug_mesh(n_devices: int | None = None, model: int = 2):
    """Small mesh over however many (host) devices exist — used by tests."""
    n = n_devices or len(jax.devices())
    model = min(model, n)
    return _make_mesh((n // model, model), ("data", "model"))
