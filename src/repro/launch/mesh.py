"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benches see the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading "pod"
    axis.  The multi-pod dry-run proves the "pod" axis shards."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(multi_pod: bool) -> tuple[str, ...]:
    return ("pod", "data") if multi_pod else ("data",)


def make_host_mesh(n_devices: int | None = None, tp: int = 1):
    """Small mesh over whatever devices exist (tests, examples)."""
    n = n_devices or len(jax.devices())
    assert n % tp == 0
    return jax.make_mesh((n // tp, tp), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
