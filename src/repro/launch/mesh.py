"""Production mesh construction.

Target hardware: TPU v5e pods, 256 chips/pod (16x16), 2 pods for the
multi-pod dry-run (512 chips).  Per-chip constants used by the roofline
analysis live in repro.roofline.analysis.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state -- smoke tests must see
1 CPU device while the dry-run (which sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import) sees 512 placeholder devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(n_devices: int | None = None, model: int = 2):
    """Small mesh over however many (forced-host) devices exist -- used by the
    multi-device integration tests (8 CPU devices)."""
    n = n_devices or len(jax.devices())
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
