"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — jax locks the device count on first init,
and only the dry-run entrypoint forces 512 host devices.

Axes are ``Auto``: the models place activations with sharding constraints
and leave the rest (e.g. the embedding gather) to GSPMD, which ``Explicit``
axes — ``jax.make_mesh``'s default — refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(n_devices: int | None = None, model: int = 2):
    """Small mesh over the first ``n_devices`` devices (default: all)."""
    devices = jax.devices()[:n_devices] if n_devices else jax.devices()
    n = len(devices)
    assert n % model == 0, (n, model)
    return _mesh((n // model, model), ("data", "model"), devices)
