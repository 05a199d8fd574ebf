"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): 16x16 = 256 chips per pod ('data','model'); multi-pod
adds a leading 'pod' axis -> (2,16,16) = 512 chips.  Every axis is
``AxisType.Auto``: shardings propagate as in GSPMD.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
