"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16×16 = 256 chips ("data","model");
multi-pod: 2×16×16 = 512 chips ("pod","data","model").
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_smoke_mesh"]


def _auto(n: int):
    """Auto axis types: the sharding rules place arrays through
    ``with_sharding_constraint``, which Explicit axes refuse."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_smoke_mesh(*, devices=None):
    """Small mesh over whatever devices exist (tests/examples on CPU)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    model = 1
    for m in (4, 2, 1):
        if n % m == 0:
            model = m
            break
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=_auto(2))
