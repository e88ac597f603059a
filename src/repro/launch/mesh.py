"""Production mesh construction.

Functions, not module-level constants — importing this module never touches
jax device state. The dry-run sets ``XLA_FLAGS=--xla_force_host_platform_
device_count=512`` *before* importing jax; tests and benches see 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (sharding by annotation)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    The ``pod`` axis participates only in FSDP/gradient collectives (DCN-
    friendly); ``data`` is batch/FSDP; ``model`` is TP/EP/flash-decode.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — smoke tests."""
    return make_mesh((data, model), ("data", "model"))
