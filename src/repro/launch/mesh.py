"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module touches no jax device state — required because the dry-run must set
XLA_FLAGS before the first device query, and smoke tests must see the real
single-CPU topology.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_worker_mesh", "auto_axes",
           "FSDP_AXES", "BATCH_AXES"]

# logical groupings used by launch/sharding.py
FSDP_AXES = ("pod", "data")     # parameter-sharding (FSDP/ZeRO-3) axes
BATCH_AXES = ("pod", "data")    # activation batch axes


def auto_axes(n: int) -> dict:
    """``make_mesh`` keywords for n Auto-sharded axes (jax defaults to
    Explicit, which would require sharding-in-types everywhere)."""
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **auto_axes(len(axes)))


def make_worker_mesh(n_workers: int, axis: str = "workers"):
    """1-D mesh for the coded-computing runtime (n coded workers)."""
    return jax.make_mesh((n_workers,), (axis,), **auto_axes(1))
