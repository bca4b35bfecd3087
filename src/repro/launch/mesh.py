"""Device-mesh construction.

Defined as functions (never module-level constants) so importing this
module touches no jax device state: a caller may set XLA_FLAGS before the
first device query, and smoke tests must see the real single-CPU topology.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_worker_mesh", "auto_axes"]


def auto_axes(n: int) -> dict:
    """``make_mesh`` keywords for n Auto-sharded axes (jax defaults to
    Explicit, which would require sharding-in-types everywhere)."""
    return {"axis_types": (AxisType.Auto,) * n}


def make_worker_mesh(n_workers: int, axis: str = "workers"):
    """1-D mesh for the coded-computing runtime (n coded workers)."""
    return jax.make_mesh((n_workers,), (axis,), **auto_axes(1))
