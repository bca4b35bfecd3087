"""Parameter-spec system: shapes + logical axes first, arrays later.

Models declare their parameters as a nested dict of :class:`ParamSpec`
(shape, dtype, logical axes, initializer).  From the spec tree we derive:

* ``abstract(specs)``   — ShapeDtypeStructs for allocation-free dry-runs;
* ``initialize(specs)`` — real arrays for smoke tests / training;
* ``logical_axes(specs)`` — the axes tree, mapped to mesh axes by a rules
  table (t5x-style; :data:`repro.launch.partition.DEFAULT_RULES`).

Logical axis vocabulary:
``layers, vocab, embed, q_proj, kv_proj, heads, head_dim, mlp, expert,
conv, state, unsharded``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ParamSpec", "abstract", "initialize", "logical_axes",
           "param_count", "tree_bytes"]

Initializer = str  # "normal" | "zeros" | "ones" | "scaled_normal"


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.bfloat16
    init: Initializer = "normal"
    scale: float = 0.02

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes} mismatch")


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def abstract(specs) -> Any:
    """Spec tree -> ShapeDtypeStruct tree (zero allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs,
        is_leaf=_is_spec)


def logical_axes(specs) -> Any:
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=_is_spec)


def _init_one(spec: ParamSpec, key: jax.Array) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "normal":
        return (jax.random.normal(key, spec.shape, jnp.float32)
                * spec.scale).astype(spec.dtype)
    if spec.init == "scaled_normal":
        # variance-scaled by fan-in (last-but-one dim if 2D+)
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        s = 1.0 / np.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, spec.shape, jnp.float32) * s
                ).astype(spec.dtype)
    raise ValueError(f"unknown init {spec.init!r}")


def initialize(specs, key: jax.Array) -> Any:
    """Spec tree -> real param arrays (for smoke tests and actual training)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    arrays = [_init_one(s, k) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, arrays)


def cast_specs(specs, dtype) -> Any:
    """Replace the default (bfloat16) param dtype throughout a spec tree.

    Norm/gate params declared explicitly float32 stay float32 (mixed
    precision); only the bf16 defaults are re-targeted.
    """
    def _cast(s: ParamSpec) -> ParamSpec:
        if s.dtype == jnp.bfloat16:
            return dataclasses.replace(s, dtype=dtype)
        return s
    return jax.tree.map(_cast, specs, is_leaf=_is_spec)


def param_count(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=_is_spec)
    return int(sum(int(np.prod(s.shape)) for s in leaves))


def tree_bytes(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=_is_spec)
    return int(sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
                   for s in leaves))
