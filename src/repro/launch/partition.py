"""Low-level logical-axis partitioning helpers (no model imports).

Kept free of model imports so model code can use ``constrain`` without a
circular import.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
from jax.sharding import AbstractMesh, Mesh, PartitionSpec as P

__all__ = ["DEFAULT_RULES", "resolve_axes", "current_mesh", "constrain",
           "mentions"]

# logical axis -> mesh axis name(s); "__fsdp__"/"__batch__" expand to the
# present subset of ("pod", "data").
DEFAULT_RULES: Dict[str, object] = {
    "layers": None,
    "vocab": "model",
    "embed": "__fsdp__",
    "q_proj": "model",
    "kv_proj": "model",
    "heads": "model",
    "head_dim": None,
    "mlp": "model",
    "expert": "model",
    "conv": None,
    "state": None,
    "unsharded": None,
    # activation axes
    "batch": "__batch__",
    "seq": None,
    "kv_seq": None,
}


def _expand(rule, mesh: Mesh):
    if rule in ("__fsdp__", "__batch__"):
        axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        return axes if axes else None
    return rule


def resolve_axes(axes: Sequence[Optional[str]], shape: Sequence[int],
                 mesh: Mesh, rules: Optional[Dict] = None) -> P:
    """Logical axes tuple -> PartitionSpec, dropping non-divisible mappings
    and never assigning one mesh axis twice."""
    rules = {**DEFAULT_RULES, **(rules or {})}
    used: set = set()
    out = []
    for dim, ax in zip(shape, axes):
        rule = _expand(rules.get(ax), mesh) if ax is not None else None
        if rule is None:
            out.append(None)
            continue
        mesh_axes = rule if isinstance(rule, tuple) else (rule,)
        kept = []
        size = 1
        for m in mesh_axes:
            if m not in mesh.shape or m in used:
                continue
            if dim % (size * mesh.shape[m]) != 0:
                continue
            kept.append(m)
            size *= mesh.shape[m]
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
            used.add(kept[0])
        else:
            out.append(tuple(kept))
            used.update(kept)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def mentions(spec: P, axis: str) -> bool:
    for e in spec:
        if e == axis or (isinstance(e, tuple) and axis in e):
            return True
    return False


def current_mesh() -> Optional[AbstractMesh]:
    """The ambient ``jax.set_mesh`` mesh, or None (e.g. CPU smoke tests).

    Abstract, so it is readable inside ``jit`` as well as outside.
    """
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def constrain(x, axes: Sequence[Optional[str]], rules: Optional[Dict] = None):
    """with_sharding_constraint by logical axes; identity when no mesh.

    Models call this at scan-carry boundaries (activation sequence
    sharding) and on logits (vocab sharding) — the constraints silently
    drop wherever dims don't divide, so the same model code runs on one
    CPU and on the 512-chip mesh.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = resolve_axes(axes, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, spec)
