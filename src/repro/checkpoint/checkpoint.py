"""Checkpointing with elastic restore (no orbax dependency).

Layout: one directory per step containing

* ``manifest.json``   — step, flat param/opt keys, shapes/dtypes, extras
                        (data-pipeline cursor, rng, mesh signature);
* ``<key>.npy``       — one array file per leaf (host-gathered).

Restore is **elastic**: arrays are loaded host-side and re-placed with the
*current* mesh's shardings, so a job restarted on a different topology
(e.g. 512 → 256 chips after losing a pod) resumes without any format
conversion — re-sharding happens in ``jax.device_put``.  Partial restores
(missing optimizer state after an optimizer change) fall back to fresh
init per-leaf when ``strict=False``.
"""

from __future__ import annotations

import json
import os
import shutil
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

__all__ = ["save_checkpoint", "restore_checkpoint", "latest_step",
           "cleanup_old"]

_SEP = "§"


def _flatten(tree) -> Dict[str, Any]:
    flat = jax.tree.flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = _SEP.join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        out[key] = leaf
    return out


def save_checkpoint(ckpt_dir: str, step: int, params, opt_state=None,
                    extras: Optional[Dict] = None) -> str:
    """Write params (+ opt state, + extras) for ``step``; atomic via rename."""
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    tmp = final + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    manifest = {"step": step, "extras": extras or {}, "arrays": {}}
    for prefix, tree in (("p", params), ("o", opt_state)):
        if tree is None:
            continue
        for key, leaf in _flatten(tree).items():
            arr = np.asarray(jax.device_get(leaf))
            name = f"{prefix}{_SEP}{key}"
            fn = f"{len(manifest['arrays']):06d}.npy"
            logical_dtype = str(arr.dtype)
            if arr.dtype == jax.numpy.bfloat16:
                # .npy has no bf16: store the raw bits as uint16
                arr = arr.view(np.uint16)
            np.save(os.path.join(tmp, fn), arr)
            manifest["arrays"][name] = {"file": fn, "shape": list(arr.shape),
                                        "dtype": logical_dtype}
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def latest_step(ckpt_dir: str) -> Optional[int]:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = [int(d.split("_")[1]) for d in os.listdir(ckpt_dir)
             if d.startswith("step_") and not d.endswith(".tmp")]
    return max(steps) if steps else None


def restore_checkpoint(ckpt_dir: str, params_like, opt_like=None,
                       shardings: Optional[Tuple] = None,
                       step: Optional[int] = None, strict: bool = True):
    """Restore into the structure of ``params_like``/``opt_like``.

    ``shardings``: optional (param_shardings, opt_shardings) trees — arrays
    are placed with them (elastic re-shard on the current mesh).  Returns
    (step, params, opt_state, extras).
    """
    step = latest_step(ckpt_dir) if step is None else step
    if step is None:
        raise FileNotFoundError(f"no checkpoints under {ckpt_dir}")
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)

    def load_tree(prefix, like, shard_tree):
        if like is None:
            return None
        flat_like = _flatten(like)
        flat_shard = _flatten(shard_tree) if shard_tree is not None else None
        leaves, treedef = jax.tree.flatten(like)
        keys = list(_flatten(like).keys())
        out = []
        for key, leaf in zip(keys, leaves):
            name = f"{prefix}{_SEP}{key}"
            info = manifest["arrays"].get(name)
            if info is None:
                if strict:
                    raise KeyError(f"checkpoint missing {name}")
                out.append(leaf)      # fresh value (non-strict restore)
                continue
            arr = np.load(os.path.join(d, info["file"]))
            if info["dtype"] == "bfloat16":
                arr = arr.view(jax.numpy.bfloat16)
            want_dtype = getattr(leaf, "dtype", arr.dtype)
            if arr.dtype != want_dtype:
                arr = np.asarray(jax.numpy.asarray(arr).astype(want_dtype))
            if flat_shard is not None:
                out.append(jax.device_put(arr, flat_shard[key]))
            else:
                out.append(jax.device_put(arr))
        del flat_like
        return jax.tree.unflatten(treedef, out)

    p_sh = shardings[0] if shardings else None
    o_sh = shardings[1] if shardings and opt_like is not None else None
    params = load_tree("p", params_like, p_sh)
    opt_state = load_tree("o", opt_like, o_sh)
    return step, params, opt_state, manifest["extras"]


def cleanup_old(ckpt_dir: str, keep: int = 3) -> None:
    if not os.path.isdir(ckpt_dir):
        return
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(ckpt_dir)
                   if d.startswith("step_") and not d.endswith(".tmp"))
    for s in steps[:-keep]:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:08d}"),
                      ignore_errors=True)
