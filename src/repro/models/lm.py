"""Unified decoder LM covering the dense / moe / ssm / hybrid / vlm families.

Layer stacking uses a **period-scan**: the layer pattern of every assigned
arch is periodic (gemma3 = 5 local + 1 global, xLSTM = 3 mLSTM + 1 sLSTM,
Zamba2 = shared-attn + 6 mamba, dense/moe = period 1), so parameters are
stacked per *slot within the period* and a single `lax.scan` walks the
periods with the period body unrolled.  This keeps the HLO small (body =
one period), avoids `lax.switch` branch duplication, wastes no parameters,
and gives each slot its *static* attention pattern (exact sub-quadratic
FLOPs for local slots).  Leftover layers (L mod period) are a small
unstacked remainder.

Paths:
* ``loss_fn``      — training forward + cross-entropy (scan over periods).
* ``prefill``      — full-sequence forward that also emits per-layer decode
                     caches (python-unrolled: cache shapes may differ per
                     layer — rotating windows vs full, SSM states).
* ``decode_step``  — single-token step over unrolled layers with explicit
                     cache I/O.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.launch.partition import constrain
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.params import ParamSpec, cast_specs

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Period layout
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Slot:
    kind: str          # attn_mlp | attn_moe | mamba | mlstm | slstm
    local: bool        # attention locality (static per slot)
    shared_attn: bool  # zamba: run the shared attention block before this slot


def period_layout(cfg: ArchConfig) -> Tuple[List[Slot], int, List[Slot]]:
    """Returns (period_slots, n_periods, remainder_slots)."""
    kinds = cfg.layer_kinds()
    nl = cfg.num_layers
    if cfg.family == "ssm" and cfg.slstm_every:
        plen = cfg.slstm_every
    elif cfg.family == "hybrid" and cfg.shared_attn_every:
        plen = cfg.shared_attn_every
    elif cfg.attn_pattern == "local_global":
        plen = cfg.local_global_ratio + 1
    else:
        plen = 1
    plen = min(plen, nl)

    def slot_for(i: int) -> Slot:
        return Slot(
            kind=kinds[i],
            local=cfg.attn_layer_is_local(i),
            shared_attn=(cfg.shared_attn_every > 0
                         and i % cfg.shared_attn_every == 0),
        )

    n_periods = nl // plen
    period = [slot_for(i) for i in range(plen)]
    remainder = [slot_for(n_periods * plen + j)
                 for j in range(nl - n_periods * plen)]
    return period, n_periods, remainder


# ---------------------------------------------------------------------------
# Per-block specs / apply
# ---------------------------------------------------------------------------

def block_specs(cfg: ArchConfig, slot: Slot) -> Dict[str, Any]:
    s: Dict[str, Any] = {"norm1": L.norm_spec(cfg)}
    if slot.kind == "attn_mlp":
        s["attn"] = L.attn_specs(cfg)
        s["norm2"] = L.norm_spec(cfg)
        s["mlp"] = L.mlp_specs(cfg)
    elif slot.kind == "attn_moe":
        s["attn"] = L.attn_specs(cfg)
        s["norm2"] = L.norm_spec(cfg)
        s["moe"] = MOE.moe_specs(cfg)
    elif slot.kind == "mamba":
        # Zamba2-style: mamba layers have no per-layer MLP; the d_ff MLP
        # belongs to the shared attention block.
        s["mamba"] = SSM.mamba_specs(cfg)
    elif slot.kind == "mlstm":
        s["mlstm"] = SSM.mlstm_specs(cfg)
    elif slot.kind == "slstm":
        s["slstm"] = SSM.slstm_specs(cfg)
    else:
        raise ValueError(slot.kind)
    return s


def block_apply(p: Params, x: jax.Array, cfg: ArchConfig, slot: Slot,
                shared_p: Optional[Params]) -> jax.Array:
    """Full-sequence (train) path for one block."""
    if slot.shared_attn and shared_p is not None:
        x = x + L.attn_apply(shared_p["attn"],
                             L.apply_norm(shared_p["norm"], x),
                             cfg, causal=True, local=False)
        if cfg.d_ff:
            x = x + L.mlp_apply(shared_p["mlp"],
                                L.apply_norm(shared_p["norm2"], x), cfg)
    h = L.apply_norm(p["norm1"], x)
    if slot.kind in ("attn_mlp", "attn_moe"):
        x = x + L.attn_apply(p["attn"], h, cfg, causal=True, local=slot.local)
        h2 = L.apply_norm(p["norm2"], x)
        if slot.kind == "attn_mlp":
            x = x + L.mlp_apply(p["mlp"], h2, cfg)
        else:
            x = x + MOE.moe_apply(p["moe"], h2, cfg)
    elif slot.kind == "mamba":
        x = x + SSM.mamba_apply(p["mamba"], h, cfg)
    elif slot.kind == "mlstm":
        x = x + SSM.mlstm_apply(p["mlstm"], h, cfg)
    elif slot.kind == "slstm":
        x = x + SSM.slstm_apply(p["slstm"], h, cfg)
    return x


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LM:
    cfg: ArchConfig

    # -- parameter specs -----------------------------------------------------
    def specs(self) -> Params:
        cfg = self.cfg
        period, n_periods, remainder = period_layout(cfg)
        out: Params = {"embed": L.embed_specs(cfg),
                       "final_norm": L.norm_spec(cfg)}

        def stack(spec_tree, n):
            return jax.tree.map(
                lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes,
                                    s.dtype, s.init, s.scale),
                spec_tree,
                is_leaf=lambda v: isinstance(v, ParamSpec))

        out["slots"] = {f"s{i}": stack(block_specs(cfg, slot), n_periods)
                        for i, slot in enumerate(period)}
        out["rem"] = {f"r{j}": block_specs(cfg, slot)
                      for j, slot in enumerate(remainder)}
        if cfg.shared_attn_every:
            out["shared_attn"] = {"norm": L.norm_spec(cfg),
                                  "attn": L.attn_specs(cfg)}
            if cfg.d_ff:
                out["shared_attn"]["norm2"] = L.norm_spec(cfg)
                out["shared_attn"]["mlp"] = L.mlp_specs(cfg)
        if cfg.frontend == "vit_stub":
            out["projector"] = {
                "w": ParamSpec((cfg.frontend_dim, cfg.d_model),
                               ("unsharded", "embed"), init="scaled_normal")}
        return cast_specs(out, jnp.dtype(cfg.dtype))

    # -- embedding of (tokens [, image embeds]) ------------------------------
    def _embed_inputs(self, params: Params, batch: Dict) -> jax.Array:
        x = L.embed_apply(params["embed"], batch["tokens"])
        if self.cfg.frontend == "vit_stub":
            img = batch["image_embeds"].astype(x.dtype) @ params["projector"]["w"]
            x = jnp.concatenate([img, x], axis=1)
        return x

    # -- training forward -----------------------------------------------------
    def forward_train(self, params: Params, batch: Dict) -> jax.Array:
        """Returns logits (B, S_total, vocab_padded), f32."""
        cfg = self.cfg
        period, n_periods, remainder = period_layout(cfg)
        x = self._embed_inputs(params, batch)
        x = constrain(x, ("batch", None, None))
        shared_p = params.get("shared_attn")

        sp_rules = {"seq_sp": "model" if cfg.seq_shard_train else None}

        def period_body(x_c, slot_params):
            for i, slot in enumerate(period):
                x_c = block_apply(slot_params[f"s{i}"], x_c, cfg, slot, shared_p)
            # scan-carry boundary: batch over (pod,data); optionally SP over
            # model so the remat-saved activations fit HBM on deep archs.
            x_c = constrain(x_c, ("batch", "seq_sp", None), sp_rules)
            return x_c, None

        body = period_body
        if cfg.remat:
            body = jax.checkpoint(period_body,
                                  prevent_cse=False)  # type: ignore[assignment]
        if n_periods > 0:
            x, _ = jax.lax.scan(body, x, params["slots"], length=n_periods)
        for j, slot in enumerate(remainder):
            x = block_apply(params["rem"][f"r{j}"], x, cfg, slot, shared_p)

        x = L.apply_norm(params["final_norm"], x)
        logits = L.head_apply(params["embed"], x, cfg).astype(jnp.float32)
        # keep logits vocab-sharded end-to-end; the loss below reduces over
        # the sharded vocab without ever all-gathering (B, S, V).
        return constrain(logits, ("batch", None, "vocab"))

    def loss_fn(self, params: Params, batch: Dict) -> jax.Array:
        """Causal LM loss on the text tokens (image prefix excluded).

        Written as logsumexp − ⟨logits, onehot⟩ so the vocab dim reduces
        locally per shard (psum epilogue) instead of gathering logits."""
        cfg = self.cfg
        logits = self.forward_train(params, batch)
        if cfg.frontend == "vit_stub":
            logits = logits[:, batch["image_embeds"].shape[1]:]
        tgt = batch["labels"][:, 1:]
        lg = logits[:, :-1]
        lse = jax.nn.logsumexp(lg, axis=-1)
        onehot = jax.nn.one_hot(tgt, lg.shape[-1], dtype=lg.dtype)
        gold = jnp.sum(lg * onehot, axis=-1)
        return (lse - gold).mean()

    # -- layer bookkeeping for the unrolled serving paths ---------------------
    def _layer_slots(self) -> List[Tuple[Slot, Any]]:
        """[(slot, param_getter(params) -> layer params)] for all L layers."""
        cfg = self.cfg
        period, n_periods, remainder = period_layout(cfg)
        plen = len(period)
        out = []
        for l in range(cfg.num_layers):
            if l < n_periods * plen:
                pi, si = divmod(l, plen)
                getter = (lambda params, pi=pi, si=si: jax.tree.map(
                    lambda a: a[pi], params["slots"][f"s{si}"]))
                out.append((period[si], getter))
            else:
                j = l - n_periods * plen
                out.append((remainder[j],
                            lambda params, j=j: params["rem"][f"r{j}"]))
        return out

    # -- caches ---------------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int, dtype=None) -> List:
        """Per-layer decode state; attention caches sized full or window."""
        cfg = self.cfg
        dtype = dtype or self.cache_dtype()
        caches: List[Any] = []
        for slot, _ in self._layer_slots():
            entry: Dict[str, Any] = {}
            if slot.shared_attn and cfg.shared_attn_every:
                entry["shared"] = self._attn_cache(batch, max_seq, False, dtype)
            if slot.kind in ("attn_mlp", "attn_moe"):
                entry["attn"] = self._attn_cache(batch, max_seq, slot.local,
                                                 dtype)
            elif slot.kind == "mamba":
                entry["mamba"] = SSM.mamba_init_state(cfg, batch)
            elif slot.kind == "mlstm":
                entry["mlstm"] = SSM.mlstm_init_state(cfg, batch)
            elif slot.kind == "slstm":
                entry["slstm"] = SSM.slstm_init_state(cfg, batch)
            caches.append(entry)
        return caches

    def cache_dtype(self):
        return jnp.dtype(self.cfg.dtype)

    def _attn_cache(self, batch: int, max_seq: int, local: bool, dtype):
        cfg = self.cfg
        t = min(cfg.sliding_window, max_seq) if local else max_seq
        shape = (batch, t, cfg.num_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    # -- decode ---------------------------------------------------------------
    def decode_step(self, params: Params, token: jax.Array, caches: List,
                    pos: jax.Array) -> Tuple[jax.Array, List]:
        """token: (B, 1) int32; pos: () int32 current absolute position.

        Returns (logits (B, vocab), updated caches).
        """
        cfg = self.cfg
        x = L.embed_apply(params["embed"], token)
        x = constrain(x, ("batch", None, None))
        shared_p = params.get("shared_attn")
        new_caches: List[Any] = []
        for (slot, getter), cache in zip(self._layer_slots(), caches):
            p = getter(params)
            x = constrain(x, ("batch", None, None))
            entry: Dict[str, Any] = {}
            if slot.shared_attn and shared_p is not None:
                y, c2 = L.attn_decode(shared_p["attn"],
                                      L.apply_norm(shared_p["norm"], x),
                                      cfg, cache["shared"], pos, local=False)
                x = x + y
                entry["shared"] = c2
                if cfg.d_ff:
                    x = x + L.mlp_apply(shared_p["mlp"],
                                        L.apply_norm(shared_p["norm2"], x),
                                        cfg)
            h = L.apply_norm(p["norm1"], x)
            if slot.kind in ("attn_mlp", "attn_moe"):
                y, c2 = L.attn_decode(p["attn"], h, cfg, cache["attn"], pos,
                                      local=slot.local)
                x = x + y
                entry["attn"] = c2
                h2 = L.apply_norm(p["norm2"], x)
                if slot.kind == "attn_mlp":
                    x = x + L.mlp_apply(p["mlp"], h2, cfg)
                else:
                    x = x + MOE.moe_apply(p["moe"], h2, cfg)
            elif slot.kind == "mamba":
                y, st = SSM.mamba_decode(p["mamba"], h, cfg, cache["mamba"])
                x = x + y
                entry["mamba"] = st
            elif slot.kind == "mlstm":
                y, st = SSM.mlstm_decode(p["mlstm"], h, cfg, cache["mlstm"])
                x = x + y
                entry["mlstm"] = st
            elif slot.kind == "slstm":
                y, st = SSM.slstm_decode(p["slstm"], h, cfg, cache["slstm"])
                x = x + y
                entry["slstm"] = st
            new_caches.append(entry)
        x = L.apply_norm(params["final_norm"], x)
        logits = L.head_apply(params["embed"], x, cfg).astype(jnp.float32)
        return logits[:, 0], new_caches

    # -- prefill ---------------------------------------------------------------
    def prefill(self, params: Params, tokens: jax.Array,
                image_embeds: Optional[jax.Array] = None,
                max_seq: Optional[int] = None) -> Tuple[jax.Array, List]:
        """Full forward emitting final-position logits + per-layer caches.

        Attention caches are written full-length (local layers keep the last
        ``window`` keys in rotating layout); SSM layers return final states.
        ``max_seq``: allocate global caches at this length (> S) so decode
        can continue appending; default = exactly S (the dry-run shape).
        """
        cfg = self.cfg
        batch = {"tokens": tokens}
        if image_embeds is not None:
            batch["image_embeds"] = image_embeds
        x = self._embed_inputs(params, batch)
        bsz, s, _ = x.shape
        x = constrain(x, ("batch", None, None))
        shared_p = params.get("shared_attn")
        caches: List[Any] = []
        for slot, getter in self._layer_slots():
            p = getter(params)
            x = constrain(x, ("batch", None, None))
            entry: Dict[str, Any] = {}
            if slot.shared_attn and shared_p is not None:
                h = L.apply_norm(shared_p["norm"], x)
                x = x + L.attn_apply(shared_p["attn"], h, cfg,
                                     causal=True, local=False)
                k, v = L.attn_prefill_kv(shared_p["attn"], h, cfg)
                if max_seq is not None and max_seq > s:
                    pad = ((0, 0), (0, max_seq - s), (0, 0), (0, 0))
                    k, v = jnp.pad(k, pad), jnp.pad(v, pad)
                entry["shared"] = {"k": k.astype(self.cache_dtype()),
                                   "v": v.astype(self.cache_dtype())}
                if cfg.d_ff:
                    x = x + L.mlp_apply(shared_p["mlp"],
                                        L.apply_norm(shared_p["norm2"], x),
                                        cfg)
            h = L.apply_norm(p["norm1"], x)
            if slot.kind in ("attn_mlp", "attn_moe"):
                x = x + L.attn_apply(p["attn"], h, cfg, causal=True,
                                     local=slot.local)
                k, v = L.attn_prefill_kv(p["attn"], h, cfg)
                if slot.local and cfg.sliding_window < s:
                    w = cfg.sliding_window
                    # rotating layout: last w keys at slots (pos % w)
                    k, v = k[:, -w:], v[:, -w:]
                    roll = (s % w)
                    k = jnp.roll(k, roll, axis=1)
                    v = jnp.roll(v, roll, axis=1)
                elif max_seq is not None and max_seq > s:
                    pad = ((0, 0), (0, max_seq - s), (0, 0), (0, 0))
                    k, v = jnp.pad(k, pad), jnp.pad(v, pad)
                entry["attn"] = {"k": k.astype(self.cache_dtype()),
                                 "v": v.astype(self.cache_dtype())}
                h2 = L.apply_norm(p["norm2"], x)
                if slot.kind == "attn_mlp":
                    x = x + L.mlp_apply(p["mlp"], h2, cfg)
                else:
                    x = x + MOE.moe_apply(p["moe"], h2, cfg)
            elif slot.kind == "mamba":
                y, st = SSM.mamba_apply(p["mamba"], h, cfg, return_state=True)
                x = x + y
                entry["mamba"] = st
            elif slot.kind == "mlstm":
                y, st = SSM.mlstm_apply(p["mlstm"], h, cfg, return_state=True)
                x = x + y
                entry["mlstm"] = st
            elif slot.kind == "slstm":
                y, st = SSM.slstm_apply(p["slstm"], h, cfg, return_state=True)
                x = x + y
                entry["slstm"] = st
            caches.append(entry)
        x = L.apply_norm(params["final_norm"], x)
        logits = L.head_apply(params["embed"], x[:, -1:], cfg)
        return logits[:, 0].astype(jnp.float32), caches
