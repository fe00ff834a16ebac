"""Decoder-only LM stack covering dense / MoE / hybrid / SSM families.

Layers are rolled into ``lax.scan`` over *periods* (the lcm of the structural
interleave periods): a dense arch scans L one-block periods, jamba scans 4
eight-block periods (7 mamba + 1 attn, MoE on odd slots). Each period-slot's
parameters are stacked along a leading axis and consumed as scan xs, keeping
HLO size flat across 24..64-layer architectures.

Entry points: ``forward`` (train / full-sequence), ``prefill`` (build a ring
KV cache + last-token logits), ``decode_step`` (one token against the cache).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import kvcache as kvcache_lib
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.config import ModelConfig
from repro.sharding.logical import logical_constraint


# --------------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------------- #

def _init_slot(key, cfg: ModelConfig, slot, dtype):
    keys = jax.random.split(key, 4)
    p = {"norm1": L.init_norm(cfg.d_model, cfg.norm_type, dtype)}
    if slot.mixer == "attn":
        p["attn"] = L.init_attention(keys[0], cfg, dtype)
    else:
        p["mamba"] = ssm_lib.init_mamba(keys[1], cfg, dtype)
    if slot.ffn is not None:
        p["norm2"] = L.init_norm(cfg.d_model, cfg.norm_type, dtype)
        if slot.ffn == "moe":
            p["moe"] = moe_lib.init_moe(keys[2], cfg, dtype)
        else:
            p["mlp"] = L.init_mlp(keys[3], cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    return p


def _slot_axes(cfg: ModelConfig, slot):
    a = {"norm1": dict(L.NORM_AXES) if cfg.norm_type == "layernorm"
         else {"scale": (None,)}}
    if slot.mixer == "attn":
        a["attn"] = dict(L.ATTN_AXES)
    else:
        a["mamba"] = ssm_lib.mamba_axes(cfg)
    if slot.ffn is not None:
        a["norm2"] = dict(a["norm1"])
        if slot.ffn == "moe":
            a["moe"] = dict(moe_lib.MOE_AXES)
        else:
            a["mlp"] = L.mlp_axes(cfg.mlp_type)
    return a


def init_params(key, cfg: ModelConfig):
    """Parameter pytree; per-slot params stacked along a leading periods axis."""
    dtype = jnp.dtype(cfg.param_dtype)
    pattern = cfg.block_pattern()
    n = cfg.num_periods()
    k_embed, k_head, k_final, k_blocks = jax.random.split(key, 4)

    def stacked_slot(slot_key, slot):
        keys = jax.random.split(slot_key, n)
        per = [_init_slot(k, cfg, slot, dtype) for k in keys]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per)

    slot_keys = jax.random.split(k_blocks, len(pattern))
    params = {
        "embed": L.init_embedding(k_embed, cfg.vocab_size, cfg.d_model, dtype),
        "slots": {f"slot{i}": stacked_slot(sk, s)
                  for i, (sk, s) in enumerate(zip(slot_keys, pattern))},
        "final_norm": L.init_norm(cfg.d_model, cfg.norm_type, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_embedding(k_head, cfg.vocab_size, cfg.d_model, dtype)
    return params


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct pytree (no allocation) — used by the dry-run."""
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def param_axes(cfg: ModelConfig):
    pattern = cfg.block_pattern()

    def add_layer_dim(axes_dict):
        return jax.tree.map(
            lambda t: ("layers",) + t, axes_dict,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))

    axes = {
        "embed": dict(L.EMBED_AXES),
        "slots": {f"slot{i}": add_layer_dim(_slot_axes(cfg, s))
                  for i, s in enumerate(pattern)},
        "final_norm": {"scale": (None,)} if cfg.norm_type == "rmsnorm"
        else dict(L.NORM_AXES),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = dict(L.EMBED_AXES)
    return axes


# --------------------------------------------------------------------------- #
# block application
# --------------------------------------------------------------------------- #

def _apply_slot(slot_params, x, cfg: ModelConfig, slot, positions, cdtype,
                cache=None, pos=None):
    """One layer: pre-norm mixer + residual, then pre-norm FFN + residual.
    Returns (x, new_cache, aux). Each part runs under a ``jax.named_scope``
    named for its kind (``attention``, ``mamba``, ``mlp``, ``moe``), which
    the ops' metadata in HLO and in device traces carries."""
    with jax.named_scope("attention" if slot.mixer == "attn" else "mamba"):
        h = L.apply_norm(x, slot_params["norm1"], cfg.norm_type, cfg.norm_eps)
        if slot.mixer == "attn":
            kv = None if cache is None else (cache["k"], cache["v"])
            out, new_kv = L.attention_block(
                slot_params["attn"], h, cfg, positions, cache=kv, pos=pos,
                compute_dtype=cdtype)
            if cache is not None:
                new_cache = {"k": new_kv[0], "v": new_kv[1]}
            else:
                new_cache = new_kv  # (k, v) of this segment (prefill harvests it)
        else:
            state = cache if (cache is not None and "ssm" in cache) else None
            out, new_cache = ssm_lib.mamba_forward(
                slot_params["mamba"], h, cfg, cdtype, state=state)
    x = x + out
    aux = jnp.zeros((), jnp.float32)
    if slot.ffn is not None:
        with jax.named_scope(slot.ffn):
            h2 = L.apply_norm(x, slot_params["norm2"], cfg.norm_type,
                              cfg.norm_eps)
            if slot.ffn == "moe":
                out2, aux, _ = moe_lib.moe_block(slot_params["moe"], h2, cfg,
                                                 cdtype)
            else:
                out2 = L.mlp_block(slot_params["mlp"], h2, cfg.mlp_type,
                                   cdtype)
        x = x + out2
    return x, new_cache, aux


def _default_positions(cfg: ModelConfig, batch, seq, offset=0):
    pos = offset + jnp.arange(seq)[None, :]
    pos = jnp.broadcast_to(pos, (batch, seq))
    if cfg.mrope_sections:
        return jnp.broadcast_to(pos[None], (3, batch, seq))
    return pos


# --------------------------------------------------------------------------- #
# forward (train / scoring)
# --------------------------------------------------------------------------- #

def forward(params, tokens, cfg: ModelConfig, positions=None,
            input_embeds=None, mode: str = "train"):
    """Full-sequence forward. Returns (logits [B,S,V], aux_loss)."""
    cdtype = jnp.dtype(cfg.compute_dtype)
    if input_embeds is not None:
        x = input_embeds.astype(cdtype)
        b, s = x.shape[:2]
    else:
        b, s = tokens.shape
        x = L.embed(params["embed"], tokens, cdtype)
    if positions is None:
        positions = _default_positions(cfg, b, s)
    pattern = cfg.block_pattern()

    def period_body(carry, slot_params):
        x, aux = carry
        for i, slot in enumerate(pattern):
            x, _, a = _apply_slot(slot_params[f"slot{i}"], x, cfg, slot,
                                  positions, cdtype)
            aux = aux + a
        return (x, aux), None

    body = period_body
    if cfg.remat and mode == "train":
        body = jax.checkpoint(period_body, prevent_cse=False)
    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["slots"])
    else:
        aux = jnp.zeros((), jnp.float32)
        for p in range(cfg.num_periods()):
            sliced = jax.tree.map(lambda a: a[p], params["slots"])
            (x, aux), _ = body((x, aux), sliced)

    x = L.apply_norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = L.unembed(head, x, cfg.logical_vocab_size, cdtype)
    return logits, aux


# --------------------------------------------------------------------------- #
# prefill
# --------------------------------------------------------------------------- #

def prefill(params, tokens, cfg: ModelConfig, cache_width: int,
            positions=None, input_embeds=None):
    """Run the prompt, build a ring KV cache of ``cache_width`` slots.
    Returns (last-token logits [B,V], cache)."""
    cdtype = jnp.dtype(cfg.compute_dtype)
    if input_embeds is not None:
        x = input_embeds.astype(cdtype)
        b, s = x.shape[:2]
    else:
        b, s = tokens.shape
        x = L.embed(params["embed"], tokens, cdtype)
    if positions is None:
        positions = _default_positions(cfg, b, s)
    pattern = cfg.block_pattern()

    def to_ring(kv_seg):
        """Place a [B,S,Hkv,hd] KV segment into a heads-major [B,Hkv,W,hd]
        ring buffer."""
        k = kv_seg.transpose(0, 2, 1, 3)             # [B,Hkv,S,hd]
        if s >= cache_width:
            tail = k[:, :, s - cache_width:]
            return jnp.roll(tail, s % cache_width, axis=2)
        return jnp.pad(k, ((0, 0), (0, 0), (0, cache_width - s), (0, 0)))

    def period_body(x, slot_params):
        caches = {}
        for i, slot in enumerate(pattern):
            x, new_cache, _ = _apply_slot(slot_params[f"slot{i}"], x, cfg,
                                          slot, positions, cdtype)
            kvdt = jnp.dtype(cfg.kv_dtype)
            if slot.mixer == "attn":
                k, v = new_cache
                caches[f"slot{i}"] = {"k": to_ring(k).astype(kvdt),
                                      "v": to_ring(v).astype(kvdt)}
            else:
                caches[f"slot{i}"] = {
                    "conv": new_cache["conv"].astype(kvdt),
                    "ssm": new_cache["ssm"],
                }
        return x, caches

    if cfg.scan_layers:
        x, cache = jax.lax.scan(period_body, x, params["slots"])
    else:
        cache_list = []
        for p in range(cfg.num_periods()):
            sliced = jax.tree.map(lambda a: a[p], params["slots"])
            x, c = period_body(x, sliced)
            cache_list.append(c)
        cache = jax.tree.map(lambda *xs: jnp.stack(xs), *cache_list)

    x = L.apply_norm(x[:, -1:], params["final_norm"], cfg.norm_type, cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = L.unembed(head, x, cfg.logical_vocab_size, cdtype)[:, 0]
    return logits, cache


# --------------------------------------------------------------------------- #
# decode
# --------------------------------------------------------------------------- #

def decode_step(params, token, pos, cache, cfg: ModelConfig, positions=None):
    """One decode step. token: [B,1] int32; pos: scalar int32 (absolute).
    Returns (logits [B,V], new cache)."""
    cdtype = jnp.dtype(cfg.compute_dtype)
    b = token.shape[0]
    x = L.embed(params["embed"], token, cdtype)
    if positions is None:
        positions = _default_positions(cfg, b, 1, offset=pos)
    pattern = cfg.block_pattern()

    def period_body(x, xs):
        slot_params, slot_caches = xs
        new_caches = {}
        for i, slot in enumerate(pattern):
            x, nc, _ = _apply_slot(slot_params[f"slot{i}"], x, cfg, slot,
                                   positions, cdtype,
                                   cache=slot_caches[f"slot{i}"], pos=pos)
            new_caches[f"slot{i}"] = nc
        return x, new_caches

    if cfg.scan_layers:
        x, new_cache = jax.lax.scan(period_body, x, (params["slots"], cache))
    else:
        ncs = []
        for p in range(cfg.num_periods()):
            sliced = jax.tree.map(lambda a: a[p], (params["slots"], cache))
            x, nc = period_body(x, sliced)
            ncs.append(nc)
        new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *ncs)

    x = L.apply_norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = L.unembed(head, x, cfg.logical_vocab_size, cdtype)[:, 0]
    return logits, new_cache
