"""Core layer primitives: norms, RoPE/M-RoPE, GQA attention (chunked
online-softmax prefill + ring-buffer decode), SwiGLU/GELU MLPs.

All functions are pure; parameters are plain dicts of jnp arrays. Activation
sharding is expressed through ``logical_constraint`` so the same model code
lowers for every mesh via the logical-rule tables.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.logical import logical_constraint

# --------------------------------------------------------------------------- #
# initialisation helpers
# --------------------------------------------------------------------------- #

def dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


@jax.custom_vjp
def _sharding_barrier(x):
    return jax.lax.optimization_barrier(x)


def _sharding_barrier_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _sharding_barrier_bwd(_, g):
    return (g,)


_sharding_barrier.defvjp(_sharding_barrier_fwd, _sharding_barrier_bwd)


def cast_param(p, compute_dtype, *axes):
    """Cast a (possibly fp32, FSDP-sharded) parameter to the compute dtype
    *before* any gather: the sharding constraint + optimization barrier pin
    the convert to the param's sharding, so XLA's FSDP all-gather moves bf16,
    not fp32 — 2x on weight-gather traffic and peak temp
    (EXPERIMENTS.md SSPerf). ``optimization_barrier`` has no differentiation
    rule, so the barrier goes through a custom_vjp whose cotangent is the
    identity — the cast's own grad path (bf16 -> fp32 accumulation) is
    untouched."""
    if p.dtype == compute_dtype:
        return p
    out = p.astype(compute_dtype)
    if axes:
        out = logical_constraint(out, *axes)
        out = _sharding_barrier(out)
    return out


# --------------------------------------------------------------------------- #
# norms
# --------------------------------------------------------------------------- #

def rmsnorm(x, scale, eps=1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(dtype)


def layernorm(x, scale, bias, eps=1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(dtype)


def apply_norm(x, params, norm_type, eps):
    if norm_type == "layernorm":
        return layernorm(x, params["scale"], params["bias"], eps)
    return rmsnorm(x, params["scale"], eps)


def init_norm(d, norm_type, dtype):
    p = {"scale": jnp.ones((d,), dtype)}
    if norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


NORM_AXES = {"scale": (None,), "bias": (None,)}


# --------------------------------------------------------------------------- #
# rotary embeddings
# --------------------------------------------------------------------------- #

def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta: float, sections: Tuple[int, ...] = ()):
    """Rotate-half RoPE.

    x: [B, S, H, hd]; positions: [B, S] (standard) or [3, B, S] (M-RoPE with
    ``sections`` splitting the half-dim into temporal/height/width bands).
    """
    b, s, h, hd = x.shape
    half = hd // 2
    freqs = jnp.asarray(rope_frequencies(hd, theta))          # [half]
    if sections:
        assert sum(sections) == half, (sections, half)
        assert positions.ndim == 3, "M-RoPE requires position triples [3,B,S]"
        # band i of the half-dim rotates with positions[i]
        section_ids = np.repeat(np.arange(len(sections)), sections)  # [half]
        pos = positions.astype(jnp.float32)                    # [3,B,S]
        pos_per_band = pos[section_ids]                        # [half,B,S]
        angles = jnp.einsum("dbs,d->bsd", pos_per_band, freqs)  # [B,S,half]
    else:
        pos = positions.astype(jnp.float32)                    # [B,S]
        angles = pos[..., None] * freqs                        # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# attention
# --------------------------------------------------------------------------- #

NEG_INF = -1e30


def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      chunk: int = 1024, q_offset=0, kv_len=None):
    """Online-softmax attention streamed over KV chunks (XLA flash).

    q: [B, S, Hq, hd]; k, v: [B, T, Hkv, hd]. Never materialises the full
    [S, T] score matrix. ``q_offset`` gives the absolute position of q[0]
    (prefill continuation / decode). ``kv_len`` masks trailing cache slots.

    GQA is handled by expanding KV to the query heads up front: under TP the
    KV heads are replicated (or head-sharded) so the expansion is device-
    local, and every internal tensor then carries a single "heads" dim that
    shards cleanly on the model axis — the split [Hkv, G] layout forced GSPMD
    into involuntary full-rematerialization copies between the attention
    body and the seq-sharded residual (§Perf iteration B2).
    """
    b, s, hq, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    if g > 1:
        # expand KV to query heads BEFORE the chunk scan: one reshard to the
        # clean heads layout up front — expanding per chunk makes GSPMD
        # re-slice a seq-sharded KV every iteration (involuntary full-remat
        # copies; §Perf B6, refuted and reverted)
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    # KV is NEVER seq-sharded inside the chunk scan (chunk slices would cross
    # shards); heads shard when divisible, else KV replicates and the q rows
    # carry the parallelism ("seq_attn" -> model for 24/12-head archs, B7)
    k = logical_constraint(k, "batch", None, "heads", None)
    v = logical_constraint(v, "batch", None, "heads", None)
    c = min(chunk, t)
    n_chunks = (t + c - 1) // c
    t_pad = n_chunks * c
    if t_pad != t:
        k = jnp.pad(k, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    kv_len = t if kv_len is None else kv_len

    qh = (q * (hd ** -0.5)).astype(q.dtype)
    qh = logical_constraint(qh, "batch", "seq_attn", "heads", None)
    q_pos = q_offset + jnp.arange(s)

    def body(carry, idx):
        m, l, acc = carry                      # [b,h,s], [b,h,s], [b,h,s,d]
        kc = jax.lax.dynamic_slice_in_dim(k, idx * c, c, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, idx * c, c, axis=1)
        k_pos = idx * c + jnp.arange(c)
        scores = jnp.einsum("bshd,bchd->bhsc", qh, kc,
                            preferred_element_type=jnp.float32)
        mask = (k_pos[None, :] < kv_len)
        if causal:
            mask = mask & (q_pos[:, None] >= k_pos[None, :])
        if window:
            mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhsc,bchd->bhsd", p, vc,
                        preferred_element_type=jnp.float32)
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = logical_constraint(jnp.full((b, hq, s), NEG_INF, jnp.float32),
                            "batch", "heads", "seq_attn")
    l0 = logical_constraint(jnp.zeros((b, hq, s), jnp.float32),
                            "batch", "heads", "seq_attn")
    acc0 = logical_constraint(jnp.zeros((b, hq, s, hd), jnp.float32),
                              "batch", "heads", "seq_attn", None)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 2, 1, 3)            # [b, s, hq, hd]
    return out.astype(q.dtype)


def ring_decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                          new_kv=None):
    """Single-token attention against a (possibly ring-buffer) KV cache.

    q: [B, 1, Hq, hd]; caches: [B, Hkv, W, hd] (heads-major — the dot
    contracts the trailing [W, hd] tile with no layout copy); ``pos`` is the
    absolute position of the new token. Ring semantics: cache slot i holds
    absolute position ``pos - ((pos - i) mod W)``.

    With ``new_kv=(k_new, v_new)`` ([B, Hkv, 1, hd]) the caches are the
    PRE-update buffers: the new token's slot is masked out of the cache
    scores (it holds the stale pos-W entry) and its attention term is added
    explicitly — callers then update the cache purely for the NEXT step.
    """
    b, _, hq, hd = q.shape
    hkv, w = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    qg = (q * (hd ** -0.5)).reshape(b, hkv, g, hd)
    slots = jnp.arange(w)
    abs_pos = pos - jnp.mod(pos - slots, w)          # [W]
    valid = abs_pos >= 0
    if window:
        valid = valid & (pos - abs_pos < window)
    if new_kv is not None:
        valid = valid & (slots != jnp.mod(pos, w))   # stale slot -> self term
    scores = jnp.einsum("bngd,bnwd->bngw", qg, k_cache,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    if new_kv is not None:
        k_new, v_new = new_kv
        s_self = jnp.einsum("bngd,bnwd->bngw", qg, k_new,
                            preferred_element_type=jnp.float32)  # [b,n,g,1]
        m = jnp.maximum(scores.max(-1, keepdims=True), s_self)
        p = jnp.exp(scores - m)
        p_self = jnp.exp(s_self - m)
        denom = p.sum(-1, keepdims=True) + p_self
        out = jnp.einsum("bngw,bnwd->bngd", p.astype(v_cache.dtype), v_cache,
                         preferred_element_type=jnp.float32)
        out = (out + p_self.astype(jnp.float32)
               * v_new[:, :, 0, :][:, :, None].astype(jnp.float32))
        out = out / denom
        return out.reshape(b, 1, hq, hd).astype(q.dtype)
    p = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bngw,bnwd->bngd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, hd).astype(q.dtype)


def init_attention(key, cfg, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d, cfg.num_heads * hd), dtype),
        "wk": dense_init(kk, (d, cfg.num_kv_heads * hd), dtype),
        "wv": dense_init(kv, (d, cfg.num_kv_heads * hd), dtype),
        "wo": dense_init(ko, (cfg.num_heads * hd, d), dtype, fan_in=cfg.num_heads * hd),
    }


ATTN_AXES = {
    "wq": ("embed", "qkv"),
    "wk": ("embed", "qkv"),
    "wv": ("embed", "qkv"),
    "wo": ("qkv", "embed"),
}


def attention_block(params, x, cfg, positions, *, cache=None, pos=None,
                    cross_kv=None, causal=True, compute_dtype=jnp.bfloat16):
    """GQA attention. Three modes:
      - prefill/train: cache=None -> chunked attention over x itself
        (returns (out, (k, v)) so callers can build a cache);
      - decode: cache=(k_cache, v_cache), pos given -> ring decode;
      - cross-attention: cross_kv=(k, v) precomputed (whisper decoder).
    q and k are rotated (RoPE) where ``positions`` is given and the
    architecture has ``position_encoding == "rope"``.
    """
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ cast_param(params["wq"], compute_dtype, *ATTN_AXES["wq"])
         ).reshape(b, s, cfg.num_heads, hd)
    if cross_kv is None:
        k = (x @ cast_param(params["wk"], compute_dtype, *ATTN_AXES["wk"])
             ).reshape(b, s, cfg.num_kv_heads, hd)
        v = (x @ cast_param(params["wv"], compute_dtype, *ATTN_AXES["wv"])
             ).reshape(b, s, cfg.num_kv_heads, hd)
        if positions is not None and cfg.position_encoding == "rope":
            q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        k, v = cross_kv
    q = logical_constraint(q, "batch", "seq_attn", "heads", None)
    k = logical_constraint(k, "batch", "kv_seq", "kv_heads", None)
    v = logical_constraint(v, "batch", "kv_seq", "kv_heads", None)

    use_pallas = cfg.attn_impl == "pallas"
    new_cache = None
    if cache is not None and cross_kv is None:
        # heads-major ring cache [B, Hkv, W, hd]; the single new row is
        # written in place (donated buffer, shard-local when heads carry the
        # model axis). Attention runs against the PRE-update cache plus an
        # explicit self term, so the updated cache feeds nothing downstream
        # and its update stays a pure in-place bf16 DUS (§Perf iteration A2).
        k_cache, v_cache = cache
        w = k_cache.shape[2]
        slot = jnp.mod(pos, w)
        k_new = k.astype(k_cache.dtype).transpose(0, 2, 1, 3)   # [B,Hkv,1,hd]
        v_new = v.astype(v_cache.dtype).transpose(0, 2, 1, 3)
        if use_pallas:
            kc = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, slot,
                                                     axis=2)
            vc = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, slot,
                                                     axis=2)
            new_cache = (kc, vc)
            from repro.kernels import decode_attention_op
            out = decode_attention_op(
                q[:, 0], kc, vc, pos,
                window=cfg.sliding_window)[:, None]
        else:
            out = ring_decode_attention(q, k_cache, v_cache, pos,
                                        window=cfg.sliding_window,
                                        new_kv=(k_new, v_new))
            new_cache = (
                jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, slot,
                                                    axis=2),
                jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, slot,
                                                    axis=2))
    elif cache is not None:  # cross-attention with cached encoder KV
        out = chunked_attention(q, k, v, causal=False, chunk=cfg.attn_chunk)
    else:
        if use_pallas:
            from repro.kernels import flash_attention_op
            out = flash_attention_op(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=causal,
                window=cfg.sliding_window).transpose(0, 2, 1, 3)
        else:
            out = chunked_attention(q, k, v, causal=causal,
                                    window=cfg.sliding_window,
                                    chunk=cfg.attn_chunk)
        new_cache = (k, v)
    out = out.reshape(b, s, cfg.num_heads * hd)
    out = out @ cast_param(params["wo"], compute_dtype, *ATTN_AXES["wo"])
    out = logical_constraint(out, "batch", "seq_q", "embed_act")
    return out, new_cache


# --------------------------------------------------------------------------- #
# MLPs
# --------------------------------------------------------------------------- #

def init_mlp(key, d, d_ff, mlp_type, dtype):
    if mlp_type == "swiglu":
        k1, k3 = jax.random.split(key, 2)
        return {
            # gate/up fused along a local pair dim (§Perf iteration B3):
            # one matmul + ONE input-grad all-reduce in the TP backward
            "w_in": dense_init(k1, (d, 2, d_ff), dtype),
            "w_down": dense_init(k3, (d_ff, d), dtype, fan_in=d_ff),
        }
    k1, k2 = jax.random.split(key)
    return {
        "w_up": dense_init(k1, (d, d_ff), dtype),
        "w_down": dense_init(k2, (d_ff, d), dtype, fan_in=d_ff),
    }


MLP_AXES = {
    "w_in": ("embed", None, "mlp"),
    "w_up": ("embed", "mlp"),
    "w_down": ("mlp", "embed"),
}


def mlp_axes(mlp_type: str):
    if mlp_type == "swiglu":
        return {k: MLP_AXES[k] for k in ("w_in", "w_down")}
    return {k: MLP_AXES[k] for k in ("w_up", "w_down")}


def mlp_block(params, x, mlp_type, compute_dtype=jnp.bfloat16):
    if mlp_type == "swiglu":
        wi = cast_param(params["w_in"], compute_dtype, *MLP_AXES["w_in"])
        gu = jnp.einsum("bsd,dxf->bsxf", x, wi)      # [B,S,2,ff] fused
        h = jax.nn.silu(gu[..., 0, :]) * gu[..., 1, :]
    else:
        h = jax.nn.gelu(x @ cast_param(params["w_up"], compute_dtype,
                                       *MLP_AXES["w_up"]))
    h = logical_constraint(h, "batch", "seq_attn", "mlp")
    out = h @ cast_param(params["w_down"], compute_dtype, *MLP_AXES["w_down"])
    return logical_constraint(out, "batch", "seq_q", "embed_act")


# --------------------------------------------------------------------------- #
# embeddings / head
# --------------------------------------------------------------------------- #

def init_embedding(key, vocab, d, dtype):
    return {"table": dense_init(key, (vocab, d), dtype, fan_in=d)}


EMBED_AXES = {"table": ("vocab", "embed")}


def embed(params, tokens, compute_dtype=jnp.bfloat16):
    out = cast_param(params["table"], compute_dtype, *EMBED_AXES["table"])[tokens]
    return logical_constraint(out, "batch", "seq_q", "embed_act")


def unembed(params, x, logical_vocab=0, compute_dtype=jnp.bfloat16):
    logits = x @ cast_param(params["table"], compute_dtype,
                            *EMBED_AXES["table"]).T
    if logical_vocab and logical_vocab < params["table"].shape[0]:
        pad = params["table"].shape[0] - logical_vocab
        mask = jnp.concatenate([jnp.zeros((logical_vocab,), logits.dtype),
                                jnp.full((pad,), NEG_INF, logits.dtype)])
        logits = logits + mask
    return logical_constraint(logits, "batch", "seq_q", "vocab")
