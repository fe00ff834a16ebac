"""GQA decode attention vs a ring KV cache — Pallas TPU kernel.

Flash-decoding layout: one program per (batch, kv_head) handles that head's
whole query group ([G, D] tile, G = Hq/Hkv) while streaming [block_k, D]
cache tiles along the sequential grid axis; (m, l, acc) carried in VMEM
scratch. Ring-buffer validity (slot i holds absolute position
``pos - ((pos - i) mod W)``) and the sliding window are evaluated per tile
from the scalar ``pos`` carried in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dec_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                scale, block_k, width, window, n_kv_blocks):
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[0]
    q = q_ref[0].astype(jnp.float32) * scale                 # [G, D]
    k = k_ref[0].astype(jnp.float32)                         # [bk, D]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [G, bk]

    slots = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)                           # [1, bk]
    abs_pos = pos - jnp.mod(pos - slots, width)
    valid = (abs_pos >= 0) & (slots < width)
    if window:
        valid &= (pos - abs_pos) < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_prev * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == n_kv_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     block_k: int = 256, interpret: bool):
    """q: [B,H,D]; caches: [B,Hkv,W,D]; pos: scalar int32 -> [B,H,D]."""
    b, h, d = q.shape
    hkv, w = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    block_k = min(block_k, w)
    nk = pl.cdiv(w, block_k)
    w_pad = nk * block_k - w
    if w_pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, w_pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, w_pad), (0, 0)))
    scale = d ** -0.5

    qf = q.reshape(b * hkv, g, d)
    kf = k_cache.reshape(b * hkv, w + w_pad, d)
    vf = v_cache.reshape(b * hkv, w + w_pad, d)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    kernel = functools.partial(
        _dec_kernel, scale=scale, block_k=block_k, width=w, window=window,
        n_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(b * hkv, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, g, d), lambda bh, kj: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kj: (bh, kj, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kj: (bh, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda bh, kj: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="decode_attention",  # the op's name in HLO and device traces
        interpret=interpret,
    )(pos_arr, qf, kf, vf)
    return out.reshape(b, h, d)
