"""Flash attention (causal / GQA / sliding-window) as a Pallas TPU kernel.

Tiling: one program handles a [block_q, head_dim] query tile held in VMEM
while streaming [block_k, head_dim] K/V tiles; online softmax carries
(m, l, acc) in VMEM scratch across the sequential kv-block grid dimension.
Block sizes are MXU-aligned (multiples of 128 on the contracting dims).
Grid: (batch*heads, q_blocks, kv_blocks) — kv is the innermost sequential
loop ("arbitrary" semantics); fully-masked tiles above the causal diagonal
or outside the sliding window are skipped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale, block_q, block_k, seq_q, seq_k, causal, window,
               n_kv_blocks):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # global positions of this tile (causality is right-aligned for T >= S)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + (seq_k - seq_q)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [bq, d]
        k = k_ref[0].astype(jnp.float32)                  # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = k_pos < seq_k
        if causal:
            mask &= q_pos >= k_pos
        if window:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_prev * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal or window:
        # skip tiles entirely above the diagonal / outside the window
        first_q = qi * block_q + (seq_k - seq_q)
        last_q = first_q + block_q - 1
        live = (kj * block_k <= last_q) if causal else (kj * block_k < seq_k)
        if window:
            live &= (kj + 1) * block_k - 1 >= first_q - window + 1
        pl.when(live)(_compute)
    else:
        _compute()

    @pl.when(kj == n_kv_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool):
    """q: [B,H,S,D]; k,v: [B,Hkv,T,D] -> [B,H,S,D] (GQA via head grouping)."""
    b, h, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = h // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    nq = pl.cdiv(s, block_q)
    nk = pl.cdiv(t, block_k)
    scale = d ** -0.5

    # pad to block multiples (zero-fill; padded keys are masked by k_pos)
    s_pad, t_pad = nq * block_q - s, nk * block_k - t
    if s_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, s_pad), (0, 0)))
    if t_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, t_pad), (0, 0)))

    qf = q.reshape(b * h, s + s_pad, d)
    kf = k.reshape(b * hkv, t + t_pad, d)
    vf = v.reshape(b * hkv, t + t_pad, d)

    def kv_index(bh, qi, kj):
        # program bh = bi*H + hi; its kv row is bi*Hkv + hi//g
        return ((bh // h) * hkv + (bh % h) // g, kj, 0)

    kernel = functools.partial(
        _fa_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_q=s, seq_k=t, causal=causal, window=window, n_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s + s_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention",  # the op's name in HLO and device traces
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s + s_pad, d)[:, :, :s]
