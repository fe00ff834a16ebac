"""Operations and bytes that a dense decoder's work needs, from its shapes.

These functions are the benchmark's own count, never XLA's: a compiled
program's cost analysis counts a scanned layer body once. A multiply-add is
two operations. Norms, RoPE, softmax and activations are left out, as in the
usual model-FLOP count; they are well under 1% of the matrix work here.
"""
from __future__ import annotations

from bench.reference.dense_decoder import Dims

BF16_BYTES = 2


def attention_pairs(seq: int, window: int = 0) -> int:
    """(query, key) pairs a causal (optionally windowed) attention scores."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return sum(min(i + 1, window) for i in range(seq))


def layer_flops(dm: Dims, seq: int) -> float:
    """One layer over one sequence of ``seq`` tokens."""
    q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    proj = 2 * seq * dm.d * (q + 2 * kv) + 2 * seq * q * dm.d
    attn = 2 * 2 * dm.heads * dm.head_dim * attention_pairs(seq, dm.window)
    mlp = 2 * seq * dm.d * dm.ff * (3 if dm.mlp == "swiglu" else 2)
    return float(proj + attn + mlp)


def forward_flops(dm: Dims, seq: int) -> float:
    """The served step for one sequence: every layer over the whole prompt,
    and the output head at the last position only (the one row served)."""
    return dm.layers * layer_flops(dm, seq) + 2.0 * dm.d * dm.vocab


def flash_attention_cost(dm: Dims, batch: int, seq: int):
    """(operations, bytes) of one causal attention call over ``batch``
    sequences: q·kᵀ and p·v on every scored pair, and q, k, v read and the
    output written once each in bfloat16."""
    flops = 2 * 2 * batch * dm.heads * dm.head_dim \
        * attention_pairs(seq, dm.window)
    elems = batch * seq * dm.head_dim * (2 * dm.heads + 2 * dm.kv_heads)
    return float(flops), float(elems * BF16_BYTES)
