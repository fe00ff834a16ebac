"""Operations and bytes that a hybrid Mamba-1 / attention decoder's work
needs, from its shapes (``bench/reference/hybrid_decoder.Dims``).

The benchmark's own count, as in ``flops.py``: a multiply-add is two
operations; norms, the depthwise conv, softmax and activations are left out.
The operations are the matrix work the MXU does. The selective scan does no
matrix product: its elementwise work runs on the vector unit, so it is not
counted against the MXU's peak, and the scan kernel is read against HBM
bandwidth alone (``mamba_scan_bytes``).
"""
from __future__ import annotations

from bench.lib.flops import attention_pairs
from bench.reference.hybrid_decoder import Dims, layer_kind

BF16_BYTES = 2
F32_BYTES = 4


def mlp_flops(dm: Dims, seq: int) -> float:
    return 2.0 * seq * dm.d * dm.ff * 3


def attention_layer_flops(dm: Dims, seq: int) -> float:
    """Projections and causal scores of one attention layer (no MLP)."""
    q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    proj = 2 * seq * dm.d * (q + 2 * kv) + 2 * seq * q * dm.d
    return float(proj + 2 * 2 * dm.heads * dm.head_dim
                 * attention_pairs(seq))


def mamba_layer_flops(dm: Dims, seq: int) -> float:
    """in_proj, x_proj, dt_proj and out_proj of one Mamba-1 mixer (no MLP,
    no scan)."""
    di, rk, st = dm.d_inner, dm.dt_rank, dm.d_state
    return 2.0 * seq * (dm.d * 2 * di + di * (rk + 2 * st) + rk * di
                        + di * dm.d)


def mamba_layers(dm: Dims) -> int:
    return sum(layer_kind(dm, i) == "mamba" for i in range(dm.layers))


def forward_flops(dm: Dims, seq: int) -> float:
    """The served step for one sequence: every layer over the whole prompt,
    and the output head at the last position only (the one row served)."""
    n_mamba = mamba_layers(dm)
    mixers = n_mamba * mamba_layer_flops(dm, seq) \
        + (dm.layers - n_mamba) * attention_layer_flops(dm, seq)
    return mixers + dm.layers * mlp_flops(dm, seq) + 2.0 * dm.d * dm.vocab


def mamba_scan_bytes(dm: Dims, batch: int, seq: int) -> float:
    """Bytes one ``mamba_scan`` call over ``batch`` sequences moves, with
    the served dtypes: x read and y written in bfloat16, dt, B and C read
    and the final state written in float32, each once."""
    di, st = dm.d_inner, dm.d_state
    elems = batch * seq * di
    return float(elems * (BF16_BYTES + F32_BYTES + BF16_BYTES)
                 + batch * seq * 2 * st * F32_BYTES
                 + batch * di * st * F32_BYTES)
