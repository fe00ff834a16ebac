"""Jit'd kernel entry points with backend selection.

Source of truth for kernel dispatch: ``interpret_mode()`` is the one place
that decides how a Pallas kernel runs. On TPU the kernels lower natively
(``tpu_custom_call``); on any other backend (the CPU test runs) they run in
the Pallas interpreter. The kernels themselves take ``interpret`` as a
required argument, so every call either goes through here or states its
mode. ``impl="xla"`` selects the pure-jnp reference instead. Models call
these through ``cfg.attn_impl``.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan


def interpret_mode() -> bool:
    """True when the Pallas kernels must run interpreted (no TPU backend)."""
    return jax.default_backend() != "tpu"


def flash_attention_op(q, k, v, *, causal=True, window=0, impl="pallas",
                       block_q=None, block_k=None):
    """q: [B,H,S,D]; k,v: [B,Hkv,T,D]. Blocks default to the kernel's plan
    (``flash_attention_plan``)."""
    if impl == "xla":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret_mode())


def decode_attention_op(q, k_cache, v_cache, pos, *, window=0, impl="pallas",
                        block_k=256):
    """q: [B,H,D]; caches: [B,Hkv,W,D]."""
    if impl == "xla":
        return ref.decode_attention_ref(q, k_cache, v_cache, pos,
                                        window=window)
    return decode_attention(q, k_cache, v_cache, pos, window=window,
                            block_k=block_k, interpret=interpret_mode())


def mamba_scan_op(x, dt, b_mat, c_mat, a, d_vec, *, impl="pallas"):
    """Returns (y [B,S,D], h_final [B,D,N]), tiled by the kernel's plan
    (``mamba_scan_plan``)."""
    if impl == "xla":
        return ref.mamba_scan_ref(x, dt, b_mat, c_mat, a, d_vec)
    return mamba_scan(x, dt, b_mat, c_mat, a, d_vec,
                      interpret=interpret_mode())
