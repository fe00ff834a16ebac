"""Pallas TPU kernels for the compute hot-spots, with pure-jnp oracles.

- flash_attention: prefill/train attention (causal, GQA, sliding window)
- decode_attention: one-token GQA attention vs a ring KV cache
- mamba_scan: chunked selective scan for the SSM/hybrid architectures

Each kernel is a ``pl.pallas_call`` with explicit BlockSpec VMEM tiling,
validated in interpret mode against ``ref.py`` across shape/dtype sweeps
and compiled for a described TPU v5e chip (``tests/test_tpu_compile.py``).
``ops.interpret_mode()`` picks native or interpreted execution.
"""
from repro.kernels.ops import (decode_attention_op, flash_attention_op,
                               mamba_scan_op)

__all__ = ["decode_attention_op", "flash_attention_op", "mamba_scan_op"]
