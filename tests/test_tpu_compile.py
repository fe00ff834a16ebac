"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed next to the CPU backend, so each test here
compiles a kernel (or the served LM forward) for device 0 of a described
``v5e:2x2`` topology and asserts that the Pallas kernel lowered natively
(``tpu_custom_call``). This finds tiling, VMEM and lowering refusals that
interpret-mode tests cannot see. Nothing runs, so nothing here says anything
about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file. The persistent compilation cache is off around these
compiles (an entry written for a described chip cannot be read back here).
"""
from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _native(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("b,h,hkv,d,w,dtype", [
    (1, 4, 2, 64, 64, jnp.float32),         # RingKVCache default geometry
    (8, 24, 2, 128, 4096, jnp.bfloat16),    # starcoder2_3b widths
])
def test_decode_attention_compiles_for_v5e(one_chip, b, h, hkv, d, w, dtype):
    q = _shape(one_chip, (b, h, d), dtype)
    kv = _shape(one_chip, (b, hkv, w, d), dtype)
    pos = _shape(one_chip, (), jnp.int32)
    _native(lambda q, k, v, p: decode_attention(q, k, v, p, interpret=False),
            q, kv, kv, pos)


@pytest.mark.parametrize("b,h,hkv,s,window", [
    (1, 24, 2, 16, 4096), (1, 24, 2, 2048, 4096),   # starcoder2_3b widths
    (4, 24, 2, 1024, 4096),                 # starcoder2_3b, the served batch
    (4, 24, 8, 1024, 0),                    # phi4_mini_3_8b: 8 KV heads
    (8, 20, 1, 1024, 0),                    # jamba2_3b: 20 heads over 1 KV
], ids=["16", "2048", "sc2-served", "phi4-served", "jamba2-served"])
def test_flash_attention_compiles_for_v5e(one_chip, b, h, hkv, s, window):
    q = _shape(one_chip, (b, h, s, 128), jnp.bfloat16)
    kv = _shape(one_chip, (b, hkv, s, 128), jnp.bfloat16)
    compiled = _native(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, interpret=False), q, kv, kv)
    assert "flash_attention" in compiled.as_text()


@pytest.mark.parametrize("b,s,d,io_dtype", [
    (1, 256, 8192, jnp.bfloat16),           # falcon_mamba_7b d_inner
    # jamba2_3b as served: d_inner 5120, the plan's tiling, x in bf16 and
    # dt/B/C in f32 (the mixer's dtypes)
    (8, 1024, 5120, jnp.float32),
], ids=["falcon-mamba", "jamba2-served"])
def test_mamba_scan_compiles_for_v5e(one_chip, b, s, d, io_dtype):
    n = 16
    x = _shape(one_chip, (b, s, d), jnp.bfloat16)
    dt = _shape(one_chip, (b, s, d), io_dtype)
    bc = _shape(one_chip, (b, s, n), io_dtype)
    a = _shape(one_chip, (d, n), jnp.float32)
    d_vec = _shape(one_chip, (d,), jnp.float32)
    compiled = _native(
        lambda x, dt, bm, cm, a, dv: mamba_scan(
            x, dt, bm, cm, a, dv, interpret=False),
        x, dt, bc, bc, a, d_vec)
    assert "mamba_scan" in compiled.as_text()


def _served(cfg):
    return dataclasses.replace(cfg, param_dtype="bfloat16",
                               attn_impl="pallas", remat=False)


def _forward_fits_two_experts(one_chip, cfg, batch, seq):
    """Compile the served forward for one chip and return the compiled
    text; two experts' weights plus its working set fit the chip's HBM."""
    from repro.models import transformer
    params = jax.tree.map(lambda a: _shape(one_chip, a.shape, a.dtype),
                          transformer.abstract_params(cfg))
    tokens = _shape(one_chip, (batch, seq), jnp.int32)
    compiled = _native(
        lambda p, t: transformer.forward(p, t, cfg, mode="eval")[0][:, -1],
        params, tokens)
    mem = compiled.memory_analysis()
    expert = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= expert
    assert 2 * expert + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes < V5E_HBM_BYTES
    return compiled.as_text()


def test_jamba2_forward_two_experts_fit_v5e(one_chip, monkeypatch):
    """Jamba2-3B as the benchmark serves it (Pallas attention and scan, bf16
    weights), at the largest profiled batch of 1024-token prompts: both
    kernels lower natively under their own names, and two experts fit."""
    import repro.kernels.ops as ops
    from repro.configs import get_config

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    text = _forward_fits_two_experts(one_chip,
                                     _served(get_config("jamba2_3b")), 8, 1024)
    assert re.search(r"^\s*%?mamba_scan[\w.]* = ", text, re.M)
    assert re.search(r"^\s*%?flash_attention[\w.]* = ", text, re.M)


def test_forward_carries_mixer_scopes():
    """On the CPU: the lowered forward's op locations name the layer kind
    (``jax.named_scope`` in ``transformer._apply_slot``), which device
    traces carry as op metadata."""
    from repro.configs import get_config, smoke_config
    from repro.models import transformer
    cfg = smoke_config(get_config("jamba2_3b"))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = jax.jit(
        lambda p, t: transformer.forward(p, t, cfg, mode="eval")[0]).lower(
        transformer.abstract_params(cfg), tokens).as_text(debug_info=True)
    for scope in ("mamba", "attention", "mlp"):
        assert re.search(rf'loc\("(?:[^"]*/)?{scope}/', text), scope


def test_starcoder2_forward_two_experts_fit_v5e(one_chip, monkeypatch):
    """The served full-width forward (Pallas attention, bf16 weights)
    compiles with native kernels, and two experts' weights plus its working
    set fit one chip's HBM (the pool the chip smoke serves from)."""
    import repro.kernels.ops as ops
    from repro.configs import get_config

    # the CPU process would pick interpret mode; compile the chip's branch
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    _forward_fits_two_experts(one_chip, _served(get_config("starcoder2_3b")),
                              8, 64)
