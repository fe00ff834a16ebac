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


@pytest.mark.parametrize("b,hkv,s", [
    (1, 2, 16), (1, 2, 2048),               # starcoder2_3b widths
    (4, 2, 1024),                           # starcoder2_3b, the served batch
    (4, 8, 1024),                           # phi4_mini_3_8b: 8 KV heads
], ids=["16", "2048", "sc2-served", "phi4-served"])
def test_flash_attention_compiles_for_v5e(one_chip, b, hkv, s):
    q = _shape(one_chip, (b, 24, s, 128), jnp.bfloat16)
    kv = _shape(one_chip, (b, hkv, s, 128), jnp.bfloat16)
    compiled = _native(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=4096 if hkv == 2 else 0,
        interpret=False), q, kv, kv)
    assert "flash_attention" in compiled.as_text()


def test_mamba_scan_compiles_for_v5e(one_chip):
    b, s, d, n = 1, 256, 8192, 16                 # falcon_mamba_7b d_inner
    x = _shape(one_chip, (b, s, d), jnp.bfloat16)
    bc = _shape(one_chip, (b, s, n), jnp.bfloat16)
    a = _shape(one_chip, (d, n), jnp.float32)
    d_vec = _shape(one_chip, (d,), jnp.float32)
    _native(lambda x, dt, bm, cm, a, dv: mamba_scan(x, dt, bm, cm, a, dv,
                                                    interpret=False),
            x, x, bc, bc, a, d_vec)


def test_starcoder2_forward_two_experts_fit_v5e(one_chip, monkeypatch):
    """The served full-width forward (Pallas attention, bf16 weights)
    compiles with native kernels, and two experts' weights plus its working
    set fit one chip's HBM (the pool the chip smoke serves from)."""
    import repro.kernels.ops as ops
    from repro.configs import get_config
    from repro.models import transformer

    # the CPU process would pick interpret mode; compile the chip's branch
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cfg = dataclasses.replace(get_config("starcoder2_3b"),
                              param_dtype="bfloat16", attn_impl="pallas",
                              remat=False)
    params = jax.tree.map(lambda a: _shape(one_chip, a.shape, a.dtype),
                          transformer.abstract_params(cfg))
    tokens = _shape(one_chip, (8, 64), jnp.int32)
    compiled = _native(
        lambda p, t: transformer.forward(p, t, cfg, mode="eval")[0][:, -1],
        params, tokens)
    mem = compiled.memory_analysis()
    expert = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= expert
    assert 2 * expert + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes < V5E_HBM_BYTES
