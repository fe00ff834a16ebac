"""The hybrid (Jamba) family against its plain reference at a one-period
smoke size on the CPU, and its shape functions against hand counts.

The program is served as the benchmark serves it (bfloat16, the Pallas scan
interpreted) and also with the chunked-XLA scan. Two tolerances, on the
widest |program - reference| last-position logit over the largest reference
logit:

- ``SERVED_TOL`` for the bfloat16 program: bf16 rounding over 14 layers read
  0.045 (Pallas scan) and 0.038 (XLA scan); the float8 control reads 0.48.
- ``FLOAT32_TOL`` for the same program computed in float32: it read 3e-6.
  Dropping the dt/B/C norms read 1.05 and adding RoPE to the attention layer
  0.13, so each such departure from the published layer fails it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import flops_hybrid as fh
from bench.models import hybrid_decoder as prog
from bench.reference import hybrid_decoder as ref
from bench.tests.tiny import ROOT
from repro.configs import get_config
from repro.models import transformer
from repro.models.ssm import INNER_NORMS

SERVED_TOL = 0.1
FLOAT32_TOL = 1e-4
# one period (14 layers, attention at layer 7) at smoke widths; the Mamba
# d_state, expand and d_conv stay as published
SMOKE = {"hidden_size": 64, "num_hidden_layers": 14, "num_attention_heads": 4,
         "num_key_value_heads": 1, "intermediate_size": 128,
         "vocab_size": 256}
SEED, EXPERT = 2 ** 33 + 5, 1


def config(**overrides) -> dict:
    cfg = json.loads((ROOT / "bench/configs/jamba2_3b-coe.json").read_text())
    cfg["name"] = "jamba2_3b-coe"
    cfg.update(overrides)
    return cfg


@pytest.fixture(scope="module")
def smoke():
    cfg = config(**SMOKE)
    tokens = np.random.default_rng(0).integers(
        0, SMOKE["vocab_size"], (2, 32), dtype=np.int32)
    params = prog.init_fn(cfg)(ref.expert_key(SEED, EXPERT))
    want = ref.last_logits(cfg, SEED, EXPERT, tokens)
    return cfg, tokens, params, want


def _program_logits(cfg, params, tokens, impl, float32=False, **arch):
    mc = dataclasses.replace(prog.program_config(cfg), attn_impl=impl,
                             **arch)
    if float32:
        mc = dataclasses.replace(mc, param_dtype="float32",
                                 compute_dtype="float32")
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    if not mc.ssm_inner_norms:
        params = jax.tree.map(lambda a: a, params)
        for slot in params["slots"].values():
            for k in INNER_NORMS if "mamba" in slot else ():
                del slot["mamba"][k]

    @jax.jit
    def fn(p, t):
        return transformer.forward(p, t, mc, mode="eval")[0][:, -1]
    with jax.default_matmul_precision("highest") if float32 \
            else contextlib.nullcontext():
        return np.asarray(fn(params, tokens), np.float32)


def _err(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("check,impl", [
    ("weights", "pallas"),
    ("served", "pallas"), ("served", "xla"),
    ("float32", "pallas"), ("float32", "xla"),
    ("inner_norms_dropped", "pallas"), ("inner_norms_dropped", "xla"),
    ("rope_added", "pallas"), ("rope_added", "xla"),
    ("control", "pallas"),
])
def test_program_against_reference(smoke, check, impl):
    cfg, tokens, params, want = smoke
    if check == "weights":
        # the program's slot i of period p is the reference's layer
        # p * 14 + i; one period here
        dm = ref.dims(cfg)
        ek = ref.expert_key(SEED, EXPERT)
        for i in (0, 7, 13):
            w = ref.make_layer(ref.layer_key(ek, i), dm, ref.layer_kind(dm, i))
            slot = params["slots"][f"slot{i}"]
            mixer = slot["attn"] if i == 7 else slot["mamba"]
            for name in (("wq", "wk", "wo") if i == 7
                         else ("in_proj", "dt_norm", "dt_bias", "A_log")):
                np.testing.assert_array_equal(mixer[name][0], w[name])
            np.testing.assert_array_equal(slot["mlp"]["w_in"][0], w["w_in"])
        np.testing.assert_array_equal(
            params["embed"]["table"],
            ref.make_top(ref.top_key(ek), dm)["embed"])
    elif check == "served":
        got = _program_logits(cfg, params, tokens, impl)
        assert _err(got, want) < SERVED_TOL
        np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))
    elif check == "float32":
        got = _program_logits(cfg, params, tokens, impl, float32=True)
        assert _err(got, want) < FLOAT32_TOL
    elif check == "inner_norms_dropped":
        got = _program_logits(cfg, params, tokens, impl, float32=True,
                              ssm_inner_norms=False)
        assert _err(got, want) > FLOAT32_TOL
    elif check == "rope_added":
        got = _program_logits(cfg, params, tokens, impl, float32=True,
                              position_encoding="rope")
        assert _err(got, want) > FLOAT32_TOL
    else:
        ctrl = ref.last_logits(cfg, SEED, EXPERT, tokens,
                               precision="float8_e4m3fn")
        assert _err(ctrl, want) > SERVED_TOL


def test_program_config_is_the_listed_model():
    """The served configuration at published widths is the program's
    ``jamba2_3b`` but for the serving dtypes and kernels."""
    served = prog.program_config(config())
    listed = get_config("jamba2_3b")
    runtime = ("name", "param_dtype", "attn_impl", "remat")
    assert dataclasses.replace(served, **{
        k: getattr(listed, k) for k in runtime}) == listed
    assert served.dt_rank == 160 and served.d_inner == 5120
    # a copy cut in width (as the harness's tiny cells and SMOKE are) keeps
    # the published dt_rank, which is then not ceil(d_model / 16)
    assert prog.program_config(config(**SMOKE)).dt_rank == 160
    assert [s.mixer for s in served.block_pattern()].count("attn") == 1
    assert served.num_periods() == 2


def test_shallow_model_is_the_first_layers_of_a_period():
    mc = prog.program_config(config(**dict(SMOKE, num_hidden_layers=2)))
    assert [s.mixer for s in mc.block_pattern()] == ["mamba", "mamba"]
    with pytest.raises(ValueError):
        prog.program_config(config(num_hidden_layers=20))


@pytest.mark.parametrize("key,value", [("num_experts", 16),
                                       ("mamba_proj_bias", True),
                                       ("hidden_act", "gelu")])
def test_reference_refuses_what_it_does_not_compute(key, value):
    with pytest.raises(ValueError):
        ref.dims(config(**{key: value}))


def test_selective_scan_is_the_recurrence():
    """The reference's scan against the recurrence written out in NumPy."""
    rng = np.random.default_rng(3)
    b, s, di, st = 2, 5, 3, 4
    x, dt = rng.normal(size=(b, s, di)), rng.uniform(0.01, 0.1, (b, s, di))
    bm, cm = rng.normal(size=(b, s, st)), rng.normal(size=(b, s, st))
    a, d_vec = -rng.uniform(1, 4, (di, st)), rng.normal(size=di)
    h = np.zeros((b, di, st))
    want = np.zeros((b, s, di))
    for t in range(s):
        h = np.exp(dt[:, t, :, None] * a) * h \
            + (dt[:, t] * x[:, t])[..., None] * bm[:, t, None, :]
        want[:, t] = (h * cm[:, t, None, :]).sum(-1) + d_vec * x[:, t]
    got = ref.selective_scan(*(jnp.asarray(v, jnp.float32)
                               for v in (x, dt, bm, cm, a, d_vec)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_flops_equal_hand_counts_at_published_widths():
    dm = ref.dims(config())
    # one Mamba-1 mixer over 1024 tokens: in_proj 2560x10240, x_proj
    # 5120x192, dt_proj 160x5120, out_proj 5120x2560, two operations a
    # multiply-add: 2*1024*41,123,840 = 84,221,624,320 (the scan is vector
    # work and not counted)
    assert fh.mamba_layer_flops(dm, 1024) == 84_221_624_320
    # attention: projections 2*1024*2560*(2560+2*128) + 2*1024*2560*2560 =
    # 28,185,722,880, causal scores 4*20*128*524,800 = 5,373,952,000
    assert fh.attention_layer_flops(dm, 1024) == 33_559_674_880
    # SwiGLU 2*1024*2560*8192*3 on all 28 layers; 26 Mamba and 2 attention
    # mixers; the head at the last position 2*2560*65536
    assert fh.mlp_flops(dm, 1024) == 128_849_018_880
    assert fh.mamba_layers(dm) == 26
    assert fh.forward_flops(dm, 1024) == 5_864_989_655_040
    # one mamba_scan call at batch 4: x and y 4*1024*5120 bf16 each, dt the
    # same in f32, B and C 4*1024*16 f32 each, the final state 4*5120*16 f32
    assert fh.mamba_scan_bytes(dm, 4, 1024) == 169_607_168
