"""The plain reference against the program's forward, the seeded weights,
the shape functions against hand counts, and the traffic generator."""
from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import flops
from bench.lib.traffic import Traffic, block_counts
from bench.models import dense_decoder as prog
from bench.reference import dense_decoder as ref
from bench.tests.tiny import ROOT, TINY

CONFIGS = ["starcoder2_3b-coe", "phi4_mini_3_8b-coe"]


def config(name: str, **overrides) -> dict:
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    cfg["name"] = name
    cfg.update(overrides)
    return cfg


@pytest.mark.parametrize("name", CONFIGS)
def test_program_weights_are_the_reference_weights(name):
    cfg = config(name, **TINY)
    dm = ref.dims(cfg)
    ek = ref.expert_key(2 ** 33 + 5, 2)
    params = prog.init_fn(cfg)(ek)
    slot = params["slots"]["slot0"]
    for li in range(dm.layers):
        want = ref.make_layer(ref.layer_key(ek, li), dm)
        np.testing.assert_array_equal(slot["attn"]["wq"][li], want["wq"])
        np.testing.assert_array_equal(slot["mlp"]["w_down"][li],
                                      want["w_down"])
        np.testing.assert_array_equal(slot["norm2"]["scale"][li],
                                      want["mlp_norm_scale"])
    np.testing.assert_array_equal(params["embed"]["table"],
                                  ref.make_top(ref.top_key(ek), dm)["embed"])


def test_experts_and_seeds_get_distinct_weights():
    dm = ref.dims(config("starcoder2_3b-coe", **TINY))
    a = ref.make_top(ref.top_key(ref.expert_key(7, 0)), dm)["embed"]
    b = ref.make_top(ref.top_key(ref.expert_key(7, 1)), dm)["embed"]
    c = ref.make_top(ref.top_key(ref.expert_key(7 + 2 ** 32, 0)), dm)["embed"]
    assert not np.array_equal(a, b) and not np.array_equal(a, c)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_program_forward(name):
    """At a smoke size, the program's served step (bf16 weights and
    activations, the Pallas kernel interpreted) against the float32
    reference: logits agree to bf16 rounding accumulated over two layers."""
    cfg = config(name, **TINY)
    seed, expert = 2 ** 31 + 11, 1
    tokens = np.random.default_rng(0).integers(0, TINY["vocab_size"],
                                               (3, 32), dtype=np.int32)
    params = prog.init_fn(cfg)(ref.expert_key(seed, expert))
    got = np.asarray(prog.serve_fn(cfg)(params, tokens))
    want = ref.last_logits(cfg, seed, expert, tokens)
    assert got.shape == want.shape == (3, TINY["vocab_size"])
    assert np.abs(got - want).max() < 0.05 * np.abs(want).max()
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))


def test_reference_blocks_do_not_change_the_result():
    cfg = config("phi4_mini_3_8b-coe", **TINY)
    tokens = np.random.default_rng(1).integers(0, TINY["vocab_size"],
                                               (5, 16), dtype=np.int32)
    whole = ref.last_logits(cfg, 3, 0, tokens)
    blocks = ref.last_logits(cfg, 3, 0, tokens, block_tokens=32)
    np.testing.assert_allclose(whole, blocks, rtol=1e-5, atol=1e-5)


def test_partial_rotary_reference_rotates_only_its_share():
    cfg = config("phi4_mini_3_8b-coe", **TINY, partial_rotary_factor=0.5)
    dm = ref.dims(cfg)
    x = jnp.ones((1, 4, 1, dm.head_dim))
    out = np.asarray(ref._rope(x, dm))
    np.testing.assert_array_equal(out[..., dm.rotary_dim:], 1.0)
    assert not np.allclose(out[0, 1, 0, :dm.rotary_dim], 1.0)
    with pytest.raises(ValueError):
        prog.program_config(cfg)


def test_flops_equal_hand_counts_at_published_widths():
    sc2 = ref.dims(config("starcoder2_3b-coe"))
    phi = ref.dims(config("phi4_mini_3_8b-coe"))
    # starcoder2-3b, one 1024-token sequence: per layer q/k/v/o projections
    # 2*1024*3072*(3072+2*256) + 2*1024*3072*3072 = 41,875,931,136, causal
    # attention 4*24*128*(1024*1025/2) = 6,448,742,400, GELU MLP
    # 2*1024*3072*12288*2 = 154,618,822,656; 30 layers plus the head at the
    # last position, 2*3072*49152
    assert flops.layer_flops(sc2, 1024) == 202_943_496_192
    assert flops.forward_flops(sc2, 1024) == 6_088_606_875_648
    # phi-4-mini: projections 2*1024*3072*(3072+2*1024) + 2*1024*3072*3072,
    # the same attention, SwiGLU 2*1024*3072*8192*3; 32 layers, head
    # 2*3072*200064
    assert flops.forward_flops(phi, 1024) == 6_804_658_716_672
    # flash attention, 8 x 1024 on starcoder2: 4*8*24*128*524,800 operations;
    # q and out 8*1024*128*24 each, k and v 8*1024*128*2 each, 2 bytes
    assert flops.flash_attention_cost(sc2, 8, 1024) == (51_589_939_200.0,
                                                        109_051_904.0)
    assert flops.attention_pairs(6, window=2) == 11


def test_traffic_sends_exact_shares_in_a_seeded_order():
    spec = {"clients": 4, "prompt_len": 8, "domain_weights": [0.5, 0.3, 0.2]}
    assert list(block_counts([0.5, 0.3, 0.2], 100)) == [50, 30, 20]
    assert list(block_counts([1, 1, 1], 100)) == [34, 33, 33]
    a = Traffic(spec, 3, 100, seed=2 ** 40 + 1)
    b = Traffic(spec, 3, 100, seed=2 ** 40 + 1)
    c = Traffic(spec, 3, 100, seed=2 ** 40 + 2)
    da = [a.next() for _ in range(100)]
    db = [b.next() for _ in range(100)]
    dc = [c.next() for _ in range(100)]
    assert all(x[0] == y[0] and np.array_equal(x[1], y[1])
               for x, y in zip(da, db))
    assert np.bincount([d for d, _ in da]).tolist() == [50, 30, 20]
    assert np.bincount([d for d, _ in dc]).tolist() == [50, 30, 20]
    assert [d for d, _ in da] != [d for d, _ in dc]
    with pytest.raises(ValueError):
        Traffic(spec, 2, 100, seed=1)
