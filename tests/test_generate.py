"""Autoregressive generation: greedy, top-k sampling, ring-cache wrap."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.models import sampling, transformer


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(smoke_config(get_config("starcoder2_3b")),
                              compute_dtype="float32")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_greedy_generation_matches_teacher_forcing(tiny):
    """Greedy generate() must reproduce argmax decoding of the full forward
    at every step (prefill + ring-cache decode path end to end)."""
    cfg, params = tiny
    prompt = jnp.asarray(np.random.RandomState(0).randint(0, 500, (2, 8)),
                         jnp.int32)
    n_new = 5
    out = sampling.generate(params, prompt, cfg, max_new_tokens=n_new)
    assert out.shape == (2, n_new)
    seq = prompt
    for i in range(n_new):
        logits, _ = transformer.forward(params, seq, cfg, mode="eval")
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(out[:, i]), np.asarray(nxt))
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)


def test_temperature_sampling_respects_top_k(tiny):
    cfg, params = tiny
    logits = jnp.asarray([[0.0, 10.0, 9.0, -5.0]])
    for seed in range(10):
        tok = sampling.sample_token(logits, jax.random.PRNGKey(seed),
                                    temperature=1.0, top_k=2)
        assert int(tok[0]) in (1, 2)


def test_generation_ring_cache_wrap(tiny):
    """Cache narrower than prompt+new tokens: the ring must wrap without
    shape errors or NaNs (sliding-window semantics)."""
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, sliding_window=12)
    prompt = jnp.asarray(np.random.RandomState(1).randint(0, 500, (1, 10)),
                         jnp.int32)
    out = sampling.generate(params, prompt, cfg, max_new_tokens=8,
                            cache_width=12)
    assert out.shape == (1, 8)
    assert (np.asarray(out) >= 0).all()
