"""Per-architecture smoke tests: every assigned arch as a REDUCED
same-family config on the CPU — one forward (output shapes, no NaNs), and
prefill + one decode step against the teacher-forced forward, under both
attention implementations (the served one is ``pallas``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batches import make_batch_for
from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.models import encdec, transformer


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_no_nan(arch, key):
    cfg = smoke_config(get_config(arch))
    b, s = 2, 32
    batch = make_batch_for(cfg, b, s)
    if cfg.is_encoder_decoder:
        params = encdec.init_params(key, cfg)
        logits = encdec.decode_train(
            params, jnp.asarray(batch["tokens"]),
            jnp.asarray(batch["audio_embeds"]), cfg)
    else:
        params = transformer.init_params(key, cfg)
        logits, aux = transformer.forward(
            params, jnp.asarray(batch["tokens"]), cfg,
            positions=jnp.asarray(batch["positions"])
            if "positions" in batch else None)
        assert jnp.isfinite(aux).all()
    assert logits.shape == (b, s, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any()), f"{arch} produced NaNs"


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_consistency(arch, attn_impl, key):
    """Prefill+decode logits must match the teacher-forced forward."""
    cfg = dataclasses.replace(smoke_config(get_config(arch)),
                              compute_dtype="float32", attn_impl=attn_impl)
    b, s = 2, 16
    batch = make_batch_for(cfg, b, s)
    tokens = jnp.asarray(batch["tokens"])
    if cfg.is_encoder_decoder:
        params = encdec.init_params(key, cfg)
        audio = jnp.asarray(batch["audio_embeds"])
        last, cache = encdec.prefill(params, tokens, audio, cfg, s + 4)
        tok = jnp.argmax(last, -1)[:, None]
        dl, _ = encdec.decode_step(params, tok, s, cache, cfg)
        full = encdec.decode_train(
            params, jnp.concatenate([tokens, tok], 1), audio, cfg)
        np.testing.assert_allclose(np.asarray(dl), np.asarray(full[:, -1]),
                                   rtol=2e-4, atol=2e-4)
        return
    params = transformer.init_params(key, cfg)
    logits, _ = transformer.forward(params, tokens, cfg, mode="eval")
    last, cache = transformer.prefill(params, tokens, cfg, cache_width=s + 4)
    np.testing.assert_allclose(np.asarray(last), np.asarray(logits[:, -1]),
                               rtol=2e-4, atol=2e-4)
    tok = jnp.argmax(last, -1)[:, None]
    dl, _ = transformer.decode_step(params, tok, s, cache, cfg)
    full, _ = transformer.forward(
        params, jnp.concatenate([tokens, tok], 1), cfg, mode="eval")
    np.testing.assert_allclose(np.asarray(dl), np.asarray(full[:, -1]),
                               rtol=2e-4, atol=2e-4)
