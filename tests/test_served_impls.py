"""The served attention implementation against the XLA one.

The chip serves every expert with ``attn_impl="pallas"``: the Pallas
``flash_attention`` for attention layers and ``mamba_scan`` for Mamba
layers. Here each assigned arch, as a reduced same-family config in float32
on the CPU (kernels interpreted), must give the same full-sequence logits
under both implementations — at an aligned prompt length and at an
unaligned one that goes through the kernels' padding.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batches import make_batch_for
from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.models import encdec, transformer


def _logits(cfg, params, batch):
    tokens = jnp.asarray(batch["tokens"])
    if cfg.is_encoder_decoder:
        return encdec.decode_train(params, tokens,
                                   jnp.asarray(batch["audio_embeds"]), cfg)
    positions = batch.get("positions")
    logits, _ = transformer.forward(
        params, tokens, cfg, mode="eval",
        positions=None if positions is None else jnp.asarray(positions))
    return logits


@pytest.mark.parametrize("s", [32, 37])
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_pallas_matches_xla(arch, s):
    xla = dataclasses.replace(smoke_config(get_config(arch)),
                              compute_dtype="float32", attn_impl="xla")
    pallas = dataclasses.replace(xla, attn_impl="pallas")
    init = encdec.init_params if xla.is_encoder_decoder \
        else transformer.init_params
    params = init(jax.random.PRNGKey(0), xla)
    batch = make_batch_for(xla, 2, s)
    want = _logits(xla, params, batch)
    got = _logits(pallas, params, batch)
    assert got.shape == want.shape == (2, s, xla.vocab_size)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-4)
