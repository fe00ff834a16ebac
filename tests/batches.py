"""Deterministic input batches shaped for an architecture.

Tokens follow a sparse Markov chain over the vocabulary seeded at 0 (the
stream the tests were written against); encoder-decoder configurations get
stub audio frames and M-RoPE configurations text positions tripled
(t=h=w).
"""
from typing import Dict

import numpy as np

from repro.models.config import ModelConfig

_BRANCHING = 4      # Markov out-degree


def make_batch_for(cfg: ModelConfig, batch: int,
                   seq: int) -> Dict[str, np.ndarray]:
    """``tokens`` [batch, seq], plus ``audio_embeds`` / ``positions``."""
    vocab = cfg.logical_vocab_size or cfg.vocab_size
    succ = np.random.RandomState(0).randint(0, vocab, size=(vocab, _BRANCHING))
    rng = np.random.RandomState(0)
    tokens = np.empty((batch, seq), np.int32)
    tokens[:, 0] = rng.randint(0, vocab, size=batch)
    choices = rng.randint(0, _BRANCHING, size=(batch, seq))
    for t in range(seq - 1):
        tokens[:, t + 1] = succ[tokens[:, t], choices[:, t]]
    out = {"tokens": tokens}
    rng = np.random.RandomState(0)
    if cfg.is_encoder_decoder:
        out["audio_embeds"] = rng.randn(
            batch, cfg.encoder_seq, cfg.d_model).astype(np.float32) * 0.02
    if cfg.mrope_sections:
        pos = np.broadcast_to(np.arange(seq, dtype=np.int32), (batch, seq))
        out["positions"] = np.broadcast_to(pos, (3, batch, seq)).copy()
    return out
