"""The one request generator: a traffic file's parameters and a seed in,
the prompts of a closed loop out.

A traffic file gives the number of ``clients``, the ``prompt_len`` of every
prompt, and ``domain_weights``, the share of requests sent to each domain
expert of the catalog in order (missing entries are 0). Requests are drawn in
blocks of ``mix_block`` (default 100): each block holds the exact shares,
rounded by largest remainder, in an order shuffled by the seed. So every
seed sends the same work, in another order, and prompt ids are drawn
uniformly from the vocabulary.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A NumPy generator for any whole-number seed, negative or beyond 64
    bits, and one of several independent streams of it."""
    words = [stream, int(seed < 0)]
    s = abs(int(seed))
    while True:
        words.append(s & 0xFFFFFFFF)
        s >>= 32
        if not s:
            return np.random.default_rng(words)


def block_counts(weights, block: int):
    """Requests per domain in one block: exact shares, largest remainder."""
    w = np.asarray(weights, float)
    if w.min() < 0 or w.sum() <= 0:
        raise ValueError(f"domain_weights {list(weights)} must be >= 0 "
                         "with a positive sum")
    raw = w / w.sum() * block
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:block
                                                          - counts.sum()]:
        counts[i] += 1
    return counts


class Traffic:
    """Prompts in issue order: ``next()`` -> (domain index, token ids)."""

    def __init__(self, spec: dict, n_domains: int, vocab: int, seed: int):
        weights = list(spec["domain_weights"])
        if len(weights) > n_domains:
            raise ValueError(f"{len(weights)} domain weights for a catalog "
                             f"of {n_domains} domain experts")
        weights += [0.0] * (n_domains - len(weights))
        self.clients = int(spec["clients"])
        self.prompt_len = int(spec["prompt_len"])
        self.vocab = vocab
        self.counts = block_counts(weights, int(spec.get("mix_block", 100)))
        self.rng = rng_for(seed, stream=1)
        self._block: list = []

    def domains_used(self):
        return [i for i, c in enumerate(self.counts) if c > 0]

    def next(self):
        if not self._block:
            block = np.repeat(np.arange(len(self.counts)), self.counts)
            self._block = list(self.rng.permutation(block)[::-1])
        domain = int(self._block.pop())
        tokens = self.rng.integers(0, self.vocab, self.prompt_len,
                                   dtype=np.int32)
        return domain, tokens
