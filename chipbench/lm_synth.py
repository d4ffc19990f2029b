"""Packed pretraining batches from a seed (the ``lm`` runner's traffic
generator): token ids Zipf-distributed over a vocabulary slice, cut into
documents of log-normal length that each begin with id 0, packed end to
end into fixed-length sequences."""

from __future__ import annotations

import numpy as np


class PackedBatches:
    """``next()`` gives one [sequences, seq_len] int32 batch."""

    def __init__(self, seed: int, vocab: int, mix: dict):
        self.rng = np.random.default_rng(seed)
        self.seq_len = mix["seq_len"]
        self.sequences = mix["sequences_per_launch"]
        self.mu = np.log(mix["doc_length_median"])
        self.sigma = mix["doc_length_sigma"]
        # ids 1..vocab-1 by Zipf rank; id 0 begins a document
        weights = np.arange(1, vocab, dtype=np.float64) ** -mix["zipf_exponent"]
        self.cdf = np.cumsum(weights / weights.sum())
        self.left = 0  # tokens left of the document being packed

    def next(self) -> np.ndarray:
        n = self.sequences * self.seq_len
        ids = 1 + np.searchsorted(self.cdf, self.rng.random(n))
        ids = np.minimum(ids, len(self.cdf)).astype(np.int32)
        at = self.left
        while at < n:
            ids[at] = 0
            at += max(1, int(self.rng.lognormal(self.mu, self.sigma)))
        self.left = at - n
        return ids.reshape(self.sequences, self.seq_len)
