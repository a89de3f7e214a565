"""The benchmark's token generator, read from a traffic file.

A copy of the program's synthetic pipeline, kept here so that no later
change to the program changes what the benchmark feeds it: Zipf-distributed
token ids with a copy structure (the second half of a share of the rows
repeats the first half), so a language model has something to learn.  The
same seed gives the same batches; every seed gives the same sizes.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


class TokenStream:
    """Endless ``{"tokens", "labels"}`` batches of ``int32 (batch, seq)``.

    ``traffic`` keys: ``global_batch``, ``seq_len``, ``zipf_a`` (Zipf
    exponent of the token ids), ``copy_share`` (share of rows whose second
    half repeats the first)."""

    def __init__(self, traffic: Dict, vocab_size: int, seed: int):
        self.batch = int(traffic["global_batch"])
        self.seq = int(traffic["seq_len"])
        self.zipf_a = float(traffic["zipf_a"])
        self.copy_share = float(traffic["copy_share"])
        self.vocab = int(vocab_size)
        self.rng = np.random.default_rng(seed)

    def __iter__(self):
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        z = self.rng.zipf(self.zipf_a, size=(self.batch, self.seq))
        toks = (z - 1) % self.vocab
        half = self.seq // 2
        rows = self.rng.random(self.batch) < self.copy_share
        toks[rows, half:2 * half] = toks[rows, :half]
        toks = toks.astype(np.int32)
        return {"tokens": toks, "labels": toks}
