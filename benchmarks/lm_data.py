"""The training job's token stream: learnable sequences from ``--seed``.

A copy of what ``SyntheticLMDataModule`` makes (arithmetic progressions
modulo the vocabulary, so that the loss has to fall), kept here because the
yardstick may not move with the program. Every row differs: its start and
stride come from the row's own draw.
"""
from __future__ import annotations

import numpy as np


def rows(seed: int, n: int, seq_len: int, vocab: int) -> np.ndarray:
    """[n, seq_len] int32; the same seed gives the same rows."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0x4C4D])
    if n > 3 * vocab:
        raise ValueError(f"{n} rows cannot all differ: {3 * vocab} (start, stride) pairs")
    pair = rng.permutation(3 * vocab)[:n].astype(np.int64)[:, None]  # no two alike
    starts, strides = pair % vocab, 1 + pair // vocab
    seq = (starts + strides * np.arange(seq_len, dtype=np.int64)[None, :]) % vocab
    return seq.astype(np.int32)
