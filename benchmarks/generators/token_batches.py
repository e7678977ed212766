"""Training batches from a seed: uniform random token ids, every row its own.

Parameters (traffic file): ``batch`` rows of ``sequence_length`` tokens. Labels
are the ids shifted left by one (next-token prediction over packed text); the
last label of a row is drawn too, so no position is ignored. The stream is a
pure function of the seed: the reference replays the first batches by asking
again with the same seed.
"""
from __future__ import annotations

import numpy as np


def batches(seed, traffic, cfg):
    """Yield ``(ids, labels)`` int32 arrays of shape (batch, sequence_length),
    without end."""
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    shape = (int(traffic["batch"]), int(traffic["sequence_length"]) + 1)
    vocab = int(cfg["vocab_size"])
    while True:
        draw = rng.integers(0, vocab, shape, dtype=np.int32)
        yield np.ascontiguousarray(draw[:, :-1]), np.ascontiguousarray(draw[:, 1:])
