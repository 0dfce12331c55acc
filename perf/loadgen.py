"""Inputs from the seed: one general generator, parameters in the cell's file.

A pure function of its arguments; nothing here imports the program. Training
traffic is a token corpus; the open-loop request generator that a serving
cell needs (due times fixed by the mix, latencies taken from the due time)
comes with that cell (PERF.md 7).
"""

import numpy as np


def token_corpus(seed, vocab, samples, seq_len):
    """(samples, seq_len + 1) int32 rows from the seed; row r gives inputs
    [:-1] and labels [1:], and rows all differ. A markov-ish stream (each
    token is the last plus 1..4, modulo ``vocab``): structure a model learns
    within tens of steps, so the window's losses fall as a real run's do."""
    rng = np.random.default_rng([int(seed), 0x636F7270])
    n = samples * (seq_len + 1)
    start = rng.integers(0, vocab)
    toks = (start + np.cumsum(rng.integers(1, 5, size=n))) % vocab
    return toks.astype(np.int32).reshape(samples, seq_len + 1)
