"""Training input: batches of uniform random token ids, labels = ids.

Parameters (the workload file's ``traffic`` section): ``seq_len``. The
batch's rows come from the engine (micro-batch x accumulation x data
parallel width), the vocabulary from the configuration.
"""
import numpy as np


def batches(params, seed, rows, vocab, accumulation=1):
    """An endless iterator of ``(ids, labels)`` stacked as
    (accumulation, rows, seq_len) int32, a new batch every step, the
    same stream for the same seed."""
    rng = np.random.default_rng([seed, 1])
    shape = (accumulation, rows, params["seq_len"])
    while True:
        ids = rng.integers(0, vocab, shape, dtype=np.int32)
        yield ids, ids
