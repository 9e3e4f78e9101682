"""Traffic for the training cells, made from the seed and nothing else."""

from __future__ import annotations

import os
from typing import Dict, Iterator

import numpy as np


def token_batches(seed: int, global_batch: int, seq_len: int, vocab: int,
                  support: int) -> Iterator[Dict[str, np.ndarray]]:
    """Endless ``{"inputs", "targets"}`` batches of ``[global_batch,
    seq_len]`` int32 ids, targets the inputs shifted by one, each id drawn
    from ``support`` of the vocabulary's ids chosen by the seed: something a
    language model can learn, so the loss falls and stays finite (a stream
    uniform over the whole vocabulary has nothing to learn)."""
    rng = np.random.default_rng(seed)
    ids = rng.choice(vocab, min(support, vocab), replace=False)
    while True:
        window = ids[rng.integers(0, len(ids), (global_batch, seq_len + 1))]
        window = window.astype(np.int32)
        yield {"inputs": window[:, :-1], "targets": window[:, 1:]}


def write_corpus(directory: str, seed: int, vocab: int,
                 n_tokens: int, support: int = 512) -> str:
    """``<directory>/tokens-0.npy``: ``n_tokens`` draws from ``support`` of
    the vocabulary's ids — a shard the program's file dataset reads, so that
    the data cursor is saved and restored with the model (the pattern of
    ``chip_smoke.corpus``, copied, not imported). numpy only."""
    os.makedirs(directory, exist_ok=True)
    rng = np.random.default_rng(seed)
    ids = rng.choice(vocab, min(support, vocab), replace=False)
    np.save(os.path.join(directory, "tokens-0.npy"),
            ids[rng.integers(0, len(ids), n_tokens)].astype(np.int32))
    return directory
