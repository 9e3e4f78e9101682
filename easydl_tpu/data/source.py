"""How a data directory becomes a data source: one probe, for the elastic
worker, the zoo runner and the evaluator alike.

The directory says what it holds: ``images.npy`` (array images),
``sparse.npy`` (click logs), else ``tokens-*.npy`` shards. A new file format
(a packing source for documents) is wired in here and nowhere else.
"""

from __future__ import annotations

import os
from typing import Any, Optional

from easydl_tpu.data.clicks import ClickLogDataset
from easydl_tpu.data.datasets import ArrayImageDataset, TokenFileDataset


def open_dataset(data_dir: str, bundle: Any, *, batch_size: int,
                 rank: int = 0, world: int = 1, seq_len: int = 0,
                 split: str = "train", val_fraction: float = 0.0,
                 seed: int = 0):
    """The dataset under ``data_dir`` matching ``bundle``'s input contract,
    this rank's share of ``split``.

    For token shards ``seq_len`` 0 means the model's own: the length of the
    bundle's synthetic stream (its actual config) — a hardcoded fallback
    would silently train a long-context model on short windows. ValueError
    where neither says."""
    share = dict(batch_size=batch_size, rank=rank, world=world, seed=seed,
                 split=split, val_fraction=val_fraction)
    if os.path.exists(os.path.join(data_dir, "images.npy")):
        return ArrayImageDataset(data_dir, **share)
    if os.path.exists(os.path.join(data_dir, "sparse.npy")):
        return ClickLogDataset(data_dir, **share)
    seq_len = seq_len or getattr(bundle.make_data(1), "seq_len", 0)
    if not seq_len:
        raise ValueError(
            f"cannot infer seq_len for model {bundle.name!r}; state it")
    return TokenFileDataset(data_dir, seq_len=seq_len, **share)


def restore_cursor(source: Any, ckpt: Any, step: int) -> Optional[dict]:
    """Resume ``source``'s cursor from the ``data_state`` that rode the
    checkpoint of ``step`` (world/batch-tagged, so a reshaped generation
    rescales it); returns it, None where the checkpoint carries none."""
    state = ckpt.metadata(step).get("metadata", {}).get("data_state")
    if state:
        source.restore_state(state)
    return state
