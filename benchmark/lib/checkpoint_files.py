"""A committed checkpoint's arrays read plainly from its files: numpy and
json, nothing of the program.

The layout is the program's (``core/checkpoint.py``'s docstring), read here
as a format: ``<dir>/step_<8 digits>/manifest.json`` lists the leaves
(``index``, ``key`` — the leaf's path in the state as ``jax.tree_util.keystr``
writes it —, ``shape``, ``dtype``), and ``leaf_<5 digits>/`` holds the leaf
as ``.npy`` chunks named by their bounds, ``0-128_0-64.npy`` for
``[0:128, 0:64]``, ``scalar.npy`` for a leaf without axes."""

from __future__ import annotations

import json
import os
from typing import Dict, Iterator, Tuple

import numpy as np


def leaves(ckpt_dir: str, step: int) -> Iterator[Tuple[str, np.ndarray]]:
    """``(key, array)`` of every leaf of checkpoint ``step``: the file
    itself, memory-mapped, where one chunk holds the whole leaf (state that
    was saved replicated), else put together from its chunks; a leaf its
    chunks do not cover raises."""
    step_dir = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(step_dir, "manifest.json")) as f:
        manifest = json.load(f)
    for leaf in manifest["leaves"]:
        leaf_dir = os.path.join(step_dir, f"leaf_{leaf['index']:05d}")
        shape = tuple(leaf["shape"])
        names = sorted(n for n in os.listdir(leaf_dir) if n.endswith(".npy"))
        if len(names) == 1 and shape:
            whole = np.load(os.path.join(leaf_dir, names[0]), mmap_mode="r",
                            allow_pickle=False)
            if whole.shape == shape:
                yield leaf["key"], whole
                continue
        out = np.empty(shape, np.dtype(leaf["dtype"]))
        covered = 0
        for name in names:
            chunk = np.load(os.path.join(leaf_dir, name), allow_pickle=False)
            if name == "scalar.npy":
                out[...] = chunk
            else:
                out[tuple(slice(int(a), int(b)) for a, b in (
                    part.split("-") for part in name[:-4].split("_")))] = chunk
            covered += chunk.size
        if covered != out.size:
            raise ValueError(f"{leaf_dir}: chunks hold {covered} of "
                             f"{out.size} numbers")
        yield leaf["key"], out


def read(ckpt_dir: str, step: int) -> Dict[str, np.ndarray]:
    return dict(leaves(ckpt_dir, step))


def same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    """Whether two arrays have one shape and type and hold the same bits (a
    NaN equals itself, -0.0 is not 0.0): compared as whole numbers of their
    width, in place."""
    if a.shape != b.shape or a.dtype != b.dtype:
        return False
    if a.dtype.kind not in "iub":
        bits = np.dtype(f"u{a.dtype.itemsize}")
        a, b = a.view(bits), b.view(bits)
    return bool(np.array_equal(a, b))
