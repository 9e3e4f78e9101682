"""What a rematerialised block keeps for its backward pass under
``remat_policy="dots"``: every value that costs a matrix product to make
again, and of what costs a kernel as much as pays.

jax's ``dots_saveable`` keeps the result of every ``dot_general``. Values it
gets wrong are named where they are made (:func:`name`, which is
``jax.ad_checkpoint.checkpoint_name``), and :func:`dots_policy` saves the
names in :data:`KEPT` beside it:

- a projection whose result a kernel reads (q, k, v; ``out`` with them:
  ``models/transformer._RowsDense``), AFTER its bias: kept before it, the
  bias was added, and q, k, v written, again in every backward. The product
  in front of the bias is then read by nothing and is dropped: the sum costs
  no byte;
- the flash forward's ``lse`` as dense rows (``ops/flash_attention.
  _flash_fwd``, inside the differentiation rule): 0.5 MB a layer.

The flash forward's ``out`` carries a name too (:data:`FLASH_OUT`) and is
NOT kept: a Mosaic call is no ``dot_general``, so the forward kernel runs a
second time in every backward (4.4% of gpt2-medium's step). Kept — add the
name to :data:`KEPT`, and pin the input of ``out``'s projection to rows —
the kernel runs once, but the chip measured what that buys (PERF.md section
6, PR 30): gpt2-medium +2.2% where this policy gives +1.15%, at 15.668 of
the chip's 15.75 GiB (``out`` is 0.375 GiB over 24 layers; 85 MB spare at
launch), and gpt2-xl under ``fsdp=4`` 5.0% SLOWER than with nothing named:
beside the kept ``out`` XLA's memory-space assignment no longer holds the
FFN's input in VMEM and its forward products run 8 ms (medium) and 16 ms
(XL) a step slower. It waits for a lever on that, and for room.

Every other product stays as ``dots_saveable`` keeps it (the FFN's, the
MoE's, the Mamba-2 scan's, the reference attention's): its bias is fused
into whatever reads it, in the forward as in the recomputation. Under any
other policy (``"full"`` is ``policy=None``) a name is inert.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, List, Optional, Tuple

import jax
from jax.ad_checkpoint import checkpoint_name

PROJECTION = "projection"
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"
NAMES = (PROJECTION, FLASH_OUT, FLASH_LSE)
#: the names remat ``dots`` saves; :data:`FLASH_OUT` is not among them
KEPT = (PROJECTION, FLASH_LSE)

#: (name, bytes) of the values named while a :func:`tally` is open
_tally: contextvars.ContextVar[Optional[List[Tuple[str, int]]]] = \
    contextvars.ContextVar("easydl_remat_tally", default=None)


def name(x: jax.Array, label: str) -> jax.Array:
    """``x`` under ``label`` (one of :data:`NAMES`): :func:`dots_policy` saves
    it if the label is in :data:`KEPT`."""
    assert label in NAMES, label
    named = _tally.get()
    if named is not None:
        named.append((label, x.size * x.dtype.itemsize))
    return checkpoint_name(x, label)


def dots_policy():
    """The ``jax.checkpoint`` policy of ``remat_policy="dots"``."""
    policies = jax.checkpoint_policies
    return policies.save_from_both_policies(
        policies.dots_saveable, policies.save_only_these_names(*KEPT))


@contextlib.contextmanager
def tally() -> Iterator[List[Tuple[str, int]]]:
    """The ``(name, bytes)`` of every value named while this is open, in
    order: a trace-time count, for a block's one line in the log."""
    named: List[Tuple[str, int]] = []
    token = _tally.set(named)
    try:
        yield named
    finally:
        _tally.reset(token)
