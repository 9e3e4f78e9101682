"""What a rematerialised block keeps for its backward pass: under
``remat_policy="dots"`` every value that costs a matrix product to make
again, and under both policies of what costs a kernel as much as pays.

jax's ``dots_saveable`` keeps the result of every ``dot_general``. Values it
gets wrong are named where they are made (:func:`name`, which is
``jax.ad_checkpoint.checkpoint_name``), and :func:`policy` saves the names in
:data:`KEPT` — under ``dots`` beside jax's, under ``full`` alone:

- a projection whose result a kernel reads (q, k, v; ``out`` with them:
  ``models/transformer._RowsDense``), AFTER its bias (``dots``): kept before
  it, the bias was added, and q, k, v written, again in every backward. The
  product in front of the bias is then read by nothing and is dropped: the
  sum costs no byte;
- the flash forward's ``lse`` as dense rows (``ops/flash_attention.
  _flash_fwd``, inside the differentiation rule): 0.5 MB a layer at GPT-2's
  shapes, under ``dots`` whatever the call;
- the flash forward's ``out`` and ``lse`` where the CALL is dear to make
  again (:func:`name_flash`), under ``dots`` and ``full`` alike;
- the experts a router chose, where the expert layer is told to keep its
  routing (:data:`ROUTED`; 0.5 MB a layer at 16,384 rows), under both: not
  for what the top-k costs to make again but because the rematerialised
  forward is another program than the pass, rounds bf16 elsewhere, and may
  choose OTHER experts at a near-tie — under block diffusion every masked
  row is nearly the mask token's one vector, their near-ties are one, and
  the backward then weighed experts the forward had not run for a quarter
  of a layer's rows (a routed leaf's gradient off by up to 0.88 on the chip
  against 0.015 without remat: PERF.md section 6, PR 51).

A Mosaic call is no ``dot_general``: what nothing keeps of it the forward
kernel makes a second time in every backward of a scanned run of layers. By
TIME, keeping ``out`` pays at every size a benchmark cell runs (a byte kept
costs a write and a read, 480 FLOP of a v5e's time; gpt2-medium gained 2.2%
at 1,000 FLOP a byte, PERF.md section 6, PR 30). What limits it is ROOM, and
a kept byte buys as much as the call costs to make again: the forward's
FLOPs (``2 x pairs the mask keeps x (score size + value size)`` a head and
batch row) over the bytes of ``out`` + ``lse``, near enough ``mean keys a
query sees x (score size + value size) / value size`` — something the call's
own shapes, ``causal`` and ``window`` say. At the benchmark's cells (PERF.md
section 6, PR 38):

====================================================  ==========  =======================
call ``[batch, seq, heads x score / value size]``     FLOP a byte room a chip for bytes
====================================================  ==========  =======================
JoyAI-LLM-Flash ``[2, 8192, 32 x 192 / 128]``             10,084  1.95 GiB for 0.51: KEPT
ZAYA1, Laguna's full layers ``[2, 8192, 8|48 x 128]``      8,067  0.88 GiB for 0.19: KEPT
Ouro ``[1, 4096, 16 x 128]``, 32 uses x 4 microbatches     4,034  0.26 GiB for 0.50
the hybrid ``[2, 4096, 32 x 64]``                          3,973  0.15 GiB, one layer
Laguna's window layers ``[2, 8192, 64 x 128]``, 512          977  0.90 GiB for 0.75
gpt2-medium, gpt2-xl ``[8, 1024, 16|25 x 64]``               994  85 MB for 0.375 GiB
====================================================  ==========  =======================

:data:`FLASH_KEEP_FLOP_PER_BYTE` stands between the cells that have the gain
AND the room (8,067 and up) and those that do not fit or gain a tenth as much
a byte (4,034 and down: it is room, not time, that puts Ouro under it);
nothing measured lies between. What the rule cannot see is room itself: a
job of long sequences that filled the chip under a ``full`` that kept
nothing is now refused by the compiler (the block's logged-once ``remat
full:`` line says how many bytes a layer the rule holds). No cell is such a
job; a chooser from ``compiled.memory_analysis()`` is ROADMAP Design 3's
open item.

Whoever reads a kept ``out`` has to read the kept rows, not a copy of them
(PR 30: XLA wrote the stack's slice twice and transposed it, 12 ms a step):
``tests/test_tpu_compile.py`` reads the compiled text between the kernel and
its projection. Beside a kept ``out`` at GPT-2's shapes XLA's memory-space
assignment stopped holding the FFN's input in VMEM (gpt2-xl under ``fsdp=4``
5.0% slower, PR 30); the rule does not pick those calls.

Every other product stays as ``dots_saveable`` keeps it (the FFN's, the
MoE's, the Mamba-2 scan's, the reference attention's): its bias is fused
into whatever reads it, in the forward as in the recomputation. Under
``full`` everything but the picked names is made again; a block in which
nothing is picked lowers to the program of ``policy=None``.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, List, NamedTuple, Optional, Tuple

import jax
import numpy as np
from jax.ad_checkpoint import checkpoint_name

PROJECTION = "projection"
#: the flash forward's results of a call the rule picks ...
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"
#: ... and of a call cheap to make again, for the room it would take
FLASH_OUT_CHEAP = "flash_out_cheap"
FLASH_LSE_CHEAP = "flash_lse_cheap"
#: the experts a router chose, ``[rows, k]`` int32, where the expert layer
#: names them (``ops/moe.py MoeMlp.keep_routing``): a selection made again
#: from a forward that rounds another way may be ANOTHER selection, and the
#: backward then weighs experts the pass did not run
ROUTED = "moe_chosen"
NAMES = (PROJECTION, FLASH_OUT, FLASH_LSE, FLASH_OUT_CHEAP, FLASH_LSE_CHEAP,
         ROUTED)
#: the names each ``remat_policy`` saves; :data:`FLASH_OUT_CHEAP` is in
#: neither (it is named for the block's line in the log)
KEPT = {
    "full": (FLASH_OUT, FLASH_LSE, ROUTED),
    "dots": (PROJECTION, FLASH_OUT, FLASH_LSE, FLASH_LSE_CHEAP, ROUTED),
}

#: a flash forward whose ``out`` + ``lse`` cost at least this many FLOP a
#: byte to make again is kept: the table in the module's docstring
FLASH_KEEP_FLOP_PER_BYTE = 6000


class Named(NamedTuple):
    """One value named while a :func:`tally` is open: its label, its bytes
    and, for a flash forward's result, the rule's FLOP a byte of its call."""
    label: str
    bytes: int
    flop_per_byte: Optional[float] = None


_tally: contextvars.ContextVar[Optional[List[Named]]] = \
    contextvars.ContextVar("easydl_remat_tally", default=None)


def name(x: jax.Array, label: str,
         flop_per_byte: Optional[float] = None) -> jax.Array:
    """``x`` under ``label`` (one of :data:`NAMES`): :func:`policy` saves it
    if the label is in the policy's :data:`KEPT`."""
    assert label in NAMES, label
    named = _tally.get()
    if named is not None:
        named.append(Named(label, x.size * x.dtype.itemsize, flop_per_byte))
    return checkpoint_name(x, label)


def seen_pairs(s_q: int, s_k: int, causal: bool,
               window: Optional[int]) -> int:
    """(query, key) pairs the flash kernels' mask keeps: all of them, or
    under ``causal`` the keys up to a query's own (the last query sees the
    last key), and of those at most the nearest ``window``."""
    if not causal:
        return s_q * s_k
    reach = s_k if window is None else window
    return int(np.clip(np.arange(s_q) + (s_k - s_q + 1), 0, reach).sum())


def flash_flop_per_byte(out: jax.ShapeDtypeStruct, lse: jax.ShapeDtypeStruct,
                        *, s_k: int, head_dim: int, causal: bool,
                        window: Optional[int],
                        pairs: Optional[int] = None) -> float:
    """What a byte of a flash forward's results costs to make again: the
    call's FLOPs over the bytes of ``out`` ``[batch, s_q, heads x value
    size]`` and ``lse`` ``[batch, heads, s_q]``; ``head_dim`` is the score
    size. ``pairs``: the (query, key) pairs a mask of another kind keeps
    (block diffusion's: ``BlockDiffusion.pairs``), counted by the caller."""
    batch, heads, s_q = lse.shape
    if pairs is None:
        pairs = seen_pairs(s_q, s_k, causal, window)
    flop = 2 * pairs * batch * heads \
        * (head_dim + out.shape[-1] // heads)
    return flop / (out.size * out.dtype.itemsize
                   + lse.size * lse.dtype.itemsize)


def name_flash(out: jax.Array, lse: jax.Array, *, s_k: int, head_dim: int,
               causal: bool, window: Optional[int],
               pairs: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """The flash forward's two results under the names the rule gives them:
    :data:`FLASH_OUT` and :data:`FLASH_LSE` where the call costs
    :data:`FLASH_KEEP_FLOP_PER_BYTE` or more, the ``_CHEAP`` pair below."""
    cost = flash_flop_per_byte(out, lse, s_k=s_k, head_dim=head_dim,
                               causal=causal, window=window, pairs=pairs)
    labels = (FLASH_OUT, FLASH_LSE) if cost >= FLASH_KEEP_FLOP_PER_BYTE \
        else (FLASH_OUT_CHEAP, FLASH_LSE_CHEAP)
    return name(out, labels[0], cost), name(lse, labels[1], cost)


#: ``full``'s policy is ONE object, as the ``nothing_saveable`` of
#: ``policy=None`` is: jax caches a block's inner functions, split for the
#: backward, by (function, policy), and a policy made anew a call lowers each
#: of them once a use — a block in which nothing is picked would no longer
#: lower to the text it had
_FULL = jax.checkpoint_policies.save_only_these_names(*KEPT["full"])


def policy(remat_policy: str):
    """The ``jax.checkpoint`` policy of ``remat_policy`` (``"full"`` or
    ``"dots"``; ``dots``' is made anew a call, as it was when GPT-2's three
    lowered programs were pinned)."""
    if remat_policy == "full":
        return _FULL
    policies = jax.checkpoint_policies
    return policies.save_from_both_policies(
        policies.dots_saveable,
        policies.save_only_these_names(*KEPT[remat_policy]))


@contextlib.contextmanager
def tally() -> Iterator[List[Named]]:
    """Every value named while this is open, in order: a trace-time count,
    for a block's one line in the log."""
    named: List[Named] = []
    token = _tally.set(named)
    try:
        yield named
    finally:
        _tally.reset(token)
