"""What a rematerialised block keeps for its backward pass: under
``remat_policy="dots"`` every value that costs a matrix product to make
again, and under both policies what is dear to make again AND fits the chip.

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
- the experts a router chose, where the expert layer is told to keep its
  routing (:data:`ROUTED`; 0.5 MB a layer at 16,384 rows), under both: not
  for what the top-k costs to make again but because the rematerialised
  forward is another program than the pass, rounds bf16 elsewhere, and may
  choose OTHER experts at a near-tie — under block diffusion every masked
  row is nearly the mask token's one vector, their near-ties are one, and
  the backward then weighed experts the forward had not run for a quarter
  of a layer's rows (a routed leaf's gradient off by up to 0.88 on the chip
  against 0.015 without remat: PERF.md section 6, PR 51);
- the CANDIDATES, under both: values that carry what a byte of them costs to
  make again, kept dearest first while the compiled step leaves them room.

**The candidates and their cost.** A Mosaic call is no ``dot_general``: what
nothing keeps of the flash forward the kernel makes a second time in every
backward. Under ``full`` an FFN's first products are made a second time too.
A kept byte costs a write and a read, 480 FLOP of a v5e's time (gpt2-medium
gained 2.2% at 1,000 FLOP a byte, PERF.md section 6, PR 30; four of
Phi-4-mini-flash's six FFNs kept at 2,560 gained its cell 5.6%, 19,193 ->
20,263 tokens/s: my chip run, PR 58, call p58h), so by TIME keeping pays at
every size a benchmark cell runs; what limits it is ROOM, and a kept byte
buys as much as it costs to make again:

- the flash forward's ``out`` + ``lse`` (:func:`name_flash`): the call's
  FLOPs (``2 x pairs the mask keeps x (score size + value size)`` a head and
  batch row) over their bytes, near enough ``mean keys a query sees x (score
  size + value size) / value size`` — what the call's own shapes, ``causal``
  and ``window`` say;
- an FFN's ``gate`` and ``up`` (SwiGLU) or ``up`` (GELU), under ``full``
  (:func:`name_products`; ``dots`` keeps them as products already): ``2 x
  rows x width in x width out`` a product over ``rows x width out`` values,
  the width of the product's INPUT in a bf16 program (the second forward's
  ``down`` product is read by nothing either way, and was never made);
- under ``full``, in a SCANNED run (below), every other matrix product of the block that a backward reads, at the same measure —
  the width it contracts (PR 59): an attention projection's finished rows
  (:func:`name_rows`, from ``models/transformer._RowsDense``: q, k and v at
  the model's width; the output map's RESULT at ``heads x head size``, which
  with a kept flash ``out`` leaves nothing of the attention to make again
  but what its own backward reads), a scan mixer's input maps (Mamba-2's
  five, Mamba-1's ``in_x`` and ``in_z``) and a shared expert's first
  products (``ops/moe.py``), each kind one candidate a run. What is named is
  the product's rows, which a backward always reads: where a rotary kernel
  stands behind q and k it runs again on the kept rows (its backward needs
  no input; where the per-head norm rides it — ``ops/rope.py
  rope_rows(norm=)`` — the backward reads exactly these rows). The expert
  layers' GROUPED products are no candidates: they are made inside a
  differentiation rule (``ops/moe.py _routed``), which is traced after the
  block's contexts have closed.

**Where the new kinds are offered.** In a scanned run (several layers, or
one layer a looped pass) the second forward is the backward scan's body, and
real. In a run of ONE layer that no barrier guards XLA has merged the second
forward with the first: every value is alive already, a name there buys a
``reduce_precision`` pass of its own (1.23 ms on each of Laguna's full
layers, PR 38) and is charged to a room it does not use. In a stack whose
runs are ALL runs of one a barrier guards each block (``prevent_cse``:
Phi-4-mini-flash's six) and the second forward is real too — but offered
there, the kinds LOST that cell 0.19%: beside its four FFNs the rule kept one
mixer's maps and a window layer's q and k (461 MB: 6.9 ms of products a
step), the step compiled to 15.126 GiB where it had 14.730, and XLA's own
rematerialisation, which that pressure set off in the last layer's FFN
(``fusion.766.remat`` and its like: +7 ms), took more than they gave
(20,263.1 -> 20,223.8 tokens/s, step 808.48 -> 810.05 ms: my chip run, PR
59, call p59c; there a run of one's bytes cost the sum what they are, not
twice). The block is told its run (:func:`run`: the run's uses) and offers
the new kinds only where :attr:`Run.scanned`; what a run of one offered
before PR 59 (its flash results, a dense FFN's ``gate`` and ``up``) it still
offers, so that those layers — and Phi-4-mini-flash's step — lower to the
text they had.

At the benchmark's cells (a microbatch, every layer and pass of a run; the
room is what the step compiled with nothing kept leaves of a v5e's 15.748
GiB less :data:`MARGIN_BYTES`; the compiles: the rehearsal's, PR 58 and PR
59 — on the chip a process holds 0.06 GiB more beside the step; the gains:
PERF.md section 6, PR 38, PR 58 and PR 59; "2nd": left out by the second
fill, the first choice having compiled over the budget):

======================================================  ===========  =========================
candidate                                               FLOP a byte  bytes, room: kept?
======================================================  ===========  =========================
Phi-4-mini-flash's two whole-sequence differential
calls ``[1, 16384, 20 x 64 / 128]``                          12,100  2 x 170 MB, 3.35 GiB: yes
JoyAI-LLM-Flash ``[2, 8192, 32 x 192 / 128]``, 6 layers      10,084  818 MB, 1.66 GiB: yes
Laguna's window layers' output map ``64 x 128 -> 2048``       8,192  3 x 67 MB, 1.53 GiB: yes
SDAR under the block mask ``[1, 16384, 32 x 128]``, 6         8,070  818 MB, 2.03 GiB: yes
ZAYA1 ``[2, 8192, 8 x 128]``, 6 layers                        8,067  205 MB, 0.67 GiB: yes
Laguna's two full layers ``[2, 8192, 48 x 128]``              8,067  2 x 205 MB, 1.53 GiB: yes
Nemotron 3 Nano, Mellum 2 ``[2, 8192, 32 x 128]``, one        8,067  136 MB, 2.01 / 3.91: yes
SDAR's output map ``[1, 16384, 4096 -> 2048]``, 6             4,096  403 MB, 2.03 GiB: yes
JoyAI-LLM-Flash's ``[2, 8192, 4096 -> 2048]``, 4 scanned      4,096  268 MB, 1.66 GiB: yes
Mellum 2's ``[2, 8192, 4096 -> 2304]``, 3 window layers       4,096  226 MB, 3.91 GiB: yes
Ouro ``[1, 4096, 16 x 128]``, 8 layers x 4 passes             4,034  545 MB, 0.01 GiB: no
the hybrid ``[2, 4096, 32 x 64]``, one layer                  3,973  35 MB, 0.016 GiB: no
Nemotron's two scanned Mamba-2 layers' five input maps        2,688  675 MB, 2.01 GiB: yes
Nemotron's two scanned shared experts' ``up``                 2,688  243 MB, 2.01 GiB: yes
Phi-4-mini-flash's FFNs ``[1, 16384, 2560 -> 10240]``         2,560  6 x 671 MB: four of six
Phi-4-mini-flash's maps (336 MB), q, ``out`` (84), k, v       2,560  runs of one: not offered
Mellum 2's q ``[2, 8192, 2304 -> 4096]``; k, v, 3 layers      2,304  403 + 2 x 50 MB: yes
Laguna's dense layer ``[2, 8192, 2048 -> 8192]``              2,048  537 MB: yes
JoyAI-LLM-Flash's dense layer ``[2, 8192, 2048 -> 7168]``     2,048  470 MB: yes
SDAR's q ``[1, 16384, 2048 -> 4096]``; k, v, 6 layers         2,048  805 MB: 2nd; 2 x 101: yes
Laguna's window layers' q; k, v; shared ``gate``, ``up``      2,048  805; 2 x 101; 101 MB: 2nd
ZAYA1's q, k, v into the latent, 6 layers                     2,048  201 + 2 x 50 MB: yes
JoyAI-LLM-Flash's four shared experts' ``gate``, ``up``       2,048  201 MB: not on the chip
Ouro's FFN ``[1, 4096, 2048 -> 5632]``, 32 uses               2,048  2.95 GB, 0.01 GiB: no
Ouro's q, k, v, ``out``; the hybrid's five layers' maps       2,048  0.01 / 0.016 GiB: no
the hybrid's FFNs ``[2, 4096, 2048 -> 8192]``, 5 + 1          2,048  1.34 GB + 268 MB: no
Mellum 2's window layers ``[2, 8192, 32 x 128]``, 1,024       1,891  409 MB, 3.91 GiB: yes
JoyAI-LLM-Flash's ``q_b`` (contracts the latent's 1,536)      1,536  under the floor
ZAYA1's output map (contracts the latent's 8 x 128)           1,024  under the floor
gpt2-medium, gpt2-xl ``[8, 1024, 16|25 x 64]``                  994  under the floor
Laguna's window layers ``[2, 8192, 64 x 128]``, 512             977  under the floor
Phi-4-mini-flash's window layer, 512                            744  under the floor
======================================================  ===========  =========================

**The rule** (:class:`Chooser`). Kept are the candidates in order of FLOP a
byte, dearest first, each where it still fits the room; one that does not
fit is passed over and the cheaper ones behind it are still tried. What a
candidate states is what keeping it SAVES a byte; of candidates that state
the same (everything that contracts the model's width: Laguna's dense FFN,
its window layers' q, k, v and shared products all read 2,048) the one that
saves more in all goes first — what keeping costs an ARRAY, its rounding
pass and its slice of the layers' stack, is spread over more bytes. A scanned
run of N equal layers is one candidate, N x a layer's bytes x the passes of a
looped stack, kept or left whole (a microbatch's: one is alive at a time); a
run of one stands alone — Phi-4-mini-flash's six layers are six runs of one.
Under :data:`FLOOR_FLOP_PER_BYTE` nothing is kept whatever the room: beside
a kept ``out`` at GPT-2's shapes (994) XLA's memory-space assignment stopped
holding the FFN's input in VMEM and gpt2-xl under ``fsdp=4`` lost 5.0% (PR
30); at 1,891 — the band's ``out`` + ``lse`` of Mellum 2's three window
layers, 409 MB, ``swa_fwd`` 8.72 ms a step made again — keeping GAINED that
cell 1.45% beside everything else kept (53,785 -> 54,565 tokens/s, step
304.58 -> 300.23 ms, ``remat_time_pct`` 4.98 -> 2.14: my chip run, PR 59,
call p59a; the floor was 2,000 until then). Nothing measured lies between
994 and 1,891, and the floor stands at 1,800.

**Where the room comes from.** ``core/train_loop.py Trainer`` alone knows the
step: it traces it once with nothing kept (an open :class:`Chooser` with the
empty plan: :func:`choosing`), compiles, reads ``compiled.memory_analysis()``
against the device's ``bytes_limit`` less what the process holds beside the
step's arguments less :data:`MARGIN_BYTES`, and traces again under what the
rule keeps in that room — where it keeps anything (its docstring has the
rest: the choice remembered beside the compile cache).
Anyone else — a ``jax.grad`` of a loss on its own, every program on the CPU,
where the device states no limit — traces with no chooser open: NO candidate
is kept and the program is that of ``policy=None`` but for the routing (on a
TPU that states no limit the ``Trainer`` warns of it). A job that fills the
chip therefore keeps less, down to nothing, where the constant this rule
replaced (6,000 FLOP a byte, PR 38) kept the flash results whatever the room
and the compiler refused the step (Ouro's and the hybrid's cells are such
jobs: `remat keeps nothing`, their steps and rates the parent's to 0.01%: my
chip run, PR 58, call p58c). A value kept by name in a run of ONE layer that
no barrier guards gains nothing: XLA had merged that layer's second forward
with its first (Laguna's and JoyAI-LLM-Flash's dense layers: steps 441.6 and
554.2 ms before and after, call p58b). A kept value costs the compiled step
a little less than its bytes (Phi-4-mini-flash's: 2.82 GiB kept in 2.58, the
rehearsal's compile, PR 58), and the 0.71 GiB that leaves beside its four
FFNs would hold the fifth: a third trace and compile there bought 0.09%
(20,263 -> 20,281 tokens/s: my chip runs, PR 58, calls p58h, p58g) and is
not made. A value a SCANNED run stacks costs that sum more than its bytes, up
to twice (Mellum 2's ``out`` rows: 0.211 GiB kept read +0.422; SDAR's first
choice 1.887 GiB read +2.055, Laguna's 1.506 read +2.01), though the
compiler's own buffer assignment allocates the bytes (Mellum 2: 11.49 ->
11.71 GiB; my compiles for the described v5e, PR 59): the room is the sum's,
so a room filled to the brim can compile over the budget — SDAR's and
Laguna's do — and the ``Trainer`` then fills ONCE more, in the room at the
price that compile showed, before it lets the step that keeps nothing stand
(``core/train_loop.py _FittedStep._chosen``).

Whoever reads a kept ``out`` has to read the kept rows, not a copy of them
(PR 30: XLA wrote the stack's slice twice and transposed it, 12 ms a step):
``tests/test_tpu_compile_stack.py`` reads the compiled text between the
kernel and its projection, and for a kept q and a kept output map's result
between the product, the rotary kernel and the flash call.

Every other product stays as ``dots_saveable`` keeps it (the MoE's, the
Mamba-2 scan's, the reference attention's): its bias is fused into whatever
reads it, in the forward as in the recomputation. Under ``full`` everything
but the kept names is made again; a block in which nothing is kept lowers to
the program of ``policy=None``.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import (Dict, FrozenSet, Iterator, List, NamedTuple, Optional, Set,
                    Tuple)

import jax
import numpy as np
from jax.ad_checkpoint import checkpoint_name

PROJECTION = "projection"
#: the flash forward's results of a call the chooser keeps (and, under
#: ``dots``, the ``lse`` rows of every call)
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"
#: an FFN's first products where the chooser keeps them: SwiGLU's ``gate``
#: and ``up``, GELU's ``up``
FFN_IN = "ffn_in"
#: under ``full``, where the chooser keeps them in a scanned run
#: (:class:`Run`): an attention projection's finished rows (q, k, v;
#: the output map's result: ``models/transformer._RowsDense``), a scan
#: mixer's input maps, a shared expert's first products
ROWS = "rows"
MIXER_IN = "mixer_in"
SHARED_IN = "shared_in"
#: the experts a router chose, ``[rows, k]`` int32, where the expert layer
#: names them (``ops/moe.py MoeMlp.keep_routing``): a selection made again
#: from a forward that rounds another way may be ANOTHER selection, and the
#: backward then weighs experts the pass did not run
ROUTED = "moe_chosen"
#: what a learned index selected (``ops/index.py``: the packed words, a bit a
#: pair, and the selected scores' ``lse`` rows; 33.6 MB a layer at 16,384
#: rows), kept under both for :data:`ROUTED`'s reason: a ranking made again
#: from a forward that rounds another way may be ANOTHER selection, and the
#: backward would weigh pairs the forward had not scored
SELECTED = "index_selected"
#: the three gradients of the index's own loss, which its ONE kernel forms
#: beside the loss (``ops/index.py kl``: the differentiation rule's
#: residuals; 35.7 MB a layer at 16,384 rows): kept under both, so that the
#: kernel — as much work as the attention's forward — runs once a layer
INDEX_GRADS = "index_grads"
#: a delta-rule kernel's result and the state each of its chunks started from
#: (``ops/kda.py``: the differentiation rule's residuals), ONE candidate at
#: what the chunk form's products cost a byte (:func:`kernel_keeps`)
KDA_OUT = "kda_out"
KDA_STATES = "kda_states"
NAMES = (PROJECTION, FLASH_OUT, FLASH_LSE, FFN_IN, ROWS, MIXER_IN, SHARED_IN,
         ROUTED, SELECTED, INDEX_GRADS, KDA_OUT, KDA_STATES)
#: the names each ``remat_policy`` saves; a candidate that is not kept is not
#: named (``dots`` keeps an FFN's products unnamed, as ``dot_general``s)
KEPT = {
    "full": (FLASH_OUT, FLASH_LSE, FFN_IN, ROWS, MIXER_IN, SHARED_IN, ROUTED,
             SELECTED, INDEX_GRADS, KDA_OUT, KDA_STATES),
    "dots": (PROJECTION, FLASH_OUT, FLASH_LSE, ROUTED, SELECTED, INDEX_GRADS,
             KDA_OUT, KDA_STATES),
}

#: under this many FLOP a byte nothing is kept whatever the room: the table
#: in the module's docstring
FLOOR_FLOP_PER_BYTE = 1800
#: what the chooser leaves of the device's limit beside the compiled step:
#: ``memory_analysis`` is a sum, not the allocator's peak, and a step that
#: compiles to the limit's last megabytes leaves the runtime nothing for a
#: batch sent ahead. The hybrid's cell runs at 15.48 of 15.75 GiB with nothing
#: kept (0.27 spare); a quarter of a GiB keeps its 35 MB candidate out
MARGIN_BYTES = 256 << 20


class Named(NamedTuple):
    """One value named while a :func:`block` is open: its label, its bytes
    and, for a candidate, what a byte of it costs to make again."""
    label: str
    bytes: int
    flop_per_byte: Optional[float] = None
    #: which of the label's values (an attention projection's name)
    what: Optional[str] = None


#: a candidate's place in the program: (the run of layers, what it is —
#: ``flash``, a key of :data:`PRODUCTS`, an attention projection's name —
#: which such value of a layer)
Key = Tuple[str, str, int]
#: the kinds of matrix product a ``full`` block offers (:func:`name_products`)
#: -> the name a kept one is saved by. An FFN's are offered in every run; the
#: others, as an attention projection's :data:`ROWS`, in a scanned run alone
PRODUCTS = {"ffn": FFN_IN, "maps": MIXER_IN, "shared": SHARED_IN}


class Candidate(NamedTuple):
    """What a chooser keeps or leaves whole; ``bytes`` a microbatch's over
    every layer and pass of the run."""
    key: Key
    bytes: int
    flop_per_byte: float

    def __str__(self) -> str:
        run, what, nth = self.key
        return (f"{run or 'a block'} {what}{f' {nth}' if nth else ''} "
                f"{self.bytes / 1e6:,.1f} MB at "
                f"{round(self.flop_per_byte):,} FLOP a byte")


class Chooser:
    """Which candidates a step keeps in ``room`` bytes.

    While the step is traced each candidate is offered once (:meth:`offer`;
    a block traced again — the microbatches' scan, flax's lifts — gets the
    answer it got). With a ``plan`` the answer is whether the key is in it:
    the ``Trainer`` traces once under the empty plan, sizes the step, and
    traces again under what :meth:`fill` keeps in the room that leaves.
    Without one the candidates are kept as they come while they fit — for
    whoever differentiates a loss of their own and can state a room."""

    def __init__(self, room: int, plan: Optional[FrozenSet[Key]] = None):
        self.room = max(int(room), 0)
        self.plan = plan
        #: every candidate at or over the floor, by key, as traced
        self.seen: Dict[Key, Candidate] = {}
        self._kept: Set[Key] = set()

    def offer(self, candidate: Candidate) -> bool:
        key = candidate.key
        if key not in self.seen:
            self.seen[key] = candidate
            if key in self.plan if self.plan is not None else \
                    candidate.bytes <= self.room - self.kept_bytes:
                self._kept.add(key)
        return key in self._kept

    @property
    def kept(self) -> FrozenSet[Key]:
        return frozenset(self._kept)

    @property
    def kept_bytes(self) -> int:
        return sum(self.seen[key].bytes for key in self._kept)

    def ranked(self) -> List[Candidate]:
        """The candidates dearest first; of equals the one that saves more
        in all (what keeping costs an ARRAY — its rounding pass, its slice of
        the layers' stack — is then spread over more bytes), and those equal
        in that too in the order of the trace."""
        return sorted(self.seen.values(),
                      key=lambda c: (-c.flop_per_byte, -c.bytes))

    def fill(self, room: int) -> FrozenSet[Key]:
        """The keys the rule keeps in ``room`` bytes: dearest first, each
        where it still fits."""
        keys = []
        for candidate in self.ranked():
            if candidate.bytes <= room:
                room -= candidate.bytes
                keys.append(candidate.key)
        return frozenset(keys)

    def said(self) -> str:
        """What was kept, in rank order, and the first candidate left out."""
        kept = [str(c) for c in self.ranked() if c.key in self._kept]
        left = [str(c) for c in self.ranked() if c.key not in self._kept]
        return (f"keeps {'; '.join(kept) or 'nothing'}"
                + (f"; the first of {len(left)} left out for want of room: "
                   f"{left[0]}" if left else "; leaves no candidate out"))


@dataclasses.dataclass
class Said:
    """What a block named and offered while it was traced: for its one line
    in the log."""
    policy: Optional[str]
    #: the room of the chooser the block was traced under (None: none open)
    room: Optional[int] = None
    named: List[Named] = dataclasses.field(default_factory=list)
    #: candidates not kept, ``label`` what they are, -> why
    left: List[Tuple[Named, str]] = dataclasses.field(default_factory=list)
    #: how many candidates of a kind the block has offered
    offered: Dict[str, int] = dataclasses.field(default_factory=dict)

    def line(self) -> str:
        """The room given, what the block keeps by name — dearest first,
        with bytes and FLOP a byte — and each candidate left out, with why."""
        def listed(values):
            return ", ".join(
                f"{value.label}{f' {value.what}' if value.what else ''} "
                f"{value.bytes / 1e6:.1f} MB"
                + ("" if value.flop_per_byte is None else
                   f" at {round(value.flop_per_byte):,} FLOP a byte")
                for value in values) or "nothing"

        kept = sorted((value for value in self.named
                       if value.label in KEPT[self.policy]),
                      key=lambda value: -(value.flop_per_byte or 0))
        return (("no room stated" if self.room is None else
                 "given no room (the trace a step is sized by keeps nothing)"
                 if not self.room else
                 f"given {self.room / 2**30:.3f} GiB of room")
                + f", keeps by name {listed(kept)} a microbatch as traced (a "
                f"kernel's per shard under a mesh), "
                + ("beside its unnamed products" if self.policy == "dots"
                   else "and makes everything else again")
                + "; candidates left out: " + ("; ".join(
                    f"{listed([value])} ({why})" for value, why in self.left)
                    or "none"))


class Run(NamedTuple):
    """The run of layers being traced: its name among the stack's parameters
    and how many times a value kept in its block is held (layers x passes)."""
    name: str = ""
    uses: int = 1

    @property
    def scanned(self) -> bool:
        """Whether the run is a scan's body (several layers, or one layer a
        looped pass): its second forward is the backward scan's, and real.
        The second forward of a run of ONE XLA has merged with the first
        unless a barrier guards it: its values are alive already, and a name
        there buys a rounding pass."""
        return self.uses > 1


_chooser: contextvars.ContextVar[Optional[Chooser]] = \
    contextvars.ContextVar("easydl_remat_chooser", default=None)
_run: contextvars.ContextVar[Run] = \
    contextvars.ContextVar("easydl_remat_run", default=Run())
_said: contextvars.ContextVar[Optional[Said]] = \
    contextvars.ContextVar("easydl_remat_block", default=None)


@contextlib.contextmanager
def _set(var: contextvars.ContextVar, value) -> Iterator:
    token = var.set(value)
    try:
        yield value
    finally:
        var.reset(token)


def choosing(chooser: Optional[Chooser]):
    """``chooser`` decides what the blocks traced while this is open keep."""
    return _set(_chooser, chooser)


def run(name: str, uses: int):
    """The blocks traced while this is open are the run ``name``, whose kept
    values are held ``uses`` times."""
    return _set(_run, Run(name, uses))


def block(policy: Optional[str]):
    """A block under ``remat_policy`` ``policy`` (None: no remat) is traced
    while this is open; yields its :class:`Said`."""
    chooser = _chooser.get()
    return _set(_said, Said(policy, chooser and chooser.room))


def name(x: jax.Array, label: str, flop_per_byte: Optional[float] = None,
         what: Optional[str] = None) -> jax.Array:
    """``x`` under ``label`` (one of :data:`NAMES`): :func:`policy` saves it
    if the label is in the policy's :data:`KEPT`."""
    assert label in NAMES, label
    said = _said.get()
    if said is not None:
        said.named.append(Named(label, _bytes(x), flop_per_byte, what))
    return checkpoint_name(x, label)


def _bytes(*arrays) -> int:
    return sum(x.size * x.dtype.itemsize for x in arrays)


def _offer(what: str, nbytes: int, cost: float) -> bool:
    """Whether the block being traced keeps a candidate of ``nbytes`` a layer
    that costs ``cost`` FLOP a byte to make again."""
    said, chooser, at = _said.get(), _chooser.get(), _run.get()
    if said is None or said.policy is None:
        return False
    nth = said.offered[what] = said.offered.get(what, -1) + 1
    if cost < FLOOR_FLOP_PER_BYTE:
        why = f"under the floor of {FLOOR_FLOP_PER_BYTE:,}"
    elif chooser is None:
        why = "no room stated"
    elif chooser.offer(Candidate((at.name, what, nth), nbytes * at.uses,
                                 cost)):
        return True
    else:
        why = (f"no room: {nbytes * at.uses / 1e6:,.1f} MB in all, "
               f"{(chooser.room - chooser.kept_bytes) / 1e6:,.1f} MB left"
               if chooser.plan is None else "left out by the room")
    said.left.append((Named(what, nbytes, cost), why))
    return False


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
    return flop / _bytes(out, lse)


def flash_keeps(out: jax.ShapeDtypeStruct, lse: jax.ShapeDtypeStruct, *,
                s_k: int, head_dim: int, causal: bool, window: Optional[int],
                pairs: Optional[int] = None) -> Tuple[bool, bool]:
    """Whether the block being traced keeps a flash call's ``out`` and its
    ``lse`` rows, the call a candidate by :func:`flash_flop_per_byte`: both
    where the chooser keeps it; where it does not, neither, but for the rows
    under ``dots``. Asked where the CALL is traced, inside its block: the
    differentiation rule that names the two (:func:`name_flash`) is traced
    when the block is differentiated, after the block's trace is over."""
    cost = flash_flop_per_byte(out, lse, s_k=s_k, head_dim=head_dim,
                               causal=causal, window=window, pairs=pairs)
    keep = _offer("flash", _bytes(out, lse), cost)
    said = _said.get()
    rows = keep or (said is not None and said.policy == "dots")
    for kept, value, label in ((keep, out, FLASH_OUT), (rows, lse, FLASH_LSE)):
        if kept:
            said.named.append(Named(label, _bytes(value), cost))
    return keep, rows


def name_flash(out: jax.Array, lse: jax.Array,
               keeps: Tuple[bool, bool]) -> Tuple[jax.Array, jax.Array]:
    """The flash forward's two results, each under its name
    (:data:`FLASH_OUT`, :data:`FLASH_LSE`) where :func:`flash_keeps` said so."""
    return (checkpoint_name(out, FLASH_OUT) if keeps[0] else out,
            checkpoint_name(lse, FLASH_LSE) if keeps[1] else lse)


def kernel_keeps(what: str, kept: Tuple[jax.ShapeDtypeStruct, ...],
                 labels: Tuple[str, ...], flop: float) -> bool:
    """Whether the block being traced keeps the results ``kept`` of a Mosaic
    call that is no flash kernel (``what``: ``kda``), ONE candidate at the
    call's ``flop`` over their bytes — asked where the call is traced, as
    :func:`flash_keeps` is; the call's differentiation rule then names them
    by ``labels``."""
    nbytes = _bytes(*kept)
    cost = flop / nbytes
    keep = _offer(what, nbytes, cost)
    if keep:
        _said.get().named.extend(
            Named(label, _bytes(value), cost)
            for label, value in zip(labels, kept))
    return keep


def _offer_products(what: str, products: Tuple[jax.Array, ...],
                    contracted: int) -> Optional[float]:
    """What a byte of ``products`` — each ``rows x contracted`` times
    ``contracted x width`` — costs to make again, where the ``full`` block
    being traced keeps them as the candidate ``what``; else None. Any kind
    but an FFN's is a candidate only in a scanned run (:attr:`Run.scanned`)."""
    said = _said.get()
    if said is None or said.policy != "full" or not (
            what == "ffn" or _run.get().scanned):
        return None
    nbytes = _bytes(*products)
    cost = 2 * contracted * sum(x.size for x in products) / nbytes
    return cost if _offer(what, nbytes, cost) else None


def name_products(products: Tuple[jax.Array, ...], contracted: int,
                  what: str = "ffn") -> Tuple[jax.Array, ...]:
    """The products of one input ``contracted`` wide that a backward reads —
    ``what``, a key of :data:`PRODUCTS`: an FFN's first (SwiGLU's ``gate`` and
    ``up``, GELU's ``up``), a scan mixer's input maps, a shared expert's
    first — one candidate under ``full``: under the kind's name where the
    block's chooser keeps them. ``dots`` keeps them as the products they
    are."""
    return tuple(map(product_namer(products, contracted, what), products))


def product_namer(products: Tuple[jax.ShapeDtypeStruct, ...], contracted: int,
                  what: str):
    """:func:`name_products` for a caller that makes its products one by one
    between other operations (a program's text follows the order its values
    are made in): asked with their shapes, it gives what names each."""
    cost = _offer_products(what, products, contracted)
    if cost is None:
        return lambda x: x
    return lambda x: name(x, PRODUCTS[what], cost)


def name_rows(rows: jax.Array, contracted: int, what: str) -> jax.Array:
    """An attention projection's finished rows (``what``: ``q``, ``k``,
    ``v``, ``out``; the product of an input ``contracted`` wide, its bias
    added): under ``dots`` by :data:`PROJECTION`; under ``full`` a candidate
    at the width it contracts, under :data:`ROWS` where the block's chooser
    keeps it."""
    cost = _offer_products(what, (rows,), contracted)
    if cost is None:
        return name(rows, PROJECTION)
    return name(rows, ROWS, cost, what)


#: ``full``'s policy is ONE object, as the ``nothing_saveable`` of
#: ``policy=None`` is: jax caches a block's inner functions, split for the
#: backward, by (function, policy), and a policy made anew a call lowers each
#: of them once a use — a block in which nothing is kept would no longer
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
