"""Pallas TPU flash attention (forward + backward).

Memory-efficient attention: the [S, S] score matrix never hits HBM — each
grid cell streams K/V blocks through VMEM with an online softmax (running
max + normaliser), so HBM traffic is O(S·d) instead of O(S²). This is the
hot op the reference would have written in CUDA (SURVEY.md §2.1 item 5); on
TPU it is a Pallas kernel tiled for the MXU (block sizes multiples of 128
lanes).

Backward follows the standard flash decomposition: save per-row logsumexp
``lse`` from the forward; recompute P = exp(qkᵀ·scale − lse) blockwise; the
rowwise ``delta = Σ dO∘O`` term is formed inside the kernels from an ``O``
operand (a product and a turn on the XLU a Q-block, under the kernels'
compute, where an XLA pass over dO and O stood in the open). The backward
is ONE kernel on the grid ``(batch row, lane block, K-block)`` that walks
the K-block's live Q-blocks: a pair's score
tile, ``exp``, ``dP``, ``delta`` and ``dS`` are made once and feed three
products, ``dV += Pᵀ dO``, ``dK += dSᵀ Q`` and ``dQᵀ[Q-block] += Kᵀ dSᵀ`` —
five products a pair, walked in tiles (below). dq's float32 sum lives in
VMEM scratch, ``[Q-blocks, cell heads · head_dim, block_q]`` (transposed, a
Q-block a leading index), zeroed at K-block 0, added to in ascending K-block
order, and turned, scaled and rounded once at the last K-block into a dq
block that stays resident while the K-block axis runs
(``dimension_semantics``: that axis ``arbitrary``); rows no key sees stay
zero. The call says what VMEM it needs from what it holds (q, O, dO and the
dq block of a cell's heads whole, twice, and the sums: 56 MB at 8,192 x two
heads of 192 / 128). Every call takes it, the short ones too (below: until
PR 60 a head of at most 16 block pairs had two kernels of its own, dq
walking K-blocks and dk/dv Q-blocks, each making the pair's tile for itself:
seven products a pair); the band path alone keeps a dq and a dk/dv kernel.

What follows the input and what is float32, always. Every matmul takes its
operands in the dtype q, k, v and dO arrive in (bf16 in, bf16 to the MXU;
float32 in, float32 operands as before) and accumulates in float32
(``preferred_element_type``). The probabilities are rounded to ``v.dtype``
before ``P V`` and ``Pᵀ dO``, ``dS`` to ``q.dtype`` before ``dS K`` and
``dSᵀ Q`` — the two roundings ``ops/attention._reference_attention`` makes.
Float32 regardless of the input: the scores as they leave the MXU, the
running max and normaliser, ``exp``, ``lse``, ``delta``, ``dp − delta`` and
the output / dq / dk / dv accumulators. The softmax scale costs no second
rounding: it is folded into the block a loop keeps (q, or k in the dk/dv
and the one-kernel backward) when that is exact — float32, or a power of two as 1/8 is at
head_dim 64 — and multiplies the float32 scores otherwise.

All the kernels hold a score tile transposed, ``Sᵀ = K Qᵀ`` as
``[block_k, block_q]``: keys along sublanes, queries along lanes. The
per-query statistics (max, normaliser, ``lse``, ``delta``) are then rows of
``block_q`` lanes instead of ``[block_q, 1]`` columns that fill one lane in
128, reductions over keys are elementwise across vregs, ``Pᵀ dO`` and
``dSᵀ Q`` are plain products, and the forward and dq accumulate ``Oᵀ = Vᵀ
Pᵀ`` / ``dQᵀ = Kᵀ dSᵀ`` as ``[head_dim, block_q]`` (transposed once, when a
Q-block is finished; the ``[block_k, head_dim]`` blocks of V and K are
transposed in the kernel, on the otherwise idle XLU). ``lse`` leaves the
forward as a ``[.., seq, 1]`` column (the result type the benchmark's reader
tells the forward by); the differentiation rule turns it once into dense
``[batch, heads, seq]`` rows, the residual it keeps (0.5 MB where the column
is 64 MB of lane padding), and the backward kernels are handed those rows
by Q-block (``_lse_operand``).

How the forward walks a block pair. A ``[512, 512]`` float32 score
tile is 256 vregs on a register file of 64, and a pair as one tile is one
chain — ``K Qᵀ``, then max / ``exp`` / sum, then ``Vᵀ Pᵀ`` — with one head of
128 a cell and so nothing beside it: the TPU's compiler scheduled an
unmasked pair as 1,336 bundles of which 607 hold a store, every one a spill
(the tile went to VMEM behind the product, came back for the max, and its
``exp`` went and came again), where the MXU's own operations need some 790;
1.21 us a pair on the chip. The forward therefore walks a pair in TILES of
128 keys x 128 queries (``_FWD_TILE``: one tile of the MXU, 16 vregs of scores), key
tile after key tile, under each the cell's heads and their Q tiles, as
straight-line code. A chain — one head's 128 queries — has a running max, a
normaliser and a ``[value_dim, 128]`` slice of the accumulator of its own,
kept in VMEM scratch between pairs (read at the chain's first tile of a
pair, values through it, written back after its last) and corrected a key
tile at a time: the running max is exact, the float32 sums are taken in
another order than a block's, as another block size would take them. The
scores' products are written half a pair's tiles AHEAD of the softmax that
consumes them (``_behind``: 8 of 16 at one head a cell, 16 of 32 at two), so
that the scheduler has MXU work that waits for no VPU result. In a masked
pair whose corner the diagonal passes through (equal blocks, ``s_k − s_q``
a multiple of them: every training step) a tile wholly above the diagonal
is not computed and one wholly below it takes no mask — 10 tiles of 16, 4 of
them masked; anywhere else every tile of a crossed pair is masked under its
own traced edge. After: 1,181 bundles an unmasked pair, 443 stores of which
372 spills, 805 a masked pair for 1,381; 0.92 us a pair on the chip (the
chip gains more than the count says and ranks depth and tile differently,
so both were swept there: ``_FWD_TILE``).

How the backward walks a block pair: the same way. Its pair as one
tile held TWO ``[512, 512]`` float32 tiles at once (``Sᵀ`` and ``dPᵀ``) on
one chain of five products, and carried 128 vregs of dk and dv through
every turn of its loop; 2.23 us a pair on the chip where the MXU's five
products need 1.70. It walks tiles of 128 x 128 (``_BWD_TILE``) key tile
after key tile: a tile's two score products ``Sᵀ = K Qᵀ`` and ``dPᵀ = V
dOᵀ``, which wait for no VPU result, stand half a pair's tiles ahead of
``exp``, ``dSᵀ`` and the three products that consume them (``_behind``); dk
and dv live in a float32 VMEM scratch ``[cell heads, block_k, d | dv]``,
zeroed a grid cell, a key tile's slices values through its Q tiles; dQᵀ is
added to in its scratch a tile; the masked pair's tiles are placed as the
forward's (six of sixteen not computed). 1.91 us a live pair at 128 / 128
(1.99 a pair computed whole), the call 14 to 20% shorter; what else was
tried is in ``_BWD_TILE``'s table. The call is a ``jax.jit`` of its own, as
the forward's (``_bwd``, ``_fwd``).

What a rematerialised block may keep. The rule's forward names the two
results that cost a kernel to make again, ``out`` and the ``lse`` rows
(``ops/remat.py``), INSIDE the rule: the residuals the backward receives are
the named values themselves, so a policy that saves both names runs the
forward kernel once. Whether they are named is the enclosing block's chooser's
(``ops/remat.py``: the call's FLOP a byte of ``out`` + ``lse`` against the
room the compiled step leaves); remat ``dots`` keeps the rows of every call.

Causal masking is bottom-right aligned (``offset = s_k − s_q``: query row
r sees key columns ≤ r + offset, as the reference's ``tril(k=s_k−s_q)``).
Each Q-block (K-block in the backward that walks Q-blocks) walks its live
block pairs in two loops:
pairs wholly below the diagonal take no mask, pairs the diagonal crosses are
masked (tile by tile, above). A grid cell is one Q-block (in the backward:
one K-block) of its heads and loops at run time.

The short problem. A sequence of at most ``SHORT_SEQ`` 2,048 rows takes
blocks of ``SHORT_BLOCK`` 1,024: a 1,024-long causal head (GPT-2's) is ONE
block pair a grid cell, walked in the same tiles of 128 x 128 — the 36 on and
under the diagonal of its 64; no dead tile is computed — by the same two
kernels as an 8,192-long one. Where a cell is the only block of its side its
place is a Python number: a loop of no turn is not traced and a loop of one
is that turn (``_loop``), so the one pair lowers its masked body alone,
under static slices. Where a head's whole problem is one pair of at most 512
rows a side (BERT's 128 to 512, not causal) a cell takes four heads of 64
for two (``_cell_heads``): so little work is mostly a cell's start. Until PR
60 a head of at most 16 block pairs of 512 ran kernels of its own — one grid
cell a head's whole sequence, every pair of 512 x 512 (dk/dv: 256 x 256)
written out as one tile, three kernels — which every short shape measured
loses to these (``SHORT_BLOCK``'s table: GPT-2's forward -12%, its backward
-34%); they are gone, with the rule that chose them.

The band path. Under a ``window`` of at most a grid cell's keys on a square
problem (``s_q == s_k``: a training step's window layers) a cell meets only
ONE neighbour, and the three kernels have bodies of their own
(``_band_fwd_kernel``, ``_band_dq_kernel``, ``_band_dkv_kernel``; the rule
is :func:`choose_blocks`', from ``window``, ``s_q``, ``s_k`` alone; a window
wider than a cell's ``BAND_ROWS`` or a rectangle keeps the loops above). A
grid cell takes ``rows``
rows — Q rows in the forward and dq, K rows in dk/dv — and beside them only
the ``reach`` rows the band reaches, the fewest whole blocks that hold the
window (512 rows for Laguna's window of 512, 1,024 for Mellum 2's of 1,024:
a window wider than a block is a wider neighbour, not another kernel): the
block before them of K and V, the
block after them of q, O, dO and ``lse``. The neighbour is the same array
handed to the call a second time under a block index of its own, clipped at
the sequence's ends; no array is padded, shifted or copied in front of a
kernel, and nothing else of the sequence is in VMEM. Inside a cell,
sub-blocks of ``sub`` rows each take their band as a few static slices
(``_band_pieces``): for 256 queries under window 512 the 768 keys ``[start
− 512, start + 256)`` (under 1,024: the 1,280 from ``start − 1,024``), cut
where a mask starts or stops being needed — the
far edge crosses the first 256, the diagonal the last 256, the 256 (768)
between are multiplied as they are. No loop and no traced bound: the one cell
without a neighbour (the first of a sequence, the last in dk/dv) holds the
clipped block's scores under the sentinel with one ``minimum`` a piece. With
a sub-block's whole band in hand the forward's softmax is one pass (max,
``exp``, sum over the band's keys; no running max to correct, and every row
sees its own key, so no row is dead); its mathematics, its float32 values
and the two roundings are the looped kernels'.

The block mask. A step trained by diffusion over blocks runs each sequence
as ``[noised || clean]``, twice its tokens in rows, under a mask that is
neither causal nor a window (:class:`BlockDiffusion`: a block length and
where the halves meet — ONE description, never a dense array): a noised row
sees its own block of the noised half both ways and the clean half's blocks
before its own, a clean row the clean half by blocks, nothing sees the noised
half from outside its block. ``seam² + seam · block`` of the ``4 seam²`` pairs
are live, half of what a causal mask over as many rows keeps, so a kernel that
only MASKED the dead ones would make twice the products. It is ONE call over
the ``2 seam`` rows on the kernels above, both under one square block that divides a half and is
whole mask blocks, so that a block pair's place under the mask is its two
block indices' (``_bd_k_blocks``, ``_bd_q_blocks``): with ``n`` blocks a half a
Q-block walks the clean blocks before its own position's with no mask, the
clean block AT its position under the rule ``upto`` (strictly, for a noised
Q-block) and, if it is noised, its own block of the noised half under ``own``
— ``n² + 2 n`` of ``4 n²`` pairs, 288 of 1,024 at 16,384 rows in blocks of 512
where causal keeps 528; no other pair is visited, and the backward's K-block
walks the mirror. A pair on one of the three diagonals is placed tile by tile
as PR 49's masked pairs are: of the noised half's own pair only the four
diagonal tiles of 128 hold a live pair (the one-kernel backward walks those
four alone too), of the other two ten of sixteen, four of them crossed; a
tile wholly dead is not computed, one wholly live takes no mask, a crossed
one compares its keys' and queries' block numbers (a shift). Where one loop
serves both halves the rule's strictness is a traced number. The form not
taken — the noised rows' two parts, a rectangle over the clean blocks before
and a ``block x block`` square, joined by their ``lse`` — would write and
read ``out`` and ``lse`` of the noised half twice and run a merge pass for
what the walk above gets from two loop bounds. The mathematics, the float32
values and the two roundings are the looped kernels'; the calls carry names
of their own (``bd_fwd``, ``bd_bwd``),
hold ``2 seam`` rows of k and v (q, O and dO) resident — the two-size
allowance of VMEM — and refuse ``causal``, a ``window`` and two head sizes
beside the mask.

Layout. Public shapes are the models' ``[batch, seq, heads, head_dim]``; the
kernels take q, k, v, O, dO and give O, dq, dk, dv as ``[batch, seq,
heads·head_dim]`` — the same bytes in the same order, and the layout a
projection's matrix product leaves and takes, so no swap of axes, no copy
and no lane padding stands between the two. A grid cell ``(batch row, lane
block[, Q- or K-block])`` holds ``(rows, lanes)`` blocks of whole 128-lane
tiles: two heads of 64 side by side (four of 32, one of 128; the whole
width where that is narrower than a tile), each head a static lane slice
inside the kernel, the results of a cell's heads joined and stored as whole
rows. Short sequences widen the block (``_cell_heads``).

Grouped queries. k and v reach every kernel at the KEY/VALUE heads,
``[batch, seq, kv_heads·head_dim]``, and query head ``h`` reads head ``h //
(heads // kv_heads)`` by the lane-block index of the BlockSpecs that read k
or v (:func:`_shared`: the forward and the one-call backward, the band
path's own and neighbour blocks; under
``causal``, a window and the block mask alike): no repeat of k and v to the
query's heads stands in HBM in front of a call. That holds where a grid cell
is ONE head (heads of 128 and wider). A lane block of two heads of 64 cannot
name half a tile, so there k and v are repeated in front of the call as far
as a CELL's heads and no further (:func:`_cell_repeat`: two-fold under a
ratio of four, and cell ``c`` reads block ``c // 2``; the whole ratio where
a cell's heads do not divide it, or where a short sequence widened the
cell). dk and dv LEAVE the kernels a query head, ``[batch, seq,
heads·head_dim]``, and the differentiation rule sums a group's heads
(``_flash_bwd``: the reshape and ``reduce_sum`` a repeat's transpose was, in
the same dtype): the looped backward sums dq in VMEM across its K-block
axis, the grid's last, so a group's heads cannot be that axis's neighbours
too, and Pallas gives no output block revisited between other blocks. With
equal head counts every spec, operand and index map is what it was.

Two head sizes. The scores' size (q, k, dq, dk: ``head_dim``) and the
values' (v, O, dO, dv: ``value_dim``) are two numbers through the forward,
the one-kernel backward, the blocks' specs and ``_cell_heads``: latent attention
scores 192 deep (128 without positions beside 64 rotated) and weighs values
128 wide. A cell then takes the heads that fill whole tiles of BOTH widths
— two heads: a 384-lane block of q and k beside a 256-lane block of v, O and
dO — and every product keeps its own depth (``S = K Qᵀ`` 192, ``P V`` and
``dP = V dOᵀ`` 128): v is not padded to the scores' size, which would cost
half again those products and the bytes of v, O and dO. Where the two are
equal the kernels lower to what they lowered to with one; where they differ
the calls carry names of their own (``mla_fwd``, ``mla_bwd``; ``diff_*`` where the values are the wider:
differential attention's pair of score heads of 64 against one value of 128).
Under a window the band path keeps its names (``swa_*``) and takes values
the wider, its blocks of v, O and dO as wide as they; scores deeper than the
values under a window are refused (no model has them). A head count the
tile does not divide (25 heads of 64: 13 lane blocks) leaves the last cell
half outside the array: Pallas reads and writes only the part inside, and
since every head is computed from its own slice alone, whatever the other
half holds reaches no live head.

The kernels are compiled by Mosaic, which needs a TPU. ``interpret=True``
runs them in the Pallas interpreter instead — something only a test passes,
to check numerics against the XLA reference path without hardware.
"""

from __future__ import annotations

import functools
import math
import operator
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from easydl_tpu.ops import index, remat
from easydl_tpu.utils.logging import get_logger, log_once

log = get_logger("ops", "flash_attention")

NEG_INF = float(jnp.finfo(jnp.float32).min)

#: the largest block a kernel takes when the caller names none (the sweep
#: in :func:`choose_blocks`)
MAX_BLOCK = 512

#: the largest block where both lengths are at most ``SHORT_SEQ``. Swept on a
#: v5e, us a call by the kernels' own events, five calls a variant, bf16,
#: forward / backward (PERF.md section 6, PR 60), at every shape the unrolled
#: kernels served — the parent's three, one grid cell a head's whole sequence,
#: every pair of 512 x 512 (dk/dv: 256 x 256 under causal) written out as one
#: tile, the backward as dq + dk/dv — against these two kernels at a block:
#:
#:   shape, causal                 unrolled         block 256        block 512        block 1,024
#:   [8, 1024, 16 x 64] medium     277.9 / 830.7    506.3 / 740.3    295.2 / 592.8    205.1 / 522.9 <-
#:   [4, 1024, 25 x 64] XL's shard 249.7 / 723.0    419.3 / 627.4    251.5 / 510.9    190.8 / 457.6 <-
#:   [16, 1024, 8 x 64] tp=2       277.7 / 830.8    506.4 / 740.5    295.2 / 592.7    204.9 / 522.5 <-
#:   [2, 2048, 16 x 128]           296.7 / 794.5    884.7 / 917.0    316.2 / 663.7    270.5 / 548.8 <-
#:   [4, 2048, 16 x 64]            470.2 / 1,460.1  837.6 / 1,229.8  461.9 / 1,005.7  407.9 / 974.0 <-
#:   [8, 512 x 1024, 16 x 64]      199.8 / 588.1    336.5 / 552.7    157.0 / 444.9 <- 214.5 / 483.8 (512 x 1,024)
#:   not causal:
#:   [8, 512 x 2048, 16 x 64]      428.2 / 1,192.4                   374.9 / 966.9    349.1 / 944.8 <- (512 x 1,024)
#:
#: and a head that is ONE pair of at most 512 rows, by the heads a grid cell
#: takes (``_cell_heads``; the unrolled cell took four):
#:
#:   shape                         unrolled         two heads        four heads       six, twelve
#:   [16, 512, 16 x 64] causal     178.1 / 564.4    190.3 / 361.3    135.5 / 317.1 <-
#:   [16, 512, 12 x 64] BERT       177.8 / 467.7    156.2 / 357.0    134.4 / 350.4 <- 134.2 / 350.5
#:   [64, 128, 12 x 64] BERT       197.1 / 411.6    244.8 / 313.8    166.2 / 224.4 <- 166.2 / 224.0
#:
#: The taken column wins forward AND backward at every shape (-13 to -27% and
#: -21 to -45%), so the unrolled kernels, the rule that chose them
#: (``_unrolled``, ``_UNROLL_PAIRS``), the cell's widening by pairs and bytes
#: (``_CELL_PAIRS``, ``_CELL_BYTES``) and dk/dv's small blocks are gone. What
#: the numbers say: five products a pair for seven, and no dead tile
#: computed, is the backward's third; a block of 512 at 1,024 rows — two grid
#: cells a head, of one and two pairs — pays a cell's start and its
#: look-ahead's fill and drain once for little work, and its forward LOSES to
#: the unrolled one (+6%); the whole head as one pair does not. The column at
#: 1,024 with the cell's place traced (``program_id``, both loop bodies
#: lowered) read 246.1 / 544.2 at medium's shape and 225.5 / 485.5 at XL's:
#: the place as a Python number (``_loop``) is more than half of the
#: forward's gain. Four heads a cell at 1,024 x 1,024 did not fit the
#: backward's VMEM (28.9 MB over 27.3). NOT taken, because it would move nine
#: cells' programs and none was run end to end: blocks of 1,024 at 4,096 rows
#: read 350.9 / 892.9 for 399.4 / 932.7 at ``[2, 4096, 8 x 64]`` and 464.9 /
#: 1,011.9 for 544.6 / 1,136.6 at ``[1, 4096, 16 x 128]`` (PERF.md section 7).
SHORT_BLOCK = 1024
SHORT_SEQ = 2048

#: lanes of the scores' size that a grid cell is widened to where a head is
#: ONE block pair of at most ``MAX_BLOCK`` rows a side (``SHORT_BLOCK``'s table:
#: four heads of 64 for two)
_WIDE_LANES = 256

#: (block_q, block_k) of the forward and, twice, of the backward (the band
#: path's :data:`Bands` are a dq and a dk/dv kernel's; the ONE backward
#: kernel takes the last).
Blocks = Tuple[Tuple[int, int], Tuple[int, int], Tuple[int, int]]


class Band(NamedTuple):
    """How one band kernel is cut (:func:`choose_blocks` gives three).
    ``rows``: the rows a grid cell takes, Q rows in the forward and dq, K
    rows in dk/dv. ``sub``: the rows of a sub-block inside it, whose band is
    one fixed set of pieces. ``reach``: the rows of the one neighbour block
    resident beside the cell's own (the block before it for K and V, the
    block after it for q, O, dO and ``lse``); the window is at most that,
    and ``rows`` a whole number of them."""
    rows: int
    sub: int
    reach: int


#: the band path's cut of the forward, the dq and the dk/dv kernel.
Bands = Tuple[Band, Band, Band]

#: rows of a band kernel's grid cell and of a sub-block in it, when the
#: caller names no blocks (the sweep in :func:`choose_blocks`)
BAND_ROWS = 2048
BAND_SUB = 256

#: the VMEM a band kernel may take: its cell's blocks twice and the scores
#: of the sub-blocks in flight — 9 MB for bf16 operands at 2,048 rows,
#: within the compiler's own 16 MB, but 25 MB for float32 ones; beside a
#: neighbour of 1,024 rows under a window of 1,024 (two sub-blocks' scores
#: and probabilities of 1,280 x 256 in flight: 5 MB) 13 MB and 33 (a v5e's
#: VMEM is 128 MB)
_BAND_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 << 20)


class BlockDiffusion(NamedTuple):
    """The mask of a step trained by diffusion over blocks (BD3-LM,
    arXiv:2503.09573), over ``[noised || clean]`` rows of one sequence:
    rows ``[0, seam)`` are the noised half, ``[seam, 2 seam)`` the clean
    one, a row's position is ``r mod seam`` and its block ``position //
    block``. A noised query sees the noised keys of its own block (both
    ways) and the clean keys of the blocks before its own; a clean query
    the clean keys of the blocks up to its own (its own both ways); nothing
    sees the noised half from outside its block. Every row sees itself, and
    ``seam² + seam · block`` of the ``4 seam²`` pairs are live."""
    block: int
    seam: int

    def pairs(self) -> int:
        """(query, key) pairs the mask keeps, a head and sequence."""
        return self.seam * (self.seam + self.block)

    def dense(self):
        """The mask written out, ``[2 seam, 2 seam]`` bool (query, key):
        what the XLA reference path and the tests hold the kernels to."""
        row = jnp.arange(2 * self.seam)
        clean, blk = row >= self.seam, row % self.seam // self.block
        q_clean, k_clean = clean[:, None], clean[None, :]
        q_blk, k_blk = blk[:, None], blk[None, :]
        return jnp.where(
            q_clean, k_clean & (k_blk <= q_blk),
            jnp.where(k_clean, k_blk < q_blk, k_blk == q_blk))

    def block_pairs(self, block: int) -> Tuple[int, int]:
        """``(visited, all)`` pairs of ``block x block`` kernel blocks a
        head: with ``n = seam / block`` blocks a half the clean half's
        triangle with its diagonal, the noised rows' clean blocks before
        their own and the pair on that offset diagonal, and the noised
        half's diagonal — ``n² + 2 n`` of ``4 n²`` (288 of 1,024 at 16,384
        rows in blocks of 512); where a kernel block IS the mask's block
        the offset diagonal is dead too and ``n² + n`` are visited."""
        n = self.seam // block
        return n * n + (n if block == self.block else 2 * n), 4 * n * n


#: dot_general dimension numbers: ``A Bᵀ`` (both contract their last
#: dimension) and the plain ``A B``.
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))


def _pick_block(s: int, target: int) -> Optional[int]:
    """Largest block <= target that divides s, preferring multiples of 128
    (MXU/lane tiling). None when s can't be tiled."""
    b = min(target, s)
    if s % b == 0:
        return b
    if s % 128 == 0:
        b -= b % 128
        while b >= 128:
            if s % b == 0:
                return b
            b -= 128
    return None


def choose_blocks(s_q: int, s_k: int, causal: bool,
                  block_q: Optional[int] = None,
                  block_k: Optional[int] = None,
                  window: Optional[int] = None,
                  mask: Optional[BlockDiffusion] = None,
                  ) -> Optional[Union[Blocks, Bands]]:
    """Block sizes per kernel from what the call can see; a caller's
    ``block_q`` / ``block_k`` hold for all three. None when a length has no
    block divisor: the kernels cannot tile it, and whoever chooses the
    attention path (``ops/attention.py``) asks here before calling them.

    The forward and the ONE backward kernel take the same block: ``MAX_BLOCK``
    512 x 512 (swept on a v5e over {128, 256, 512, 1024}² while a pair was one
    tile: PERF.md section 6, PR 24), walked in tiles of 128 x 128 with the
    scores half a pair ahead (``_FWD_TILE``, ``_BWD_TILE``, ``_behind``: PR
    49, PR 52), where a dead tile of the causal diagonal's pair is not
    computed — so a smaller block wastes no less of the triangle and only
    pays more grid cells (256 x 256 at 1,024: the forward +82%, ``SHORT_BLOCK``'s
    table). Where both lengths are at most ``SHORT_SEQ`` the block is
    ``SHORT_BLOCK`` 1,024: a 1,024-long head is ONE pair a grid cell, walked
    in the same tiles, and a 2,048-long one four (``SHORT_BLOCK``'s table, PR 60; longer
    sequences were not measured at it and keep 512). Under ``causal`` the
    block is square, no larger than the shorter side: the diagonal then
    passes through a pair's corner and its tiles are placed statically (a
    512 x 1,024 block on a 512 x 1,024 rectangle masks every tile under a
    traced edge and loses 7% forward to 512 x 512).

    Under a ``window`` of at most ``BAND_ROWS`` keys (a causal band: query i
    sees the ``window`` keys up to its own) on a square problem the three
    kernels take the band path and the result is three :class:`Band` cuts
    (``_band``): cells of ``BAND_ROWS`` rows (as many whole neighbour blocks
    as divide the sequence, at most four), sub-blocks of ``BAND_SUB``, a
    neighbour of ``MAX_BLOCK`` rows — of the fewest whole blocks that hold a
    wider window — or the caller's ``block_k``. Swept on a
    v5e at ``[2, 8192, 64 x 128]`` bf16, window 512, ms a call forward / dq
    / dkv (PERF.md section 6, PR 34; the kernels' own events in a trace):

        rows / sub   forward    dq     dkv
        looped         6.70    7.66    9.49   (512 x 512, dkv 512 x 256)
        2048 / 256     3.01    3.41    4.37   <- taken
        2048 / 128     3.01    3.86    4.52
        1024 / 256     3.53    3.61    4.55
         512 / 256     4.25    4.29    5.17
        2048 / 512     3.56    4.41    5.71

    Sub-blocks of 256 compute 768 keys for the 512 a row sees (128: 640),
    but a product of 128 rows costs the MXU more a FLOP than it saves;
    larger cells spread a cell's start and end (the first sub-block's
    scores and the last one's softmax overlap nothing) over more rows and
    read a neighbour's K and V once for more of them.

    The same sweep at ``[2, 8192, 32 x 128]`` bf16 under a window of 1,024
    beside a neighbour of 1,024 rows (PERF.md section 6, PR 45; the looped
    backward there is ONE kernel, ``swa_bwd``):

        rows / sub   forward    dq     dkv
        looped         4.32      7.70 (both)   (512 x 512)
        1024 / 128     2.67    3.31    3.67
        1024 / 256     2.51    2.78    3.67
        1024 / 512     2.89    3.31    4.31
        2048 / 128     2.44    3.47    3.48
        2048 / 256     2.42    2.71    3.59   <- taken
        2048 / 512     2.85    3.24    4.26
        4096 / 128     2.31    3.56    3.43
        4096 / 256     2.37    2.67    3.56
        4096 / 512     2.79    3.20    4.26

    The cut that is fastest at 512 is within 1.4% of the fastest at 1,024
    (8.72 ms the three against 8.60 at 4,096 / 256), so one pair of
    constants holds for both; a sub-block of 256 computes 1,280 keys for the
    1,024 a row sees, a quarter over where 512 computes a half.

    Under a window wider than a cell (over ``BAND_ROWS`` keys: no neighbour
    within a cell's rows holds it), where a caller's ``block_k`` does not
    hold the window, or where ``s_q != s_k`` (a decode's or a prefix's
    rectangle), the looped
    kernels walk the band's blocks. Swept on a v5e at ``[2, 8192, 64 x
    128]``, window 512 (PERF.md section 6, PR 31): the forward and dq are
    fastest at 512 x 512 like the plain kernels (7.6 ms a call against 10.8
    at 256 x 256: fewer, fuller block pairs win over the band's masked
    corners); the backward takes K-blocks of 256 (as a dk/dv kernel of its
    own 512 x 512 did not fit its VMEM beside the whole sequence's q, O and
    dO, and 512 x 256 beat 256 x 256 by 1.5 ms).

    Under a ``mask`` (:class:`BlockDiffusion`) all three kernels take ONE
    square block that divides a half and is whole mask blocks, so that a
    block pair's place under the mask is its two block indices': a pair is
    wholly live, wholly dead (never visited: ``BlockDiffusion.block_pairs``)
    or one of the three diagonals, which a kernel masks tile by tile."""
    def pick(target, target_k=None):
        return (_pick_block(s_q, block_q or target),
                _pick_block(s_k, block_k or target_k or target))

    if mask is not None:
        if not (s_q == s_k == 2 * mask.seam and mask.seam % mask.block == 0):
            return None
        side = _pick_block(mask.seam, block_q or block_k or MAX_BLOCK)
        if side is None or side % mask.block or (block_k or side) != side:
            return None
        return ((side, side),) * 3

    if window is not None and s_q == s_k:
        band = _band(s_q, window, block_q, block_k)
        if band is not None:
            return band, band, band
    if window is not None and window <= MAX_BLOCK:
        big, banded = pick(MAX_BLOCK), pick(MAX_BLOCK, MAX_BLOCK // 2)
        if None not in big + banded:
            return big, big, banded
    target = MAX_BLOCK
    if window is None and max(s_q, s_k) <= SHORT_SEQ and not (
            s_q % _FWD_TILE or s_k % _FWD_TILE):  # whole tiles alone
        target = min(SHORT_BLOCK, s_q, s_k) if causal else SHORT_BLOCK
    big = pick(target)
    return None if None in big else (big, big, big)


def _band(s: int, window: int, block_q: Optional[int],
          block_k: Optional[int]) -> Optional[Band]:
    """The band path's cut of a square problem, or None where no neighbour
    within a cell's rows holds the window (the looped kernels then walk the
    band's blocks). A caller's ``block_k`` is the neighbour's rows
    (``reach``), its ``block_q`` a sub-block's. Where the caller names no
    neighbour and the window is wider than a block, ``reach`` is the fewest
    whole blocks that hold it and divide the sequence (1,024 rows for a
    window of 513 to 1,024), no more than ``BAND_ROWS``."""
    reach = _pick_block(s, block_k or MAX_BLOCK)
    if reach is None:
        return None
    if window > reach:
        reach = None if block_k else next(
            (r for r in range(2 * reach, BAND_ROWS + 1, reach)
             if r >= window and s % r == 0), None)
        if reach is None:
            return None
    sub = _pick_block(reach, block_q or BAND_SUB)
    if sub is None or (sub > 128 and sub % 128):
        return None
    cells = max(c for c in range(1, BAND_ROWS // max(reach, MAX_BLOCK) + 1)
                if s // reach % c == 0)
    return Band(cells * reach, sub, reach)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _fold_scale(x, scale: float):
    """``(x·scale, 1)`` where that is exact in x's dtype — float32, or a
    power-of-two scale — else ``(x, scale)``: the scale then multiplies the
    float32 scores, so no operand is rounded twice."""
    if x.dtype == jnp.float32 or math.frexp(scale)[0] == 0.5:
        return x * scale, 1.0
    return x, scale


def _clip(x, lo: int, hi: int):
    if isinstance(x, int):
        return max(lo, min(x, hi))
    return jnp.clip(x, lo, hi)


def _least(a, b):
    return min(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.minimum(a, b)


def _most(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.maximum(a, b)


def _loop(lo, hi, body, carry):
    """``fori_loop``. Where the bounds are Python numbers — the grid cell is
    the only block of its side, so its place is no ``program_id`` — a range
    of no turn is not traced and a range of one is that turn: the one pair
    of a 1,024-long causal head lowers its masked body alone, under static
    slices (the other body would be two thirds of the kernel's text and
    never run)."""
    if isinstance(lo, int) and isinstance(hi, int) and hi - lo <= 1:
        return body(lo, carry) if hi > lo else carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _aligned(x, block: int):
    """``x``, a multiple of ``block``: told to Mosaic where it is traced."""
    return x if isinstance(x, int) else pl.multiple_of(x, block)


def _block_start(i, block: int):
    return _aligned(i * block, block)


def _when(cond, body):
    """``pl.when``; a Python ``cond`` (the grid cell is the only block of
    its side) decides here."""
    if not isinstance(cond, bool):
        pl.when(cond)(body)
    elif cond:
        body()


def _scores_t(k, q, s_scale: float, bound, window: Optional[int] = None):
    """``Sᵀ = K Qᵀ`` of one block pair, float32 ``[block_k, block_q]``;
    causally masked when ``bound`` (= q_start + offset − k_start) is given:
    key j is seen by query i iff j − i <= bound, and under a ``window`` iff
    also j − i > bound − window (the ``window`` keys up to the query's
    own)."""
    st = _dot(k, q, _NT)
    if s_scale != 1.0:
        st = st * s_scale
    if bound is not None:
        keys = jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
        queries = jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
        seen = keys - queries <= bound
        if window is not None:
            seen = seen & (keys - queries > bound - window)
        st = jnp.where(seen, st, NEG_INF)
    return st


def _where(cond, a, b):
    """``a if cond else b`` for Python numbers, ``jnp.where`` for traced."""
    if isinstance(cond, (bool, int)):
        return a if cond else b
    return jnp.where(cond, a, b)


def _chosen(st, words, half: int):
    """The score tile ``st`` (``[index.TILE keys, queries]``) with the pairs
    a selection leaves out set to NEG_INF; ``words``: the ``[8, queries]``
    rows of the keys' group of the packed selection (``ops/index.py``),
    ``half`` which 128 keys of the group the tile is."""
    return jnp.where(index.unpack_tile(words, half) != 0, st, NEG_INF)


#: the rules a block pair on one of the block mask's three diagonals is
#: masked by, as ``(kind, strict)``: ``("own", 0)`` — a noised block's own
#: keys: key block == query block — and ``("upto", strict)`` — the clean
#: keys under a clean (``strict`` 0: key block <= query block) or a noised
#: query block (``strict`` 1: key block < query block); ``strict`` may be
#: traced, where one loop's body serves both halves.
_OWN = ("own", 0)


def _bd_tile(rule, block: int, k_at: int, k_n: int, q_at: int,
             q_n: int) -> Optional[bool]:
    """What the tile of keys ``[k_at, k_at + k_n)`` x queries ``[q_at, q_at
    + q_n)`` of a diagonal block pair (rows counted from the pair's first,
    which the two sides share by position) is under ``rule``: None — no
    query of it sees a key of it: not computed —, False — every query sees
    every key: no mask —, True — crossed: masked. Under a traced ``strict``
    a tile is dead or live only where it is under both values."""
    k_lo, k_hi = k_at // block, (k_at + k_n - 1) // block
    q_lo, q_hi = q_at // block, (q_at + q_n - 1) // block
    kind, strict = rule
    if kind == "own":
        if k_hi < q_lo or k_lo > q_hi:
            return None
        return not k_lo == k_hi == q_lo == q_hi
    least, most = (strict, strict) if isinstance(strict, int) else (0, 1)
    if k_lo + least > q_hi:
        return None
    return not k_hi + most <= q_lo


def _bd_mask(st, rule, block: int, k_at: int, q_at: int):
    """The score tile ``st`` (``[keys, queries]``, the first key ``k_at``
    and the first query ``q_at`` rows into their diagonal pair) with what
    ``rule`` hides set to NEG_INF."""
    def blocks(axis, at):
        rows = jax.lax.broadcasted_iota(jnp.int32, st.shape, axis) + at
        if block & (block - 1) == 0:  # a shift where it is a power of two
            return rows >> (block.bit_length() - 1)
        return rows // block

    kind, strict = rule
    keys, queries = blocks(0, k_at), blocks(1, q_at)
    seen = keys == queries if kind == "own" else keys + strict <= queries
    return jnp.where(seen, st, NEG_INF)


def _flag(cond):
    """0 / 1 of a comparison: a Python number's, or a traced one's."""
    return int(cond) if isinstance(cond, bool) else cond.astype(jnp.int32)


def _bd_k_blocks(body, carry, qb, *, mask: BlockDiffusion, side: int):
    """``body(kb, carry, masked=)`` over the K-blocks that Q-block ``qb``
    (of ``side`` rows) sees under the block mask, ``n`` blocks a half: the
    clean blocks before its own position's take no mask (``masked`` False);
    the clean block AT its position is masked ``upto`` — strictly for a
    noised Q-block, and where a kernel block is one mask block not visited
    by it at all; a noised Q-block sees its own block of the noised half,
    masked ``own``. Nothing else is visited."""
    n = mask.seam // side
    noised = _flag(qb < n)
    at = qb - n * (1 - noised)  # the block's place in its half
    carry = _loop(n, n + at, functools.partial(body, masked=False), carry)
    if side == mask.block:
        carry = _loop(n + at, n + at + 1 - noised, functools.partial(
            body, masked=("upto", 0)), carry)
    else:
        carry = body(n + at, carry, masked=("upto", noised))
    return _loop(qb, qb + noised, functools.partial(body, masked=_OWN),
                 carry)


def _bd_q_blocks(body, carry, kb, *, mask: BlockDiffusion, side: int):
    """:func:`_bd_k_blocks`' mirror, the Q-blocks that see K-block ``kb``: a
    noised K-block is seen by its own Q-block alone (``own``); a clean one
    by the noised Q-block at its position (``upto``, strictly) and the clean
    one there (``upto``), and whole by the later Q-blocks of both halves —
    ``t`` counts through both stretches, so that one body serves each."""
    n = mask.seam // side
    clean = _flag(kb >= n)
    at = kb - n * clean
    carry = _loop(kb, kb + 1 - clean, functools.partial(body, masked=_OWN),
                  carry)
    if side == mask.block:  # the strict pair is dead, the other whole
        carry = _loop(kb, kb + clean, functools.partial(
            body, masked=("upto", 0)), carry)
    else:
        carry = _loop(0, 2 * clean, lambda t, c: body(
            at + t * n, c, masked=("upto", 1 - t)), carry)
    later = n - at - 1
    return _loop(0, 2 * later * clean, lambda t, c: body(
        at + 1 + t + _where(t >= later, n - later, 0), c, masked=False),
        carry)


def _over_k_blocks(body, carry, q_start, *, block_q: int, block_k: int, n_k: int,
                   offset: int, causal: bool,
                   window: Optional[int] = None):
    """``body(kb, carry, masked=)`` over the K-blocks the Q-block at
    ``q_start`` sees: [0, n_full) lie wholly below the diagonal (every row
    sees every column) and take no mask, [n_full, n_live) are crossed by it,
    the rest are seen by no row. Under a ``window`` the blocks before
    ``first`` lie wholly outside the band and are not visited, those before
    ``inside`` are crossed by its far edge and masked."""
    if not causal:
        return _loop(0, n_k, functools.partial(body, masked=False), carry)
    n_full = _clip((q_start + offset + 1) // block_k, 0, n_k)
    n_live = _clip((q_start + block_q + offset + block_k - 1) // block_k, 0, n_k)
    if window is None:
        carry = _loop(0, n_full, functools.partial(body, masked=False), carry)
        return _loop(n_full, n_live, functools.partial(body, masked=True), carry)
    # the band's far edge: row r sees keys > r + offset - window
    first = _clip((q_start + offset - window + 1) // block_k, 0, n_k)
    inside = _clip((q_start + block_q + offset - window + block_k - 1) // block_k,
                   0, n_k)
    masked = functools.partial(body, masked=True)
    carry = _loop(first, _least(inside, n_live), masked, carry)
    carry = _loop(inside, n_full, functools.partial(body, masked=False), carry)
    return _loop(_most(n_full, inside), n_live, masked, carry)


def _cell_heads(heads: int, head_dim: int, value_dim: Optional[int] = None,
                pair: Optional[Tuple[int, int]] = None) -> int:
    """Heads one grid cell takes, side by side in the lanes of its blocks.
    As many as fill whole 128-lane tiles (two of 64, one of 128; all of
    them where the array is narrower than that) of the scores' size
    ``head_dim`` AND of the values' ``value_dim`` (None: the same; two heads
    of 192 / 128: 384 lanes beside 256), and no more — unless a head's
    whole problem is ONE block pair (``pair``: its rows a side) of at most
    ``MAX_BLOCK`` rows (a short non-causal sequence, 128 to 512 long): a
    cell of so little work is mostly its own start, and it takes the whole
    tiles of heads that fill ``_WIDE_LANES`` (four heads of 64) and divide
    the head count: independent chains for the compiler to interleave
    (``SHORT_BLOCK``'s table: the forward at 128 rows 245 us a call at two heads
    a cell, 166 at four, no less at six or twelve). A head count the tile's
    heads do not divide (25 heads of 64) leaves the last cell part outside
    the array: its blocks are read and written only where the array is, and
    each head is its own lane slice inside the kernels, so what lies outside
    meets no live head."""
    tile = math.lcm(*(math.lcm(d, 128) // d
                      for d in (head_dim, value_dim or head_dim)))
    if tile >= heads:
        return heads
    if pair is None or max(pair) > MAX_BLOCK or heads % tile:
        return tile
    most = max(1, _WIDE_LANES // (tile * head_dim))
    return tile * max(g for g in range(1, most + 1) if heads // tile % g == 0)


def _one_pair(s_q: int, s_k: int, block_q: int,
              block_k: int) -> Optional[Tuple[int, int]]:
    """The rows a side of a head's whole problem where it is ONE block pair
    (what :func:`_cell_heads` widens a cell by), else None."""
    return (block_q, block_k) if (s_q, s_k) == (block_q, block_k) else None


def _lse_operand(lse, cell: int, block_q: int, whole: bool,
                 at=lambda i: i):
    """``(BlockSpec, operand)`` that hand a backward kernel ``lse`` (dense
    rows ``[B, H, S]``) as one ``[1, block_q]`` row a Q-block, ``[B, H,
    S // block_q, 1, block_q]``: every Q-block of the cell's heads
    (``whole``: the one-kernel backward's, which walks them all) or the grid cell's own (the band kernels, whose Q-block is a
    cell's rows or, through ``at``, its neighbour's). The kernels read
    Q-block ``qb`` of head ``g`` as ``lse_ref[g, qb]``."""
    b, h, s = lse.shape
    blocks = s // block_q if whole else 1
    return pl.BlockSpec((None, cell, blocks, 1, block_q),
                        lambda b, h, i: (b, h, 0 if whole else at(i), 0, 0)
                        ), lse.reshape(b, h, s // block_q, 1, block_q)


def _delta_rows(do, o, d: int):
    """``delta = Σ dO∘O`` over the head size, float32, for every head of
    ``[rows, heads · d]`` blocks: the product turned once on the XLU for all
    of them, then each head's ``d`` sublanes summed into a ``[1, rows]``
    row."""
    prod_t = (do.astype(jnp.float32) * o.astype(jnp.float32)).T
    return [jnp.sum(prod_t[g * d:(g + 1) * d], axis=0, keepdims=True)
            for g in range(prod_t.shape[0] // d)]


def _total(parts):
    """The sum of a piece-wise product's parts."""
    return functools.reduce(operator.add, parts)


def _side_by_side(heads, axis: int):
    """The heads of a cell joined along ``axis`` into one value to store."""
    return heads[0] if len(heads) == 1 else jnp.concatenate(heads, axis=axis)


def _head_cols(lanes: int, d: int):
    """The lane slice of each head of a ``lanes``-wide block."""
    return [slice(g * d, (g + 1) * d) for g in range(lanes // d)]


def _head_sizes(q, k, v, heads: int) -> Tuple[int, int, int]:
    """``(head_dim, value_dim, ratio)`` of the kernels' views: q ``[B, S,
    heads·head_dim]``, k and v at the key/value heads, which ``ratio`` query
    heads read each (1: as many as the query's)."""
    d = q.shape[2] // heads
    kv_heads = k.shape[2] // d
    return d, v.shape[2] // kv_heads, heads // kv_heads


def _cell_repeat(ratio: int, cell: int) -> int:
    """How often a call whose grid cells take ``cell`` query heads needs
    each key/value head side by side: once where a cell is one head, ``cell``
    times where a lane block is ``cell`` heads of one group (two heads of 64
    under a ratio of four), the whole ``ratio`` where ``cell`` does not
    divide it."""
    return cell if ratio % cell == 0 else ratio


def _shared(q, k, v, heads: int, cell: int):
    """``(k, v, at)`` for a call whose grid cells take ``cell`` query heads,
    from the views :func:`_head_sizes` reads. ``at(spec)`` is a q-side
    BlockSpec over ``(batch row, rows, lane block)`` as k or v is read: lane
    block ``h // group`` for ``h``, ``group`` consecutive cells naming the
    same block (which the pipeline then does not fetch again). One head a
    cell reads key/value head ``h // ratio`` by that index and nothing is
    repeated in HBM; where a lane block is several heads, k and v are
    repeated in front of the call as far as :func:`_cell_repeat` says and no
    further. Equal head counts: the arrays and the specs as they came (a
    division by one would still be an operation of every cell's index
    maps)."""
    d, dv, ratio = _head_sizes(q, k, v, heads)
    rep = _cell_repeat(ratio, cell)
    group = ratio // rep
    if rep > 1:
        k, v = (jnp.repeat(x.reshape(*x.shape[:2], -1, size), rep,
                           axis=2).reshape(*x.shape[:2], -1)
                for x, size in ((k, d), (v, dv)))

    def at(spec: pl.BlockSpec) -> pl.BlockSpec:
        if group == 1:
            return spec

        def index_map(*cell):
            b, rows, lanes = spec.index_map(*cell)
            return b, rows, lanes // group

        return pl.BlockSpec(spec.block_shape, index_map)

    return k, v, at


#: what the forward may take of VMEM where the two head sizes differ: a cell
#: holds the whole sequence of two heads' k and v, 15 MB at 8,192 x (384 +
#: 256) bf16 lanes, twice — over the compiler's own 16 MB (a v5e's VMEM is
#: 128 MB); the one-kernel backward states its own
_TWO_SIZE_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=96 << 20)


def _call_name(kernel: str, d: int, dv: int, window: Optional[int],
               params: Optional[pltpu.CompilerParams] = None,
               mask: Optional[BlockDiffusion] = None, selected: bool = False):
    """A kernel's ``name=`` (and compiler parameters: the call's own
    ``params``, else the two-size allowance) by what it computes:
    ``flash_*``, ``swa_*`` under a window, where the scores' and the values'
    head sizes differ ``mla_*`` (latent attention: scores deeper than the
    values are wide) or ``diff_*`` (differential attention: a pair's value
    wider than its scores are deep), ``bd_*`` under the block mask (whose
    forward holds ``2 seam`` rows of k and v whole: the two-size allowance
    of VMEM), ``dsa_*`` under a selection (``select``: a learned index's)."""
    if selected:
        named = dict(name=f"dsa_{kernel}")
    elif mask is not None:
        named = dict(name=f"bd_{kernel}", compiler_params=_TWO_SIZE_PARAMS)
    elif d != dv:
        named = dict(name=f"{'mla' if d > dv else 'diff'}_{kernel}",
                     compiler_params=_TWO_SIZE_PARAMS)
    else:
        named = dict(name=f"{'flash' if window is None else 'swa'}_{kernel}")
    if params is not None:
        named["compiler_params"] = params
    return named


#: keys and queries of a tile of the looped forward's block pair: one tile of
#: the MXU (a ``[128, 128]`` block of Pᵀ is one set of weights for ``Vᵀ Pᵀ``),
#: 16 float32 vregs of scores. Swept on a v5e, us a forward call by the
#: kernel's own events (PERF.md section 6, PR 49), tiles of keys x queries
#: with their scores ``ahead`` tiles in front of their softmax. One head of
#: 128 a cell at ``[1, 4096, 16 x 128]`` / ``[2, 8192, 4 x 128]``: the pair as
#: one tile 730.8 / 1,314.4; 128 x 128 ahead 4 601.9 / 1,113.2, 8 544.7 /
#: 1,005.4, 12 549.5 / 1,014.1, 16 547.4 / 1,010.8; 128 x 256 ahead 2 600.6 /
#: 1,101.1, 4 558.5 / 1,019.3; 128 x 512 ahead 1 642.2 / 1,160.8, 2 603.0 /
#: 1,077.3; 256 x 128 ahead 4 651.3 / 1,201.7. Two heads of 192 / 128 at ``[1,
#: 8192, 4 x 192 / 128]``: one tile 766.6; 128 x 128 ahead 8 661.4, 12 631.3,
#: 16 629.7, 24 634.6; 128 x 256 ahead 4 674.3. One rule is within 1% of the
#: best of each: tiles of 128 x 128, the scores half a pair's tiles ahead (8
#: at one head a cell, 16 at two). Measured slower and not taken: V turned
#: once a cell of heads into a scratch (+3 to 5%), the sums read and written a
#: tile (+0 to 2%), the next pair's first tiles' scores made behind this
#: pair's last and handed on through a scratch (+5 to 7% at this depth).
#: Since PR 60 the short shapes walk the same tiles (a 1,024-long head's ONE
#: pair of 64, 64 ahead at two heads a cell: 205.1 us a call at ``[8, 1024,
#: 16 x 64]`` where the unrolled pair as one tile took 277.9:
#: ``SHORT_BLOCK``'s table).
_FWD_TILE = 128


def _fwd_tiles(block_q: int, block_k: int, cell_heads: int,
               tile: int = _FWD_TILE) -> Tuple[int, int, int]:
    """``(keys, queries, ahead)``: the tiles the looped forward walks a
    block pair in, and how many of them the scores run ahead of the softmax:
    half of what a cell's heads have in a pair. A block the tile does not
    divide is one tile."""
    sub_k, sub_q = (tile if block % tile == 0 else block
                    for block in (block_k, block_q))
    return sub_k, sub_q, max(
        1, (block_k // sub_k) * (block_q // sub_q) * cell_heads // 2)


def _live_tiles(steps, sub_k: int, sub_q: int, placed: bool,
                mask: Optional[BlockDiffusion], masked):
    """``(steps, crossed)``: the tiles ``(first key, head, first query)`` of
    a block pair's ``steps`` that hold a live pair, and under the block mask
    ``{(first key, first query): whether the tile is masked}``. ``placed``:
    the causal diagonal's place in the pair is static — it passes through
    the pair's corner — so a tile wholly above it is not computed (one wholly
    below it takes no mask: the caller's). Under the block mask a pair on
    one of its diagonals (``masked``: the rule) is placed by
    :func:`_bd_tile`: a tile no query of which sees a key is not computed,
    one wholly live takes no mask, a crossed one its rule."""
    if placed:
        steps = [(ki, g, qj) for ki, g, qj in steps if ki <= qj + sub_q - 1]
    crossed = {}
    if mask is not None and masked:
        crossed = {(ki, qj): _bd_tile(masked, mask.block, ki, sub_k, qj,
                                      sub_q)
                   for ki, _, qj in steps}
        steps = [(ki, g, qj) for ki, g, qj in steps
                 if crossed[ki, qj] is not None]
    return steps, crossed


def _online_softmax(state, st, vt):
    """One step of the online softmax: ``(m, l, acc)`` — the running max and
    normaliser ``[1, queries]`` and ``Oᵀ``'s float32 sum ``[dv, queries]`` —
    after the keys of the score tile ``st`` (``[keys, queries]``) and their
    values ``vt`` (``[dv, keys]``)."""
    m, l, acc = state
    m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))
    pt = jnp.exp(st - m_new)
    correction = jnp.exp(m - m_new)
    l_new = l * correction + jnp.sum(pt, axis=0, keepdims=True)
    acc_new = acc * correction + _dot(vt, pt.astype(vt.dtype), _NN)
    return m_new, l_new, acc_new


def _behind(steps, first, second, ahead: int = 1):
    """``second(step, first(step))`` for every step, written so that a
    step's ``first`` half (its products into scores: the MXU's work) stands
    in the program ``ahead`` steps before its ``second`` half (softmax and
    what follows: the VPU's), that is before the ``second`` half of the
    steps before it: the compiler's scheduler then runs them side by side,
    which it does not find by itself across a whole step. Two kernels are
    written so: the band forward's cell of sub-blocks, one behind (8,006
    bundles as written in order, 7,165 one behind), and the looped forward's
    block pair of tiles, half a pair behind (``_FWD_TILE``: 0.92 us a pair
    on the chip eight behind, 1.02 four behind, 1.21 as one tile)."""
    waiting = []
    for step in steps:
        waiting.append((step, first(step)))
        if len(waiting) > ahead:
            second(*waiting.pop(0))
    for behind in waiting:
        second(*behind)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *sums,
    head_dim: int, value_dim: int, block_k: int, n_q: int,
    causal: bool, scale: float, offset: int,
    window: Optional[int], mask: Optional[BlockDiffusion] = None,
    select=None,
):
    # select: None, or the packed selection of this Q-block, [S_k / 32,
    # block_q] int32 (``ops/index.py``): a pair it leaves out is no pair
    # q_ref: [block_q, cell heads · d], o_ref: [block_q, cell heads · dv];
    # k_ref: [S_k, cell heads · d], v_ref: [S_k, cell heads · dv]; lse_ref:
    # [cell heads, block_q, 1]; sums (scratch, float32): m and l [cell heads,
    # 1, block_q], acc [cell heads, dv, block_q]
    block_q, lanes = q_ref.shape
    d, dv = head_dim, value_dim
    heads = _head_cols(lanes, d)
    v_heads = _head_cols(v_ref.shape[1], dv)
    n_k = k_ref.shape[0] // block_k
    # whether the diagonal crosses a masked pair at a place known here: a
    # square of blocks whose corner it passes through
    diagonal = (window is None and block_q == block_k
                and offset % block_k == 0)
    # the Q-block's place: a Python number where it is the only one. (Here
    # and below the statements keep the order, and the one ``+ 0``, that the
    # nine longer cells' kernels were traced in before PR 60: their step
    # programs are held to that text by hash.)
    qb = 0 if n_q == 1 else pl.program_id(2)
    q_start = qb * block_q + 0
    qs = [_fold_scale(q_ref[:, cols], scale) for cols in heads]

    def pair(kb, carry, *, masked):
        # the pair in tiles of [sub_k, sub_q], key tile after key tile,
        # under each the cell's heads and their Q tiles, the scores'
        # products ``ahead`` tiles in front of their softmax (_FWD_TILE has
        # why and the measurements). A chain — one head's queries of one Q
        # tile — has a running max, a normaliser and a slice of the
        # accumulator of its own: read from the scratch at its first tile of
        # the pair, values through the pair, written back after its last, so
        # that no turn of the loop carries them in registers it has not got.
        # ``masked``: whether the diagonal crosses the pair or, under the
        # block mask, the rule of the pair's diagonal
        sub_k, sub_q, ahead = _fwd_tiles(block_q, block_k, len(heads))
        k_start = _block_start(kb, block_k)
        steps = [(ki, g, qj) for ki in range(0, block_k, sub_k)
                 for g in range(len(heads))
                 for qj in range(0, block_q, sub_q)]
        placed = masked and mask is None and diagonal
        steps, crossed = _live_tiles(steps, sub_k, sub_q, placed, mask,
                                     masked)
        last = {(g, qj): (ki, g, qj) for ki, g, qj in steps}
        state, vts = {}, {}

        def keys(ki):
            return pl.ds(_block_start(
                kb * (block_k // sub_k) + ki // sub_k, sub_k), sub_k)

        def scores(step):
            ki, g, qj = step
            q, s_scale = qs[g]
            edge = q_start + offset - k_start + qj - ki \
                if masked and mask is None else None
            if placed and ki + sub_k - 1 <= qj or select is not None:
                edge = None  # (a selection's bits are causal already)
            st = _scores_t(k_ref[keys(ki), heads[g]], q[qj:qj + sub_q],
                           s_scale, edge, window)
            if crossed.get((ki, qj)):
                st = _bd_mask(st, masked, mask.block, ki, qj)
            if select is not None:
                group = kb * (block_k // index.GROUP) + ki // index.GROUP
                st = _chosen(st, select[pl.ds(_block_start(group, 8), 8),
                                        qj:qj + sub_q], ki // sub_k % 2)
            return st

        def softmax(step, st):
            ki, g, qj = step
            mine = slice(qj, qj + sub_q)
            if ki not in vts:  # V's tile turned once for all heads
                vts[ki] = v_ref[keys(ki), :].T
            if (g, qj) not in state:
                state[g, qj] = tuple(ref[g, :, mine] for ref in sums)
            state[g, qj] = _online_softmax(state[g, qj], st,
                                           vts[ki][v_heads[g]])
            if step == last[g, qj]:
                for ref, value in zip(sums, state.pop((g, qj))):
                    ref[g, :, mine] = value

        _behind(steps, scores, softmax, ahead)
        return carry

    # the sums live in the scratch, the loops carry none
    starts = [(jnp.full((1, block_q), NEG_INF, jnp.float32),
               jnp.zeros((1, block_q), jnp.float32),
               jnp.zeros((dv, block_q), jnp.float32)) for _ in heads]
    for g, start in enumerate(starts):
        for ref, value in zip(sums, start):
            ref[g] = value
    if mask is not None:  # 2 seam rows: never the only block
        _bd_k_blocks(pair, None, pl.program_id(2), mask=mask, side=block_q)
    else:
        _over_k_blocks(pair, None, q_start, block_q=block_q, block_k=block_k,
                       n_k=n_k, offset=offset, causal=causal, window=window)
    ends = [tuple(ref[g] for ref in sums) for g in range(len(heads))]
    o_ts = []
    for g, (m, l, acc) in enumerate(ends):
        # Rows that saw no unmasked key (bottom-right-aligned causal with
        # s_q > s_k leaves the first s_q - s_k rows empty) still have m at
        # the NEG_INF sentinel: their p would be exp(0)=1, silently
        # averaging V. Define such rows as zero output, and poison their
        # lse to +|NEG_INF| so the backward's exp(s - lse) underflows to
        # exactly 0 (no grad leak).
        dead = m <= NEG_INF * 0.5
        l = jnp.maximum(l, 1e-30)
        o_ts.append(jnp.where(dead, 0.0, acc / l))
        lse = jnp.where(dead, -NEG_INF, m + jnp.log(l))
        # lse leaves as a [block_q, 1] column (the result's shape): a row
        # of 8 equal sublanes transposed, of which one lane is kept.
        lse_ref[g] = jnp.broadcast_to(lse, (8, block_q)).T[:, :1]
    # the cell's heads one under the other as [lanes, block_q], turned
    # once: whole rows of the model's layout to store
    o_ref[:, :] = _side_by_side(o_ts, 0).T.astype(o_ref.dtype)


def _fwd_call(q, k, v, *, heads: int, causal: bool, scale: float,
              block_q: int, block_k: int, interpret: bool,
              window: Optional[int] = None,
              mask: Optional[BlockDiffusion] = None, select=None):
    """The forward call (``_fwd``: a ``jax.jit`` of its own). The body
    walked in tiles is sixteen times the operations of a pair as one tile, a
    ``pallas_call``'s body is traced and lowered once a USE (the pass,
    remat's, each run of layers, each of the benchmark's programs) and
    tracing is set-up time at every start, cached executable or not: JoyAI's
    step lowered in 11.1 s for the parent's 8.1 before the jit, Ouro's in 5.8
    for 3.4 (PERF.md section 6, PR 49; ``ops/ssd.py _kernel_jit`` is the
    same cure)."""
    # q, k: [B, S, H·d]; v: [B, S, H·dv]
    b, s_q, _ = q.shape
    s_k, (d, dv, _) = k.shape[1], _head_sizes(q, k, v, heads)
    assert s_q % block_q == 0 and s_k % block_k == 0, (s_q, s_k, block_q, block_k)
    n_q, n_k = s_q // block_q, s_k // block_k
    cell = _cell_heads(heads, d, dv, _one_pair(s_q, s_k, block_q, block_k))
    k, v, shared = _shared(q, k, v, heads, cell)
    kernel = functools.partial(
        _fwd_kernel, head_dim=d, value_dim=dv, block_k=block_k, n_q=n_q,
        causal=causal, scale=scale, offset=s_k - s_q, window=window,
        mask=mask,
    )
    more, more_specs = _selection(select, b, n_q, block_q, whole_keys=True)
    if more:
        kernel = functools.partial(_with_selection, kernel, 3)

    def mine(size):
        return pl.BlockSpec((None, block_q, cell * size),
                            lambda b, h, qi: (b, qi, h))

    def whole(size):
        return pl.BlockSpec((None, s_k, cell * size),
                            lambda b, h, qi: (b, 0, h))

    # what a cell holds: its heads' q and out blocks and the WHOLE of their
    # k and v, two buffers each, the lse column (one lane in 128) and the
    # sums. Stated, as the backward states its own, where that and a pair's
    # tiles in flight pass the compiler's own 16 MB and no allowance is
    # named (`_call_name`): a causal forward at 16,384 rows of heads of 128
    # holds 17.3 MB of k and v alone and did not compile (PERF.md section 7,
    # SDAR's (h)); every call that fitted keeps its text
    params = None
    held = (2 * cell * ((block_q + s_k) * (d + dv) * q.dtype.itemsize
                        + block_q * 128 * 4)
            + cell * (dv + 16) * block_q * 4)
    held += sum(2 * 4 * x.shape[2] * x.shape[3] for x in more)
    if d == dv and mask is None and held + _TILES_VMEM > _DEFAULT_VMEM:
        params = pltpu.CompilerParams(
            vmem_limit_bytes=held + _DEFAULT_VMEM)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, pl.cdiv(heads, cell), n_q),
        in_specs=[mine(d), shared(whole(d)), shared(whole(dv)), *more_specs],
        out_specs=[
            mine(dv),
            pl.BlockSpec((None, cell, block_q, 1), lambda b, h, qi: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s_q, heads * dv), q.dtype),
            jax.ShapeDtypeStruct((b, heads, s_q, 1), jnp.float32),
        ],
        # the running max, normaliser and accumulator of a cell's heads
        # (0.26 MB a head at 512 x 128)
        scratch_shapes=[
            pltpu.VMEM((cell, 1, block_q), jnp.float32),
            pltpu.VMEM((cell, 1, block_q), jnp.float32),
            pltpu.VMEM((cell, dv, block_q), jnp.float32)],
        interpret=interpret,
        **_call_name("fwd", d, dv, window, params, mask=mask,
                     selected=bool(more)),
    )(q, k, v, *more)
    return out, lse


def _selection(select, batch: int, n_q: int, block_q: int, *,
               whole_keys: bool, block_k: int = 0):
    """``(operands, specs)`` that hand a kernel the packed selection
    ``select [batch, S_k / 32, S_q]`` (``ops/index.py``; None: nothing), a
    Q-block a leading index ``[batch, n_q, S_k / 32, block_q]``: the forward
    takes its own Q-block's rows of every key (``whole_keys``), the backward
    its K-block's ``block_k / 32`` rows of every Q-block."""
    if select is None:
        return (), ()
    rows = select.shape[1]
    by_block = select.reshape(batch, rows, n_q, block_q).transpose(0, 2, 1, 3)
    if whole_keys:
        spec = pl.BlockSpec((None, None, rows, block_q),
                            lambda b, h, qi: (b, qi, 0, 0))
    else:
        spec = pl.BlockSpec((None, n_q, block_k // 32, block_q),
                            lambda b, h, ki: (b, 0, ki, 0))
    return (by_block,), (spec,)


def _with_selection(kernel, at: int, *refs):
    """``kernel`` handed the selection's ref, which stands behind the
    ``at`` other inputs, by keyword."""
    return kernel(*refs[:at], *refs[at + 1:], select=refs[at])


_fwd = jax.jit(_fwd_call, static_argnames=(
    "heads", "causal", "scale", "block_q", "block_k", "interpret", "window",
    "mask"))


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _over_q_blocks(body, carry, k_start, *, block_q: int, block_k: int,
                   n_q: int, offset: int, causal: bool,
                   window: Optional[int] = None):
    """``body(qb, carry, masked=)`` over the Q-blocks that see the K-block
    at ``k_start``, :func:`_over_k_blocks`' mirror: those before
    ``first_live`` see none of it, [first_live, first_full) are crossed by
    the diagonal and masked, from ``first_full`` on every row sees all of it
    — under a ``window`` up to ``last_full``, where the band's far edge
    crosses the pair (masked again); from ``last_live`` on no row sees any
    of it."""
    first_full, last_live, last_full = 0, n_q, n_q
    masked = functools.partial(body, masked=True)
    if causal:
        first_live = _clip((k_start - offset) // block_q, 0, n_q)
        first_full = _clip(
            (k_start + block_k - 1 - offset + block_q - 1) // block_q, 0, n_q)
        near_end = first_full
        if window is not None:
            last_live = _clip(
                (k_start + block_k - 2 - offset + window) // block_q + 1,
                0, n_q)
            last_full = _least(_clip(
                (k_start - offset + window) // block_q, 0, n_q), last_live)
            near_end = _least(first_full, last_live)
        carry = _loop(first_live, near_end, masked, carry)
    carry = _loop(first_full, last_full, functools.partial(body, masked=False),
                  carry)
    if window is not None:
        carry = _loop(_most(first_full, last_full), last_live, masked, carry)
    return carry


#: keys and queries of a tile of the looped backward's block pair, as the
#: forward's ``_FWD_TILE``: 32 float32 vregs of ``Sᵀ`` and ``dPᵀ`` a tile where
#: the pair as one tile held 512. Swept on a v5e, us a backward call by the
#: kernel's own events, five calls a variant, bf16 (PERF.md section 6, PR 52)
#: at ``[2, 8192, 4 x 128]`` / ``[1, 4096, 16 x 128]`` / ``[1, 8192, 4 x 192 /
#: 128]`` (two heads a cell) / ``[1, 16384, 4 x 128]`` under ``BlockDiffusion(4,
#: 8192)`` / ``[2, 4096, 8 x 64]`` (two heads a cell). The pair as one tile (the
#: parent): 2,426.5 / 1,365.5 / 1,724.0 / 2,513.7 / 1,167.3. Tiles of keys x
#: queries, a tile's two score products ``ahead`` tiles (a share of what a
#: cell's heads have in a pair) in front of the chain that consumes them, dk
#: and dv in a VMEM scratch and a key tile's slices of them values through its
#: query tiles (TAKEN): 128 x 128 ahead a half 2,073.9 / 1,139.2 / 1,486.2 /
#: 2,122.7 / 933.4 (-14 to -20%), a whole 2,073.7 / 1,139.1 / 1,485.7 /
#: 2,122.6 / 933.8 (three eighths and three quarters within 0.1%), a quarter
#: 2,099.9 / 1,151.1 / 1,484.4 / 2,149.0 / 933.9, TWO tiles 2,189.2 / 1,196.8
#: / 1,500.3 / 2,240.8 / 957.3, one 2,470.0 / 1,338.9 / 1,564.9 / 2,526.5 /
#: 1,060.9 (no gain: the depth is the mechanism); 128 x 256 and 256 x 128
#: ahead a half 2,101 / 1,163 / 1,504 / 2,169 / 952 (+1.3 to 2.1%); 128 x 512
#: 2,153.8 / 1,209.9 / 1,546.7 / 2,276.7 / 1,004.6 (+4 to 8%). Other homes of
#: the sums, at 128 x 128 ahead a half: query-major with ``dQᵀ``'s slice the
#: value and dk, dv added to in the scratch a tile 2,079.0 / 1,141.9 / 1,494.6
#: / 2,127.8 / 942.0 (+0.2 to 0.9%); dk and dv carried by the loop as values
#: 2,216.7 / 1,232.8 / 1,652.5 / 2,280.6 / 1,051.2 (+7 to 13%: 128 vregs and
#: more through every turn). Measured no faster and not taken: the sums read
#: and written a tile (two heads a cell +2.5 / +7%), the two heads of a cell
#: alternating tile by tile (+1.4 / +2.5%), a tile's q and dO handed from its
#: products to its chain instead of read again (0.0%). One rule is within
#: 0.1% of the best of each shape: the forward's. Since PR 60 the short
#: shapes take this kernel too (522.9 us a call at ``[8, 1024, 16 x 64]``
#: where the unrolled dq + dk/dv took 357.4 + 473.4: ``SHORT_BLOCK``'s table).
_BWD_TILE = 128


def _bwd_tiles(block_q: int, block_k: int,
               cell_heads: int) -> Tuple[int, int, int]:
    """``(keys, queries, ahead)``: the tiles the looped backward walks a
    block pair in, and how many of them a tile's two score products run
    ahead of the chain that consumes them — the forward's rule
    (:func:`_fwd_tiles`) on the backward's own measurements."""
    return _fwd_tiles(block_q, block_k, cell_heads, _BWD_TILE)


def _bwd_kernel(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref,
    dq_sum_ref, dk_sum_ref, dv_sum_ref, *, head_dim: int, value_dim: int,
    block_q: int, n_k: int, causal: bool, scale: float, offset: int,
    window: Optional[int], mask: Optional[BlockDiffusion] = None,
    select=None,
):
    """The backward, one K-block a grid cell: dq, dk and dv from ONE
    score tile, ``exp``, ``dP`` and ``delta``, a block pair walked in tiles
    (``_BWD_TILE``) as the looped forward's. Under the block mask a K-block
    walks the Q-blocks that see it (:func:`_bd_q_blocks`), a pair on one of
    the mask's diagonals in the tiles that hold a live pair."""
    # q_ref, dq_ref: [S_q, cell heads · d]; o_ref, do_ref: [S_q, cell heads ·
    # dv]; k_ref, dk_ref: [block_k, cell heads · d]; v_ref, dv_ref: [block_k,
    # cell heads · dv]; lse_ref: [cell heads, n_q, 1, block_q]; scratch,
    # float32: dq_sum_ref [n_q, cell heads · d, block_q], dQᵀ by Q-block;
    # dk_sum_ref [cell heads, block_k, d], dv_sum_ref [cell heads, block_k, dv]
    block_k, lanes = dk_ref.shape
    d = head_dim
    heads = _head_cols(lanes, d)
    v_heads = _head_cols(dv_ref.shape[1], value_dim)
    n_q = q_ref.shape[0] // block_q
    # the K-block's place: a Python number where it is the only one
    kb = 0 if n_k == 1 else pl.program_id(2)
    k_start = kb * block_k
    sub_k, sub_q, ahead = _bwd_tiles(block_q, block_k, len(heads))
    # whether the diagonal crosses a masked pair at a place known here: a
    # square of blocks whose corner it passes through
    diagonal = (window is None and block_q == block_k
                and offset % block_k == 0)

    @functools.partial(_when, kb == 0)
    def _():
        # rows no key sees (s_q > s_k) are never added to: they stay zero
        dq_sum_ref[...] = jnp.zeros_like(dq_sum_ref)

    dk_sum_ref[...] = jnp.zeros_like(dk_sum_ref)
    dv_sum_ref[...] = jnp.zeros_like(dv_sum_ref)
    ks = [_fold_scale(k_ref[:, cols], scale) for cols in heads]
    vs = [v_ref[:, cols] for cols in v_heads]
    kt_all = k_ref[...].T  # [lanes, block_k]: turned once a grid cell

    def pair(qb, carry, *, masked):
        # the pair in tiles of [sub_k, sub_q], key tile after key tile, under
        # each the cell's heads and their Q tiles. A tile's FIRST half is the
        # MXU's two products that wait for nothing, ``Sᵀ = K Qᵀ`` and ``dPᵀ
        # = V dOᵀ``, written ``ahead`` tiles in front of its SECOND: ``exp``,
        # ``dSᵀ`` and the three products that consume them. A chain — one
        # head's key tile — has its slices of dk and dv as values through
        # its Q tiles: read from the scratch at its first tile of the pair,
        # written back after its last, so that no turn of the loop carries
        # 128 vregs of sums on a file of 64; dQᵀ's slice is added to in its
        # scratch a tile (_BWD_TILE has the measurements of each).
        # ``masked``: whether the diagonal crosses the pair or, under the
        # block mask, the rule of the pair's diagonal
        q_start = _block_start(qb, block_q)
        steps = [(ki, g, qj) for ki in range(0, block_k, sub_k)
                 for g in range(len(heads))
                 for qj in range(0, block_q, sub_q)]
        placed = masked and mask is None and diagonal
        steps, crossed = _live_tiles(steps, sub_k, sub_q, placed, mask,
                                     masked)
        last = {(g, ki): (ki, g, qj) for ki, g, qj in steps}
        deltas, sums = {}, {}

        def rows_at(qj):
            return pl.ds(_aligned(q_start + qj, sub_q), sub_q)

        def products(step):
            ki, g, qj = step
            there = slice(ki, ki + sub_k)
            k, s_scale = ks[g]
            edge = q_start + offset - k_start + qj - ki \
                if masked and mask is None else None
            if placed and ki + sub_k - 1 <= qj or select is not None:
                edge = None  # (a selection's bits are causal already)
            st = _scores_t(k[there], q_ref[rows_at(qj), heads[g]], s_scale,
                           edge, window)
            if crossed.get((ki, qj)):
                st = _bd_mask(st, masked, mask.block, ki, qj)
            if select is not None:
                # [n_q, block_k / 32, block_q]: this K-block's rows
                group = ki // index.GROUP
                st = _chosen(st, select[qb, group * 8:(group + 1) * 8,
                                        qj:qj + sub_q], ki // sub_k % 2)
            return st, _dot(vs[g][there], do_ref[rows_at(qj), v_heads[g]],
                            _NT)

        def chain(step, made):
            ki, g, qj = step
            st, dpt = made
            there, mine = slice(ki, ki + sub_k), slice(qj, qj + sub_q)
            rows, cols = rows_at(qj), heads[g]
            # a Q tile's statistics are read and made by tile: a lane slice
            # of a [1, block_q] VALUE is a layout Mosaic does not broadcast
            # from
            if qj not in deltas:
                deltas[qj] = _delta_rows(do_ref[rows, :], o_ref[rows, :],
                                         value_dim)
            q, do = q_ref[rows, cols], do_ref[rows, v_heads[g]]
            pt = jnp.exp(st - lse_ref[g, qb, :, mine])
            dst = (pt * (dpt - deltas[qj][g])).astype(q.dtype)
            if (g, ki) not in sums:
                sums[g, ki] = (dk_sum_ref[g, there, :],
                               dv_sum_ref[g, there, :])
            dk, dv = sums[g, ki]
            sums[g, ki] = (dk + _dot(dst, q, _NN),
                           dv + _dot(pt.astype(do.dtype), do, _NN))
            dq_sum_ref[qb, cols, mine] += _dot(kt_all[cols, there], dst, _NN)
            if step == last[g, ki]:
                dk_sum_ref[g, there, :], dv_sum_ref[g, there, :] = sums.pop(
                    (g, ki))

        _behind(steps, products, chain, ahead)
        return carry

    if mask is not None:
        _bd_q_blocks(pair, None, kb, mask=mask, side=block_q)
    else:
        _over_q_blocks(
            pair, None, k_start, block_q=block_q, block_k=block_k, n_q=n_q,
            offset=offset, causal=causal, window=window)
    # q and k entered the products unscaled (the scale sat on k or the
    # scores).
    dk_ref[...] = (_side_by_side([dk_sum_ref[g] for g in range(len(heads))], 1)
                   * scale).astype(dk_ref.dtype)
    dv_ref[...] = _side_by_side([dv_sum_ref[g] for g in range(len(heads))], 1
                                ).astype(dv_ref.dtype)

    @functools.partial(
        _when, kb == (0 if n_k == 1 else pl.num_programs(2) - 1))
    def _():
        def finish(qb, _):
            rows = pl.ds(_block_start(qb, block_q), block_q)
            dq_ref[rows, :] = (dq_sum_ref[qb] * scale).T.astype(dq_ref.dtype)

        _loop(0, n_q, finish, None)


#: what the compiler allows a kernel of VMEM where the call names no limit:
#: the one-kernel backward asks for what its blocks hold and this much for
#: a pair's tiles in flight (all the split looped kernels had); the looped
#: forward states its limit where its blocks leave less than `_TILES_VMEM`
#: of it
_DEFAULT_VMEM = 16 << 20
_TILES_VMEM = 4 << 20


def _bwd_call(q, k, v, out, lse, do, *, heads: int, causal: bool,
              scale: float, block_q: int, block_k: int, interpret: bool,
              window: Optional[int] = None,
              mask: Optional[BlockDiffusion] = None, select=None):
    """The backward (``_bwd``: a ``jax.jit`` of its own, as the forward's):
    ONE call on the grid ``(batch row, lane block, K-block)`` gives all
    three — dq summed in VMEM across the K-block axis, which is therefore
    sequential."""
    b, s_q, _ = q.shape
    s_k, (d, vd, _) = k.shape[1], _head_sizes(q, k, v, heads)
    item = q.dtype.itemsize
    n_q, n_k = s_q // block_q, s_k // block_k
    cell = _cell_heads(heads, d, vd, _one_pair(s_q, s_k, block_q, block_k))
    k, v, shared = _shared(q, k, v, heads, cell)

    def whole(size):
        return pl.BlockSpec((None, s_q, cell * size), lambda b, h, ki: (b, 0, h))

    def mine(size):
        return pl.BlockSpec((None, block_k, cell * size),
                            lambda b, h, ki: (b, ki, h))

    lse_spec, lse_in = _lse_operand(lse, cell, block_q, whole=True)
    # q, dq, O, dO and lse whole and k, v, dk, dv by block, two buffers
    # each; the float32 sums of dq, dk and dv once
    held = (2 * cell * (2 * (s_q + block_k) * (d + vd) * item + s_q * 8 * 4)
            + cell * (d * s_q + (d + vd) * block_k) * 4)
    kernel = functools.partial(
        _bwd_kernel, head_dim=d, value_dim=vd, block_q=block_q, n_k=n_k,
        causal=causal, scale=scale, offset=s_k - s_q, window=window,
        mask=mask)
    more, more_specs = _selection(select, b, n_q, block_q, whole_keys=False,
                                  block_k=block_k)
    if more:
        kernel = functools.partial(_with_selection, kernel, 6)
        held += 2 * 4 * n_q * (block_k // 32) * block_q
    return pl.pallas_call(
        kernel,
        grid=(b, pl.cdiv(heads, cell), n_k),
        in_specs=[whole(d), shared(mine(d)), shared(mine(vd)), whole(vd),
                  whole(vd), lse_spec, *more_specs],
        out_specs=[whole(d), mine(d), mine(vd)],
        # dk and dv a QUERY head: the rule sums a group's (`_flash_bwd`)
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, s_k, heads * d), k.dtype),
            jax.ShapeDtypeStruct((b, s_k, heads * vd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_q, cell * d, block_q), jnp.float32),
            pltpu.VMEM((cell, block_k, d), jnp.float32),
            pltpu.VMEM((cell, block_k, vd), jnp.float32)],
        interpret=interpret,
        **_call_name("bwd", d, vd, window, pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=held + _DEFAULT_VMEM), mask=mask,
            selected=bool(more)),
    )(q, k, v, out, do, lse_in, *more)


_bwd = jax.jit(_bwd_call, static_argnames=(
    "heads", "causal", "scale", "block_q", "block_k", "interpret", "window",
    "mask"))


# ---------------------------------------------------------------------------
# the band path: a window of at most a cell's keys, s_q == s_k
# ---------------------------------------------------------------------------


def _band_pieces(at: int, sub: int, window: int, edge: int, *, keys: bool):
    """``[(start, stop, far, near)]``: the pieces of the other side's rows
    that the sub-block ``[at, at + sub)`` meets under the band, counted from
    the cell's first row — for a Q sub-block its keys (``keys``), ``[at −
    window + 1, at + sub)``, for a K sub-block the queries ``[at, at + sub +
    window − 1)``, both widened to whole 128-row tiles (to sub-blocks where
    those are smaller). Cut at ``edge`` (where the
    cell's own block ends and its neighbour starts) and where a mask starts
    or stops being needed: ``far`` says that the band's far edge crosses the
    piece, ``near`` that the diagonal does; a piece with neither is
    multiplied as it is."""
    align = min(sub, 128)
    if keys:
        lo, hi = (at - window + 1) // align * align, at + sub
        cuts = (edge, -(-(at + sub - window) // align) * align, at)
    else:
        lo, hi = at, -(-(at + sub + window - 1) // align) * align
        cuts = (edge, at + sub, (at + window) // align * align)
    marks = sorted({lo, hi} | {c for c in cuts if lo < c < hi})
    pieces = []
    for start, stop in zip(marks, marks[1:]):
        if keys:  # queries [at, at + sub), keys [start, stop)
            far = start <= at + sub - 1 - window
            near = stop - 1 > at
        else:  # keys [at, at + sub), queries [start, stop)
            far = stop - 1 - at >= window
            near = at + sub - 1 > start
        pieces.append((start, stop, far, near))
    return pieces


class _BandMasks:
    """The masks of one band kernel's pieces, each made once a grid cell
    (every sub-block's pieces have the same few shapes and edges)."""

    def __init__(self, window: int):
        self.window, self.made = window, {}

    def __call__(self, st, bound: int, far: bool, near: bool):
        """``st`` (``[keys, queries]``, the first key ``bound`` rows before
        the first query) with what the crossing edges hide set to NEG_INF:
        key j is seen by query i iff j − i <= bound (``near``: the diagonal)
        and j − i > bound − window (``far``)."""
        if not (far or near):
            return st
        key = (st.shape, bound, far, near)
        if key not in self.made:
            ahead = (jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
                     - jax.lax.broadcasted_iota(jnp.int32, st.shape, 1))
            seen = ahead <= bound if near else None
            if far:
                inside = ahead > bound - self.window
                seen = inside if seen is None else seen & inside
            self.made[key] = seen
        return jnp.where(self.made[key], st, NEG_INF)


def _neighbour_cap(hidden):
    """A ceiling for the scores of a neighbour block's pieces: the sentinel
    in the one cell that has no such neighbour (the sequence's first cell in
    the forward and dq, its last in dk/dv — the block the clipped index map
    hands it instead holds real rows, so every product is finite), no
    ceiling elsewhere. One ``minimum`` a piece; no second program."""
    return jnp.where(hidden, NEG_INF, -NEG_INF)


def _band_fwd_kernel(
    q_ref, k_ref, v_ref, k_prev_ref, v_prev_ref, o_ref, lse_ref,
    *, head_dim: int, value_dim: int, band: Band, window: int, scale: float,
):
    # q_ref, k_ref: [cell rows, cell heads · d]; v_ref, o_ref: [cell rows,
    # cell heads · dv]; k_prev_ref, v_prev_ref: [reach, ..], the block before
    # the cell's own; lse_ref: [cell heads, cell rows, 1]
    rows, lanes = q_ref.shape
    d, sub, reach = head_dim, band.sub, band.reach
    heads = _head_cols(lanes, d)
    wide = _head_cols(v_ref.shape[1], value_dim)
    masks = _BandMasks(window)
    cap = _neighbour_cap(pl.program_id(2) == 0)
    # V turned once a cell for all its heads and sub-blocks
    vt_own, vt_prev = v_ref[...].T, v_prev_ref[...].T

    def scores(at):
        mine = slice(at, at + sub)
        pieces = _band_pieces(at, sub, window, 0, keys=True)
        found = []
        for g, cols in enumerate(heads):
            q, s_scale = _fold_scale(q_ref[mine, cols], scale)
            sts, vts = [], []
            for start, stop, far, near in pieces:
                ref_k, vt, base = ((k_prev_ref, vt_prev, reach) if start < 0
                                   else (k_ref, vt_own, 0))
                there = slice(base + start, base + stop)
                vts.append(vt[wide[g], there])
                st = masks(_scores_t(ref_k[there, cols], q, s_scale, None),
                           at - start, far, near)
                sts.append(jnp.minimum(st, cap) if start < 0 else st)
            found.append((sts, vts))
        return found

    def softmax(found):
        out = []
        for sts, vts in found:
            m = functools.reduce(jnp.maximum, [
                jnp.max(st, axis=0, keepdims=True) for st in sts])
            pts = [jnp.exp(st - m) for st in sts]
            l = _total([jnp.sum(pt, axis=0, keepdims=True) for pt in pts])
            out.append((m, l, pts, vts))
        return out

    def finish(at, found):
        mine = slice(at, at + sub)
        o_ts = []
        for g, (m, l, pts, vts) in enumerate(found):
            acc = _total([_dot(vt, pt.astype(vt.dtype), _NN)
                          for vt, pt in zip(vts, pts)])
            o_ts.append(acc / l)
            lse = m + jnp.log(l)
            lse_ref[g, mine, :] = jnp.broadcast_to(lse, (8, sub)).T[:, :1]
        o_ref[mine, :] = _side_by_side(o_ts, 0).T.astype(o_ref.dtype)

    _behind(range(0, rows, sub), scores,
                lambda at, found: finish(at, softmax(found)))


def _band_dq_kernel(
    q_ref, k_ref, v_ref, k_prev_ref, v_prev_ref, o_ref, do_ref, lse_ref,
    dq_ref, *, head_dim: int, value_dim: int, band: Band, window: int,
    scale: float,
):
    # lse_ref: [cell heads, 1, 1, cell rows]
    rows, lanes = q_ref.shape
    d, sub, reach = head_dim, band.sub, band.reach
    heads = _head_cols(lanes, d)
    wide = _head_cols(v_ref.shape[1], value_dim)
    masks = _BandMasks(window)
    cap = _neighbour_cap(pl.program_id(2) == 0)
    # K turned once a cell for all its heads and sub-blocks
    kt_own, kt_prev = k_ref[...].T, k_prev_ref[...].T

    def products(at):
        mine = slice(at, at + sub)
        pieces = _band_pieces(at, sub, window, 0, keys=True)
        found = []
        for g, cols in enumerate(heads):
            q, s_scale = _fold_scale(q_ref[mine, cols], scale)
            do = do_ref[mine, wide[g]]
            parts = []
            for start, stop, far, near in pieces:
                ref_k, ref_v, kt, base = (
                    (k_prev_ref, v_prev_ref, kt_prev, reach) if start < 0
                    else (k_ref, v_ref, kt_own, 0))
                there = slice(base + start, base + stop)
                st = masks(_scores_t(ref_k[there, cols], q, s_scale, None),
                           at - start, far, near)
                if start < 0:
                    st = jnp.minimum(st, cap)
                parts.append((st, _dot(ref_v[there, wide[g]], do, _NT),
                              kt[cols, there]))
            found.append(parts)
        return found

    def finish(at, found):
        mine = slice(at, at + sub)
        deltas = _delta_rows(do_ref[mine, :], o_ref[mine, :], value_dim)
        dq_ts = []
        for g, parts in enumerate(found):
            lse = lse_ref[g, 0, :, mine]
            dq_ts.append(_total([
                _dot(kt, (jnp.exp(st - lse) * (dpt - deltas[g])
                          ).astype(kt.dtype), _NN)
                for st, dpt, kt in parts]))
        dq_ref[mine, :] = (_side_by_side(dq_ts, 0) * scale
                           ).T.astype(dq_ref.dtype)

    for at in range(0, rows, sub):
        finish(at, products(at))


def _band_dkv_kernel(
    k_ref, v_ref, q_ref, o_ref, do_ref, lse_ref, q_next_ref, o_next_ref,
    do_next_ref, lse_next_ref, dk_ref, dv_ref, delta_ref,
    *, head_dim: int, value_dim: int, band: Band, window: int, scale: float,
):
    # k_ref, q_ref, dk_ref: [cell rows, cell heads · d]; v_ref, o_ref,
    # do_ref, dv_ref: [cell rows, cell heads · dv]; the *_next_ref: [reach,
    # ..], the block after the cell's
    # own; lse_ref: [cell heads, 1, 1, cell rows], lse_next_ref: [.., reach];
    # delta_ref (scratch): [cell heads, 1, cell rows + reach]
    rows, lanes = k_ref.shape
    d, sub = head_dim, band.sub
    heads = _head_cols(lanes, d)
    wide = _head_cols(v_ref.shape[1], value_dim)
    masks = _BandMasks(window)
    cap = _neighbour_cap(pl.program_id(2) == pl.num_programs(2) - 1)
    # delta of every query the cell meets, once: rows [0, rows) its own
    # block's, the rest its neighbour's (a ref, so that a piece's lanes are
    # read and not cut out of a value)
    for g, (own, nxt) in enumerate(zip(
            _delta_rows(do_ref[...], o_ref[...], value_dim),
            _delta_rows(do_next_ref[...], o_next_ref[...], value_dim))):
        delta_ref[g, :, :rows] = own
        delta_ref[g, :, rows:] = nxt
    for at in range(0, rows, sub):
        mine = slice(at, at + sub)
        pieces = _band_pieces(at, sub, window, rows, keys=False)
        dks, dvs = [], []
        for g, cols in enumerate(heads):
            k, s_scale = _fold_scale(k_ref[mine, cols], scale)
            v = v_ref[mine, wide[g]]
            dk_parts, dv_parts = [], []
            for start, stop, far, near in pieces:
                nxt = start >= rows
                base = rows if nxt else 0
                there = slice(start - base, stop - base)
                q = (q_next_ref if nxt else q_ref)[there, cols]
                do = (do_next_ref if nxt else do_ref)[there, wide[g]]
                lse = (lse_next_ref if nxt else lse_ref)[g, 0, :, there]
                st = masks(_scores_t(k, q, s_scale, None), start - at, far,
                           near)
                if nxt:
                    st = jnp.minimum(st, cap)
                pt = jnp.exp(st - lse)
                dv_parts.append(_dot(pt.astype(do.dtype), do, _NN))
                dst = pt * (_dot(v, do, _NT) - delta_ref[g, :, start:stop])
                dk_parts.append(_dot(dst.astype(q.dtype), q, _NN))
            dks.append(_total(dk_parts))
            dvs.append(_total(dv_parts))
        # q entered the products unscaled (the scale sat on k or the scores).
        dk_ref[mine, :] = (_side_by_side(dks, 1) * scale).astype(dk_ref.dtype)
        dv_ref[mine, :] = _side_by_side(dvs, 1).astype(dv_ref.dtype)


def _band_neighbours(band: Band, s: int):
    """``(before, after)``: grid cell ``i``'s neighbour blocks, counted in
    blocks of ``reach`` rows and clipped at the sequence's ends (a cell
    there hides what it is given instead: ``_neighbour_cap``)."""
    per, last = band.rows // band.reach, s // band.reach - 1
    return (lambda i: jnp.maximum(i * per - 1, 0),
            lambda i: jnp.minimum((i + 1) * per, last))


def _band_specs(band: Band, lanes: int, s: int):
    """``(own, prev, next)`` BlockSpecs over a ``[B, S, H·d]`` array for grid
    cell ``(batch row, lane block, i)``: the cell's own ``rows``, and the
    ``reach`` rows before and after them — the same array handed to the
    kernel again under its neighbour's block index."""
    before, after = _band_neighbours(band, s)
    return (
        pl.BlockSpec((None, band.rows, lanes), lambda b, h, i: (b, i, h)),
        pl.BlockSpec((None, band.reach, lanes),
                     lambda b, h, i: (b, before(i), h)),
        pl.BlockSpec((None, band.reach, lanes),
                     lambda b, h, i: (b, after(i), h)),
    )


def _band_fwd(q, k, v, *, heads: int, scale: float, band: Band, window: int,
              interpret: bool):
    b, s, _ = q.shape
    d, dv, _ = _head_sizes(q, k, v, heads)
    cell = _cell_heads(heads, d, dv)
    k, v, shared = _shared(q, k, v, heads, cell)
    own, prev, _ = _band_specs(band, cell * d, s)
    own_v, prev_v, _ = _band_specs(band, cell * dv, s)
    return pl.pallas_call(
        functools.partial(_band_fwd_kernel, head_dim=d, value_dim=dv,
                          band=band, window=window, scale=scale),
        grid=(b, pl.cdiv(heads, cell), s // band.rows),
        in_specs=[own, shared(own), shared(own_v), shared(prev),
                  shared(prev_v)],
        out_specs=[
            own_v,
            pl.BlockSpec((None, cell, band.rows, 1),
                         lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, heads * dv), q.dtype),
            jax.ShapeDtypeStruct((b, heads, s, 1), jnp.float32),
        ],
        compiler_params=_BAND_PARAMS,
        interpret=interpret,
        name="swa_fwd",
    )(q, k, v, k, v)


def _band_bwd(q, k, v, out, lse, do, *, heads: int, scale: float,
              dq_band: Band, dkv_band: Band, window: int, interpret: bool):
    b, s, _ = q.shape
    d, dv, _ = _head_sizes(q, k, v, heads)
    cell = _cell_heads(heads, d, dv)
    k, v, shared = _shared(q, k, v, heads, cell)
    static = dict(head_dim=d, value_dim=dv, window=window, scale=scale)

    band = dq_band
    own, prev, _ = _band_specs(band, cell * d, s)
    own_v, prev_v, _ = _band_specs(band, cell * dv, s)
    lse_spec, lse_in = _lse_operand(lse, cell, band.rows, whole=False)
    dq = pl.pallas_call(
        functools.partial(_band_dq_kernel, band=band, **static),
        grid=(b, pl.cdiv(heads, cell), s // band.rows),
        in_specs=[own, shared(own), shared(own_v), shared(prev),
                  shared(prev_v), own_v, own_v, lse_spec],
        out_specs=own,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_BAND_PARAMS,
        interpret=interpret,
        name="swa_bwd_dq",
    )(q, k, v, k, v, out, do, lse_in)

    band = dkv_band
    own, _, nxt = _band_specs(band, cell * d, s)
    own_v, _, nxt_v = _band_specs(band, cell * dv, s)
    lse_spec, lse_in = _lse_operand(lse, cell, band.rows, whole=False)
    lse_next_spec, lse_next_in = _lse_operand(
        lse, cell, band.reach, whole=False, at=_band_neighbours(band, s)[1])
    dk, dv = pl.pallas_call(
        functools.partial(_band_dkv_kernel, band=band, **static),
        grid=(b, pl.cdiv(heads, cell), s // band.rows),
        in_specs=[shared(own), shared(own_v), own, own_v, own_v, lse_spec,
                  nxt, nxt_v, nxt_v, lse_next_spec],
        out_specs=[own, own_v],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, heads * d), k.dtype),
            jax.ShapeDtypeStruct((b, s, heads * dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((cell, 1, band.rows + band.reach), jnp.float32)],
        compiler_params=_BAND_PARAMS,
        interpret=interpret,
        name="swa_bwd_dkv",
    )(k, v, q, out, do, lse_in, q, out, do, lse_next_in)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, heads, causal, scale, blocks: Blocks, interpret, window,
           mask=None, keeps=(False, False)):
    return _flash_fwd(q, k, v, heads, causal, scale, blocks, interpret,
                      window, mask, keeps)[0]


def _flash_fwd(q, k, v, heads, causal, scale, blocks: Blocks, interpret,
               window, mask=None, keeps=(False, False)):
    if isinstance(blocks[0], Band):
        out, lse = _band_fwd(q, k, v, heads=heads, scale=scale,
                             band=blocks[0], window=window,
                             interpret=interpret)
    else:
        out, lse = _fwd(
            q, k, v, heads=heads, causal=causal, scale=scale,
            block_q=blocks[0][0], block_k=blocks[0][1], interpret=interpret,
            window=window, mask=mask,
        )
    # The kernel's column [B, H, S, 1] turned once into dense rows
    # [B, H, S]; both named HERE, so that the residuals below are the named
    # values (a name on the primal outside the rule would name a copy, and
    # the kernel's own results would still be made again). Whether they
    # are named (`keeps`), and so whether a rematerialised block keeps them
    # or runs this kernel again, ops/remat.py's rule said where the call was
    # traced, on what the call shows and the room the step has. Where
    # nothing is differentiated the turn is dead code.
    out, lse = remat.name_flash(out, lse.reshape(lse.shape[:3]), keeps)
    return out, (q, k, v, out, lse)


def _flash_bwd(heads, causal, scale, blocks: Blocks, interpret, window, mask,
               keeps, res, g):
    q, k, v, out, lse = res
    if isinstance(blocks[1], Band):
        dq, dk, dv = _band_bwd(q, k, v, out, lse, g, heads=heads, scale=scale,
                               dq_band=blocks[1], dkv_band=blocks[2],
                               window=window, interpret=interpret)
    else:
        dq, dk, dv = _bwd(
            q, k, v, out, lse, g, heads=heads, causal=causal, scale=scale,
            block_q=blocks[2][0], block_k=blocks[2][1], interpret=interpret,
            window=window, mask=mask,
        )
    return (dq, *_group_sums(dk, dv, q, k, v, heads))


def _group_sums(dk, dv, q, k, v, heads: int):
    """dk and dv at the key/value heads."""
    ratio = _head_sizes(q, k, v, heads)[2]
    if ratio > 1:
        # dk and dv leave the kernels a QUERY head (the looped backward's
        # K-block axis carries dq's sum, so a group's heads cannot be that
        # axis's neighbours too); a shared head's gradient is its readers'
        # sum — what the transpose of a repeat in front of the kernels was,
        # in the same dtype
        dk, dv = (jax.lax.reduce_sum(
            dx.reshape(*x.shape[:2], heads // ratio, ratio, -1),
            axes=(3,)).reshape(x.shape) for dx, x in ((dk, k), (dv, v)))
    return dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_selected(q, k, v, select, heads, scale, blocks: Blocks, interpret,
                    keeps=(False, False)):
    """``(out, lse rows)`` of causal attention under the packed selection
    ``select`` (``ops/index.py``: an int array, no gradient). The rows
    ``[batch, heads, seq]`` leave for a reader that takes no gradient
    through them (the index's own loss): their cotangent is not read."""
    return _flash_selected_fwd(q, k, v, select, heads, scale, blocks,
                               interpret, keeps)[0]


def _flash_selected_fwd(q, k, v, select, heads, scale, blocks: Blocks,
                        interpret, keeps=(False, False)):
    out, lse = _fwd(
        q, k, v, heads=heads, causal=True, scale=scale,
        block_q=blocks[0][0], block_k=blocks[0][1], interpret=interpret,
        select=select)
    out, lse = remat.name_flash(out, lse.reshape(lse.shape[:3]), keeps)
    return (out, lse), (q, k, v, select, out, lse)


def _flash_selected_bwd(heads, scale, blocks: Blocks, interpret, keeps, res,
                        g):
    q, k, v, select, out, lse = res
    dq, dk, dv = _bwd(
        q, k, v, out, lse, g[0], heads=heads, causal=True, scale=scale,
        block_q=blocks[2][0], block_k=blocks[2][1], interpret=interpret,
        select=select)
    return (dq, *_group_sums(dk, dv, q, k, v, heads), None)


_flash_selected.defvjp(_flash_selected_fwd, _flash_selected_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
    window: Optional[int] = None,
    mask: Optional[BlockDiffusion] = None,
    select: Optional[jax.Array] = None,
):
    """Flash attention over [batch, seq, heads, head_dim] tensors: the
    kernels' result, or ValueError where they cannot tile the lengths
    (:func:`choose_blocks` is the question; this module holds no other
    path). ``v`` may carry a head size of its own (``[batch, seq, heads,
    value_dim]``: the result's); the calls are then named ``mla_*``.

    Grouped queries: k and v may carry fewer heads than q, ``kv_heads``
    dividing ``heads``; query head ``h`` reads key/value head ``h // (heads
    // kv_heads)`` BY ITS INDEX in every kernel (:func:`_shared`), and dk,
    dv come back at ``kv_heads``, each the sum of its readers'.

    ``window`` (with ``causal``): query i sees only the ``window`` keys up
    to its own, ``0 <= i + (s_k - s_q) - j < window``. K-blocks (Q-blocks in
    the backward) wholly outside that band are not visited — on a square
    problem whose window a cell's neighbour holds not even resident (the
    band path) —
    and the calls carry names of their own (``swa_fwd``, ``swa_bwd_dq``,
    ``swa_bwd_dkv``; looped, ``swa_bwd``).

    ``mask`` (a :class:`BlockDiffusion`; without ``causal``): the rows are
    one sequence's ``[noised || clean]`` halves under block diffusion's
    mask, which is neither causal nor a window. Block pairs no row of which
    sees a key are not visited, a tile of 128 x 128 wholly dead inside a
    visited pair is not computed, and the calls carry names of their own
    (``bd_fwd``, ``bd_bwd``).

    ``select`` (with ``causal``, a square problem): the packed selection of
    a learned index (``ops/index.py``: ``[batch, seq / 32, seq]`` int32, a
    bit a pair, causal already) — a mask that is DATA of the step. A pair it
    leaves out adds nothing to ``out``, to ``lse`` or to a gradient; the
    blocks and tiles visited are the causal ones (which hold a selected pair
    is not known where the program is written), each under its bits. The
    result is then ``(out, lse)``, the forward's statistics ``[batch, heads,
    seq]`` beside it for a reader that takes no gradient through them, and
    the calls are named ``dsa_fwd``, ``dsa_bwd``. Remat's rule weighs the
    call by the CAUSAL pairs: what making it again costs is what the kernel
    visits, not what the selection keeps.

    ``block_q`` / ``block_k``, when passed, hold for every kernel; left out,
    each kernel's are chosen from what the call shows."""
    b, s, h, d = q.shape
    s_k, kv_heads, dv = k.shape[1], k.shape[2], v.shape[-1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if h % kv_heads or v.shape[2] != kv_heads:
        raise ValueError(
            f"flash attention: {h} query heads over {kv_heads} / "
            f"{v.shape[2]} key / value heads: every key/value head is read "
            f"by as many query heads (flash_attention refuses it)")
    if mask is not None and (causal or window is not None):
        raise ValueError(
            f"flash attention: the block mask {mask} replaces causal="
            f"{causal} and window={window}: it is neither (flash_attention "
            f"refuses the pair)")
    if mask is not None and dv != d:
        raise NotImplementedError(
            f"flash attention: the block mask {mask} with head sizes {d} / "
            f"{dv} (flash_attention refuses the pair)")
    if window is not None and (not causal or window < 1):
        raise ValueError(f"flash attention: window={window} needs causal "
                         f"attention and at least one key")
    if window is not None and d > dv:
        raise NotImplementedError(
            f"flash attention: a window with head sizes {d} / {dv}")
    if select is not None and (not causal or window is not None or mask
                               is not None or s != s_k or d != dv):
        raise NotImplementedError(
            f"flash attention: a selection stands on causal attention over "
            f"a square problem at one head size; got causal={causal}, "
            f"window={window}, mask={mask}, lengths {s}/{s_k}, head sizes "
            f"{d}/{dv} (flash_attention refuses it)")
    blocks = choose_blocks(s, s_k, causal, block_q, block_k, window, mask)
    if select is not None and blocks is not None and (
            blocks[0] != blocks[2] or blocks[0][1] % index.GROUP):
        # a tile of the walk is index.TILE keys of one group of the packed
        # words
        raise NotImplementedError(
            f"flash attention: a selection over blocks {blocks[0]} / "
            f"{blocks[2]}: its words are unpacked {index.TILE} keys of a "
            f"group of {index.GROUP} at a time (flash_attention refuses it)")
    if blocks is None:
        raise ValueError(
            f"flash attention: lengths q={s} k={s_k} have no block divisor "
            f"<= {block_q or MAX_BLOCK}/{block_k or MAX_BLOCK}"
            + ("" if mask is None else
               f" that is whole blocks of {mask} over 2 x {mask.seam} rows"))
    device = jax.devices()[0]
    how = "INTERPRETED" if interpret else "compiled"
    cuts, tile = dict(zip(("fwd", "dq", "dkv"), blocks)), _cell_heads(h, d, dv)
    if not isinstance(blocks[2], Band):
        cuts = {"fwd": blocks[0], "bwd": blocks[2]}  # what _bwd will call
        # what _fwd_call and _bwd_call will take: wider where a head is one
        # short pair
        tile = _cell_heads(h, d, dv, _one_pair(s, s_k, *blocks[0])
                           if blocks[0] == blocks[2] else None)

    def walk(name, cut):
        sub_k, sub_q, ahead = (_fwd_tiles if name == "fwd" else _bwd_tiles)(
            *cut, tile)
        if (sub_q, sub_k) == cut:
            return "looped"
        return f"looped in tiles of {sub_q}/{sub_k}, {ahead} behind"

    chosen = ", ".join(
        f"{name} band {cut.rows}/{cut.sub} beside {cut.reach}"
        if isinstance(cut, Band) else
        f"{name} {cut[0]}/{cut[1]} {walk(name, cut)}"
        for name, cut in cuts.items()) + (", one kernel" if "bwd" in cuts
                                          else "")
    sizes = f"head_dim {d}" if dv == d else f"head_dim {d}/{dv}"
    lanes = f"{tile * d}-lane block" if dv == d else \
        f"{tile * d}-lane block of q, k and a {tile * dv}-lane block of v, O"
    ratio = h // kv_heads
    repeat = _cell_repeat(ratio, tile)
    shared = "" if ratio == 1 else (
        f"; k, v of {kv_heads} head(s), {ratio} query heads each, "
        + ("read by index" if repeat == 1 else
           f"repeated {repeat}-fold to a cell's heads"))
    log_once(log, f"flash attention: {how} Pallas kernel on "
                  f"{device.platform} ({device.device_kind}), "
                  f"{jnp.dtype(q.dtype).name} operands to the MXU, blocks "
                  f"q/k {chosen}, over lengths {s}/{s_k}"
                  + (f" (window {window})" if window is not None else "")
                  + ("" if mask is None else
                     " ({0}: {1} of {2} block pairs visited)".format(
                         mask, *mask.block_pairs(blocks[0][0])))
                  + ("" if select is None else
                     " (under a selection, a bit a pair: dsa_fwd, dsa_bwd)")
                  + f", {sizes}, on "
                  f"[batch, seq, heads·head_dim] = [{b}, {s}, {h * d}] with "
                  f"{tile} head(s) to a {lanes}{shared}")
    # [B, S, H, d] -> [B, S, H·d] and back: the same bytes in the same order
    keeps = remat.flash_keeps(
        jax.ShapeDtypeStruct((b, s, h * dv), q.dtype),
        jax.ShapeDtypeStruct((b, h, s), jnp.float32), s_k=s_k, head_dim=d,
        causal=causal, window=window, pairs=mask and mask.pairs())
    if select is not None:
        out, lse = _flash_selected(
            q.reshape(b, s, h * d), k.reshape(b, s_k, kv_heads * d),
            v.reshape(b, s_k, kv_heads * dv), select, h, scale, blocks,
            interpret, keeps)
        return out.reshape(b, s, h, dv), lse
    out = _flash(
        q.reshape(b, s, h * d), k.reshape(b, s_k, kv_heads * d),
        v.reshape(b, s_k, kv_heads * dv), h, causal, scale, blocks, interpret,
        window, mask, keeps,
    )
    return out.reshape(b, s, h, dv)
