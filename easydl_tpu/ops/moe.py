"""An expert layer that is told which experts it holds.

A fine-grained mixture-of-experts FFN as DeepSeek-V3-class models have it
(Laguna's ``sparse`` layers): a router over ALL ``experts_total`` experts in
float32 (one of three described forms, below: sigmoid scores, the ``k``
largest, their scores renormalised and scaled; the same over a softmax; an
MLP's softmax top-1), experts of a described form (:data:`EXPERT_FORMS`: SwiGLU, three
matrices, or NemotronH's ungated ``relu(h W_up)^2 W_down``, two), and a
shared expert of the same form that every token passes through (none where
``shared_d_ff`` is 0: Mellum 2's layers share nothing).
The layer holds a contiguous range ``experts_held = [lo, hi)`` of the routed
experts — all of them, or one chip's share of an expert-parallel group — and
computes ITS experts' part of the result: the choices that fall in the range
sorted by expert, their tokens' rows gathered, three (or two) grouped matrix
products over the experts held, the weighted sum back per token; plus the
shared expert, once. What absent experts would have added is left out; no code
stands in for them or for their traffic.

The grouped products are Pallas kernels of this module (:func:`grouped_rows`
``[R, C] x [G, C, N] -> [R, N]``, with the weights as they lie or transposed,
and :func:`grouped_weights` ``[R, C]^T [R, N] -> [G, C, N]``). A schedule of
(group, row tile) steps is built on the device from the groups' sizes and
handed in by scalar prefetch: row tiles on the rows' own grid, a group's tiles
one after the other with its weight block — the contraction whole, every
column where 8 MiB hold them — resident meanwhile, a tile two groups share
visited once for each and stored under a mask, float32 sums and one rounding.
Rows behind the last group are neither visited nor trusted: the row kernels
write nothing there that is read, the weight gradient stops at the last
group's end and masks a tile's foreign rows on both operands. The tiles come
from the shapes (:func:`choose_tiles`). The experts' differentiation rule is
written out around them (:func:`_piece_backward`): a Pallas call has no
derivative, and the rule needs no product a third time. Measured on the chip
at ZAYA1's 8 experts of 2048 x 2048 over 7,700 of 15,488 rows (PERF.md section
6, PR 36): the compiler's own grouped product, which this replaced — itself a
Mosaic call, on row tiles of 128 — 1.14 / 0.76 / 0.93 ms a call by form (29 /
43 / 35% of the MXU's peak for the rows that landed), these 0.53 / 0.53 / 0.55
(62%).

No token is ever dropped, and the layer moves the rows that landed, not the
rows that could. A token's ``k`` choices are distinct experts, so at most
``min(k, held)`` of them fall here — ``tokens x min(k, held)`` rows
(:func:`rows_bound`) — while ``k x held / total`` do on average (one in
Laguna's eight-chip group, two in Mellum 2's four-chip group). So the sort is worked through in pieces of twice
the expected load (:func:`piece_rows`; the bound itself where all experts are
held): one function of a piece's rows, run ``ceil(landed / piece)`` times —
once for a balanced load, again and again up to the bound where everything
lands here — inside the layer's own differentiation rule (:func:`_routed`:
reverse mode cannot differentiate a loop whose length is traced; the experts'
weight gradients are summed over the pieces). Every choice that falls here
has a row for any routing; the counter ``dropped`` (choices in the range that
no piece gave a row) says so in every step, ``overflow`` how often more than
one piece was needed.

Half of a balanced piece is dead by construction, and only the kernels knew:
their schedules stop at the last live tile, but XLA's row gathers between
them ran over the whole static piece, and a row gather from HBM to HBM costs
by the ROW, dead or live: 37.5 ns each at 2,304 lanes, 2.46 ms a call on a
piece of 65,536 (PERF.md section 6, PR 46). Three of a layer's five gathers
are of that kind — the rows re-ordered by token in front of each sum back
(their source is a piece, which no fast memory holds) and the cotangent's
rows — and run, as do the two gathers of the choices' weights (a scalar
costs the chip what a row does), over the piece's live CHUNKS alone
(:func:`_live_rows`): a chunk is a ninth of a piece in whole row tiles
(:func:`chunk_rows`), the gather is a function of a chunk's rows, run for
chunks ``0 .. ceil(live / chunk) - 1`` by a loop whose length is traced (it
needs no derivative inside the hand-written rule) and written in place into
a piece-sized array that starts UNWRITTEN (:func:`_unwritten`: zeros would
be a pass over it). Rows behind the last live chunk are never made; nothing
reads them (the kernels by their schedules and live counts, ``scale`` by
``live``). A chunk's row costs 41
ns — 23 to gather it into VMEM, 18 for XLA's ``dynamic-update-slice`` to put
it in its place, a pass of its own that no fusion takes — so the loop wins by
the rows it leaves out and by nothing else, and what is cheaper whole stays
whole: the layer's own input ``h`` is where XLA keeps it in VMEM, and a
whole-piece gather of it runs at 7 ns a row; the activation and the
backward's float32 chain are passes at the memory's rate, which a copy a
chunk eats up. The chunks are nine because a balanced load must fall INSIDE a
chunk, not on an edge: a piece is twice the expected load, so any even split
puts the expected count exactly on a boundary and a balanced layer would flip
between one chunk more and one less by the step; nine puts the nearest edges
at 0.89 and 1.11 of it, and five of nine run (``moe_row_fill`` 0.9: the
landed rows over the rows the chunk loops made).

The sums back to the tokens are formed from the rows' side. ``y[t]`` (the
forward) and ``dh[t]`` (the gather's transpose) are sums of a piece's rows
into their tokens' rows, accumulated in float32 from the float32-weighted rows
and rounded once (:func:`_to_tokens`); a choice's weight gradient is its row's
product with its token's cotangent, a scalar put back by the choice's number.
Measured on the chip at 32,768 rows of 2048 (PERF.md section 6, PR 32): a
gather of all ``k`` choices a token — the form this replaces, 7/8 of its rows
dead — 4.4 ms a call on the bound's 131,072 rows; XLA's own scatter-add of
the float32 rows 2.7 ms and 0.75 to write them; the rows re-ordered by token
and each block of 128 tokens summed from the slab of rows it owns by a
selection product in a Pallas kernel, the form that stayed.

The router is a described form (:data:`ROUTERS`). ``linear-sigmoid-
renormalised`` is the one above; with ``selection_bias`` (DeepSeek-V3's
``noaux_tc``) the ``k`` largest of ``scores + b`` are chosen, ``b`` a leaf
``router_bias [experts_total]`` at zero that takes no gradient, and the
weights are the chosen experts' scores without it (:func:`route`; the bias's
load-driven update is a training recipe's and is not here).
``linear-softmax-renormalised`` (Mellum 2's; the Qwen3-MoE / Mixtral
convention with ``norm_topk_prob``) is the same linear router with the softmax
over ALL experts for its scores: the ``k`` largest, each weight its
probability over the chosen ones' sum — the softmax over the chosen logits
alone — times ``scaling``; no selection bias (it raises); the entropy is the
softmax's own, and the layer counts one thing more, ``router_chosen_mass``,
the mean softmax mass on the chosen ``k`` before the renormalisation (``k /
experts`` at uniform logits).
``mlp-softmax-top1`` is ZAYA's
(:func:`route_mlp`): the normed input projected DOWN to ``router_hidden``
(with a bias), the previous layer's router state added to it through a learned
gain (the state runs through the depth: the layer takes it and hands its own
on), RMSNorm, a three-layer GELU MLP to ``experts_total`` logits and, with
``skip_choice``, one more — a token whose largest probability is the last
takes nothing from this layer's experts — softmax, the largest, its
probability itself the weight (not renormalised: with one choice that would
be the constant 1 and the router would get no gradient). The sort, the
pieces, the grouped products and the sums back are the same code with ``k =
1``; the skip choice is an expert held nowhere.

Under a mesh whose ``ep`` axis is larger than one the expert weights are
sharded over it (rule ``("expert", "ep")``, ``core/sharding.py``): each shard
holds ``held / ep`` experts of the range, routes the tokens it has over all
experts, computes its experts' part, and the parts are summed over ``ep``
(``jax.shard_map``: the grouped products are kernels, which GSPMD cannot
partition). Tokens stay where the batch axes put them: there is no
all-to-all, each ``ep`` shard sees every token of its batch shard.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from easydl_tpu.core.mesh_shapes import BATCH_AXES
from easydl_tpu.ops import platform, remat
from easydl_tpu.utils.logging import get_logger, log_once

log = get_logger("ops", "moe")

#: mesh axis the held experts are sharded over (core/sharding.py rules)
EXPERT_AXIS = "ep"

#: the layer's counters, in the order of the vector it returns
COUNTERS = ("moe_dropped", "moe_rows_per_token", "moe_load_max_over_mean",
            "moe_buffer_fill", "router_entropy", "moe_overflow",
            "moe_tile_fill", "moe_row_fill")
#: the router's forms
ROUTERS = ("linear-sigmoid-renormalised", "mlp-softmax-top1",
           "linear-softmax-renormalised")
#: an expert's forms, the shared expert's too: ``swiglu`` — three matrices,
#: ``(silu(h W_gate) * (h W_up)) W_down`` — and ``relu2`` — two, ungated,
#: ``relu(h W_up)^2 W_down`` (NemotronH's ``mlp_hidden_act``). The routed
#: experts' leaves travel as a tuple in that order, ``(w_gate, w_up,
#: w_down)`` or ``(w_up, w_down)``, and its length says the form
EXPERT_FORMS = ("swiglu", "relu2")
#: the MLP router's three maps start orthogonal (the last with orthonormal
#: columns), the first at this scale and the others at that: every choice's
#: logit is then the same isotropic map of the normed state, and its hidden
#: units see inputs of 0.05, where GELU is linear to a part in forty — so at
#: seeded weights every choice has the same chance whatever the seed. Normal
#: 0.02 maps do not give that: GELU's positive mean at inputs of 0.3, passed
#: through a random map, is an offset of a quarter of the logits' spread that
#: no token moves, and one choice of seventeen then takes 2.3-2.9 times its
#: share and the rows that land here 0.39-0.50 a token by the seed alone
#: (PERF.md section 6, PR 35)
MLP_ROUTER_INIT = (0.05, 1.0)


def _zero_column_sums(init):
    """``init``'s values less their mean over the map's INPUT axis (the
    second to last): every output's weights then sum to zero
    (``MoeMlp.down_zero_sums``)."""
    def centred(key, shape, dtype=jnp.float32):
        w = init(key, shape, dtype)
        return w - jnp.mean(w, axis=-2, keepdims=True)
    return centred


def counters(skip_choice: bool = False,
             router: str = ROUTERS[0]) -> Tuple[str, ...]:
    """The names of the vector a layer returns: :data:`COUNTERS`, the share
    of tokens that took the skip choice where the router has one, and the
    softmax mass on the chosen ``k`` where the router is the linear
    softmax."""
    return COUNTERS + (("moe_skipped",) if skip_choice else ()) \
        + (("router_chosen_mass",) if router == ROUTERS[2] else ())


#: a piece of the sort holds this many times the rows expected to land on
#: the experts held (a balanced deployment lands the expected count; Laguna's
#: cell 0.001-1.73 of it over its window: PERF.md section 6, PR 31)
PIECE_OVER_EXPECTED = 2
#: a tile of the MXU's: rows a piece is rounded up to and, in the sum back to
#: the tokens, rows a step reads and tokens a block of the result holds
TILE = 128


def rows_bound(tokens: int, k: int, held: int) -> int:
    """Rows of the sort that can hold a choice: every choice that can fall
    on ``held`` experts (a token's ``k`` choices are distinct)."""
    return tokens * min(k, held)


def piece_rows(tokens: int, k: int, held: int, total: int) -> int:
    """Rows the layer moves at a time: twice the ``tokens x k x held /
    total`` expected to land on ``held`` of ``total`` experts, in whole row
    tiles; the bound itself where that is no more (all experts held)."""
    expected = -(-PIECE_OVER_EXPECTED * tokens * k * held // total)
    return min(rows_bound(tokens, k, held), -(-expected // TILE) * TILE)


#: a piece's HBM-to-HBM gathers are cut into this many chunks and run over
#: the live ones alone. ODD, so that no edge lies on the expected load —
#: half a piece: an even split would flip a balanced layer between one chunk
#: more and one less by the step — nine puts the nearest edges at 0.89 and
#: 1.11 of it. On the chip at Mellum 2's piece, ms a gather of 32,768 live
#: rows by 5 / 7 / 9 / 11 / 13 / 17 chunks: 1.65 / 1.57 / 1.53 / 1.52 /
#: 1.76 / 1.52, and of 36,408: 1.65 / 1.57 / 1.53 / 1.78 / 2.02 / 1.70
#: (PERF.md section 6, PR 46)
PIECE_CHUNKS = 9


def chunk_rows(piece: int) -> int:
    """Rows of a chunk of a piece of ``piece`` rows: a :data:`PIECE_CHUNKS`-th
    of it in whole row tiles (the piece itself where it is no more than a
    tile: one chunk)."""
    return min(piece, -(-piece // (PIECE_CHUNKS * TILE)) * TILE)


def rows_made(n_live, piece: int):
    """Rows the chunk loops of a piece with ``n_live`` live rows produce:
    its live chunks' (the last chunk ends with the piece)."""
    chunk = chunk_rows(piece)
    return jnp.minimum(-(-n_live // chunk) * chunk, piece)


def route(h: jax.Array, kernel: jax.Array, k: int, scaling: float,
          bias: jax.Array | None = None, softmax: bool = False):
    """``(logits [T, E] float32, chosen [T, k] int32, weights [T, k]
    float32)`` of tokens ``h [T, D]``: logits in float32 at ``highest``
    precision whatever ``h``'s dtype (on a TPU a float32 product is
    otherwise made of bf16 passes), sigmoid scores — or, with ``softmax``,
    the softmax over ALL experts — the ``k`` largest, each weight its score
    over the chosen scores' sum times ``scaling`` (of a softmax: the softmax
    over the chosen logits alone). With a
    selection ``bias [E]`` (DeepSeek-V3's ``noaux_tc``) the ``k`` largest of
    ``scores + bias`` are chosen and the weights are the chosen experts'
    scores WITHOUT it: the bias selects and does not weigh, and takes no
    gradient."""
    logits = jnp.dot(h.astype(jnp.float32), kernel.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, -1) if softmax else jax.nn.sigmoid(logits)
    if bias is None:
        top, chosen = jax.lax.top_k(scores, k)
    else:
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
        top = jnp.take_along_axis(scores, chosen, -1)
    return logits, chosen, scaling * top / jnp.sum(top, -1, keepdims=True)


def route_mlp(h: jax.Array, state: jax.Array, w, eps: float):
    """``(state [T, R], logits [T, C], chosen [T, 1] int32, weights [T, 1])``
    of tokens ``h [T, D]`` and the previous layer's router state ``[T, R]``,
    float32 throughout, products at ``highest``: ``r = h W_down + b + gamma *
    state`` (the state handed on, before its norm), ``logits = W_3
    gelu(W_2 gelu(W_1 RMSNorm(r) + b_1) + b_2)``, softmax, the largest
    choice, its probability the weight. ``w``: the router's leaves by name
    (:class:`MoeMlp`)."""
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    w = {name: leaf.astype(jnp.float32) for name, leaf in w.items()}
    with jax.named_scope("router_eda"):
        r = dot(h.astype(jnp.float32), w["down"]) + w["down_bias"]
        r = r + w["gamma"] * state.astype(jnp.float32)
    with jax.named_scope("router_mlp"):
        u = r * jax.lax.rsqrt(jnp.mean(r * r, -1, keepdims=True) + eps) \
            * w["norm"]
        u = jax.nn.gelu(dot(u, w["w1"]) + w["b1"], approximate=False)
        u = jax.nn.gelu(dot(u, w["w2"]) + w["b2"], approximate=False)
        logits = dot(u, w["w3"])
        p = jax.nn.softmax(logits, -1)
        chosen = jnp.argmax(p, -1).astype(jnp.int32)[:, None]
        return r, logits, chosen, jnp.take_along_axis(p, chosen, -1)


# ------------------------------------------------------ the pieces of the sort
def _piece(p, piece, order, by_token, ends, rowed):
    """Piece ``p`` of the sort: ``(choice [piece], live [piece], sizes [E],
    token_order)`` — the choices (numbered ``token * k + j``) of its rows,
    which of its rows hold a choice that landed, its rows an expert, and
    ``(rows, choices, live rows)`` of the same rows put in the order of
    their tokens (dead rows last)."""
    start = p * piece
    choice, at, number = (jax.lax.dynamic_slice(a, (start,), (piece,))
                          for a in (order, *by_token))
    live = start + jnp.arange(piece) < rowed
    sizes = jnp.diff(jnp.clip(jnp.minimum(ends, rowed) - start, 0, piece))
    return (choice, live, sizes.astype(jnp.int32),
            (at, number, jnp.clip(rowed - start, 0, piece)))


def _unwritten(shape, dtype, after, interpret):
    """An array nobody has written: the result of a kernel with an empty
    body, left where it is allocated. ``jnp.zeros`` (and ``jax.lax.empty``,
    which lowers to it) costs a pass over the array at the memory's rate;
    the interpreter fills it with NaN. ``after``: what the loop that writes
    into it reads, operands the kernel does not touch. They keep the call
    where it stands — with no operand it is an invariant of every loop
    around it: XLA hoists it out of the layers' scan and COPIES the array in
    each layer for the chunk loop to write into (a pass again, and every
    layer's arrays held at once) — and tell two loops' calls apart, which
    XLA would else merge (one array and a copy a loop). The call claims no
    side effects: a forward and the one remat makes again stay equal and
    are merged whole (PERF.md section 6, PR 46)."""
    return pl.pallas_call(
        lambda *refs: None, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(after),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        interpret=interpret, name="unwritten")(*after)


def _live_rows(source, at, n_live, interpret):
    """``source[at]`` (rows or scalars) for the live chunks of ``at
    [piece]`` alone: chunk ``c``'s rows (:func:`chunk_rows`) gathered for ``c
    = 0 .. ceil(n_live / chunk) - 1``, a loop whose length is traced, and
    written in place into a piece-sized array that starts unwritten. The
    rows behind the last live chunk are never made and hold anything: what
    reads the result stops at the live rows (the kernels by their schedules
    and live counts, ``scale`` by ``live``). The last chunk of a piece that
    is no whole number of them starts early and makes some of its
    neighbour's rows again."""
    piece = at.shape[0]
    chunk = chunk_rows(piece)

    def one(c, made):
        start = jnp.minimum(c * chunk, piece - chunk)
        rows = source[jax.lax.dynamic_slice_in_dim(at, start, chunk)]
        return jax.lax.dynamic_update_slice_in_dim(made, rows, start, 0)

    with jax.named_scope("live_rows"):
        return jax.lax.fori_loop(
            0, -(-n_live // chunk), one,
            _unwritten((piece,) + source.shape[1:], source.dtype,
                       (at, source), interpret))


# ------------------------------------------------------ the grouped products
#: ``dot_general`` dimension numbers: ``A B``, ``A B^T`` and ``A^T B``
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
#: the VMEM a grouped product may take (a v5e's is 128 MiB, the compiler's
#: own limit 16): a weight block of :data:`WEIGHT_BLOCK_BYTES` twice for each
#: operand pair — or, for a weight gradient, its float32 sum and the rounded
#: block twice — and the operands' row tiles twice
_GROUPED_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=96 << 20)
#: the most an expert's weight block holds, kept resident while the expert's
#: rows pass: the whole contraction by as many columns as fit (ZAYA1's 2048
#: x 2048 in bf16 whole; measured on the chip, PERF.md section 6, PR 36:
#: blocks of 512 and 1,024 of its columns cost 14 and 5% more)
WEIGHT_BLOCK_BYTES = 8 << 20
#: the most rows a grouped product's row tile holds
ROW_TILE = 512
#: a grid step of a grouped product does at least this many FLOPs (2.7 us at
#: a v5e's peak, eight times what a step costs to start) where the rows a
#: group expects allow it
STEP_FLOPS = 1 << 29


def choose_tiles(rows: int, groups: int, contract: int, cols: int,
                 itemsize: int) -> Tuple[int, int]:
    """``(row tile, column tile)`` of a grouped product ``[rows, contract]
    x [groups, contract, cols]`` (or its transpose's, or the weight
    gradient ``[rows, contract]^T [rows, cols]``) from its shapes alone: the
    contraction whole, as many columns as keep a weight block within
    :data:`WEIGHT_BLOCK_BYTES`, and the least row tile — a multiple of the
    MXU's 128, no more than :data:`ROW_TILE` — that gives a step
    :data:`STEP_FLOPS`, but no more than half the rows a group expects
    (``rows`` is a piece: :data:`PIECE_OVER_EXPECTED` times what lands): a
    group at an arbitrary offset touches ``expected / tile + 1`` tiles, so
    small tiles waste the fewest rows at its edges and large ones the
    fewest steps. Columns that do not all fit are split into as few equal
    blocks of whole lane tiles as do. ZAYA1's 2048 x 2048 over 964 rows a
    group gets (128, 2048), Laguna's 2048 x 512 and 512 x 2048 over 512 rows
    (256, whole) — the fastest of the nine measured for each form on the
    chip (PERF.md section 6, PR 36); Nemotron 3 Nano's 2688 x 1856 over 768
    rows (128, 1024) and back (128, 1408)."""
    most = max(WEIGHT_BLOCK_BYTES // (contract * itemsize), TILE)
    if cols <= most:
        tn = cols
    else:
        # as few column blocks as fit, of one size in whole lane tiles:
        # NemotronH's 2688 x 1856 (9.98 MB a block, 14.5 lane tiles) goes in
        # two of 1,024 (the second holds 832), not 1,536 and a ragged 320
        blocks = -(-cols // (most // TILE * TILE))
        tn = -(-cols // (blocks * TILE)) * TILE
    expected = rows // (PIECE_OVER_EXPECTED * groups)
    tm = TILE
    while 2 * tm <= min(ROW_TILE, expected // 2) \
            and 2 * tm * contract * tn < STEP_FLOPS:
        tm *= 2
    return min(tm, rows), tn


def _schedule(sizes, rows: int, tile: int, empty_too: bool):
    """The (group, row tile) steps of a grouped product over ``rows`` rows
    sorted by group, ``sizes`` rows each, in row tiles of ``tile`` on the
    rows' own grid: ``(group [S], tile [S], meta [G + 2])`` — a group's
    tiles one after the other (so its weight block stays where it is), a
    tile two groups share once for each; with ``empty_too`` a group without
    rows has one step (its weight gradient is written too). ``meta`` holds
    the groups' first rows, the last one's end, and the steps that count:
    a step past them repeats the last (nothing moves) and does nothing, so
    tiles behind the last group are not visited."""
    groups = sizes.shape[0]
    tiles = -(-rows // tile)
    steps = tiles + groups - 1  # no schedule is longer
    ends = jnp.minimum(jnp.cumsum(sizes), rows).astype(jnp.int32)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), ends[:-1]])
    first = starts // tile
    n = jnp.where(ends > starts, -(-ends // tile) - first, int(empty_too))
    done = jnp.cumsum(n)
    total = done[-1]
    s = jnp.minimum(jnp.arange(steps), jnp.maximum(total - 1, 0))
    # the group of step s: how many groups are done by then (a comparison,
    # not a search: the compiler merges equal schedules — a recomputed
    # forward's and the backward's — and with them the equal products, which
    # it does not do across a search's loop)
    group = jnp.minimum(jnp.sum(done[None, :] <= s[:, None], 1), groups - 1)
    at = jnp.minimum(first[group] + s - (done[group] - n[group]), tiles - 1)
    meta = jnp.concatenate([starts, ends[-1:], total[None]])
    return group.astype(jnp.int32), at.astype(jnp.int32), meta


def _own_rows(s, tile_ref, meta_ref, group, tm):
    """``(row numbers [tm, 1], first row, end, whole)`` of step ``s``'s tile
    and its group's rows, and whether the tile lies inside the group."""
    first = tile_ref[s] * tm
    lo, hi = meta_ref[group], meta_ref[group + 1]
    row = first + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return row, lo, hi, (lo <= first) & (first + tm <= hi)


def _rows_kernel(group_ref, tile_ref, meta_ref, *refs, dims, groups):
    """One step of :func:`grouped_rows`: the row tile ``tile_ref[s]`` of
    each operand times its group's weight block, summed in float32 and
    rounded once. A tile inside its group is stored as it is; at a group's
    edge the rows of earlier groups keep what the step before wrote (the
    tile is still resident), later rows get zeros."""
    *operands, out_ref = refs
    s = pl.program_id(1)

    @pl.when(s < meta_ref[groups + 1])
    def _():
        tm = out_ref.shape[0]
        row, lo, hi, whole = _own_rows(s, tile_ref, meta_ref, group_ref[s],
                                       tm)
        half = len(operands) // 2
        acc = sum(jax.lax.dot_general(x[...], w[...], dims,
                                      preferred_element_type=jnp.float32)
                  for x, w in zip(operands[:half], operands[half:]))

        @pl.when(whole)
        def _():
            out_ref[...] = acc.astype(out_ref.dtype)

        @pl.when(jnp.logical_not(whole))
        def _():
            out_ref[...] = jnp.where(
                row < lo, out_ref[...].astype(jnp.float32),
                jnp.where(row < hi, acc, 0.0)).astype(out_ref.dtype)


# The two products are jitted for the tracing's sake too: calls of one shape
# — gate and up; the forward's and the backward's, which makes them again —
# share one trace and one lowered kernel, so that a program which holds them
# beside each other on equal operands (the backward of a rematerialised
# layer) holds two EQUAL calls and the compiler makes the product once. Traced
# twice they are not equal: a kernel's payload carries its call site.
@functools.partial(jax.jit, static_argnums=(3, 4))
def grouped_rows(xs, ws, sizes, transposed: bool, interpret: bool):
    """``sum_i xs[i][rows of g] @ ws[i][g]`` (``transposed``: ``@
    ws[i][g]^T``) for every group ``g`` of rows — ``xs[i] [R, C]`` sorted by
    group, ``sizes [G]`` rows each, ``ws[i] [G, C, N]`` (``[G, N, C]``) — as
    ``[R, N]`` in the operands' dtype, accumulated in float32 and rounded
    once. Rows behind the last group hold nothing to be read: their tiles
    are not visited. Tiles by :func:`choose_tiles`; the visited tiles' rows
    are the second result."""
    rows, contract = xs[0].shape
    groups = ws[0].shape[0]
    cols = ws[0].shape[1 if transposed else 2]
    tm, tn = choose_tiles(rows, groups, contract, cols, xs[0].dtype.itemsize)
    group, at, meta = _schedule(sizes, rows, tm, False)
    x_spec = pl.BlockSpec((tm, contract), lambda n, s, g, t, _: (t[s], 0))
    w_spec = pl.BlockSpec(
        (None, tn, contract) if transposed else (None, contract, tn),
        (lambda n, s, g, t, _: (g[s], n, 0)) if transposed
        else (lambda n, s, g, t, _: (g[s], 0, n)))
    out = pl.pallas_call(
        functools.partial(_rows_kernel, dims=_NT if transposed else _NN,
                          groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(-(-cols // tn), group.size),
            in_specs=[x_spec] * len(xs) + [w_spec] * len(ws),
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, s, g, t, _: (t[s], n))),
        out_shape=jax.ShapeDtypeStruct((rows, cols), xs[0].dtype),
        compiler_params=_GROUPED_PARAMS,
        interpret=interpret,
        name="grouped_rows_t" if transposed else "grouped_rows",
    )(group, at, meta, *xs, *ws)
    return out, meta[groups + 1] * tm


def _weights_kernel(group_ref, tile_ref, meta_ref, x_ref, y_ref, out_ref,
                    acc_ref, *, groups):
    """One step of :func:`grouped_weights`: ``x^T y`` of a row tile added to
    its group's float32 block, the rows of other groups and behind the last
    taken as zeros whatever they hold; the block is zeroed at the group's
    first step and rounded into the result at its last."""
    s = pl.program_id(1)
    last = meta_ref[groups + 1] - 1
    g = group_ref[s]

    @pl.when((s <= last)
             & ((s == 0) | (g != group_ref[jnp.maximum(s - 1, 0)])))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s <= last)
    def _():
        tm = x_ref.shape[0]
        row, lo, hi, whole = _own_rows(s, tile_ref, meta_ref, g, tm)

        def add(x, y):
            acc_ref[...] += jax.lax.dot_general(
                x, y, _TN, preferred_element_type=jnp.float32)

        @pl.when(whole)
        def _():
            add(x_ref[...], y_ref[...])

        @pl.when(jnp.logical_not(whole))
        def _():
            mine = (row >= lo) & (row < hi)
            add(jnp.where(mine, x_ref[...], 0), jnp.where(mine, y_ref[...], 0))

    @pl.when((s == last)
             | (g != group_ref[jnp.minimum(s + 1, pl.num_programs(1) - 1)]))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3,))
def grouped_weights(x, y, sizes, interpret: bool):
    """``x[rows of g]^T @ y[rows of g]`` for every group: ``[G, C, N]`` of
    ``x [R, C]`` and ``y [R, N]`` sorted by group, ``sizes [G]`` rows each,
    in their dtype, accumulated in float32 over the group's rows and rounded
    once; a group without rows gets zeros, rows behind the last group are
    not read."""
    rows, contract = x.shape
    cols = y.shape[1]
    groups = sizes.shape[0]
    tm, tn = choose_tiles(rows, groups, contract, cols, x.dtype.itemsize)
    group, at, meta = _schedule(sizes, rows, tm, True)
    return pl.pallas_call(
        functools.partial(_weights_kernel, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(-(-cols // tn), group.size),
            in_specs=[
                pl.BlockSpec((tm, contract), lambda n, s, g, t, _: (t[s], 0)),
                pl.BlockSpec((tm, tn), lambda n, s, g, t, _: (t[s], n))],
            out_specs=pl.BlockSpec((None, contract, tn),
                                   lambda n, s, g, t, _: (g[s], 0, n)),
            scratch_shapes=[pltpu.VMEM((contract, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, contract, cols), x.dtype),
        compiler_params=_GROUPED_PARAMS,
        interpret=interpret,
        name="grouped_weights",
    )(group, at, meta, x, y)


def _sum_kernel(blk_ref, chunk_ref, ends_ref, rows_ref, tok_ref, w_ref,
                out_ref, *, block):
    """One step of :func:`_to_tokens`: the chunk of rows ``chunk_ref[s]``
    into the block of tokens ``blk_ref[s]``, as a product with the chunk's
    selection matrix (row ``r``'s weight at its token's place). ``ends_ref``
    holds the schedule's steps and the live rows: a step past the first does
    nothing, a row past the second counts as zeros whatever it holds."""
    s = pl.program_id(0)
    b = blk_ref[s]

    @pl.when((s == 0) | (b != blk_ref[jnp.maximum(s - 1, 0)]))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(s < ends_ref[0])
    def _():
        chunk = rows_ref.shape[0]
        row = chunk_ref[s] * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (chunk, 1), 0)
        here = tok_ref[...] - b * block == jax.lax.broadcasted_iota(
            jnp.int32, (block, chunk), 0)
        out_ref[...] += jnp.dot(
            jnp.where(here, w_ref[...], 0.0),
            jnp.where(row < ends_ref[1], rows_ref[...], 0).astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)


def _to_tokens(rows, scale, token_order, tokens, k, interpret):
    """``[tokens, D]`` float32: ``scale[j] * rows[by_token[j]]`` summed in
    float32 into the token ``number[j] // k``, over the first ``n_live``
    ``j`` — ``token_order = (by_token, number, n_live)``: the rows of
    ``rows [R, D]`` in the order of their tokens, and their choices.

    The rows are put in that order (one gather of the live chunks' rows:
    :func:`_live_rows`), so that a block
    of tokens owns a contiguous slab of rows, and a Pallas kernel sums each
    block's slab a chunk at a time: a schedule of (block, chunk) steps, a
    block's steps one after the other and its result resident between them
    (``interpret``: in the Pallas interpreter, off the TPU)."""
    by_token, number, n_live = token_order
    m, d = rows.shape
    block = min(TILE, -(-tokens // 8) * 8)
    n_blocks = -(-tokens // block)
    nowhere = n_blocks * block
    pad = -m % TILE
    rows = jnp.pad(_live_rows(rows, by_token, n_live, interpret),
                   ((0, pad), (0, 0)))
    tok = jnp.pad(jnp.where(jnp.arange(m) < n_live, number // k, nowhere),
                  (0, pad), constant_values=nowhere)
    scale = jnp.pad(scale, (0, pad))
    chunks = (m + pad) // TILE
    # block b owns the rows [starts[b], starts[b + 1]): the chunks that hold
    # them, at least one (an empty block is written too)
    starts = jnp.searchsorted(tok, jnp.arange(n_blocks + 1) * block)
    first = starts[:-1] // TILE
    n = jnp.maximum(-(-starts[1:] // TILE) - first, 1)
    done = jnp.cumsum(n)
    steps = chunks + n_blocks  # no schedule is longer
    s = jnp.arange(steps)
    blk = jnp.minimum(jnp.searchsorted(done, s, side="right"), n_blocks - 1)
    chunk = jnp.minimum(first[blk] + s - (done[blk] - n[blk]), chunks - 1)
    out = pl.pallas_call(
        functools.partial(_sum_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(steps,),
            in_specs=[
                pl.BlockSpec((TILE, d), lambda s, b, c, _: (c[s], 0)),
                pl.BlockSpec((1, TILE), lambda s, b, c, _: (0, c[s])),
                pl.BlockSpec((1, TILE), lambda s, b, c, _: (0, c[s]))],
            out_specs=pl.BlockSpec((block, d), lambda s, b, c, _: (b[s], 0))),
        out_shape=jax.ShapeDtypeStruct((nowhere, d), jnp.float32),
        interpret=interpret,
        name="rows_to_tokens",
    )(blk, chunk, jnp.stack([done[-1], n_live]), rows, tok[None],
      scale[None])
    return out[:tokens]


def _over_pieces(one, n):
    """``one(0) + ... + one(n - 1)``, at least ``one(0)``: ``n`` is traced
    (the landed count's). The first piece is the program's own; a loop adds
    the others where there are any."""
    def more(carry):
        p, acc = carry
        return p + 1, jax.tree.map(jnp.add, acc, one(p))

    return jax.lax.while_loop(lambda carry: carry[0] < n, more,
                              (jnp.int32(1), one(jnp.int32(0))))[1]


# A piece's two functions are jitted for the tracing's sake, not the
# compiler's (it inlines them): the first piece and the loop's, and every
# program of a process with these shapes, share one trace of each.
@functools.partial(jax.jit, static_argnums=(0,))
def _piece_forward(how, p, h, weights, experts, order, by_token, ends, rowed):
    """``(y [T, D] float32, live rows, rows of the row tiles visited, rows
    the chunk loops made)`` of piece ``p``: its tokens' rows, the experts'
    results (SwiGLU: three grouped products; relu2: two), their weighted sum
    back into the tokens (the rows by token: the live chunks' alone,
    :func:`_live_rows`)."""
    piece, interpret = how
    tokens, k = weights.shape
    choice, live, sizes, token_order = _piece(p, piece, order, by_token, ends,
                                              rowed)
    with jax.named_scope("dispatch"):
        x = h[choice // k]
    with jax.named_scope("experts"):
        *inward, w_down = experts
        pre = [grouped_rows([x], [w], sizes, False, interpret)
               for w in inward]
        visited = pre[0][1]
        out, _ = grouped_rows([_activation(*(a for a, _ in pre))], [w_down],
                              sizes, False, interpret)
    with jax.named_scope("combine"):
        y = _to_tokens(out, _live_rows(weights.reshape(-1), token_order[1],
                                       token_order[2], interpret),
                       token_order, tokens, k, interpret)
    return (y, jnp.sum(live, dtype=jnp.int32), visited,
            rows_made(token_order[2], piece))


def _activation(*pre):
    """What an expert's down product takes, from its inward products in the
    compute dtype: ``silu(gate) * up`` of a SwiGLU's pair, ``relu(up)^2`` of
    an ungated one."""
    if len(pre) == 2:
        gate, up = pre
        return nn.silu(gate) * up
    return jnp.square(nn.relu(pre[0]))


@functools.partial(jax.jit, static_argnums=(0,))
def _piece_backward(how, p, g, h, weights, experts, order, by_token, ends,
                    rowed):
    """Piece ``p``'s part of the gradients by ``h`` (float32), ``weights``
    (flat) and the expert weights (a tuple as ``experts``) under the
    cotangent ``g [T, D]``, the experts' rule written out: the inward
    products are made again, the down product is not — with ``u = g_rows
    W_down^T`` a row's result times its token's cotangent is ``<act, u>``
    (the choice's weight gradient), ``scale * u`` the activation's cotangent
    (the scale applied in float32, after the product), and ``(scale * act)^T
    g_rows`` the down weights' gradient. SwiGLU, eight grouped products: two
    again, ``u``, the two that give the rows' gradient summed in one call,
    three weight gradients. relu2, five: ``up`` again, ``u``, ``d_up_rows =
    scale * u * 2 relu(up)`` and ONE product back to the rows, two weight
    gradients."""
    piece, interpret = how
    tokens, k = weights.shape
    choice, live, sizes, token_order = _piece(p, piece, order, by_token, ends,
                                              rowed)
    tok = choice // k
    f32 = jnp.float32
    with jax.named_scope("dispatch"):
        x = h[tok]
    with jax.named_scope("combine"):
        scale = jnp.where(live, _live_rows(weights.reshape(-1), choice,
                                           token_order[2], interpret),
                          0.0)[:, None]
        g_rows = _live_rows(g, tok, token_order[2], interpret)
    with jax.named_scope("experts"):
        *inward, w_down = experts
        pre = [grouped_rows([x], [w], sizes, False, interpret)[0]
               for w in inward]
        u = grouped_rows([g_rows], [w_down], sizes, True,
                         interpret)[0].astype(f32)
        act = _activation(*pre).astype(f32)  # rounded as the forward's
        pre = [a.astype(f32) for a in pre]
        if len(pre) == 2:
            gate, up = pre
            sig = jax.nn.sigmoid(gate)
            d_act = scale * u
            d_pre = [(d_act * up * sig * (1 + gate * (1 - sig))).astype(
                         x.dtype),
                     (d_act * gate * sig).astype(x.dtype)]
        else:
            d_pre = [(scale * u * 2 * nn.relu(pre[0])).astype(x.dtype)]
        d_x, _ = grouped_rows(d_pre, inward, sizes, True, interpret)
        d_inward = [grouped_weights(x, d, sizes, interpret) for d in d_pre]
        d_down = grouped_weights((scale * act).astype(x.dtype), g_rows, sizes,
                                 interpret)
    with jax.named_scope("combine"):
        # a choice's weight gets its row's result times the token's g:
        # scalars, put back by the choice's number (each has one row; a
        # dead row's goes nowhere, whatever it holds)
        d_weights = jnp.zeros((tokens * k,), f32).at[
            jnp.where(live, choice, tokens * k)].set(
                jnp.sum(act * u, -1), mode="drop", unique_indices=True)
    with jax.named_scope("dispatch"):
        d_h = _to_tokens(d_x, jnp.ones_like(scale[:, 0]), token_order, tokens,
                         k, interpret)
    return d_h, d_weights, (*d_inward, d_down)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _routed(how, h, weights, experts, order, by_token, ends, rowed):
    """``(y [T, D], rows [] int32, tile rows [] int32, chunk rows [] int32)``:
    the experts' results for the first ``rowed`` rows of the sort, weighted
    and summed into their tokens in float32, a piece at a time (``how =
    (piece, interpret)``; ``experts`` the held experts' leaves, a tuple by
    :data:`EXPERT_FORMS`); the rows that were visited; the rows of the row
    tiles the grouped products' schedules visited for them; and the rows the
    pieces' chunk loops made for them (:func:`rows_made`). The
    differentiation rule is written out: reverse mode cannot differentiate a
    loop whose length is traced, nor a Pallas call."""
    args = (h, weights, experts, order, by_token, ends, rowed)
    y, *counts = _over_pieces(lambda p: _piece_forward(how, p, *args),
                              -(-rowed // how[0]))
    return (y.astype(h.dtype), *counts)


def _routed_fwd(how, *args):
    return _routed(how, *args), args


def _routed_bwd(how, args, cts):
    g = cts[0]  # [T, D]; the visited rows and tiles are integers
    d_h, d_weights, d_experts = _over_pieces(
        lambda p: _piece_backward(how, p, g, *args), -(-args[-1] // how[0]))
    h, weights = args[:2]
    return (d_h.astype(h.dtype), d_weights.reshape(weights.shape), d_experts,
            None, None, None, None)


_routed.defvjp(_routed_fwd, _routed_bwd)


def _routed_part(h, chosen, weights, experts, lo, total):
    """:func:`routed_experts` on the experts' leaves as a tuple
    (:data:`EXPERT_FORMS`), with a fifth and a sixth number: the rows of the
    row tiles the grouped products visited (the landed rows over them is the
    layer's ``moe_tile_fill``) and the rows the pieces' chunk loops made
    (``moe_row_fill``)."""
    tokens, k = chosen.shape
    held = experts[0].shape[0]
    bound = rows_bound(tokens, k, held)
    piece = piece_rows(tokens, k, held, total)
    with jax.named_scope("dispatch"):
        local = chosen - lo
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held).reshape(-1)
        # row r of the sort holds choice order[r]; the choices that landed
        # come first, by expert
        order = jnp.argsort(key, stable=True)[:bound]
        order = jnp.pad(order, (0, -bound % piece))
        sizes = jnp.sum(key[:, None] == jnp.arange(held), 0, dtype=jnp.int32)
        ends = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(sizes)])
        landed = ends[held]
        rowed = jnp.minimum(landed, bound)
        # each piece's rows by token, for the sums back to the tokens: a
        # landed choice's number sorts its row within its piece (the pieces
        # before the last are full, dead rows come last of all)
        row = jnp.arange(order.size)
        number, at = jax.lax.sort(
            (jnp.where(row < rowed, row // piece * (tokens * k) + order,
                       jnp.iinfo(jnp.int32).max), row % piece), num_keys=1)
        by_token = (at, number % (tokens * k))
    y, rows, visited, made = _routed(
        (piece, not platform.on_tpu()), h, weights, tuple(experts), order,
        by_token, ends, rowed)
    stats = jnp.stack([landed - rows, landed, jnp.int32(landed > piece),
                       jnp.max(sizes), visited, made]).astype(jnp.float32)
    return y, stats


def routed_experts(h, chosen, weights, w_gate, w_up, w_down, lo, total):
    """The part of the routed result the experts ``[lo, lo + E)`` of
    ``total`` give, ``E = w_up.shape[0]``: ``(y [T, D], stats [4])`` with
    ``stats`` = (choices in the range that got no row, choices in the
    range, whether they needed more than one piece, the largest expert's
    rows), float32. ``w_gate`` None: ungated ``relu2`` experts
    (:data:`EXPERT_FORMS`). ``lo`` may be traced (a shard's own under
    ``ep``)."""
    experts = (w_up, w_down) if w_gate is None else (w_gate, w_up, w_down)
    y, stats = _routed_part(h, chosen, weights, experts, lo, total)
    return y, stats[:4]


def _over_expert_shards(fn, tokens: int, held: int):
    """``fn(h, chosen, weights, experts, lo)`` per ``ep`` shard of the expert
    weights (a tuple of leaves) under the context mesh, the parts summed over
    ``ep`` (and the stats with them: sums summed, the shards' calls that
    needed more than one piece as their share, the largest group the largest
    anywhere, the visited tiles' rows and the chunk loops' summed); ``fn``
    itself where the mesh has no ``ep`` axis larger than
    one. Tokens are split over the batch axes where they
    divide, as attention's per-shard wrap has it."""
    mesh = jax.sharding.get_abstract_mesh()
    if EXPERT_AXIS not in mesh.axis_names \
            or EXPERT_AXIS in mesh.manual_axes \
            or mesh.shape[EXPERT_AXIS] == 1:
        return fn
    shards = mesh.shape[EXPERT_AXIS]
    if held % shards:
        raise ValueError(f"{held} experts held do not divide over "
                         f"{EXPERT_AXIS}={shards}")
    batch = tuple(a for a in BATCH_AXES if a in mesh.axis_names
                  and a not in mesh.manual_axes and mesh.shape[a] > 1)
    if batch and tokens % math.prod(mesh.shape[a] for a in batch):
        batch = ()
    batch_shards = math.prod(mesh.shape[a] for a in batch)
    every = (EXPERT_AXIS,) + batch

    def shard(h, chosen, weights, experts, lo):
        lo = lo + jax.lax.axis_index(EXPERT_AXIS) * (held // shards)
        y, stats = fn(h, chosen, weights, experts, lo)
        # the largest group is of ONE batch shard's tokens: times the batch
        # shards, so that it stands against the summed rows' mean
        return jax.lax.psum(y, EXPERT_AXIS), jnp.concatenate([
            jax.lax.psum(stats[:2], every),
            jax.lax.pmean(stats[2:3], every),
            jax.lax.pmax(stats[3:4], every) * batch_shards,
            jax.lax.psum(stats[4:], every)])

    rows, experts = P(batch or None), P(EXPERT_AXIS)
    return jax.shard_map(
        shard, in_specs=(rows, rows, rows, experts, P()),
        out_specs=(rows, P()), check_vma=False)


class MoeMlp(nn.Module):
    """Router, the routed experts held here, the shared expert: ``x [B, S,
    D]`` (the normed input) to ``(y [B, S, D], counters, state)``, the
    counters in :func:`counters`' order, float32: choices on the held experts
    that got no row (0 by construction), landed rows a token, the largest
    expert's rows over the mean, landed rows over the bound, the router's
    entropy, whether the landed rows needed more than one piece
    (:func:`piece_rows` of the router's width: the share of the shards'
    calls under ``ep``), the landed rows over the rows of the row tiles the
    grouped products visited for them (1 where nothing landed), the landed
    rows over the rows the pieces' chunk loops made for them
    (``moe_row_fill``: 0.9 at a balanced load, five chunks of nine; 1 where
    nothing landed) and, with ``skip_choice``, the share of tokens that took
    it. ``state`` is the router state handed on ``[B, S, router_hidden]``
    float32 (``mlp-softmax-top1``: it takes the previous
    layer's as its second argument), else None.

    Scopes (``jax.named_scope``): ``router`` (``router_eda`` and
    ``router_mlp`` inside it where the router is the MLP), ``dispatch`` (the
    sort, a piece's gather and the transpose's sum back to the tokens),
    ``experts`` (the kernels ``grouped_rows``, ``grouped_rows_t`` and
    ``grouped_weights`` and the activation between them), ``combine`` (the
    weighted sum back to the tokens and its transpose), ``shared_expert``;
    inside the first three ``live_rows`` around each chunk loop (a ``while``
    and the empty kernel ``unwritten`` whose result it writes into); the
    caller's ``moe`` scope is around them, a ``while`` inside where a piece
    is not the first. Where
    ``intermediates`` is a mutable collection (the benchmark's check, tests)
    the layer also sows what it routed on: ``router_in``, ``router_logits``,
    ``chosen`` (and ``router_state_in``, ``router_state``)."""

    experts_total: int
    experts_held: Tuple[int, int]
    d_ff: int
    shared_d_ff: int
    k: int
    scaling: float = 1.0
    #: init scale of the down projections (the dense path's residual scale)
    out_init_scale: float = 1.0
    dtype: str = "float32"
    #: one of :data:`ROUTERS`; the MLP's width and its norm's epsilon, and
    #: whether it has one more choice than experts, which adds nothing
    router: str = ROUTERS[0]
    router_hidden: int = 0
    router_eps: float = 1e-5
    skip_choice: bool = False
    #: the linear router's chosen experts are NAMED for a rematerialised
    #: block to keep (``ops/remat.py ROUTED``) and the weights are read from
    #: the scores at the kept choice: the backward weighs the experts the
    #: pass ran, whatever a forward made again would choose (the stack
    #: sets it under block diffusion; the others' programs stay theirs)
    keep_routing: bool = False
    #: the linear router chooses by ``scores + b``, ``b`` a leaf
    #: ``router_bias [experts_total]`` at zero that takes no gradient (its
    #: load-driven update is a training recipe's and is not here)
    selection_bias: bool = False
    #: one of :data:`EXPERT_FORMS`, the routed experts' and the shared
    #: expert's: ``relu2`` has no ``w_gate`` / ``shared_gate`` leaf
    expert_form: str = EXPERT_FORMS[0]
    #: the down maps (routed and shared) start with zero column sums. A
    #: positive activation's mean (``relu2``'s every hidden unit) is then no
    #: vector that all tokens add to the stream; through a plain normal map
    #: it is, the next routers' logits take a per-expert offset from it,
    #: top-k turns the offsets into loads that follow the seed, and the
    #: rows on a chip's share of the experts with them (PERF.md section 6,
    #: PR 42: a step 448-471 ms by the seed without, 450-451 with)
    down_zero_sums: bool = False

    def _route_mlp(self, h, state):
        """The MLP router's parameters and :func:`route_mlp` on them."""
        d, r = h.shape[-1], self.router_hidden
        choices = self.experts_total + int(self.skip_choice)
        normal = nn.initializers.normal(stddev=0.02)
        ones, zeros = nn.initializers.ones_init(), nn.initializers.zeros_init()
        first, later = (nn.initializers.orthogonal(scale)
                        for scale in MLP_ROUTER_INIT)
        w = {name: self.param(f"router_{name}", nn.with_logical_partitioning(
            init, axes), shape) for name, init, axes, shape in (
                ("down", normal, ("embed", None), (d, r)),
                ("down_bias", zeros, (None,), (r,)),
                ("gamma", ones, (None,), (r,)),
                ("norm", ones, (None,), (r,)),
                ("w1", first, (None, None), (r, r)),
                ("b1", zeros, (None,), (r,)),
                ("w2", later, (None, None), (r, r)),
                ("b2", zeros, (None,), (r,)),
                ("w3", later, (None, None), (r, choices)))}
        return route_mlp(h, state, w, self.router_eps)

    @nn.compact
    def __call__(self, x, state=None):
        batch, seq, d = x.shape
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.experts_total:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.experts_total}")
        if self.router not in ROUTERS:
            raise ValueError(f"router {self.router!r} is none of {ROUTERS}")
        if self.expert_form not in EXPERT_FORMS:
            raise ValueError(f"expert form {self.expert_form!r} is none of "
                             f"{EXPERT_FORMS}")
        mlp, softmax = self.router == ROUTERS[1], self.router == ROUTERS[2]
        if mlp and self.k != 1 or self.skip_choice and not mlp \
                or self.selection_bias and (mlp or softmax):
            raise ValueError(f"router {self.router!r} with k={self.k}, "
                             f"skip_choice={self.skip_choice}, "
                             f"selection_bias={self.selection_bias}")
        held, tokens = hi - lo, batch * seq
        choices = self.experts_total + int(self.skip_choice)
        dt = jnp.dtype(self.dtype)
        h = x.astype(dt).reshape(tokens, d)
        gated = self.expert_form == EXPERT_FORMS[0]

        def weight(name, shape, axes, scale=1.0, centred=False):
            init = nn.initializers.normal(stddev=0.02 * scale)
            if centred:
                init = _zero_column_sums(init)
            return jnp.asarray(self.param(
                name, nn.with_logical_partitioning(init, axes), shape), dt)

        with jax.named_scope("router"):
            if mlp:
                state_in = state.reshape(tokens, -1)
                state, logits, chosen, weights = self._route_mlp(h, state_in)
                self.sow("intermediates", "router_state_in", state_in)
                self.sow("intermediates", "router_state", state)
                state = state.reshape(batch, seq, -1)
                share = jax.nn.softmax(logits, -1)
            else:
                # the router's width is the published one, whatever is held
                kernel = self.param("router", nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=0.02), ("embed", None)),
                    (d, self.experts_total))
                bias = self.param("router_bias", nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), (None,)),
                    (self.experts_total,)) if self.selection_bias else None
                # (the bias and the form by keyword, and only where they
                # are not the default: the benchmark's tests stand a
                # four-argument `route` in)
                logits, chosen, weights = route(
                    h, kernel, self.k, self.scaling,
                    **({} if bias is None else {"bias": bias}),
                    **({"softmax": True} if softmax else {}))
                scores = jax.nn.softmax(logits, -1) if softmax \
                    else jax.nn.sigmoid(logits)
                if self.keep_routing:
                    chosen = remat.name(chosen, remat.ROUTED)
                    # the scores at the kept choice, read by a compare
                    # against the experts' numbers: a gather's transpose is
                    # a scatter, which cost the chip 4% of a step here
                    picked = chosen[..., None] == jnp.arange(
                        self.experts_total)
                    top = jnp.sum(jnp.where(picked, scores[:, None, :], 0.0),
                                  -1)
                    weights = self.scaling * top / jnp.sum(top, -1,
                                                           keepdims=True)
                share = scores if softmax \
                    else scores / jnp.sum(scores, -1, keepdims=True)
            self.sow("intermediates", "router_in", h)
            self.sow("intermediates", "router_logits", logits)
            self.sow("intermediates", "chosen", chosen)
            entropy = -jnp.mean(jnp.sum(
                share * jnp.log(jnp.maximum(share, 1e-30)), -1))

        inward, outward = ("expert", "embed", "mlp"), ("expert", "mlp", "embed")
        experts = tuple(
            weight(name, (held, d, self.d_ff), inward)
            for name in (("w_gate", "w_up") if gated else ("w_up",))
        ) + (weight("w_down", (held, self.d_ff, d), outward,
                    self.out_init_scale, self.down_zero_sums),)
        piece = piece_rows(tokens, self.k, held, choices)
        log_once(log, f"moe: {self.expert_form} experts ({len(experts)} "
                      f"matrices each), {held} of {self.experts_total} held, "
                      f"pieces of {piece} rows; (row, column) tiles of the "
                      f"grouped products at {d} x {self.d_ff}: "
                      f"{choose_tiles(piece, held, d, self.d_ff, dt.itemsize)}"
                      f" inward, "
                      f"{choose_tiles(piece, held, self.d_ff, d, dt.itemsize)}"
                      f" back; router {self.router}, top-{self.k}")
        y, stats = _over_expert_shards(
            functools.partial(_routed_part, total=choices),
            tokens, held)(h, chosen, weights, experts, jnp.int32(lo))
        if self.shared_d_ff:
            with jax.named_scope("shared_expert"):
                *gate, up = (weight(name, (d, self.shared_d_ff),
                                    ("embed", "mlp"))
                             for name in (("shared_gate", "shared_up")
                                          if gated else ("shared_up",)))
                down = weight("shared_down", (self.shared_d_ff, d),
                              ("mlp", "embed"), self.out_init_scale,
                              self.down_zero_sums)
                # one candidate for what remat ``full`` keeps, as a dense
                # FFN's first products (``ops/remat.py``)
                kept = remat.product_namer((jax.ShapeDtypeStruct(
                    h.shape[:-1] + (self.shared_d_ff,), h.dtype),)
                    * (1 + gated), d, "shared")
                if gated:
                    hidden = nn.silu(kept(h @ gate[0])) * kept(h @ up)
                else:
                    hidden = _activation(kept(h @ up))
                y = y + hidden @ down
        dropped, n_mine, overflow, largest, visited, made = stats
        counted = [
            dropped,
            n_mine / tokens,
            largest * held / jnp.maximum(n_mine, 1.0),
            n_mine / rows_bound(tokens, self.k, held),
            entropy,
            overflow,
            jnp.where(visited > 0, n_mine / jnp.maximum(visited, 1.0), 1.0),
            jnp.where(made > 0, n_mine / jnp.maximum(made, 1.0), 1.0)]
        if self.skip_choice:
            counted.append(jnp.mean(chosen == self.experts_total,
                                    dtype=jnp.float32))
        if softmax:
            # what the renormalisation restores: k / experts at uniform
            # logits. The chosen experts' shares by a mask, not a gather: a
            # gather of k scalars a token costs the chip 1.3 ms a layer at
            # 16,384 tokens (PERF.md section 6, PR 46), the mask's pass 0.1
            picked = jnp.any(chosen[:, :, None] == jnp.arange(choices), 1)
            counted.append(jnp.mean(jnp.sum(
                jnp.where(picked, share, 0.0), -1)))
        counters = jnp.stack(counted)
        return y.reshape(batch, seq, d), counters, state
