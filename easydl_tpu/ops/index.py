"""A learned index over keys, in front of an attention: which keys a query may
attend to is DATA of the step (DeepSeek Sparse Attention's lightning indexer,
DeepSeek-V3.2-Exp's report; here in front of grouped-query attention).

For query ``t`` and key ``s <= t``, with ``a [L, heads, dim]`` the index's
queries, ``b [L, dim]`` its ONE key a token and ``w [L, heads]`` its head
weights (all three made by the caller from a DETACHED layer input, rotated and
scaled there): ``I[t, s] = sum_j w[t, j] * relu(a[t, j] . b[s])``, and ``S_t``
is the ``min(t + 1, topk)`` keys of largest ``I[t, s]``, ties to the lower
``s``. The products take their operands as they come (bf16 in a bf16 program)
and accumulate in float32; relu, the weighted sum, the ranking, both
softmaxes' statistics and the loss are float32.

**The selection is a bit a pair** (:func:`select`): ``words [batch, L / 32,
L]`` int32, the mask transposed — keys along the rows, as every kernel here
holds a score tile — and packed along the keys so that a kernel unpacks a
tile of 128 keys with shifts alone: of each GROUP of 256 keys, word row ``i``
(of 8) carries key ``8 * bit + i`` at ``bit`` (of 32). 2 MiB a thousand rows
squared: 33.5 MB a layer at 16,384, where the scores would be 1 GiB. It has no
gradient. ``ops/flash_attention.py`` takes it as the operand ``select``
(``dsa_fwd``, ``dsa_bwd``), :func:`unpack` writes it out for the XLA path and
the tests, and a rematerialised block keeps it by name (``ops/remat.py
SELECTED``): a second forward that rounded another way would rank a near-tie
the other way, and the backward would weigh pairs the forward had not scored.

**The index is trained by its own loss** (:func:`kl`): ``mean_t KL(p_t ||
softmax_{s in S_t} I[t, s])``, ``p[t, s]`` the attention's probabilities over
``S_t``, the mean of its heads, DETACHED — so the loss moves the index's
leaves and nothing else, and the language model's loss (which sees the index
through the selection alone) moves none of them.

Two paths each, chosen by the caller (``ops/attention.py``): the XLA one
writes ``[L, L]`` arrays out — what the tests and the CPU take — and the
Pallas one never does:

- ``index_select``: a grid cell is 256 queries. Their scores against the
  causal keys, chunk by chunk, go into a VMEM scratch ``[L, 256]`` as
  ORDER-PRESERVING int32 keys (a float's bits, the magnitude flipped where the
  sign is set); the ``topk``-th largest of each query is then found bit by
  bit from the top (32 counts over the scratch), the ties at it by position
  (``log2 L`` counts more: the lower ``s`` first), and the words, the
  selected scores' ``lse`` and the causal scores' sum of squares leave the
  cell. No sort, no ``[L, L]`` array.
- ``index_kl``: loss AND gradients in one pass over the causal tiles — the
  index's scores and the attention's (from its q, k and ``lse`` rows) made
  again a tile of 128 keys x 256 queries, ``p`` and ``softmax(I)`` formed
  under the unpacked bits, ``dI = (softmax(I) - p) / n`` pushed back through
  relu into ``da``, ``db`` (summed over the query blocks in a block that
  stays resident) and ``dw``. A TILE keeps each index head's relu'd scores
  in VMEM from the loop that sums ``I`` to the loop that pushes ``dI`` back,
  so every score product is made once; the head weights stay out of that
  second loop: it multiplies ``v_j = dI * [s_j > 0]`` (no ``w``) into the
  query gradient's accumulator ``sum_s b[s] v_j[s, t]`` and, for ``db``,
  with ``w[t, j] * a[t, j]``, which a QUERY BLOCK forms once at its first
  program. At the block's last program ``da[t, j]`` is ``w[t, j]`` times the
  accumulator and ``dw[t, j]`` the accumulator's product with ``a[t, j]``
  along ``dim`` — ``relu(x) = x * [x > 0]``, nothing is divided by ``w``,
  a weight of exactly zero keeps its gradient. The differentiation rule's
  forward IS that pass; its residuals are the three gradients, named for
  remat (``ops/remat.py INDEX_GRADS``) so that the kernel runs once a layer
  and step.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from easydl_tpu.ops import remat

#: keys a group of the packed selection: 8 word rows of 32 bits
GROUP = 256
_ROWS = 8
#: keys of a tile the kernels unpack at a time (``ops/flash_attention.py``'s
#: tile of scores), half a group: 16 bits of its 8 word rows
TILE = 128
#: queries a grid cell of the two kernels takes
QUERIES = 256

_INT_MIN = np.int32(-2 ** 31)
_NEG_INF = float(jnp.finfo(jnp.float32).min)
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))


def _dot(x, y, dims):
    return jax.lax.dot_general(x, y, dims, preferred_element_type=jnp.float32)


def selected_pairs(seq: int, topk: int) -> int:
    """``sum_t min(t + 1, topk)``: the pairs a sequence's selection holds."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def tiles(seq: int) -> int:
    """The causal tiles of ``TILE x TILE`` of a sequence."""
    n = seq // TILE
    return n * (n + 1) // 2


# --------------------------------------------------------------------------
# the packed selection
# --------------------------------------------------------------------------


def pack(dense: jax.Array) -> jax.Array:
    """``dense [batch, queries, keys]`` bool -> ``words [batch, keys / 32,
    queries]`` int32 (the module's docstring has the layout)."""
    batch, s_q, s_k = dense.shape
    if s_k % GROUP:
        raise ValueError(f"index: {s_k} keys are not whole groups of {GROUP}")
    bits = dense.transpose(0, 2, 1).reshape(
        batch, s_k // GROUP, 32, _ROWS, s_q).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, None, :, None, None]
    words = jnp.sum(bits << shifts, axis=2, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32).reshape(
        batch, s_k // 32, s_q)


def unpack(words: jax.Array) -> jax.Array:
    """:func:`pack`'s inverse: ``[batch, queries, keys]`` bool, written
    out."""
    batch, rows, s_q = words.shape
    w = jax.lax.bitcast_convert_type(words, jnp.uint32).reshape(
        batch, rows // _ROWS, 1, _ROWS, s_q)
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, None, :, None, None]
    bits = (w >> shifts) & jnp.uint32(1)
    return bits.reshape(batch, rows * 32, s_q).transpose(0, 2, 1) != 0


def live_tiles(words: jax.Array) -> jax.Array:
    """How many ``TILE x TILE`` tiles (keys x queries) hold a selected pair,
    over the batch: float32. What a kernel that skipped dead tiles would
    still visit."""
    batch, rows, s_q = words.shape
    w = jax.lax.bitcast_convert_type(words, jnp.uint32).reshape(
        batch, rows // _ROWS, _ROWS, s_q // TILE, TILE)
    halves = jnp.stack([w & jnp.uint32(0xFFFF), w >> jnp.uint32(16)])
    return jnp.sum(jnp.any(halves != 0, axis=(3, 5)), dtype=jnp.float32)


def unpack_tile(words, half: int):
    """``words [8, n]`` int32, a group's rows -> ``[TILE, n]`` int32 0 / 1,
    the group's first (``half`` 0) or second 128 keys: 16 shifts, the pieces
    one under the other (whole sublane tiles: no shuffle)."""
    return jnp.concatenate(
        [(words >> (16 * half + bit)) & 1 for bit in range(16)], axis=0)


# --------------------------------------------------------------------------
# the XLA path: [L, L] arrays written out
# --------------------------------------------------------------------------


def scores_reference(a, b, w):
    """``I [batch, queries, keys]`` float32, every pair (the caller masks)."""
    s = jnp.einsum("bthd,bsd->bhts", a, b,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bhts,bth->bts", jnp.maximum(s, 0.0),
                      w.astype(jnp.float32)) + 0.0


def select_dense(scores: jax.Array, topk: int) -> jax.Array:
    """``[batch, L, L]`` bool from scores ``[batch, L, L]``: for query ``t``
    the ``min(t + 1, topk)`` causal keys of largest score, ties to the lower
    key (a stable sort of the negated scores)."""
    seq = scores.shape[-1]
    causal = jnp.tril(jnp.ones((seq, seq), jnp.bool_))
    order = jnp.argsort(jnp.where(causal, -(scores + 0.0), jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return causal & (rank < topk)


def _select_reference(a, b, w, topk: int):
    scores = scores_reference(a, b, w)
    seq = scores.shape[-1]
    causal = jnp.tril(jnp.ones((seq, seq), jnp.bool_))
    chosen = select_dense(scores, topk)
    lse = jax.nn.logsumexp(jnp.where(chosen, scores, -jnp.inf), axis=-1)
    squares = jnp.sum(jnp.where(causal, scores * scores, 0.0), axis=-1)
    return pack(chosen), lse, squares


def _kl_reference(a, b, w, q, k, words, scale: float):
    """The loss written out; differentiated by jax (``q`` and ``k`` arrive
    detached)."""
    chosen = unpack(words)
    scores = scores_reference(a, b, w)
    log_i = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
    heads, groups = q.shape[2], k.shape[2]
    k = jnp.repeat(k, heads // groups, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(chosen[:, None], s, -jnp.inf), axis=-1)
    p = jax.lax.stop_gradient(jnp.mean(p, axis=1))
    live = chosen & (p > 0)
    terms = jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0))
                                 - jnp.where(live, log_i, 0.0)), 0.0)
    return jnp.sum(terms) / (terms.shape[0] * terms.shape[1])


# --------------------------------------------------------------------------
# index_select: scores, the top-k's threshold, the words
# --------------------------------------------------------------------------


def _ordered(x):
    """float32 -> int32 that orders as the floats do (and back: the map is
    its own inverse on the bits)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _floats(keys):
    return jax.lax.bitcast_convert_type(
        keys ^ ((keys >> 31) & jnp.int32(0x7FFFFFFF)), jnp.float32)


def _select_kernel(a_ref, b_ref, w_ref, words_ref, lse_ref, sq_ref, key_ref,
                   *, topk: int, heads: int, dim: int, chunk: int):
    # a_ref [queries, heads * dim]; b_ref [L, dim]; w_ref [heads, queries]
    # float32; words_ref [L / 32, queries] int32; lse_ref, sq_ref [1,
    # queries]; key_ref (scratch) [L, queries] int32: the scores as ordered
    # keys, keys along the rows
    n_q = a_ref.shape[0]
    seq = b_ref.shape[0]
    q_start = pl.program_id(1) * n_q
    n_live = (q_start + n_q + chunk - 1) // chunk  # chunks with a causal key
    queries = q_start + jax.lax.broadcasted_iota(jnp.int32, (TILE, n_q), 1)

    def score(c, carry):
        top, squares = carry
        for at in range(0, chunk, TILE):
            start = pl.multiple_of(c * chunk + at, TILE)
            bk = b_ref[pl.ds(start, TILE), :]
            acc = jnp.zeros((TILE, n_q), jnp.float32)
            for j in range(heads):
                s = _dot(bk, a_ref[:, j * dim:(j + 1) * dim], _NT)
                acc = acc + w_ref[j:j + 1, :] * jnp.maximum(s, 0.0)
            acc = acc + 0.0  # one zero: -0.0 would rank under 0.0
            keys = start + jax.lax.broadcasted_iota(jnp.int32, (TILE, n_q), 0)
            seen = keys <= queries
            key_ref[pl.ds(start, TILE), :] = jnp.where(
                seen, _ordered(acc), _INT_MIN)
            top = jnp.maximum(top, jnp.max(
                jnp.where(seen, acc, _NEG_INF), axis=0, keepdims=True))
            squares = squares + jnp.sum(
                jnp.where(seen, acc * acc, 0.0), axis=0, keepdims=True)
        return top, squares

    top, squares = jax.lax.fori_loop(
        0, n_live, score, (jnp.full((1, n_q), _NEG_INF, jnp.float32),
                           jnp.zeros((1, n_q), jnp.float32)))

    def count(test):
        """How many of a query's live keys pass ``test(keys, first
        position)``, ``[1, queries]`` int32."""
        def body(c, n):
            start = pl.multiple_of(c * chunk, chunk)
            return n + jnp.sum(
                test(key_ref[pl.ds(start, chunk), :], start).astype(jnp.int32),
                axis=0, keepdims=True)
        return jax.lax.fori_loop(0, n_live, body,
                                 jnp.zeros((1, n_q), jnp.int32))

    # the topk-th largest key of each query, bit by bit from the top: the
    # sign first, then each lower bit stays where as many still pass
    enough = count(lambda keys, _: keys >= 0) >= topk
    tau = jnp.where(enough, jnp.int32(0), _INT_MIN)

    def lower_bit(i, tau):
        cand = tau | (jnp.int32(1) << (30 - i))
        return jnp.where(count(lambda keys, _: keys >= cand) >= topk, cand,
                         tau)

    tau = jax.lax.fori_loop(0, 31, lower_bit, tau)
    # of the ties at it, the first `rest` by position: the largest place
    # before which fewer than `rest` ties lie is the last tie taken
    rest = topk - count(lambda keys, _: keys > tau)

    def ties_before(place):
        def test(keys, start):
            at = start + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 0)
            return (keys == tau) & (at < place)
        return count(test)

    def place_bit(i, place):
        cand = place | (jnp.int32(1) << ((seq - 1).bit_length() - 1 - i))
        return jnp.where(ties_before(cand) < rest, cand, place)

    place = jax.lax.fori_loop(0, (seq - 1).bit_length(), place_bit,
                              jnp.zeros((1, n_q), jnp.int32))

    words_ref[...] = jnp.zeros_like(words_ref)
    rows8 = q_start + jax.lax.broadcasted_iota(jnp.int32, (_ROWS, n_q), 1)

    def pack_group(g, total):
        word = jnp.zeros((_ROWS, n_q), jnp.int32)
        for bit in range(32):
            start = pl.multiple_of(g * GROUP + _ROWS * bit, _ROWS)
            keys = key_ref[pl.ds(start, _ROWS), :]
            at = start + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 0)
            chosen = (at <= rows8) & (
                (rows8 < topk) | (keys > tau)
                | ((keys == tau) & (at <= place)))
            word = word | (chosen.astype(jnp.int32) << bit)
            total = total + jnp.sum(
                jnp.where(chosen, jnp.exp(_floats(keys) - top), 0.0),
                axis=0, keepdims=True)
        words_ref[pl.ds(pl.multiple_of(g * _ROWS, _ROWS), _ROWS), :] = word
        return total

    total = jax.lax.fori_loop(0, n_live * (chunk // GROUP), pack_group,
                              jnp.zeros((1, n_q), jnp.float32))
    lse_ref[...] = top + jnp.log(total)
    sq_ref[...] = squares


def _select_call(a, b, w, *, topk: int, chunk: int, interpret: bool):
    batch, seq, heads, dim = a.shape
    n_q = min(QUERIES, seq)
    held = (seq * n_q * 4 + 2 * seq * max(dim, 128) * b.dtype.itemsize
            + 2 * n_q * heads * dim * a.dtype.itemsize
            + 2 * (seq // 32) * n_q * 4)
    words, lse, squares = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, heads=heads, dim=dim,
                          chunk=chunk),
        grid=(batch, seq // n_q),
        in_specs=[
            pl.BlockSpec((None, n_q, heads * dim), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, seq, dim), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, heads, n_q), lambda i, j: (i, 0, j))],
        out_specs=[
            pl.BlockSpec((None, seq // 32, n_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 1, n_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 1, n_q), lambda i, j: (i, 0, j))],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq // 32, seq), jnp.int32),
            jax.ShapeDtypeStruct((batch, 1, seq), jnp.float32),
            jax.ShapeDtypeStruct((batch, 1, seq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((seq, n_q), jnp.int32)],
        interpret=interpret, name="index_select",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=held + (24 << 20)),
    )(a.reshape(batch, seq, heads * dim), b,
      jnp.swapaxes(w.astype(jnp.float32), 1, 2))
    return words, lse[:, 0], squares[:, 0]


_select_jit = jax.jit(_select_call,
                      static_argnames=("topk", "chunk", "interpret"))


def _check(seq: int, topk: int, chunk: int) -> int:
    chunk = min(chunk, seq)
    if seq % GROUP or chunk % GROUP or seq % chunk or topk < 1:
        raise ValueError(
            f"index: {seq} rows in key chunks of {chunk}, top-{topk}: the "
            f"packed selection takes whole groups of {GROUP} keys and at "
            f"least one key a query (ops/index.py refuses it)")
    return chunk


def select(a: jax.Array, b: jax.Array, w: jax.Array, *, topk: int,
           kernels: bool, chunk: int = 512, interpret: bool = False
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(words, lse, squares)`` of the index ``a [batch, L, heads, dim]``,
    ``b [batch, L, dim]``, ``w [batch, L, heads]``: the packed selection
    (no gradient), the selected scores' ``logsumexp`` a query ``[batch, L]``
    and the causal scores' sum of squares a query (both for readers that
    take no gradient either). ``kernels``: the Pallas kernel, else the XLA
    path, which writes ``[L, L]`` out."""
    chunk = _check(a.shape[1], topk, chunk)
    a, b, w = (jax.lax.stop_gradient(x) for x in (a, b, w))
    if kernels:
        return _select_jit(a, b, w, topk=topk, chunk=chunk,
                           interpret=interpret)
    return _select_reference(a, b, w, topk)


# --------------------------------------------------------------------------
# index_kl: the loss and its three gradients in one pass
# --------------------------------------------------------------------------


def _kl_kernel(a_ref, b_ref, w_ref, q_ref, k_ref, lse_ref, words_ref,
               lsei_ref, loss_ref, da_ref, db_ref, dw_ref, dat_sum, aw, kept,
               stats, *, heads: int, dim: int, q_heads: int, kv_heads: int,
               head_dim: int, scale: float, inv_n: float, chunk: int):
    # a_ref, da_ref [queries, heads * dim]; b_ref [chunk, dim]; w_ref, dw_ref
    # [heads, queries] float32; q_ref [queries, q_heads * head_dim]; k_ref
    # [chunk, kv_heads * head_dim]; lse_ref [q_heads, queries]; words_ref
    # [chunk / 32, queries]; lsei_ref, loss_ref [1, queries]; db_ref [L, dim]
    # float32, resident. Scratch: kept [heads, TILE, queries] float32, a
    # TILE's relu(scores) head by head — made ONCE, by the loop that sums the
    # index, and read again by the loop that pushes dI back through relu;
    # aw [heads * dim, queries] in a's dtype, a QUERY BLOCK's w[t, j] *
    # a[t, j] turned, formed at its first program; dat_sum [heads * dim,
    # queries] float32, sum_s b[s] * dI[s, t] * [s_j > 0] WITHOUT w — at the
    # block's last program da is w times it and dw its product with a along
    # dim (relu(x) = x * [x > 0]); stats [3, queries] float32
    n_q = a_ref.shape[0]
    qi, ki = pl.program_id(1), pl.program_id(2)
    last = (qi * n_q + n_q - 1) // chunk
    heads_rows = [slice(j * dim, (j + 1) * dim) for j in range(heads)]

    @pl.when(ki == 0)
    def _():
        dat_sum[...] = jnp.zeros_like(dat_sum)
        stats[...] = jnp.zeros_like(stats)
        a_t = a_ref[...].astype(jnp.float32).T
        for j, mine in enumerate(heads_rows):
            aw[mine, :] = (a_t[mine, :] * w_ref[j:j + 1, :]).astype(aw.dtype)

    @pl.when((qi == 0) & (ki == 0))
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)

    @pl.when(ki <= last)
    def _():
        ratio = q_heads // kv_heads
        for at in range(0, chunk, TILE):
            bk = b_ref[at:at + TILE, :]
            group = at // GROUP
            chosen = unpack_tile(
                words_ref[group * _ROWS:(group + 1) * _ROWS, :],
                at // TILE % 2) != 0
            index = jnp.zeros((TILE, n_q), jnp.float32)
            for j, mine in enumerate(heads_rows):
                r = jnp.maximum(_dot(bk, a_ref[:, mine], _NT), 0.0)
                kept[j] = r
                index = index + w_ref[j:j + 1, :] * r
            p = jnp.zeros((TILE, n_q), jnp.float32)
            for h in range(q_heads):
                g = h // ratio
                s = _dot(k_ref[at:at + TILE, g * head_dim:(g + 1) * head_dim],
                         q_ref[:, h * head_dim:(h + 1) * head_dim], _NT)
                p = p + jnp.exp(s * scale - lse_ref[h:h + 1, :])
            p = jnp.where(chosen, p * (1.0 / q_heads), 0.0)
            soft = jnp.where(chosen, jnp.exp(index - lsei_ref[...]), 0.0)
            live = p > 0.0
            stats[0:1, :] += jnp.sum(
                jnp.where(live, p * jnp.log(jnp.where(live, p, 1.0)), 0.0),
                axis=0, keepdims=True)
            stats[1:2, :] += jnp.sum(jnp.where(chosen, p * index, 0.0),
                                     axis=0, keepdims=True)
            stats[2:3, :] += jnp.sum(p, axis=0, keepdims=True)
            d_index = (soft - p) * inv_n
            bkt = bk.T
            db = jnp.zeros((TILE, dim), jnp.float32)
            for j, mine in enumerate(heads_rows):
                v = jnp.where(kept[j] > 0.0, d_index, 0.0).astype(aw.dtype)
                dat_sum[mine, :] += _dot(bkt, v, _NN)
                db = db + _dot(v, aw[mine, :], _NT)
            rows = pl.ds(pl.multiple_of(ki * chunk + at, TILE), TILE)
            db_ref[rows, :] += db

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        a_t = a_ref[...].astype(jnp.float32).T
        for j, mine in enumerate(heads_rows):
            unweighted = dat_sum[mine, :]
            dw_ref[j:j + 1, :] = jnp.sum(a_t[mine, :] * unweighted, axis=0,
                                         keepdims=True)
            dat_sum[mine, :] = unweighted * w_ref[j:j + 1, :]
        da_ref[...] = dat_sum[...].T.astype(da_ref.dtype)
        loss_ref[...] = (stats[0:1, :] - stats[1:2, :]
                         + lsei_ref[...] * stats[2:3, :])


def _kl_call(a, b, w, q, k, lse, words, lse_i, *, scale: float, chunk: int,
             interpret: bool):
    batch, seq, heads, dim = a.shape
    q_heads, head_dim, kv_heads = q.shape[2], q.shape[3], k.shape[2]
    n_q = min(QUERIES, seq)

    def live(j, c):  # a dead chunk names the last live one: not fetched
        return jnp.minimum(c, (j * n_q + n_q - 1) // chunk)

    def mine(width):
        return pl.BlockSpec((None, n_q, width), lambda i, j, c: (i, j, 0))

    def rows(height):
        return pl.BlockSpec((None, height, n_q), lambda i, j, c: (i, 0, j))

    def keys(width):
        return pl.BlockSpec((None, chunk, width),
                            lambda i, j, c: (i, live(j, c), 0))

    held = (2 * n_q * (heads * dim * 2 * a.dtype.itemsize
                       + q_heads * head_dim * q.dtype.itemsize)
            + 2 * chunk * (kv_heads * head_dim + 128) * 4
            + 2 * seq * max(dim, 128) * 4
            + heads * n_q * (dim * (4 + a.dtype.itemsize) + TILE * 4))
    loss, da, db, dw = pl.pallas_call(
        functools.partial(
            _kl_kernel, heads=heads, dim=dim, q_heads=q_heads,
            kv_heads=kv_heads, head_dim=head_dim, scale=scale,
            inv_n=1.0 / (batch * seq), chunk=chunk),
        grid=(batch, seq // n_q, seq // chunk),
        in_specs=[
            mine(heads * dim), keys(dim), rows(heads),
            mine(q_heads * head_dim), keys(kv_heads * head_dim),
            rows(q_heads),
            pl.BlockSpec((None, chunk // 32, n_q),
                         lambda i, j, c: (i, live(j, c), j)),
            rows(1)],
        out_specs=[
            rows(1), mine(heads * dim),
            pl.BlockSpec((None, seq, dim), lambda i, j, c: (i, 0, 0)),
            rows(heads)],
        out_shape=[
            jax.ShapeDtypeStruct((batch, 1, seq), jnp.float32),
            jax.ShapeDtypeStruct((batch, seq, heads * dim), a.dtype),
            jax.ShapeDtypeStruct((batch, seq, dim), jnp.float32),
            jax.ShapeDtypeStruct((batch, heads, seq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads * dim, n_q), jnp.float32),
                        pltpu.VMEM((heads * dim, n_q), a.dtype),
                        pltpu.VMEM((heads, TILE, n_q), jnp.float32),
                        pltpu.VMEM((3, n_q), jnp.float32)],
        interpret=interpret, name="index_kl",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=held + (24 << 20)),
    )(a.reshape(batch, seq, heads * dim), b,
      jnp.swapaxes(w.astype(jnp.float32), 1, 2),
      q.reshape(batch, seq, -1), k.reshape(batch, seq, -1), lse, words,
      lse_i[:, None, :])
    return (jnp.sum(loss) * (1.0 / (batch * seq)),
            (da.reshape(a.shape), db.astype(b.dtype),
             jnp.swapaxes(dw, 1, 2).astype(w.dtype)))


_kl_jit = jax.jit(_kl_call, static_argnames=("scale", "chunk", "interpret"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _kl(a, b, w, q, k, lse, words, lse_i, scale, chunk, interpret):
    return _kl_jit(a, b, w, q, k, lse, words, lse_i, scale=scale,
                   chunk=chunk, interpret=interpret)[0]


def _kl_fwd(a, b, w, q, k, lse, words, lse_i, scale, chunk, interpret):
    loss, grads = _kl_jit(a, b, w, q, k, lse, words, lse_i, scale=scale,
                          chunk=chunk, interpret=interpret)
    # named HERE: the residuals the backward receives are the named values,
    # so a rematerialised block that saves the name runs the kernel once
    return loss, tuple(remat.name(x, remat.INDEX_GRADS) for x in grads)


def _kl_bwd(scale, chunk, interpret, grads, ct):
    da, db, dw = (ct.astype(x.dtype) * x for x in grads)
    return da, db, dw, None, None, None, None, None


_kl.defvjp(_kl_fwd, _kl_bwd)


def kl(a: jax.Array, b: jax.Array, w: jax.Array, q: jax.Array, k: jax.Array,
       lse: jax.Array, words: jax.Array, lse_i: jax.Array, *, scale: float,
       kernels: bool, chunk: int = 512, interpret: bool = False) -> jax.Array:
    """``mean over batch and t of KL(p_t || softmax_{S_t} I_t)``, float32:
    the index's own loss. ``a``, ``b``, ``w`` as :func:`select` took them
    (the gradient goes to them alone); ``q [batch, L, heads, head_dim]`` and
    ``k [batch, L, kv_heads, head_dim]`` the attention's, as its score
    product takes them (normed, rotated), and ``lse [batch, heads, L]`` its
    forward's statistics under the same selection — all three DETACHED
    here; ``words``, ``lse_i``: :func:`select`'s. The XLA path reads neither
    ``lse``: it forms both softmaxes itself."""
    q, k, lse, lse_i = (jax.lax.stop_gradient(x) for x in (q, k, lse, lse_i))
    if kernels:
        return _kl(a, b, w, q, k, lse, words, lse_i, float(scale),
                   _check(a.shape[1], 1, chunk), interpret)
    return _kl_reference(a, b, w, q, k, words, scale)
