"""Block diffusion's mask through the flash kernels (interpreted on the CPU)
against the mask WRITTEN OUT from its four rules: forward, ``lse``, dq, dk and
dv at few block pairs a head and at many, at a block length of 4 and of 32,
under kernel blocks that hold many mask blocks, one, and — where a pair is
walked in tiles of 128 — a tile that is one; that nothing leaks through the
attention (bit for bit); the walk's block pairs against the formula and a
count from the written-out mask; the refusals by name; both paths of
``multihead_attention`` under grouped-query attention."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import normal, out_and_grads

from easydl_tpu.ops import attention as attention_module
from easydl_tpu.ops import flash_attention as fa
from easydl_tpu.ops import multihead_attention, remat
from easydl_tpu.ops.flash_attention import BlockDiffusion


def four_rules(seq, block):
    """``[2 seq, 2 seq]`` bool, entry by entry."""
    mask = np.zeros((2 * seq, 2 * seq), bool)
    for q in range(2 * seq):
        for k in range(2 * seq):
            bq, bk = (q % seq) // block, (k % seq) // block
            if q < seq and k < seq:
                mask[q, k] = bk == bq
            elif q < seq:
                mask[q, k] = bk < bq
            elif k >= seq:
                mask[q, k] = bk <= bq
    return mask


def written_out(q, k, v, mask):
    """``(out, lse)`` of softmax attention under a written-out mask."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") \
        * q.shape[-1] ** -0.5
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                     precision="highest")
    return out, jax.nn.logsumexp(scores, -1)


@pytest.mark.parametrize("seq,block", [(8, 4), (32, 4), (64, 32)])
def test_dense_is_the_four_rules_and_pairs_the_formula(seq, block):
    mask = BlockDiffusion(block, seq)
    want = four_rules(seq, block)
    np.testing.assert_array_equal(np.asarray(mask.dense()), want)
    assert want.sum() == mask.pairs() == seq * seq + seq * block
    assert want.diagonal().all()  # every row sees itself


#: (tokens, block length, kernel block): at most 16 block pairs a head and
#: more; a kernel block of many mask blocks and of one;
#: 768 x 256 walks a pair in tiles of 128, which at a block length of 128
#: ARE mask blocks
CASES = [(64, 4, 32), (64, 32, 32), (128, 4, 32), (128, 32, 32),
         (768, 4, 256), (768, 128, 256)]


@pytest.mark.parametrize("seq,block,side", CASES)
def test_kernels_against_the_written_out_mask(seq, block, side):
    mask = BlockDiffusion(block, seq)
    dense = jnp.asarray(four_rules(seq, block))
    heads, d = 2, 64
    q, k, v, g = normal(seq + block, *[(1, 2 * seq, heads, d)] * 4)
    blocks = fa.choose_blocks(2 * seq, 2 * seq, False, side, side, mask=mask)
    assert blocks == ((side, side),) * 3

    def kernels(q, k, v):
        flat = [x.reshape(1, 2 * seq, heads * d) for x in (q, k, v)]
        out, (_, _, _, _, lse) = fa._flash_fwd(
            *flat, heads, False, d ** -0.5, blocks, True, None, mask)
        return out.reshape(q.shape), lse

    got, lse = jax.jit(kernels)(q, k, v)
    want, want_lse = jax.jit(lambda q, k, v: written_out(q, k, v, dense))(
        q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5)
    weigh = lambda out: jnp.sum(out * g)  # noqa: E731
    _, mine = out_and_grads(functools.partial(
        fa.flash_attention, mask=mask, interpret=True, block_q=side,
        block_k=side), weigh)(q, k, v)
    _, theirs = out_and_grads(
        lambda q, k, v: written_out(q, k, v, dense)[0], weigh)(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), mine, theirs):
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("seq,block,side", [(64, 4, 32), (128, 4, 32)])
def test_nothing_leaks_through_the_attention_bit_for_bit(seq, block, side):
    """Block ``b`` of the NOISED half changed: every clean row's result and
    every other noised block's is what it was, bit for bit. Block ``b`` of
    the CLEAN half changed: the noised blocks up to ``b`` and the clean
    blocks before ``b`` are what they were. And the clean half's result is a
    run of the clean rows ALONE under the mask by blocks."""
    mask = BlockDiffusion(block, seq)
    attend = jax.jit(functools.partial(
        fa.flash_attention, mask=mask, interpret=True, block_q=side,
        block_k=side))
    q, k, v, other = normal(3, *[(1, 2 * seq, 2, 64)] * 4)
    base = np.asarray(attend(q, k, v))
    b = 5
    rows = np.arange(2 * seq)
    blk = (rows % seq) // block

    def changed(at):
        where = jnp.asarray(at)[None, :, None, None]
        return np.asarray(attend(*(jnp.where(where, other, x)
                                   for x in (q, k, v))))

    noised_b = (rows < seq) & (blk == b)
    same = ~noised_b
    np.testing.assert_array_equal(changed(noised_b)[:, same], base[:, same])
    clean_b = (rows >= seq) & (blk == b)
    same = ((rows < seq) & (blk <= b)) | ((rows >= seq) & (blk < b))
    np.testing.assert_array_equal(changed(clean_b)[:, same], base[:, same])
    moved = ((rows < seq) & (blk > b)) | ((rows >= seq) & (blk >= b))
    assert (changed(clean_b)[:, moved] != base[:, moved]).any(-1).all()
    alone, _ = written_out(q[:, seq:], k[:, seq:], v[:, seq:],
                           jnp.asarray(four_rules(seq, block))[seq:, seq:])
    np.testing.assert_allclose(base[:, seq:], alone, atol=2e-5)


@pytest.mark.parametrize("rows,side,block,visited", [
    (16384, 512, 4, 288), (16384, 512, 512, 272), (256, 32, 4, 24),
    (128, 16, 4, 24), (128, 16, 16, 20)])
def test_the_walk_visits_the_live_block_pairs_and_no_other(rows, side, block,
                                                          visited):
    """``n² + 2 n`` of ``4 n²`` (288 of 1,024 at 16,384 rows in blocks of
    512: causal keeps 528), ``n² + n`` where a kernel block is one mask
    block; against the written-out mask where that is small; and the walk
    itself, Q-block by Q-block and K-block by K-block."""
    seq = rows // 2
    mask = BlockDiffusion(block, seq)
    n = seq // side
    assert mask.block_pairs(side) == (visited, 4 * n * n)
    assert visited == n * n + (n if side == block else 2 * n)
    walked, mirrored = set(), set()
    with jax.disable_jit():  # the loops' turns as Python's, on numbers
        for qb in range(2 * n):
            fa._bd_k_blocks(
                lambda kb, carry, masked: walked.add((qb, int(kb))) or carry,
                0, qb, mask=mask, side=side)
        for kb in range(2 * n):
            fa._bd_q_blocks(
                lambda qb, carry, masked: mirrored.add((int(qb), kb)) or carry,
                0, kb, mask=mask, side=side)
    assert walked == mirrored and len(walked) == visited
    if rows <= 256:
        dense = four_rules(seq, block).reshape(2 * n, side, 2 * n, side)
        live = {(int(a), int(b)) for a, b in zip(*np.nonzero(
            dense.any((1, 3))))}
        assert walked == live


def test_the_tiles_of_a_diagonal_pair():
    """A pair of 512 on the noised half's diagonal holds a live pair in its
    four diagonal tiles of 128 alone; on the clean diagonals ten of sixteen,
    four of them crossed — under a traced strictness too."""
    def live_tiles(rule):  # [(first key, first query, crossed)]
        found = [(k, q, fa._bd_tile(rule, 4, k, 128, q, 128))
                 for k in range(0, 512, 128) for q in range(0, 512, 128)]
        return [tile for tile in found if tile[2] is not None]

    assert [(k, q) for k, q, _ in live_tiles(fa._OWN)] == [
        (at, at) for at in range(0, 512, 128)]
    for strict in (0, 1, jnp.int32(1)):
        tiles = live_tiles(("upto", strict))
        assert len(tiles) == 10 and sum(c for _, _, c in tiles) == 4
        assert all(k <= q for k, q, _ in tiles)
    # a tile that IS a mask block: wholly live, or dead under the strict rule
    assert fa._bd_tile(("upto", 0), 128, 0, 128, 0, 128) is False
    assert fa._bd_tile(("upto", 1), 128, 0, 128, 0, 128) is None
    assert fa._bd_tile(("upto", jnp.int32(0)), 128, 0, 128, 0, 128) is True
    assert fa._bd_tile(fa._OWN, 128, 0, 128, 0, 128) is False


def test_refusals_say_which_line_refuses():
    q, k, v = normal(0, *[(1, 128, 2, 64)] * 3)
    mask = BlockDiffusion(4, 64)
    with pytest.raises(ValueError, match="replaces causal=True.*"
                                         "flash_attention refuses"):
        fa.flash_attention(q, k, v, causal=True, mask=mask)
    with pytest.raises(ValueError, match="window=8.*flash_attention refuses"):
        fa.flash_attention(q, k, v, window=8, mask=mask)
    with pytest.raises(NotImplementedError,
                       match="head sizes 64 / 32.*flash_attention refuses"):
        fa.flash_attention(q, k, v[..., :32], mask=mask)
    with pytest.raises(ValueError, match="whole blocks of BlockDiffusion"):
        fa.flash_attention(q, k, v, mask=BlockDiffusion(4, 32))
    with pytest.raises(ValueError, match="whole blocks of BlockDiffusion"):
        fa.flash_attention(q, k, v, mask=mask, block_q=32, block_k=16)
    assert fa.choose_blocks(128, 128, False, mask=BlockDiffusion(48, 64)) \
        is None  # a mask block no kernel block is whole of
    with pytest.raises(ValueError, match="multihead_attention refuses"):
        multihead_attention(q, k, v, causal=True, mask=mask)
    with pytest.raises(ValueError, match="multihead_attention refuses"):
        multihead_attention(q[:, :64], k, v, mask=mask)


def test_both_paths_agree_under_grouped_query_attention(monkeypatch):
    """``multihead_attention``'s XLA reference path and the kernels (here
    interpreted) take the same mask; four query heads a key/value head."""
    monkeypatch.setattr(
        attention_module, "flash_attention",
        functools.partial(fa.flash_attention, interpret=True))
    mask = BlockDiffusion(4, 64)
    q, k, v = normal(1, (2, 128, 8, 16), *[(2, 128, 2, 16)] * 2)
    got = jax.jit(functools.partial(
        multihead_attention, impl="flash", mask=mask))(q, k, v)
    want = jax.jit(functools.partial(
        multihead_attention, impl="reference", mask=mask))(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5)
    alone, _ = written_out(q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2),
                           jnp.asarray(four_rules(64, 4)))
    np.testing.assert_allclose(want, alone, atol=2e-5)


def test_remat_counts_the_masks_pairs():
    """What a byte of the forward's results costs to make again, by the
    LIVE pairs: at the cell's call half of a causal call's over as many
    rows — the same 8,070 FLOP a byte as a causal call at 8,192."""
    out = jax.ShapeDtypeStruct((1, 16384, 32 * 128), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((1, 32, 16384), jnp.float32)
    mask = BlockDiffusion(4, 8192)
    cost = remat.flash_flop_per_byte(
        out, lse, s_k=16384, head_dim=128, causal=False, window=None,
        pairs=mask.pairs())
    assert cost == pytest.approx(2 * (8192 ** 2 + 8192 * 4) * 256
                                 / (16384 * (256 + 4)))
    assert round(cost) == 8070 >= remat.FLOOR_FLOP_PER_BYTE
