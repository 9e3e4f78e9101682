"""The window in the flash kernels (``ops/flash_attention.py``, interpreted)
and in the XLA path against the mask written out entry by entry: the band
path and the looped kernels under a window, forward and gradients, and the
rule that chooses between them from the shapes. (Laguna's window-512 and
Mellum 2's window-1,024 layers run these; the models' own tests are
``tests/test_laguna.py`` and ``tests/test_mellum.py``.)"""

import functools

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import normal, out_and_grads, written_out

from easydl_tpu.ops import attention as attention_module
from easydl_tpu.ops.flash_attention import (
    Band,
    choose_blocks,
    flash_attention,
)


# window against block: below, equal, above; square and s_q != s_k; few
# block pairs a head (<= 16) and many. A square problem under a
# window of at most a block's keys takes the band path (block_q: a
# sub-block's rows, block_k: the neighbour's), every other the looped one.
LOOP, BAND = "loop", "band"


def _case(s_q, s_k, block, window, path, *, sub=None, heads=2, d=16, kv=None):
    return pytest.param(s_q, s_k, sub or block, block, window, heads, d, kv,
                        path, id=f"{s_q}x{s_k}-{sub or block}/{block}-w{window}"
                        f"-{heads}x{d}" + (f"kv{kv}" if kv else ""))


WINDOW_CASES = [
    # s_q, s_k, block, window
    _case(64, 64, 16, 8, BAND), _case(64, 64, 16, 16, BAND),
    _case(64, 64, 16, 24, LOOP), _case(64, 64, 16, 40, LOOP),
    _case(32, 64, 16, 8, LOOP), _case(32, 64, 16, 16, LOOP),
    _case(32, 64, 16, 24, LOOP),
    _case(128, 128, 16, 16, BAND), _case(128, 128, 16, 20, LOOP),
    _case(96, 128, 16, 7, LOOP),
    _case(64, 64, 32, 1, BAND), _case(64, 64, 16, 64, LOOP),
    _case(64, 64, 16, 100, LOOP),
    # the band path over several grid cells: the first has no block before
    # it and the last none after it, the others read a neighbour's rows.
    # Window below and equal to the block, sub-blocks smaller than it
    _case(128, 128, 16, 16, BAND, sub=8), _case(256, 256, 16, 1, BAND),
    _case(128, 128, 16, 11, BAND, sub=8, heads=3),
    _case(256, 256, 32, 20, BAND, sub=16),
    # eight heads of 16 to a 128-lane block, two lane blocks
    _case(256, 256, 32, 32, BAND, heads=16),
    # key/value heads repeated to the query's, as the models hand them over
    _case(192, 192, 16, 9, BAND, heads=4, kv=2),
    # one head of 128 to a lane block at the sub-blocks the chip runs (256
    # and 128 rows, pieces cut at whole 128-row tiles)
    _case(2048, 2048, 256, 256, BAND, heads=1, d=128),
    _case(2048, 2048, 256, 200, BAND, sub=128, heads=2, d=128, kv=1),
    # no caller's blocks: the rule's own cut (one cell of 1,024 rows)
    _case(1024, 1024, None, 512, BAND, heads=1, d=128),
    # one key past the block, and a rectangle: the looped kernels
    _case(256, 256, 16, 17, LOOP), _case(128, 256, 16, 16, LOOP),
    # the looped walk with the forward's pair in tiles of 128 (blocks of
    # 256: four tiles, each masked under its own two edges): a rectangle,
    # and a square whose window the caller's neighbour block does not hold
    _case(1024, 1280, 256, 384, LOOP, heads=1, d=128),
    _case(1280, 1280, 256, 300, LOOP, heads=2, d=64),
]


@pytest.mark.parametrize(
    "s_q,s_k,block_q,block_k,window,heads,d,kv,path", WINDOW_CASES)
def test_window_kernels_against_the_written_out_mask(
        s_q, s_k, block_q, block_k, window, heads, d, kv, path):
    _against_the_written_out_mask(s_q, s_k, block_q, block_k, window, heads,
                                  d, kv, path)


def _against_the_written_out_mask(s_q, s_k, block_q, block_k, window, heads,
                                  d, kv, path):
    """Forward, dq and dk / dv of the kernels (interpreted) and of the XLA
    path against the dense band; returns the cut that was taken."""
    kv_shape = (2, s_k, kv or heads, d)
    q, k, v = normal(0, (2, s_q, heads, d), kv_shape, kv_shape)
    if kv:
        k, v = (jnp.repeat(x, heads // kv, axis=2) for x in (k, v))
    took = choose_blocks(s_q, s_k, True, block_q, block_k, window)
    assert {True: BAND, False: LOOP}[isinstance(took[0], Band)] == path
    want = written_out(*(np.asarray(x, np.float64) for x in (q, k, v)),
                       window)
    weights, = normal(9, want.shape)

    def weighed(out):
        return jnp.sum(out * weights)

    # the three kernels (forward and both backward) as one program, the XLA
    # path's forward and gradients as one more
    mine, g_mine = out_and_grads(functools.partial(
        flash_attention, causal=True, window=window, block_q=block_q,
        block_k=block_k, interpret=True), weighed)(q, k, v)
    xla, g_xla = out_and_grads(functools.partial(
        attention_module._reference_attention, causal=True,
        scale=q.shape[-1] ** -0.5, window=window), weighed)(q, k, v)
    np.testing.assert_allclose(np.asarray(mine), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(xla), want, atol=2e-5)
    for a, b in zip(g_mine, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)
    return took


@pytest.fixture
def blocks_of_16(monkeypatch):
    """The rule's own cut at a test's size: blocks of 16 rows, cells of 64,
    sub-blocks of 8 (``MAX_BLOCK`` 512, ``BAND_ROWS`` 2,048 and ``BAND_SUB``
    256 on the chip), so that a window wider than a block is a few rows."""
    from easydl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "MAX_BLOCK", 16)
    monkeypatch.setattr(fa, "BAND_ROWS", 64)
    monkeypatch.setattr(fa, "BAND_SUB", 8)


@pytest.mark.parametrize("s,window,heads,kv,want", [
    # two blocks; one block and one row: a neighbour of two blocks, two to a
    # cell, so that the second and fourth cell read a neighbour's rows
    (128, 32, 2, None, Band(64, 8, 32)),
    (128, 17, 2, None, Band(64, 8, 32)),
    # two blocks and one row: no three blocks divide the sequence, so four,
    # the cell itself; key/value heads repeated as the models hand them over
    (128, 33, 4, 2, Band(64, 8, 64)),
    # three blocks, which divide this sequence: a cell is its neighbour
    (144, 40, 2, None, Band(48, 8, 48)),
], ids=["two-blocks", "a-block-and-a-row", "two-blocks-and-a-row",
        "three-blocks"])
def test_the_band_holds_a_window_wider_than_a_block(blocks_of_16, s, window,
                                                    heads, kv, want):
    """Forward, dq and dk / dv on the band path under a window wider than a
    block, no caller's blocks: the rule's own neighbour of whole blocks."""
    took = _against_the_written_out_mask(s, s, None, None, window, heads, 16,
                                         kv, BAND)
    assert took == (want,) * 3


def test_the_band_at_the_chips_blocks_under_a_window_of_two(monkeypatch):
    """One head of 128 at the blocks the chip runs, window 1,024: cells of
    1,024 rows (``BAND_ROWS`` lowered so that the sequence is two) beside a
    neighbour of 1,024, sub-blocks of 256 whose band is 1,280 keys."""
    from easydl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "BAND_ROWS", 1024)
    took = _against_the_written_out_mask(2048, 2048, None, None, 1024, 1, 128,
                                         None, BAND)
    assert took == (Band(1024, 256, 1024),) * 3


def test_a_window_wider_than_a_cell_stays_looped(blocks_of_16):
    """Past the band's cell (``BAND_ROWS``) no neighbour holds the window:
    the looped kernels walk the band's blocks, as on a rectangle."""
    took = _against_the_written_out_mask(256, 256, None, None, 65, 2, 16, None,
                                         LOOP)
    assert took == ((16, 16),) * 3
    assert choose_blocks(128, 256, True, window=32) == ((16, 16),) * 3


def test_window_blocks_are_chosen_from_the_window():
    banded = choose_blocks(8192, 8192, True, window=512)
    assert banded == (Band(rows=2048, sub=256, reach=512),) * 3
    # a caller's blocks hold for all three kernels, window or not
    assert choose_blocks(64, 64, True, 16, 16, window=8) == (
        Band(rows=64, sub=16, reach=16),) * 3
    # under a window wider than a block: the fewest whole blocks that hold
    # it beside a cell (Mellum 2's window layers)
    assert choose_blocks(8192, 8192, True, window=1024) == (
        Band(rows=2048, sub=256, reach=1024),) * 3
    # without a window, and under one wider than a cell: the plain rule
    assert choose_blocks(8192, 8192, True) == ((512, 512),) * 3
    assert choose_blocks(8192, 8192, True, window=4096) == ((512, 512),) * 3
    with pytest.raises(ValueError, match="window"):
        flash_attention(*normal(0, *[(2, 32, 2, 16)] * 3), causal=False,
                        window=8, interpret=True)


def test_band_or_loop_is_chosen_from_the_shapes_alone():
    """The band path where a block holds the window and the problem is
    square; the looped kernels' blocks, as they were, everywhere else."""
    looped = ((512, 512), (512, 512), (512, 256))
    # one key more than a cell holds; a rectangle (a decode's, a prefix's);
    # a caller's neighbour that does not hold the window
    assert choose_blocks(8192, 8192, True, window=2049) == ((512, 512),) * 3
    assert choose_blocks(4096, 8192, True, window=512) == looped
    assert choose_blocks(4096, 8192, True, window=1024) == ((512, 512),) * 3
    assert choose_blocks(8192, 8192, True, 512, 256, window=512) == (
        (512, 256),) * 3
    for s, window, want in [
            (8192, 512, Band(2048, 256, 512)), (8192, 128, Band(2048, 256, 512)),
            (4096, 512, Band(2048, 256, 512)), (1536, 300, Band(1536, 256, 512)),
            (2560, 512, Band(512, 256, 512)), (256, 64, Band(256, 256, 256)),
            # a window wider than a block: a neighbour of two blocks (three
            # where the sequence is not a whole number of twos, the cell
            # then the neighbour itself), of four past 1,024
            (8192, 513, Band(2048, 256, 1024)), (4096, 1024, Band(2048, 256, 1024)),
            (1536, 600, Band(1536, 256, 1536)), (3072, 1024, Band(1024, 256, 1024)),
            (8192, 1025, Band(2048, 256, 2048)), (1024, 1024, Band(1024, 256, 1024))]:
        assert choose_blocks(s, s, True, window=window) == (want,) * 3
    # a cell's rows are whole neighbour blocks, a neighbour whole sub-blocks
    assert choose_blocks(256, 256, True, 8, 16, window=16) == (
        Band(rows=64, sub=8, reach=16),) * 3
    # no block divisor: no kernel at all, as without a window
    assert choose_blocks(520, 520, True, window=64) is None
