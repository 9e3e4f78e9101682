"""The flash kernels' backward (custom VJP) against the XLA reference path's
gradients: causal and bidirectional, s_q != s_k either way and dead rows,
block_q != block_k, few block pairs a head and many on the ONE kernel, bf16
operands, gpt2-medium's, gpt2-xl's and BERT's shapes. Interpreted on the CPU; a case is the kernels' forward and
gradients as ONE jitted program and the reference's as one more, on inputs
drawn on the host (``conftest.out_and_grads``, ``conftest.normal``)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import normal, out_and_grads

from easydl_tpu.ops import flash_attention as flash_module
from easydl_tpu.ops.attention import _reference_attention
from easydl_tpu.ops.flash_attention import flash_attention


def cos_weighed(out):
    """A scalar of ``out`` whose gradient differs from entry to entry."""
    out = out.astype(jnp.float32)
    return (out * jnp.cos(out)).sum()


def assert_grads_close(got, want, atol, rtol):
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            atol=atol, rtol=rtol, err_msg=f"d{name} mismatch")


def flash_and_reference(q, k, v, *, causal, block_q, block_k, window=None):
    """``(out, grads)`` of the interpreted kernels and of the XLA reference
    on the same inputs, under :func:`cos_weighed`: two programs."""
    flash = functools.partial(flash_attention, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=True, window=window)
    ref = functools.partial(_reference_attention, causal=causal,
                            scale=q.shape[-1] ** -0.5, window=window)
    return (out_and_grads(flash, cos_weighed)(q, k, v),
            out_and_grads(ref, cos_weighed)(q, k, v))


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    q, k, v = normal(1, *[(1, 64, 2, 16)] * 3)
    (_, g_flash), (_, g_ref) = flash_and_reference(
        q, k, v, causal=causal, block_q=32, block_k=32)
    assert_grads_close(g_flash, g_ref, atol=5e-4, rtol=5e-4)


def test_causal_cross_length_bottom_right_aligned():
    """s_q != s_k causal masking must match the reference path's
    bottom-right alignment (tril k=s_k-s_q) — e.g. decode: q_len 32 against a
    64-long KV cache attends all past keys, not just the first 32."""
    b, h, d, s_q, s_k = 2, 2, 32, 32, 64
    q, k, v = normal(4, (b, s_q, h, d), (b, s_k, h, d), (b, s_k, h, d))
    (out, g_flash), (ref, g_ref) = flash_and_reference(
        q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
    assert_grads_close(g_flash, g_ref, atol=5e-4, rtol=5e-4)


def test_causal_cross_length_sq_gt_sk_dead_rows():
    """s_q > s_k bottom-right-aligned causal: the first s_q - s_k query rows
    attend nothing. Both paths must define such rows as zero output with
    zero gradient (not softmax's uniform mean of V) — and agree on the live
    rows. Exercises dead rows both inside a mixed q-block (block 16 > 8
    dead rows? no: 32 dead rows span blocks) and whole-dead q-blocks."""
    b, h, d, s_q, s_k = 2, 2, 16, 64, 32
    q, k, v = normal(6, (b, s_q, h, d), (b, s_k, h, d), (b, s_k, h, d))
    n_dead = s_q - s_k
    # block 16 divides both: dead rows cover 2 whole q-blocks; also run with
    # block 32 so one q-block mixes dead and live rows (its gradients too).
    (out, g_flash), (ref, g_ref) = flash_and_reference(
        q, k, v, causal=True, block_q=32, block_k=16)
    whole = jax.jit(functools.partial(
        flash_attention, causal=True, block_q=16, block_k=16,
        interpret=True))(q, k, v)
    for bq, got in ((16, whole), (32, out)):
        np.testing.assert_allclose(
            np.asarray(got[:, :n_dead]), 0.0, err_msg=f"bq={bq} dead rows"
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5,
            err_msg=f"bq={bq}",
        )
    np.testing.assert_allclose(np.asarray(g_flash[0][:, :n_dead]), 0.0,
                               err_msg="dead rows must not leak dq")
    assert_grads_close(g_flash, g_ref, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("s_q,s_k", [(128, 128), (64, 128), (128, 64)],
                         ids=["square", "sq<sk", "sq>sk"])
@pytest.mark.parametrize("block_q,block_k", [(64, 32), (32, 64)])
def test_causal_rectangular_blocks_forward_and_grads(block_q, block_k, s_q, s_k):
    """block_q != block_k, both ways, square and with a non-zero offset
    either way: the unmasked loop, the masked loop and the boundary between
    them all run (8 block pairs a head), and dead rows where s_q > s_k."""
    b, h, d = 1, 2, 16
    q, k, v = normal(7, (b, s_q, h, d), (b, s_k, h, d), (b, s_k, h, d))
    (out, g_flash), (ref, g_ref) = flash_and_reference(
        q, k, v, causal=True, block_q=block_q, block_k=block_k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert_grads_close(g_flash, g_ref, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s_q,s_k,block_q,block_k,heads,d", [
    (256, 256, 32, 32, 2, 32), (128, 256, 16, 64, 2, 32),
    (256, 128, 64, 16, 2, 32), (32, 576, 32, 32, 2, 32),
    # the backward's pair in tiles of 128 x 128 (blocks of 256: four tiles a
    # head, the masked pair's three live ones placed statically): one head
    # of 128 a cell, and two of 64
    (1280, 1280, 256, 256, 1, 128), (1280, 1280, 256, 256, 2, 64),
    # an offset of 128 under blocks of 128 x 256: the diagonal at no static
    # place, every tile of a crossed pair under its own traced edge
    (1152, 1280, 128, 256, 1, 128),
    # blocks the tile does not divide: the pair is one tile
    (1344, 1344, 192, 192, 2, 32)],
    ids=["square", "sq<sk", "sq>sk", "one-q-block", "tiles-one-head-of-128",
         "tiles-two-heads-of-64", "tiles-offset-no-static-place",
         "one-tile-a-block-of-192"])
def test_looped_walk_matches_reference(s_q, s_k, block_q, block_k, heads, d,
                                       causal):
    """More than 16 block pairs a head: one Q-block (K-block) a grid cell,
    block indices known only at run time, both loops ``fori_loop``s."""
    assert (s_q // block_q) * (s_k // block_k) > 16
    q, k, v = normal(8, (1, s_q, heads, d), (1, s_k, heads, d),
                     (1, s_k, heads, d))
    (out, g_flash), (ref, g_ref) = flash_and_reference(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert_grads_close(g_flash, g_ref, atol=5e-4, rtol=5e-4)


ONE_KERNEL = {
    # s_q, s_k, heads, d, dv, block_q, block_k, causal, window: every one
    # more than 16 block pairs a head (until PR 60 the side of the rule that
    # made the backward one kernel).
    "causal-square": (256, 256, 2, 32, 32, 32, 32, True, None),
    "sq<sk": (128, 256, 2, 32, 32, 16, 64, True, None),
    "sq>sk-dead-rows": (256, 128, 2, 32, 32, 64, 16, True, None),
    "not-causal": (160, 160, 2, 32, 32, 32, 32, False, None),
    "window-wider-than-a-block": (256, 256, 2, 32, 32, 32, 32, True, 80),
    "192-128": (640, 640, 2, 192, 128, 128, 128, True, None),
    "three-heads-of-a-tile-of-two": (320, 320, 3, 64, 64, 64, 64, True, None),
}


def _eqns(jaxpr, kernel=None):
    """``(equation, name of the pallas_call it stands inside or None)`` for
    every equation under ``jaxpr``, kernel bodies and loop bodies included."""
    for eqn in jaxpr.eqns:
        yield eqn, kernel
        inside = (eqn.params["name"] if eqn.primitive.name == "pallas_call"
                  else kernel)
        for value in eqn.params.values():
            for v in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, inside)


def _traced_and_run(fn, *args):
    """``({name: number of results} of the *_bwd* pallas_calls that
    fn(*args) traces to, fn(*args))``: traced ONCE, the trace read, then
    lowered, compiled and run."""
    traced = jax.jit(fn).trace(*args)
    calls = {eqn.params["name"]: len(eqn.outvars)
             for eqn, _ in _eqns(traced.jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"
             and "_bwd" in eqn.params["name"]}
    return calls, traced.lower().compile()(*args)


@pytest.mark.parametrize("case", ONE_KERNEL)
def test_the_looped_backward_is_one_kernel(case):
    """ONE call gives dq, dk and dv: against the reference's gradients on
    float32 operands; rows no key sees get a dq of exactly zero. (The split
    dq and dkv kernels it was held to bit for bit on bf16 operands went with
    PR 60: nothing calls them.)"""
    s_q, s_k, heads, d, dv, block_q, block_k, causal, window = ONE_KERNEL[case]
    q, k, v, g = normal(12, (1, s_q, heads, d), (1, s_k, heads, d),
                        (1, s_k, heads, dv), (1, s_q, heads, dv))
    flash = functools.partial(flash_attention, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=True, window=window)
    ref = functools.partial(_reference_attention, causal=causal,
                            scale=d ** -0.5, window=window)

    def grads(attend, *x):
        return jax.grad(lambda *x: jnp.sum(attend(*x) * g.astype(x[0].dtype)),
                        (0, 1, 2))(*x)

    kind = "mla" if d != dv else "swa" if window else "flash"
    calls, got = _traced_and_run(functools.partial(grads, flash), q, k, v)
    assert calls == {f"{kind}_bwd": 3}
    assert_grads_close(got, jax.jit(functools.partial(grads, ref))(q, k, v),
                       atol=1e-4, rtol=1e-4)
    dead = max(s_q - s_k, 0) if causal else 0
    assert not np.asarray(got[0][:, :dead]).any()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("against", ["bf16", "float32"])
def test_bf16_grads(against, causal):
    """bf16 in, bf16 operands to every matmul: gradients against the XLA
    reference on the same bf16 inputs (which makes the same two roundings,
    of the probabilities and of dS), and against it on float32 copies."""
    q, k, v = normal(9, *[(1, 128, 2, 32)] * 3, dtype="bfloat16")
    flash = functools.partial(flash_attention, causal=causal, block_q=64,
                              block_k=32, interpret=True)
    ref = functools.partial(_reference_attention, causal=causal, scale=32**-0.5)
    _, got = out_and_grads(flash, cos_weighed)(q, k, v)
    assert all(g.dtype == jnp.bfloat16 for g in got)
    if against == "float32":
        q, k, v = (np.asarray(x, np.float32) for x in (q, k, v))
    _, want = out_and_grads(ref, cos_weighed)(q, k, v)
    # bf16 keeps 8 bits: a few percent of the largest entry, as chip_smoke's
    # KERNEL_GRAD_RTOL states for the real size
    for g, w, name in zip(got, want, "qkv"):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= 3e-2 * np.abs(w).max(), f"d{name}"


def _kernel_dots(fn, *args):
    """{kernel name: [(lhs dtype, rhs dtype) of every dot_general inside]}
    for the ``pallas_call``s that ``fn(*args)`` traces to."""
    found = {}
    for eqn, kernel in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name == "pallas_call":
            found.setdefault(eqn.params["name"], [])
        elif eqn.primitive.name == "dot_general" and kernel:
            found[kernel].append(tuple(
                jnp.dtype(x.aval.dtype).name for x in eqn.invars))
    return found


@pytest.mark.parametrize("rows", [64, 256], ids=["4-pairs", "64-pairs"])
@pytest.mark.parametrize("dtype,other", [("bfloat16", "float32"),
                                         ("float32", "bfloat16")])
def test_matmul_operands_follow_the_input_dtype(dtype, other, rows):
    """Walk the kernel jaxprs inside the ``pallas_call``s: with bf16
    inputs no ``dot_general`` takes a float32 operand, with float32 inputs
    none takes a bf16 one."""
    q, k, v = normal(10, *[(1, rows, 1, 32)] * 3, dtype=dtype)
    flash = functools.partial(flash_attention, causal=True, block_q=32,
                              block_k=32, interpret=True)
    dots = _kernel_dots(jax.grad(lambda *x: cos_weighed(flash(*x)), argnums=(0, 1, 2)), q, k, v)
    # products a block pair, in the masked and in the unmasked loop's body:
    # five in the backward (the split dq and dkv kernels made seven)
    assert {n: len(found) for n, found in dots.items()} == {
        "flash_fwd": 4, "flash_bwd": 10}
    for name, operands in dots.items():
        assert all(pair == (dtype, dtype) for pair in operands), (name, operands)
        assert not any(other in pair for pair in operands)


@pytest.mark.parametrize("s_q,s_k,causal,mask,dots", [
    # 25 pairs a head in blocks of 512, sixteen tiles of 128 x 128 a pair
    # and five products a tile: the unmasked pair's body 80, and the masked
    # pair's the ten tiles on and under the diagonal — the six above it are
    # not computed
    (2560, 2560, True, None, 80 + 50),
    (2560, 2560, False, None, 80),
    # the diagonal at no static place (an offset of 128; K-blocks of 384,
    # twelve tiles a pair): every tile of a crossed pair is computed, under
    # its own edge
    (2560, 2688, True, None, 60 + 60),
    # the block mask at 6,144 rows: the wholly live pair's body, the noised
    # half's own pair in its four diagonal tiles, the clean diagonals' (one
    # body under a traced strictness) in ten
    (6144, 6144, False, flash_module.BlockDiffusion(4, 3072), 80 + 20 + 50),
    # gpt2-medium's call: ONE pair of 1,024 x 1,024 a grid cell, its place a
    # Python number — the masked body alone is traced, the 36 tiles on and
    # under the diagonal of its 64
    (1024, 1024, True, None, 36 * 5),
    # a 2,048-long head: four pairs of 1,024, both bodies
    (2048, 2048, True, None, (64 + 36) * 5),
], ids=["placed", "no-mask", "offset-no-static-place", "block-mask",
        "one-pair-of-1024", "four-pairs-of-1024"])
def test_a_masked_pairs_dead_tiles_are_not_computed(s_q, s_k, causal, mask,
                                                    dots):
    """The backward's kernel traced, not run: the products in its bodies, by
    the tiles a pair is walked in."""
    q, k, v = normal(11, (1, s_q, 1, 128), (1, s_k, 1, 128),
                     (1, s_k, 1, 128), dtype="bfloat16")
    blocks = flash_module.choose_blocks(s_q, s_k, causal, mask=mask)[2]
    assert flash_module._bwd_tiles(*blocks, 1)[:2] == (128, 128)
    found = _kernel_dots(jax.grad(
        lambda *x: cos_weighed(flash_attention(*x, causal=causal, mask=mask)),
        argnums=(0, 1, 2)), q, k, v)
    assert len(found["bd_bwd" if mask else "flash_bwd"]) == dots


#: the shapes the unrolled kernels served until PR 60, a batch row of each:
#: ``(s_q, s_k, heads, causal, blocks)`` at heads of 64
SHORT = {
    "gpt2-medium": (1024, 1024, 16, True, (1024, 1024)),
    # 25 heads of 64 on 13 lane blocks, the last half outside the array
    "gpt2-xl": (1024, 1024, 25, True, (1024, 1024)),
    "bert-512": (512, 512, 12, False, (512, 512)),
    "bert-128": (128, 128, 12, False, (128, 128)),
    # a rectangle either way: the square block of the shorter side
    "sq<sk": (512, 1024, 4, True, (512, 512)),
    "sq>sk-dead-rows": (1024, 512, 4, True, (512, 512)),
    "2048": (2048, 2048, 2, True, (1024, 1024)),
}


@pytest.mark.parametrize("case", SHORT)
def test_the_short_shapes_against_the_reference(case):
    """bf16 operands at the shapes' own blocks (``choose_blocks``: no block
    named): out, dq, dk and dv against the float32 reference on the same
    values, within what bf16 keeps; a row no key sees has an out and a dq of
    exactly zero."""
    s_q, s_k, heads, causal, blocks = SHORT[case]
    assert flash_module.choose_blocks(s_q, s_k, causal) == (blocks,) * 3
    q, k, v = normal(13, (1, s_q, heads, 64), (1, s_k, heads, 64),
                     (1, s_k, heads, 64), dtype="bfloat16")
    flash = functools.partial(flash_attention, causal=causal, interpret=True)
    ref = functools.partial(_reference_attention, causal=causal,
                            scale=64 ** -0.5)
    out, got = out_and_grads(flash, cos_weighed)(q, k, v)
    want_out, want = out_and_grads(ref, cos_weighed)(
        *(np.asarray(x, np.float32) for x in (q, k, v)))
    assert out.dtype == jnp.bfloat16
    for g, w, name in zip((out,) + tuple(got), (want_out,) + tuple(want),
                          ("out", "dq", "dk", "dv")):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= 3e-2 * np.abs(w).max(), name
    dead = max(s_q - s_k, 0)
    assert not np.asarray(out[:, :dead], np.float32).any()
    assert not np.asarray(got[0][:, :dead], np.float32).any()


@pytest.mark.parametrize("s_q,s_k,causal,window,blocks", [
    (128, 128, False, None, (128, 128)),
    (512, 512, True, None, (512, 512)),
    (1024, 1024, True, None, (1024, 1024)),
    (1024, 1024, False, None, (1024, 1024)),
    (2048, 2048, True, None, (1024, 1024)),
    # causal: square, the shorter side's, so that the diagonal passes
    # through a pair's corner; not causal: each side's own
    (512, 1024, True, None, (512, 512)),
    (1024, 512, True, None, (512, 512)),
    (512, 2048, False, None, (512, 1024)),
    (1536, 1536, True, None, (768, 768)),
    # longer than 2,048 either side, under a window, or not whole tiles of
    # 128: the blocks of 512 every longer cell has
    (4096, 4096, True, None, (512, 512)),
    (512, 4096, True, None, (512, 512)),
    (1024, 1024, True, 2048, (512, 512)),
    (72, 72, True, None, (72, 72)),
    (520, 520, True, None, None),  # no block: the XLA path's
], ids=lambda x: str(x).replace(" ", ""))
def test_the_blocks_are_a_function_of_the_shape(s_q, s_k, causal, window,
                                                blocks):
    """The rule as a table (``choose_blocks``; ``_SHORT``'s measurements):
    lengths, causal, window -> the one block the forward and the ONE
    backward take. No form to choose: every call is the same two kernels."""
    assert flash_module.choose_blocks(
        s_q, s_k, causal, window=window) == (blocks and (blocks,) * 3)
