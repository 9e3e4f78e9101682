"""Flash-attention kernel numerics vs the XLA reference path — forward and
backward (custom VJP), causal and bidirectional, multiple block splits, and
use inside a jitted transformer step. Kernels run in Pallas interpreter mode
on CPU (same code path the TPU compiles).
"""

import ast
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.ops import attention as attention_module
from easydl_tpu.ops import flash_attention as flash_module
from easydl_tpu.ops.attention import _reference_attention, multihead_attention
from easydl_tpu.ops.flash_attention import choose_blocks, flash_attention
from easydl_tpu.utils import logging as easydl_logging


def rand_qkv(key, b=2, s=128, h=4, d=32, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, s, h, d), dtype)
    v = jax.random.normal(kv, (b, s, h, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [32, 64, 128])
def test_forward_matches_reference(causal, block):
    q, k, v = rand_qkv(jax.random.PRNGKey(0))
    scale = q.shape[-1] ** -0.5
    ref = _reference_attention(q, k, v, causal=causal, scale=scale)
    out = flash_attention(
        q, k, v, causal=causal, block_q=block, block_k=block, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    q, k, v = rand_qkv(jax.random.PRNGKey(1), b=1, s=64, h=2, d=16)
    scale = q.shape[-1] ** -0.5

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, causal=causal, block_q=32, block_k=32, interpret=True
        )
        return (out * jnp.cos(out)).sum()

    def loss_ref(q, k, v):
        out = _reference_attention(q, k, v, causal=causal, scale=scale)
        return (out * jnp.cos(out)).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_uneven_blocks_and_rectangular():
    # seq not equal to block multiples exercises the min() clamping
    q, k, v = rand_qkv(jax.random.PRNGKey(2), s=96, d=64)
    ref = _reference_attention(q, k, v, causal=True, scale=64**-0.5)
    out = flash_attention(q, k, v, causal=True, block_q=96, block_k=96, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_causal_cross_length_bottom_right_aligned():
    """s_q != s_k causal masking must match the reference path's
    bottom-right alignment (tril k=s_k-s_q) — e.g. decode: q_len 32 against a
    64-long KV cache attends all past keys, not just the first 32."""
    key = jax.random.PRNGKey(4)
    kq, kk, kv = jax.random.split(key, 3)
    b, h, d, s_q, s_k = 2, 2, 32, 32, 64
    q = jax.random.normal(kq, (b, s_q, h, d))
    k = jax.random.normal(kk, (b, s_k, h, d))
    v = jax.random.normal(kv, (b, s_k, h, d))
    scale = d**-0.5
    ref = _reference_attention(q, k, v, causal=True, scale=scale)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
        return (o * jnp.cos(o)).sum()

    def loss_ref(q, k, v):
        o = _reference_attention(q, k, v, causal=True, scale=scale)
        return (o * jnp.cos(o)).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_causal_cross_length_sq_gt_sk_dead_rows():
    """s_q > s_k bottom-right-aligned causal: the first s_q - s_k query rows
    attend nothing. Both paths must define such rows as zero output with
    zero gradient (not softmax's uniform mean of V) — and agree on the live
    rows. Exercises dead rows both inside a mixed q-block (block 16 > 8
    dead rows? no: 32 dead rows span blocks) and whole-dead q-blocks."""
    key = jax.random.PRNGKey(6)
    kq, kk, kv = jax.random.split(key, 3)
    b, h, d, s_q, s_k = 2, 2, 16, 64, 32
    q = jax.random.normal(kq, (b, s_q, h, d))
    k = jax.random.normal(kk, (b, s_k, h, d))
    v = jax.random.normal(kv, (b, s_k, h, d))
    scale = d**-0.5
    n_dead = s_q - s_k
    ref = _reference_attention(q, k, v, causal=True, scale=scale)
    # block 16 divides both: dead rows cover 2 whole q-blocks; also run with
    # block 32 so one q-block mixes dead and live rows.
    for bq in (16, 32):
        out = flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=16, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(out[:, :n_dead]), 0.0, err_msg=f"bq={bq} dead rows"
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
            err_msg=f"bq={bq}",
        )

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=32, block_k=16,
                            interpret=True)
        return (o * jnp.cos(o)).sum()

    def loss_ref(q, k, v):
        o = _reference_attention(q, k, v, causal=True, scale=scale)
        return (o * jnp.cos(o)).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(g_flash[0][:, :n_dead]), 0.0,
                               err_msg="dead rows must not leak dq")
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_untileable_length_falls_back_to_reference():
    """Lengths with no usable block divisor (520: over a block of 512, not a
    multiple of 128) must not assert — ``multihead_attention`` drops to the
    XLA path."""
    q, k, v = rand_qkv(jax.random.PRNGKey(5), s=520, d=16)
    ref = _reference_attention(q, k, v, causal=True, scale=16**-0.5)
    out = multihead_attention(q, k, v, causal=True, impl="flash")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_kernels_raise_on_lengths_they_cannot_tile():
    """The kernel module has no other path: the question is a function, the
    call a ValueError."""
    q, k, v = rand_qkv(jax.random.PRNGKey(5), s=72, d=16)
    assert choose_blocks(72, 72, True, 48, 48) is None
    assert choose_blocks(520, 520, True) is None
    assert choose_blocks(72, 72, True) == ((72, 72),) * 3
    with pytest.raises(ValueError, match="q=72 k=72 have no block divisor"):
        flash_attention(q, k, v, causal=True, block_q=48, block_k=48,
                        interpret=True)
    with pytest.raises(TypeError, match="segment_ids"):
        flash_attention(q, k, v, causal=True, interpret=True,
                        segment_ids=jnp.zeros((2, 72), jnp.int32))


def test_kernel_module_imports_nothing_from_the_module_that_chooses():
    """``ops/attention.py`` imports the kernels; the kernels' module reaches
    back up for nothing (read from its imports, at any depth)."""
    with open(flash_module.__file__) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module or "")
            imported |= {f"{node.module}.{a.name}" for a in node.names}
    assert imported and not {m for m in imported if "attention" in m}, imported
    names = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
    assert "_reference_attention" not in names


@pytest.fixture
def attention_log(monkeypatch):
    """Messages ``ops/attention.py`` logs during the test, with ``log_once``
    forgetting what earlier tests of this process said."""
    monkeypatch.setattr(easydl_logging, "_logged_once", set())
    records = []
    handler = logging.Handler()
    handler.emit = lambda record: records.append(record.getMessage())
    attention_module.log.addHandler(handler)
    yield records
    attention_module.log.removeHandler(handler)


@pytest.mark.parametrize("mesh", [None, "dp=2,tp=2"], ids=["one-device", "mesh"])
@pytest.mark.parametrize("case,reason", [
    ("segment-mask", "segment mask requested"),
    ("untileable", "lengths q=520 k=520 have no block divisor <= 512/512"),
])
def test_each_drop_from_the_kernel_is_logged_once_with_its_reason(
        attention_log, monkeypatch, eight_devices, case, reason, mesh):
    """``impl="flash"`` where the kernels cannot serve: the reference path's
    result, one log line with the reason however often the call is traced,
    and the kernels never called — under a mesh (no per-shard wrap around
    the reference path) and without."""
    def never(*a, **k):
        raise AssertionError("the kernels were called")

    monkeypatch.setattr(attention_module, "flash_attention", never)
    s = 520 if case == "untileable" else 64
    q, k, v = rand_qkv(jax.random.PRNGKey(7), b=4, s=s, d=16)
    segments = None
    if case == "segment-mask":
        segments = jnp.asarray(np.arange(s) // 24, jnp.int32)[None].repeat(4, 0)
    want = _reference_attention(q, k, v, causal=True, scale=0.25,
                                segment_ids=segments)
    call = jax.jit(functools.partial(multihead_attention, causal=True,
                                     impl="flash"))
    if mesh is None:
        got = [call(q, k, v, segment_ids=segments) for _ in range(2)]
    else:
        spec = MeshSpec.parse(mesh)
        with jax.set_mesh(build_mesh(spec, devices=eight_devices[:spec.size])):
            got = [call(q, k, v, segment_ids=segments),
                   jax.jit(lambda *a: call(*a, segment_ids=segments))(q, k, v)]
            assert "shard_map" not in str(jax.make_jaxpr(
                lambda *a: call(*a, segment_ids=segments))(q, k, v))
    for out in got:
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
    assert attention_log == [
        f"flash attention: XLA reference path, not the kernel: {reason}"]


def test_bf16_inputs():
    q, k, v = rand_qkv(jax.random.PRNGKey(3), dtype=jnp.bfloat16, s=64)
    ref = _reference_attention(q, k, v, causal=True, scale=q.shape[-1] ** -0.5)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2, rtol=2e-2
    )


def _loss(attend):
    def loss(q, k, v):
        out = attend(q, k, v).astype(jnp.float32)
        return (out * jnp.cos(out)).sum()
    return loss


def _assert_grads_close(got, want, atol, rtol):
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            atol=atol, rtol=rtol, err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("s_q,s_k", [(128, 128), (64, 128), (128, 64)],
                         ids=["square", "sq<sk", "sq>sk"])
@pytest.mark.parametrize("block_q,block_k", [(64, 32), (32, 64)])
def test_causal_rectangular_blocks_forward_and_grads(block_q, block_k, s_q, s_k):
    """block_q != block_k, both ways, square and with a non-zero offset
    either way: the unmasked loop, the masked loop and the boundary between
    them all run (8 block pairs: unrolled), and dead rows where s_q > s_k."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    b, h, d = 1, 2, 16
    q = jax.random.normal(kq, (b, s_q, h, d))
    k = jax.random.normal(kk, (b, s_k, h, d))
    v = jax.random.normal(kv, (b, s_k, h, d))
    flash = functools.partial(flash_attention, causal=True, block_q=block_q,
                              block_k=block_k, interpret=True)
    ref = functools.partial(_reference_attention, causal=True, scale=d**-0.5)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)), atol=2e-5, rtol=2e-5)
    _assert_grads_close(jax.grad(_loss(flash), argnums=(0, 1, 2))(q, k, v),
                        jax.grad(_loss(ref), argnums=(0, 1, 2))(q, k, v),
                        atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s_q,s_k,block_q,block_k", [
    (256, 256, 32, 32), (128, 256, 16, 64), (256, 128, 64, 16),
    (32, 576, 32, 32)],
    ids=["square", "sq<sk", "sq>sk", "one-q-block"])
def test_looped_walk_matches_reference(s_q, s_k, block_q, block_k, causal):
    """More block pairs than ``_UNROLL_PAIRS``: one Q-block (K-block) a grid
    cell, block indices known only at run time, both loops ``fori_loop``s."""
    from easydl_tpu.ops import flash_attention as fa

    assert (s_q // block_q) * (s_k // block_k) > fa._UNROLL_PAIRS
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(8), 3)
    b, h, d = 1, 2, 32
    q = jax.random.normal(kq, (b, s_q, h, d))
    k = jax.random.normal(kk, (b, s_k, h, d))
    v = jax.random.normal(kv, (b, s_k, h, d))
    flash = functools.partial(flash_attention, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=True)
    ref = functools.partial(_reference_attention, causal=causal, scale=d**-0.5)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)), atol=2e-5, rtol=2e-5)
    _assert_grads_close(jax.grad(_loss(flash), argnums=(0, 1, 2))(q, k, v),
                        jax.grad(_loss(ref), argnums=(0, 1, 2))(q, k, v),
                        atol=5e-4, rtol=5e-4)


ONE_KERNEL = {
    # s_q, s_k, heads, d, dv, block_q, block_k, causal, window: every one
    # more block pairs a head than ``_UNROLL_PAIRS``
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


def _backward_calls(fn, *args):
    """``{name: number of results}`` of the ``*_bwd*`` ``pallas_call``s that
    ``fn(*args)`` traces to."""
    return {eqn.params["name"]: len(eqn.outvars)
            for eqn, _ in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "pallas_call"
            and "_bwd" in eqn.params["name"]}


@pytest.mark.parametrize("case", ONE_KERNEL)
def test_the_looped_backward_is_one_kernel(case, monkeypatch):
    """Where the backward is looped ONE call gives dq, dk and dv: against
    the reference's gradients on float32 operands; rows no key sees get a dq
    of exactly zero; and on bf16 operands (where both put the softmax scale
    in the same place) all three to the bit what the split dq and dkv
    kernels give on the same inputs and blocks — reached by calling this
    shape unrolled, which only a test can ask for."""
    s_q, s_k, heads, d, dv, block_q, block_k, causal, window = ONE_KERNEL[case]
    assert not flash_module._unrolled(s_q // block_q, s_k // block_k)
    keys = jax.random.split(jax.random.PRNGKey(12), 4)
    q = jax.random.normal(keys[0], (1, s_q, heads, d))
    k = jax.random.normal(keys[1], (1, s_k, heads, d))
    v = jax.random.normal(keys[2], (1, s_k, heads, dv))
    g = jax.random.normal(keys[3], (1, s_q, heads, dv))
    flash = functools.partial(flash_attention, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=True, window=window)
    ref = functools.partial(_reference_attention, causal=causal,
                            scale=d ** -0.5, window=window)

    def grads(attend, *x):
        return jax.grad(lambda *x: jnp.sum(attend(*x) * g.astype(x[0].dtype)),
                        (0, 1, 2))(*x)

    kind = "mla" if d != dv else "swa" if window else "flash"
    assert _backward_calls(functools.partial(grads, flash), q, k, v) \
        == {f"{kind}_bwd": 3}
    got = grads(flash, q, k, v)
    _assert_grads_close(got, grads(ref, q, k, v), atol=1e-4, rtol=1e-4)
    dead = max(s_q - s_k, 0) if causal else 0
    assert not np.asarray(got[0][:, :dead]).any()

    low = [x.astype(jnp.bfloat16) for x in (q, k, v)]
    one = grads(flash, *low)
    monkeypatch.setattr(flash_module, "_UNROLL_PAIRS", 1 << 20)
    assert _backward_calls(functools.partial(grads, flash), *low) \
        == {f"{kind}_bwd_dq": 1, f"{kind}_bwd_dkv": 2}
    for mine, theirs, name in zip(one, grads(flash, *low), "qkv"):
        np.testing.assert_array_equal(
            np.asarray(mine, np.float32), np.asarray(theirs, np.float32),
            err_msg=f"d{name}")


@pytest.mark.parametrize("s,window,said", [
    (64, None, "fwd 32/32 unrolled, dq 32/32 unrolled, dkv 32/32 unrolled,"),
    (256, None, "fwd 32/32 looped, bwd 32/32 looped, one kernel,"),
    (256, 80, "fwd 32/32 looped, bwd 32/32 looped, one kernel,"),
    (256, 32, "fwd band 128/32 beside 32, dq band 128/32 beside 32, "
              "dkv band 128/32 beside 32,"),
], ids=["unrolled", "looped", "looped-window", "band"])
def test_the_logged_line_says_how_many_kernels_the_backward_is(
        monkeypatch, s, window, said):
    """The engagement is static: the line a process logs once for a call's
    shape says ``one kernel`` where (and only where) the backward is."""
    monkeypatch.setattr(easydl_logging, "_logged_once", set())
    records = []
    handler = logging.Handler()
    handler.emit = lambda record: records.append(record.getMessage())
    flash_module.log.addHandler(handler)
    try:
        q, k, v = rand_qkv(jax.random.PRNGKey(1), b=1, s=s, h=1, d=32)
        jax.eval_shape(functools.partial(
            flash_attention, causal=True, block_q=32, block_k=32,
            interpret=True, window=window), q, k, v)
    finally:
        flash_module.log.removeHandler(handler)
    line, = records
    assert f"blocks q/k {said} over lengths" in line, line


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("against", ["bf16", "float32"])
def test_bf16_grads(against, causal):
    """bf16 in, bf16 operands to every matmul: gradients against the XLA
    reference on the same bf16 inputs (which makes the same two roundings,
    of the probabilities and of dS), and against it on float32 copies."""
    q, k, v = rand_qkv(jax.random.PRNGKey(9), b=1, s=128, h=2, d=32,
                       dtype=jnp.bfloat16)
    flash = functools.partial(flash_attention, causal=causal, block_q=64,
                              block_k=32, interpret=True)
    ref = functools.partial(_reference_attention, causal=causal, scale=32**-0.5)
    got = jax.grad(_loss(flash), argnums=(0, 1, 2))(q, k, v)
    assert all(g.dtype == jnp.bfloat16 for g in got)
    if against == "float32":
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    want = jax.grad(_loss(ref), argnums=(0, 1, 2))(q, k, v)
    # bf16 keeps 8 bits: a few percent of the largest entry, as chip_smoke's
    # KERNEL_GRAD_RTOL states for the real size
    for g, w, name in zip(got, want, "qkv"):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= 3e-2 * np.abs(w).max(), f"d{name}"


@pytest.mark.parametrize("heads,d,pairs,head_bytes,cell", [
    (16, 64, 4, 6 * 1024 * 128, 2),   # gpt2-medium: two heads fill 128 lanes
    (25, 64, 4, 6 * 1024 * 128, 2),   # gpt2-xl's shard: 13 cells, the last half outside
    (12, 64, 1, 6 * 128 * 128, 4),    # BERT at 128: one pair a head
    (12, 64, 2, 6 * 256 * 128, 2),    # two pairs a head
    (6, 64, 1, 6 * 512 * 128, 2),     # three tiles: what divides them
    (16, 64, 1, 6 * 2048 * 256, 2),   # VMEM: float32 [2048, 256] x 6 is the limit
    (8, 128, 4, 6 * 1024 * 256, 1),   # head_dim 128: a head is a tile
    (8, 32, 4, 6 * 1024 * 64, 4),     # head_dim 32: four heads a tile
    (2, 16, 1, 6 * 64 * 32, 2),       # narrower than a tile: the whole width
    (5, 80, 4, 6 * 1024 * 160, 5),    # lcm(80, 128) = 640 lanes > 400: the whole width
], ids=["medium", "xl-shard", "bert-128", "two-pairs", "six-heads", "float32",
        "head-dim-128", "head-dim-32", "narrow", "head-dim-80"])
def test_heads_a_grid_cell(heads, d, pairs, head_bytes, cell):
    from easydl_tpu.ops.flash_attention import _cell_heads

    assert _cell_heads(heads, d, pairs, True, head_bytes) == cell
    # looped at run time: the tile's heads, never more
    import math

    assert _cell_heads(heads, d, pairs, False, head_bytes) \
        == min(heads, math.lcm(d, 128) // d)


def _flash_vs_reference(q, k, v, *, causal, block_q, block_k, atol, rtol,
                        grad_tol):
    """Forward and the three gradients of the interpreted kernels against
    the XLA reference on the same inputs."""
    scale = q.shape[-1] ** -0.5
    flash = functools.partial(flash_attention, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=True)
    ref = functools.partial(_reference_attention, causal=causal, scale=scale)
    out, want = flash(q, k, v), ref(q, k, v)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=atol, rtol=rtol)
    got = jax.grad(_loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(_loss(ref), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all(), f"d{name}"
        assert np.abs(g - w).max() <= grad_tol * np.abs(w).max(), f"d{name}"


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads,d", [
    (2, 64), (4, 64), (3, 64), (25, 64), (1, 64), (2, 128), (3, 128), (5, 32)],
    ids=["pair", "two-pairs", "odd-3", "odd-25", "lone-64", "two-of-128",
         "odd-of-128", "five-of-32"])
def test_the_models_layout_even_and_odd_head_counts(heads, d, dtype):
    """``[batch, seq, heads·head_dim]`` blocks of whole 128-lane tiles: two
    heads of 64 (or four of 32, one of 128) side by side in a grid cell; an
    odd count leaves the last cell half outside the array, and what lies
    there reaches no live head's output or gradient."""
    q, k, v = rand_qkv(jax.random.PRNGKey(11), b=2, s=64, h=heads, d=d,
                       dtype=jnp.dtype(dtype))
    tight = dtype == "float32"
    _flash_vs_reference(q, k, v, causal=True, block_q=32, block_k=32,
                        atol=2e-5 if tight else 2e-2,
                        rtol=2e-5 if tight else 2e-2,
                        grad_tol=5e-4 if tight else 3e-2)


@pytest.mark.parametrize("heads", [2, 3], ids=["even", "odd"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s_q,s_k,block_q,block_k", [
    (64, 64, 32, 32), (64, 128, 32, 64), (128, 64, 64, 32),
    (256, 256, 32, 32), (128, 256, 16, 64)],
    ids=["square", "sq<sk", "sq>sk", "looped-square", "looped-sq<sk"])
def test_the_models_layout_rectangular_unrolled_and_looped(
        s_q, s_k, block_q, block_k, causal, heads):
    """Heads of 64 two to a lane block on both sides of ``_UNROLL_PAIRS``,
    square and with an offset either way (dead rows where s_q > s_k)."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(kq, (1, s_q, heads, 64))
    k = jax.random.normal(kk, (1, s_k, heads, 64))
    v = jax.random.normal(kv, (1, s_k, heads, 64))
    _flash_vs_reference(q, k, v, causal=causal, block_q=block_q,
                        block_k=block_k, atol=2e-5, rtol=2e-5, grad_tol=5e-4)


@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (6, 3), (6, 1), (3, 3)],
                         ids=["4-over-2", "6-over-3", "6-over-1", "3-over-3"])
def test_grouped_queries_reach_the_kernels_repeated(monkeypatch, heads, kv_heads):
    """``multihead_attention`` repeats the shared key/value heads on the
    heads axis in front of the kernels' view; the repeat's transpose sums
    their gradients."""
    monkeypatch.setattr(attention_module, "flash_attention",
                        functools.partial(flash_attention, interpret=True))
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(kq, (2, 64, heads, 64))
    k = jax.random.normal(kk, (2, 64, kv_heads, 64))
    v = jax.random.normal(kv, (2, 64, kv_heads, 64))
    flash = functools.partial(multihead_attention, causal=True, impl="flash")
    ref = functools.partial(multihead_attention, causal=True, impl="reference")
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)), atol=2e-5, rtol=2e-5)
    got = jax.grad(_loss(flash), argnums=(0, 1, 2))(q, k, v)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    _assert_grads_close(got, jax.grad(_loss(ref), argnums=(0, 1, 2))(q, k, v),
                        atol=5e-4, rtol=5e-4)


def _primitives_outside_kernels(fn, *args):
    """Names of every primitive ``fn(*args)`` traces to, at any depth,
    except what runs inside a ``pallas_call``."""
    seen = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            seen.append(eqn.primitive.name)
            if eqn.primitive.name == "pallas_call":
                continue
            for value in eqn.params.values():
                for v in value if isinstance(value, (tuple, list)) else (value,):
                    inner = getattr(v, "jaxpr", v)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return seen


@pytest.mark.parametrize("heads", [4, 3], ids=["even", "odd"])
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_no_transpose_stands_outside_the_kernels(what, heads):
    """The kernels take q, k, v, O, dO and give O, dq, dk, dv in the model's
    own layout: around the three ``pallas_call``s ``flash_attention`` and
    its gradient hold reshapes only — no ``transpose``, and no product of
    whole arrays either (``delta`` is formed in the kernels; the one
    ``reduce_sum`` is this test's loss)."""
    q, k, v = rand_qkv(jax.random.PRNGKey(14), b=2, s=64, h=heads, d=64,
                       dtype=jnp.bfloat16)
    flash = functools.partial(flash_attention, causal=True, block_q=32,
                              block_k=32, interpret=True)
    fn = flash if what == "forward" else jax.grad(
        lambda q, k, v: flash(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    names = _primitives_outside_kernels(fn, q, k, v)
    assert names.count("pallas_call") == (1 if what == "forward" else 3)
    assert "transpose" not in names, names
    assert not {"dot_general", "mul"} & set(names), names


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


@pytest.mark.parametrize("looped", [False, True], ids=["unrolled", "looped"])
@pytest.mark.parametrize("dtype,other", [("bfloat16", "float32"),
                                         ("float32", "bfloat16")])
def test_matmul_operands_follow_the_input_dtype(dtype, other, looped):
    """Walk the kernel jaxprs inside the ``pallas_call``s: with bf16
    inputs no ``dot_general`` takes a float32 operand, with float32 inputs
    none takes a bf16 one."""
    q, k, v = rand_qkv(jax.random.PRNGKey(10), b=1, s=256 if looped else 64,
                       h=1, d=32, dtype=jnp.dtype(dtype))
    flash = functools.partial(flash_attention, causal=True, block_q=32,
                              block_k=32, interpret=True)
    dots = _kernel_dots(jax.grad(_loss(flash), argnums=(0, 1, 2)), q, k, v)
    # products a block pair, in the masked and in the unmasked loop's body
    # (unrolled: 3 live pairs a head): five where dq and dkv make seven
    assert {n: len(found) for n, found in dots.items()} == (
        {"flash_fwd": 4, "flash_bwd": 10} if looped else
        {"flash_fwd": 6, "flash_bwd_dq": 9, "flash_bwd_dkv": 12})
    for name, operands in dots.items():
        assert all(pair == (dtype, dtype) for pair in operands), (name, operands)
        assert not any(other in pair for pair in operands)


def test_inside_jitted_train_step(monkeypatch):
    """Flash path composes with jit + grad in a real model step — on a
    2-device mesh, so the kernel runs per shard inside ``jax.shard_map``."""
    import optax

    from easydl_tpu.ops import attention

    # No chip here: the test, not the program, asks for the interpreter.
    monkeypatch.setattr(
        attention, "flash_attention",
        functools.partial(attention.flash_attention, interpret=True))

    from easydl_tpu.core.mesh import MeshSpec
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.registry import get_model

    bundle = get_model("gpt", size="test", seq_len=64, vocab=256, attention_impl="flash")
    trainer = Trainer(
        init_fn=bundle.init_fn,
        loss_fn=bundle.loss_fn,
        optimizer=optax.adam(1e-3),
        config=TrainConfig(global_batch=8),
        mesh_spec=MeshSpec(dp=2),
    )
    state = trainer.init_state()
    batch = next(iter(bundle.make_data(8)))
    state, metrics = trainer.train_step(state, batch)
    assert np.isfinite(jax.device_get(metrics)["loss"])

    # And matches the reference-attention model numerically.
    bundle_ref = get_model(
        "gpt", size="test", seq_len=64, vocab=256, attention_impl="reference"
    )
    trainer_ref = Trainer(
        init_fn=bundle_ref.init_fn,
        loss_fn=bundle_ref.loss_fn,
        optimizer=optax.adam(1e-3),
        config=TrainConfig(global_batch=8),
        mesh_spec=MeshSpec(dp=2),
    )
    state_ref = trainer_ref.init_state()
    batch_ref = next(iter(bundle_ref.make_data(8)))
    _, metrics_ref = trainer_ref.train_step(state_ref, batch_ref)
    np.testing.assert_allclose(
        jax.device_get(metrics)["loss"],
        jax.device_get(metrics_ref)["loss"],
        rtol=1e-3,
    )
