"""The Mamba-2 convolutions' Pallas kernels (``ops/ssd.py``: ``conv1d_fwd`` and
``conv1d_bwd`` under one ``jax.custom_vjp``) against ``silu(causal_conv1d(x,
w, b))`` and jax's gradients of it, which they stand in for on the chip: in
the Pallas interpreter on an x-like array (the sequence along the lanes) and a
B-like one (along the sublanes), float32 and bfloat16, with a bias and without,
over several blocks and strips of the sequence and two batch rows; under
``jax.checkpoint``; what ``causal_conv1d_silu`` dispatches to where and what
its logged line says; and the call per shard under a mesh."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import out_and_grads
from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.ops import ssd

NAMES = ("x", "taps", "bias")
#: [batch, seq, heads, P]: 32 channels turned, 512 positions along the lanes;
#: [batch, seq, groups, N]: 64 positions along the sublanes of 2 lane tiles
X_LIKE, B_LIKE = (2, 512, 4, 8), (2, 64, 2, 128)


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.sqrt(((a - b) ** 2).mean())
                 / (np.sqrt((b ** 2).mean()) + 1e-30))


@pytest.fixture
def small_blocks(monkeypatch):
    """Strips of one tile along the lanes (128) or two along the sublanes
    (16 rows), blocks of two strips each way: a test-size sequence is several
    blocks of several strips, so every neighbour the taps reach into — a
    strip's inside its block, a block's, none at the sequence's ends — is
    met."""
    monkeypatch.setattr(ssd, "_STRIP", {1: (1, 128), 0: (1, 16)})
    monkeypatch.setattr(ssd, "_BLOCK_STRIPS", {1: (2, 2), 0: (1, 2)})


def conv_inputs(seed, shape, dtype, taps=4):
    r = np.random.default_rng(seed)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    return (f32(r.normal(size=shape)).astype(dtype),
            f32(r.uniform(-0.5, 0.5, size=(taps,) + shape[2:])),
            f32(r.normal(size=shape[2:])))


def reference(x, w, b=None):
    return jax.nn.silu(ssd.causal_conv1d(x, w, b))


def weighted(shape):
    weights = np.random.default_rng(1).normal(size=shape).astype(np.float32)
    return lambda y: (y.astype(jnp.float32) * weights).sum()


def three_ways(shape, with_bias):
    """ONE jitted program: ``(y, gradients)`` by the kernels in the Pallas
    interpreter, by the reference as the mixer ran it before them (in the
    inputs' dtype), and by the reference in float32 on the same ROUNDED
    inputs — the truth both are measured against."""
    kernels = functools.partial(ssd.causal_conv1d_silu_kernels,
                                interpret=True)
    scalar = weighted(shape)

    def run(x, w, b):
        args = (x, w, b) if with_bias else (x, w)
        exact = (x.astype(jnp.float32),) + tuple(
            a.astype(x.dtype).astype(jnp.float32) for a in args[1:])
        return (out_and_grads(kernels, scalar)(*args),
                out_and_grads(reference, scalar)(*args),
                out_and_grads(reference, scalar)(*exact))

    return jax.jit(run)


@pytest.mark.parametrize("with_bias", [True, False], ids=["bias", "no-bias"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [X_LIKE, B_LIKE], ids=["x-like", "B-like"])
def test_kernels_equal_the_convolution(small_blocks, shape, dtype, with_bias):
    """``y`` and the gradients by x, the taps and the bias. In float32 the
    kernels and the reference differ by summation order; in bfloat16 the
    kernels round ONCE (products, sums and the SiLU are float32) where the
    reference rounds every operation, so against the float32 truth on the
    rounded inputs the kernels are one rounding off (2^-9) and never further
    off than the reference. Two batch rows: nothing of the first row's end
    reaches the second's start."""
    args = conv_inputs(7, shape, jnp.dtype(dtype))
    (y, grads), (y_ref, grads_ref), (y_true, grads_true) = three_ways(
        shape, with_bias)(*args)
    assert y.dtype == args[0].dtype and y.shape == shape
    tol = 2e-6 if dtype == "float32" else 4e-3
    coarse = dtype == "bfloat16"  # in float32 the reference IS the truth
    assert rel(y, y_true) < tol
    assert not coarse or rel(y, y_true) <= rel(y_ref, y_true)
    for name, g, ref, true in zip(NAMES, grads, grads_ref, grads_true):
        assert g.shape == ref.shape and g.dtype == ref.dtype, name
        assert rel(g, true) < tol, (name, rel(g, true))
        assert not coarse or rel(g, true) <= rel(ref, true), name


@pytest.mark.parametrize("shape", [X_LIKE, B_LIKE], ids=["x-like", "B-like"])
def test_under_checkpoint_the_forward_runs_twice_and_the_sums_once(
        small_blocks, shape):
    """Under ``jax.checkpoint`` (the cells' remat ``full``: a block whose
    later parts need ``y`` again) the differentiated program holds
    ``conv1d_fwd`` twice — the pass and the one made again — and
    ``conv1d_bwd`` once, and the gradients are the plain ones."""
    x, w, b = conv_inputs(3, shape, jnp.float32)
    kernels = functools.partial(ssd.causal_conv1d_silu_kernels,
                                interpret=True)
    scalar = weighted(shape)

    def block(conv):
        return lambda *a: scalar(jnp.tanh(conv(*a)))

    kept = jax.value_and_grad(jax.checkpoint(block(kernels)),
                              argnums=(0, 1, 2))
    assert kernel_calls(jax.make_jaxpr(kept)(x, w, b).jaxpr) == {
        "conv1d_fwd": 2, "conv1d_bwd": 1}
    want = jax.jit(jax.grad(block(reference), argnums=(0, 1, 2)))(x, w, b)
    for name, g, ref in zip(NAMES, jax.jit(kept)(x, w, b)[1], want):
        assert rel(g, ref) < 2e-6, name


def kernel_calls(jaxpr) -> dict:
    """``{kernel's name: calls}`` of the ``pallas_call``s a jaxpr holds, at
    any depth (its printed text shows a body that stands twice once)."""
    from jax._src import core

    counts: dict = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            counts[name] = counts.get(name, 0) + 1
        for sub in core.jaxprs_in_params(eqn.params):
            for name, n in kernel_calls(sub).items():
                counts[name] = counts.get(name, 0) + n
    return counts


def test_the_kernels_raise_on_a_shape_they_cannot_tile():
    x, w, b = conv_inputs(0, (1, 40, 2, 8), jnp.float32)
    with pytest.raises(ValueError, match="no whole tiles of 128 lanes"):
        ssd.causal_conv1d_silu_kernels(x, w, b, interpret=True)


def like(shape, dtype, taps=4):
    return (jax.ShapeDtypeStruct(shape, jnp.dtype(dtype)),
            jax.ShapeDtypeStruct((taps,) + shape[2:], jnp.float32),
            jax.ShapeDtypeStruct(shape[2:], jnp.float32))


def fresh():
    """``causal_conv1d_silu`` as a function jax has not traced yet: a trace
    is cached by the function and the shapes, whatever the platform said."""
    return functools.partial(ssd.causal_conv1d_silu)


def _never(*a, **k):
    raise AssertionError("the kernels were called")


@pytest.mark.parametrize("on_tpu,shape,dtype,taps,why", [
    (False, (2, 8192, 64, 64), "bfloat16", 4, "no tpu"),
    (True, (2, 64, 4, 16), "float32", 4,
     "a sequence of 64 is no whole tiles of 128 lanes"),
    (True, (2, 256, 3, 4), "bfloat16", 4,
     "12 channels are no whole tiles of 16 sublanes"),
    (True, (2, 200, 1, 128), "bfloat16", 4,
     "a sequence of 200 is no whole tiles of 16 sublanes"),
    (True, (2, 256, 1, 128), "float32", 10,
     "10 taps reach past a tile of 8"),
], ids=["cpu", "test-preset-widths", "ragged-channels", "ragged-sequence",
        "long-taps"])
def test_conv_takes_the_reference_path_and_says_why(
        ssd_log, monkeypatch, on_tpu, shape, dtype, taps, why):
    """Off the chip, at the ``test`` presets' short sequences, on channels or
    a sequence that are no whole tiles and with taps that reach past one,
    ``causal_conv1d_silu`` is ``silu(causal_conv1d(...))``, the kernels are
    never called, and ONE logged line says so and why however often the call
    is traced."""
    from easydl_tpu.ops import platform

    monkeypatch.setattr(platform, "on_tpu", lambda: on_tpu)
    monkeypatch.setattr(ssd, "causal_conv1d_silu_kernels", _never)
    for _ in range(2):
        y = jax.eval_shape(fresh(), *like(shape, dtype, taps))
    assert y.shape == shape and y.dtype == jnp.dtype(dtype)
    assert len(ssd_log) == 1, ssd_log
    assert ssd_log[0].startswith(
        f"conv1d: jax.numpy, not the kernels ({why}), {taps} taps, bias and "
        f"SiLU over {list(shape)} {dtype}"), ssd_log


@pytest.mark.parametrize("shape,cut", [
    ((2, 8192, 64, 64), "the sequence along the lanes, blocks of 64 channels "
     "by 8192 positions in strips of 32 by 4096"),
    ((2, 8192, 8, 128), "the sequence along the sublanes, blocks of 128 "
     "channels by 4096 positions in strips of 128 by 256"),
    ((2, 4096, 64, 64), "the sequence along the lanes, blocks of 64 channels "
     "by 4096 positions in strips of 32 by 4096"),
    ((2, 4096, 1, 128), "the sequence along the sublanes, blocks of 128 "
     "channels by 4096 positions in strips of 128 by 256"),
], ids=["nemotron-x", "nemotron-B", "hybrid-x", "hybrid-B"])
def test_conv_takes_the_kernels_at_the_cells_shapes_on_a_tpu(
        ssd_log, described_tpu, shape, cut):
    """On a TPU both cells' shapes tile — x turned, B and C as they are —
    and the line says which way and with what blocks."""
    jaxpr = str(jax.make_jaxpr(fresh())(*like(shape, "bfloat16")))
    assert "name=conv1d_fwd" in jaxpr
    assert ssd_log == [
        f"conv1d: Pallas kernels conv1d_fwd / conv1d_bwd, 4 taps, bias and "
        f"SiLU over {list(shape)} bfloat16, products and sums float32; {cut}; "
        f"the backward keeps x and makes the pre-activation again"]


def test_a_shards_channels_decide_under_a_mesh(
        ssd_log, described_tpu, monkeypatch, eight_devices):
    """Four heads of 8 are 32 channels, two sublane tiles of bfloat16, and
    one under ``tp=2``; under ``tp=4`` a shard holds 8, no whole tile: the
    reference, and the line says whose channels it counted."""
    args = like((2, 256, 4, 8), "bfloat16")
    with jax.set_mesh(build_mesh(MeshSpec(tp=2), devices=eight_devices[:2])):
        assert "name=conv1d_fwd" in str(jax.make_jaxpr(fresh())(*args))
    monkeypatch.setattr(ssd, "causal_conv1d_silu_kernels", _never)
    with jax.set_mesh(build_mesh(MeshSpec(tp=4), devices=eight_devices[:4])):
        jax.eval_shape(fresh(), *args)
    assert [line.split(",")[0] for line in ssd_log] == [
        "conv1d: Pallas kernels conv1d_fwd / conv1d_bwd",
        "conv1d: jax.numpy"], ssd_log
    assert "over [2, 256, 2, 8]" in ssd_log[0]
    assert "(8 channels are no whole tiles of 16 sublanes)" in ssd_log[1]
    assert "over [2, 256, 1, 8]" in ssd_log[1]


@pytest.mark.parametrize("mesh,shape,split", [
    ("tp=2", X_LIKE, "x's heads over tp"),
    ("dp=2,tp=2", X_LIKE, "batch over dp, x's heads over tp"),
    ("tp=2", B_LIKE, "B whole on every shard"),
    ("dp=2,tp=4", B_LIKE, "batch over dp, B whole"),
], ids=["tp2-x", "dp2-tp2-x", "tp2-B", "dp2-tp4-B"])
def test_kernels_per_shard_under_a_mesh(small_blocks, eight_devices, mesh,
                                        shape, split):
    """Under a mesh whose ``tp`` or batch axes span devices the call goes
    through ``jax.shard_map`` (GSPMD cannot partition a Mosaic kernel): x's
    channels over ``tp`` with the heads, B and C whole, batch over the batch
    axes; the taps' and biases' sums are added across the batch's shards.
    ``y`` and the gradients are the one-device ones."""
    args = conv_inputs(5, shape, jnp.float32)
    run = out_and_grads(functools.partial(ssd.causal_conv1d_silu_kernels,
                                          interpret=True), weighted(shape))
    y_want, want = jax.jit(run)(*args)
    spec = MeshSpec.parse(mesh)
    with jax.set_mesh(build_mesh(spec, devices=eight_devices[:spec.size])):
        assert "shard_map" in str(jax.make_jaxpr(run)(*args)), split
        y, grads = jax.jit(run)(*args)
    np.testing.assert_allclose(y, y_want, atol=1e-6)
    for name, g, w in zip(NAMES, grads, want):
        assert rel(g, w) < 1e-6, name
