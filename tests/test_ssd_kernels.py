"""The Mamba-2 scan's Pallas kernels (``ops/ssd.py``: ``ssd_fwd`` and
``ssd_bwd`` under one ``jax.custom_vjp``) against the ``jax.numpy`` scan they
stand in for on the chip: ``y`` and all six gradients in the Pallas
interpreter; what ``ssd_scan`` dispatches to where, and what its logged line
says; the call under a ``tp`` mesh through ``jax.shard_map``; and, compiled
for a DESCRIBED v5e, one Mamba-2 block at each benchmark cell's scan shape
(the two kernels by name, and nothing a flash reader would take for one of
its own). The kernels against the sequential recurrence is two steps: this
file, and ``tests/test_hybrid_stack.py``'s scan against the recurrence."""

from __future__ import annotations

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.ops import ssd

NAMES = "x dt A B C D".split()


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.sqrt(((a - b) ** 2).mean())
                 / (np.sqrt((b ** 2).mean()) + 1e-30))


def scan_inputs(seed, b, s, h, p, g, n, dtype):
    """Operands in Mamba-2's own ranges: step sizes under one, decays that
    leave a chunk between almost whole and almost nothing."""
    r = np.random.default_rng(seed)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    x = f32(r.normal(size=(b, s, h, p))).astype(dtype)
    dt = jax.nn.softplus(f32(r.normal(size=(b, s, h)) - 1.0))
    A = -jnp.exp(f32(r.uniform(-2.0, 2.0, size=(h,))))
    B = f32(r.normal(size=(b, s, g, n))).astype(dtype)
    C = f32(r.normal(size=(b, s, g, n))).astype(dtype)
    return x, dt, A, B, C, f32(r.normal(size=(h,)))


def both_paths(chunk, weights):
    """ONE jitted program: ``(y, gradients)`` by the kernels in the Pallas
    interpreter and by the ``jax.numpy`` scan, on the same operands."""
    def side(fn):
        def run(*args):
            def loss(*a):
                y = fn(*a)
                return (y.astype(jnp.float32) * weights).sum(), y
            grads, y = jax.grad(loss, argnums=tuple(range(6)),
                                has_aux=True)(*args)
            return y, grads
        return run

    kernels = side(functools.partial(ssd.ssd_scan_kernels, chunk=chunk,
                                     interpret=True))
    scan = side(functools.partial(ssd.ssd_scan, chunk=chunk))
    return jax.jit(lambda *args: (kernels(*args), scan(*args)))


#: groups 1 / 2 / 8 x (chunk, chunks) x dtype, a case a (groups, chunks'
#: shape) pair: every group count and every chunks' shape meets both dtypes
CASES = [
    pytest.param(groups, chunk, chunks, *(
        ("float32", 2e-5) if (i + j) % 2 == 0 else ("bfloat16", 1e-2)))
    for i, groups in enumerate((1, 2, 8))
    for j, (chunk, chunks) in enumerate(((16, 1), (16, 4), (32, 1), (32, 3)))]


@pytest.mark.parametrize("groups,chunk,chunks,dtype,tol", CASES)
def test_kernels_equal_the_scan(groups, chunk, chunks, dtype, tol):
    """``y`` and the gradients by ``x``, ``dt``, ``A``, ``B``, ``C``, ``D``,
    over groups 1 / 2 / 8, chunks of 16 and 32, one chunk and several,
    float32 and bfloat16: 16 heads of 8 with a state of 16, so one group's
    16 heads are two grid cells of 8 that share its scores and sum its
    ``dB`` / ``dC`` across cells, two groups' are a cell each, eight groups'
    a cell of 2. In float32 the two paths differ by summation order; in
    bfloat16 by where a group's ``dB`` / ``dC`` are rounded (the kernels sum
    a group's heads in float32 and round once) and by the turned products'
    order."""
    seq = chunk * chunks
    args = scan_inputs(groups * 100 + seq, 2, seq, 16, 8, groups, 16,
                       jnp.dtype(dtype))
    weights = np.random.default_rng(1).normal(size=args[0].shape).astype(
        np.float32)
    with jax.default_matmul_precision("highest"):
        (y, grads), (y_want, want) = both_paths(chunk, weights)(*args)
    assert y.dtype == args[0].dtype and y.shape == args[0].shape
    assert rel(y, y_want) < tol
    for name, g, w in zip(NAMES, grads, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert rel(g, w) < tol, (name, rel(g, w))


def test_kernels_take_whole_chunks_only():
    args = scan_inputs(0, 1, 40, 8, 8, 1, 16, jnp.float32)
    with pytest.raises(ValueError, match="whole chunks of 16"):
        ssd.ssd_scan_kernels(*args, chunk=16, interpret=True)


def operands_like(shape):
    """The scan's six operands at ``shape = (batch, seq, heads, P, groups,
    N)`` as the chip's step has them, for a trace without values."""
    b, s, h, p, g, n = shape
    rows, by_head = jax.ShapeDtypeStruct((b, s, g, n), jnp.bfloat16), \
        jax.ShapeDtypeStruct((h,), jnp.float32)
    return (jax.ShapeDtypeStruct((b, s, h, p), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, s, h), jnp.float32), by_head, rows, rows,
            by_head)


def _never(*a, **k):
    raise AssertionError("the kernels were called")


@pytest.mark.parametrize("on_tpu,shape,chunk,why", [
    (False, (2, 256, 64, 64, 8, 128), 128, "no tpu"),
    (True, (2, 64, 4, 16, 2, 16), 16,
     "2 heads of 16 a cell (4 heads in 2 groups) are no whole tiles"),
    (True, (2, 256, 64, 64, 8, 64), 128,
     "state 64 or chunk 128 is no multiple of 128 lanes"),
    (True, (2, 192, 64, 64, 8, 128), 64,
     "state 128 or chunk 64 is no multiple of 128 lanes"),
    (True, (2, 200, 64, 64, 1, 128), 128,
     "the sequence is no multiple of the chunk (128)"),
], ids=["cpu", "test-preset-widths", "narrow-state", "short-chunk", "ragged"])
def test_scan_takes_the_reference_path_and_says_why(
        ssd_log, monkeypatch, on_tpu, shape, chunk, why):
    """Off the chip, at the ``test`` presets' 16-wide shapes, at a state or
    a chunk that is no lane tile and on a ragged sequence ``ssd_scan`` is the
    ``jax.numpy`` scan, the kernels are never called, and ONE logged line
    says so and why however often the call is traced."""
    from easydl_tpu.ops import platform

    monkeypatch.setattr(platform, "on_tpu", lambda: on_tpu)
    monkeypatch.setattr(ssd, "ssd_scan_kernels", _never)
    like = operands_like(shape)
    for _ in range(2):
        y = jax.eval_shape(functools.partial(ssd.ssd_scan, chunk=chunk), *like)
    assert y.shape == shape[:4] and y.dtype == jnp.bfloat16
    assert len(ssd_log) == 1, ssd_log
    assert ssd_log[0].startswith(
        f"ssd: chunked scan in jax.numpy, not the kernels ({why}), "), ssd_log


@pytest.mark.parametrize("shape,chunk,said", [
    ((2, 8192, 64, 64, 8, 128), 128,
     "64 chunks of 128 a sequence, 64 heads of 64 in 8 B/C groups of state "
     "128"),
    ((2, 4096, 64, 64, 1, 128), 256,
     "16 chunks of 256 a sequence, 64 heads of 64 in 1 B/C groups of state "
     "128"),
], ids=["nemotron", "hybrid"])
def test_scan_takes_the_kernels_at_the_cells_shapes_on_a_tpu(
        ssd_log, described_tpu, shape, chunk, said):
    """On a TPU both cells' shapes tile: the kernels, and the line says
    which and with what blocks."""
    like = operands_like(shape)
    jaxpr = str(jax.make_jaxpr(functools.partial(ssd.ssd_scan, chunk=chunk))(
        *like))
    assert "ssd_fwd" in jaxpr
    assert ssd_log == [
        f"ssd: Pallas kernels ssd_fwd / ssd_bwd, {said}, matmul operands "
        f"bfloat16, decay and state float32; a grid cell is one chunk of 8 "
        f"heads, the state carried in VMEM, every chunk's entry state kept "
        f"for the backward"]


def test_a_shards_heads_decide_under_a_mesh(ssd_log, described_tpu,
                                            monkeypatch, eight_devices):
    """32 heads in one group tile on one device (cells of 8) and under
    ``tp=2`` (16 a shard); under ``tp=8`` a shard holds 4, no whole cell:
    the ``jax.numpy`` scan, and the line says whose heads it counted."""
    like = operands_like((2, 256, 32, 64, 1, 128))
    scan = functools.partial(ssd.ssd_scan, chunk=128)
    with jax.set_mesh(build_mesh(MeshSpec(tp=2), devices=eight_devices[:2])):
        assert "ssd_fwd" in str(jax.make_jaxpr(scan)(*like))
    monkeypatch.setattr(ssd, "ssd_scan_kernels", _never)
    with jax.set_mesh(build_mesh(MeshSpec(tp=8), devices=eight_devices)):
        jax.eval_shape(scan, *like)
    assert [line.split(",")[0] for line in ssd_log] == [
        "ssd: Pallas kernels ssd_fwd / ssd_bwd",
        "ssd: chunked scan in jax.numpy"], ssd_log
    assert "(4 heads of 64 a cell (4 heads in 1 groups) are no whole tiles)" \
        in ssd_log[1]


def test_a_ragged_sequence_still_pads_and_cuts():
    """50 positions in chunks of 16: the scan of the sequence padded to 64
    with ``dt = 0`` behind it, cut back — on the reference path, as before
    the kernels."""
    args = scan_inputs(3, 2, 50, 4, 8, 2, 16, jnp.float32)
    x, dt, A, B, C, D = args
    pad = lambda a: jnp.pad(a, [(0, 0), (0, 14)] + [(0, 0)] * (a.ndim - 2))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(functools.partial(ssd.ssd_scan, chunk=16))(*args)
        whole = jax.jit(functools.partial(ssd.ssd_scan, chunk=16))(
            pad(x), pad(dt), A, pad(B), pad(C), D)
    assert got.shape == x.shape
    np.testing.assert_allclose(got, whole[:, :50], atol=1e-5)


@pytest.mark.parametrize("mesh,groups,sharded", [
    ("tp=2", 2, "heads and groups"), ("tp=2", 1, "heads, one group whole"),
    ("dp=2,tp=4", 2, "batch; 2 groups do not divide over 4: all heads"),
], ids=["tp2-groups", "tp2-one-group", "dp2-tp4-whole"])
def test_kernels_per_shard_under_a_mesh(eight_devices, mesh, groups, sharded):
    """Under a mesh whose ``tp`` or batch axes span devices the call goes
    through ``jax.shard_map`` (GSPMD cannot partition a Mosaic kernel):
    heads over ``tp`` with their groups, or one group whole on every shard,
    batch over the batch axes; where the groups do not divide every shard
    computes all heads. ``y`` and the gradients are the one-device ones."""
    args = scan_inputs(5, 2, 32, 16, 8, groups, 16, jnp.float32)
    weights = np.random.default_rng(1).normal(size=args[0].shape).astype(
        np.float32)

    def run(*a):
        def loss(*a):
            y = ssd.ssd_scan_kernels(*a, chunk=16, interpret=True)
            return (y * weights).sum(), y
        grads, y = jax.grad(loss, argnums=tuple(range(6)), has_aux=True)(*a)
        return y, grads

    with jax.default_matmul_precision("highest"):
        y_want, want = jax.jit(run)(*args)
        spec = MeshSpec.parse(mesh)
        with jax.set_mesh(build_mesh(spec, devices=eight_devices[:spec.size])):
            assert "shard_map" in str(jax.make_jaxpr(run)(*args)), sharded
            y, grads = jax.jit(run)(*args)
    np.testing.assert_allclose(y, y_want, atol=1e-5)
    for name, g, w in zip(NAMES, grads, want):
        assert rel(g, w) < 1e-5, name


# ------------------------------------------------- compiled for a described v5e
def _mamba_block_text(devices, make, **cut):
    """Compiled text of loss and gradients through ONE Mamba-2 block of a
    cell's description at the cell's microbatch (every published width of
    the mixer; a 1,024-row head; a dense FFN cut thin, an expert layer's
    experts cut to two: the scan's shape is the mixer's alone) on one
    described chip."""
    import flax.linen as nn

    one = SingleDeviceSharding(devices[0])
    bundle = make(dtype="bfloat16", remat=True, remat_policy="full",
                  attention_impl="flash", **cut)
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one),
        jax.eval_shape(lambda: nn.unbox(
            bundle.init_fn(jax.random.PRNGKey(0)))))
    seq = cut["seq_len"]
    tokens = jax.ShapeDtypeStruct((2, seq), jnp.int32, sharding=one)
    return jax.jit(jax.grad(
        lambda p, batch: bundle.loss_fn(p, batch, jax.random.PRNGKey(0))[0]
    )).lower(params, {"inputs": tokens, "targets": tokens}).compile().as_text()


@pytest.mark.parametrize("cell", ["nemotron", "hybrid"])
def test_a_mamba_block_at_a_cells_shape_holds_the_two_kernels(
        v5e_2x2, described_tpu, no_persistent_cache, cell):
    """One Mamba-2 block at each benchmark cell's scan shape (Nemotron 3
    Nano's ``M`` sub-layer at 2 x 8,192: 8 groups, 64 chunks of 128; the
    hybrid's layer at 2 x 4,096: one group, 16 chunks of 256), loss and
    gradients under remat ``full``, compiled for the described chip: Mosaic
    takes both kernels at both shapes, ``ssd_fwd`` stands once beside one
    ``ssd_bwd`` (a block that is no scanned run: the compiler merges the
    forward and the one made again), the convolutions' ``conv1d_fwd`` and
    ``conv1d_bwd`` (PR 44) three times each beside them — x, B and C — no
    other Mosaic call is in the program, and ``benchmark/lib/hlo.py
    flash_calls`` — which tells a flash kernel's kind by its name's end and
    lists the call where the number and rank of its results are that kind's
    — lists none of them (``ssd_fwd`` ends as a forward does, but the
    mixer's results are rank 4): a Mamba-2 block held no flash call before
    the kernels either."""
    import importlib
    import sys

    from easydl_tpu.models.granite_hybrid import make_granite_hybrid
    from easydl_tpu.models.nemotron_h import make_nemotron_h

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    hlo = importlib.import_module("lib.hlo")
    if cell == "nemotron":
        text = _mamba_block_text(
            v5e_2x2, make_nemotron_h, seq_len=8192, vocab=1024,
            hybrid_override_pattern="M", experts_held=(0, 8))
    else:
        text = _mamba_block_text(
            v5e_2x2, make_granite_hybrid, seq_len=4096, vocab=1024,
            layer_types=["mamba"])
    calls = [line.split(" = ", 1)[0].strip().lstrip("%").split(".")[0]
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(calls) == ["conv1d_bwd"] * 3 + ["conv1d_fwd"] * 3 + [
        "ssd_bwd", "ssd_fwd"], calls
    assert hlo.flash_calls(text) == []
