"""The stack described by data (``models/transformer.py``): GPT-2 unchanged
from the commit before it, the Granite 4.0-H description against the plain
float32 reference (``benchmark/lib/reference_granite_hybrid.py``, the Mamba-2
layers as the sequential recurrence), the scan and its neighbours
(``ops/ssd.py``), grouped-query attention, the head chosen by shape, the
hybrid's state sharded, saved and restored, and its names in the step.

CPU, small sizes, seeded weights. Tolerances: float32 against float32 is the
same mathematics in another order of summation (1e-5 relative); bf16 operands
against the float32 recurrence carry bf16's 8 bits through a few products
(2-3e-2 relative RMS at these sizes).
"""

from __future__ import annotations

import ast
import collections
import copy
import dataclasses
import functools
import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import normal, out_and_grads

from easydl_tpu.core.checkpoint import CheckpointManager
from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.core.sharding import flatten_dict, unbox
from easydl_tpu.core.train_loop import TrainConfig, Trainer
from easydl_tpu.models import lm as gpt_module
from easydl_tpu.models.granite_hybrid import describe
from easydl_tpu.models.registry import get_model, list_models
from easydl_tpu.ops import attention as attention_module
from easydl_tpu.ops.flash_attention import flash_attention
from easydl_tpu.ops.fused_xent import fused_softmax_xent
from easydl_tpu.ops.ssd import (causal_conv1d, gated_rmsnorm,
                                ssd_flops_per_token, ssd_scan)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, HERE)
from gpt2_fingerprint import (  # noqa: E402
    fingerprint,
    gpt2_fsdp4_step_program_sha256,
    hybrid_step_program_sha256,
    step_program_sha256,
)


def _bench_lib(name):
    """A module of ``benchmark/lib`` (the package is not on tier-1's path)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(f"lib.{name}")


ref = _bench_lib("reference_granite_hybrid")
check_module = _bench_lib("check_granite_hybrid")
TEST = dict(size="test", seq_len=32, vocab=256)


def rel(a, r):
    a, r = np.asarray(a, np.float64), np.asarray(r, np.float64)
    return float(np.linalg.norm(a - r) / max(np.linalg.norm(r), 1e-30))


# ------------------------------------------------------------------ GPT-2
@pytest.fixture(scope="module")
def gpt2_now_and_then():
    with open(os.path.join(HERE, "goldens", "gpt2_stack.json")) as f:
        return fingerprint(), json.load(f)


def test_gpt2_parameter_tree_is_the_parents(gpt2_now_and_then):
    now, then = gpt2_now_and_then
    assert now["axes"] == then["axes"]
    assert {k: v["shape"] for k, v in now["params"].items()} \
        == {k: v["shape"] for k, v in then["params"].items()}


@pytest.mark.parametrize("what", ["params", "grads"])
def test_gpt2_values_are_the_parents(gpt2_now_and_then, what):
    """Seeded initial values and every gradient leaf: sums and absolute sums
    recorded on the parent commit (the same jax, the same CPU)."""
    now, then = gpt2_now_and_then
    assert sorted(now[what]) == sorted(then[what])
    for key, want in then[what].items():
        got = now[what][key]
        assert got["sum"] == pytest.approx(want["sum"], rel=1e-5, abs=1e-7), key
        assert got["abs"] == pytest.approx(want["abs"], rel=1e-5), key


def test_gpt2_loss_is_the_parents(gpt2_now_and_then):
    now, then = gpt2_now_and_then
    assert now["loss"] == pytest.approx(then["loss"], rel=1e-6)


def test_gpt2_step_program_is_the_parents_op_for_op(gpt2_now_and_then):
    """The lowered bf16 / remat-dots / two-microbatch step at the test size:
    the same StableHLO text as recorded. PRs 28 and 30 meant to change the
    program (the projections became matrix products; remat ``dots`` keeps
    their sums by name, the bias added to merged rows) and wrote this hash
    anew; the parameter tree, the seeded values, the loss and every gradient
    above are still held to PR 25's parent."""
    assert step_program_sha256() == gpt2_now_and_then[1]["step_program_sha256"]


@pytest.fixture(scope="module")
def parents_step_programs():
    """Hashes of the step programs as PR 30 left them (GPT-2's: what remat
    ``dots`` keeps; the hybrid's is still PR 28's program, hashed anew
    without the private functions' running numbers — the parent commit gives
    the same hash): a later PR that does not mean to change a step program finds
    them equal."""
    with open(os.path.join(HERE, "goldens", "step_programs.json")) as f:
        return json.load(f)


def test_hybrid_step_program_with_the_fused_head_is_the_parents_op_for_op(
        fused_head, parents_step_programs):
    """The hybrid's test description, bf16, remat ``full``, two
    microbatches of 4 x 64, the fused head in 4 chunks of 64 rows, chosen
    by the shape rule with its constant lowered: the recorded StableHLO
    text."""
    fused_head(chunk_rows=64)
    assert hybrid_step_program_sha256() \
        == parents_step_programs["hybrid_step_program_sha256"]


def test_gpt2_step_program_under_fsdp4_is_the_parents_op_for_op(
        monkeypatch, eight_devices, parents_step_programs):
    """GPT-2 at the test size under ``MeshSpec(fsdp=4)`` with the kernels
    called per shard (interpreted): what ``_per_shard`` wraps, and the
    choice in front of it, lower to the recorded text."""
    monkeypatch.setattr(attention_module, "flash_attention",
                        functools.partial(flash_attention, interpret=True))
    assert gpt2_fsdp4_step_program_sha256() \
        == parents_step_programs["gpt2_fsdp4_step_program_sha256"]


@pytest.mark.parametrize("x_shape,w_shape,n", [
    ((2, 8, 32), (32, 4, 8), 1),     # q, k, v: [embed, heads, kv]
    ((2, 8, 4, 8), (4, 8, 32), 2),   # out: [heads, kv, embed]
    ((2, 8, 32), (32, 64), 1),       # the FFN's: a matrix already
], ids=["embed-heads-kv", "heads-kv-embed", "matrix"])
def test_projections_are_matrix_products_with_the_same_numbers(
        x_shape, w_shape, n):
    """``_matrix_dot_general`` (the attention projections' ``dot_general``): the
    result and both gradients of ``lax.dot_general`` on the weight as the
    parameter tree holds it, from ONE product whose right side is a matrix
    and whose result stays ``[batch, seq, heads·kv]`` rows (``_RowsDense``
    gives them the features' shape) — the layout XLA lays q, k, v out in."""
    from easydl_tpu.models.transformer import _matrix_dot_general

    x, w = normal(28, x_shape, w_shape)
    dims = ((tuple(range(x.ndim - n, x.ndim)), tuple(range(n))), ((), ()))
    want = jax.lax.dot_general(x, w, dims)

    def rows(x, w, dims):
        return _matrix_dot_general(x, w, dims).reshape(want.shape)

    def loss(dot):
        return lambda x, w: jnp.sin(dot(x, w, dims)).sum()

    assert jax.eval_shape(
        lambda x, w: _matrix_dot_general(x, w, dims), x, w).shape \
        == x.shape[:x.ndim - n] + (int(np.prod(w_shape[n:])),)
    np.testing.assert_allclose(jax.jit(rows, static_argnums=2)(x, w, dims),
                               want, rtol=1e-5, atol=1e-5)
    got = jax.jit(jax.grad(loss(rows), argnums=(0, 1)))(x, w)
    want = jax.jit(jax.grad(loss(jax.lax.dot_general), argnums=(0, 1)))(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-5)
    products = [eqn for eqn in jax.make_jaxpr(
        lambda x, w: _matrix_dot_general(x, w, dims))(x, w).eqns
        if eqn.primitive.name == "dot_general"]
    assert [eqn.invars[1].aval.ndim for eqn in products] == [2]


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no-bias"])
@pytest.mark.parametrize("x_shape,features,axis", [
    ((2, 8, 32), (4, 8), -1),          # q, k, v
    ((2, 8, 4, 8), 32, (-2, -1)),      # out
], ids=["embed-to-heads-kv", "heads-kv-to-embed"])
def test_rows_projection_is_dense_general_with_the_bias_added_to_rows(
        x_shape, features, axis, bias):
    """``_dense(rows=True)`` against ``_dense()``, flax's ``DenseGeneral``:
    the same parameter tree (names, shapes, logical axes), the same seeded
    initial values, the same result and gradients — and every addition it
    makes is on an array of ONE merged feature dimension, never on ``[batch,
    seq, heads, kv]`` (the compiler lays a kept four-dimensional sum out with
    the sequence as its minor dimension: PERF.md section 6, PR 30)."""
    import flax.linen as nn

    from easydl_tpu.models.transformer import _dense

    kernel_axes = ("embed", "heads", "kv") if axis == -1 \
        else ("heads", "kv", "embed")
    bias_axes = kernel_axes[1:] if axis == -1 else ("embed",)
    x, = normal(30, x_shape)
    built = {rows: _dense(features, kernel_axes, bias_axes, name="p",
                          use_bias=bias, axis=axis, rows=rows)
             for rows in (True, False)}
    boxed = {rows: jax.jit(m.init)(jax.random.PRNGKey(1), x)
             for rows, m in built.items()}
    assert nn.get_partition_spec(boxed[True]) \
        == nn.get_partition_spec(boxed[False])
    params = {rows: nn.unbox(v) for rows, v in boxed.items()}
    assert jax.tree.structure(params[True]) == jax.tree.structure(params[False])
    for a, b in zip(jax.tree.leaves(params[True]),
                    jax.tree.leaves(params[False])):
        np.testing.assert_array_equal(a, b)
    # a bias that is not zero, so that its place shows
    rng = np.random.default_rng(2)
    values = jax.tree.map(lambda p: np.asarray(p) + 0.1 * rng.standard_normal(
        p.shape, np.float32), params[True])

    def both(rows):
        """The projection's result and its gradients: one program."""
        return out_and_grads(built[rows].apply,
                             lambda y: jnp.sin(y).sum())(values, x)

    (y_rows, got), (y_dense, want) = both(True), both(False)
    np.testing.assert_allclose(y_rows, y_dense, rtol=1e-5, atol=1e-5)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-5)
    adds = [eqn for eqn in jax.make_jaxpr(
        lambda v, x: built[True].apply(v, x))(values, x).eqns
        if eqn.primitive.name == "add"]
    assert len(adds) == int(bias)
    assert all(eqn.outvars[0].aval.ndim == 3 for eqn in adds)


def test_gpt2_hint_is_the_all_attention_formula():
    from easydl_tpu.core.mfu import model_flops_per_token

    bundle = get_model("gpt", size="345m")
    assert bundle.flops_per_sample_hint == model_flops_per_token(
        bundle.param_count_hint, 24, 1024, 1024) * 1024


# -------------------------------------------------------------- ops/ssd.py
def _scan_inputs(seed, b, s, h, p, g, n, dtype):
    r = np.random.default_rng(seed)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    x = f32(r.normal(size=(b, s, h, p))).astype(dtype)
    dt = jax.nn.softplus(f32(r.normal(size=(b, s, h)) - 1.0))
    A = -jnp.exp(f32(r.uniform(0, 2.7, size=(h,))))
    B = f32(r.normal(size=(b, s, g, n))).astype(dtype)
    C = f32(r.normal(size=(b, s, g, n))).astype(dtype)
    return x, dt, A, B, C, f32(r.normal(size=(h,)))


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("seq", [64, 50, 12], ids=["multiple", "ragged",
                                                   "under-a-chunk"])
def test_chunked_scan_equals_the_recurrence(seq, dtype, tol):
    """Forward and all six gradients; the recurrence is the reference's
    ``lax.scan`` over positions, in float32 on the same (rounded) inputs."""
    args = _scan_inputs(seq, 2, seq, 4, 8, 2, 16, jnp.dtype(dtype))
    as32 = [a.astype(jnp.float32) for a in args]
    weights = np.random.default_rng(1).normal(size=args[0].shape).astype(
        np.float32)

    def highest(fn):
        def run(*a):
            with jax.default_matmul_precision("highest"):
                return fn(*a)
        return run

    # the chunked scan with its six gradients: one program; the recurrence
    # with its: one more
    y, grads = out_and_grads(highest(functools.partial(ssd_scan, chunk=16)),
                             lambda y: (y * weights).sum())(*args)
    y_ref, grads_ref = out_and_grads(highest(ref.recurrence),
                                     lambda y: (y * weights).sum())(*as32)
    assert y.dtype == args[0].dtype and y.shape == args[0].shape
    assert rel(y, y_ref) < tol
    for name, g, g_ref in zip("x dt A B C D".split(), grads, grads_ref):
        assert rel(g, g_ref) < 2 * tol, name


def test_scan_keeps_its_decay_sums_in_float32():
    """bf16 inputs at the published chunk (256) with Mamba-2's own ranges of
    dt (1e-3 to 1e-1) and A (-1 to -16): the cumulative log-decay reaches
    hundreds, where bf16 resolves to 2 and the decay matrix is noise. With
    the sums in float32 the result is off by the output's own bf16 rounding,
    1.7e-3; with them in bf16 it was 1.3e-2 (tried while writing this)."""
    r = np.random.default_rng(0)
    seq, h, p, n = 512, 4, 16, 32
    x = jnp.asarray(r.normal(size=(1, seq, h, p)), jnp.bfloat16)
    dt0 = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), h))
    dt = jax.nn.softplus(jnp.asarray(
        r.normal(size=(1, seq, h)) * 0.5 + np.log(np.expm1(dt0)), jnp.float32))
    A = -jnp.asarray(np.linspace(1, 16, h), jnp.float32)
    B, C = (jnp.asarray(r.normal(size=(1, seq, 1, n)) / n ** 0.25,
                        jnp.bfloat16) for _ in range(2))
    args = (x, dt, A, B, C, jnp.ones((h,), jnp.float32))
    y = jax.jit(functools.partial(ssd_scan, chunk=256))(*args)
    y_ref = jax.jit(ref.recurrence)(*[a.astype(jnp.float32) for a in args])
    assert rel(y, y_ref) < 4e-3


def test_causal_conv1d_is_a_depthwise_causal_convolution():
    r = np.random.default_rng(0)
    x = r.normal(size=(2, 9, 3, 5)).astype(np.float32)
    w = r.normal(size=(4, 3, 5)).astype(np.float32)
    b = r.normal(size=(3, 5)).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(9):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += w[k] * x[:, t - 3 + k]
    want += b
    np.testing.assert_allclose(causal_conv1d(jnp.asarray(x), jnp.asarray(w),
                                             jnp.asarray(b)), want, atol=1e-5)


def test_gated_rmsnorm_normalises_the_gated_product_over_all_channels():
    r = np.random.default_rng(0)
    y, z = r.normal(size=(2, 2, 5, 3, 4)).astype(np.float32)
    w = r.normal(size=(3, 4)).astype(np.float32)
    g = y * (z / (1 + np.exp(-z)))
    want = g / np.sqrt((g ** 2).mean(axis=(-2, -1), keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(
        gated_rmsnorm(jnp.asarray(y), jnp.asarray(z), jnp.asarray(w), 1e-5),
        want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("groups", [2, 4])
def test_gated_rmsnorm_by_groups_is_a_reshape_and_a_norm(groups):
    """Mamba-2's and NemotronH's gated norm: the mean square over each
    group's channels apart (4 heads of 6 in 2 groups of 12, in 4 of 6), the
    gate first, one gain over all channels."""
    r = np.random.default_rng(groups)
    y, z = r.normal(size=(2, 2, 5, 4, 6)).astype(np.float32)
    w = r.normal(size=(4, 6)).astype(np.float32)
    g = (y * (z / (1 + np.exp(-z)))).reshape(2, 5, groups, -1)
    want = (g / np.sqrt((g ** 2).mean(axis=-1, keepdims=True) + 1e-5)
            ).reshape(y.shape) * w
    got = jax.jit(gated_rmsnorm, static_argnums=(3, 4))(y, z, w, 1e-5, groups)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # and no group's statistics reach another's channels
    y2 = y.copy()
    y2[..., 0, :] *= 7.0
    other = jax.jit(gated_rmsnorm, static_argnums=(3, 4))(y2, z, w, 1e-5,
                                                           groups)
    np.testing.assert_array_equal(np.asarray(other)[..., 4 // groups:, :],
                                  np.asarray(got)[..., 4 // groups:, :])
    with pytest.raises(ValueError, match="norm groups"):
        gated_rmsnorm(y, z, w, 1e-5, 3)


def test_gated_rmsnorm_at_one_group_is_todays_program_bit_for_bit():
    """``groups=1`` (the hybrid's: GraniteMoeHybrid's gated norm has no
    groups whatever ``n_groups``) traces to the expression the function was
    before it took groups — the same jaxpr — and so gives the same bits."""
    r = np.random.default_rng(1)
    y, z = r.normal(size=(2, 2, 5, 3, 4)).astype(np.float32)
    w = r.normal(size=(3, 4)).astype(np.float32)

    def before(y, z, weight, eps):
        g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        axes = tuple(range(g.ndim - weight.ndim, g.ndim))
        var = jnp.mean(jnp.square(g), axis=axes, keepdims=True)
        out = g * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
        return out.astype(y.dtype)

    now = jax.make_jaxpr(lambda y, z, w: gated_rmsnorm(y, z, w, 1e-5))(y, z, w)
    then = jax.make_jaxpr(lambda y, z, w: before(y, z, w, 1e-5))(y, z, w)
    assert str(now) == str(then)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda y, z, w: gated_rmsnorm(y, z, w, 1e-5, 1))(
            y, z, w)),
        np.asarray(jax.jit(lambda y, z, w: before(y, z, w, 1e-5))(y, z, w)))


def test_the_hybrids_gated_norm_has_no_groups_at_two_bc_groups():
    """The hybrid's test description has two B/C groups and its gated norm
    still takes ONE mean square over all inner channels (``grouped_norm``
    off: the published GraniteMoeHybrid code); NemotronH's description turns
    it on."""
    from easydl_tpu.models.nemotron_h import describe as nemotron

    hybrid = describe(**TEST)
    assert (hybrid.ssm.n_groups, hybrid.ssm.grouped_norm,
            hybrid.ssm.conv_bias_zero) == (2, False, False)
    mine = nemotron(size="test").ssm
    assert (mine.grouped_norm, mine.conv_bias_zero) == (True, True)


def test_scan_flop_count_by_hand():
    # chunk 256, 64 heads of 64, one group of 128: 2*256*128 + 2*256*64*64
    # + 4*64*128*64
    assert ssd_flops_per_token(64, 64, 128, 1, 256) \
        == 65_536 + 2_097_152 + 2_097_152
    # eight groups, chunk 128 (Nemotron 3 Nano): the scores once a GROUP —
    # 2*128*128*8 + 2*128*64*64 + 4*64*128*64
    assert ssd_flops_per_token(64, 64, 128, 8, 128) \
        == 262_144 + 1_048_576 + 2_097_152


# ------------------------------------------------- grouped-query attention
def _qkv(heads, kv_heads, seq=128, dtype=jnp.float32):
    r = np.random.default_rng(0)
    mk = lambda h: jnp.asarray(r.normal(size=(2, seq, h, 32)), dtype)  # noqa: E731
    return mk(heads), mk(kv_heads), mk(kv_heads)


@pytest.mark.parametrize("impl", ["reference", "flash"])
@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (8, 2), (4, 1)])
def test_grouped_query_attention_reads_head_h_over_r(monkeypatch, impl, heads,
                                                     kv_heads):
    """Both paths (the kernel in interpret mode) against attention on
    explicitly repeated key/value heads, forward and gradients; the shared
    heads' gradients are the sums over their query heads."""
    monkeypatch.setattr(attention_module, "flash_attention",
                        functools.partial(flash_attention, interpret=True))
    q, k, v = _qkv(heads, kv_heads)
    rep = heads // kv_heads

    def grouped(q, k, v):
        return attention_module.multihead_attention(
            q, k, v, causal=True, scale=0.2, impl=impl)

    def repeated(q, k, v):
        return attention_module._reference_attention(
            q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
            causal=True, scale=0.2)

    weights = np.random.default_rng(1).normal(size=q.shape).astype(np.float32)

    def weighed(out):
        return (out * weights).sum()

    out, got = out_and_grads(grouped, weighed)(q, k, v)
    out_want, want = out_and_grads(repeated, weighed)(q, k, v)
    np.testing.assert_allclose(out, out_want, atol=2e-5)
    for g, w in zip(got, want):
        assert g.shape == w.shape and rel(g, w) < 5e-4


def test_heads_that_do_not_group_are_refused():
    q, k, v = _qkv(4, 3)
    with pytest.raises(ValueError, match="query heads"):
        attention_module.multihead_attention(q, k, v, impl="reference")


def test_per_shard_wrap_splits_heads_only_where_both_counts_divide(
        monkeypatch, eight_devices):
    """tp=4 over 8 query heads and 2 key/value heads: the query's divide,
    the shared ones do not, so every shard computes all heads; under tp=2
    both divide. Either way the result is the one-device one."""
    monkeypatch.setattr(attention_module, "flash_attention",
                        functools.partial(flash_attention, interpret=True))
    q, k, v = _qkv(8, 2)
    want = jax.jit(lambda q, k, v: attention_module._reference_attention(
        q, jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2), causal=True,
        scale=0.2))(q, k, v)
    for spec in (MeshSpec(dp=2, tp=4), MeshSpec(dp=2, tp=2)):
        mesh = build_mesh(spec, devices=eight_devices[:spec.size])
        with jax.set_mesh(mesh):
            got = jax.jit(functools.partial(
                attention_module.multihead_attention, causal=True, scale=0.2,
                impl="flash"))(q, k, v)
        np.testing.assert_allclose(got, want, atol=2e-5)


# ------------------------------------------------------ the head, by shape
@pytest.mark.parametrize("scale", [1.0, 0.125])
def test_fused_head_with_logit_scale_equals_full_logits(scale):
    r = np.random.default_rng(0)
    hidden = jnp.asarray(r.normal(size=(2, 40, 16)), jnp.float32)
    head = jnp.asarray(r.normal(size=(64, 16)), jnp.float32)
    targets = jnp.asarray(r.integers(0, 64, (2, 40)), jnp.int32)

    def full(hidden, head):
        return gpt_module.lm_loss(
            jnp.einsum("bsd,vd->bsv", hidden, head) * scale, targets)[0]

    def fused(hidden, head):
        return fused_softmax_xent(hidden, head, targets, chunk_size=16,
                                  logit_scale=scale)[0]

    with jax.default_matmul_precision("highest"):
        (got, g_got), (want, g_want) = (
            jax.jit(jax.value_and_grad(loss, (0, 1)))(hidden, head)
            for loss in (fused, full))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, w in zip(g_got, g_want):
        assert rel(g, w) < 1e-5


@pytest.mark.parametrize("shape,mesh,fused", [
    ((8, 1024, 50304), None, False),      # gpt2-medium's microbatch: 1.5 GiB
    ((2, 4096, 100352), None, True),      # the hybrid's: 3.1 GiB
    ((16, 1024, 50304), "fsdp=4", False),  # gpt2-xl: 4 rows a chip
    ((16, 1024, 50304), None, True),      # the same rows on one device
    ((2, 1024, 100352), None, False),     # the check's gradient prefix
])
def test_head_is_chosen_by_one_devices_share_of_the_logits(
        eight_devices, shape, mesh, fused):
    if mesh is None:
        assert gpt_module.fused_head_by_shape(*shape) is fused
        return
    spec = MeshSpec.parse(mesh)
    with jax.set_mesh(build_mesh(spec, devices=eight_devices[:spec.size])):
        assert gpt_module.fused_head_by_shape(*shape) is fused


@pytest.fixture(scope="module")
def hybrid_test():
    """``(bundle, seeded parameters)`` of the hybrid at ``TEST``, float32:
    built once for the tests that read them."""
    bundle = get_model("granite_hybrid", **TEST)
    return bundle, unbox(jax.jit(bundle.init_fn)(jax.random.PRNGKey(0)))


def test_hybrid_loss_is_the_same_through_either_head(fused_head, hybrid_test):
    bundle, params = hybrid_test
    tokens = np.random.default_rng(0).integers(0, 256, (2, 33), np.int32)
    batch = {"inputs": jnp.asarray(tokens[:, :-1]),
             "targets": jnp.asarray(tokens[:, 1:])}

    def f(params):
        # a program of its own a call: the head is chosen when it is traced
        return jax.jit(jax.value_and_grad(
            lambda p: bundle.loss_fn(p, batch, None)[0]))(params)

    full, g_full = f(params)
    fused_head()
    fused, g_fused = f(params)
    assert float(fused) == pytest.approx(float(full), rel=1e-5)
    for key, g in flatten_dict(g_fused).items():
        assert rel(g, flatten_dict(g_full)[key]) < 1e-4, key


# ---------------------------------------- the program against the reference
def _config(dtype):
    with open(os.path.join(BENCH, "configs", "granite-test.json")) as f:
        config = copy.deepcopy(json.load(f))
    config["kwargs"]["dtype"] = dtype
    return config


def _program(dtype, compute_dtype, seed=0):
    """``(config, bundle, trainer)`` as the harness's ``check`` takes them."""
    config = _config(dtype)
    bundle = get_model(config["factory"], **config["kwargs"])
    trainer = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(1e-3),
        config=TrainConfig(global_batch=2, compute_dtype=compute_dtype,
                           seed=seed),
        mesh=build_mesh(MeshSpec.parse("dp=1"), devices=jax.devices()[:1]))
    return config, bundle, trainer


def _check(program, tolerances=None, seed=0):
    """The harness's own comparison of ``program``; ``tolerances`` stand in
    the configuration where ``check`` reads them, in a copy."""
    config, bundle, trainer = program
    if tolerances:
        config = copy.deepcopy(config)
        config["check"]["tolerances"] = tolerances
    return check_module.check(config, bundle, trainer, seed)


TIGHT = {"loss_abs": 2e-5, "hidden_rel_rms": 2e-5, "grad_rel_rms_worst": 2e-4}


@pytest.mark.parametrize("seed", [0, 7])
def test_float32_hybrid_equals_the_reference_to_rounding(seed):
    """Two Mamba-2 layers and one attention layer: loss, final hidden state
    and EVERY gradient leaf (the worst is reported) of the chunked, split
    program against the sequential, fused reference."""
    result = _check(_program("float32", jnp.float32, seed), TIGHT, seed=seed)
    assert result["ok"], result
    assert result["errors"]["grad_rel_rms_all"] > 0  # it did compare


@pytest.fixture(scope="module")
def bf16_program():
    """The bf16 model and its trainer, built once for the two checks below
    (each is a run of ``check`` of its own: the harness decides ``ok``)."""
    return _program("bfloat16", jnp.bfloat16)


def test_bf16_hybrid_sits_inside_the_files_tolerances(bf16_program):
    result = _check(bf16_program)
    assert result["ok"], result
    assert result["errors"]["hidden_rel_rms"] > 1e-3  # bf16 is visible


def test_a_lower_precision_than_stated_fails_the_hybrid_check(bf16_program):
    result = _check(bf16_program, TIGHT)
    assert not result["ok"], result


def test_to_reference_rebuilds_the_published_fused_layout(hybrid_test):
    params = hybrid_test[1]
    plain = check_module.to_reference(
        params, ["mamba", "mamba", "attention"])
    mamba, attn = plain["layers"][1], plain["layers"][2]
    # test size: 4 heads of 16 (inner 64), 2 groups of 16, d_model 64
    assert mamba["in_proj"].shape == (64, 2 * 64 + 2 * 32 + 4)
    assert mamba["conv_w"].shape == (4, 64 + 2 * 32)
    assert mamba["conv_b"].shape == (128,)
    assert mamba["out_proj"].shape == (64, 64)
    assert mamba["w_in"].shape == (64, 256)
    assert attn["wq"].shape == (64, 4, 16) and attn["wk"].shape == (64, 2, 16)
    np.testing.assert_array_equal(
        mamba["in_proj"][:, 64:128],
        params["blocks_0"]["in_x"]["kernel"][1].reshape(64, 64))


# ------------------------------------------------- counts, hints, registry
def test_registry_builds_the_hybrid_like_any_model():
    assert "granite_hybrid" in list_models()
    bundle = get_model("granite_hybrid", **TEST)
    assert bundle.name == "granite-4.0-h-test-3l"
    data = bundle.make_data(4, seed=0)
    batch = next(iter(data))
    assert batch["inputs"].shape == (4, 32)


@pytest.mark.parametrize("layers,millions", [
    (("mamba",) * 5 + ("attention",), 647.259328),   # the benchmark's slice
    (None, 3191.396096),                             # as published
])
def test_hybrid_parameter_count_is_exact(layers, millions):
    cfg = describe(layer_types=layers)
    assert cfg.param_count == round(millions * 1e6)
    bundle = get_model("granite_hybrid", layer_types=layers)
    shapes = jax.eval_shape(bundle.init_fn, jax.random.PRNGKey(0))
    real = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(unbox(shapes)))
    assert real == cfg.param_count == bundle.param_count_hint


def test_hybrid_flops_hint_counts_no_scores_for_mamba_layers():
    cfg = describe(layer_types=("mamba",) * 5 + ("attention",))
    per_token = (6.0 * cfg.param_count + 12.0 * 1 * 2048 * 4096
                 + 3.0 * 5 * ssd_flops_per_token(64, 64, 128, 1, 256))
    bundle = get_model("granite_hybrid",
                       layer_types=("mamba",) * 5 + ("attention",))
    assert bundle.flops_per_sample_hint == per_token * 4096
    # 32,768 tokens a step: the issue's 133 TFLOP
    assert per_token * 32768 == pytest.approx(132.6e12, rel=2e-3)
    # and not 6N + 12 L d s
    assert per_token < 6.0 * cfg.param_count + 12.0 * 6 * 2048 * 4096


def test_models_run_trains_the_hybrid_from_the_command_line(tmp_path):
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run(
        [sys.executable, "-m", "easydl_tpu.models.run", "--model",
         "granite_hybrid", "--steps", "3", "--batch", "4", "--model-arg",
         "size=test", "--model-arg", "seq_len=32", "--model-arg", "vocab=256",
         "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-every", "2"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert (tmp_path / "ckpt").exists()


def test_runs_group_equal_neighbours():
    cfg = describe(size="test",
                   layer_types=("mamba", "mamba", "attention", "mamba"))
    assert cfg.runs == ((("mamba2", "swiglu"), 2), (("attention", "swiglu"), 1),
                        (("mamba2", "swiglu"), 1))
    # the tree's names and shapes: nothing is initialised
    tree = unbox(jax.eval_shape(get_model(
        "granite_hybrid", **TEST,
        layer_types=("mamba", "mamba", "attention", "mamba")
    ).init_fn, jax.random.PRNGKey(0)))
    assert sorted(k for k in tree if k.startswith("blocks")) \
        == ["blocks_0", "blocks_1", "blocks_2"]
    assert "pos_emb" not in tree and "bias" not in tree["blocks_1"]["q"]


@pytest.mark.parametrize("bad", [
    dict(layers=(("attention", "gelu"),), n_layers=2),
    dict(layers=(("mamba2", "swiglu"),), n_layers=1),          # no ssm widths
    dict(layers=(("conv", "gelu"),), n_layers=1),
    dict(position="rotary"),
    dict(n_heads=4, n_kv_heads=3, d_model=64),
])
def test_a_description_that_does_not_hold_together_is_refused(bad):
    from easydl_tpu.models.transformer import TransformerConfig

    with pytest.raises(ValueError):
        TransformerConfig(**bad)


# ------------------------------------------------- the table of mixer families
def test_a_mixers_name_stands_in_the_table_and_nowhere_else():
    """``models/transformer.py`` asks :data:`MIXER_FAMILIES` what a mixer
    is: a scan's name is a string constant of the module once, as the
    table's key (``gmu`` and ``kda`` a second time, the scope each opens,
    which ``gmu_time_pct`` / ``kda_time_pct`` read), and no comparison holds
    one."""
    from easydl_tpu.models import transformer

    tree = ast.parse(open(transformer.__file__).read())
    names = ("mamba2", "mamba1", "gmu", "kda")

    def named(node):
        return [n.value for n in ast.walk(node)
                if isinstance(n, ast.Constant) and n.value in names]

    assert collections.Counter(named(tree)) == {
        "mamba2": 1, "mamba1": 1, "gmu": 2, "kda": 2}
    assert not [(n.lineno, named(n)) for n in ast.walk(tree)
                if isinstance(n, ast.Compare) and named(n)]
    assert [name for name, family in transformer.MIXER_FAMILIES.items()
            if family.written] == ["attention", "mamba2", "mamba1", "gmu",
                                   "kda"]


def test_a_new_mixer_is_one_entry_of_the_table(monkeypatch):
    """A family put into the table — the identity, no parameters, under
    ``ssm`` — and nothing else of the module patched: the description takes
    its name, counts it, and the stack scans two of its layers as a run."""
    from easydl_tpu.models import transformer

    monkeypatch.setitem(
        transformer.MIXER_FAMILIES, "toy", transformer.MixerFamily(
            scope="ssm", norm="ln_ssm",
            apply=lambda block, kind, h, rope, handed: (h, {}),
            params=lambda cfg, kind: 0,
            score_flops=lambda cfg, kind, seq_len: 7.0 * seq_len))
    base = describe(**TEST, layer_types=("attention",))
    cfg = dataclasses.replace(
        base, n_layers=3, layers=(("toy", "swiglu"),) * 2 + base.layers)
    assert cfg.runs == ((("toy", "swiglu"), 2), (("attention", "swiglu"), 1))
    assert cfg.mixer_family("toy") == (transformer.MIXER_FAMILIES["toy"], None)
    # counted: the FFN and the two norms of a layer, no mixer; its scores
    d = cfg.d_model
    assert cfg.layer_params(("toy", "swiglu")) == 3 * d * cfg.d_ff + 2 * d
    assert cfg.train_flops_per_token(32) - base.train_flops_per_token(32) \
        == 2 * (6.0 * cfg.layer_params(("toy", "swiglu")) + 7.0 * 32)
    model = transformer.Transformer(cfg)
    tokens = jnp.zeros((2, 32), jnp.int32)
    params = unbox(model.init(jax.random.PRNGKey(0), tokens)["params"])
    assert sorted(params["blocks_0"]) == ["down", "gate", "ln_mlp", "ln_ssm",
                                          "up"]
    assert params["blocks_0"]["ln_ssm"]["scale"].shape == (2, d)
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) \
        == cfg.param_count
    logits = model.apply({"params": params}, tokens)
    assert logits.shape == (2, 32, 256) and bool(jnp.isfinite(logits).all())
    # the run's ONE traced layer stands under the family's scope
    text = jax.jit(lambda p: model.apply({"params": p}, tokens)).lower(
        params).as_text(debug_info=True)
    assert re.search(r'loc\("[^"]*blocks_0/ssm/ln_ssm', text)
    with pytest.raises(ValueError, match="unknown layer kind"):
        dataclasses.replace(cfg, layers=(("diff", "swiglu"),) * 3)


# ------------------------------------------ sharded, saved, restored, named
@functools.lru_cache(maxsize=None)
def _hybrid_trainer(spec):
    """A trainer of the hybrid at ``TEST`` over ``spec`` and its bundle: one
    a mesh for the file (its step compiles once, whoever steps it)."""
    bundle = get_model("granite_hybrid", **TEST)
    return Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(1e-3),
        config=TrainConfig(global_batch=4, seed=3),
        mesh=build_mesh(spec, devices=jax.devices()[:spec.size])), bundle


@pytest.fixture(scope="module")
def one_device_step(eight_devices):
    trainer, bundle = _hybrid_trainer(MeshSpec())
    batch = next(iter(bundle.make_data(4, seed=5)))
    state, metrics = trainer.train_step(trainer.init_state(), batch)
    return batch, float(metrics["loss"]), jax.device_get(unbox(state.params))


@pytest.mark.parametrize("mesh,sharded", [
    ("fsdp=2", {"blocks_0/in_x/kernel": "fsdp", "tok_emb/embedding": "fsdp"}),
    ("tp=2", {"blocks_0/in_x/kernel": "tp", "blocks_0/conv_x": "tp",
              "blocks_0/A_log": "tp", "blocks_1/k/kernel": "tp",
              "blocks_0/gate/kernel": "tp"}),
    ("fsdp=2,tp=2", {"blocks_0/out/kernel": "tp"}),
])
def test_sharded_hybrid_steps_like_one_device(eight_devices, one_device_step,
                                              mesh, sharded):
    batch, loss, params = one_device_step
    trainer, _ = _hybrid_trainer(MeshSpec.parse(mesh))
    specs = flatten_dict(jax.tree.map(lambda s: str(s.spec),
                                      trainer.state_shardings().params))
    for key, axis in sharded.items():
        assert axis in specs[key], (key, specs[key])
    for whole in ("blocks_0/in_B/kernel", "blocks_0/conv_C"):
        assert "tp" not in specs[whole], (whole, specs[whole])
    state, metrics = trainer.train_step(trainer.init_state(), batch)
    assert float(metrics["loss"]) == pytest.approx(loss, rel=1e-5)
    got = flatten_dict(jax.device_get(unbox(state.params)))
    for key, want in flatten_dict(params).items():
        np.testing.assert_allclose(got[key], want, atol=2e-5, err_msg=key)


def test_hybrid_state_survives_the_checkpoint_manager(tmp_path,
                                                      eight_devices):
    """Saved under fsdp=2, restored under tp=2: every leaf equal, and the
    next step's loss too."""
    t1, bundle = _hybrid_trainer(MeshSpec(fsdp=2))
    batch = next(iter(bundle.make_data(4, seed=5)))
    s1, _ = t1.train_step(t1.init_state(), batch)
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, s1, metadata={"mesh": "fsdp=2"})
    t2, _ = _hybrid_trainer(MeshSpec(tp=2))
    abstract, _, _ = t2._abstract_state()
    s2 = mgr.restore(1, abstract, t2.state_shardings())
    for a, b in zip(jax.tree.leaves(unbox(s1.params)),
                    jax.tree.leaves(unbox(s2.params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _, m1 = t1.train_step(s1, batch)
    _, m2 = t2.train_step(s2, batch)
    assert float(m2["loss"]) == pytest.approx(float(m1["loss"]), rel=1e-5)


@pytest.fixture(scope="module")
def hybrid_step_paths():
    bundle = get_model("granite_hybrid", **TEST, dtype="bfloat16", remat=True,
                       remat_policy="full")
    trainer = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(1e-3),
        config=TrainConfig(global_batch=4, grad_accum=2),
        mesh=build_mesh(MeshSpec(), devices=jax.devices()[:1]))
    tokens = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    text = trainer.step_fn.lower(
        trainer.abstract_state(), {"inputs": tokens, "targets": tokens}
    ).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]*)"', text))


@pytest.mark.parametrize("scope", ["ssm", "ssm/ssd", "ssm/conv1d",
                                   "attention", "ffn"])
@pytest.mark.parametrize("where", ["forward", "backward", "recomputed"])
def test_hybrid_scopes_in_every_pass(hybrid_step_paths, scope, where):
    """The lowered module keeps a scanned block's body as a function of its
    own, with paths relative to the call (tests/test_step_scopes.py joins
    them): the forward's start at the run's name, the backward of a
    rematerialised block under ``checkpoint/`` and its second forward under
    ``checkpoint/rematted_computation/``."""
    run = "blocks_1" if scope == "attention" else "blocks_0"
    own = [p for p in hybrid_step_paths
           if re.search(rf"(^|/){run}/{scope}(/|$)", p)]
    if scope == "ffn":
        own = [p for p in hybrid_step_paths if re.search(r"/ffn(/|$)", p)]
    assert own, scope
    prefix = {"forward": r"^blocks_\d/",
              "backward": r"^checkpoint/blocks_\d/",
              "recomputed": r"^checkpoint/rematted_computation/blocks_\d/"}
    assert any(re.search(prefix[where], p) for p in own), own[:8]
