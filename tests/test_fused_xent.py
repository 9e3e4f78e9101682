"""Chunked fused LM loss (ops/fused_xent.py): numerics must match the naive
optax path exactly (same formula, f32 accumulation) and gradients must flow
to both hidden states and the head — this is the lever that removes the
[B,S,V] f32 logits buffer capping bench microbatch/MFU (VERDICT r2 item 6)."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.models import get_model
from easydl_tpu.models import lm as gpt_module
from easydl_tpu.models.lm import lm_loss
from easydl_tpu.ops import fused_xent
from easydl_tpu.ops.fused_xent import chunk_positions, fused_softmax_xent


def naive(hidden, head, targets, ignore_id=-1, logit_scale=1.0):
    logits = (hidden @ head.T).astype(jnp.float32) * logit_scale
    mask = (targets != ignore_id).astype(jnp.float32)
    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.maximum(targets, 0)
    )
    denom = jnp.maximum(mask.sum(), 1.0)
    return (losses * mask).sum() / denom


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seq,chunk", [(64, 16), (60, 16), (8, 128)],
                         ids=["even", "ragged-pad", "chunk>seq"])
def test_matches_naive_loss_and_grads(dtype, seq, chunk):
    rng = np.random.RandomState(0)
    B, D, V = 4, 32, 96
    hidden = jnp.asarray(rng.randn(B, seq, D), jnp.dtype(dtype))
    head = jnp.asarray(rng.randn(V, D) * 0.1, jnp.dtype(dtype))
    targets = jnp.asarray(rng.randint(0, V, (B, seq)), jnp.int32)
    # mask a few positions
    targets = targets.at[:, :3].set(-1)

    # loss and both gradients: one program a side
    (loss_f, denom), g_f = jax.jit(jax.value_and_grad(
        lambda h, w: fused_softmax_xent(h, w, targets, chunk_size=chunk),
        argnums=(0, 1), has_aux=True))(hidden, head)
    loss_n, g_n = jax.jit(jax.value_and_grad(
        lambda h, w: naive(h, w, targets), argnums=(0, 1)))(hidden, head)
    # bf16: the fused op keeps f32 accumulation (preferred_element_type)
    # where the naive bf16 matmul rounds its output to bf16 — the fused
    # result is the more accurate one, so the comparison needs bf16 slack.
    np.testing.assert_allclose(float(loss_f), float(loss_n),
                               rtol=2e-6 if dtype == "float32" else 1e-3)
    assert float(denom) == B * (seq - 3)

    tol = 1e-5 if dtype == "float32" else 2e-2
    for a, b in zip(g_f, g_n):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol)


def test_all_masked_is_finite():
    hidden = jnp.ones((2, 8, 16), jnp.float32)
    head = jnp.ones((32, 16), jnp.float32)
    targets = jnp.full((2, 8), -1, jnp.int32)
    loss, denom = jax.jit(lambda h, w: fused_softmax_xent(
        h, w, targets, chunk_size=4))(hidden, head)
    assert float(loss) == 0.0 and float(denom) == 1.0


def test_gpt_bundle_fused_matches_logits_path(eight_devices, fused_head):
    """End-to-end through the model: the bundle with the fused head (4
    chunks of 16 positions) and with full logits compute the same loss and
    the same gradients on the same params."""
    bundle = get_model("gpt", size="test", seq_len=64, vocab=256)
    rng = jax.random.PRNGKey(0)
    params = jax.jit(bundle.init_fn)(rng)
    batch = next(iter(bundle.make_data(4, seed=3)))

    def loss_metrics_grads():
        # a program of its own a call: the head is chosen when it is traced
        return jax.jit(jax.value_and_grad(
            lambda p: bundle.loss_fn(p, batch, rng), has_aux=True))(params)

    (lp, mp), gp = loss_metrics_grads()
    fused_head(chunk_rows=64)
    assert gpt_module.fused_head_by_shape(4, 64, 256)
    (lf, mf), gf = loss_metrics_grads()
    np.testing.assert_allclose(float(lf), float(lp), rtol=1e-6)
    np.testing.assert_allclose(float(mf["perplexity"]),
                               float(mp["perplexity"]), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def _reject_cases():
    from easydl_tpu.core.train_loop import TrainConfig
    from easydl_tpu.models.lm import lm_bundle
    from easydl_tpu.models.granite_hybrid import describe

    job = {"model": "gpt", "model_kwargs": {"size": "test", "seq_len": 32}}
    return {
        "make_gpt-fused_loss": lambda: get_model(
            "gpt", size="test", fused_loss=True),
        "make_gpt-loss_chunk": lambda: get_model(
            "gpt", size="test", loss_chunk=16),
        "lm_bundle-fused_loss": lambda: lm_bundle(
            describe(size="test"), "x", fused_loss=False),
        "lm_bundle-loss_chunk": lambda: lm_bundle(
            describe(size="test"), "x", loss_chunk=16),
        "TrainConfig-accum_unroll": lambda: TrainConfig(accum_unroll=2),
        # as the worker, the runner and the evaluator build a job's model
        "job-fused_loss": lambda: get_model(
            job["model"], **job["model_kwargs"], fused_loss=True),
        "job-hybrid-loss_chunk": lambda: get_model(
            "granite_hybrid", size="test", loss_chunk=128),
    }


@pytest.mark.parametrize("case", sorted(_reject_cases()))
def test_the_retired_switches_are_rejected_by_name(case):
    """Which head, how many positions a chunk and the accumulation scan's
    unroll are no arguments any more: the head is the shape rule's, the
    chunk ``chunk_positions``'s. A job description that still carries one
    fails where its model is built, with the argument named."""
    with pytest.raises(TypeError, match=case.rsplit("-", 1)[1]):
        _reject_cases()[case]()


@pytest.mark.parametrize("family,described", [
    ("gpt", dict(size="test")),
    ("laguna", dict(size="test", experts_held=(0, 4))),
], ids=["dense", "moe4"])
def test_bf16_bundle_with_the_head_chosen_by_shape_matches_full_logits(
        monkeypatch, fused_head, family, described):
    """Through ``lm_bundle`` as a training cell reaches it: bf16, the shape
    rule picks the one-pass head and the chunk is sized in rows, the rule's
    own — against the same bundle with full logits, loss and every
    parameter's gradient."""
    bundle = get_model(family, seq_len=512, vocab=256, dtype="bfloat16",
                       **described)
    rng = jax.random.PRNGKey(0)
    params = jax.jit(bundle.init_fn)(rng)
    batch = next(iter(bundle.make_data(4, seed=3)))

    def loss_and_grads():
        # a program of its own a call: the head is chosen when it is traced
        return jax.jit(jax.value_and_grad(
            lambda p: bundle.loss_fn(p, batch, rng)[0]))(params)

    want, g_want = loss_and_grads()

    fused_head()
    chunks = []
    monkeypatch.setattr(
        gpt_module, "fused_softmax_xent",
        lambda *a, **k: chunks.append(k.get("chunk_size"))
        or fused_softmax_xent(*a, **k))
    got, g_got = loss_and_grads()
    assert chunks == [None]           # 4 x 512 rows: two chunks of 1,024
    assert chunk_positions(4, 512, 256) == 256
    np.testing.assert_allclose(float(got), float(want), rtol=2e-3)

    def f32(tree):
        return [np.asarray(x, np.float32) for x in jax.tree.leaves(tree)]

    def rms(xs):
        return np.sqrt(sum((x ** 2).sum() for x in xs)
                       / sum(x.size for x in xs))

    g_got, g_want = f32(g_got), f32(g_want)
    whole = rms(g_want)
    assert rms([a - b for a, b in zip(g_got, g_want)]) < 1.5e-2 * whole
    for a, b in zip(g_got, g_want):
        # a leaf whose gradient is rounding alone (the keys' bias: a
        # softmax does not see it) is held by the whole, not by itself
        assert rms([a - b]) < 3e-2 * max(rms([b]), 1e-2 * whole)


def _problem(dtype, B=4, S=48, D=32, V=96, seed=1):
    rng = np.random.RandomState(seed)
    hidden = jnp.asarray(rng.randn(B, S, D), jnp.dtype(dtype))
    head = jnp.asarray(rng.randn(V, D) * 0.3, jnp.dtype(dtype))
    targets = jnp.asarray(rng.randint(0, V, (B, S)), jnp.int32)
    return hidden, head, targets


#: what the one-pass gradients must carry through, one case each: the
#: model's logit scale, an upstream cotangent other than 1, masked rows
GRAD_CASES = {
    "logit_scale": dict(scale=0.125, upstream=1.0, ignore_rows=()),
    "cotangent_3": dict(scale=1.0, upstream=3.0, ignore_rows=()),
    "ignore_rows": dict(scale=1.0, upstream=1.0, ignore_rows=(0, 2)),
    "all_three": dict(scale=0.125, upstream=3.0, ignore_rows=(1,)),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunk", [16, None], ids=["chunk16", "by-rows"])
@pytest.mark.parametrize("case", sorted(GRAD_CASES))
def test_one_pass_gradients_match_naive(case, chunk, dtype):
    scale, upstream, ignore_rows = (GRAD_CASES[case][k] for k in (
        "scale", "upstream", "ignore_rows"))
    hidden, head, targets = _problem(dtype)
    for row in ignore_rows:  # whole sequences, and a ragged prefix
        targets = targets.at[row].set(-1)
    targets = targets.at[3, :5].set(-1)

    def fused(h, w):
        return upstream * fused_softmax_xent(
            h, w, targets, chunk_size=chunk, logit_scale=scale)[0]

    def plain(h, w):
        return upstream * naive(h, w, targets, logit_scale=scale)

    (lf, gf), (ln, gn) = (jax.jit(jax.value_and_grad(f, (0, 1)))(hidden, head)
                          for f in (fused, plain))
    f32 = dtype == "float32"
    np.testing.assert_allclose(float(lf), float(ln),
                               rtol=2e-6 if f32 else 2e-3)
    for a, b in zip(gf, gn):
        assert a.dtype == b.dtype == jnp.dtype(dtype)
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        err = np.sqrt(((a - b) ** 2).mean() / (b ** 2).mean())
        assert err < (1e-5 if f32 else 1.5e-2), err
    for row in ignore_rows:
        assert not np.asarray(gf[0], np.float32)[row].any()


def test_denom_carries_no_gradient():
    hidden, head, targets = _problem("float32")
    targets = targets.at[:, :7].set(-1)

    def f(h, w, weight):
        loss, denom = fused_softmax_xent(h, w, targets, chunk_size=16)
        return loss + weight * denom

    grads = jax.jit(jax.grad(f, (0, 1)))
    g0, g5 = grads(hidden, head, 0.0), grads(hidden, head, 5.0)
    for a, b in zip(g0, g5):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(a).any()
    only_denom = jax.jit(jax.grad(lambda h: fused_softmax_xent(
        h, head, targets, chunk_size=16)[1]))(hidden)
    assert not np.asarray(only_denom).any()


@pytest.mark.parametrize("chunk", [4, None], ids=["chunk4", "by-rows"])
def test_all_masked_gives_zero_gradients_and_no_nan(chunk):
    hidden, head, _ = _problem("float32", S=8)
    targets = jnp.full(hidden.shape[:2], -1, jnp.int32)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda h, w: fused_softmax_xent(h, w, targets, chunk_size=chunk)[0],
        (0, 1)))(hidden, head)
    assert float(loss) == 0.0
    for g in grads:
        assert np.isfinite(np.asarray(g)).all() and not np.asarray(g).any()


@pytest.mark.parametrize("mesh", ["dp=8", "dp=2,fsdp=4", "dp=4,tp=2"])
def test_batch_sharded_equals_one_device(eight_devices, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    hidden, head, targets = _problem("float32", B=8, S=32)
    targets = targets.at[:, :2].set(-1)
    f = jax.jit(jax.value_and_grad(
        lambda h, w, t: fused_softmax_xent(h, w, t, logit_scale=0.5)[0],
        (0, 1)))
    want, g_want = f(hidden, head, targets)
    spec = MeshSpec.parse(mesh)
    built = build_mesh(spec, devices=eight_devices[:spec.size])
    batch = tuple(a for a in ("dp", "fsdp") if a in built.axis_names)
    with jax.set_mesh(built):
        rows = NamedSharding(built, P(batch))
        got, g_got = f(jax.device_put(hidden, rows), head,
                       jax.device_put(targets, rows))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


# ------------------------------------------------ the schedule, from the jaxpr
def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _scan_products(jaxpr):
    """FLOPs of each ``dot_general`` inside the one scan of ``jaxpr``."""
    scan, = [e for e in _equations(jaxpr.jaxpr) if e.primitive.name == "scan"]
    out = []
    for eqn in _equations(scan.params["jaxpr"].jaxpr):
        if eqn.primitive.name == "dot_general":
            (lhs_c, _), _ = eqn.params["dimension_numbers"]
            contracted = np.prod([eqn.invars[0].aval.shape[d] for d in lhs_c])
            out.append(2 * np.prod(eqn.outvars[0].aval.shape) * contracted)
    return scan, out


@pytest.mark.parametrize("chunk", [16, None], ids=["chunk16", "by-rows"])
def test_gradient_program_is_three_products_a_chunk_and_no_remat(chunk):
    hidden, head, targets = _problem("bfloat16", B=4, S=64, D=32, V=96)
    with jax.named_scope("lm_head_loss"):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda h, w: fused_softmax_xent(h, w, targets,
                                            chunk_size=chunk)[0],
            (0, 1)))(hidden, head)
    names = {e.primitive.name for e in _equations(jaxpr.jaxpr)}
    assert not {n for n in names if "checkpoint" in n or "remat" in n}, names
    scan, products = _scan_products(jaxpr)
    positions = chunk or 64
    assert scan.params["length"] == 64 // positions
    # logits, d_hidden, d_head: each the whole [rows, D] x [V, D] product
    assert products == [2 * 4 * positions * 32 * 96] * 3
    # bf16 operands into every product, f32 out of it
    for eqn in _equations(scan.params["jaxpr"].jaxpr):
        if eqn.primitive.name == "dot_general":
            assert {v.aval.dtype for v in eqn.invars} == {jnp.dtype("bfloat16")}
            assert eqn.outvars[0].aval.dtype == jnp.float32
    # the head gradient rides the scan in the head's dtype (never lower),
    # the loss beside it in float32
    carried = [v.aval for v in scan.outvars[:scan.params["num_carry"]]]
    assert [(a.shape, a.dtype) for a in carried] == [
        ((), jnp.float32), ((96, 32), head.dtype)]


@pytest.mark.parametrize("wanted,products", [
    ("loss", ["f32[64,96]"]),                    # evaluation: the logits
    ("d_hidden", ["f32[64,96]", "f32[64,32]"]),  # a frozen head pays none
    ("both", ["f32[64,96]", "f32[64,32]", "f32[96,32]"]),
])
def test_compiled_program_holds_only_the_products_that_are_read(
        wanted, products):
    """Evaluation is the forward rule's loss (one scan body, not two): XLA
    drops the gradient nobody reads, its product and its carry, so the
    loss-only program is one product a chunk and forms no gradient."""
    hidden, head, targets = _problem("bfloat16", B=4, S=64, D=32, V=96)

    def loss(h, w):
        return fused_softmax_xent(h, w, targets, chunk_size=16)[0]

    f = {"loss": loss, "d_hidden": jax.grad(loss, 0),
         "both": jax.grad(loss, (0, 1))}[wanted]
    jaxpr = jax.make_jaxpr(f)(hidden, head)
    names = {e.primitive.name for e in _equations(jaxpr.jaxpr)}
    assert not {n for n in names if "checkpoint" in n or "remat" in n}, names
    text = jax.jit(f).lower(hidden, head).compile().as_text()
    loop, = [line for line in text.split("\n") if " while(" in line]
    # 4 sequences x 16 positions a chunk: [64, 32] x [96, 32]
    assert sorted(re.findall(r"= (f32\[\d+,\d+\])\S* dot\(", text)) == sorted(
        products)
    # the scan stacks d_hidden's chunks only where somebody reads them
    assert ("[4,4,16,32]" in loop.split(" while(")[0]) == (wanted != "loss")


@pytest.mark.parametrize("shape,positions", [
    ((2, 4096, 100352), 512),    # the hybrid's microbatch: 8 chunks of 1,024
    ((8, 1024, 50304), 128),     # gpt2-medium's, were it fused
    ((4, 64, 1024), 64),         # B x S under the target: the whole sequence
    ((1, 4096, 262144), 512),    # 0.5 GiB of logits binds before 1,024 rows
    ((3, 1000, 50304), 334),     # equal chunks, not a ragged last one
    ((2048, 16, 1024), 1),       # one position is the least
])
def test_chunk_is_sized_in_rows_by_shape(shape, positions):
    batch, _, vocab = shape
    assert chunk_positions(*shape) == positions
    if positions > 1:  # never over either limit
        assert batch * positions <= fused_xent.CHUNK_ROWS
        assert 4 * batch * positions * vocab <= fused_xent.CHUNK_LOGITS_BYTES


@pytest.mark.parametrize("mesh,batch,positions", [
    ("fsdp=4", 16, 256),      # 4 sequences a device
    ("dp=2,tp=2", 16, 128),   # tp does not split the batch: 8 a device
    ("dp=8", 16, 512),        # 2 a device
    ("dp=4", 6, 147),         # 6 does not divide: whole, 7 equal chunks
])
def test_chunk_rows_are_one_devices_share(eight_devices, mesh, batch,
                                          positions):
    spec = MeshSpec.parse(mesh)
    with jax.set_mesh(build_mesh(spec, devices=eight_devices[:spec.size])):
        assert chunk_positions(batch, 1024, 50304) == positions
