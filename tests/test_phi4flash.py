"""Phi-4-mini-flash-reasoning (SambaY) on the CPU at its ``test`` preset —
widths a sixteenth, the same six kinds of layer: the stack against
``benchmark/lib/reference_phi4flash`` (loss, every layer's state, the parts on
equal inputs, every gradient leaf), the eight wrong programs the check has to
refuse, what a layer hands to the layers behind it and the gradients that
come back that way, the construction's refusals by name, the one-call
differential attention against four plain attention calls, and the
vocabulary's slices."""

import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import normal

from easydl_tpu.core import sharding as shd
from easydl_tpu.core import train_loop
from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.core.train_loop import TrainConfig, Trainer
from easydl_tpu.models import phi4flash, transformer
from easydl_tpu.models.registry import get_model
from easydl_tpu.models.transformer import (AttentionKind, Mamba1Config,
                                           TransformerConfig)
from easydl_tpu.ops.attention import _reference_attention

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
HELD = [0, 1, 16, 17, 18, 19]


def _bench_lib(name):
    """A module of ``benchmark/lib`` (the package is not on tier-1's path)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(f"lib.{name}")


ref = _bench_lib("reference_phi4flash")
check_module = _bench_lib("check_phi4flash")
SEED = 2147483659
#: the float32 program against the float32 reference
LIMITS = {
    "loss_abs": 2e-5, "state_rel_rms_layer_0": 1e-5,
    "state_rel_rms_layer_1": 1e-5, "state_rel_rms_layer_2": 1e-5,
    "state_rel_rms_layer_3": 1e-5, "state_rel_rms_layer_4": 1e-5,
    "state_rel_rms_layer_5": 1e-5, "state_rel_rms_final": 1e-5,
    "token_rel_max": 5e-5, "logits_token_rel_max": 5e-5,
    "scan_operands_token_rel_max": 1e-5, "scan_token_rel_max": 1e-5,
    "memory_abs": 0.0, "cross_kv_abs": 0.0,
    "diff_before_norm_token_rel_max": 1e-5, "diff_out_token_rel_max": 1e-5,
    "diff_out_rel_rms": 1e-5,
    "grad_rel_rms_worst": 5e-4, "grad_rel_rms_all": 1e-4}


def _config(name="phi4flash-test"):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def _float32(config):
    config["kwargs"] = dict(config["kwargs"], dtype="float32")
    bundle = get_model(config["factory"], **config["kwargs"])
    trainer = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(1e-3),
        config=TrainConfig(global_batch=2, compute_dtype=jnp.float32),
        mesh=build_mesh(MeshSpec(), devices=jax.devices()[:1]))
    return config, bundle, trainer


# --------------------------------------------------------- the whole model
@pytest.fixture(scope="module")
def float32_check():
    """``lib/check_phi4flash.check`` at the test size with float32 compute."""
    return check_module.check(*_float32(_config()), seed=SEED)


@pytest.mark.parametrize("what", sorted(LIMITS))
def test_program_against_reference_phi4flash(float32_check, what):
    """Loss, every layer's state, the logits, the scan and its operands, the
    memory as the unit receives it, a differential head before and after its
    norm, the cross layer's keys and values, every gradient leaf."""
    assert float32_check["errors"][what] <= LIMITS[what], \
        float32_check["errors"]


def test_the_check_reports_the_static_counters(float32_check):
    assert float32_check["counters"] == {
        "kv_readers": 1.0, "memory_readers": 1.0, "sscan_chunks": 1.0,
        "sscan_state_bytes_kept": 2 * 1 * 320 * 16 * 4.0}
    # the units of the gradient's comparison: embedding and final norm; two
    # Mamba-1 layers with leaves of 15 each, the unit's 8, and three
    # attention layers whose 15 leaves are 11 units (the four lambda vectors
    # and the inner gain are one: ``check_phi4flash._units``)
    assert float32_check["errors"]["grad_leaves"] == 3 + 2 * 15 + 8 + 3 * 11


def test_every_gradient_leaf_was_compared():
    config = _config()
    params = shd.unbox(jax.jit(get_model(
        "phi4flash", **config["kwargs"]).init_fn)(jax.random.PRNGKey(0)))
    mapped = check_module.to_reference(
        params, phi4flash.describe(**config["kwargs"]), ref.hyper(config))
    assert sum(x.size for x in jax.tree.leaves(mapped)) == sum(
        x.size for x in jax.tree.leaves(params))


# ------------------------------------------------------ the wrong programs
def _float8_copy(monkeypatch):
    """(1) the bf16 copy rounded to float8_e4m3's three mantissa bits (an
    explicit ``reduce_precision`` at bfloat16's range: a float32 -> float8
    -> float32 pair of converts came back unrounded from the TPU
    compiler)."""
    cast = train_loop.cast_floating
    monkeypatch.setattr(train_loop, "cast_floating", lambda tree, dtype: cast(
        jax.tree.map(lambda a: jax.lax.reduce_precision(a, 8, 3)
                     if jnp.issubdtype(a.dtype, jnp.floating) else a, tree),
        dtype))


def _bf16_state(monkeypatch):
    """(2) the scan's state carried in bfloat16: the chunked walk of
    ``ops/selective_scan.py`` with the state rounded after every position."""
    def walk(x, dt, A, B, C, D):
        f32 = jnp.float32
        batch, seq = x.shape[:2]
        q = min(128, seq)

        def step(h, at):
            x_t, dt_t, b_t, c_t = at
            h = jnp.exp(dt_t[..., None] * A) * h \
                + (dt_t * x_t)[..., None] * b_t[:, None, :]
            h = jax.lax.reduce_precision(h, 8, 7)
            return h, jnp.sum(h * c_t[:, None, :], -1)

        def cut(a):  # [batch, seq, w] -> [chunks, q, batch, w]
            return jnp.moveaxis(a.astype(f32).reshape(batch, seq, -1), 1,
                                0).reshape(seq // q, q, batch, -1)

        x32 = x.astype(f32).reshape(batch, seq, -1)
        _, y = jax.lax.scan(
            jax.checkpoint(lambda h, at: jax.lax.scan(step, h, at)),
            jnp.zeros((batch, *A.shape), f32),
            tuple(cut(a) for a in (x, dt, B, C)))
        y = jnp.moveaxis(y.reshape(seq, batch, -1), 0, 1) + D * x32
        return y.astype(x.dtype).reshape(x.shape)
    monkeypatch.setattr(transformer, "selective_scan", walk)


def _lambda_by_position(monkeypatch):
    """(3) ``lambda_init`` from the held position (0..5), not the published
    index."""
    published = phi4flash.lambda_init
    monkeypatch.setattr(phi4flash, "lambda_init",
                        lambda i: published(HELD.index(i)))


def _gated_memory(monkeypatch):
    """(4) the unit fed ``y * silu(z)`` in place of ``y``."""
    mamba1 = transformer._mamba1

    def gated(block, u):
        out, y = mamba1(block, u)
        if block.is_initializing():
            return out, y
        kernel = block.get_variable("params", "in_z")["kernel"]
        kernel = jnp.asarray(getattr(kernel, "unbox", lambda: kernel)(),
                             u.dtype)
        return out, y * jax.nn.silu(jnp.einsum("bsd,dhp->bshp", u, kernel))
    monkeypatch.setattr(transformer, "_mamba1", gated)


def _other_kv(monkeypatch):
    """(5) the cross layer given other keys and values than layer 17's."""
    attend = transformer._diff_attention
    monkeypatch.setattr(
        transformer, "_diff_attention",
        lambda block, kind, h, kv=None: attend(
            block, kind, h, kv and tuple(jnp.roll(a, 1, 1) for a in kv)))


def _window_off_by_one(monkeypatch):
    """(6) the window one key short."""
    for size in phi4flash.SIZES.values():
        monkeypatch.setitem(size, "sliding_window",
                            size["sliding_window"] - 1)


def _halves_swapped(monkeypatch):
    """(7) the pair's value halves swapped."""
    attend = transformer.multihead_attention

    def swapped(q, k, v, **kwargs):
        d = q.shape[-1]
        return attend(q, k, jnp.concatenate([v[..., d:], v[..., :d]], -1),
                      **kwargs)
    monkeypatch.setattr(transformer, "multihead_attention", swapped)


def _dlambda_flipped(monkeypatch):
    """(8) a wrong BACKWARD and a right forward: lambda's value as it is,
    its gradient's sign turned — what the four lambda vectors receive is
    minus what they should."""
    right = transformer._diff_lambda

    def flipped(vector, init):
        lam = right(vector, init)
        return 2.0 * jax.lax.stop_gradient(lam) - lam
    monkeypatch.setattr(transformer, "_diff_lambda", flipped)


#: name -> the patch that makes the program wrong (the chip's one-off run of
#: the real cell's check applies the same eight)
WRONG = {"float8_copy": _float8_copy, "bf16_state": _bf16_state,
         "lambda_by_position": _lambda_by_position,
         "gated_memory": _gated_memory, "other_kv": _other_kv,
         "window_off_by_one": _window_off_by_one,
         "halves_swapped": _halves_swapped,
         "dlambda_flipped": _dlambda_flipped}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_a_wrong_program_is_refused(monkeypatch, wrong):
    WRONG[wrong](monkeypatch)
    config = _config()
    config["check"] = dict(config["check"], tolerances=dict(LIMITS),
                           sequences=1)
    found = check_module.check(*_float32(config), seed=SEED)
    over = [k for k, limit in LIMITS.items() if found["errors"][k] > limit]
    assert not found["ok"] and over, found["errors"]
    if wrong == "dlambda_flipped":
        # the forward is the right program's: the gradient's limits alone
        # refuse it, and the worst unit is an attention layer's `diff` (the
        # four lambda vectors and the inner gain: `check_phi4flash._units`).
        # It reads 0.16 here, where the vectors' gradient is 0.08 of the
        # gain's; at the published widths it is 0.2 to 13 times the gain's
        # and this program reads 1.0 to 1.9 in every attention layer's unit,
        # against the real cell's limit of 0.3 (on the chip, PR 53)
        assert set(over) == {"grad_rel_rms_worst", "grad_rel_rms_all"}, over
        assert found["errors"]["grad_worst_leaf"].endswith("['diff']")
        assert found["errors"]["grad_rel_rms_worst"] > 0.1, found["errors"]


# ----------------------------------- what a layer hands to the layers behind
def _model(layer_ids=HELD, seq=32, vocab=64):
    cfg = phi4flash.describe(size="test", seq_len=seq, vocab=vocab,
                             layer_ids=layer_ids)
    model = transformer.Transformer(cfg)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, vocab, (2, seq)), jnp.int32)
    params = shd.unbox(jax.jit(model.init)(jax.random.PRNGKey(1),
                                           tokens)["params"])
    return cfg, model, params, tokens


def test_handoffs_of_the_published_stack():
    cfg = phi4flash.describe(size="test", seq_len=32, vocab=64)
    assert len(cfg.runs) == 32 and cfg.readers("kv") == 7 \
        and cfg.readers("memory") == 7
    gives = [given for _, given in cfg.handoffs]
    assert [i for i, g in enumerate(gives) if g] == [16, 17]
    assert gives[16] == ("memory",) and gives[17] == ("kv",)
    carried = [names for names, _ in cfg.handoffs]
    assert carried[17] == ("memory",) and carried[18] == ("memory", "kv")
    assert carried[30] == ("memory", "kv") and carried[31] == ("kv",)
    assert not any(carried[:17])
    held = phi4flash.describe(size="test", seq_len=32, vocab=64,
                              layer_ids=HELD)
    assert held.handoffs == (((), ()), ((), ()), ((), ("memory",)),
                             (("memory",), ("kv",)),
                             (("memory", "kv"), ()), (("kv",), ()))
    kinds = dict(held.attention_kinds)
    assert kinds["window_1"].window == 8 and kinds["full_17"].kv == "gives" \
        and kinds["cross_19"].kv == "takes"
    assert kinds["cross_19"].diff == pytest.approx(
        0.8 - 0.6 * np.exp(-0.3 * 19))


@pytest.mark.parametrize("giver,taker,leaves", [
    ("blocks_3", "blocks_5", ("k", "v")),      # layer 17's map <- layer 19
    ("blocks_2", "blocks_4", ("in_x", "A_log", "D", "conv_x")),  # 16 <- 18
])
def test_a_takers_gradient_reaches_the_giver(giver, taker, leaves):
    """With the taker's output map zeroed the giver's leaves lose exactly
    the part of their gradient that came back through the taker: the two
    differ, and the taker's part is what the carry brought."""
    cfg, model, params, tokens = _model()

    def loss(params):
        return jnp.sum(model.apply({"params": params}, tokens,
                                   return_hidden=True) ** 2)

    whole = jax.jit(jax.grad(loss))(params)
    cut = jax.tree.map(lambda a: a, params)
    cut[taker] = dict(cut[taker], out=jax.tree.map(jnp.zeros_like,
                                                   cut[taker]["out"]))
    without = jax.jit(jax.grad(loss))(cut)
    for leaf in leaves:
        a, b = (jax.tree.leaves(g[giver][leaf])[0] for g in (whole, without))
        assert float(jnp.linalg.norm(a - b)) > 1e-3 * float(
            jnp.linalg.norm(a)), leaf
    # a layer in front of both givers feels the taker too, through them
    a, b = whole["blocks_0"]["in_x"]["kernel"], \
        without["blocks_0"]["in_x"]["kernel"]
    assert float(jnp.linalg.norm(a - b)) > 0


@pytest.mark.parametrize("layer_ids,said", [
    ([1, 18], "takes 'memory' and no layer in front of it gives it"),
    ([0, 1, 16, 19], "takes 'kv' and no layer in front of it gives it"),
    ([18, 19], "layer 0 ('gmu') takes 'memory'"),
])
def test_a_taker_without_a_giver_is_refused_by_name(layer_ids, said):
    with pytest.raises(ValueError, match=said.replace("(", r"\(").replace(
            ")", r"\)")):
        phi4flash.describe(size="test", seq_len=32, vocab=64,
                           layer_ids=layer_ids)


@pytest.mark.parametrize("kind,said", [
    (dict(diff=0.5, kv="takes", window=4), "a taker has no window"),
    (dict(kv="gives"), "kv or bias stand on a diff kind alone"),
    (dict(diff=0.5, kv="borrows"), "kv is '', 'gives' or 'takes'"),
    (dict(diff=0.5, qk_norm=True), "no rotary scheme, gate, q/k norm"),
])
def test_a_differential_kind_is_refused_by_name(kind, said):
    with pytest.raises(ValueError, match=said):
        TransformerConfig(n_layers=1, n_heads=4, d_model=64, position="none",
                          layers=(("a", "swiglu"),),
                          attention_kinds=(("a", AttentionKind(**kind)),))


def test_refusals_of_layer_ids_and_missing_widths():
    with pytest.raises(ValueError, match="ascending published indices"):
        phi4flash.describe(size="test", layer_ids=[1, 0])
    with pytest.raises(ValueError, match="a gmu layer needs mamba1="):
        TransformerConfig(n_layers=1, layers=(("gmu", "swiglu"),))
    with pytest.raises(NotImplementedError, match="reads an earlier layer"):
        TransformerConfig(
            n_layers=2, loops=2, position="none", n_heads=4, d_model=64,
            layers=(("mamba1", "swiglu"), ("gmu", "swiglu")),
            mamba1=Mamba1Config(d_inner=128, dt_rank=4, view=16))


def test_it_trains_through_the_trainer():
    bundle = get_model("phi4flash", size="test", seq_len=32, vocab=64,
                       layer_ids=HELD, remat=True)
    trainer = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(3e-3), config=TrainConfig(global_batch=4),
        mesh=build_mesh(MeshSpec(), devices=jax.devices()[:1]))
    state = trainer.init_state()
    batch = next(iter(bundle.make_data(4, seed=0)))
    losses = []
    for _ in range(6):
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.05, losses
    assert float(metrics["kv_readers"]) == 1.0 \
        and float(metrics["memory_readers"]) == 1.0 \
        and float(metrics["sscan_chunks"]) == 1.0


# ------------------------------------------------- differential attention
@pytest.mark.parametrize("window", [None, 5])
def test_one_call_against_four_plain_calls(window):
    """The stack's ONE call of score heads ``d`` deep against values ``2 d``
    wide is the published form's four plain calls of ``d`` / ``d``: ``attn(q1,
    k1, v1) | attn(q1, k1, v2)`` less lambda times ``attn(q2, k2, v1) |
    attn(q2, k2, v2)``."""
    seq, heads, groups, d = 16, 8, 4, 8
    q, k, v = normal(5, (2, seq, heads, d), (2, seq, groups, d),
                     (2, seq, groups, d))
    lam, gain = 0.37, jnp.ones((2 * d,))
    hp = {"eps": 1e-5}
    before, _ = jax.vmap(lambda q, k, v: ref.diff_heads(
        q, k, v, lam, gain, 3, hp, window))(q, k, v)

    def plain(q, k, v):  # [B, S, pairs, d] each
        return _reference_attention(q, k, v, causal=True, scale=d ** -0.5,
                                    window=window)

    per = heads // groups
    pairs = np.arange(heads // 2)
    q1, q2 = q[:, :, 0::2], q[:, :, 1::2]
    k1, k2 = (k[:, :, 2 * (pairs // per) + c] for c in (0, 1))
    v1, v2 = (v[:, :, 2 * (pairs // per) + c] for c in (0, 1))
    four = jnp.concatenate([plain(q1, k1, v1), plain(q1, k1, v2)], -1) \
        - lam * jnp.concatenate([plain(q2, k2, v1), plain(q2, k2, v2)], -1)
    np.testing.assert_allclose(before, four, atol=2e-6)


@pytest.mark.parametrize("seq,window,block", [
    (512, 96, 128),    # the band path beside a neighbour of 128
    (1024, None, 128),  # 64 block pairs a head
    (256, None, 64),    # 16
], ids=["band", "64-pairs", "16-pairs"])
def test_the_kernels_at_scores_half_the_values_width(seq, window, block):
    """Score heads ``d`` deep against values ``2 d`` wide through the flash
    kernels, interpreted: the result and the three gradients against the
    XLA reference path — the band path's v, O and dO blocks twice as wide
    as q's and k's since this PR."""
    from easydl_tpu.ops.flash_attention import flash_attention

    q, k, v, w = normal(9, (1, seq, 4, 16), (1, seq, 4, 16), (1, seq, 4, 32),
                        (1, seq, 4, 32))

    def kernels(q, k, v):
        return jnp.sum(w * flash_attention(
            q, k, v, causal=True, window=window, interpret=True,
            block_q=block, block_k=block))

    def reference(q, k, v):
        return jnp.sum(w * _reference_attention(
            q, k, v, causal=True, scale=0.25, window=window))

    mine = jax.jit(jax.value_and_grad(kernels, (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(reference, (0, 1, 2)))(q, k, v)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


# ------------------------------------------------- the vocabulary's slices
def test_the_slices_side_by_side_are_the_whole_head():
    """Eight slices of the tied embedding: their logits side by side are the
    whole head's, and the loss over a slice is the whole loss restricted to
    it — the logits of the slice's rows under a softmax over those rows."""
    vocab, slices = 64, 8
    cfg, model, params, tokens = _model(vocab=vocab)
    hidden = model.apply({"params": params}, tokens, return_hidden=True)
    whole = model.apply({"params": params}, tokens)
    table = params["tok_emb"]["embedding"]
    rows = vocab // slices
    parts = [hidden @ table[i * rows:(i + 1) * rows].T for i in range(slices)]
    np.testing.assert_allclose(jnp.concatenate(parts, -1), whole, atol=1e-5)
    # a chip's model IS the slice: its own rows, ids and loss over them
    first = get_model("phi4flash", size="test", seq_len=32, vocab=rows,
                      layer_ids=HELD)
    sliced = dict(params, tok_emb={"embedding": table[:rows]})
    ids = tokens % rows
    batch = {"inputs": ids, "targets": jnp.roll(ids, -1, 1)}
    loss, _ = first.loss_fn(sliced, batch, jax.random.PRNGKey(0))
    logits = transformer.Transformer(phi4flash.describe(
        size="test", seq_len=32, vocab=rows, layer_ids=HELD)).apply(
            {"params": sliced}, ids)
    restricted = -jnp.mean(jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1), batch["targets"][..., None], -1))
    assert float(loss) == pytest.approx(float(restricted), abs=1e-5)
