"""What remat ``full`` keeps (PR 38, PR 58): the candidates of
``ops/remat.py`` — the flash forward's ``out`` and ``lse``, an FFN's first
products — dearest FLOP a byte first while the room lasts, nothing under the
floor, nothing at all where no room is stated; ``dots`` keeps a kept ``out``
beside what it kept.

On the CPU, kernels interpreted; a test-size call is a candidate only with
the floor lowered and has room only where a chooser is open (the
``flash_kept`` fixture of ``conftest.py`` does both; the ``Trainer`` opens
one where ``ops/platform.memory_stats`` states a limit, which a test
injects). What the compiled program holds at the cells' sizes is
``tests/test_tpu_compile.py``'s.
"""

from __future__ import annotations

import functools
import logging
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from envprobe import requires_multiproc_cpu

from easydl_tpu.models.registry import get_model
from easydl_tpu.models.transformer import Transformer, TransformerConfig
from easydl_tpu.ops import attention as attention_module
from easydl_tpu.ops import remat
from easydl_tpu.ops.flash_attention import flash_attention

#: cell's flash call -> ((batch, seq, heads, score size, value size, window),
#: the rule's FLOP a byte rounded (ISSUE 38's table, to its rounding), whether
#: that is at or over the floor). Batch is a microbatch's.
CELLS = {
    "joyai-llm-flash": ((2, 8192, 32, 192, 128, None), 10084, True),
    "zaya1-8b": ((2, 8192, 8, 128, 128, None), 8067, True),
    "laguna-xs.2-full": ((2, 8192, 48, 128, 128, None), 8067, True),
    "ouro-2.6b": ((1, 4096, 16, 128, 128, None), 4034, True),
    "granite-4.0-h-micro": ((2, 4096, 32, 64, 64, None), 3973, True),
    # PR 59 measured this one: kept, Mellum 2's cell gained 1.45% more
    "mellum2-window": ((2, 8192, 32, 128, 128, 1024), 1891, True),
    "laguna-xs.2-window": ((2, 8192, 64, 128, 128, 512), 977, False),
    "gpt2-medium": ((8, 1024, 16, 64, 64, None), 994, False),
    "gpt2-xl": ((4, 1024, 25, 64, 64, None), 994, False),
}


def _results(batch, seq, heads, value, dtype=jnp.bfloat16):
    return (jax.ShapeDtypeStruct((batch, seq, heads * value), dtype),
            jax.ShapeDtypeStruct((batch, heads, seq), jnp.float32))


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_rule_at_the_cells_flash_shapes(cell):
    """What a byte of each cell's flash results costs to make again, which
    side of the floor that is, and what a block then keeps of the call: with
    room both results, without room or under the floor neither — but for the
    ``lse`` rows under ``dots`` — and nothing where no chooser is open."""
    (batch, seq, heads, score, value, window), want, over = CELLS[cell]
    out, lse = _results(batch, seq, heads, value)
    asked = dict(s_k=seq, head_dim=score, causal=True, window=window)
    cost = remat.flash_flop_per_byte(out, lse, **asked)
    assert round(cost) == want
    assert (cost >= remat.FLOOR_FLOP_PER_BYTE) == over
    assert 994 < remat.FLOOR_FLOP_PER_BYTE <= 1891
    held = out.size * 2 + lse.size * 4

    def keeps(policy, room):
        chooser = None if room is None else remat.Chooser(room)
        with remat.choosing(chooser), remat.block(policy) as said:
            kept = remat.flash_keeps(out, lse, **asked)
        assert [value.label for value in said.named] == [
            label for label, named in zip((remat.FLASH_OUT, remat.FLASH_LSE),
                                          kept) if named]
        assert len(said.left) == (policy is not None and not kept[0])
        return kept

    for policy, rows in (("full", False), ("dots", True)):
        assert keeps(policy, held) == (over, over or rows)
        assert keeps(policy, held - 1) == (False, rows)
        assert keeps(policy, None) == (False, rows)
    assert keeps(None, held) == (False, False)  # no remat: no candidate


@pytest.mark.parametrize("s_q,s_k,causal,window", [
    (5, 5, False, None), (5, 7, False, None), (6, 6, True, None),
    (4, 7, True, None), (7, 4, True, None), (9, 9, True, 3),
    (4, 9, True, 2), (8, 8, True, 100),
])
def test_seen_pairs_counts_the_kernels_mask(s_q, s_k, causal, window):
    """Against the mask written out: ``0 <= i + (s_k - s_q) - j < window``."""
    i, j = np.arange(s_q)[:, None], np.arange(s_k)[None, :]
    seen = np.ones((s_q, s_k), bool)
    if causal:
        seen = i + (s_k - s_q) - j >= 0
        if window is not None:
            seen &= i + (s_k - s_q) - j < window
    assert remat.seen_pairs(s_q, s_k, causal, window) == int(seen.sum())


def _forward_calls(jaxpr) -> int:
    """Forward flash kernels (``flash_fwd``, ``mla_fwd``, ``swa_fwd``) in a
    jaxpr, a scan's body counted once: calls a layer of a scanned run."""
    from jax.extend import core

    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += eqn.params["name"].endswith("_fwd")
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                if isinstance(sub, core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, core.Jaxpr):
                    n += _forward_calls(sub)
    return n


def _gpt(**kw):
    cfg = TransformerConfig(
        vocab=64, d_model=128, n_heads=4, n_layers=2, d_ff=64, max_seq=64,
        position="rope", bias=False, attention_impl="flash", **kw)
    model = Transformer(cfg)
    return model.init, lambda p, tokens: (model.apply(
        p, tokens).astype(jnp.float32) ** 2).mean()


def _joyai(**kw):
    """Two sparse layers of JoyAI-LLM's test size: latent attention at head
    sizes 24 / 16 (192 / 128 at an eighth), one scanned run."""
    bundle = get_model("joyai", size="test", seq_len=64, vocab=64,
                       layer_types=["sparse"] * 2, mtp=False,
                       attention_impl="flash", **kw)
    return (lambda key, tokens: bundle.init_fn(key)), \
        lambda p, tokens: bundle.loss_fn(
            p, {"inputs": tokens, "targets": tokens},
            jax.random.PRNGKey(0))[0]


@pytest.fixture
def interpreted(monkeypatch):
    from easydl_tpu.ops import rope

    monkeypatch.setattr(attention_module, "flash_attention", functools.partial(
        flash_attention, interpret=True, block_q=16, block_k=16))
    monkeypatch.setattr(attention_module, "rope_rows", functools.partial(
        rope.rope_rows, interpret=True))


@pytest.mark.parametrize("model", [_gpt, _joyai],
                         ids=["heads-32-32", "heads-24-16"])
def test_a_scanned_run_under_full_runs_the_forward_kernel_once_a_layer(
        interpreted, flash_kept, model):
    """A two-layer scanned stack, differentiated: where the rule picks the
    call the forward kernel stands ONCE in the program (the forward scan's
    body; the backward's reads the kept ``out`` and ``lse``), where it does
    not, twice, as on the parent — and the gradients are those of the stack
    with no remat, to float32 rounding, either way."""
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, 64, (2, 64), np.int32))
    init, _ = model()
    params = nn.unbox(jax.jit(init)(jax.random.PRNGKey(0), tokens))
    if "params" in params and len(params) == 1:
        params = {"params": jax.tree.map(
            lambda p: np.asarray(p) + 0.02 * rng.standard_normal(
                p.shape, np.float32), params["params"])}
    # traced once a program: the trace is read, then lowered and run
    plain = jax.jit(jax.grad(model()[1])).trace(params, tokens)
    assert _forward_calls(plain.jaxpr.jaxpr) == 1  # no remat: made once
    want = plain.lower().compile()(params, tokens)
    loss = model(remat=True, remat_policy="full")[1]
    left = jax.make_jaxpr(jax.grad(loss))(params, tokens).jaxpr
    assert _forward_calls(left) == 2
    flash_kept()
    kept = jax.jit(jax.grad(loss)).trace(params, tokens)
    assert _forward_calls(kept.jaxpr.jaxpr) == 1
    got = kept.lower().compile()(params, tokens)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=2e-5, atol=2e-6 * float(jnp.abs(w).max()) + 1e-12,
            err_msg=jax.tree_util.keystr(path))


def test_dots_keeps_a_picked_out_beside_what_it_kept(interpreted, flash_kept):
    """One rule, two policies: under ``dots`` the forward kernel of a picked
    call runs once a layer too; at an unpicked call it runs twice and ``lse``
    is kept all the same (``tests/test_remat_dots.py``)."""
    tokens = jnp.zeros((2, 64), jnp.int32)
    init, _ = _gpt()
    # traced, never run: the parameters' shapes are enough
    params = jax.eval_shape(init, jax.random.PRNGKey(0), tokens)
    loss = _gpt(remat=True, remat_policy="dots")[1]
    assert _forward_calls(jax.make_jaxpr(jax.grad(loss))(
        params, tokens).jaxpr) == 2
    flash_kept()
    assert _forward_calls(jax.make_jaxpr(jax.grad(loss))(
        params, tokens).jaxpr) == 1


def _bundle(factory, **kw):
    """``factory``'s test preset at 32 positions and 64 tokens, unless ``kw``
    says otherwise."""
    return get_model(factory, seq_len=32, **{"size": "test", "vocab": 64,
                                             **kw})


def _preset(preset):
    """``OFFERED[preset]``'s model as :func:`_gpt` gives its own."""
    def model(**kw):
        factory, more, _ = OFFERED[preset]
        bundle = _bundle(factory, **more, **kw)
        return (lambda key, tokens: bundle.init_fn(key)), \
            lambda p, tokens: bundle.loss_fn(
                p, {"inputs": tokens, "targets": tokens},
                jax.random.PRNGKey(0))[0]
    return model


@pytest.mark.parametrize("model", [
    _gpt, _joyai, "hybrid-mamba2", "phi4flash", "nemotron", "laguna"],
    ids=["heads-32-32", "heads-24-16", "mamba2", "mamba1-diff",
         "mamba2-relu2-shared", "window-shared"])
def test_a_run_in_which_nothing_is_picked_lowers_to_the_parents_text(
        interpreted, monkeypatch, model):
    """``full``'s policy saves its names; a block that holds none of them —
    no chooser is open: a bare ``jax.grad``, the CPU — lowers to the text of
    ``policy=None``, the parent's ``full``: same operations, same private
    functions, as many times. Whatever the block could have offered: flash
    results, an FFN's products, and (PR 59) its attention's rows, its scan
    mixer's input maps, its shared expert's products."""
    if isinstance(model, str):
        model = _preset(model)
    # jax's tracing caches are bounded (2,048 / 4,096 entries, least recently
    # used out first): in a worker that has run a few hundred tests an entry
    # one lowering reads twice can be gone the second time, and that function
    # is then lowered twice — the texts differ by a private function's running
    # number (seen once in three whole runs, PR 54). Both start from empty
    # caches here, as in a process of their own.
    jax.clear_caches()
    tokens = jnp.zeros((2, 64), jnp.int32)
    init, _ = model()
    params = jax.eval_shape(lambda: nn.unbox(init(jax.random.PRNGKey(0),
                                                  tokens)))

    def text():
        loss = model(remat=True, remat_policy="full")[1]
        return jax.jit(jax.grad(loss)).lower(params, tokens).as_text()

    ours = text()
    monkeypatch.setattr(remat, "policy", lambda remat_policy: None)
    assert ours == text()


def test_the_policies_keep_what_their_names_say():
    policies = jax.checkpoint_policies
    x = jnp.ones((4, 8))

    def block(x):
        y = remat.name(jnp.tanh(x), remat.FLASH_OUT)
        z = remat.name(jnp.tanh(y)[:2], remat.PROJECTION)
        w = remat.name(jnp.tanh(z)[:, :4], remat.FFN_IN)
        return jnp.tanh(w @ w.T).sum()

    from tests.test_remat_dots import saved_residuals

    def kept(policy):
        return sorted(shape for shape, _ in saved_residuals(
            jax.checkpoint(block, policy=policy, prevent_cse=False), x))

    assert kept(remat.policy("full")) == [(2, 4), (4, 8)]
    # the FFN's products are no names of dots': it keeps them as products
    assert kept(remat.policy("dots")) == [(2, 2), (2, 8), (4, 8)]
    assert kept(policies.nothing_saveable) == []
    # one object: a policy made anew a call splits jax's caches by call
    assert remat.policy("full") is remat.policy("full")


@pytest.fixture
def said_by_the_block(monkeypatch):
    """The ``remat full:`` / ``train step:`` lines logged during the test."""
    from easydl_tpu.core import train_loop
    from easydl_tpu.models import transformer
    from easydl_tpu.utils import logging as easydl_logging

    monkeypatch.setattr(easydl_logging, "_logged_once", set())
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    for module in (transformer, train_loop):
        monkeypatch.setattr(module.log, "handlers",
                            module.log.handlers + [handler])
    return said


def test_a_full_stack_says_once_what_a_layer_keeps(interpreted, flash_kept,
                                                   said_by_the_block):
    tokens = jnp.zeros((2, 64), jnp.int32)
    init, _ = _gpt()
    # traced, never run: the parameters' shapes are enough
    params = jax.eval_shape(init, jax.random.PRNGKey(0), tokens)
    loss = _gpt(remat=True, remat_policy="full")[1]
    jax.make_jaxpr(jax.grad(loss))(params, tokens)
    jax.make_jaxpr(loss)(params, tokens)
    flash_kept()
    jax.make_jaxpr(jax.grad(loss))(params, tokens)
    said = [message for message in said_by_the_block
            if message.startswith("remat full:")]
    assert len(said) == 2, said
    # out [2, 64, 128] float32 and lse [2, 4, 64] float32; 33 keys a query
    # at 64 + 64 lanes a head over 128 lanes and a row of lse; the FFN's up
    # [2, 64, 64] float32 contracts 128: 2 x 128 FLOP over 4 bytes
    out, lse, up = 2 * 64 * 128 * 4, 2 * 4 * 64 * 4, 2 * 64 * 64 * 4
    cost = round(2 * (64 * 65 // 2) * 2 * 4 * 64 / (out + lse))
    # ... and, the run being a scan of two, the attention's q, k, v and its
    # output map's result [2, 64, 128] float32, which contract 128 as well
    assert "a (attention, gelu) layer at (2, 64, 128), no room stated, " \
           "keeps by name nothing a microbatch" in said[0]
    under = "(under the floor of 1,800)"
    rows = [f"{what} {out / 1e6:.1f} MB at 64 FLOP a byte"
            for what in ("q", "k", "v", "out")]
    assert said[0].endswith("candidates left out: " + "; ".join(
        f"{left} {under}" for left in (
            *rows[:3], f"flash {(out + lse) / 1e6:.1f} MB at {cost:,} FLOP a "
            f"byte", rows[3], f"ffn {up / 1e6:.1f} MB at 64 FLOP a byte")))
    assert f"given {(1 << 60) / 2**30:.3f} GiB of room, keeps by name " \
           + "".join(f"rows {row}, " for row in rows) + \
           f"ffn_in {up / 1e6:.1f} MB at 64 FLOP a byte, " \
           f"flash_out {out / 1e6:.1f} MB at {cost:,} FLOP a byte, " \
           f"flash_lse {lse / 1e6:.1f} MB at {cost:,} FLOP a byte " \
           f"a microbatch" in said[1]  # dearest first
    assert said[1].endswith("candidates left out: none")


# ------------------------------------------------------ the rule and the room
def _candidates(*costs_and_bytes):
    return [remat.Candidate((f"blocks_{i}", "ffn", 0), nbytes, cost)
            for i, (cost, nbytes) in enumerate(costs_and_bytes)]


@pytest.mark.parametrize("room,kept", [
    (0, []), (99, [3]), (100, [2]), (399, [2, 3]), (400, [2, 0]),
    # the 500 bytes at 3,000 do not fit: passed over, and the cheaper 50
    # behind them are still tried
    (450, [2, 0, 3]), (899, [2, 0, 3]), (900, [2, 0, 1]),
    (950, [2, 0, 1, 3]), (1 << 40, [2, 0, 1, 3]),
])
def test_the_rule_ranks_by_flop_a_byte_and_fills_to_the_room(room, kept):
    """Dearest first, each where it still fits; equals in the order of the
    trace. The same whether the candidates were kept as they came (a first
    trace) or by a plan."""
    offered = _candidates((5000, 300), (3000, 500), (9000, 100), (2500, 50))
    first = remat.Chooser(250)  # as they come: 0 does not fit, 2 and 3 do
    assert [first.offer(c) for c in offered] == [False, False, True, True]
    assert first.kept_bytes == 150
    want = first.fill(room)
    assert want == frozenset(offered[i].key for i in kept)
    planned = remat.Chooser(room, want)
    assert [planned.offer(c) for c in offered] == [i in kept for i in range(4)]
    assert planned.kept == want and planned.fill(room) == want
    # a block traced again (the microbatches' scan) gets the answer it got
    assert [planned.offer(c) for c in offered] == [i in kept for i in range(4)]
    assert [c.flop_per_byte for c in planned.ranked()] == [9000, 5000, 3000,
                                                           2500]
    if kept and len(kept) < 4:
        left = next(c for c in planned.ranked() if c.key not in want)
        assert planned.said().endswith(
            f"the first of {4 - len(kept)} left out for want of room: "
            f"{left.key[0]} ffn {left.bytes / 1e6:,.1f} MB at "
            f"{left.flop_per_byte:,} FLOP a byte")


def _phi4flash(**kw):
    """Phi-4-mini-flash's test preset cut as its cell is: the six layers 0,
    1, 16, 17, 18, 19, each a run of one, SwiGLU FFNs (XLA attention on the
    CPU: the FFNs are the only candidates)."""
    return get_model("phi4flash", size="test", seq_len=32, vocab=64,
                     layer_ids=[0, 1, 16, 17, 18, 19], **kw)


def _offered(bundle, chooser, batch=2, seq=32, what=("ffn",)):
    """The candidates of the kinds ``what`` that ``chooser`` sees while the
    gradient of ``bundle``'s loss is traced, and what it kept of them: by run
    for one kind, by (run, kind) for several."""
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    params = jax.eval_shape(lambda: nn.unbox(
        bundle.init_fn(jax.random.PRNGKey(0))))
    with remat.choosing(chooser):
        jax.make_jaxpr(jax.grad(lambda p, tokens: bundle.loss_fn(
            p, {"inputs": tokens, "targets": tokens},
            jax.random.PRNGKey(0))[0]))(params, tokens)
    return {c.key[0] if len(what) == 1 else c.key[:2]:
            (c.bytes, c.key in chooser.kept)
            for c in chooser.seen.values() if c.key[1] in what}


def _filled(bundle, room, **kw):
    """:func:`_offered` under what the rule keeps in ``room``, as the
    ``Trainer`` finds it: a trace that keeps nothing shows the candidates,
    ``fill`` ranks them, a second trace is held to that plan."""
    first = remat.Chooser(0, frozenset())
    _offered(bundle, first, **kw)
    return _offered(bundle, remat.Chooser(room, first.fill(room)), **kw)


def test_a_scanned_run_is_all_or_nothing_and_a_run_of_one_stands_alone(
        monkeypatch):
    """GPT-2's test preset is ONE scanned run of two layers: one candidate of
    both layers' bytes, left whole where only one layer's fit — its FFN as
    each of its attention's rows, which cost as much a byte (they contract
    the same 128) and are ranked behind it, the smaller.
    Phi-4-mini-flash's six layers are six runs of one: with room for two and
    a half FFNs the first two are kept and the other four made again."""
    monkeypatch.setattr(remat, "FLOOR_FLOP_PER_BYTE", 0)
    gpt = get_model("gpt", size="test", seq_len=32, vocab=64, remat=True,
                    remat_policy="full")
    layer = 2 * 32 * 512 * 4  # up [2, 32, 512] float32
    assert _filled(gpt, 2 * layer - 1) == {"blocks": (2 * layer, False)}
    assert _filled(gpt, 2 * layer) == {"blocks": (2 * layer, True)}
    rows = 2 * 32 * 128 * 4  # q [2, 32, 128] float32
    kinds = ("ffn", "q", "k", "v", "out")
    assert _filled(gpt, 2 * layer + 5 * rows, what=kinds) == {
        ("blocks", what): (2 * (rows if n else layer), n < 3)
        for n, what in enumerate(kinds)}
    phi = _phi4flash(remat=True, remat_policy="full")
    layer = 2 * 2 * 32 * 640 * 4  # gate and up [2, 32, 640] float32
    assert _filled(phi, 5 * layer // 2) == {
        f"blocks_{i}": (layer, i < 2) for i in range(6)}
    # what is looped is held once a pass: two layers x two passes
    ouro = get_model("ouro", size="test", seq_len=32, vocab=64, remat=True,
                     remat_policy="full", layer_types=["full_attention"] * 2,
                     total_ut_steps=2)
    (nbytes, kept), = _offered(ouro, remat.Chooser(1 << 40)).values()
    layer = nbytes // 4
    assert kept and nbytes == 4 * layer
    assert _filled(ouro, 4 * layer - 1) == {"blocks": (4 * layer, False)}


@pytest.mark.parametrize("room", [0, 1 << 20, 1 << 60])
def test_under_the_floor_nothing_is_kept_at_any_room(room):
    """A test-size FFN contracts 64: 32 FLOP a byte in float32. Whatever the
    room no candidate reaches the chooser."""
    chooser = remat.Chooser(room)
    assert _offered(_six_runs(), chooser) == {} and not chooser.kept
    with remat.choosing(remat.Chooser(room)), remat.block("full") as said:
        x = jnp.ones((4, 64), jnp.bfloat16)
        remat.name_products((x, x), remat.FLOOR_FLOP_PER_BYTE - 1)
        assert (len(said.named), len(said.left)) == (0, 1)
        # 1,024 bytes at the floor
        remat.name_products((x, x), remat.FLOOR_FLOP_PER_BYTE)
    assert (len(said.named), len(said.left)) == ((2, 1) if room >= 1024
                                                 else (0, 2))


def _trainer(bundle, batch=4, accum=2):
    import optax

    from easydl_tpu.core.mesh import MeshSpec, build_mesh
    from easydl_tpu.core.train_loop import TrainConfig, Trainer

    return Trainer(init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
                   optimizer=optax.sgd(1e-3),
                   config=TrainConfig(global_batch=batch, grad_accum=accum,
                                      compute_dtype=jnp.float32),
                   mesh=build_mesh(MeshSpec(), devices=jax.devices()[:1]))


def _lowered(trainer, batch=4, seq=32):
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    return trainer.step_fn.lower(trainer.abstract_state(),
                                 {"inputs": tokens, "targets": tokens})


def _six_runs():
    """Six layers 64 wide, GELU and SwiGLU FFNs of 128 in turn: six runs of
    one, each a candidate at 32 FLOP a byte in float32 — a GELU's ``up`` one
    ``UNIT``, a SwiGLU's ``gate`` and ``up`` two."""
    from easydl_tpu.models.lm import lm_bundle

    return lm_bundle(TransformerConfig(
        vocab=64, d_model=64, n_heads=2, n_layers=6, d_ff=128, max_seq=32,
        layers=(("attention", "gelu"), ("attention", "swiglu")) * 3,
        remat=True, remat_policy="full"), "six-runs-of-one")


#: a microbatch's [2, 32, 128] float32
UNIT = 2 * 32 * 128 * 4
#: the bytes the six runs' FFNs keep, in the order of the trace
RUNS = [UNIT, 2 * UNIT] * 3
#: every candidate of the six runs in the order of the trace, (key, bytes):
#: runs of one offer their FFNs alone, all at 32 FLOP a byte (the width 64
#: they contract, in float32)
SIX = [((f"blocks_{i}", "ffn", 0), RUNS[i]) for i in range(6)]


def _rule(room):
    """The keys the rule keeps of ``SIX`` (equal a byte) in ``room``, in rank
    order: the larger first, equals as traced; each where it fits."""
    kept = []
    for key, nbytes in sorted(SIX, key=lambda c: -c[1]):
        if nbytes <= room:
            room -= nbytes
            kept.append(key)
    return kept
#: what the step is SAID to compile to with nothing kept
BASE = 1 << 30


@pytest.fixture
def a_chip(monkeypatch):
    """``a_chip(room, ...)``: from the call on every device states a limit
    (``ops/platform.memory_stats``, as a TPU states its own) that leaves
    ``room`` bytes beside a step of ``BASE`` bytes and the margin, and a
    compiled step is SAID to take ``BASE + share x`` the bytes it keeps
    (``share`` a function: ``BASE + share(the bytes)``) (the
    CPU's compiler, which packs a test-size step's buffers its own way, is
    not asked: nothing is compiled), or refused for memory where it keeps more than
    ``refused_over``; the floor is lowered so that a test-size FFN is a
    candidate. Returns the choosers the ``Trainer`` opens, in order: one a
    trace, then the one that replays the choice."""
    from easydl_tpu.core import train_loop
    from easydl_tpu.ops import platform

    def state(room, share=1.0, refused_over=None, in_use=0):
        opened = []
        cost = share if callable(share) else lambda kept: int(share * kept)

        class Chooser(remat.Chooser):
            def __init__(self, *args):
                super().__init__(*args)
                opened.append(self)

        def said_size(lowered):
            kept = opened[-1].kept_bytes
            if refused_over is not None and kept > refused_over:
                raise jax.errors.JaxRuntimeError(
                    "RESOURCE_EXHAUSTED: Used more than the chip has")
            return BASE + cost(kept)

        monkeypatch.setattr(remat, "FLOOR_FLOP_PER_BYTE", 0)
        monkeypatch.setattr(remat, "Chooser", Chooser)
        monkeypatch.setattr(train_loop, "compiled_bytes", said_size)
        monkeypatch.setattr(platform, "memory_stats", lambda device: {
            "bytes_limit": BASE + remat.MARGIN_BYTES + room,
            "bytes_in_use": in_use})
        return opened

    return state


def _kept(trainer):
    """The keys the trainer's step keeps, sorted."""
    return sorted(trainer.step_fn._chooser.plan)


@pytest.mark.parametrize("room,share,refused_over,traces,stands", [
    # room for all six: the trace that sizes the step, then the rule's
    (9 * UNIT, 1.0, None, 2, None),
    # room for half: the two largest
    (9 * UNIT // 2, 1.0, None, 2, None),
    # no room: the step that keeps nothing stands, traced and compiled once
    (UNIT - 1, 1.0, None, 1, None), (0, 1.0, None, 1, None),
    # XLA packs half of what is kept into room it had: the room is the
    # sized step's all the same
    (4 * UNIT, 0.5, None, 2, None),
    # what is kept costs half as much again as its bytes: the rule once more
    # in the room at that price, two thirds of it
    (4 * UNIT, 1.5, None, 3, None),
    # three units more than its bytes, whatever is kept: the second choice is
    # over too; or the compiler refuses the step that keeps the first: the
    # step that keeps nothing stands
    (4 * UNIT, lambda kept: kept and kept + 3 * UNIT, None, 3, "compiles to"),
    (9 * UNIT // 2, 1.0, 3 * UNIT, 2, "is refused"),
])
def test_the_trainer_fits_what_is_kept_to_the_limit_it_is_given(
        a_chip, said_by_the_block, room, share, refused_over, traces, stands):
    """With an injected limit the ``Trainer`` keeps the candidates that fit
    the room the compiled step leaves — where they cost alike the larger
    first, equals in the order of the trace — and says once what the step
    compiled to beside the limit (one microbatch a step here; two in the
    cases below)."""
    bundle = _six_runs()
    opened = a_chip(room, share, refused_over)
    trainer = _trainer(bundle, batch=2, accum=1)
    lowered = _lowered(trainer, batch=2)
    assert len(opened) == traces + 1
    assert [tuple(c.key) for c in opened[0].seen.values()] \
        == [key for key, _ in SIX]
    cost = share if callable(share) else lambda kept: share * kept

    def held(keys):
        return sum(dict(SIX)[key] for key in keys)

    kept = _rule(room)
    if traces == 3:  # over: again in the room at the price the compile showed
        kept = _rule(room * held(kept) // cost(held(kept)))
    kept = [] if stands else kept
    assert _kept(trainer) == sorted(kept)
    size = BASE + cost(held(kept))
    assert size <= BASE + room
    del lowered
    said, = [m for m in said_by_the_block
             if m.startswith("train step: compiled")]
    warned = [m for m in said_by_the_block if "train step: keeping" in m]
    assert len(warned) == (traces > 1) * (traces - 2 + (stands is not None))
    assert all(f"GiB the step {stands or 'compiles to'}" in line
               for line in warned)
    assert len([m for m in said_by_the_block if m == "train step: the step "
                "that keeps nothing stands"]) == (stands is not None)
    assert f"compiled to {size / 2**30:.3f} GiB a device of a limit of " \
        in said and "; remat keeps " in said
    left = [key for key in _rule(1 << 40) if key not in kept]
    assert said.endswith(
        "leaves no candidate out" if not left else
        f"the first of {len(left)} left out for want of room: "
        f"{remat.Candidate(left[0], dict(SIX)[left[0]], 32)}")


def test_the_same_program_and_limit_give_the_same_choice_twice(a_chip):
    """Two builds of one job — a resume — choose alike and lower to one
    text: the choice is a function of the program and the limit alone."""
    bundle = _six_runs()
    opened = a_chip(9 * UNIT)
    first, again = _trainer(bundle), _trainer(bundle)
    text = _lowered(first).as_text()
    assert text == _lowered(again).as_text()
    assert _kept(first) == _kept(again) == sorted(key for key, _ in SIX)
    # a build: the trace that sizes the step, the rule's, the replay; two
    # microbatches: the block is traced twice a step and offered once
    assert len(opened) == 6 and all(len(c.seen) == len(SIX)
                                    for c in opened[:2])
    jax.clear_caches()  # a later TRACE of the step replays the choice
    assert _lowered(first).as_text() == text
    assert len(opened) == 6 and opened[2].kept == opened[2].plan


@pytest.fixture
def a_compile_cache(tmp_path):
    """A persistent compile cache's directory for the test (nothing is
    compiled into it: the step's size is said, ``a_chip``)."""
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_enable_compilation_cache", before[1])


def test_a_warm_start_traces_once_from_the_choice_it_remembers(
        a_chip, a_compile_cache, said_by_the_block):
    """Beside the persistent compile cache the ``Trainer`` leaves the choice
    a step settled on; the next build of the same step starts from it, finds
    it lower to the remembered text in the remembered budget and traces
    once. A memory that is not this program's choice (another program's
    under the same shapes, another budget's, no choice at all) costs its
    trace and is written over; one that cannot be written costs the NEXT
    start its second trace and this one nothing."""
    import json

    bundle = _six_runs()
    opened = a_chip(9 * UNIT // 2)
    cold = _trainer(bundle, batch=2, accum=1)
    text = _lowered(cold, batch=2).as_text()
    assert len(opened) == 2 + 1  # the sizing trace, the rule's, the replay
    memo, = a_compile_cache.iterdir()
    right = json.loads(memo.read_text())
    assert right["kept"] == sorted(map(list, _rule(9 * UNIT // 2)))
    assert right["kept"] == [["blocks_1", "ffn", 0], ["blocks_3", "ffn", 0]]
    assert (right["room"], right["budget"]) == (9 * UNIT // 2,
                                                BASE + 9 * UNIT // 2)
    del opened[:]
    warm = _trainer(bundle, batch=2, accum=1)
    assert _lowered(warm, batch=2).as_text() == text
    assert len(opened) == 1 + 1 and _kept(warm) == _kept(cold)
    for wrong, traces in (
            (dict(right, kept=[["blocks_5", "ffn", 0]]), 3),
            (dict(right, kept=right["kept"] + [["blocks_9", "flash", 0]]), 3),
            (dict(right, budget=right["budget"] + 1), 2),
            (right["kept"], 2), ("not a choice", 2)):
        memo.write_text(json.dumps(wrong))
        del opened[:]
        again = _trainer(bundle, batch=2, accum=1)
        assert _lowered(again, batch=2).as_text() == text
        assert len(opened) == traces + 1 \
            and _kept(again) == _kept(cold)
        assert json.loads(memo.read_text()) == right
    a_chip(9 * UNIT)  # another limit is another step's memory
    _lowered(_trainer(bundle, batch=2, accum=1), batch=2)
    assert len(list(a_compile_cache.iterdir())) == 2
    # a cache that cannot be written (here: its directory is a file's name)
    jax.config.update("jax_compilation_cache_dir", str(memo / "below"))
    opened = a_chip(9 * UNIT // 2)
    served = _trainer(bundle, batch=2, accum=1)
    assert _lowered(served, batch=2).as_text() == text
    assert len(opened) == 2 + 1 and _kept(served) == _kept(cold)
    assert [m for m in said_by_the_block if "is not remembered" in m]


def test_without_a_limit_the_step_is_traced_once_with_no_chooser_open():
    """The CPU states no limit (``memory_stats()`` is None): the step is the
    jitted function it was, nothing is compiled to choose, nothing more is
    kept."""
    trainer = _trainer(_six_runs())
    _lowered(trainer)
    assert trainer.step_fn._chooser is None


def test_a_tpu_that_states_no_limit_is_named_in_the_log(
        monkeypatch, said_by_the_block):
    """A TPU runtime whose ``memory_stats()`` is None gives the rule no room:
    no candidate is kept — the flash results that a constant kept whatever
    the room (PR 38) are made again — and the ``train step:`` line warns of
    it, once a step; the CPU, which never kept them, is told nothing."""
    from easydl_tpu.ops import platform

    for on_tpu, warned in ((False, 0), (True, 1)):
        monkeypatch.setattr(platform, "on_tpu", lambda: on_tpu)
        del said_by_the_block[:]
        step = _trainer(_six_runs()).step_fn
        step._fit(()), step._fit(())  # (nothing is traced where none is open)
        assert step._fn is not None and step._chooser is None
        lines = [m for m in said_by_the_block if m.startswith("train step:")]
        assert len(lines) == warned
        for line in lines:
            assert "state no memory limit" in line \
                and "keeps NO candidate" in line


def test_a_job_that_fills_the_chip_keeps_nothing_and_is_still_served(
        a_chip, interpreted):
    """PERF.md section 7's refused job: under the constant (PR 38) a flash
    call at 6,000 FLOP a byte or more kept its results whatever the room, and
    a job of long sequences that filled the chip was refused by the compiler.
    A step whose size with nothing kept is the whole limit keeps none of its
    candidates, the flash results among them, and lowers to the text it has
    where no limit is stated."""
    init, loss = _gpt(remat=True, remat_policy="full")
    tokens = jnp.zeros((2, 64), jnp.int32)
    params = jax.eval_shape(lambda: nn.unbox(init(jax.random.PRNGKey(0),
                                                  tokens)))
    bundle = type("Bundle", (), {
        "init_fn": staticmethod(lambda key: init(key, tokens)),
        "loss_fn": staticmethod(lambda p, batch, key: (
            loss(p, batch["inputs"]), {}))})
    plain = _lowered(_trainer(bundle), seq=64).as_text()
    opened = a_chip(0)
    trainer = _trainer(bundle)
    assert _lowered(trainer, seq=64).as_text() == plain
    assert _kept(trainer) == []
    # the run's flash results and its FFN were candidates, and had no room
    assert {key[1] for key in opened[0].seen} == {"flash", "ffn", "q", "k",
                                                  "v", "out"}
    assert len(opened) == 1 + 1
    del params


_TWO_PROCESSES = """
import sys
import jax
rank, port, tests = int(sys.argv[1]), sys.argv[2], sys.argv[3]
jax.distributed.initialize(coordinator_address="localhost:" + port,
                           num_processes=2, process_id=rank)
sys.path.insert(0, tests)
import jax.numpy as jnp
import optax
from jax.experimental import multihost_utils
import test_remat_full as t
from easydl_tpu.core import train_loop
from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.models.lm import lm_bundle
from easydl_tpu.models.transformer import TransformerConfig
from easydl_tpu.ops import platform, remat

# process 0 alone has room for all six beside the 64 MiB it holds, process
# 1 for the two largest and holds nothing: one program, so one choice — two
# in the least limit less the most held
room, in_use = ((9 * t.UNIT, 1 << 26), (9 * t.UNIT // 2, 0))[rank]
opened, asked = [], []

class Chooser(remat.Chooser):
    def __init__(self, *args):
        super().__init__(*args)
        opened.append(self)

gather = multihost_utils.process_allgather
multihost_utils.process_allgather = lambda x: asked.append(1) or gather(x)
remat.FLOOR_FLOP_PER_BYTE, remat.Chooser = 0, Chooser
train_loop.compiled_bytes = lambda lowered: t.BASE + opened[-1].kept_bytes
platform.memory_stats = lambda device: {
    "bytes_limit": t.BASE + remat.MARGIN_BYTES + room + (1 << 26),
    "bytes_in_use": in_use}

def kept(bundle):
    trainer = train_loop.Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.sgd(1e-3), config=train_loop.TrainConfig(
            global_batch=2, grad_accum=1, compute_dtype=jnp.float32),
        mesh=build_mesh(MeshSpec(dp=2)))
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    trainer.step_fn.lower(trainer.abstract_state(),
                          {"inputs": tokens, "targets": tokens})
    return sorted(key[0] for key in trainer.step_fn._chooser.plan)

# a program that offers no candidate asks the other process nothing
dots = lm_bundle(TransformerConfig(
    vocab=64, d_model=64, n_heads=2, n_layers=2, d_ff=128, max_seq=32,
    remat=True, remat_policy="dots"), "dots")
assert kept(dots) == [] and not asked, asked
print("KEPT", kept(t._six_runs()), len(asked), flush=True)
"""


@requires_multiproc_cpu()
def test_two_processes_of_one_program_agree_on_one_choice(tmp_path):
    """The step of a multi-process job is one program: every process keeps
    what fits the LEAST limit less the MOST held beside the step of any of
    them (each injected here, as a TPU states its own), asked for only once
    the first trace has shown a candidate."""
    import socket
    import subprocess
    import sys

    from easydl_tpu.utils.env import cpu_subprocess_env

    with socket.socket() as s:
        s.bind(("", 0))
        port = s.getsockname()[1]
    env = cpu_subprocess_env(1)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(__file__))]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    procs = [subprocess.Popen(
        [sys.executable, "-c", _TWO_PROCESSES, str(rank), str(port),
         os.path.dirname(__file__)], env=env, cwd=tmp_path,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for rank in (0, 1)]
    for proc in procs:
        out, err = proc.communicate(timeout=300)
        assert proc.returncode == 0, err[-3000:]
        assert out.splitlines()[-1] == \
            "KEPT ['blocks_1', 'blocks_3'] 1", out


def test_what_the_process_holds_beside_the_step_is_not_room(a_chip):
    """``bytes_in_use`` less the step's own arguments, in whole 64 MiB, comes
    off the room: a limit with room for all six beside 64 MiB in use has
    room for none beside one byte more."""
    bundle = _six_runs()
    for in_use, kept in ((1 << 26, 6), ((1 << 26) + 1, 0)):
        a_chip(9 * UNIT + (1 << 26), in_use=in_use)
        trainer = _trainer(bundle)
        _lowered(trainer)
        assert len(_kept(trainer)) == kept


def test_with_gate_and_up_kept_the_gradients_are_no_remats_leaf_for_leaf(
        monkeypatch):
    """Phi-4-mini-flash's test preset (a Mamba-1 layer and the whole-sequence
    attention layer) in float32, both FFNs' ``gate`` and ``up`` kept by name:
    the gradients of the stack with no remat, leaf for leaf, and the second
    forward's ``gate`` and ``up`` products are gone from the traced
    backward."""
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, 64, (2, 32), np.int32))
    batch = {"inputs": tokens, "targets": tokens}

    def grads(chooser, run=True, **kw):
        bundle = get_model("phi4flash", size="test", seq_len=32, vocab=64,
                           layer_ids=[0, 17], **kw)
        params = nn.unbox(bundle.init_fn(jax.random.PRNGKey(0)))
        fn = jax.jit(jax.grad(lambda p: bundle.loss_fn(
            p, batch, jax.random.PRNGKey(0))[0]))
        with remat.choosing(chooser):
            traced = fn.trace(params)
        return run and traced.lower().compile()(params), str(
            traced.jaxpr).count("dot_general")

    want, _ = grads(None)
    _, products_left = grads(None, run=False, remat=True, remat_policy="full")
    monkeypatch.setattr(remat, "FLOOR_FLOP_PER_BYTE", 0)
    chooser = remat.Chooser(1 << 40)
    got, products_kept = grads(chooser, remat=True, remat_policy="full")
    # the two layers are runs of one: their FFNs are all they offer
    assert len(chooser.kept) == 2
    # gate and up, two layers (the second forward's down is read by nothing
    # and is not made either way)
    assert products_left - products_kept == 2 * 2
    # (a key's bias has no gradient but rounding: held to the tree's largest)
    largest = max(float(jnp.abs(w).max()) for w in jax.tree.leaves(want))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6 * largest,
                                   err_msg=jax.tree_util.keystr(path))


# ------------------------------- every product at or over the floor (PR 59)
def _model(factory, **kw):
    return _bundle(factory, remat=True, **{"remat_policy": "full", **kw})


#: a test preset -> every candidate its ``full`` blocks offer beside the flash
#: calls and the dense FFNs, ``{(run, what): (bytes of a [2, 32] microbatch
#: over the run's layers, FLOP a byte)}``: float32, so half the width a
#: product contracts. Offered in a scanned run alone
OFFERED = {
    # one scanned run of two layers, 128 wide
    "gpt": ("gpt", {}, {
        ("blocks", what): (2 * 2 * 32 * 128 * 4, 64)
        for what in ("q", "k", "v", "out")}),
    # two Mamba-2 layers scanned: z and x 64 wide, B and C 32, dt 4
    "hybrid-mamba2": ("granite_hybrid", dict(layer_types=["mamba"] * 2), {
        ("blocks", "maps"): (2 * 2 * 32 * (64 + 64 + 32 + 32 + 4) * 4, 32)}),
    # a Mamba-1 layer and a differential layer, two runs of ONE under the
    # barrier: nothing new (offered there the kinds lost Phi-4-mini-flash's
    # cell 0.19%: ``ops/remat.py``'s docstring)
    "phi4flash": ("phi4flash", dict(layer_ids=[0, 17]), {}),
    # two Mamba-1 layers scanned: in_x and in_z, 320 wide each
    "phi4flash-scanned": ("phi4flash", dict(layer_ids=[0, 2]), {
        ("blocks", "maps"): (2 * 2 * 32 * 2 * 320 * 4, 80)}),
    # a dense layer alone (nothing new), two sparse layers scanned: q's way
    # up (4 heads of 24) contracts the latent's 48, the shared expert is gate
    # and up of 32
    "joyai": ("joyai", dict(layer_types=["dense"] + ["sparse"] * 2,
                            mtp=False), {
        ("blocks_1", "q_b"): (2 * 2 * 32 * 4 * 24 * 4, 24),
        ("blocks_1", "out"): (2 * 2 * 32 * 64 * 4, 32),
        ("blocks_1", "shared"): (2 * 2 * 32 * 2 * 32 * 4, 32)}),
    # NemotronH's four sub-layer pairs, every one a run of one: nothing new
    "nemotron": ("nemotron_h", {}, {}),
    # Laguna's full layers are runs of one beside a scanned run of three
    # window layers: no barrier, so the three alone offer anything new — and
    # their output map contracts 8 heads of 16, twice the model's width
    "laguna": ("laguna", dict(vocab=128), {
        ("blocks_1", "q"): (3 * 2 * 32 * 128 * 4, 32),
        ("blocks_1", "k"): (3 * 2 * 32 * 32 * 4, 32),
        ("blocks_1", "v"): (3 * 2 * 32 * 32 * 4, 32),
        ("blocks_1", "out"): (3 * 2 * 32 * 64 * 4, 64),
        ("blocks_1", "shared"): (3 * 2 * 32 * 2 * 32 * 4, 32)}),
}


def _seen(bundle, chooser):
    _offered(bundle, chooser)
    return {c.key[:2]: (c.bytes, c.flop_per_byte)
            for c in chooser.seen.values() if c.key[1] not in ("ffn", "flash")}


@pytest.mark.parametrize("preset", list(OFFERED))
def test_a_full_block_offers_every_product_at_or_over_the_floor(
        monkeypatch, preset):
    """Each kind — an attention projection's rows, a scan mixer's input maps,
    a shared expert's first products — with its bytes over the run and what
    a byte of it costs to make again; with room all are kept; under the
    floor as it stands none reaches the chooser; and ``dots``, which keeps
    them as the products they are, is offered none."""
    factory, kw, want = OFFERED[preset]
    chooser = remat.Chooser(1 << 40)
    assert _seen(_model(factory, **kw), chooser) == {}
    monkeypatch.setattr(remat, "FLOOR_FLOP_PER_BYTE", 0)
    chooser = remat.Chooser(1 << 40)
    assert _seen(_model(factory, **kw), chooser) == want
    assert {key[:2] for key in chooser.kept} >= set(want)
    assert _seen(_model(factory, **{**kw, "remat_policy": "dots"}),
                 remat.Chooser(1 << 40)) == {}


@pytest.mark.parametrize("named,policy,uses,kept", [
    # an FFN's products in any run; the others in a scanned run (several
    # layers, or a layer a looped pass: uses over one)
    ("ffn", "full", 1, True), ("ffn", "full", 3, True),
    ("maps", "full", 1, False), ("maps", "full", 2, True),
    ("shared", "full", 1, False), ("shared", "full", 6, True),
    ("q", "full", 1, False), ("q", "full", 4, True),
    ("out", "full", 1, False), ("out", "full", 3, True),
    # dots keeps them unnamed, no remat keeps everything
    ("ffn", "dots", 3, False), ("q", "dots", 3, False),
    ("maps", None, 3, False),
])
def test_a_kind_is_named_in_a_scanned_run_alone(named, policy, uses, kept):
    x = jnp.ones((4, 64), jnp.bfloat16)
    chooser = remat.Chooser(1 << 20)
    with remat.choosing(chooser), remat.run("blocks_7", uses), \
            remat.block(policy) as said:
        if named in remat.PRODUCTS:
            remat.name_products((x, x), 2560, named)
            label, n = remat.PRODUCTS[named], 2
        else:
            remat.name_rows(x, 2560, named)
            label, n = remat.ROWS if kept else remat.PROJECTION, 1
    if not kept:
        assert not chooser.seen and not said.left
        assert [v.label for v in said.named] == (
            [] if named in remat.PRODUCTS else [remat.PROJECTION])
        return
    # the bytes a layer's x the run's uses, at the width contracted
    assert [tuple(c) for c in chooser.seen.values()] == [
        (("blocks_7", named, 0), n * 512 * uses, 2560.0)]
    assert [(v.label, v.bytes, v.flop_per_byte) for v in said.named] \
        == [(label, 512, 2560.0)] * n
    assert label in remat.KEPT["full"] and label not in remat.KEPT["dots"]


#: candidates at 2,560 FLOP a byte (Phi-4-mini-flash's widths), in the order
#: of a trace, MB: a Mamba-1 layer's maps and FFN, a window layer's rows and
#: FFN
TIED = [("maps", 335), ("ffn", 671), ("q", 84), ("k", 42), ("v", 42),
        ("out", 84), ("ffn1", 671)]


@pytest.mark.parametrize("room,kept", [
    # of equals the larger first: both FFNs before anything smaller ...
    (1342, ["ffn", "ffn1"]), (1341, ["ffn", "maps", "q", "out", "k", "v"]),
    # ... then what still fits, the larger first, equals as traced
    (1342 + 335 + 84, ["ffn", "ffn1", "maps", "q"]),
    (1342 + 100, ["ffn", "ffn1", "q"]),
    (1342 + 84 + 84 + 42, ["ffn", "ffn1", "q", "out", "k"]),
    # dearer beats larger: a flash call's 341 at 12,100 goes first
    (341 + 671, ["flash", "ffn"]), (341 + 670, ["flash", "maps", "q", "out",
                                                "k", "v"]),
])
def test_a_tie_goes_to_the_candidate_that_saves_more_in_all(room, kept):
    """What a candidate states is what keeping it saves a byte; of those that
    state the same (at Phi-4-mini-flash's widths an FFN, a mixer's maps and
    the attention's rows all read 2,560) the one that saves more in all is
    ranked first: FFNs, then maps, then q and ``out``, then k and v."""
    chooser = remat.Chooser(0, frozenset())
    for what, mb in TIED + [("flash", 341)] * ("flash" in kept):
        chooser.offer(remat.Candidate(
            ("blocks", what, 0), mb, 12100 if what == "flash" else 2560))
    ranked = [c.key[1] for c in chooser.ranked()]
    assert ranked == ["flash"] * ("flash" in kept) + [
        "ffn", "ffn1", "maps", "q", "out", "k", "v"]
    assert chooser.fill(room) == frozenset(("blocks", what, 0)
                                           for what in kept)


def test_the_floor_and_what_stands_under_it():
    """1,800 FLOP a byte: measured on both sides (``ops/remat.py``'s
    docstring: XL lost 5.0% at 994, Mellum 2 gained 1.45% at 1,891) — under
    it the GPT-2 cells' flash calls (994), Laguna's and Phi-4-mini-flash's
    window layers (977, 744) and every product that contracts less than
    1,800 (ZAYA1's output map, 1,024; JoyAI's ``q_b``, 1,536); at or over it
    every benchmark cell's model width and Mellum 2's band."""
    assert remat.FLOOR_FLOP_PER_BYTE == 1800
    x = jnp.ones((8, 128), jnp.bfloat16)
    for contracted, over in ((1024, False), (1536, False), (1799, False),
                             (2048, True), (2304, True), (2560, True),
                             (2688, True), (4096, True), (8192, True)):
        chooser = remat.Chooser(1 << 20)
        with remat.choosing(chooser), remat.run("blocks", 2), \
                remat.block("full") as said:
            remat.name_rows(x, contracted, "out")
        assert bool(chooser.kept) == over
        assert [why for _, why in said.left] == ([] if over else [
            f"under the floor of {remat.FLOOR_FLOP_PER_BYTE:,}"])
    out, lse = _results(2, 8192, 32, 128)
    band = remat.flash_flop_per_byte(out, lse, s_k=8192, head_dim=128,
                                     causal=True, window=1024)
    assert round(band) == 1891  # Mellum 2's window layers
    assert band >= remat.FLOOR_FLOP_PER_BYTE


# ------------------------------------------- the stacks' texts, PR 58's parent
#: every stack ``tests/test_tpu_compile_stack.py`` compiles for the described
#: v5e -> how it is lowered. The first four are given no room (GPT-2's under
#: ``dots`` and ``full``, under ``fsdp=4`` through the ``Trainer``, Ouro's
#: two layers and passes): no limit is stated, nothing more is kept. The
#: three scanned runs at a cell's widths kept their flash results there and
#: are held to that choice (``FLASH``: what else a scanned run keeps since PR
#: 59 is another text).
FLASH = frozenset({("blocks", "flash", 0)})
STACKS = {
    "gpt2-two-layers-dots": lambda stack, devices:
        stack._two_layer_gpt2_lowered(devices, "dots"),
    "gpt2-two-layers-full": lambda stack, devices:
        stack._two_layer_gpt2_lowered(devices, "full"),
    "gpt2-two-layers-dots-fsdp4": lambda stack, devices:
        stack._two_layer_gpt2_lowered(devices, "dots", stack.MeshSpec(fsdp=4)),
    "ouro-two-layers-two-passes": lambda stack, devices:
        stack._two_layer_rotary_lowered(devices),
    "sdar-scanned": lambda stack, devices: stack._scanned_lowered(
        devices, "sdar", batch=1, plan=FLASH, size="30b-a3b-chat",
        block_length=4, layer_types=["full_attention"] * 2,
        experts_held=(0, 16)),
    "joyai-scanned": lambda stack, devices: stack._scanned_lowered(
        devices, "joyai", plan=FLASH, size="llm-flash",
        layer_types=["sparse"] * 2, mtp=False, experts_held=(0, 16)),
    "zaya-scanned": lambda stack, devices: stack._scanned_lowered(
        devices, "zaya", plan=FLASH, size="8b", layer_types=["hybrid"] * 2,
        experts_held=(0, 8)),
}


def stack_sha256(name, devices) -> str:
    """``scripts/rehearse_tpu_compile.py program_sha256`` of ``STACKS[name]``
    lowered (the Mosaic payloads without their source locations, private
    functions without jax's running numbers)."""
    import importlib.util
    import os

    from tests import test_tpu_compile_stack as stack

    spec = importlib.util.spec_from_file_location(
        "rehearse_tpu_compile", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "rehearse_tpu_compile.py"))
    rehearse = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rehearse)
    return rehearse.program_sha256(STACKS[name](stack, devices).as_text())


@pytest.mark.parametrize("name", list(STACKS))
def test_every_stack_lowers_to_the_text_it_had_before_the_rule_saw_room(
        name, v5e_2x2, described_tpu):
    """``tests/goldens/remat_stacks.json`` holds each stack's hash on PR 58's
    parent (cb83808), where a constant picked the flash calls: with no limit
    stated — and, for the runs that kept their flash results there, held to
    that choice — every one lowers to that text: what a ``full`` block offers
    beside them since PR 59 moves no program that does not keep it. The three
    GPT-2 stacks' hashes were written anew by PR 60, which MEANT to change
    their kernels (a 1,024-long head on the two looped kernels: one backward
    call for two); the four at 4,096 and 8,192 rows are still cb83808's, which
    is how PR 60 showed that it moved no longer cell's kernels."""
    import json
    import os

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "goldens", "remat_stacks.json")) as f:
        assert stack_sha256(name, v5e_2x2) == json.load(f)[name]
