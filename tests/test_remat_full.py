"""What remat ``full`` keeps (PR 38): the flash forward's ``out`` and ``lse``
of the calls that ``ops/remat.py``'s rule picks — those that cost
``FLASH_KEEP_FLOP_PER_BYTE`` or more to make again for each byte held — and
nothing else; ``dots`` keeps a picked ``out`` beside what it kept.

On the CPU, kernels interpreted, the constant lowered by the ``flash_kept``
fixture (``conftest.py``) so that a test-size call is picked. What the
compiled program holds at the cells' sizes is ``tests/test_tpu_compile.py``'s.
"""

from __future__ import annotations

import functools
import logging

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydl_tpu.models.registry import get_model
from easydl_tpu.models.transformer import Transformer, TransformerConfig
from easydl_tpu.ops import attention as attention_module
from easydl_tpu.ops import remat
from easydl_tpu.ops.flash_attention import flash_attention

#: cell's flash call -> ((batch, seq, heads, score size, value size, window),
#: the rule's FLOP a byte rounded (ISSUE 38's table, to its rounding), picked). Batch is a
#: microbatch's; the key/value heads are repeated before the kernels.
CELLS = {
    "joyai-llm-flash": ((2, 8192, 32, 192, 128, None), 10084, True),
    "zaya1-8b": ((2, 8192, 8, 128, 128, None), 8067, True),
    "laguna-xs.2-full": ((2, 8192, 48, 128, 128, None), 8067, True),
    "ouro-2.6b": ((1, 4096, 16, 128, 128, None), 4034, False),
    "granite-4.0-h-micro": ((2, 4096, 32, 64, 64, None), 3973, False),
    "laguna-xs.2-window": ((2, 8192, 64, 128, 128, 512), 977, False),
    "gpt2-medium": ((8, 1024, 16, 64, 64, None), 994, False),
    "gpt2-xl": ((4, 1024, 25, 64, 64, None), 994, False),
}


def _results(batch, seq, heads, value, dtype=jnp.bfloat16):
    return (jax.ShapeDtypeStruct((batch, seq, heads * value), dtype),
            jax.ShapeDtypeStruct((batch, heads, seq), jnp.float32))


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_rule_at_the_cells_flash_shapes(cell):
    """The constant stands between the cells with the gain and the room and
    those without: which side each cell's call is on, and by how much."""
    (batch, seq, heads, score, value, window), want, picked = CELLS[cell]
    cost = remat.flash_flop_per_byte(
        *_results(batch, seq, heads, value), s_k=seq, head_dim=score,
        causal=True, window=window)
    assert round(cost) == want
    assert (cost >= remat.FLASH_KEEP_FLOP_PER_BYTE) == picked
    assert 4033 < remat.FLASH_KEEP_FLOP_PER_BYTE < 8067
    with remat.tally() as named:  # nothing of a cell's size is made
        jax.eval_shape(functools.partial(
            remat.name_flash, s_k=seq, head_dim=score, causal=True,
            window=window), *_results(batch, seq, heads, value))
    labels = [value.label for value in named]
    assert labels == ([remat.FLASH_OUT, remat.FLASH_LSE] if picked else
                      [remat.FLASH_OUT_CHEAP, remat.FLASH_LSE_CHEAP])
    for policy, kept in (("full", picked), ("dots", picked)):
        assert (labels[0] in remat.KEPT[policy]) == kept
    assert (labels[1] in remat.KEPT["full"]) == picked
    assert labels[1] in remat.KEPT["dots"]  # lse: with dots, whatever the call


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


@pytest.mark.parametrize("model", [_gpt, _joyai],
                         ids=["heads-32-32", "heads-24-16"])
def test_a_run_in_which_nothing_is_picked_lowers_to_the_parents_text(
        interpreted, monkeypatch, model):
    """``full``'s policy saves two names; a block that holds neither lowers
    to the text of ``policy=None``, the parent's ``full``: same operations,
    same private functions, as many times."""
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
        z = remat.name(jnp.tanh(y)[:2], remat.FLASH_LSE_CHEAP)
        return jnp.tanh(z @ z.T).sum()

    from tests.test_remat_dots import saved_residuals

    def kept(policy):
        return sorted(shape for shape, _ in saved_residuals(
            jax.checkpoint(block, policy=policy, prevent_cse=False), x))

    assert kept(remat.policy("full")) == [(4, 8)]
    assert kept(remat.policy("dots")) == [(2, 2), (2, 8), (4, 8)]
    assert kept(policies.nothing_saveable) == []
    # one object: a policy made anew a call splits jax's caches by call
    assert remat.policy("full") is remat.policy("full")


def test_a_full_stack_says_once_what_a_layer_keeps(interpreted, flash_kept,
                                                   monkeypatch):
    from easydl_tpu.models import transformer
    from easydl_tpu.utils import logging as easydl_logging

    monkeypatch.setattr(easydl_logging, "_logged_once", set())
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    monkeypatch.setattr(transformer.log, "handlers",
                        transformer.log.handlers + [handler])
    tokens = jnp.zeros((2, 64), jnp.int32)
    init, _ = _gpt()
    # traced, never run: the parameters' shapes are enough
    params = jax.eval_shape(init, jax.random.PRNGKey(0), tokens)
    loss = _gpt(remat=True, remat_policy="full")[1]
    jax.make_jaxpr(jax.grad(loss))(params, tokens)
    jax.make_jaxpr(loss)(params, tokens)
    flash_kept()
    jax.make_jaxpr(jax.grad(loss))(params, tokens)
    said = [message for message in said if message.startswith("remat full:")]
    assert len(said) == 2, said
    # out [2, 64, 128] float32 and lse [2, 4, 64] float32; 33 keys a query
    # at 64 + 64 lanes a head over 128 lanes and a row of lse
    held = 2 * 64 * 128 * 4 + 2 * 4 * 64 * 4
    cost = round(2 * (64 * 65 // 2) * 2 * 4 * 64 / held)
    assert "a (attention, gelu) layer at (2, 64, 128)" in said[0]
    assert "keeps 0 values by name (none), 0.0 MB" in said[0]
    assert "named and not kept: flash_lse_cheap, flash_out_cheap, " \
           "projection" in said[0]
    assert said[0].endswith(f"costs {cost:,} FLOP a byte of out + lse to make "
                            f"again (kept from 6,000)")
    assert "keeps 2 values by name (1 x flash_out, 1 x flash_lse), " \
           f"{held / 1e6:.1f} MB" in said[1]
    assert "named and not kept: projection; " in said[1]
    assert said[1].endswith(f"costs {cost:,} FLOP a byte of out + lse to make "
                            f"again (kept from 0)")
