"""What remat ``dots`` keeps (PR 30): the attention projections' sums and the
flash forward's ``lse`` as dense rows, by name (``ops/remat.py``); the
forward's ``out`` is named and, at these sizes, not kept (a call dear enough
to make again keeps it: ``tests/test_remat_full.py``, PR 38).

On the CPU, kernels interpreted: a kept value and a recomputed one are the
same number, so gradients under ``dots`` equal those with no remat, and the
backward kernels handed ``lse`` as rows give the parent's dq, dk, dv. What
the compiled program holds is ``tests/test_tpu_compile.py``'s.
"""

from __future__ import annotations

import contextlib
import functools
import io
import logging
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from easydl_tpu.models.transformer import Transformer, TransformerConfig
from easydl_tpu.ops import attention as attention_module
from easydl_tpu.ops import remat
from easydl_tpu.ops.flash_attention import flash_attention

#: ``(s_q, s_k, heads, block, causal, dtype)`` at head_dim 64, batch 2; and
#: ``[sum, sum of magnitudes]`` of dq, dk, dv as the PARENT commit (578fd40:
#: the cells of at most 16 block pairs a head, unrolled until PR 60, took the
#: forward's ``[B, H, S, 1]`` column and turned it in the kernel) gave them on
#: these seeded inputs, this jax, the CPU; the one kernel that serves every
#: case since PR 60 gives the same sums.
PARENTS = {
    "unrolled-even": ((64, 64, 4, 32, True, "float32"), [[4.206640409178018, 6124.620398450686], [-1.0082509902531456e-06, 5287.927846675118], [-66.39836702030402, 6647.693803479298]]),
    "unrolled-odd": ((64, 64, 3, 32, True, "float32"), [[44.29539270090656, 4751.807215665954], [-8.119290157537762e-06, 4152.178119073069], [-74.77570528847536, 5030.340359941225]]),
    "looped-even": ((160, 160, 4, 32, True, "float32"), [[40.92087442772161, 11645.672521947079], [-3.988582761849102e-07, 9751.765840874457], [113.91690027777923, 11251.962921758939]]),
    "looped-odd": ((160, 160, 3, 32, True, "float32"), [[-38.66643615345044, 8795.25717638574], [-1.7639742580399798e-05, 7423.772694034755], [99.73305288053666, 8531.962021455547]]),
    "rectangular": ((64, 96, 2, 32, False, "float32"), [[-49.782067320193164, 2044.9252937111305], [2.8032809495925903e-06, 2431.2016577301547], [-31.394115546791, 2679.041698651432]]),
    "bf16-unrolled": ((128, 128, 2, 64, True, "bfloat16"), [[11.773009240627289, 5141.804714858532], [-0.25185155868530273, 4282.1433690190315], [-66.68597477674484, 5088.45830899477]]),
    "bf16-looped": ((320, 320, 2, 64, True, "bfloat16"), [[53.014555185538484, 9263.216323985398], [0.06100944383069873, 7603.988565153908], [113.49903786554933, 8330.644745018333]]),
}


def saved_residuals(f, *args):
    """``[(shape, why)]`` of what ``f``'s backward keeps beside its
    arguments, from jax's own report (``print_saved_residuals``: ``f32[4,8]
    named 'x' from ...``, or ``output of reduce_precision from <where>``,
    the wrap jax puts around a kept value)."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        print_saved_residuals(f, *args)
    kept = []
    for line in out.getvalue().splitlines():
        m = re.match(r"\w+\[([\d,]*)\] (.*)", line)
        if "from the argument" not in m.group(2):
            kept.append((tuple(int(n) for n in m.group(1).split(",") if n),
                         m.group(2)))
    return kept


@pytest.mark.parametrize("case", list(PARENTS))
def test_dq_dk_dv_from_rows_of_lse_are_the_parents(case):
    (s_q, s_k, heads, block, causal, dtype), want = PARENTS[case]
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(30), 4)
    q = jax.random.normal(kq, (2, s_q, heads, 64)).astype(dtype)
    k = jax.random.normal(kk, (2, s_k, heads, 64)).astype(dtype)
    v = jax.random.normal(kv, (2, s_k, heads, 64)).astype(dtype)
    w = jax.random.normal(kw, (2, s_q, heads, 64))
    grads = jax.jit(jax.grad(lambda q, k, v: (flash_attention(
        q, k, v, causal=causal, block_q=block, block_k=block,
        interpret=True).astype(jnp.float32) * w).sum(), argnums=(0, 1, 2)))(
            q, k, v)
    for g, (total, magnitude), name in zip(grads, want, "qkv"):
        g = np.asarray(g, np.float64)
        assert np.abs(g).sum() == pytest.approx(magnitude, rel=1e-9), f"d{name}"
        assert g.sum() == pytest.approx(total, abs=1e-9 * magnitude), f"d{name}"


@pytest.mark.parametrize("seq", [64, 160], ids=["4-pairs", "25-pairs"])
def test_the_rule_names_out_and_lse_and_dots_keeps_the_rows(seq, flash_kept):
    """The residuals of the differentiation rule are NAMED values where the
    enclosing block's chooser keeps the call: a policy that saves every name
    keeps ``out`` and ``lse`` as ``[batch, heads, seq]`` float32 rows — no
    ``[.., seq, 1]`` column (64 MB of lane padding a layer at the medium
    cell's shape) — and runs no kernel again. Of a call this cheap to make
    again (under the floor) a ``dots`` block keeps the rows alone and a
    ``full`` block nothing (``ops/remat.py`` has the rule and the
    measurements); outside a block, and under jax's own ``dots_saveable``,
    nothing is named or kept."""
    q = jnp.ones((2, seq, 4, 64), jnp.float32)
    policies = jax.checkpoint_policies

    def attend(q, k, v, block=None):
        with remat.block(block):
            return flash_attention(q, k, v, causal=True, block_q=32,
                                   block_k=32, interpret=True)

    def kept(policy, block=None):
        return sorted(shape for shape, _ in saved_residuals(jax.checkpoint(
            functools.partial(attend, block=block), policy=policy,
            prevent_cse=False), q, q, q))

    every = policies.save_only_these_names(*remat.NAMES)
    rows, both = [(2, 4, seq)], sorted([(2, seq, 4 * 64), (2, 4, seq)])
    assert kept(every) == kept(every, "full") == []
    assert kept(every, "dots") == kept(remat.policy("dots"), "dots") == rows
    assert kept(remat.policy("full"), "full") == []
    assert kept(policies.dots_saveable, "dots") == []
    flash_kept()  # the floor lowered, a chooser with room: both, both named
    for block in ("full", "dots"):
        assert kept(every, block) == kept(remat.policy(block), block) == both
    assert kept(every) == []  # no block, no candidate


def test_dots_keeps_the_sum_and_drops_the_product_it_was_made_from():
    """A projection's named result (after the bias) is kept; the product in
    front of the bias is then read by nothing and is no residual: the sum
    costs no byte. Products that carry no name are still kept
    (``dots_saveable`` is part of the policy)."""
    x, w, b = jnp.ones((4, 8)), jnp.ones((8, 16)), jnp.ones((16,))

    def block(x, w, b, named: bool):
        y = x @ w + b
        y = remat.name(y, remat.PROJECTION) if named else y
        return jnp.tanh(y).sum()

    for named in (True, False):
        kept = saved_residuals(jax.checkpoint(
            functools.partial(block, named=named), policy=remat.policy("dots"),
            prevent_cse=False), x, w, b)
        results = [why for shape, why in kept if shape == (4, 16)]
        assert len(results) == 1, kept  # the sum OR the product, never both
        assert ("(name)" in results[0]) == named


def test_a_tally_counts_what_was_named_and_only_while_open():
    x = jnp.ones((2, 3), jnp.bfloat16)
    assert all(set(kept) < set(remat.NAMES) for kept in remat.KEPT.values())
    remat.name(x, remat.PROJECTION)  # no block open: just the name
    with remat.block(None) as said:
        remat.name(x, remat.PROJECTION)
        with remat.block(None) as inner:
            remat.name(x.astype(jnp.float32), remat.FLASH_LSE, 8067.0)
        remat.name(x, remat.FFN_IN)
    assert inner.named == [(remat.FLASH_LSE, 24, 8067.0, None)]
    assert said.named == [(remat.PROJECTION, 12, None, None),
                          (remat.FFN_IN, 12, None, None)]
    with pytest.raises(AssertionError):
        remat.name(x, "a name the policy does not save")


def _grads(cfg, params, tokens):
    model = Transformer(cfg)

    def loss(p):
        return (model.apply({"params": p}, tokens).astype(jnp.float32)
                ** 2).mean()

    return jax.jit(jax.grad(loss))(params)


@pytest.mark.parametrize("seq", [32, 96], ids=["4-pairs", "36-pairs"])
@pytest.mark.parametrize("position", ["learned", "rope"])
@pytest.mark.parametrize("heads,kv_heads", [(16, 16), (32, 8)],
                         ids=["16-over-16", "32-over-8"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no-bias"])
def test_gradients_under_dots_equal_those_with_no_remat(
        monkeypatch, bias, heads, kv_heads, position, seq):
    """Float32, the kernels interpreted in blocks of 16: every gradient leaf
    of a two-layer stack under ``dots`` (sums and the kernel's ``lse`` rows
    kept by name, the rest recomputed) against the same stack with no remat,
    to float32 rounding."""
    monkeypatch.setattr(attention_module, "flash_attention", functools.partial(
        flash_attention, interpret=True, block_q=16, block_k=16))
    base = dict(vocab=64, d_model=heads * 32, n_heads=heads,
                n_kv_heads=kv_heads, n_layers=2, d_ff=64, max_seq=seq,
                bias=bias, position=position, attention_impl="flash")
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, 64, (2, seq), np.int32))
    # the tree's shapes from the model, its values drawn on the host (an
    # initialiser under jit is a third program a case): norm scales about one,
    # everything else — the biases too, so that where they are added shows —
    # normal at 0.02, as the model's own initialisers draw the kernels
    shapes = nn.unbox(jax.eval_shape(
        Transformer(TransformerConfig(**base)).init, jax.random.PRNGKey(0),
        tokens)["params"])
    params = jax.tree_util.tree_map_with_path(
        lambda path, p: ("scale" in jax.tree_util.keystr(path))
        + 0.02 * rng.standard_normal(p.shape, np.float32), shapes)
    want = _grads(TransformerConfig(**base), params, tokens)
    got = _grads(TransformerConfig(remat=True, remat_policy="dots", **base),
                 params, tokens)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=1e-5, atol=1e-6 * float(jnp.abs(w).max()) + 1e-12,
            err_msg=jax.tree_util.keystr(path))


def test_a_dots_stack_says_once_what_a_layer_names(monkeypatch):
    from easydl_tpu.models import transformer
    from easydl_tpu.utils import logging as easydl_logging

    monkeypatch.setattr(easydl_logging, "_logged_once", set())
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    monkeypatch.setattr(transformer.log, "handlers",
                        transformer.log.handlers + [handler])
    cfg = TransformerConfig(vocab=64, d_model=128, n_heads=4, n_layers=2,
                            d_ff=64, max_seq=64, remat=True,
                            remat_policy="dots")
    tokens = jnp.zeros((8, 64), jnp.int32)
    model = Transformer(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    jax.jit(jax.grad(lambda p: model.apply(p, tokens).sum()))(params)
    jax.jit(model.apply)(params, tokens)
    said = [message for message in said if message.startswith("remat dots:")]
    assert len(said) == 1, said
    # q, k, v, out of [8, 64, 128] float32 (the reference attention path:
    # no kernel, nothing of its own to name)
    each = f"projection {128 * 8 * 64 * 4 / 1e6:.1f} MB"
    assert f"no room stated, keeps by name {', '.join([each] * 4)} a " \
           "microbatch as traced" in said[0]
    assert said[0].endswith("beside its unnamed products; candidates left "
                            "out: none")


def test_dots_is_offered_none_of_what_full_keeps_by_name(monkeypatch):
    """The names a ``full`` block keeps its products by (PR 59: an attention
    projection's rows, a scan mixer's input maps, a shared expert's first
    products) are ``full``'s alone: ``dots`` keeps every one as the product it
    is, or as the ``projection`` it always named. With the floor at zero and
    a chooser with room without end open, a ``dots`` stack traces to the
    program it is with none open — not one name more — where ``full``'s
    holds the new names."""
    new = {remat.ROWS, remat.MIXER_IN, remat.SHARED_IN}
    assert new < set(remat.KEPT["full"]) and not new & set(remat.KEPT["dots"])
    assert set(remat.PRODUCTS.values()) <= set(remat.KEPT["full"])
    tokens = jnp.zeros((2, 32), jnp.int32)

    def traced(policy, chooser):
        model = Transformer(TransformerConfig(
            vocab=64, d_model=64, n_heads=2, n_layers=2, d_ff=128, max_seq=32,
            remat=True, remat_policy=policy))
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
        with remat.choosing(chooser):
            program = jax.jit(jax.grad(lambda p: model.apply(
                p, tokens).astype(jnp.float32).sum())).trace(params)
        return str(program.jaxpr), program.lower().as_text()

    names, text = traced("dots", None)
    monkeypatch.setattr(remat, "FLOOR_FLOP_PER_BYTE", 0)
    chooser = remat.Chooser(1 << 60)
    assert traced("dots", chooser)[1] == text and not chooser.seen
    assert not any(f"name={name}" in names for name in new)
    assert f"name={remat.ROWS}" in traced("full", remat.Chooser(1 << 60))[0]
