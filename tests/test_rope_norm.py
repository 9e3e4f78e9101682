"""The per-head q/k RMSNorm inside the rotary kernel (``ops/rope.py
rope_rows(norm=)``: ``rope_norm_fwd`` / ``rope_norm_bwd``), in the Pallas
interpreter on the CPU against the ``jax.numpy`` form (``rms_norm`` then
``apply_rope``): values and both gradients, what ``multihead_attention``
hands the kernel and where it keeps the norm in ``jax.numpy``, a block of
SDAR's kind on both paths, and the gain's gradient under a mesh."""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import normal, out_and_grads
from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.ops import attention as attention_module
from easydl_tpu.ops import rope as rope_module
from easydl_tpu.ops.flash_attention import flash_attention
from easydl_tpu.ops.rope import apply_rope, rms_norm, rope_rows, rope_tables
from easydl_tpu.utils import logging as easydl_logging

EPS = 1e-6


def rel(a, r):
    a, r = np.asarray(a, np.float64), np.asarray(r, np.float64)
    return float(np.linalg.norm(a - r) / max(np.linalg.norm(r), 1e-30))


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, the jitted calls' inside."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def _names(fn, *args):
    return sorted(call.params["name"]
                  for call in _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr))


@contextlib.contextmanager
def _log(monkeypatch):
    """What ``ops/attention.py`` and ``ops/rope.py`` log inside the block,
    the lines a process logs once logged anew."""
    monkeypatch.setattr(easydl_logging, "_logged_once", set())
    records = []
    handler = logging.Handler()
    handler.emit = lambda record: records.append(record.getMessage())
    for module in (attention_module, rope_module):
        module.log.addHandler(handler)
    try:
        yield records
    finally:
        for module in (attention_module, rope_module):
            module.log.removeHandler(handler)


@pytest.fixture
def interpreted(monkeypatch):
    """``multihead_attention``'s kernels in the Pallas interpreter."""
    monkeypatch.setattr(attention_module, "flash_attention",
                        functools.partial(flash_attention, interpret=True))
    monkeypatch.setattr(attention_module, "rope_rows",
                        functools.partial(rope_rows, interpret=True))


def _gain(seed, d=128):
    # off one, so that a norm without it fails
    return 1.0 + 0.5 * normal(seed, (d,))[0]


# ------------------------------------------------------------- the kernel
@pytest.mark.parametrize("rot", [None, 64], ids=["whole", "rot64"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [16, 4, 1],
                         ids=["q-16-heads-looped", "q-4-heads", "k-1-head"])
def test_the_kernel_norms_and_rotates_as_jax_numpy_does(monkeypatch, heads,
                                                        dtype, rot):
    """Forward values, ``dx`` and ``dgain`` of the fused kernels against
    ``jax.grad`` of ``rms_norm`` then ``apply_rope`` in float32 on the same
    operands: to float32's rounding for float32 operands; for bfloat16 ones
    within ONE rounding of the float32 result (the unfused pair rounds
    twice), the gain's gradient a float32 sum. 64 rows in blocks of 32, so
    the gain's gradient is summed over grid cells as well; 16 heads are two
    turns of the kernels' loop over groups of 8, 4 and 1 are written out."""
    monkeypatch.setattr(rope_module, "_ROWS", 32)
    b, s, d = 2, 64, 128
    x, = normal(1, (b, s, heads * d), dtype=dtype)
    x = x * 1.7
    w, = normal(2, (b, s, heads * d))
    gain = _gain(3)
    cos, sin = rope_tables(s, d, 1e4, rot=rot)

    def kernel(x, gain):
        return rope_rows(x, cos, sin, head_dim=d, rot=rot, interpret=True,
                         norm=(gain, EPS))

    def plain(x, gain):
        heads_of = x.astype(jnp.float32).reshape(b, s, heads, d)
        return apply_rope(rms_norm(heads_of, gain, EPS), cos, sin,
                          rot=rot).reshape(x.shape)

    def weighed(out):
        return (out.astype(jnp.float32) * w).sum()

    out, (dx, dgain) = out_and_grads(kernel, weighed)(x, gain)
    want, (dx_want, dgain_want) = out_and_grads(plain, weighed)(x, gain)
    assert out.dtype == x.dtype and dx.dtype == x.dtype
    assert dgain.dtype == jnp.float32 and dgain.shape == gain.shape
    # one rounding to bfloat16: 2 ** -9 of a value, root mean square; the
    # cotangent the kernel sees is ``w`` rounded to the operand's dtype
    limit = 1e-6 if dtype == "float32" else 2.5e-3
    assert rel(out, want) < limit
    assert rel(dx, dx_want) < 2 * limit
    assert rel(dgain, dgain_want) < limit
    bare, _ = out_and_grads(plain, weighed)(x, jnp.ones_like(gain))
    assert rel(bare, want) > 0.1


def test_the_calls_results_are_one_and_two_and_the_bare_calls_stand(
        monkeypatch):
    """What ``benchmark/lib/hlo.flash_calls`` tells a flash kernel by — a
    name's end and its count of results — does not take these: the forward
    gives ONE array, the backward TWO (``dx`` and a ``[1, head_dim]`` float32
    partial sum a grid cell). Without a gain the calls are ``rope_fwd`` /
    ``rope_bwd`` as they were."""
    monkeypatch.setattr(rope_module, "_ROWS", 32)
    x, = normal(1, (2, 64, 256), dtype="bfloat16")
    cos, sin = rope_tables(64, 128, 1e4)

    def grad(norm):
        return jax.grad(lambda x, gain: rope_rows(
            x, cos, sin, head_dim=128, interpret=True,
            norm=norm and (gain, EPS)).astype(jnp.float32).sum(), (0, 1))

    calls = {call.params["name"]: [(v.aval.shape, str(v.aval.dtype))
                                   for v in call.outvars]
             for call in _pallas_calls(
                 jax.make_jaxpr(grad(True))(x, _gain(3)).jaxpr)}
    assert calls == {
        "rope_norm_fwd": [((2, 64, 256), "bfloat16")],
        "rope_norm_bwd": [((2, 64, 256), "bfloat16"),
                          ((2, 2, 1, 128), "float32")]}
    assert _names(grad(None), x, _gain(3)) == ["rope_bwd", "rope_fwd"]


# ------------------------------------------- what multihead_attention hands
def _attend(impl, q, k, v, tables, gains, **more):
    def loss(q, k, v, gq, gk):
        return attention_module.multihead_attention(
            q, k, v, causal=True, impl=impl, rope=tables,
            qk_norm=(gq, gk, EPS), **more)

    return loss, (q, k, v, *gains)


def test_attention_hands_the_gains_to_the_rotary_kernel(interpreted,
                                                        monkeypatch):
    """On the flash path at heads of 128 ``multihead_attention(qk_norm=)``
    norms q and k inside the rotary kernel — the two fused calls, no bare
    one, nothing under ``qk_rmsnorm`` — and says so; values and every
    gradient, the gains' among them, are the reference path's, which norms
    in ``jax.numpy``."""
    q, k, v = normal(4, (1, 128, 4, 128), (1, 128, 2, 128), (1, 128, 2, 128))
    w, = normal(5, (1, 128, 4, 128))
    tables = rope_tables(128, 128, 1e6)
    gains = (_gain(6), _gain(7))

    def weighed(out):
        return (out * w).sum()

    with _log(monkeypatch) as said:
        fused, args = _attend("flash", q, k, v, tables, gains)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *a: weighed(fused(*a)), argnums=(0, 1, 3, 4)))(*args)
        out, got = out_and_grads(fused, weighed)(*args)
    assert sum("qk norm: inside the rotary kernel (rope_norm_fwd / "
               "rope_norm_bwd)" in line for line in said) == 1, said
    names = [call.params["name"] for call in _pallas_calls(jaxpr.jaxpr)]
    assert sorted(n for n in names if n.startswith("rope")) == [
        "rope_norm_bwd"] * 2 + ["rope_norm_fwd"] * 2, names
    assert "qk_rmsnorm" not in str(jaxpr.pretty_print(name_stack=True))
    with _log(monkeypatch) as said:
        plain, _ = _attend("reference", q, k, v, tables, gains)
        want_out, want = out_and_grads(plain, weighed)(*args)
    assert any("qk norm: jax.numpy, not the rotary kernel: the XLA reference"
               in line for line in said), said
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    for name, g, x in zip("q k v q_gain k_gain".split(), got, want):
        assert rel(g, x) < 5e-5, name
    unnormed = attention_module.multihead_attention(
        q, k, v, causal=True, impl="reference", rope=tables)
    assert rel(unnormed, want_out) > 0.05


@pytest.mark.parametrize("case", ["a-head-of-64", "no-tables", "interleaved"])
def test_where_the_kernel_does_not_norm_jax_numpy_does_and_says_so(
        interpreted, monkeypatch, case):
    """A head that is not whole lane tiles, a kind without rotary positions
    and (``rope_rows`` itself) interleaved pairing, whose units are not
    heads: the norm runs in ``jax.numpy`` under ``qk_rmsnorm`` in front of
    the rotation, no fused call is made, the log says why, and the values
    are the written-out ones."""
    d = 64 if case == "a-head-of-64" else 128
    q, k, v = normal(8, (1, 128, 2, d), (1, 128, 2, d), (1, 128, 2, d))
    gains = (_gain(6, d), _gain(7, d))
    with _log(monkeypatch) as said:
        if case == "interleaved":
            cos, sin = rope_tables(128, d, 1e4, interleaved=True)

            def fn(x, gain):
                return rope_rows(x.reshape(1, 128, -1), cos, sin, head_dim=d,
                                 interpret=True, interleaved=True,
                                 norm=(gain, EPS)).reshape(x.shape)

            args = (q, gains[0])
            want = apply_rope(rms_norm(q, gains[0], EPS), cos, sin,
                              interleaved=True)
            why = "rope: interleaved pairing"
        else:
            tables = None if case == "no-tables" else rope_tables(128, d, 1e4)
            fn, args = _attend("flash", q, k, v, tables, gains)
            qn, kn = (rms_norm(x, g, EPS) for x, g in zip((q, k), gains))
            want = attention_module.multihead_attention(
                qn, kn, v, causal=True, impl="reference", rope=tables)
            why = "qk norm: jax.numpy, not the rotary kernel: " + (
                "no rotary tables" if tables is None
                else "a head of 64 is not whole lane tiles")
        jaxpr = jax.make_jaxpr(fn)(*args)
        got = jax.jit(fn)(*args)
    assert any(why in line for line in said), said
    assert not [call.params["name"] for call in _pallas_calls(jaxpr.jaxpr)
                if call.params["name"].startswith("rope_norm")]
    assert "qk_rmsnorm" in str(jaxpr.pretty_print(name_stack=True))
    np.testing.assert_allclose(got, want, atol=2e-5)


# ------------------------------------------------------ a block of the kind
def test_a_block_with_qk_norm_is_the_same_on_both_paths(interpreted):
    """A block of SDAR's kind at heads of 128 (2 over 1, block diffusion's
    mask over 2 x 128 rows): on the flash path — the norm inside the rotary
    kernel — and on the reference path, the output, the gradients of
    ``q_norm`` and ``k_norm`` (same names and shapes as ever) and the sown
    ``attn_q`` / ``attn_k``, the rows behind the norm and in front of the
    rotation, are the same."""
    from easydl_tpu.models.sdar import describe
    from easydl_tpu.models.transformer import Block

    seq = 128
    base = dataclasses.replace(
        describe(size="test", seq_len=seq, vocab=64), head_size=128,
        n_heads=2, n_kv_heads=1)
    tables = tuple(jnp.concatenate([t, t])
                   for t in rope_tables(seq, 128, 1e6))
    h, = normal(9, (1, 2 * seq, base.d_model))
    blocks = {impl: Block(dataclasses.replace(base, attention_impl=impl),
                          "full_attention", "moe")
              for impl in ("flash", "reference")}
    params = blocks["reference"].init(
        jax.random.PRNGKey(0), h, True, tables)["params"]
    import flax.linen as nn
    params = nn.unbox(params)
    assert params["q_norm"].shape == params["k_norm"].shape == (128,)
    params = dict(params, q_norm=_gain(6), k_norm=_gain(7))

    def run(impl):
        def loss(p):
            out, kept = blocks[impl].apply({"params": p}, h, True, tables,
                                           mutable=["intermediates"])
            return (out[0] if isinstance(out, tuple) else out).sum(), kept
        (value, kept), grads = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(params)
        return value, kept["intermediates"], grads

    assert "rope_norm_fwd" in _names(
        lambda p: blocks["flash"].apply({"params": p}, h, True, tables),
        params)
    value, kept, grads = run("flash")
    want_value, want_kept, want = run("reference")
    assert rel(value, want_value) < 1e-5
    for name in ("q_norm", "k_norm"):
        assert rel(grads[name], want[name]) < 2e-4, name
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        x = functools.reduce(lambda t, key: t[key.key], path, want)
        assert rel(g, x) < 2e-4 or float(jnp.abs(x).max()) < 1e-9, path
    for name, x in (("attn_q", "q"), ("attn_k", "k")):
        np.testing.assert_allclose(kept[name][0], want_kept[name][0],
                                   atol=1e-6)
    # the sown rows are NORMED rows: unit root mean square under the gain
    q = np.asarray(kept["attn_q"][0]) / np.asarray(params["q_norm"])
    np.testing.assert_allclose(np.sqrt((q ** 2).mean(-1)), 1.0, atol=1e-3)


# ------------------------------------------------------------ under a mesh
@pytest.mark.parametrize("mesh", ["tp=2", "dp=2,tp=2"])
def test_the_gains_gradient_under_a_mesh_is_the_unsharded_one(
        interpreted, eight_devices, mesh):
    """Heads over ``tp`` (and the batch over ``dp``): the kernels run per
    shard under ``jax.shard_map``, every shard takes the gains whole, and
    ``shard_map``'s transpose sums their gradient over the mesh — the
    one-device gradient."""
    q, k, v = normal(4, (2, 128, 4, 128), (2, 128, 2, 128), (2, 128, 2, 128))
    w, = normal(5, (2, 128, 4, 128))
    tables = rope_tables(128, 128, 1e6)
    fused, args = _attend("flash", q, k, v, tables, (_gain(6), _gain(7)))
    run = out_and_grads(fused, lambda out: (out * w).sum())
    want_out, want = run(*args)
    spec = MeshSpec.parse(mesh)
    with jax.set_mesh(build_mesh(spec, devices=eight_devices[:spec.size])):
        assert "shard_map" in str(jax.make_jaxpr(run)(*args))
        out, got = run(*args)
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    for name, g, x in zip("q k v q_gain k_gain".split(), got, want):
        assert rel(g, x) < 2e-5, name
