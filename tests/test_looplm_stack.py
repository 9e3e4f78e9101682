"""The looped language model (Ouro, arXiv:2510.25741) as a description of
``models/transformer.py``'s one stack: the runs of layers applied ``loops``
times over the same parameters, rotary positions, a norm before and after
every sub-layer, an untied head, an exit gate and the expected-exit
objective (``models/lm.py``) — against the plain float32 reference
(``benchmark/lib/reference_ouro.py``), against unrolled and unlooped copies
of itself, through either head, at head_dim 128 in the kernels (interpret
mode), from the command line and sharded.

CPU, ``size="test"``: 64 wide, 4 heads of 16, 3 layers, 2 and 4 passes.
Tolerances: float32 against float32 is the same mathematics in another order
of summation (1e-5 relative on values, a few 1e-4 on gradients).
"""

from __future__ import annotations

import copy
import functools
import importlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import out_and_grads

from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.core.sharding import flatten_dict, unbox
from easydl_tpu.core.train_loop import TrainConfig, Trainer
from easydl_tpu.models import lm
from easydl_tpu.models.ouro import describe
from easydl_tpu.models.registry import get_model, list_models
from easydl_tpu.models.transformer import (LoopStates, Transformer,
                                           TransformerConfig)
from easydl_tpu.ops import attention as attention_module
from easydl_tpu.ops.flash_attention import flash_attention
from easydl_tpu.ops.fused_xent import fused_softmax_xent
from easydl_tpu.ops.rope import apply_rope, rope_rows, rope_tables

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH = os.path.join(ROOT, "benchmark")


def _bench_lib(name):
    """A module of ``benchmark/lib`` (the package is not on tier-1's path)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(f"lib.{name}")


ref = _bench_lib("reference_ouro")
check_module = _bench_lib("check_ouro")
TEST = dict(size="test", seq_len=32, vocab=256)
HP = {"eps": 1e-6, "rope_theta": 1e6, "beta": 0.05}


def rel(a, r):
    a, r = np.asarray(a, np.float64), np.asarray(r, np.float64)
    return float(np.linalg.norm(a - r) / max(np.linalg.norm(r), 1e-30))


def _batch(seed=0, rows=2, seq=32, vocab=256):
    tokens = np.random.default_rng(seed).integers(0, vocab, (rows, seq + 1),
                                                  np.int32)
    return {"inputs": jnp.asarray(tokens[:, :-1]),
            "targets": jnp.asarray(tokens[:, 1:])}


def _params(bundle, seed=0, gate_scale=20.0):
    """Seeded parameters with the gate's weight enlarged: at 0.02 every exit
    probability sits near a half and a wrong distribution would pass."""
    params = unbox(jax.jit(bundle.init_fn)(jax.random.PRNGKey(seed)))
    return dict(params, exit_gate=params["exit_gate"] * gate_scale)


# --------------------------------------- the program against the reference
@pytest.fixture(scope="module", params=[2, 4], ids=["2-passes", "4-passes"])
def against_reference(request):
    """Program and reference, float32 at highest matmul precision: states,
    exit distribution, per-pass logits, loss, gradients."""
    passes = request.param
    bundle = get_model("ouro", total_ut_steps=passes, **TEST)
    params, batch = _params(bundle), _batch()
    model = Transformer(describe(total_ut_steps=passes, **TEST))
    hp = dict(HP, total_ut_steps=passes)
    plain = check_module.to_reference(params)

    # the program's states, logits, loss and gradients: one jitted program;
    # the reference's: one more
    @jax.jit
    def program(params, batch):
        out = model.apply({"params": params}, batch["inputs"],
                          return_hidden=True)
        return out, jax.value_and_grad(bundle.loss_fn, has_aux=True)(
            params, batch, None), jnp.einsum(
                "tbsd,dv->tbsv", out.hidden, params["head"]["kernel"])

    @jax.jit
    def reference(plain, batch):
        hidden, gates = ref.states(plain, batch["inputs"], hp)
        return (hidden, gates, ref.objective(
            hidden, gates, plain["head"], batch["targets"], hp["beta"]),
            ref.loss_and_grads(plain, batch["inputs"], batch["targets"],
                               hp)[1],
            jnp.stack([h @ plain["head"] for h in hidden]))

    with jax.default_matmul_precision("highest"):
        out, ((loss, aux), grads), logits = program(params, batch)
        hidden, gates, (loss_r, ce_r, p_r), grads_r, logits_r = reference(
            plain, batch)
    return dict(passes=passes, out=out, loss=loss, aux=aux,
                grads=check_module.to_reference(grads), hidden=hidden,
                gates=gates, loss_r=loss_r, ce_r=ce_r, p_r=p_r,
                grads_r=grads_r, logits=logits, logits_r=logits_r)


def test_every_passs_state_is_the_references(against_reference):
    a = against_reference
    assert isinstance(a["out"], LoopStates)
    assert a["out"].hidden.shape == (a["passes"], 2, 32, 64)
    for t in range(a["passes"]):
        assert rel(a["out"].hidden[t], a["hidden"][t]) < 1e-5, t
        assert rel(a["out"].gate[t], a["gates"][t]) < 1e-4, t


def test_every_passs_logits_are_the_references(against_reference):
    a = against_reference
    assert rel(a["logits"], a["logits_r"]) < 1e-5


def test_exit_distribution_is_the_references(against_reference):
    a = against_reference
    p = lm.exit_distribution(a["out"].gate)
    np.testing.assert_allclose(p, a["p_r"], atol=1e-5)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    assert float(p.std()) > 0.05  # the gate says something


def test_loss_and_counters_are_the_references(against_reference):
    a = against_reference
    assert float(a["loss"]) == pytest.approx(float(a["loss_r"]), rel=1e-5)
    for t in range(a["passes"]):
        assert float(a["aux"][f"loss_pass_{t}"]) == pytest.approx(
            float(a["ce_r"][t]), rel=1e-5)
    steps = (a["p_r"] * jnp.arange(1, a["passes"] + 1)[:, None, None]).sum(0)
    assert float(a["aux"]["exit_step_mean"]) == pytest.approx(
        float(steps.mean()), rel=1e-5)
    entropy = -(a["p_r"] * jnp.log(a["p_r"])).sum(0).mean()
    assert float(a["aux"]["exit_entropy"]) == pytest.approx(
        float(entropy), rel=1e-4)


def test_every_gradient_leaf_is_the_references(against_reference):
    a = against_reference
    mine = jax.tree_util.tree_leaves_with_path(a["grads"])
    theirs = jax.tree.leaves(a["grads_r"])
    assert len(mine) == len(theirs) == 5 + 3 * 11
    for (path, g), r in zip(mine, theirs):
        assert g.shape == r.shape
        assert rel(g, r) < 5e-4, jax.tree_util.keystr(path)


# ------------------------------------------- against copies of the program
def _plain_config(**over):
    """The test Ouro without loop and gate: a plain rotary sandwich
    decoder."""
    cfg = describe(**TEST)
    return TransformerConfig(**{**{f: getattr(cfg, f) for f in (
        "vocab", "d_model", "n_heads", "n_layers", "d_ff", "max_seq",
        "causal", "tied_head", "layers", "norm", "norm_eps",
        "norm_placement", "position", "rope_theta", "bias")}, **over})


def test_one_loop_without_gate_is_the_plain_stack():
    plain = lm.lm_bundle(_plain_config(), "plain")
    looped = lm.lm_bundle(_plain_config(loops=1, exit_gate=False), "looped")
    params = unbox(jax.jit(plain.init_fn)(jax.random.PRNGKey(0)))
    batch = _batch()
    a, _ = jax.jit(plain.loss_fn)(params, batch, None)
    b, _ = jax.jit(looped.loss_fn)(params, batch, None)
    assert float(a) == float(b)
    hidden = jax.jit(lambda p, x: Transformer(_plain_config()).apply(
        {"params": p}, x, return_hidden=True))(params, batch["inputs"])
    assert hidden.shape == (2, 32, 64)  # an array, as it always was


def test_loops_do_not_move_the_parameters_and_multiply_the_flops():
    one, four = _plain_config(loops=1), _plain_config(loops=4)
    assert one.param_count == four.param_count
    head = 256 * 64
    looped = sum(one.layer_params(l) for l in one.pattern) + head
    per_pass = 6.0 * looped + 12.0 * 3 * 64 * 32
    assert one.train_flops_per_token(32) == 6.0 * 64 + per_pass  # + ln_f
    assert four.train_flops_per_token(32) == 6.0 * 64 + 4 * per_pass
    n = sum(x.size for x in jax.tree.leaves(unbox(jax.eval_shape(
        lm.lm_bundle(four, "x").init_fn, jax.random.PRNGKey(0)))))
    assert n == four.param_count


def test_a_looped_layers_gradient_is_the_sum_over_an_unrolled_untied_copy():
    """Two passes over 3 layers against ONE pass over 6 layers whose
    parameters are the 3 repeated (and the final norm applied in between by
    hand): each shared leaf's gradient is the sum of its two copies'."""
    cfg = _plain_config(loops=2)
    model = Transformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.PRNGKey(1), jnp.zeros((1, 32), jnp.int32))["params"])
    batch = _batch(3)
    # a normed state's sum of squares is a constant: weigh it instead
    w = jnp.asarray(np.random.default_rng(4).normal(size=(2, 32, 64)),
                    jnp.float32)

    def looped(blocks):
        out = model.apply({"params": dict(params, blocks=blocks)},
                          batch["inputs"], return_hidden=True)
        return (out.hidden[-1].astype(jnp.float32) * w).sum()

    once = Transformer(_plain_config())

    def unrolled(first, second):
        h = once.apply({"params": dict(params, blocks=first)},
                       batch["inputs"], return_hidden=True)
        # the second pass starts from the normed state, not from tokens
        x = h
        for j in range(cfg.n_layers):
            layer = jax.tree.map(lambda a: a[j], second)
            x, _ = _block(cfg).apply({"params": layer}, x, True,
                                     rope_tables(32, cfg.head_dim,
                                                 cfg.rope_theta))
        x = _final_norm(x, params["ln_f"]["scale"])
        return (x.astype(jnp.float32) * w).sum()

    blocks = params["blocks"]
    with jax.default_matmul_precision("highest"):
        value, shared = jax.jit(jax.value_and_grad(looped))(blocks)
        value_unrolled, (g1, g2) = jax.jit(jax.value_and_grad(
            unrolled, (0, 1)))(blocks, blocks)
    assert float(value) == pytest.approx(float(value_unrolled), rel=1e-5)
    for (path, g), a, b in zip(jax.tree_util.tree_leaves_with_path(shared),
                               jax.tree.leaves(g1), jax.tree.leaves(g2)):
        assert rel(g, a + b) < 2e-4, jax.tree_util.keystr(path)
        assert rel(g, a) > 1e-2  # and not one copy's alone


def _block(cfg):
    from easydl_tpu.models.transformer import Block

    return Block(cfg, "attention", "swiglu")


def _final_norm(x, gain):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * gain


# ------------------------------------------------------------------ rotary
def test_rotary_scores_depend_on_position_differences_only():
    r = np.random.default_rng(0)
    q = jnp.asarray(r.normal(size=(16,)), jnp.float32)
    k = jnp.asarray(r.normal(size=(16,)), jnp.float32)
    cos, sin = rope_tables(64, 16, 1e6)
    rows = lambda x: jnp.broadcast_to(x, (1, 64, 1, 16))  # noqa: E731
    qr, kr = apply_rope(rows(q), cos, sin), apply_rope(rows(k), cos, sin)
    scores = jnp.einsum("qd,kd->qk", qr[0, :, 0], kr[0, :, 0])
    for shift in (0, 1, 7, 30):
        diagonal = jnp.diagonal(scores, offset=-shift)
        np.testing.assert_allclose(diagonal, diagonal[0], rtol=2e-4,
                                   atol=2e-5)
    assert float(jnp.abs(scores[5, 0] - scores[0, 5])) > 1e-3  # it rotates
    np.testing.assert_allclose(jnp.linalg.norm(qr, axis=-1),
                               jnp.linalg.norm(q), rtol=1e-5)


def test_a_prefix_of_a_sequence_gives_the_prefix_of_the_result():
    """Causal, rotary from position 0: what the benchmark's check relies on
    when it takes gradients on a prefix."""
    bundle = get_model("ouro", **TEST)
    params, batch = _params(bundle), _batch(rows=1)
    model = Transformer(describe(**TEST))
    def states(model):
        return jax.jit(lambda p, x: model.apply({"params": p}, x,
                                                return_hidden=True))

    whole = states(model)(params, batch["inputs"])
    part = states(Transformer(describe(**dict(TEST, seq_len=12))))(
        params, batch["inputs"][:, :12])
    np.testing.assert_allclose(part.hidden, whole.hidden[:, :, :12],
                               atol=2e-5)
    np.testing.assert_allclose(part.gate, whole.gate[:, :, :12], atol=2e-5)


@pytest.mark.parametrize("heads", [16, 3])
def test_rope_kernel_is_the_plain_rotation(heads):
    """``rope_rows`` (Pallas, interpret mode) on ``[B, S, H·128]`` against
    ``apply_rope``: values, and the gradient (the same kernel, sine
    negated)."""
    r = np.random.default_rng(heads)
    x = jnp.asarray(r.normal(size=(2, 64, heads, 128)), jnp.bfloat16)
    w = jnp.asarray(r.normal(size=x.shape), jnp.float32)
    cos, sin = rope_tables(64, 128, 1e6)

    def plain(x):
        return apply_rope(x, cos, sin)

    def kernel(x):
        return rope_rows(x.reshape(2, 64, -1), cos, sin, head_dim=128,
                         interpret=True).reshape(x.shape)

    # the same float32 arithmetic, fused differently: one bf16 ulp apart
    # in a few entries of a hundred thousand
    def weighed(y):
        return (y.astype(jnp.float32) * w).sum()

    rotated, (got,) = out_and_grads(kernel, weighed)(x)
    rotated_plain, (want,) = out_and_grads(plain, weighed)(x)
    np.testing.assert_allclose(np.asarray(rotated, np.float32),
                               np.asarray(rotated_plain, np.float32),
                               rtol=8e-3, atol=1e-6)
    assert rel(got, want) < 1e-2  # bf16 cotangents, rounded once each way
    with pytest.raises(ValueError, match="128-lane"):
        rope_rows(x[..., :64].reshape(2, 64, -1), cos[:, :64], sin[:, :64],
                  head_dim=64, interpret=True)


def test_rope_tables_are_the_references():
    cos, sin_signed = rope_tables(40, 16, 1e6)
    cos_r, sin_r = ref.rope_tables(40, 16, 1e6)
    np.testing.assert_array_equal(cos, cos_r)
    np.testing.assert_array_equal(sin_signed[:, 8:], sin_r[:, 8:])
    np.testing.assert_array_equal(sin_signed[:, :8], -sin_r[:, :8])


# ---------------------------------------------------------------- the head
@pytest.mark.parametrize("chunk", [16, 40, 7], ids=["chunks", "one", "ragged"])
def test_fused_head_with_row_weights_is_the_weighted_loss(chunk):
    """An untied head (``kernel.T``), per-row weights in, per-row losses
    back: the loss and the gradients of hidden, head AND weights against
    full logits; the weights' gradient is each row's own loss over the
    count."""
    r = np.random.default_rng(0)
    hidden = jnp.asarray(r.normal(size=(2, 40, 16)), jnp.float32)
    kernel = jnp.asarray(r.normal(size=(16, 64)), jnp.float32)  # [D, V]
    targets = jnp.asarray(r.integers(0, 64, (2, 40)), jnp.int32)
    targets = targets.at[1, 33:].set(-1)
    weights = jnp.asarray(r.uniform(size=(2, 40)), jnp.float32)
    mask = (targets != -1).astype(jnp.float32)

    def rows_of(hidden, kernel):
        logits = jnp.einsum("bsd,dv->bsv", hidden, kernel)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.maximum(targets, 0)) * mask

    def full(hidden, kernel, weights):
        return (rows_of(hidden, kernel) * weights).sum() / mask.sum()

    def fused(hidden, kernel, weights):
        return fused_softmax_xent(hidden, kernel.T, targets, weights=weights,
                                  chunk_size=chunk)[0]

    with jax.default_matmul_precision("highest"):
        loss, denom, rows = jax.jit(lambda h, k, w: fused_softmax_xent(
            h, k.T, targets, weights=w, chunk_size=chunk))(
                hidden, kernel, weights)
        _, got = jax.jit(jax.value_and_grad(fused, (0, 1, 2)))(
            hidden, kernel, weights)
        loss_full, want = jax.jit(jax.value_and_grad(full, (0, 1, 2)))(
            hidden, kernel, weights)
        rows_full = jax.jit(rows_of)(hidden, kernel)
    assert float(denom) == float(mask.sum())
    assert float(loss) == pytest.approx(float(loss_full), rel=1e-6)
    np.testing.assert_allclose(rows, rows_full, rtol=1e-5, atol=1e-6)
    for g, w in zip(got, want):
        assert rel(g, w) < 1e-5
    np.testing.assert_allclose(got[2], rows / denom, rtol=1e-6)


def test_looplm_loss_is_the_same_through_either_head(fused_head):
    bundle = get_model("ouro", **TEST)
    params, batch = _params(bundle), _batch()

    def f(params):
        # a program of its own a call: the head is chosen when it is traced
        return jax.jit(jax.value_and_grad(
            lambda p: bundle.loss_fn(p, batch, None), has_aux=True))(params)

    (full, aux_full), g_full = f(params)
    fused_head()
    (fused, aux_fused), g_fused = f(params)
    assert float(fused) == pytest.approx(float(full), rel=1e-5)
    for key in aux_full:
        assert float(aux_fused[key]) == pytest.approx(float(aux_full[key]),
                                                      rel=1e-5), key
    for key, g in flatten_dict(g_fused).items():
        assert rel(g, flatten_dict(g_full)[key]) < 1e-4, key


@pytest.mark.parametrize("heads,fused", [
    (1, False),   # one head of [2, 4096, 49152] float32: 1.5 GiB
    (2, True),    # two: 3 GiB
    (4, True),    # Ouro's four at the check's whole sequences: 6 GiB
])
def test_the_head_rule_counts_the_heads_a_microbatch_forms(heads, fused):
    assert lm.fused_head_by_shape(2, 4096, 49152, heads=heads) is fused
    # ... and the cell's microbatch of one sequence, four heads: 3 GiB
    assert lm.fused_head_by_shape(1, 4096, 49152, heads=4)
    # the check's gradient prefix alone would get full logits
    assert not lm.fused_head_by_shape(2, 1024, 49152, heads=4)


# ------------------------------------------------ the kernels at head_dim 128
@pytest.mark.parametrize("heads", [16, 3])
def test_flash_kernels_at_head_dim_128(heads):
    """One head a 128-lane block (nothing to slice), 16 and an odd 3 heads:
    forward and the three gradients in interpret mode against the XLA
    reference path."""
    r = np.random.default_rng(heads)
    q, k, v = (jnp.asarray(r.normal(size=(2, 256, heads, 128)) * 0.5,
                           jnp.float32) for _ in range(3))
    w = r.normal(size=q.shape).astype(np.float32)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=128,
                               block_k=128, interpret=True)

    def plain(q, k, v):
        return attention_module._reference_attention(
            q, k, v, causal=True, scale=128 ** -0.5)

    def weighed(out):
        return (out * w).sum()

    out, got = out_and_grads(kernel, weighed)(q, k, v)
    out_plain, want = out_and_grads(plain, weighed)(q, k, v)
    np.testing.assert_allclose(out, out_plain, atol=2e-5)
    for g, x in zip(got, want):
        assert rel(g, x) < 5e-4


def test_rotary_attention_takes_the_kernels_and_the_rope_kernel(monkeypatch):
    """``multihead_attention(rope=)`` on the flash path at head_dim 128 (the
    rotary kernel beside the flash kernels, both interpreted) equals the
    reference path's ``jax.numpy`` rotation."""
    monkeypatch.setattr(attention_module, "flash_attention",
                        functools.partial(flash_attention, interpret=True))
    monkeypatch.setattr(attention_module, "rope_rows",
                        functools.partial(rope_rows, interpret=True))
    r = np.random.default_rng(2)
    q, k, v = (jnp.asarray(r.normal(size=(1, 128, 2, 128)) * 0.5,
                           jnp.float32) for _ in range(3))
    tables = rope_tables(128, 128, 1e6)
    def attend(impl, rope):
        return jax.jit(functools.partial(
            attention_module.multihead_attention, causal=True, impl=impl,
            rope=rope))(q, k, v)

    got, want = attend("flash", tables), attend("reference", tables)
    np.testing.assert_allclose(got, want, atol=2e-5)
    plain = attend("reference", None)
    assert float(jnp.abs(want - plain).max()) > 1e-3


# ----------------------------------------------------- registry, run, mesh
def test_registry_builds_ouro_like_any_model():
    assert "ouro" in list_models()
    bundle = get_model("ouro", **TEST)
    assert bundle.name == "ouro-test-3l-x4"
    assert bundle.flops_per_sample_hint == describe(
        **TEST).train_flops_per_token(32) * 32


def test_the_cells_slice_counts_612_million_parameters():
    cfg = describe(size="2.6b", layer_types=["full_attention"] * 8)
    assert cfg.layer_params(cfg.pattern[0]) == 51_388_416
    assert cfg.param_count == 612_438_017
    assert cfg.head_dim == 128 and cfg.loops == 4
    assert cfg.train_flops_per_token(4096) == pytest.approx(
        15.504e9 + 6 * 4097, rel=1e-4)  # + final norm and gate
    full = describe(size="2.6b")
    assert full.n_layers == 48 and round(full.param_count / 1e6) == 2668


@pytest.mark.parametrize("bad,match", [
    (dict(d_model=100, n_heads=16), "d_model=100.*n_heads=16"),
    (dict(position="alibi"), "position"),
    (dict(norm_placement="post"), "norm_placement"),
    (dict(loops=0), "loops"),
])
def test_a_description_that_does_not_hold_together_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**bad).head_dim


def test_models_run_trains_ouro_from_the_command_line(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run(
        [sys.executable, "-m", "easydl_tpu.models.run", "--model", "ouro",
         "--steps", "3", "--batch", "4", "--model-arg", "size=test",
         "--model-arg", "seq_len=32", "--model-arg", "vocab=256"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "step 3 loss" in proc.stderr


def _trainer(spec, devices, **over):
    bundle = get_model("ouro", **TEST, **over)
    return Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(1e-3), config=TrainConfig(global_batch=4),
        mesh=build_mesh(spec, devices=devices[:spec.size])), bundle


def test_ouro_under_fsdp_steps_like_one_device(eight_devices):
    one, bundle = _trainer(MeshSpec(), eight_devices)
    batch = next(iter(bundle.make_data(4, seed=5)))
    state, metrics = one.train_step(one.init_state(), batch)
    two, _ = _trainer(MeshSpec(fsdp=2), eight_devices)
    specs = flatten_dict(jax.tree.map(lambda s: str(s.spec),
                                      two.state_shardings().params))
    assert "fsdp" in specs["blocks/gate/kernel"]
    assert "fsdp" in specs["head/kernel"]
    state2, metrics2 = two.train_step(two.init_state(), batch)
    assert float(metrics2["loss"]) == pytest.approx(float(metrics["loss"]),
                                                    rel=1e-5)
    for key in ("loss_pass_0", "loss_pass_3", "exit_step_mean",
                "exit_entropy"):
        assert float(metrics2[key]) == pytest.approx(float(metrics[key]),
                                                     rel=1e-4), key
    got = flatten_dict(jax.device_get(unbox(state2.params)))
    for key, want in flatten_dict(jax.device_get(unbox(state.params))).items():
        np.testing.assert_allclose(got[key], want, atol=2e-5, err_msg=key)


# ---------------------------------------------------------- names in the step
@pytest.fixture(scope="module")
def ouro_step_paths():
    import re

    bundle = get_model("ouro", **TEST, dtype="bfloat16", remat=True,
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


@pytest.mark.parametrize("scope", [
    "attention/multihead_attention/rope", "attention/ln_attn",
    "attention/ln_attn_out", "ffn/ln_mlp", "ffn/ln_mlp_out", "exit_gate"])
def test_looplm_names_reach_the_lowered_step(ouro_step_paths, scope):
    import re

    assert any(re.search(rf"(^|/){scope}(/|$)", p)
               for p in ouro_step_paths), scope


# ---------------------------- the benchmark's check, as tier-1 can run it
def _check(dtype, compute_dtype, tolerances=None, seed=0):
    with open(os.path.join(BENCH, "configs", "ouro-test.json")) as f:
        config = copy.deepcopy(json.load(f))
    config["kwargs"]["dtype"] = dtype
    if tolerances:
        config["check"]["tolerances"] = tolerances
    bundle = get_model(config["factory"], **config["kwargs"])
    trainer = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(1e-3),
        config=TrainConfig(global_batch=2, compute_dtype=compute_dtype,
                           seed=seed),
        mesh=build_mesh(MeshSpec.parse("dp=1"), devices=jax.devices()[:1]))
    return check_module.check(config, bundle, trainer, seed)


TIGHT = dict({f"state_rel_rms_pass_{t}": 5e-5 for t in range(4)},
             rope_table_abs=1e-6, exit_given_gate_abs=1e-6,
             loss_abs=5e-5, exit_abs=5e-5, grad_rel_rms_worst=1e-3)


@pytest.mark.parametrize("seed", [0, 7])
def test_the_check_holds_the_float32_program_to_rounding(seed, fused_head):
    """... through the fused weighted head, as on the chip: the rule's
    constant is lowered so that the check's whole sequences get it, and the
    check hands that choice to the gradient's prefix."""
    fused_head(chunk_rows=32)
    result = _check("float32", jnp.float32, TIGHT, seed=seed)
    assert result["ok"], result
    assert result["errors"]["grad_head_fused"] is True
    assert result["errors"]["grad_rel_rms_all"] > 0  # it did compare
