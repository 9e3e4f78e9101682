"""ZAYA1's mechanisms at test size on the CPU: the whole model against
``benchmark/lib/reference_zaya`` in float32 (loss, every layer's state and
router state, every gradient leaf), the expert layer's two shares adding up to
the uncut reference layer, the layer scan with the router state in its carry
against a Python loop over the layers, top-1 with the skip choice dropping
nothing, the convolutions and the value shift never reading a later token,
the description's counts against a hand count of ISSUE 35's numbers — and
Laguna's step program, which shares ``ops/moe.py`` and the block, still the
parent's op for op."""

import importlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import normal

from easydl_tpu.core import sharding as shd
from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.core.train_loop import TrainConfig, Trainer
from easydl_tpu.models import transformer
from easydl_tpu.models.registry import get_model, list_models
from easydl_tpu.models.zaya import describe
from easydl_tpu.ops import moe

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
sys.path.insert(0, HERE)
from gpt2_fingerprint import SMALL, _step_sha256  # noqa: E402


def _bench_lib(name):
    """A module of ``benchmark/lib`` (the package is not on tier-1's path)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(f"lib.{name}")


ref = _bench_lib("reference_zaya")
check_module = _bench_lib("check_zaya")
SEQ = 48


def _config(name="zaya1-test"):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# --------------------------------------------------------- the whole model
@pytest.fixture(scope="module")
def float32_check():
    """``lib/check_zaya.check`` at the test size with float32 compute: the
    program against the reference on seeded weights."""
    config = _config()
    config["kwargs"] = dict(config["kwargs"], dtype="float32")
    bundle = get_model(config["factory"], **config["kwargs"])
    trainer = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(1e-3),
        config=TrainConfig(global_batch=4, compute_dtype=jnp.float32),
        mesh=build_mesh(MeshSpec(), devices=jax.devices()[:1]))
    return check_module.check(config, bundle, trainer, seed=2147483653)


@pytest.mark.parametrize("what,limit", [
    ("loss_abs", 2e-5), ("state_rel_rms_layer_0", 1e-5),
    ("state_rel_rms_layer_1", 1e-5), ("state_rel_rms_layer_2", 1e-5),
    ("state_rel_rms_layer_3", 1e-5), ("router_state_rel_rms", 1e-5),
    ("state_rel_rms_final", 1e-5), ("token_rel_max", 5e-5),
    ("grad_rel_rms_worst", 2e-4), ("grad_rel_rms_all", 1e-4),
    ("router_logits_rel", 1e-5), ("cca_mix_token_rel_max", 1e-5),
    ("moe_dropped", 0.0), ("chosen_not_top1_share", 0.0),
    ("chosen_differ_share", 0.0),
])
def test_program_against_reference_zaya(float32_check, what, limit):
    """Loss, every layer's state and router state, every gradient leaf (the
    worst of them), the router's logits and choices, the convolutions and
    the value shift on equal inputs, the counter."""
    assert float32_check["errors"][what] <= limit, float32_check["errors"]


def test_every_gradient_leaf_was_compared(float32_check):
    kwargs = _config()["kwargs"]
    params = shd.unbox(jax.jit(get_model("zaya", **kwargs).init_fn)(
        jax.random.PRNGKey(0)))
    plain = check_module.to_reference(params)
    # nothing is left out of the map; per layer: 2 norms, 9 of the attention,
    # 2 x 4 residual vectors as two leaves, 9 of the router, 3 of the experts
    assert sum(x.size for x in jax.tree.leaves(plain)) \
        == sum(x.size for x in jax.tree.leaves(params))
    assert len(jax.tree.leaves(plain)) == 2 + 4 * 25 \
        == float32_check["errors"]["grad_leaves"]
    # the skip choice is taken, and the two halves of the experts are met
    counters = float32_check["counters"]
    assert 0.0 < counters["moe_skipped"] < 0.5
    assert 0.0 < counters["moe_rows_per_token"] < 1.0
    assert counters["router_state_rms"] > 0.0


# -------------------------------------------------------------- one layer
def _held(held):
    """The float32 test-size description of a share holding ``held``."""
    return describe(size="test", seq_len=SEQ, vocab=256,
                    layer_types=["hybrid"] * 3, experts_held=held)


def _layer_setup(held=(0, 16), seed=0):
    """A float32 test-size description holding ``held``, one layer's seeded
    parameters (all 16 experts'), a state and a router state."""
    cfg = _held(held)
    whole = describe(size="test", seq_len=SEQ, vocab=256,
                     layer_types=["hybrid"] * 3)
    x, r = normal(seed, (2, SEQ, whole.d_model),
                  (2, SEQ, whole.router_state_width))
    r = 0.3 * r
    scheme = whole.attention_kind("hybrid").rope
    rope = transformer.rope_tables(SEQ, whole.head_dim, scheme.theta,
                                   scheme.rotary_dim)
    params = shd.unbox(jax.jit(lambda key, x, r: transformer.Block(
        whole, "hybrid", "moe").init(key, (x, r), True, rope))(
            jax.random.split(jax.random.PRNGKey(seed), 3)[2], x, r))["params"]
    # seeded weights everywhere a fault could hide behind a one or a zero
    rng = np.random.default_rng(seed + 1)

    def stir(path, leaf):
        name = jax.tree_util.keystr(path)
        if any(s in name for s in ("_res_", "temperature", "gamma", "bias",
                                   "b1", "b2", "norm")):
            return np.asarray(leaf) + 0.2 * rng.standard_normal(
                leaf.shape, np.float32)
        # logits of a size at which every one of the 17 choices is taken
        return np.asarray(leaf) * 30 if "router_w" in name else leaf

    params = jax.tree_util.tree_map_with_path(stir, params)
    return cfg, params, x, r, rope


def _layer(cfg, rope):
    """``Block(cfg).apply`` on a layer's parameters, a state and a router
    state, its intermediates kept: one jitted program a description."""
    return jax.jit(lambda params, x, r: transformer.Block(
        cfg, "hybrid", "moe").apply({"params": params}, (x, r), True, rope,
                                    mutable=["intermediates"]))


def _share_of(params, lo, hi):
    return dict(params, moe=dict(params["moe"], **{
        name: params["moe"][name][lo:hi]
        for name in ("w_gate", "w_up", "w_down")}))


def _reference_layer(params, x, r, held=(0, 16)):
    hp = {"eps": 1e-5, "experts_held": held,
          "rope": {"rope_theta": 5000000.0, "partial_rotary_factor": 0.5}}
    @jax.jit
    def layer(x, r, p_r):
        with jax.default_matmul_precision("highest"):
            return ref.layer(x, r, p_r, hp)

    return layer(x, r, check_module.layer_to_reference(params)), hp


def test_the_shares_add_up_to_the_uncut_reference_layer():
    """16 experts over the two expert-parallel shares: the two layers'
    results — the skip choice's nothing in both — with what every chip
    computes alike (CCA, the scaled residual stream, the add's biases)
    counted once, equal the reference's uncut layer; the router state is
    every share's alike."""
    _, params, x, r, rope = _layer_setup()
    (want, r_want, _, own), hp = _reference_layer(params, x, r)
    @jax.jit
    def alike_of(x, p_r):
        with jax.default_matmul_precision("highest"):
            return ref.merge(ref.attention_residual(x, p_r, hp),
                             jnp.zeros_like(x), p_r["res_m"])

    alike = alike_of(x, check_module.layer_to_reference(params))
    parts, rows, skipped = [], 0.0, []
    for lo in (0, 8):
        cfg = _held((lo, lo + 8))
        ((y, state), counters), _ = _layer(cfg, rope)(
            _share_of(params, lo, lo + 8), x, r)
        named = dict(zip(cfg.counters, np.asarray(counters)))
        assert named["moe_dropped"] == 0.0
        rows += named["moe_rows_per_token"]
        skipped.append(named["moe_skipped"])
        parts.append(y)
        np.testing.assert_allclose(state, r_want, atol=2e-6)
    np.testing.assert_allclose(parts[0] + parts[1] - alike, want, atol=3e-5)
    # every token went one way: to a share, or nowhere
    assert skipped[0] == skipped[1] == float(np.mean(np.asarray(own) == 16))
    assert rows + skipped[0] == pytest.approx(1.0)
    assert 0 < skipped[0] < 1 and 0 < rows < 1


def test_top1_with_the_skip_choice_drops_nothing():
    """One share on its own: ``moe_dropped`` 0, and skipped + landed here +
    landed elsewhere is every token, by the layer's own choices."""
    cfg, params, x, r, rope = _layer_setup((0, 8), seed=3)
    ((_, _), counters), kept = _layer(cfg, rope)(_share_of(params, 0, 8), x, r)
    named = dict(zip(cfg.counters, np.asarray(counters)))
    chosen = np.asarray(kept["intermediates"]["moe"]["chosen"][0])[:, 0]
    logits = np.asarray(kept["intermediates"]["moe"]["router_logits"][0])
    assert logits.shape == (2 * SEQ, 17) and logits.dtype == np.float32
    np.testing.assert_array_equal(chosen, logits.argmax(-1))
    assert named["moe_dropped"] == 0.0 and named["moe_overflow"] == 0.0
    elsewhere = np.mean((chosen >= 8) & (chosen < 16))
    assert named["moe_rows_per_token"] == pytest.approx(np.mean(chosen < 8))
    assert named["moe_skipped"] == pytest.approx(np.mean(chosen == 16))
    assert named["moe_skipped"] + named["moe_rows_per_token"] + elsewhere \
        == pytest.approx(1.0)
    assert cfg.counters == moe.COUNTERS + ("moe_skipped",)


def test_the_router_weight_is_the_probability_and_gets_a_gradient():
    """``route_mlp``: float32 whatever the input's dtype, the weight the
    softmax probability itself (not renormalised to 1), the state handed on
    before its norm; the router's leaves get a gradient through the
    weight."""
    tokens, d, r, c = 32, 16, 8, 5
    down, down_bias, w1, w2, w3, h, state = normal(
        0, (d, r), (r,), (r, r), (r, r), (r, c), (tokens, d), (tokens, r))
    w = {"down": down, "down_bias": down_bias,
         "gamma": jnp.full((r,), 0.5), "norm": jnp.ones((r,)),
         "w1": w1, "b1": jnp.zeros((r,)), "w2": w2, "b2": jnp.zeros((r,)),
         "w3": w3}
    h = jnp.asarray(np.asarray(h).astype(jnp.bfloat16))
    out, logits, chosen, weights = jax.jit(
        lambda h, state, w: moe.route_mlp(h, state, w, 1e-5))(h, state, w)
    assert logits.dtype == weights.dtype == out.dtype == jnp.float32
    np.testing.assert_allclose(
        out, h.astype(jnp.float32) @ w["down"] + w["down_bias"] + 0.5 * state,
        rtol=1e-5, atol=1e-5)
    probs = jax.nn.softmax(logits, -1)
    np.testing.assert_array_equal(chosen[:, 0], jnp.argmax(probs, -1))
    np.testing.assert_allclose(weights[:, 0], jnp.max(probs, -1), rtol=1e-6)
    assert float(weights.min()) < 0.6  # not renormalised to 1
    grads = jax.jit(jax.grad(
        lambda w: moe.route_mlp(h, state, w, 1e-5)[3].sum()))(w)
    assert all(float(jnp.abs(g).sum()) > 0 for g in grads.values())


# ------------------------------------------------ the scan and its carry
def test_the_scan_with_the_router_state_equals_a_loop_over_the_layers():
    """``Transformer``'s ``nn.scan`` over three layers, its carry the pair
    (stream, router state), against the same ``Block`` applied three times
    in Python on slices of the stacked parameters, and the final norm."""
    kwargs = dict(size="test", seq_len=SEQ, vocab=256,
                  layer_types=["hybrid"] * 3, experts_held=(0, 8))
    cfg = describe(**kwargs)
    model = transformer.Transformer(cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, SEQ)))
    params = shd.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(1), tokens))["params"]
    hidden, sown = jax.jit(lambda params, tokens: model.apply(
        {"params": params}, tokens, return_hidden=True,
        mutable=["counters"]))(params, tokens)
    scheme = cfg.attention_kind("hybrid").rope
    rope = transformer.rope_tables(SEQ, cfg.head_dim, scheme.theta,
                                   scheme.rotary_dim)
    carry = (params["tok_emb"]["embedding"][tokens],
             jnp.zeros((2, SEQ, cfg.router_state_width)))
    summed = 0.0
    # one program for the three layers: the same Block on a layer's slice
    layer = jax.jit(lambda one, carry: transformer.Block(
        cfg, "hybrid", "moe").apply({"params": one}, carry, True, rope))
    for j in range(3):
        one = jax.tree.map(lambda a: a[j], params["blocks"])
        carry, counters = layer(one, carry)
        summed = summed + counters
    x, state = carry
    gain = params["ln_f"]["scale"]
    want = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * gain
    np.testing.assert_allclose(hidden, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(sown["counters"]["moe"][0], summed, rtol=1e-6)
    assert float(sown["counters"]["router_state_rms"][0]) == pytest.approx(
        float(jnp.sqrt(jnp.mean(state ** 2))), rel=1e-6)
    assert float(jnp.abs(state).max()) > 0


def test_nothing_reads_a_later_token():
    """Perturb token ``t``: no state before ``t`` moves — the two
    convolutions, the value shift and the router's state are causal — and
    position ``t + 1`` does (the shifts reach it even where attention were
    cut)."""
    kwargs = dict(size="test", seq_len=SEQ, vocab=256,
                  layer_types=["hybrid"] * 2, experts_held=(0, 16))
    model = transformer.Transformer(describe(**kwargs))
    tokens = np.random.default_rng(1).integers(0, 256, (1, SEQ))
    params = jax.jit(model.init)(jax.random.PRNGKey(2), jnp.asarray(tokens))
    hidden = jax.jit(lambda tokens: model.apply(params, tokens,
                                                return_hidden=True))
    t = 17
    other = tokens.copy()
    other[0, t] = (other[0, t] + 1) % 256
    a = np.asarray(hidden(jnp.asarray(tokens)))
    b = np.asarray(hidden(jnp.asarray(other)))
    np.testing.assert_array_equal(a[:, :t], b[:, :t])
    assert np.abs(a[:, t] - b[:, t]).max() > 0
    assert np.abs(a[:, t + 1] - b[:, t + 1]).max() > 0


def test_the_latent_mix_against_the_written_out_shifts():
    """``_latent_mix`` alone on seeded q, k, v: position 0 reads zeros
    behind it, position ``t`` its predecessor, by the reference's explicit
    pads."""
    _, params, x, r, rope = _layer_setup(seed=5)
    p_r = check_module.layer_to_reference(params)
    cfg = describe(size="test", seq_len=SEQ, vocab=256,
                   layer_types=["hybrid"])
    _, kept = _layer(cfg, rope)(params, x, r)
    kept = kept["intermediates"]
    h = kept["latent_in"][0]

    @jax.jit
    def written_out(h, p_r):
        with jax.default_matmul_precision("highest"):
            return (*ref.mixed_qk(h, p_r), ref.values(h, p_r["wv"]))

    q, k, v = written_out(h, p_r)
    for mine, want in ((kept["latent_q"][0], q), (kept["latent_k"][0], k),
                       (kept["latent_v"][0], v)):
        np.testing.assert_allclose(mine, want, rtol=2e-5, atol=2e-5)
    # the second key/value head at position 0 is the pad
    assert not np.asarray(kept["latent_v"][0])[:, 0, 1].any()
    assert np.asarray(kept["latent_v"][0])[:, 0, 0].any()


# ----------------------------------------------------------- the counts
def test_layer_params_and_flops_against_the_hand_count():
    """ISSUE 35's numbers: one chip's share of ZAYA1-8B."""
    kwargs = _config("zaya1-8b")["kwargs"]
    cfg = describe(**kwargs)
    d, hd, heads, kv, r, f = 2048, 128, 8, 2, 256, 2048
    cca = d * (2 * heads * hd + 2 * kv * hd) \
        + 3 * (heads + kv) * hd + (2 * hd + 1) * (heads + kv) * hd + kv
    assert cca == 5_575_682
    router = (d + 1) * r + 2 * r + 2 * (r + 1) * r + r * 17
    assert router == 660_992
    rest = 8 * d + 2 * d          # the two adds' vectors, the two norms
    layer = cca + router + 8 * 3 * d * f + rest
    assert cfg.layer_params(cfg.pattern[0]) == layer
    n_layers = len(kwargs["layer_types"])
    total = n_layers * layer + 32784 * d + d
    assert cfg.param_count == total
    assert n_layers != 6 or round(total / 1e5) == 7087
    shapes = jax.eval_shape(get_model("zaya", **kwargs).init_fn,
                            jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(
        shd.unbox(shapes))) == total
    # active: a token meets 8 / 17 of a held expert (top-1 over 17 choices)
    active = cca + router + round(8 / 17 * 3 * d * f) + rest
    assert cfg.layer_params(cfg.pattern[0], active=True) == active
    seq = 8192
    per_token = 6.0 * (n_layers * active + 32784 * d + d) \
        + 12.0 * n_layers * heads * hd * seq
    assert cfg.train_flops_per_token(seq) == pytest.approx(per_token)
    # the whole model: every expert held, the skip choice still one in 17
    whole = describe(size="8b")
    assert whole.n_layers == 40 and whole.vocab == 262272
    assert whole.layer_params(whole.pattern[0], active=True) \
        == cca + router + round(16 / 17 * 3 * d * f) + rest
    # the published counts bear the layer out, the embedding aside: "8.3B"
    # in all, "0.76B" active (one expert a token, the skip choice not taken)
    assert round((whole.param_count - 262272 * d) / 1e8) == 83
    assert round(40 * (cca + router + 3 * d * f + rest) / 1e7) == 75


def test_described_kinds_and_refusals():
    cfg = describe(size="test", seq_len=64, vocab=256)
    assert cfg.runs == ((("hybrid", "moe"), 4),)
    kind = cfg.attention_kind("hybrid")
    assert kind.latent == transformer.LatentMix(taps=(2, 2))
    assert (kind.rope.theta, kind.rope.rotary_dim) == (5e6, 4)
    assert cfg.n_heads * cfg.head_dim == cfg.d_model // 2
    assert cfg.kv_heads * cfg.head_dim == cfg.d_model // 8
    assert cfg.moe.router == moe.ROUTERS[1] and cfg.moe.choices == 17
    assert cfg.router_state_width == 16 and cfg.residual_scale
    assert cfg.tied_head and "zaya" in list_models()
    # the stacks that were there carry no router state and no scaled adds
    gpt = get_model("gpt", size="test", seq_len=32, vocab=256)
    assert gpt.name and transformer.TransformerConfig().router_state_width == 0
    with pytest.raises(ValueError, match="layers are"):
        describe(size="test", layer_types=["full_attention"])
    layer = moe.MoeMlp(experts_total=4, experts_held=(0, 4), d_ff=8,
                       shared_d_ff=0, k=2, router=moe.ROUTERS[1],
                       router_hidden=4)
    with pytest.raises(ValueError, match="with k=2"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)),
                   jnp.zeros((1, 4, 4)))
    with pytest.raises(ValueError, match="is none of"):
        moe.MoeMlp(experts_total=4, experts_held=(0, 4), d_ff=8,
                   shared_d_ff=0, k=1, router="softmax").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))


def test_zaya_trains_on_the_deployments_ep_mesh(eight_devices):
    """The deployment's own layout at the test size: all 16 experts held,
    sharded over ep=2 with the batch over dp=4 — each shard computes its
    eight experts' part and the parts are summed: the same loss as eight
    data-parallel devices give, finite and falling, nothing dropped, and
    landed + skipped is every token."""
    kwargs = dict(size="test", seq_len=32, vocab=256,
                  layer_types=["hybrid"] * 2)
    bundle = get_model("zaya", **kwargs)

    def trainer(spec):
        return Trainer(
            init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
            optimizer=optax.adam(1e-3),
            config=TrainConfig(global_batch=8, compute_dtype=jnp.float32),
            mesh_spec=spec)

    sharded = trainer(MeshSpec(dp=4, ep=2))
    state = sharded.init_state()
    flat = shd.flatten_dict(shd.unbox(state.params))
    held = {k: v for k, v in flat.items() if k.endswith("moe/w_gate")}
    assert held and all("ep" in str(w.sharding.spec) for w in held.values())
    batches = [next(iter(bundle.make_data(8, seed=0)))] * 6
    losses, metrics = [], []
    for batch in batches:
        state, m = sharded.train_step(state, batch)
        losses.append(float(m["loss"]))
        metrics.append({k: float(v) for k, v in m.items()})
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert all(m["moe_dropped"] == 0.0 for m in metrics)
    assert all(abs(m["moe_rows_per_token"] + m["moe_skipped"] - 1.0) < 1e-6
               for m in metrics)
    assert all(m["router_state_rms"] > 0 for m in metrics)
    one = trainer(MeshSpec(dp=8))
    _, first = one.train_step(one.init_state(), batches[0])
    assert abs(float(first["loss"]) - losses[0]) < 1e-4


# ------------------------------------------- what shares the changed code
def test_lagunas_step_program_is_the_parents_op_for_op():
    """Laguna's test description (bf16, remat ``full``, two microbatches of
    4 x 64) lowers to the text ``tests/goldens/laguna_step_program.json``
    holds the hash of (``gpt2_fingerprint._step_sha256``; the file says on
    which tree it was written and why: last by PR 46, whose chunk loops
    around the expert layer's kernels changed the program): what another model's description
    asks for — the expert layer's second router form, the block's carry, the
    scaled adds — is not in a program whose description does not."""
    with open(os.path.join(HERE, "goldens", "laguna_step_program.json")) as f:
        want = json.load(f)["laguna_step_program_sha256"]
    bundle = get_model("laguna", remat_policy="full", **SMALL)
    assert _step_sha256(bundle, MeshSpec(), jax.devices()[:1]) == want
