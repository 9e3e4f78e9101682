"""Mellum 2's mechanisms at test size on the CPU (the band path under a
window wider than a block against the written-out mask:
``tests/test_flash_window.py``; the softmax router: ``tests/test_moe.py``):
whole-head YaRN tables against HF's formulas written out here, GQA eight to
one through the band kernels, the four shares of 16 of 64 experts adding up
to the uncut reference layer, the whole model against
``benchmark/lib/reference_mellum`` and the description's counts against a
hand count of ISSUE 45's."""

import functools
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
from conftest import normal, written_out

from easydl_tpu.core import sharding as shd
from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.core.train_loop import TrainConfig, Trainer
from easydl_tpu.models.mellum import describe
from easydl_tpu.models.registry import get_model
from easydl_tpu.ops import attention as attention_module
from easydl_tpu.ops import flash_attention as flash_module
from easydl_tpu.ops import multihead_attention
from easydl_tpu.ops.moe import COUNTERS, ROUTERS, MoeMlp

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")


def _bench_lib(name):
    """A module of ``benchmark/lib`` (the package is not on tier-1's path)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(f"lib.{name}")


ref = _bench_lib("reference_mellum")
check_module = _bench_lib("check_mellum")


def _config(name="mellum-test"):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_gqa_eight_to_one_through_the_band_at_a_window_wider_than_a_block(
        monkeypatch):
    """The test size's window of 24 over blocks of 16 (the chip's 1,024 over
    512), eight query heads a key/value head, through the kernel path
    (interpreted): the band path beside a neighbour of two blocks, against
    the written-out attention with query head ``j`` reading ``j // 8``."""
    for name, rows in (("MAX_BLOCK", 16), ("BAND_ROWS", 32), ("BAND_SUB", 8)):
        monkeypatch.setattr(flash_module, name, rows)
    assert flash_module.choose_blocks(128, 128, True, window=24) == (
        flash_module.Band(rows=32, sub=8, reach=32),) * 3
    monkeypatch.setattr(
        attention_module, "flash_attention",
        functools.partial(flash_module.flash_attention, interpret=True))
    q, k, v = normal(8, (1, 128, 16, 16), *[(1, 128, 2, 16)] * 2)
    out = jax.jit(functools.partial(
        multihead_attention, causal=True, impl="flash", window=24))(q, k, v)
    want = written_out(
        np.asarray(q, np.float64), np.repeat(np.asarray(k, np.float64), 8, 2),
        np.repeat(np.asarray(v, np.float64), 8, 2), 24)
    np.testing.assert_allclose(np.asarray(out), want, atol=5e-5)


# ------------------------------------------------------------------ rotary
def hf_yarn_tables(seq, rot, p):
    """HF's ``_compute_yarn_parameters`` and the rotary module's tables,
    written out in numpy float64."""
    base, factor = p["rope_theta"], p["factor"]
    original = p["original_max_position_embeddings"]

    def find_correction_dim(num_rotations):
        return (rot * math.log(original / (num_rotations * 2 * math.pi))
                ) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(p["beta_fast"])), 0)
    high = min(math.ceil(find_correction_dim(p["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, rot, 2) / rot)
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    inv_freq = (1 / (factor * pos_freqs)) * (1 - extrapolation_factor) \
        + (1 / pos_freqs) * extrapolation_factor
    freqs = np.arange(seq)[:, None] * inv_freq[None, :]
    emb = np.concatenate([freqs, freqs], -1)
    return np.cos(emb) * p["attention_factor"], \
        np.sin(emb) * p["attention_factor"]


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_both_rotary_tables_over_the_whole_head_are_hfs(kind):
    """The description's two schemes at the published numbers: YaRN with the
    stated attention factor (= 0.1 ln 16 + 1) and the default one at theta
    5e5, both over all 128 dimensions."""
    p = _config("mellum2-12b-a2.5b")["rope_parameters"][kind]
    cfg = describe(**_config("mellum2-12b-a2.5b")["kwargs"])
    scheme = cfg.attention_kind(kind).rope
    assert scheme.rotary_dim == 0 and scheme.theta == 500000.0
    seq, d = 512, 128
    if kind == "full_attention":
        assert p["attention_factor"] == pytest.approx(0.1 * math.log(16) + 1)
        assert dict(scheme.yarn)["attention_factor"] == p["attention_factor"]
        cos_hf, sin_hf = hf_yarn_tables(seq, d, p)
    else:
        assert scheme.yarn is None
        angle = np.arange(seq)[:, None] * p["rope_theta"] ** (
            -np.arange(0, d, 2) / d)
        emb = np.concatenate([angle, angle], -1)
        cos_hf, sin_hf = np.cos(emb), np.sin(emb)
    cos, sin_signed = scheme.tables(seq, d)
    assert cos.shape == sin_signed.shape == (seq, d)
    sign = np.where(np.arange(d) < d // 2, -1.0, 1.0)
    # float32 angles at positions up to 511 against float64's
    np.testing.assert_allclose(np.asarray(cos), cos_hf, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sin_signed) * sign, sin_hf,
                               atol=1e-4)
    # the reference's tables are the same formulas
    cos_r, sin_r, rot = ref.rope_tables(seq, d, p)
    assert rot == d
    np.testing.assert_allclose(np.asarray(cos_r), cos_hf, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sin_r), sin_hf, atol=1e-4)


# -------------------------------------------------------------- the shares
def test_four_shares_of_16_of_64_add_up_to_the_uncut_reference_layer():
    """64 experts over 4 shares, top-8, nothing shared: the four parts —
    what every chip computes alike (the router) counted once, in the weights
    — equal the reference's uncut layer."""
    d, f, total, k = 32, 16, 64, 8
    x, = normal(0, (2, 24, d))
    whole = MoeMlp(experts_total=total, experts_held=(0, total), d_ff=f,
                   shared_d_ff=0, k=k, router=ROUTERS[2])
    params = shd.unbox(jax.jit(whole.init)(jax.random.PRNGKey(1), x))["params"]
    assert sorted(params) == ["router", "w_down", "w_gate", "w_up"]
    p_ref = {"router": params["router"], "e_gate": params["w_gate"],
             "e_up": params["w_up"], "e_down": params["w_down"]}
    hp = {"experts_held": (0, total), "k": k}
    want = jax.jit(lambda x, p: ref.moe(x, p, hp)[0])(x, p_ref)
    # a layer's result is small at seeded weights: hold the parts to a
    # ten-thousandth of its largest entry
    tol = 1e-4 * float(np.abs(np.asarray(want)).max())
    assert tol > 5e-8

    def layer(module):
        return jax.jit(lambda p, x: module.apply({"params": p}, x))

    parts, dropped, rows = [], 0.0, 0.0
    for lo in range(0, total, 16):
        share = whole.clone(experts_held=(lo, lo + 16))
        mine = dict(params, **{name: params[name][lo:lo + 16]
                               for name in ("w_gate", "w_up", "w_down")})
        y, counters, _ = layer(share)(mine, x)
        # the reference's share is the same part
        part = jax.jit(lambda x, p: ref.moe(
            x, p, {"experts_held": (lo, lo + 16), "k": k})[0])(x, {
                "router": mine["router"], "e_gate": mine["w_gate"],
                "e_up": mine["w_up"], "e_down": mine["w_down"]})
        np.testing.assert_allclose(np.asarray(y), np.asarray(part), atol=tol)
        parts.append(y)
        dropped += float(counters[0])
        rows += float(counters[1])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want),
                               atol=tol)
    assert dropped == 0.0
    assert rows == pytest.approx(k)  # every choice fell on exactly one share
    # and the whole layer alone gives the same
    y, _, _ = layer(whole)(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=tol)


# --------------------------------------------------------- the whole model
@pytest.fixture(scope="module")
def float32_check():
    """``lib/check_mellum.check`` at the test size with float32 compute: the
    program against the reference on seeded weights."""
    config = _config()
    config["kwargs"] = dict(config["kwargs"], dtype="float32")
    bundle = get_model(config["factory"], **config["kwargs"])
    trainer = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(1e-3),
        config=TrainConfig(global_batch=4, compute_dtype=jnp.float32),
        mesh=build_mesh(MeshSpec(), devices=jax.devices()[:1]))
    return check_module.check(config, bundle, trainer, seed=2147483659)


@pytest.mark.parametrize("what,limit", [
    ("loss_abs", 2e-5), ("state_rel_rms_layer_0", 1e-5),
    ("state_rel_rms_layer_1", 1e-5), ("state_rel_rms_layer_2", 1e-5),
    ("state_rel_rms_layer_3", 1e-5), ("state_rel_rms_layer_4", 1e-5),
    ("state_rel_rms_final", 1e-5), ("token_rel_max", 5e-5),
    ("grad_rel_rms_worst", 2e-4), ("grad_rel_rms_all", 1e-4),
    ("rope_table_abs", 1e-6), ("router_logits_abs", 1e-5),
    ("window_position_rel_max", 1e-5), ("window_edge_rel_max", 1e-5),
    ("moe_dropped", 0.0), ("chosen_not_top8_share", 0.0),
    ("chosen_sets_differ_share", 0.0),
])
def test_program_against_reference_mellum(float32_check, what, limit):
    """Loss, every layer's state, every gradient leaf (the worst of them),
    the router's logits and chosen sets, tables, band, counter."""
    assert float32_check["errors"][what] <= limit, float32_check["errors"]


def test_the_check_reports_the_softmax_routers_counters(float32_check):
    counters = float32_check["counters"]
    assert list(counters) == list(COUNTERS) + ["router_chosen_mass"]
    # the layers' mean: 4 of 16 held at top-4 is a row a token on average;
    # the chosen four hold more of the softmax than their 4 / 16
    assert 0.0 < counters["moe_rows_per_token"] < 4.0
    assert 4 / 16 < counters["router_chosen_mass"] < 1.0
    assert counters["router_entropy"] < math.log(16)


def test_every_gradient_leaf_was_compared(float32_check):
    cfg = describe(**_config()["kwargs"])
    params = jax.jit(get_model("mellum", **_config()["kwargs"]).init_fn)(
        jax.random.PRNGKey(0))
    mapped = check_module.to_reference(shd.unbox(params))
    # the program stacks a run's layers; nothing is left out of the map
    assert sum(x.size for x in jax.tree.leaves(mapped)) == sum(
        x.size for x in jax.tree.leaves(shd.unbox(params)))
    # per layer: 2 norms + 4 attention + the router + 3 expert leaves; 3
    # outside
    assert len(jax.tree.leaves(mapped)) == 3 + 5 * 10 and cfg.n_layers == 5


def test_layer_params_and_flops_against_the_hand_count():
    """ISSUE 45's count: one chip's share of Mellum2-12B-A2.5B."""
    kwargs = _config("mellum2-12b-a2.5b")["kwargs"]
    cfg = describe(**kwargs)
    d, hd, heads, kv, f = 2304, 128, 32, 4, 896
    attention = 2 * d * heads * hd + 2 * d * kv * hd
    assert attention == 21_233_664 and 3 * d * f == 6_193_152
    layer = attention + 2 * d + d * 64 + 16 * 3 * d * f
    assert layer == 120_476_160
    for kind in cfg.pattern:
        assert cfg.layer_params(kind) == layer
    total = 2 * 24576 * d + d + 4 * layer
    assert cfg.param_count == total and round(total / 1e5) == 5952
    shapes = jax.eval_shape(get_model("mellum", **kwargs).init_fn,
                            jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(
        shd.unbox(shapes))) == total
    # active: a token meets k * held / total = 2 routed experts here
    active = attention + 2 * d + d * 64 + 2 * 3 * d * f
    assert cfg.layer_params(cfg.pattern[0], active=True) == active
    seq = 8192
    per_token = 6.0 * (4 * active + 24576 * d + d) \
        + 12.0 * heads * hd * (seq + 3 * 1024)
    assert cfg.train_flops_per_token(seq) == pytest.approx(per_token)
    # the model as published: 12.15B in all, 2.44B a token
    whole = describe()
    assert round(whole.param_count / 1e7) == 1215
    met = sum(whole.layer_params(l, active=True) for l in whole.pattern) \
        + 2 * 98304 * d + d
    assert round(met / 1e7) == 244


def test_described_kinds_and_refusals():
    cfg = describe(size="test", seq_len=64, vocab=256)
    assert [r for r in cfg.runs] == [
        (("sliding_attention", "moe"), 3), (("full_attention", "moe"), 1),
        (("sliding_attention", "moe"), 1)]
    assert cfg.head_dim == 16 and cfg.n_heads == 4 and cfg.kv_heads == 2
    swa = cfg.attention_kind("sliding_attention")
    assert (swa.n_heads, swa.window, swa.rope.rotary_dim, swa.gate) \
        == (0, 24, 0, False) and swa.rope.yarn is None
    full = cfg.attention_kind("full_attention")
    assert (full.n_heads, full.window, full.rope.rotary_dim) == (0, 0, 0)
    assert dict(full.rope.yarn)["factor"] == 16.0
    assert cfg.moe.router == "linear-softmax-renormalised" \
        and cfg.moe.shared_d_ff == 0 and cfg.moe.scaling == 1.0 \
        and not cfg.moe.selection_bias
    assert cfg.counters == COUNTERS + ("router_chosen_mass",)
    # the one start of its own: the embedding table at unit scale (every
    # other description's, and every other map here, is normal 0.02)
    assert cfg.embedding_init_std == 1.0
    from easydl_tpu.models.laguna import describe as laguna

    assert laguna(size="test").embedding_init_std == 0.02
    leaves = shd.unbox(jax.jit(get_model(
        "mellum", size="test", seq_len=64, vocab=256).init_fn)(
            jax.random.PRNGKey(0)))
    assert np.asarray(leaves["tok_emb"]["embedding"]).std() \
        == pytest.approx(1.0, rel=0.05)
    assert np.asarray(leaves["head"]["kernel"]).std() \
        == pytest.approx(0.02, rel=0.05)
    from easydl_tpu.models.mellum import SIZES

    assert all(size["norm_topk_prob"] for size in SIZES.values())
    with pytest.raises(ValueError, match="layers are"):
        describe(size="test", layer_types=["attention"])
    with pytest.raises(ValueError, match="'sparse' FFN"):
        describe(size="test", mlp_layer_types=["dense"] + ["sparse"] * 4)
    with pytest.raises(TypeError):
        describe(size="test", heads_per_layer=[4] * 5)
