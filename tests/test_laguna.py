"""Laguna's mechanisms at test size on the CPU (the window in the flash
kernels against the written-out mask: ``tests/test_flash_window.py``): partial
rotary and YaRN tables against HF's formulas written out here, GQA 6
and 8 at head_dim 128, the expert layer's shares adding up to the uncut
reference layer, the whole model against ``benchmark/lib/reference_laguna``
and the description's counts against a hand count of ISSUE 31's table."""

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
from easydl_tpu.models import lm
from easydl_tpu.models.laguna import describe
from easydl_tpu.models.registry import get_model
from easydl_tpu.ops import attention as attention_module
from easydl_tpu.ops import multihead_attention
from easydl_tpu.ops.flash_attention import flash_attention
from easydl_tpu.ops.moe import MoeMlp
from easydl_tpu.ops.rope import apply_rope, rope_rows, rope_tables

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")


def _bench_lib(name):
    """A module of ``benchmark/lib`` (the package is not on tier-1's path)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(f"lib.{name}")


ref = _bench_lib("reference_laguna")
check_module = _bench_lib("check_laguna")


def _config(name="laguna-test"):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("heads", [48, 64])
def test_gqa_six_and_eight_query_heads_a_kv_head_at_head_dim_128(
        monkeypatch, heads):
    """Laguna's two head counts over 8 key/value heads of 128 through the
    kernel path (interpreted) against the written-out attention with each
    query head reading ``j // (heads / 8)``."""
    monkeypatch.setattr(attention_module, "flash_attention",
                        functools.partial(flash_attention, interpret=True))
    q, k, v = normal(heads, (1, 128, heads, 128), *[(1, 128, 8, 128)] * 2)
    window = 64 if heads == 64 else None
    out = jax.jit(functools.partial(
        multihead_attention, causal=True, impl="flash", window=window))(
            q, k, v)
    rep = heads // 8
    want = written_out(
        np.asarray(q, np.float64), np.repeat(np.asarray(k, np.float64), rep, 2),
        np.repeat(np.asarray(v, np.float64), rep, 2), window)
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


def test_yarn_and_partial_rotary_tables_are_hfs():
    p = _config("laguna-xs.2")["rope_parameters"]["full_attention"]
    assert p["attention_factor"] == pytest.approx(0.1 * math.log(64) + 1)
    seq, d, rot = 512, 128, 64
    cos_hf, sin_hf = hf_yarn_tables(seq, rot, p)
    yarn = {k: p[k] for k in ("factor", "original_max_position_embeddings",
                              "beta_fast", "beta_slow", "attention_factor")}
    cos, sin_signed = rope_tables(seq, d, float(p["rope_theta"]), rot, yarn)
    assert cos.shape == sin_signed.shape == (seq, d)
    np.testing.assert_allclose(np.asarray(cos[:, :rot]), cos_hf, atol=1e-4)
    sign = np.where(np.arange(rot) < rot // 2, -1.0, 1.0)
    # float32 angles at positions up to 511 against float64's
    np.testing.assert_allclose(np.asarray(sin_signed[:, :rot]) * sign, sin_hf,
                               atol=1e-4)
    # the last 64 dimensions pass
    assert np.all(np.asarray(cos[:, rot:]) == 1.0)
    assert np.all(np.asarray(sin_signed[:, rot:]) == 0.0)
    # fast dimensions keep f_i, slow ones take f_i / 64 (position 1's angle)
    angle = np.arctan2(sin_hf[1, :rot // 2], cos_hf[1, :rot // 2])
    f = p["rope_theta"] ** (-np.arange(0, rot, 2) / rot)
    assert angle[0] == pytest.approx(f[0]) \
        and angle[-1] == pytest.approx(f[-1] / 64, rel=1e-6)


def test_partial_rotation_both_forms_against_hfs_rotate_half():
    """``x_rot cos + rotate_half(x_rot) sin`` on the first 64 of 128
    dimensions, the rest passed: the jax.numpy form, and the kernel
    (interpreted) with its gradient."""
    p = _config("laguna-xs.2")["rope_parameters"]["full_attention"]
    seq, d, rot = 64, 128, 64
    cos_hf, sin_hf = hf_yarn_tables(seq, rot, p)
    yarn = {k: p[k] for k in ("factor", "original_max_position_embeddings",
                              "beta_fast", "beta_slow", "attention_factor")}
    tables = rope_tables(seq, d, float(p["rope_theta"]), rot, yarn)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, seq, 3, d))
    xr = np.asarray(x, np.float64)[..., :rot]
    half = np.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    want = np.concatenate([
        xr * cos_hf[None, :, None] + half * sin_hf[None, :, None],
        np.asarray(x, np.float64)[..., rot:]], -1)
    got = apply_rope(x, *tables, rot=rot)
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-5)

    def kernel(x):
        return rope_rows(x.reshape(2, seq, -1), *tables, head_dim=d, rot=rot,
                         interpret=True).reshape(x.shape)

    np.testing.assert_allclose(np.asarray(kernel(x)), want, atol=5e-5)
    g_kernel = jax.grad(lambda x: jnp.sum(kernel(x) ** 3))(x)
    g_plain = jax.grad(lambda x: jnp.sum(apply_rope(x, *tables, rot=rot) ** 3)
                       )(x)
    np.testing.assert_allclose(np.asarray(g_kernel), np.asarray(g_plain),
                               atol=1e-4)


@pytest.mark.parametrize("last", [False, True], ids=["leading", "last"])
def test_interleaved_pairing_both_forms_and_either_place(last):
    """Pairs ``(2i, 2i + 1)`` over the leading or the LAST 64 of 128
    dimensions, the rest passed (DeepSeek-V3's ``rope_interleave``): the
    tables, the jax.numpy form, and the kernel (interpreted) with its
    gradient, against the rotation written out."""
    seq, d, rot, theta = 64, 128, 64, 32e6
    cos, sin_signed = rope_tables(seq, d, theta, rot, interleaved=True,
                                  last=last)
    lo = d - rot if last else 0
    angle = np.arange(seq)[:, None] * theta ** (-np.arange(0, rot, 2) / rot)
    assert cos.shape == sin_signed.shape == (seq, d)
    np.testing.assert_allclose(np.asarray(cos[:, lo:lo + rot:2]),
                               np.cos(angle), atol=1e-5)
    np.testing.assert_allclose(np.asarray(cos[:, lo + 1:lo + rot:2]),
                               np.cos(angle), atol=1e-5)
    np.testing.assert_allclose(np.asarray(sin_signed[:, lo:lo + rot:2]),
                               -np.sin(angle), atol=1e-5)
    np.testing.assert_allclose(np.asarray(sin_signed[:, lo + 1:lo + rot:2]),
                               np.sin(angle), atol=1e-5)
    passed = np.ones(d, bool)
    passed[lo:lo + rot] = False
    assert np.all(np.asarray(cos)[:, passed] == 1.0)
    assert np.all(np.asarray(sin_signed)[:, passed] == 0.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, seq, 3, d))
    want = np.asarray(x, np.float64)
    a, b = want[..., lo:lo + rot:2].copy(), want[..., lo + 1:lo + rot:2].copy()
    c, s = np.cos(angle)[None, :, None], np.sin(angle)[None, :, None]
    want[..., lo:lo + rot:2] = a * c - b * s
    want[..., lo + 1:lo + rot:2] = b * c + a * s
    got = apply_rope(x, cos, sin_signed, rot=rot, interleaved=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)

    def kernel(x):
        return rope_rows(x.reshape(2, seq, -1), cos, sin_signed, head_dim=d,
                         rot=rot, interpret=True, interleaved=True
                         ).reshape(x.shape)

    np.testing.assert_allclose(np.asarray(kernel(x)), want, atol=2e-5)
    g_kernel = jax.grad(lambda x: jnp.sum(kernel(x) ** 3))(x)
    g_plain = jax.grad(lambda x: jnp.sum(apply_rope(
        x, cos, sin_signed, rot=rot, interleaved=True) ** 3))(x)
    np.testing.assert_allclose(np.asarray(g_kernel), np.asarray(g_plain),
                               atol=1e-4)
    # rotate-half tables behind a passed part are not written
    with pytest.raises(ValueError, match="interleaved"):
        rope_tables(seq, d, theta, rot, last=True)


# -------------------------------------------------------------- the shares
def test_the_shares_add_up_to_the_uncut_reference_layer():
    """16 experts over 4 shares: the four parts, the shared expert counted
    once, equal the reference's uncut layer."""
    d, f, total, k = 32, 16, 16, 4
    x, = normal(0, (2, 24, d))
    whole = MoeMlp(experts_total=total, experts_held=(0, total), d_ff=f,
                   shared_d_ff=f, k=k, scaling=2.5)
    params = shd.unbox(jax.jit(whole.init)(jax.random.PRNGKey(1), x))["params"]
    p_ref = {"router": params["router"], "e_gate": params["w_gate"],
             "e_up": params["w_up"], "e_down": params["w_down"],
             "s_gate": params["shared_gate"], "s_up": params["shared_up"],
             "s_down": params["shared_down"]}
    hp = {"experts_held": (0, total), "k": k, "scaling": 2.5}
    want, shared = jax.jit(lambda x, p: (
        ref.moe(x, p, hp)[0],
        ref.swiglu(x, p["s_gate"], p["s_up"], p["s_down"])))(x, p_ref)

    def layer(module):
        return jax.jit(lambda p, x: module.apply({"params": p}, x))

    parts, dropped, rows = [], 0.0, 0.0
    for lo in range(0, total, 4):
        share = MoeMlp(experts_total=total, experts_held=(lo, lo + 4),
                       d_ff=f, shared_d_ff=f, k=k, scaling=2.5)
        mine = dict(params, **{name: params[name][lo:lo + 4]
                               for name in ("w_gate", "w_up", "w_down")})
        y, counters, _ = layer(share)(mine, x)
        parts.append(y)
        dropped += float(counters[0])
        rows += float(counters[1])
    np.testing.assert_allclose(
        np.asarray(sum(parts) - 3 * shared), np.asarray(want), atol=2e-5)
    assert dropped == 0.0
    assert rows == pytest.approx(k)  # every choice fell on exactly one share
    # and the whole layer alone gives the same
    y, _, _ = layer(whole)(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)


# ------------------------------------------------ through the fused head
def test_moe_fused_head_runs(eight_devices, fused_head):
    """Laguna's test size (expert layers) through the fused chunked head:
    the counters reach the metrics beside the loss."""
    bundle = get_model("laguna", size="test", seq_len=32, vocab=128,
                       experts_held=(0, 4))
    fused_head(chunk_rows=32)  # 4 sequences: 8 positions a chunk
    assert lm.fused_head_by_shape(4, 32, 128)
    rng = jax.random.PRNGKey(1)
    params = jax.jit(bundle.init_fn)(rng)
    batch = next(iter(bundle.make_data(4, seed=5)))
    loss, metrics = jax.jit(bundle.loss_fn)(params, batch, rng)
    assert np.isfinite(float(loss))
    assert float(metrics["moe_dropped"]) == 0.0
    assert 0.0 < float(metrics["moe_rows_per_token"]) < 2.0


# ------------------------------------------------------- over an ep mesh
def test_laguna_trains_on_ep_mesh(eight_devices):
    """Laguna's test size, all 16 experts held, sharded over ep=4 with the
    batch over dp=2: each shard computes its four experts' part and the
    parts are summed — the same loss as one device gives, a finite,
    falling loss, nothing dropped."""
    kwargs = dict(size="test", seq_len=32, vocab=256)
    bundle = get_model("laguna", **kwargs)

    def trainer(spec):
        return Trainer(
            init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
            optimizer=optax.adam(1e-3),
            config=TrainConfig(global_batch=8, compute_dtype=jnp.float32),
            mesh_spec=spec)

    sharded = trainer(MeshSpec(dp=2, ep=4))
    state = sharded.init_state()
    flat = shd.flatten_dict(shd.unbox(state.params))
    held = {k: v for k, v in flat.items() if k.endswith("moe/w_gate")}
    assert held, list(flat)[:8]
    for key, w in held.items():
        assert "ep" in str(w.sharding.spec), (key, w.sharding.spec)
        assert w.shape[1] == 16  # every expert held, four a shard

    # the seeded parameters and the first step's rng (``Trainer.train_step``
    # folds the step into the state's), on the host, before the first step
    # donates them
    seeded, first_rng = jax.device_get(
        (state.params, jax.random.fold_in(state.rng, state.step)))
    # one batch six times over: something to learn
    batches = [next(iter(bundle.make_data(8, seed=0)))] * 6
    losses, metrics = [], []
    for batch in batches:
        state, m = sharded.train_step(state, batch)
        losses.append(float(m["loss"]))
        metrics.append({k: float(v) for k, v in m.items()})
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert all(m["moe_dropped"] == 0.0 for m in metrics)
    # all experts held: each of a token's 2 choices has a row somewhere
    assert all(abs(m["moe_rows_per_token"] - 2.0) < 1e-6 for m in metrics)

    # one device: the loss alone, on the same parameters and batch (no second
    # trainer: its state and step are programs this test does not read)
    first, _ = jax.jit(bundle.loss_fn)(seeded, batches[0], first_rng)
    assert abs(float(first) - losses[0]) < 1e-4


# --------------------------------------------------------- the whole model
@pytest.fixture(scope="module")
def float32_check():
    """``lib/check_laguna.check`` at the test size with float32 compute: the
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
    ("state_rel_rms_layer_3", 1e-5), ("state_rel_rms_layer_4", 1e-5),
    ("state_rel_rms_final", 1e-5), ("token_rel_max", 5e-5),
    ("grad_rel_rms_worst", 2e-4), ("grad_rel_rms_all", 1e-4),
    ("rope_table_abs", 1e-6), ("router_logits_abs", 1e-5),
    ("window_band_rel", 1e-5), ("moe_dropped", 0.0),
    ("chosen_sets_differ_share", 0.0),
])
def test_program_against_reference_laguna(float32_check, what, limit):
    """Loss, every layer's state, every gradient leaf (the worst of them),
    the router's logits and chosen sets, tables, band, counter."""
    assert float32_check["errors"][what] <= limit, float32_check["errors"]


def test_every_gradient_leaf_was_compared(float32_check):
    cfg = describe(**_config()["kwargs"])
    params = jax.jit(get_model("laguna", **_config()["kwargs"]).init_fn)(
        jax.random.PRNGKey(0))
    n_leaves = len(jax.tree.leaves(check_module.to_reference(
        shd.unbox(params))))
    # the program stacks a run's layers; nothing is left out of the map
    assert sum(x.size for x in jax.tree.leaves(check_module.to_reference(
        shd.unbox(params)))) == sum(
            x.size for x in jax.tree.leaves(shd.unbox(params)))
    # per layer: 2 norms + 5 attention + (3 dense | 7 sparse); 3 outside
    assert n_leaves == 3 + 5 * 7 + 3 + 4 * 7 and cfg.n_layers == 5


def test_layer_params_and_flops_against_the_hand_count():
    """ISSUE 31's table: one chip's share of Laguna-XS.2."""
    kwargs = _config("laguna-xs.2")["kwargs"]
    cfg = describe(**kwargs)
    d, hd, kv = 2048, 128, 8
    full = 2 * d * 48 * hd + 2 * d * kv * hd + d * 48 + 2 * d
    swa = 2 * d * 64 * hd + 2 * d * kv * hd + d * 64 + 2 * d
    sparse = d * 256 + 3 * d * 512 + 32 * 3 * d * 512
    assert cfg.layer_params(cfg.pattern[0]) == full + 3 * d * 8192 \
        == 29_360_128 + 98_304 + 4096 + 50_331_648
    assert cfg.layer_params(cfg.pattern[1]) == swa + sparse
    assert cfg.layer_params(cfg.pattern[4]) == full + sparse
    total = 2 * 12544 * d + d + (full + 3 * d * 8192) + 3 * (swa + sparse) \
        + (full + sparse)
    assert cfg.param_count == total and round(total / 1e5) == 6916
    shapes = jax.eval_shape(get_model("laguna", **kwargs).init_fn,
                            jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(
        shd.unbox(shapes))) == total
    # active: a token meets k * held / total = 1 routed expert here
    active = d * 256 + 3 * d * 512 + 1 * 3 * d * 512
    assert cfg.layer_params(cfg.pattern[1], active=True) == swa + active
    seq = 8192
    per_token = 6.0 * ((full + 3 * d * 8192) + 3 * (swa + active)
                       + (full + active) + 12544 * d + d) \
        + 12.0 * (2 * 48 * hd * seq + 3 * 64 * hd * 512)
    assert cfg.train_flops_per_token(seq) == pytest.approx(per_token)
    # where every expert is held, all k of a token's experts are active
    whole = describe(**dict(kwargs, experts_held=(0, 256)))
    assert whole.layer_params(whole.pattern[1], active=True) \
        == swa + d * 256 + 3 * d * 512 + 8 * 3 * d * 512


def test_described_kinds_and_refusals():
    cfg = describe(size="test", seq_len=64, vocab=256)
    assert [r for r in cfg.runs] == [
        (("full_attention", "swiglu"), 1), (("sliding_attention", "moe"), 3),
        (("full_attention", "moe"), 1)]
    assert cfg.head_dim == 16 and cfg.d_model // cfg.n_heads != 16
    swa = cfg.attention_kind("sliding_attention")
    assert (swa.n_heads, swa.window, swa.rope.rotary_dim) == (8, 16, 0)
    full = cfg.attention_kind("full_attention")
    assert (full.n_heads, full.window, full.rope.rotary_dim) == (6, 0, 8)
    with pytest.raises(ValueError, match="heads_per_layer"):
        describe(size="test", heads_per_layer=[6, 6, 6, 6, 6])
    with pytest.raises(ValueError, match="layers are"):
        describe(size="test", layer_types=["attention"])
    with pytest.raises(TypeError):
        get_model("gpt", size="test", moe_experts=4)
