"""SDAR trained by block diffusion at test size on the CPU (the mask through
the kernels: ``tests/test_flash_blockmask.py``): the whole model against
``benchmark/lib/reference_sdar`` under the program's own draw — every layer's
state on both halves, the loss, every gradient leaf —, q and k after the
per-head norm and rotary at the repeated positions, what a bf16 router or a
dropped ``1 / t`` reads, that nothing leaks between the halves through the
whole stack, the eight shares of 16 of 128 experts adding up to the uncut
reference layer, the noise's distribution and its keys, a step replayed after
``restore_from``, and the description's counts against ISSUE 51's hand
count."""

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
from easydl_tpu.core.checkpoint import CheckpointManager
from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.core.train_loop import TrainConfig, Trainer
from easydl_tpu.models import lm
from easydl_tpu.models.registry import get_model
from easydl_tpu.models.sdar import describe
from easydl_tpu.models.transformer import (AttentionKind, Block, LatentMix,
                                           RopeScheme, Transformer,
                                           TransformerConfig)
from easydl_tpu.ops import moe as moe_module
from easydl_tpu.ops.moe import COUNTERS, ROUTERS, MoeMlp
from easydl_tpu.ops.rope import apply_rope

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")


def _bench_lib(name):
    """A module of ``benchmark/lib`` (the package is not on tier-1's path)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(f"lib.{name}")


ref = _bench_lib("reference_sdar")
check_module = _bench_lib("check_sdar")
SEED = 2147483659


def _config(name="sdar-test"):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def _float32(config):
    config["kwargs"] = dict(config["kwargs"], dtype="float32")
    bundle = get_model(config["factory"], **config["kwargs"])
    trainer = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(1e-3),
        config=TrainConfig(global_batch=4, compute_dtype=jnp.float32),
        mesh=build_mesh(MeshSpec(), devices=jax.devices()[:1]))
    return config, bundle, trainer


# --------------------------------------------------------- the whole model
@pytest.fixture(scope="module")
def float32_check():
    """``lib/check_sdar.check`` at the test size with float32 compute: the
    program against the reference on seeded weights, under the program's own
    draw."""
    return check_module.check(*_float32(_config()), seed=SEED)


@pytest.mark.parametrize("what,limit", [
    ("loss_abs", 2e-5), ("state_rel_rms_layer_0", 1e-5),
    ("state_rel_rms_layer_1", 1e-5), ("state_rel_rms_layer_2", 1e-5),
    ("state_rel_rms_final", 1e-5), ("row_rel_max", 5e-5),
    ("grad_rel_rms_worst", 2e-4), ("grad_rel_rms_all", 1e-4),
    ("rope_table_abs", 1e-6), ("router_logits_abs", 1e-5),
    ("mask_position_rel_max", 1e-5), ("mask_edge_rel_max", 1e-5),
    ("moe_dropped", 0.0), ("masked_share_abs", 0.0),
    ("chosen_not_top8_share", 0.0), ("chosen_sets_differ_share", 0.0),
])
def test_program_against_reference_sdar(float32_check, what, limit):
    """Loss, every layer's state on both halves, every gradient leaf (the
    worst of them), the router's logits and chosen sets, the tables at the
    repeated positions, the mask, the counters."""
    assert float32_check["errors"][what] <= limit, float32_check["errors"]


def test_the_check_reports_the_objectives_counters(float32_check):
    counters = float32_check["counters"]
    assert list(counters) == list(COUNTERS) + [
        "router_chosen_mass", "diffusion_masked_share", "diffusion_mean_t",
        "flash_live_pairs", "flash_block_pairs"]
    assert 0.2 < counters["diffusion_masked_share"] < 0.8
    assert 0.2 < counters["diffusion_mean_t"] < 0.8
    # 128 rows in ONE kernel block a half: the noised half's own pair, the
    # offset diagonal and the clean half's own, of four
    assert (counters["flash_live_pairs"], counters["flash_block_pairs"]) \
        == (3.0, 4.0)
    # 8 of 16 held at top-4: two rows a row on average
    assert 0.0 < counters["moe_rows_per_token"] < 4.0


def test_every_gradient_leaf_was_compared():
    kwargs = _config()["kwargs"]
    params = jax.jit(get_model("sdar", **kwargs).init_fn)(
        jax.random.PRNGKey(0))
    mapped = check_module.to_reference(shd.unbox(params))
    assert sum(x.size for x in jax.tree.leaves(mapped)) == sum(
        x.size for x in jax.tree.leaves(shd.unbox(params)))
    # per layer: 2 norms + 4 attention + 2 q/k gains + the router + 3
    # expert leaves; 3 outside
    assert len(jax.tree.leaves(mapped)) == 3 + 3 * 12


def test_a_bf16_router_fails_its_tolerance(monkeypatch):
    """The router's logits rounded to bf16 where float32 is stated read
    thousands of times ``router_logits_abs``' limit."""
    route = moe_module.route

    def rounded(*args, **kwargs):
        logits, chosen, weights = route(*args, **kwargs)
        return jax.lax.reduce_precision(logits, 8, 7), chosen, weights

    monkeypatch.setattr(moe_module, "route", rounded)
    config = _config()
    config["kwargs"] = dict(config["kwargs"], layer_types=["full_attention"])
    found = check_module.check(*_float32(config), seed=SEED)
    assert found["errors"]["router_logits_abs"] \
        > 10 * found["tolerances"]["router_logits_abs"]
    assert not found["ok"]


def _draw(config, seed=SEED, n=2):
    kwargs = config["kwargs"]
    x0 = jnp.asarray(np.random.default_rng(seed).integers(
        0, kwargs["vocab"], (n, kwargs["seq_len"]), dtype=np.int32))
    _, masked, t = lm.block_diffusion_noise(
        jax.random.PRNGKey(seed), x0, block=kwargs["block_length"],
        mask_id=kwargs["vocab"] - 1)
    return x0, masked, t


@pytest.fixture(scope="module")
def seeded():
    """The test size's seeded program in float32, its parameters under the
    reference's names, and a draw."""
    config, bundle, _ = _float32(_config())
    params = shd.unbox(jax.jit(bundle.init_fn)(jax.random.PRNGKey(SEED)))
    return (config, bundle, params, check_module.to_reference(params),
            ref.hyper(config), _draw(config))


def test_a_dropped_one_over_t_fails_the_loss(seeded):
    """The program's loss is the reference's WITH the ``1 / t``; without it
    the reference reads a loss the tolerance refuses by far."""
    config, bundle, params, plain, hp, (x0, masked, t) = seeded
    loss_p, _ = jax.jit(bundle.loss_fn)(
        params, {"inputs": x0, "targets": x0}, jax.random.PRNGKey(SEED))
    weighted = jax.jit(lambda p: ref.loss(p, x0, masked, t, hp))(plain)
    dropped = jax.jit(lambda p: ref.loss(p, x0, masked, jnp.ones_like(t),
                                         hp))(plain)
    limit = config["check"]["tolerances"]["loss_abs"]
    assert abs(float(loss_p) - float(weighted)) < 2e-5 < limit
    assert abs(float(loss_p) - float(dropped)) > 20 * limit


def test_pieces_assemble_the_whole_gradient(seeded):
    """``Pieces.loss_and_grads`` (the chain rule written out over jitted
    pieces, what the chip's check runs) is ``jax.grad`` of the whole loss."""
    _, _, _, plain, hp, (x0, masked, t) = seeded
    value, whole = ref.loss_and_grads(plain, x0, masked, t, hp)
    pieces = ref.Pieces(hp)
    mine_value, mine = pieces.loss_and_grads(plain, x0, masked, t)
    assert float(mine_value) == pytest.approx(float(value), abs=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(mine),
                            jax.tree.leaves(whole)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(a, b, atol=2e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_q_and_k_after_norm_and_rotary_at_the_repeated_positions(seeded):
    """On equal inputs, the program's q and k (the per-head RMSNorm with its
    gains sown by the block, then its rotary tables for ``[0 .. L-1, 0 ..
    L-1]``) against the reference's, as the worst single position's error;
    a noised token and its clean twin are rotated alike."""
    config, _, params, plain, hp, _ = seeded
    cfg = describe(**config["kwargs"])
    seq = config["kwargs"]["seq_len"]
    h, = normal(5, (1, 2 * seq, cfg.d_model))
    layer = jax.tree.map(lambda a: a[0], params["blocks"])
    # gains off one, so that a norm without them fails
    for name in ("q_norm", "k_norm"):
        layer[name] = 1.0 + 0.1 * normal(6, layer[name].shape)[0]
    p_ref = dict(plain["layers"][0], qn=layer["q_norm"], kn=layer["k_norm"])
    tables = check_module.program_tables(cfg, seq)["full_attention"]
    _, kept = Block(cfg, "full_attention", "moe").apply(
        {"params": layer}, h, True, tables, mutable=["intermediates"])
    normed = {name: value[0] for name, value in
              kept["intermediates"].items() if name.startswith("attn_")}
    # the block norms its input first; hand the reference the same
    q_r, k_r, _ = ref.normed_rotated(normed["attn_in"], p_ref, hp)
    for name, want in (("attn_q", q_r), ("attn_k", k_r)):
        got = apply_rope(normed[name], *tables)
        worst = jnp.max(jnp.sqrt(jnp.sum((got - want) ** 2, (0, 2, 3))
                                 / jnp.sum(want ** 2, (0, 2, 3))))
        assert float(worst) < 1e-5, name
        np.testing.assert_array_equal(
            np.asarray(tables[0][:seq]), np.asarray(tables[0][seq:]))
    plain_norm = ref.normed_rotated(
        normed["attn_in"], dict(p_ref, qn=jnp.ones_like(p_ref["qn"])), hp)[0]
    assert float(jnp.max(jnp.abs(plain_norm - q_r))) > 0.01


def test_nothing_leaks_between_the_halves_through_the_whole_stack(seeded):
    """Every layer's worth at once, through ``Transformer`` (inside float32
    rounding: a changed row moves the expert layer's sorted buffers, not
    another row's sums): the noised tokens of block ``b`` changed leave
    every clean row's final state and every other noised block's as it was;
    the clean tokens of block ``b`` changed leave the noised blocks up to
    ``b`` and the clean blocks before it."""
    config, _, params, _, _, (x0, masked, _) = seeded
    cfg = describe(**config["kwargs"])
    seq, block, b = x0.shape[1], cfg.block_diffusion, 7
    run = jax.jit(lambda rows: Transformer(cfg).apply(
        {"params": params}, rows, return_hidden=True))
    xt = jnp.where(masked, cfg.vocab - 1, x0)
    base = np.asarray(run(jnp.concatenate([xt, x0], 1)))
    blk = np.arange(seq) // block
    at = jnp.asarray(blk == b)[None]
    noised = np.asarray(run(jnp.concatenate(
        [jnp.where(at, (xt + 1) % cfg.vocab, xt), x0], 1)))
    same = np.concatenate([blk != b, np.ones(seq, bool)])
    np.testing.assert_allclose(noised[:, same], base[:, same], atol=2e-6)
    assert np.abs(noised[:, ~same] - base[:, ~same]).max() > 1e-3
    clean = np.asarray(run(jnp.concatenate(
        [xt, jnp.where(at, (x0 + 1) % cfg.vocab, x0)], 1)))
    same = np.concatenate([blk <= b, blk < b])
    np.testing.assert_allclose(clean[:, same], base[:, same], atol=2e-6)
    assert (np.abs(clean[:, ~same] - base[:, ~same]).max(-1) > 1e-5).all()
    # and the clean half is a function of x0 alone
    other = np.asarray(run(jnp.concatenate([x0, x0], 1)))
    np.testing.assert_allclose(other[:, seq:], base[:, seq:], atol=2e-6)


# ------------------------------------------------------------ the share
def test_eight_shares_of_16_of_128_add_up_to_the_uncut_reference_layer():
    """128 experts over 8 shares, top-8, nothing shared: the eight parts —
    what every chip computes alike (the router) counted once, in the
    weights — equal the reference's uncut layer."""
    mellum = _bench_lib("reference_mellum")
    d, f, total, k = 32, 16, 128, 8
    x, = normal(0, (2, 24, d))
    whole = MoeMlp(experts_total=total, experts_held=(0, total), d_ff=f,
                   shared_d_ff=0, k=k, router=ROUTERS[2])
    params = shd.unbox(jax.jit(whole.init)(jax.random.PRNGKey(1), x))["params"]
    names = {"w_gate": "e_gate", "w_up": "e_up", "w_down": "e_down"}

    def reference(p, lo, hi):
        return jax.jit(lambda x, p: ref.moe(
            x, p, {"experts_held": (lo, hi), "k": k})[0])(x, dict(
                {names[n]: p[n] for n in names}, router=p["router"]))

    assert ref.moe is mellum.moe  # Mellum 2's form, as it stands
    want = reference(params, 0, total)
    tol = 1e-4 * float(np.abs(np.asarray(want)).max())
    parts, rows = [], 0.0
    for lo in range(0, total, 16):
        share = whole.clone(experts_held=(lo, lo + 16))
        mine = dict(params, **{n: params[n][lo:lo + 16] for n in names})
        y, counters, _ = jax.jit(lambda p, x: share.apply({"params": p}, x))(
            mine, x)
        np.testing.assert_allclose(y, reference(mine, lo, lo + 16), atol=tol)
        parts.append(y)
        assert float(counters[0]) == 0.0  # none dropped
        rows += float(counters[1])
    np.testing.assert_allclose(sum(parts), want, atol=tol)
    assert rows == pytest.approx(k)  # every choice fell on exactly one share


# ------------------------------------------------ routing kept over remat
def test_the_kept_routing_is_the_routers_own(seeded):
    """``keep_routing`` names the chosen experts and reads the weights from
    the scores at the kept choice: the same layer, value and gradients, as
    the router's own top-k gives."""
    x, = normal(2, (2, 24, 32))
    plain = MoeMlp(experts_total=16, experts_held=(0, 8), d_ff=16,
                   shared_d_ff=0, k=4, router=ROUTERS[2])
    kept = plain.clone(keep_routing=True)
    params = jax.jit(plain.init)(jax.random.PRNGKey(1), x)

    def run(module):
        def loss(p, x):
            y, counters, _ = module.apply(p, x)
            return jnp.sum(y * y), (y, counters)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(params, x)

    (a, (y_a, c_a)), g_a = run(plain)
    (b, (y_b, c_b)), g_b = run(kept)
    np.testing.assert_allclose(y_a, y_b, atol=1e-7)
    np.testing.assert_allclose(c_a, c_b, atol=1e-6)
    for one, other in zip(jax.tree.leaves(shd.unbox(g_a)),
                          jax.tree.leaves(shd.unbox(g_b))):
        np.testing.assert_allclose(one, other, atol=1e-6)


def test_a_rematerialised_block_routes_once():
    """Under remat the description's expert layers keep what their routers
    chose (``ops/remat.py ROUTED``): the differentiated step holds ONE top-k a
    run of layers, the pass's — a forward made again that rounds another way
    cannot choose other experts for the backward — where a description
    without block diffusion (Mellum 2's three runs of layers) holds the
    pass's and the one made again in each run."""
    from easydl_tpu.models import mellum
    from easydl_tpu.ops import remat

    assert remat.ROUTED in remat.KEPT["full"] and remat.ROUTED \
        in remat.KEPT["dots"] and remat.ROUTED in remat.NAMES
    cfg = describe(size="test", seq_len=64, vocab=256, experts_held=(0, 8),
                   remat=True)
    tokens = np.zeros((2, 64), np.int32)
    batch = {"inputs": tokens, "targets": tokens}

    def top_ks(cfg):
        bundle = lm.lm_bundle(cfg, "counted")
        params = jax.jit(bundle.init_fn)(jax.random.PRNGKey(0))
        return str(jax.make_jaxpr(jax.grad(lambda p: bundle.loss_fn(
            p, batch, jax.random.PRNGKey(1))[0]))(params)).count("top_k")

    assert top_ks(cfg) == 1 and len(cfg.runs) == 1
    plain = mellum.describe(size="test", seq_len=64, vocab=256,
                            experts_held=(0, 8), remat=True)
    assert top_ks(plain) == 2 * len(plain.runs) == 6


# ------------------------------------------------------------- the noise
def test_a_blocks_masked_share_follows_its_time():
    """Over many draws: every token of a block carries the block's time,
    ``t`` in ``[1e-3, 1)`` uniform, a token masked with probability ``t`` —
    by tenths of ``t`` the masked share is the tenth's mean time."""
    x0 = jnp.zeros((512, 256), jnp.int32)
    xt, masked, t = jax.jit(lambda key: lm.block_diffusion_noise(
        key, x0, block=4, mask_id=99))(jax.random.PRNGKey(3))
    xt, masked, t = (np.asarray(a) for a in (xt, masked, t))
    by_block = t.reshape(512, 64, 4)
    assert (by_block == by_block[..., :1]).all()
    assert lm.DIFFUSION_EPS <= t.min() < 0.01 and 0.99 < t.max() <= 1.0
    assert t.mean() == pytest.approx(0.5, abs=0.01)
    assert ((xt == 99) == masked).all()  # these tokens hold no 99
    for lo in np.arange(0.0, 1.0, 0.1):
        here = (t >= lo) & (t < lo + 0.1)
        assert masked[here].mean() == pytest.approx(t[here].mean(), abs=0.01)
    # within a block the tokens are masked independently: all four alike
    # no more often than t^4 + (1 - t)^4 says
    alike = masked.reshape(512, 64, 4)
    alike = (alike == alike[..., :1]).all(-1)
    t_b = by_block[..., 0]
    assert alike.mean() == pytest.approx(
        (t_b ** 4 + (1 - t_b) ** 4).mean(), abs=0.02)


def test_the_draw_is_a_function_of_the_key_and_the_shape_alone():
    x0, other = (jnp.asarray(np.random.default_rng(s).integers(
        0, 50, (3, 32), dtype=np.int32)) for s in (0, 1))
    draw = jax.jit(lambda key, x: lm.block_diffusion_noise(
        key, x, block=4, mask_id=50))
    a, b, c = (draw(jax.random.PRNGKey(k), x)
               for k, x in ((1, x0), (1, other), (2, x0)))
    for mine, theirs in zip(a[1:], b[1:]):  # masked and t: not the tokens
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    assert (np.asarray(a[1]) != np.asarray(c[1])).any()
    # the data may hold the mask's id: what counts is the draw
    held = jnp.full((3, 32), 50, jnp.int32)
    xt, masked, _ = draw(jax.random.PRNGKey(1), held)
    assert (np.asarray(xt) == 50).all() and not np.asarray(masked).all()


def _sdar_trainer(bundle):
    return Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(1e-3),
        config=TrainConfig(global_batch=2, seed=7),
        mesh=build_mesh(MeshSpec(), devices=jax.devices()[:1]))


def test_a_step_replayed_after_restore_from_gives_the_same_loss(tmp_path):
    """The elastic promise for a diffusion job: the noise of step ``n`` is
    ``fold_in(state.rng, n)``'s, both saved with the state, so the step a
    restored trainer takes draws what the first one drew — its loss and its
    counters bit for bit — and the steps draw different noise."""
    bundle = get_model("sdar", size="test", seq_len=64, vocab=256,
                       experts_held=(0, 8))
    batches = [{"inputs": tokens, "targets": tokens} for tokens in
               np.random.default_rng(0).integers(0, 256, (4, 2, 64),
                                                 dtype=np.int32)]
    first = _sdar_trainer(bundle)
    state = first.init_state()
    for batch in batches[:2]:
        state, metrics = first.train_step(state, batch)
    manager = CheckpointManager(str(tmp_path), async_save=False)
    manager.save(2, state)
    seen = []
    for batch in batches[2:]:
        state, metrics = first.train_step(state, batch)
        seen.append(jax.device_get(metrics))
    again = _sdar_trainer(bundle)
    restored = again.restore_from(manager, 2)
    assert restored.int_step == 2
    for batch, want in zip(batches[2:], seen):
        restored, metrics = again.train_step(restored, batch)
        got = jax.device_get(metrics)
        for name in ("loss", "diffusion_masked_share", "diffusion_mean_t",
                     "grad_norm"):
            assert np.asarray(got[name]).tobytes() \
                == np.asarray(want[name]).tobytes(), name
    assert seen[0]["diffusion_mean_t"] != seen[1]["diffusion_mean_t"]
    # the same batch under another step's key is another loss
    _, other = again.train_step(restored, batches[3])
    assert float(other["loss"]) != float(seen[1]["loss"])


def test_eval_draws_from_one_fixed_key():
    bundle = get_model("sdar", size="test", seq_len=64, vocab=256)
    params = jax.jit(bundle.init_fn)(jax.random.PRNGKey(0))
    tokens = np.random.default_rng(0).integers(0, 256, (2, 64),
                                               dtype=np.int32)
    batch = {"inputs": tokens, "targets": tokens}
    run = jax.jit(bundle.eval_fn)
    a, b = (run(params, batch, jax.random.PRNGKey(k)) for k in (1, 2))
    assert float(a[0]) == float(b[0])
    assert float(a[1]["diffusion_mean_t"]) == float(b[1]["diffusion_mean_t"])


# ---------------------------------------------------------------- counts
def test_layer_params_and_flops_against_the_hand_count():
    """ISSUE 51's count: one chip's share of SDAR-30B-A3B-Chat."""
    kwargs = _config("sdar-30b-a3b-chat")["kwargs"]
    cfg = describe(**kwargs)
    d, hd, heads, kv, f, seq, block = 2048, 128, 32, 4, 768, 8192, 4
    attention = 2 * d * heads * hd + 2 * d * kv * hd
    assert attention == 18_874_368 and 3 * d * f == 4_718_592
    layer = attention + 2 * hd + 2 * d + d * 128 + 16 * 3 * d * f
    assert layer == 94_638_336
    assert all(cfg.layer_params(kind) == layer for kind in cfg.pattern)
    total = 2 * 18992 * d + d + 6 * layer
    assert cfg.param_count == total == 645_623_296
    shapes = jax.eval_shape(get_model("sdar", **kwargs).init_fn,
                            jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(
        shd.unbox(shapes))) == total
    # active: a row meets k * held / total = 1 routed expert here; a token
    # is two rows through every layer and one through the head, and sees
    # L + B keys a layer (L² + L B live pairs a sequence)
    active = attention + 2 * hd + 2 * d + d * 128 + 1 * 3 * d * f
    assert cfg.layer_params(cfg.pattern[0], active=True) == active
    per_token = 6.0 * (2 * 6 * active + 18992 * d + d) \
        + 6 * 12.0 * heads * hd * (seq + block)
    assert cfg.train_flops_per_token(seq) == pytest.approx(per_token)
    assert get_model("sdar", **kwargs).flops_per_sample_hint \
        == pytest.approx(per_token * seq)
    # the model as published: 30.5B in all
    whole = describe()
    assert round(whole.param_count / 1e8) == 305 and whole.n_layers == 48


def test_described_kinds_and_refusals():
    cfg = describe(size="test", seq_len=64, vocab=256)
    assert cfg.runs == ((("full_attention", "moe"), 3),)
    assert (cfg.head_dim, cfg.n_heads, cfg.kv_heads) == (16, 4, 2)
    kind = cfg.attention_kind("full_attention")
    assert kind.qk_norm and not kind.window and kind.rope.theta == 1e6
    assert cfg.block_diffusion == 4 and not cfg.causal
    assert cfg.moe.router == "linear-softmax-renormalised" \
        and cfg.moe.shared_d_ff == 0 and cfg.embedding_init_std == 1.0
    leaves = shd.unbox(jax.jit(get_model(
        "sdar", size="test", seq_len=64, vocab=256).init_fn)(
            jax.random.PRNGKey(0)))
    assert leaves["blocks"]["q_norm"].shape == (3, 16)
    assert np.asarray(leaves["tok_emb"]["embedding"]).std() \
        == pytest.approx(1.0, rel=0.05)
    with pytest.raises(ValueError, match="layers are all"):
        describe(size="test", layer_types=["sliding_attention"])
    with pytest.raises(TypeError):
        describe(size="test", mlp_layer_types=["sparse"] * 3)
    base = dict(vocab=64, d_model=32, n_heads=2, n_layers=1, max_seq=16,
                causal=False, position="none", block_diffusion=4)
    refused = [
        ("causal=True", dict(causal=True)),
        ("a window", dict(layers=(("w", "gelu"),), attention_kinds=(
            ("w", AttentionKind(window=8)),))),
        ("a latent or lowrank", dict(layers=(("c", "gelu"),),
                                     attention_kinds=(
            ("c", AttentionKind(latent=LatentMix(),
                                rope=RopeScheme())),))),
        ("learned positions", dict(position="learned")),
        ("a looped or gated", dict(loops=2)),
        ("attention_fn", dict(attention_fn=lambda *a, **k: None)),
        ("a length it does not divide", dict(max_seq=18)),
    ]
    for said, change in refused:
        with pytest.raises(NotImplementedError, match=said):
            TransformerConfig(**dict(base, **change))
    with pytest.raises(ValueError, match="qk_norm on a latent or lowrank"):
        TransformerConfig(
            vocab=64, d_model=32, n_heads=2, n_layers=1,
            layers=(("c", "gelu"),), attention_kinds=(("c", AttentionKind(
                latent=LatentMix(), qk_norm=True)),))
    with pytest.raises(ValueError, match="twice a whole number of blocks"):
        Transformer(TransformerConfig(**base)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 20), jnp.int32))
