"""JoyAI-LLM-Flash's mechanisms at test size on the CPU: the whole model
against ``benchmark/lib/reference_joyai`` in float32 (both losses, every
layer's state and the module's, latent attention's parts on equal inputs,
every gradient leaf), the expert layer's four shares adding up to the uncut
reference layer (a main layer's and the module's), the selection bias
choosing and not weighing, the module reading token ``i + 1`` at position ``i``
and aiming at token ``i + 2``, the description's counts against a hand count
of ISSUE 37's numbers."""

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
from easydl_tpu.models.joyai import MLA, describe
from easydl_tpu.models.lm import mtp_objective
from easydl_tpu.models.registry import get_model, list_models
from easydl_tpu.ops import moe

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")


def _bench_lib(name):
    """A module of ``benchmark/lib`` (the package is not on tier-1's path)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(f"lib.{name}")


ref = _bench_lib("reference_joyai")
check_module = _bench_lib("check_joyai")
SEQ = 48
TEST = dict(size="test", seq_len=SEQ, vocab=256,
            layer_types=["dense", "sparse", "sparse"])


def _config(name="joyai-test"):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# --------------------------------------------------------- the whole model
@pytest.fixture(scope="module")
def float32_check():
    """``lib/check_joyai.check`` at the test size with float32 compute: the
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
    ("loss_abs", 2e-5), ("loss_main_abs", 2e-5), ("loss_mtp_abs", 2e-5),
    ("state_rel_rms_layer_0", 1e-5), ("state_rel_rms_layer_1", 1e-5),
    ("state_rel_rms_layer_2", 1e-5), ("state_rel_rms_mtp_layer", 1e-5),
    ("state_rel_rms_final", 1e-5), ("state_rel_rms_mtp_final", 1e-5),
    ("token_rel_max", 5e-5), ("grad_rel_rms_worst", 2e-4),
    ("grad_rel_rms_all", 1e-4), ("router_logits_rel", 1e-5),
    ("mla_latent_token_rel_max", 1e-5), ("mla_rotated_token_rel_max", 1e-5),
    ("mla_attn_token_rel_max", 1e-5), ("moe_dropped", 0.0),
    ("chosen_not_top8_share", 0.0), ("chosen_sets_differ_share", 0.0),
])
def test_program_against_reference_joyai(float32_check, what, limit):
    """Both losses, every layer's state and the module's, every gradient
    leaf (the worst of them) through the fused head, the latents, the
    rotated parts and the attention's result on equal inputs, the router's
    logits and chosen sets, the counter."""
    assert float32_check["errors"][what] <= limit, float32_check["errors"]


def test_every_gradient_leaf_was_compared(float32_check):
    kwargs = _config()["kwargs"]
    params = shd.unbox(jax.jit(get_model("joyai", **kwargs).init_fn)(
        jax.random.PRNGKey(0)))
    plain = check_module.to_reference(params)
    # nothing is left out of the map; a layer: 2 norms and 7 of the
    # attention, then 3 (dense) or 8 (router, bias, 3 + 3 of the experts);
    # the module: its layer, 2 norms and the join, its final norm; 3 outside
    assert sum(x.size for x in jax.tree.leaves(plain)) \
        == sum(x.size for x in jax.tree.leaves(params))
    assert len(jax.tree.leaves(plain)) == (9 + 3) + 2 * (9 + 8) \
        + (9 + 8) + 4 + 3 == float32_check["errors"]["grad_leaves"]
    counters = float32_check["counters"]
    # half of the 32 experts are held: a token meets 4 x 16 / 32 of them
    assert 1.5 < counters["moe_rows_per_token"] < 2.5
    assert counters["loss_main"] > 0.0 and counters["loss_mtp"] > 0.0


def test_the_reference_imports_nothing_from_the_program():
    for name in ("reference_joyai", "flops_joyai"):
        with open(os.path.join(BENCH, "lib", f"{name}.py")) as f:
            code = f.read()
        assert "import easydl_tpu" not in code
        assert "from easydl_tpu" not in code


# -------------------------------------------------------------- the shares
@pytest.fixture(scope="module")
def uncut():
    """The float32 test-size model with every expert held: ``(cfg, params,
    tokens)``, the selection biases stirred so that they select. Built once
    for the tests that read it (none writes to it)."""
    cfg = describe(**TEST)
    params = shd.unbox(jax.jit(get_model("joyai", **TEST).init_fn)(
        jax.random.PRNGKey(3)))
    rng = np.random.default_rng(4)
    stir = 0.2 * rng.standard_normal(32, np.float32)
    params["blocks_1"]["moe"]["router_bias"] += stir
    params["mtp_block"]["moe"]["router_bias"] += stir[::-1]
    tokens = jnp.asarray(rng.integers(0, 256, (2, SEQ), np.int32))
    return cfg, params, tokens


@pytest.mark.parametrize("where", ["main", "module"])
def test_the_shares_add_up_to_the_uncut_reference_layer(uncut, where):
    """32 experts over 4 shares (the cell's 256 over 16): the four parts of a
    sparse layer's result, with what every chip computes alike — the
    attention, the shared expert — counted once, equal the reference's
    uncut layer: a main layer on a seeded state, and the module's layer on
    what its own join gives it."""
    cfg, params, tokens = uncut
    tables = cfg.attention_kind(MLA).rope.tables(SEQ, cfg.head_dim)
    plain = check_module.to_reference(params)
    hp = {"eps": 1e-6, "theta": 32e6, "nope": 16, "rot": 8, "k": 4,
          "scaling": 2.5, "experts_held": (0, 32), "lam": 0.3}
    if where == "main":
        p_layer = jax.tree.map(lambda a: a[0], params["blocks_1"])
        p_ref = plain["layers"][1]
        x, = normal(6, (2, SEQ, cfg.d_model))
    else:
        p_layer, p_ref = params["mtp_block"], plain["mtp"]["layer"]
        state, = normal(6, (2, SEQ, cfg.d_model))
        x = jax.jit(lambda p, state: transformer.MtpMerge(cfg).apply(
            {"params": p["mtp_merge"]},
            jnp.take(p["tok_emb"]["embedding"],
                     jnp.roll(tokens, -1, 1), axis=0), state))(params, state)

    # the reference's layer and what every chip computes alike: one program
    @jax.jit
    def reference(x, p_ref):
        after_attention = ref.attention_residual(x, p_ref, hp)
        return ref.layer(x, p_ref, hp)[0], after_attention + ref.swiglu(
            ref.rms_norm(after_attention, p_ref["n2"], 1e-6),
            p_ref["s_gate"], p_ref["s_up"], p_ref["s_down"])

    def block(description):
        return jax.jit(lambda p, x: transformer.Block(
            description, MLA, "moe").apply({"params": p}, x, True, tables))

    want, alike = reference(x, p_ref)
    parts, dropped, rows = [], 0.0, 0.0
    for lo in range(0, 32, 8):
        share = describe(**TEST, experts_held=(lo, lo + 8))
        mine = dict(p_layer, moe=dict(p_layer["moe"], **{
            name: p_layer["moe"][name][lo:lo + 8]
            for name in ("w_gate", "w_up", "w_down")}))
        y, counters = block(share)(mine, x)
        parts.append(y)
        dropped += float(counters[0])
        rows += float(counters[1])
    np.testing.assert_allclose(np.asarray(sum(parts) - 3 * alike),
                               np.asarray(want), atol=3e-5)
    assert dropped == 0.0
    assert rows == pytest.approx(4)  # every choice fell on exactly one share
    whole, _ = block(cfg)(p_layer, x)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               atol=3e-5)


# ------------------------------------------------------------- the router
def test_the_bias_selects_and_does_not_weigh():
    """``noaux_tc``: the ``k`` largest of ``sigmoid(logits) + b`` are chosen,
    the weights are the chosen experts' scores WITHOUT ``b``, renormalised
    and scaled; ``b`` takes no gradient; without a bias the choice is the
    scores' own."""
    h, kernel = normal(0, (64, 32), (32, 16))
    bias = np.zeros(16, np.float32)
    bias[[3, 5]] = 2.0, -2.0
    route = jax.jit(moe.route, static_argnums=(2, 3))
    logits, chosen, weights = route(h, kernel, 4, 2.5, bias)
    scores = np.asarray(jax.nn.sigmoid(logits))
    want = np.argsort(-(scores + np.asarray(bias)), -1)[:, :4]
    assert (np.sort(np.asarray(chosen), -1) == np.sort(want, -1)).all()
    assert (np.asarray(chosen) == 3).any(-1).all()      # a bias of +2 wins
    assert not (np.asarray(chosen) == 5).any()          # one of -2 never
    picked = np.take_along_axis(scores, np.asarray(chosen), -1)
    np.testing.assert_allclose(
        np.asarray(weights), 2.5 * picked / picked.sum(-1, keepdims=True),
        rtol=1e-6)
    plain = route(h, kernel, 4, 2.5)
    assert (np.sort(np.asarray(plain[1]), -1)
            == np.sort(np.argsort(-scores, -1)[:, :4], -1)).all()
    assert (np.asarray(plain[1]) != np.asarray(chosen)).any()
    grad_b, grad_w = jax.jit(jax.grad(
        lambda b, w: jnp.sum(moe.route(h, w, 4, 2.5, b)[2] ** 2),
        argnums=(0, 1)))(bias, kernel)
    assert np.all(np.asarray(grad_b) == 0.0)
    assert np.abs(np.asarray(grad_w)).sum() > 0.0


# ------------------------------------------------------------- the module
def test_the_objective_is_two_means_over_their_own_positions(fused_head):
    """``CE(main, t_{i+1}) + lambda CE(module, t_{i+2})`` through ONE fused
    call in several chunks: against ``optax`` on full logits, the module's
    last position without a target, ignored positions in neither mean."""
    batch, seq, d, vocab = 2, 16, 8, 32
    hidden, mtp, head = normal(0, (batch, seq, d), (batch, seq, d),
                               (vocab, d))
    states = transformer.MtpStates(hidden, mtp)
    targets = np.random.default_rng(0).integers(0, vocab, (batch, seq),
                                                np.int32)
    targets[0, 5] = -1
    targets = jnp.asarray(targets)

    def by_hand(states, head):
        def mean_ce(h, t):
            nll = optax.softmax_cross_entropy_with_integer_labels(
                h @ head.T, jnp.maximum(t, 0))
            return jnp.sum(jnp.where(t >= 0, nll, 0.0)) / jnp.sum(t >= 0)
        main = mean_ce(states.hidden, targets)
        mtp = mean_ce(states.mtp[:, :-1], targets[:, 1:])
        return main + 0.3 * mtp, (main, mtp)

    (want, (main, mtp)), want_grads = jax.jit(jax.value_and_grad(
        by_hand, argnums=(0, 1), has_aux=True))(states, head)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda s, h: mtp_objective(s, h, targets, weight=0.3),
        argnums=(0, 1), has_aux=True))(states, head)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    assert float(metrics["loss_main"]) == pytest.approx(float(main), rel=1e-6)
    assert float(metrics["loss_mtp"]) == pytest.approx(float(mtp), rel=1e-6)
    assert float(metrics["perplexity"]) == pytest.approx(
        math.exp(float(main)), rel=1e-5)
    for got, expected in zip(jax.tree.leaves(grads),
                             jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   atol=1e-6)
    # the module's last position has no target: its state moves nothing
    assert np.all(np.asarray(grads[0].mtp[:, -1]) == 0.0)


def test_the_module_reads_the_next_token_and_no_later_one(uncut):
    """Changing token ``j`` moves the main stack's states from ``j`` on and
    the module's from ``j - 1`` on (position ``i`` is given the embedding of
    token ``i + 1``), and nothing before."""
    cfg, params, tokens = uncut
    model = transformer.Transformer(cfg)
    states = jax.jit(lambda tokens: model.apply(
        {"params": params}, tokens, return_hidden=True))
    j = 20
    other = tokens.at[:, j].set((tokens[:, j] + 1) % 256)
    a, b = states(tokens), states(other)

    def moved(x, y):
        return np.asarray(jnp.max(jnp.abs(x - y), (0, 2)) > 0)

    main, module = moved(a.hidden, b.hidden), moved(a.mtp, b.mtp)
    assert not main[:j].any() and main[j:].all()
    # the last position takes the FIRST token for want of a next one
    assert not module[:j - 1].any() and module[j - 1:SEQ - 1].all()
    first = tokens.at[:, 0].set((tokens[:, 0] + 1) % 256)
    c = states(first)
    assert moved(a.mtp, c.mtp).all()
    # without return_hidden the logits are the main stack's, module unused
    logits = jax.jit(lambda tokens: model.apply({"params": params}, tokens))(
        tokens)
    assert logits.shape == (2, SEQ, 256)


def test_joyai_trains_through_the_trainer():
    assert "joyai" in list_models()
    bundle = get_model("joyai", size="test", seq_len=32, vocab=256,
                       layer_types=["dense", "sparse", "sparse"],
                       experts_held=(0, 16))
    trainer = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adam(1e-3),
        config=TrainConfig(global_batch=4, compute_dtype=jnp.float32),
        mesh=build_mesh(MeshSpec(), devices=jax.devices()[:1]))
    state = trainer.init_state()
    batch = next(iter(bundle.make_data(4, seed=0)))
    before = np.asarray(
        shd.unbox(state.params)["blocks_1"]["moe"]["router_bias"])
    losses = []
    for _ in range(5):
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert float(metrics["moe_dropped"]) == 0.0
    assert {"loss_main", "loss_mtp", "perplexity"} <= set(metrics)
    assert float(metrics["loss"]) == pytest.approx(
        float(metrics["loss_main"]) + 0.3 * float(metrics["loss_mtp"]),
        rel=1e-5)
    # the selection bias has no gradient: AdamW's update of it is nothing
    after = shd.unbox(state.params)["blocks_1"]["moe"]["router_bias"]
    assert np.all(np.asarray(after) == before)


# ------------------------------------------------------- over an ep mesh
def test_joyai_trains_on_ep_mesh(eight_devices):
    """JoyAI-LLM's test size beside Laguna's, all 32 experts held, sharded
    over ep=4 with the batch over dp=2: latent attention and the module's
    layer under the mesh, each expert shard computing its eight experts'
    part (the module's too) — the same loss as one device gives, a finite,
    falling loss, nothing dropped, both heads' losses in the metrics."""
    kwargs = dict(size="test", seq_len=32, vocab=256,
                  layer_types=["dense", "sparse", "sparse"])
    bundle = get_model("joyai", **kwargs)

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
    assert {"blocks_1/moe/w_gate", "mtp_block/moe/w_gate"} <= set(held)
    for key, w in held.items():
        assert "ep" in str(w.sharding.spec), (key, w.sharding.spec)
        assert w.shape[-3] == 32  # every expert held, eight a shard

    # the seeded parameters and the first step's rng (``Trainer.train_step``
    # folds the step into the state's), on the host, before the first step
    # donates them
    seeded, first_rng = jax.device_get(
        (state.params, jax.random.fold_in(state.rng, state.step)))
    batches = [next(iter(bundle.make_data(8, seed=0)))] * 6
    losses, metrics = [], []
    for batch in batches:
        state, m = sharded.train_step(state, batch)
        losses.append(float(m["loss"]))
        metrics.append({k: float(v) for k, v in m.items()})
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert all(m["moe_dropped"] == 0.0 for m in metrics)
    # all experts held: each of a token's 4 choices has a row somewhere
    assert all(abs(m["moe_rows_per_token"] - 4.0) < 1e-6 for m in metrics)
    assert all(abs(m["loss"] - m["loss_main"] - 0.3 * m["loss_mtp"]) < 1e-4
               for m in metrics)

    # one device: the loss alone, on the same parameters and batch (no second
    # trainer: its state and step are programs this test does not read)
    first, _ = jax.jit(bundle.loss_fn)(seeded, batches[0], first_rng)
    assert abs(float(first) - losses[0]) < 1e-4


# ---------------------------------------------------------------- counts
def test_layer_params_and_flops_against_the_hand_count():
    """ISSUE 37's table: one chip's share of JoyAI-LLM-Flash."""
    kwargs = _config("joyai-llm-flash")["kwargs"]
    cfg = describe(**kwargs)
    d, heads = 2048, 32
    mla = (d * 1536 + 1536 + 1536 * heads * 192 + d * 576 + 512
           + 512 * heads * 256 + heads * 128 * d)
    assert round(mla / 1e4) == 2635                     # the issue's 26.35M
    expert = 3 * d * 768
    sparse = mla + d * 256 + 256 + expert + 16 * expert + 2 * d
    dense = mla + 3 * d * 7168 + 2 * d
    assert cfg.layer_params(cfg.pattern[0]) == dense
    assert cfg.layer_params(cfg.pattern[1]) == sparse
    assert round(dense / 1e5) == 704 and round(sparse / 1e5) == 1071
    module = sparse + 2 * d * d + 3 * d
    total = 2 * 16160 * d + d + dense + 4 * sparse + module
    assert cfg.param_count == total and round(total / 1e5) == 6804
    shapes = jax.eval_shape(get_model("joyai", **kwargs).init_fn,
                            jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(
        shd.unbox(shapes))) == total
    # active: a token meets k * held / total = 0.5 routed experts here
    active = sparse - 16 * expert + expert // 2
    assert cfg.layer_params(cfg.pattern[1], active=True) == active
    seq = 8192
    per_token = 6.0 * (dense + 4 * active + 16160 * d + d
                       + (active + 2 * d * d + 3 * d) + 16160 * d) \
        + 6.0 * 6 * heads * (192 + 128) * seq
    assert cfg.train_flops_per_token(seq) == pytest.approx(per_token)


def test_described_kinds_and_refusals():
    cfg = describe(size="test", seq_len=64, vocab=256)
    assert [r for r in cfg.runs] == [((MLA, "swiglu"), 1), ((MLA, "moe"), 2)]
    kind = cfg.attention_kind(MLA)
    assert cfg.head_dim == 24 and kind.lowrank.value_dim == 16
    assert (kind.rope.rotary_dim, kind.rope.interleaved, kind.rope.last) \
        == (8, True, True)
    assert cfg.mtp == transformer.MtpConfig(mixer=MLA, ffn="moe", weight=0.3)
    assert cfg.moe.selection_bias and cfg.counters == moe.COUNTERS
    assert describe(size="test", mtp=False).mtp is None
    with pytest.raises(ValueError, match="layers are"):
        describe(size="test", layer_types=["full_attention"])
    with pytest.raises(ValueError, match="low-rank latent attention"):
        transformer.TransformerConfig(
            n_layers=1, layers=((MLA, "gelu"),), head_size=24,
            attention_kinds=((MLA, transformer.AttentionKind(
                lowrank=transformer.LowRank(48, 16, 16, 8, 16))),))
    with pytest.raises(NotImplementedError, match="multi-token-prediction"):
        transformer.TransformerConfig(
            loops=2, mtp=transformer.MtpConfig(mixer="attention", ffn="gelu"))
