"""Nemotron 3 Nano's mechanisms at test size on the CPU: the pattern's parser
(published sub-layers paired into blocks, a mixer behind a mixer a block whose
FFN is ``none``), the whole model against
``benchmark/lib/reference_nemotron_h`` in float32 (the loss, every block's state, the Mamba-2 mixer's parts on equal
inputs at two B/C groups with the grouped gated norm, every gradient leaf),
the expert layer's four shares adding up to the uncut reference layer behind
either mixer, a block that is its mixer alone, and the description's counts
against a hand count of ISSUE 42's numbers."""

import importlib
import json
import os
import re
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
from easydl_tpu.models.nemotron_h import SIZES, blocks_of, describe
from easydl_tpu.models.registry import get_model, list_models

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")


def _bench_lib(name):
    """A module of ``benchmark/lib`` (the package is not on tier-1's path)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(f"lib.{name}")


ref = _bench_lib("reference_nemotron_h")
check_module = _bench_lib("check_nemotron_h")
SEQ = 48
TEST = dict(size="test", seq_len=SEQ, vocab=256)
M, A = "mamba2", "attention"


def _config(name="nemotron-test"):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# -------------------------------------------------------------- the parser
def test_the_pattern_pairs_sublayers_into_blocks():
    assert blocks_of("MEMEM*EME") == (
        (M, "moe"), (M, "moe"), (M, "none"), (A, "moe"), (M, "moe"))
    assert blocks_of("MEM*EME") == ((M, "moe"), (M, "none"), (A, "moe"),
                                    (M, "moe"))
    assert blocks_of("M*") == ((M, "none"), (A, "none"))
    # the published 52 sub-layers: 29 mixers open 29 blocks, 23 of them
    # followed by their E; six M stand in front of a *
    published = blocks_of(SIZES["nano-30b-a3b"]["hybrid_override_pattern"])
    assert len(published) == 23 + 6
    assert sum(ffn == "moe" for _, ffn in published) == 23
    assert [b for b in published if b[1] == "none"] == [(M, "none")] * 6
    assert sum(mixer == A for mixer, _ in published) == 6
    # the cell's nine letters are the published pattern's first nine
    assert SIZES["nano-30b-a3b"]["hybrid_override_pattern"][:9] == "MEMEM*EME"


@pytest.mark.parametrize("pattern,error", [
    ("ME-ME", NotImplementedError),   # a dense MLP layer: the row has none
    ("EMEM", ValueError),             # an E that follows no mixer
    ("MEEM", ValueError),             # a second E in a row
    ("MEXM", ValueError),             # no letter of the family's
])
def test_a_pattern_the_stack_cannot_pair_is_refused(pattern, error):
    with pytest.raises(error, match="hybrid_override_pattern"):
        blocks_of(pattern)
    with pytest.raises(error):
        describe(**TEST, hybrid_override_pattern=pattern)


# --------------------------------------------------------- the whole model
@pytest.fixture(scope="module")
def float32_check():
    """``lib/check_nemotron_h.check`` at the test size with float32 compute:
    the program against the reference on seeded weights."""
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
    ("loss_abs", 2e-5), ("state_rel_rms_block_0", 1e-5),
    ("state_rel_rms_block_1", 1e-5), ("state_rel_rms_block_2", 1e-5),
    ("state_rel_rms_block_3", 1e-5), ("state_rel_rms_final", 1e-5),
    ("token_rel_max", 5e-5), ("grad_rel_rms_worst", 2e-4),
    ("grad_rel_rms_all", 1e-4), ("router_logits_rel", 1e-5),
    ("ssm_conv_token_rel_max", 1e-5), ("ssd_token_rel_max", 2e-5),
    ("gated_norm_token_rel_max", 1e-5), ("moe_dropped", 0.0),
    ("chosen_not_top6_share", 0.0), ("chosen_sets_differ_share", 0.0),
])
def test_program_against_reference_nemotron_h(float32_check, what, limit):
    """The loss, every block's state against the reference's chain of
    published sub-layers (so the pairing too), every gradient leaf (the
    worst of them), the input map with its convolutions, the chunked scan
    against the sequential recurrence at two groups and the grouped gated
    norm on equal inputs, the router's logits and chosen sets, the
    counter."""
    assert float32_check["errors"][what] <= limit, float32_check["errors"]


def test_every_gradient_leaf_was_compared(float32_check):
    kwargs = _config()["kwargs"]
    cfg = describe(**kwargs)
    params = shd.unbox(jax.jit(get_model("nemotron_h", **kwargs).init_fn)(
        jax.random.PRNGKey(0)))
    plain = check_module.to_reference(params, cfg)
    # nothing is left out of the map; M: a norm, the fused input map, the
    # convolution and its bias, three per-head leaves, the gated norm's gain,
    # the way back (9); *: a norm and four maps (5); E: a norm, router, bias,
    # two of the experts, two of the shared one (7); three outside
    assert sum(x.size for x in jax.tree.leaves(plain)) \
        == sum(x.size for x in jax.tree.leaves(params)) == cfg.param_count
    assert len(plain["layers"]) == len("MEM*EME")
    assert len(jax.tree.leaves(plain)) == 3 * 9 + 5 + 3 * 7 + 3 \
        == float32_check["errors"]["grad_leaves"]
    # half of the 16 experts are held: a token meets 3 x 8 / 16 of them
    assert 1.0 < float32_check["counters"]["moe_rows_per_token"] < 2.0
    assert check_module.pattern_of(cfg) == "MEM*EME"


def test_the_reference_imports_nothing_from_the_program():
    for name in ("reference_nemotron_h", "flops_nemotron"):
        with open(os.path.join(BENCH, "lib", f"{name}.py")) as f:
            code = f.read()
        assert "import easydl_tpu" not in code
        assert "from easydl_tpu" not in code


# -------------------------------------------------------------- the shares
@pytest.fixture(scope="module")
def uncut():
    """The float32 test-size model with every expert held: ``(cfg, params)``,
    the selection biases stirred so that they select."""
    cfg = describe(**TEST)
    params = shd.unbox(jax.jit(get_model("nemotron_h", **TEST).init_fn)(
        jax.random.PRNGKey(3)))
    stir = 0.2 * np.random.default_rng(4).standard_normal(16, np.float32)
    for run in ("blocks_0", "blocks_2"):
        params[run]["moe"]["router_bias"] += stir
    return cfg, params


@pytest.mark.parametrize("run,mixer,letter", [("blocks_0", M, "M"),
                                              ("blocks_2", A, "*")])
def test_the_shares_add_up_to_the_uncut_reference_layer(uncut, run, mixer,
                                                        letter):
    """16 experts over 4 shares (the cell's 128 over 16): the four parts of a
    block's result, with what every chip computes alike — the mixer, the
    shared expert — counted once, equal the reference's two uncut sub-layers
    (the mixer's, then ``E``), behind a Mamba-2 mixer and behind attention."""
    cfg, params = uncut
    p_block = jax.tree.map(lambda a: a[0], params[run])
    (_, p_mixer), (_, p_e) = check_module.sublayers_to_reference(
        p_block, mixer, "moe")
    hp = ref.hyper(dict(_config(), n_routed_experts_published=16,
                        kwargs={"experts_held": [0, 16]}))
    x, = normal(6, (2, SEQ, cfg.d_model))

    # the reference's two sub-layers and what every chip computes alike
    @jax.jit
    def reference(x, p_mixer, p_e):
        mixed = ref.sublayer(x, p_mixer, letter, hp)[0]
        m = ref.rms_norm(mixed, p_e["norm_g"], hp["eps"])
        shared = ref.dot("bsf,fd->bsd", ref.relu2(
            ref.dot("bsd,df->bsf", m, p_e["s_up"])), p_e["s_down"])
        return ref.sublayer(mixed, p_e, "E", hp)[0], mixed + shared

    def block(description):
        return jax.jit(lambda p, x: transformer.Block(
            description, mixer, "moe").apply({"params": p}, x, True, None))

    want, alike = reference(x, p_mixer, p_e)
    parts, dropped, rows = [], 0.0, 0.0
    for lo in range(0, 16, 4):
        share = describe(**TEST, experts_held=(lo, lo + 4))
        mine = dict(p_block, moe=dict(p_block["moe"], **{
            name: p_block["moe"][name][lo:lo + 4]
            for name in ("w_up", "w_down")}))
        y, counters = block(share)(mine, x)
        parts.append(y)
        dropped += float(counters[0])
        rows += float(counters[1])
    np.testing.assert_allclose(np.asarray(sum(parts) - 3 * alike),
                               np.asarray(want), atol=3e-5)
    assert dropped == 0.0
    assert rows == pytest.approx(3)  # every choice fell on exactly one share
    whole, _ = block(cfg)(p_block, x)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               atol=3e-5)


# ------------------------------------------------- a block without an FFN
@pytest.fixture(scope="module")
def step_paths():
    """``(op paths of the lowered bf16 step under remat full, the trainer's
    first step's metrics)`` at the test size."""
    bundle = get_model("nemotron_h", **TEST, dtype="bfloat16", remat=True,
                       remat_policy="full", experts_held=(0, 8))
    trainer = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(1e-6),
        config=TrainConfig(global_batch=4),
        mesh=build_mesh(MeshSpec(), devices=jax.devices()[:1]))
    tokens = jax.ShapeDtypeStruct((4, SEQ), jnp.int32)
    text = trainer.step_fn.lower(
        trainer.abstract_state(), {"inputs": tokens, "targets": tokens}
    ).as_text(debug_info=True)
    batch = next(iter(bundle.make_data(4, seed=0)))
    _, metrics = trainer.train_step(trainer.init_state(), batch)
    return set(re.findall(r'loc\("([^"]*)"', text)), metrics


def test_a_block_that_is_its_mixer_alone(step_paths):
    """``(mamba2, none)``: one norm, no ``ln_mlp``, no ``moe`` leaf, and in
    the step's names no ``ffn`` or ``moe`` scope under its run; the other
    runs keep theirs, and every Mamba-2 run has the new ``gated_norm``
    scope inside ``ssm``."""
    paths, _ = step_paths
    params = shd.unbox(jax.jit(get_model("nemotron_h", **TEST).init_fn)(
        jax.random.PRNGKey(0)))
    assert "ln_mlp" not in params["blocks_1"] \
        and "moe" not in params["blocks_1"]
    assert "ln_ssm" in params["blocks_1"] and "ln_mlp" in params["blocks_0"]
    # the convolutions' biases start at zero here (the hybrid's do not)
    assert not np.asarray(params["blocks_1"]["conv_x_bias"]).any()
    assert np.asarray(params["blocks_1"]["conv_x"]).any()
    for run, has_ffn in (("blocks_0", True), ("blocks_1", False),
                         ("blocks_2", True), ("blocks_3", True)):
        under = [p for p in paths if re.search(rf"(^|/){run}/", p)]
        assert under, run
        assert any(re.search(r"/(moe|ffn)(/|$)", p) for p in under) \
            == has_ffn, run
        assert not any(re.search(r"/ffn(/|$)", p) for p in under)
        assert any("/ssm/gated_norm" in p for p in under) == (
            run != "blocks_2"), run
    # (the pieces of the sort are functions of their own: their paths start
    # at `dispatch` / `experts` / `combine`)
    for scope in ("ssm/ssd", "ssm/conv1d", "attention", "moe/router",
                  "experts", "moe/shared_expert"):
        assert any(re.search(rf"(^|/){scope}(/|$)", p) for p in paths), scope


def test_the_trainer_steps_it_like_any_model(step_paths):
    _, metrics = step_paths
    assert "nemotron_h" in list_models()
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["moe_dropped"]) == 0.0
    for name in ("moe_rows_per_token", "moe_load_max_over_mean",
                 "moe_buffer_fill", "router_entropy", "moe_overflow",
                 "moe_tile_fill"):
        assert name in metrics, name


# ------------------------------------------------------------- the counts
def test_the_description_counts_what_the_issue_counts():
    """ISSUE 42's hand count of the cell's share: a Mamba-2 mixer 38.74M,
    attention 23.40M, an expert layer 100.12M (8 ungated experts of 2 x 2688
    x 1856, a shared one of 2 x 2688 x 3712), 2 x 44.04M of embedding and
    head: 666.96M; a ``none`` FFN counts nothing and one norm less."""
    cfg = describe(experts_held=(0, 8), vocab=16384,
                   hybrid_override_pattern="MEMEM*EME")
    d = 2688
    mixer = d * (2 * 4096 + 2 * 8 * 128 + 64) + 5 * (4096 + 2 * 8 * 128) \
        + 3 * 64 + 4096 + 4096 * d
    attention = 2 * d * 32 * 128 + 2 * d * 2 * 128
    experts = d * 128 + 128 + 2 * d * 3712 + 8 * 2 * d * 1856
    assert cfg.layer_params((M, "none")) == mixer + d
    assert cfg.layer_params((M, "moe")) == mixer + experts + 2 * d
    assert cfg.layer_params((A, "moe")) == attention + experts + 2 * d
    assert cfg.param_count == 666_963_456 == (
        4 * mixer + attention + 4 * experts + 9 * d + d + 2 * 16384 * d)
    assert round(mixer / 1e6, 2) == 38.74
    assert round(experts / 1e6, 2) == 100.12
    # of the routed experts 6 x 8 / 128 a token count as active
    active = cfg.layer_params((M, "moe"), active=True)
    assert active == mixer + 2 * d + d * 128 + 128 + 2 * d * 3712 \
        + round(0.375 * 2 * d * 1856)
    # 6 a parameter (the embedding a lookup), the scores in full, the scans
    from easydl_tpu.ops.ssd import ssd_flops_per_token
    held = cfg.param_count - 16384 * d
    routed_idle = 4 * (8 - 0.375) * 2 * d * 1856
    assert cfg.train_flops_per_token(8192) == pytest.approx(
        6.0 * (held - routed_idle) + 12.0 * 32 * 128 * 8192
        + 4 * 3.0 * ssd_flops_per_token(64, 64, 128, 8, 128), rel=1e-9)
    assert 2.3e9 < cfg.train_flops_per_token(8192) < 2.4e9


def test_the_file_holds_the_program_description():
    """The cell's configuration builds the description the counts are of."""
    config = _config("nemotron-3-nano-30b-a3b")
    cfg = describe(**config["kwargs"])
    assert cfg.pattern == blocks_of("MEMEM*EME")
    assert (cfg.ssm.n_groups, cfg.ssm.chunk, cfg.ssm.grouped_norm) \
        == (8, 128, True)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.moe.experts_total, cfg.moe.experts_held, cfg.moe.k,
            cfg.moe.d_ff, cfg.moe.shared_d_ff, cfg.moe.expert_form,
            cfg.moe.selection_bias) == (128, (0, 8), 6, 1856, 3712, "relu2",
                                        True)
    assert (cfg.position, cfg.tied_head, cfg.norm_eps, cfg.bias) \
        == ("none", False, 1e-5, False)
    assert cfg.param_count == 666_963_456
