"""reference_kimi_linear against the program, in process, on the CPU at the
test size: in float32 the two are the same mathematics — the program's chunk
form against the reference's recurrence TOKEN BY TOKEN — and agree to
rounding; in bf16 the comparison's errors sit where the configuration file's
tolerances expect them; and each fault the tolerances are there for — an
additive update, a scalar decay, a dropped convolution tap, a rotated key
part, a selection that is not the largest — fails at least one of them. The
reference imports nothing from the program, its pieces give the gradient its
one function gives, and the 32 shares of a sparse layer add up to the uncut
layer."""

import ast
import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from conftest import BENCH
from lib import check_kimi_linear
from lib import reference_kimi_linear as ref


def _config():
    with open(os.path.join(BENCH, "configs", "kimi-linear-test.json")) as f:
        return copy.deepcopy(json.load(f))


def _check(dtype, compute_dtype, tolerances=None, seed=0):
    from easydl_tpu.core.mesh import MeshSpec, build_mesh
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.registry import get_model

    config = _config()
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
    return check_kimi_linear.check(config, bundle, trainer, seed)


TIGHT = dict({f"state_rel_rms_layer_{l}": 2e-5 for l in range(5)},
             state_rel_rms_final=2e-5, token_rel_max=1e-4,
             router_logits_rel=1e-5, kda_inputs_token_rel_max=2e-5,
             kda_out_token_rel_max=2e-5, kda_out_rel_rms=2e-5,
             kda_state_rel_rms=2e-5, mla_latent_token_rel_max=2e-5,
             mla_attn_token_rel_max=2e-5, moe_dropped=0,
             chosen_sets_differ_share=0.0, chosen_not_top8_share=0.0,
             loss_abs=5e-5, grad_rel_rms_worst=1e-3)


def _failing(result):
    return {k for k, tol in result["tolerances"].items()
            if not result["errors"][k] <= tol}


def test_float32_program_equals_the_reference_to_rounding():
    result = _check("float32", jnp.float32, TIGHT)
    assert result["ok"], result
    assert result["errors"]["grad_rel_rms_all"] > 0  # it did compare
    # 3 + a dense KDA layer's 20 + three sparse ones' 25 + the latent's 15
    assert result["errors"]["grad_leaves"] == 3 + 20 + 3 * 25 + 15
    assert 0.5 < result["counters"]["moe_rows_per_token"] < 1.5  # 4 x 8 / 32
    assert result["counters"]["kda_chunks"] == 1.0


@pytest.mark.parametrize("seed", [0, 2147483653])
def test_bf16_program_sits_inside_the_files_tolerances(seed):
    result = _check("bfloat16", jnp.bfloat16, seed=seed)
    assert result["ok"], result
    # and not by a mile: bf16 is visible in every layer and in the kernels
    for l in range(5):
        assert result["errors"][f"state_rel_rms_layer_{l}"] > 1e-3
    assert result["errors"]["kda_out_rel_rms"] > 1e-3
    assert result["errors"]["router_logits_rel"] <= 1e-5
    assert not _check("bfloat16", jnp.bfloat16, TIGHT, seed=seed)["ok"]


def _wrong_rule(additive=False, scalar=False):
    """``ops/kda.py kda`` with a wrong rule in its place, token by token."""
    def wrong(q, k, v, g, beta, **_):
        if scalar:  # ONE rate a head: the channels' mean
            g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        f32 = jnp.float32

        def step(state, x):
            q_t, k_t, v_t, g_t, b_t = x
            decayed = state * jnp.exp(g_t)[..., None]
            held = 0.0 if additive else jnp.einsum(
                "bhkv,bhk->bhv", decayed, k_t)
            state = decayed + k_t[..., :, None] * (
                b_t[..., None] * (v_t - held))[..., None, :]
            return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

        xs = tuple(jnp.swapaxes(x.astype(f32), 0, 1)
                   for x in (q, k, v, g, beta))
        state0 = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]))
        last, out = jax.lax.scan(step, state0, xs)
        return jnp.swapaxes(out, 0, 1).astype(v.dtype), last

    return wrong


@pytest.mark.parametrize("fault,shows", [
    ("additive", "kda_out_rel_rms"), ("scalar", "kda_inputs_token_rel_max")])
def test_a_wrong_rule_fails(monkeypatch, fault, shows):
    """``I`` for ``I - beta k k^T`` shows in the kernels' result on equal
    inputs; a scalar decay is a decay the reference's step 2 does not make
    (the check hands out the g the kernels were given)."""
    from easydl_tpu.models import transformer

    if fault == "additive":
        monkeypatch.setattr(transformer, "kda", _wrong_rule(additive=True))
    else:
        real = transformer.kda
        monkeypatch.setattr(
            transformer, "kda", lambda q, k, v, g, beta, **kw: real(
                q, k, v, jnp.broadcast_to(jnp.mean(g, -1, keepdims=True),
                                          g.shape), beta, **kw))
    result = _check("float32", jnp.float32, TIGHT)
    failing = _failing(result)
    assert failing & {shows, "kda_out_rel_rms", "state_rel_rms_layer_0"}, \
        result["errors"]
    assert "state_rel_rms_layer_0" in failing or fault == "scalar"


def test_a_dropped_tap_fails_the_kernels_inputs(monkeypatch):
    from easydl_tpu.models import transformer

    real = transformer.causal_conv1d_silu
    monkeypatch.setattr(
        transformer, "causal_conv1d_silu",
        lambda x, w, b=None: real(x, w.at[0].set(0.0), b))
    result = _check("bfloat16", jnp.bfloat16)
    assert "kda_inputs_token_rel_max" in _failing(result), result["errors"]
    # the recurrence on the program's own q, k, v is still the recurrence
    assert "kda_out_rel_rms" not in _failing(result)


def test_a_rotated_key_part_fails_the_latents(monkeypatch):
    """A rotary scheme on the latent kind (DeepSeek-V3's reading of the
    same keys): the shared key part and q's last lanes are wrong at every
    position but 0."""
    import dataclasses

    from easydl_tpu.models import kimi_linear, transformer

    real = kimi_linear.describe

    def rotated(**kwargs):
        cfg = real(**kwargs)
        (name, kind), = cfg.attention_kinds
        scheme = transformer.RopeScheme(rotary_dim=kind.lowrank.rope_dim,
                                        last=True, interleaved=True)
        return dataclasses.replace(cfg, attention_kinds=((
            name, dataclasses.replace(kind, rope=scheme)),))

    monkeypatch.setattr(kimi_linear, "describe", rotated)
    # the check applies the program's blocks with no tables: give it the
    # kind's own, as the stack does
    real_block = transformer.Block.__call__

    def with_tables(self, x, deterministic=True, rope=None):
        if rope is None and self.mixer == kimi_linear.MLA:
            rope = self.cfg.attention_kind(self.mixer).rope.tables(
                x.shape[1], self.cfg.head_dim)
        return real_block(self, x, deterministic, rope)

    monkeypatch.setattr(transformer.Block, "__call__", with_tables)
    result = _check("bfloat16", jnp.bfloat16)
    assert "mla_latent_token_rel_max" in _failing(result), result["errors"]
    assert result["errors"]["mla_latent_token_rel_max"] > 0.3


def test_a_selection_that_is_not_the_largest_fails_alone(monkeypatch):
    from easydl_tpu.ops import moe

    real = moe.route

    def tipped(h, kernel, k, scaling, bias=None):
        logits, _, _ = real(h, kernel, k, scaling, bias)
        scores = jax.nn.sigmoid(logits)
        tip = 1e-3 * (jnp.arange(scores.shape[-1]) % 2)
        _, chosen = jax.lax.top_k(scores + bias + tip, k)
        top = jnp.take_along_axis(scores, chosen, -1)
        return logits, chosen, scaling * top / jnp.sum(top, -1, keepdims=True)

    monkeypatch.setattr(moe, "route", tipped)
    result = _check("bfloat16", jnp.bfloat16)
    assert _failing(result) == {"chosen_not_top8_share"}, result["errors"]


def test_the_reference_imports_nothing_from_the_program():
    for name in ("reference_kimi_linear", "flops_kimi_linear"):
        with open(os.path.join(BENCH, "lib", f"{name}.py")) as f:
            tree = ast.parse(f.read())
        modules = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                modules |= {a.name.split(".")[0] for a in node.names}
            elif isinstance(node, ast.ImportFrom):
                modules.add((node.module or "").split(".")[0])
        assert modules <= {"__future__", "functools", "math", "typing", "jax",
                           "lib"}, name


def _seeded_reference(seed=0, held=(0, 8)):
    """Seeded parameters in the reference's own layout at a tiny size: a
    dense KDA layer, a sparse KDA layer, a sparse latent layer."""
    d, heads, size, r_kv, nope, rot, v = 32, 2, 8, 8, 8, 4, 8
    f, f_dense, experts, vocab = 16, 48, 16, 64
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 256))

    def normal(*shape, scale=0.1):
        return scale * jax.random.normal(next(keys), shape)

    def mixer(kind):
        if kind == "mla":
            return {"mq": normal(d, heads, nope + rot, scale=0.3),
                    "wkva": normal(d, r_kv + rot, scale=0.3),
                    "kvn": 1 + normal(r_kv),
                    "wkvb": normal(r_kv, heads, nope + v, scale=0.3),
                    "mo": normal(heads, v, d)}
        wide = (d, heads, size)
        return {"wq": normal(*wide, scale=0.3), "wk": normal(*wide, scale=0.3),
                "wv": normal(*wide, scale=0.3), "cq": normal(4, heads, size,
                                                             scale=0.5),
                "ck": normal(4, heads, size, scale=0.5),
                "cv": normal(4, heads, size, scale=0.5),
                "wfa": normal(d, size, scale=0.3),
                "wfb": normal(size, heads, size, scale=0.3),
                "a_log": jnp.log(1.0 + 7.0 * jax.random.uniform(
                    next(keys), (heads,))),
                "dt_bias": normal(heads, size, scale=1.0),
                "wb": normal(d, heads, scale=0.3),
                "wga": normal(d, size, scale=0.3),
                "wgb": normal(size, heads, size, scale=0.3),
                "gn": 1 + normal(size), "wo": normal(heads, size, d)}

    def layer(kind, sparse):
        p = dict(mixer(kind), n1=1 + normal(d), n2=1 + normal(d))
        if not sparse:
            return dict(p, w_gate=normal(d, f_dense), w_up=normal(d, f_dense),
                        w_down=normal(f_dense, d))
        n = held[1] - held[0]
        return dict(p, router=normal(d, experts, scale=0.5),
                    bias=normal(experts, scale=0.3),
                    e_gate=normal(n, d, f), e_up=normal(n, d, f),
                    e_down=normal(n, f, d), s_gate=normal(d, f),
                    s_up=normal(d, f), s_down=normal(f, d))

    params = {"wte": normal(vocab, d, scale=1.0),
              "head": normal(d, vocab, scale=0.3), "lnf_g": 1 + normal(d),
              "layers": [layer("kda", False), layer("kda", True),
                         layer("mla", True)]}
    hp = {"eps": 1e-5, "nope": nope, "rot": rot, "k": 4, "scaling": 2.446,
          "experts_held": held}
    tokens = np.random.default_rng(seed).integers(0, vocab, (2, 25))
    return params, hp, jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])


@pytest.mark.parametrize("by_row", [False, True])
def test_the_pieces_give_the_one_functions_loss_and_gradient(by_row):
    params, hp, tokens, targets = _seeded_reference()
    want, grads = ref.loss_and_grads(params, tokens, targets, hp)
    with jax.default_matmul_precision("highest"):
        got, pieces = ref.Pieces(hp).loss_and_grads(
            params, tokens, targets, by_row=by_row)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    flat_w, _ = jax.tree_util.tree_flatten_with_path(grads)
    flat_g = dict(jax.tree_util.tree_flatten_with_path(pieces)[0])
    assert len(flat_w) == len(flat_g) == 3 + 20 + 25 + 15
    for path, leaf in flat_w:
        np.testing.assert_allclose(flat_g[path], leaf, rtol=2e-4, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))
    # the selection bias selects and takes no gradient
    assert not np.asarray(grads["layers"][1]["bias"]).any()
    assert np.asarray(grads["layers"][0]["a_log"]).any()
    assert np.asarray(grads["layers"][0]["cq"]).all()


def test_the_shares_add_up_to_the_uncut_layer():
    """The reference itself at a small size: the routed parts of the shares
    (here four of four experts each, as 32 of eight in the deployment), with
    the mixer, the shared expert and the router counted ONCE, add up to the
    uncut layer — for a KDA layer and for the latent one; a biased expert is
    chosen and weighs by its score alone."""
    params, hp, tokens, _ = _seeded_reference(seed=3, held=(0, 16))
    x = params["wte"][tokens]
    for p in params["layers"][1:]:
        with jax.default_matmul_precision("highest"):
            whole, logits, own = ref.layer(x, p, hp)
            mid = ref.mixer_residual(x, p, hp)
            m = ref.rms_norm(mid, p["n2"], hp["eps"])
            shared = ref.swiglu(m, p["s_gate"], p["s_up"], p["s_down"])
            routed = 0.0
            for lo in range(0, 16, 4):
                share = dict(p, **{k: p[k][lo:lo + 4]
                                   for k in ("e_gate", "e_up", "e_down")})
                part = ref.layer(x, share,
                                 dict(hp, experts_held=(lo, lo + 4)))[0]
                routed = routed + (part - mid - shared)
        np.testing.assert_allclose(mid + shared + routed, whole, atol=2e-6)
        taken = np.asarray(own)
        assert all(((taken >= lo) & (taken < lo + 4)).any()
                   for lo in range(0, 16, 4))
        plain = np.argsort(-np.asarray(jax.nn.sigmoid(logits)), -1)[..., :4]
        assert (np.sort(plain, -1) != np.sort(taken, -1)).any()


def test_the_recurrence_is_the_rule_as_written():
    """One head, three tokens, by hand with the matrices: ``S_t = (I - beta
    k k^T) Diag(alpha) S_{t-1} + beta k v^T``; a convolution's tap 3 is the
    current token's."""
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v = (jax.random.normal(key, (1, 3, 1, 4)) for key in keys[:3])
    g = -jax.nn.softplus(jax.random.normal(keys[3], (1, 3, 1, 4)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, 3, 1)))
    o, last = ref.recurrence(q, k, v, g, beta)
    state, eye = np.zeros((4, 4)), np.eye(4)
    for t in range(3):
        k_t, v_t, b_t = (np.asarray(x[0, t, 0]) for x in (k, v, beta))
        state = (eye - b_t * np.outer(k_t, k_t)) @ (
            np.exp(np.asarray(g[0, t, 0]))[:, None] * state) \
            + b_t * np.outer(k_t, v_t)
        np.testing.assert_allclose(o[0, t, 0], state.T @ np.asarray(
            q[0, t, 0]), atol=1e-6)
    np.testing.assert_allclose(last[0, 0], state, atol=1e-6)
    x = jnp.arange(24.0).reshape(1, 6, 1, 4)
    taps = jnp.asarray([0.0, 0.0, 0.0, 1.0])[:, None, None] * jnp.ones((1, 4))
    np.testing.assert_allclose(ref.conv4(x, taps), x)
    back = jnp.asarray([0.0, 0.0, 1.0, 0.0])[:, None, None] * jnp.ones((1, 4))
    np.testing.assert_allclose(ref.conv4(x, back)[:, 1:], x[:, :-1])
    np.testing.assert_allclose(ref.conv4(x, back)[:, 0], 0.0)
