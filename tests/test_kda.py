"""The delta rule with a decay a channel (``ops/kda.py``) at test size on the
CPU: the chunks in ``jax.numpy`` and the interpreted kernels against the
recurrence TOKEN BY TOKEN — ``o``, the final state and every gradient, at a
length that is no multiple of the chunk, under a strong decay, under step
sizes near one with repeated keys (where an additive rule FAILS), against a
plain delta rule at ``alpha = beta = 1``; the mixer's other new parts (the
sigmoid gated norm a head, latent attention without a bottleneck or a
rotation) against ``jax.numpy`` written out; the family's place in the stack
(a rematerialised block, the counters, what is refused)."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydl_tpu.models import transformer
from easydl_tpu.models.kimi_linear import (MLA, describe,
                                           published_layer_types)
from easydl_tpu.models.registry import get_model
from easydl_tpu.ops import kda as kda_ops

HEADS, SIZE = 2, 8
NAMES = ("o", "state", "dq", "dk", "dv", "dg", "dbeta")


def token_by_token(q, k, v, g, beta, additive=False):
    """The recurrence exactly as it is written, a ``lax.scan`` over tokens;
    ``additive``: the rule WITHOUT its subtraction (``I`` for ``I - beta k
    k^T``)."""
    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        decayed = state * jnp.exp(g_t)[..., None]
        held = 0.0 if additive else jnp.einsum("bhkv,bhk->bhv", decayed, k_t)
        update = b_t[..., None] * (v_t - held)
        state = decayed + k_t[..., :, None] * update[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v, g, beta))
    state0 = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]))
    last, out = jax.lax.scan(step, state0, xs)
    return jnp.swapaxes(out, 0, 1), last


def operands(case: str, seq: int, seed: int = 3, size: int = SIZE):
    """``(q, k, v, g, beta)`` and the weights of a scalar objective over
    ``o`` and the final state. ``strong``: a rate of 16 and steps near 1 (a
    chunk's ``exp(-G)`` would overflow float32); ``repeat``: every key nearly
    the first one and step sizes near 1 (the subtraction is most of the
    update); ``plain_delta``: no decay, step size one; ``none``: no decay."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    shape = (1, seq, HEADS, size)
    q, k, v, w = (jax.random.normal(key, shape) for key in keys[:4])
    if case == "repeat":
        k = k[:, :1] + 0.01 * k
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(size)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    step = jax.nn.softplus(jax.random.normal(keys[4], shape))
    g = -step * {"strong": 16.0, "plain_delta": 0.0, "none": 0.0}.get(
        case, 0.3)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], shape[:3])
                          + (6.0 if case == "repeat" else 0.0))
    if case == "plain_delta":
        beta = jnp.ones_like(beta)
    return (q, k, v, g, beta), (w, jax.random.normal(
        keys[6], (1, HEADS, size, size)))


def results(fn, args, weights):
    """``fn``'s ``o``, final state and the gradients of the weighted sum of
    both to q, k, v, g and beta."""
    def objective(*args):
        o, last = fn(*args)
        return jnp.sum(o * weights[0]) + jnp.sum(last * weights[1])

    return tuple(fn(*args)) + tuple(
        jax.grad(objective, argnums=(0, 1, 2, 3, 4))(*args))


PATHS = {
    "xla": jax.jit(functools.partial(kda_ops.kda, chunk=16, impl="xla")),
    "kernels": jax.jit(functools.partial(
        kda_ops.kda_kernels, chunk=16, sub=8, interpret=True)),
    "token": jax.jit(token_by_token),
}


@pytest.mark.parametrize("path,seq", [("xla", 40), ("kernels", 32)])
@pytest.mark.parametrize("case", ["plain", "strong", "repeat"])
def test_chunks_against_the_recurrence(path, seq, case):
    """40 tokens are two and a half chunks of 16 (the ``jax.numpy`` path
    pads); 32 cross a chunk's edge and, in chunks of two sub-blocks of 8, a
    sub-block's."""
    args, weights = operands(case, seq)
    want = results(PATHS["token"], args, weights)
    mine = results(PATHS[path], args, weights)
    for name, a, b in zip(NAMES, mine, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        scale = float(jnp.max(jnp.abs(b))) + 1e-30
        assert float(jnp.max(jnp.abs(a - b))) / scale < 5e-5, (name, case)


@functools.partial(jax.jit, static_argnames="sub")
def chunk_parts_and_dense(q, k, kb, G, sub):
    """``_scores`` and ``_inverse`` in plain ``jax.numpy`` (no interpreter)
    beside the dense formula: ``A`` and ``P`` from ``exp(G_j - G_i)`` element
    by element over the lower triangle, as ``_chunk_step`` has them, and
    ``(I + A)^{-1}`` by a triangular solve."""
    n = q.shape[0]
    a_front, a_rows, P = kda_ops._scores(q, k, kb, G, sub, jnp.float32)
    T = kda_ops._inverse(a_front, a_rows, sub)
    lower = jnp.tril(jnp.ones((n, n), bool))[..., None]
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, G[:, None] - G[None, :], 0.0)), 0.0)
    A = jnp.sum(kb[:, None] * k[None, :] * decay, -1) \
        * jnp.tril(jnp.ones((n, n)), -1)
    want_T = jax.scipy.linalg.solve_triangular(
        jnp.eye(n) + A, jnp.eye(n), lower=True, unit_diagonal=True)
    return (a_front, jnp.stack(a_rows), P, T), (
        A, jnp.sum(q[:, None] * k[None, :] * decay, -1), want_T)


@pytest.mark.parametrize("n,sub,case", [
    (128, 16, "plain"), (128, 16, "strong"), (128, 16, "repeat"),
    (128, 16, "none"), (16, 8, "plain"), (16, 8, "strong"),
    (16, 8, "repeat"), (16, 16, "repeat")])
def test_the_chunk_algebras_parts_against_the_dense_formula(n, sub, case):
    """``A`` in front of the sub-blocks, ``P`` whole and ``T`` to 1e-6 of
    the largest entry; the compact rows are ``A``'s diagonal sub-blocks side
    by side, ``W_t[j, (b, c)] = A_b[t, j]`` for ``j < t`` in every lane ``c``
    of sub-block ``b``; ``chunk == sub`` (the last case) has no front and
    returns the diagonal inverse alone."""
    (q, k, _, g, beta), _ = operands(case, n, size=8 if n == 16 else 128)
    q, k, g, beta = (x[0, :, 0] for x in (q, k, g, beta))
    (a_front, a_rows, P, T), (A, want_P, want_T) = chunk_parts_and_dense(
        q, k, k * beta[:, None], jnp.cumsum(g, axis=0), sub=sub)
    row, col = np.indices((n, n))
    t, j, lane = np.indices((sub - 1, sub, n))
    own = lane // sub * sub
    want = {"A in front": (a_front, np.where(col < row // sub * sub, A, 0.0)),
            "P": (P, want_P), "T": (T, want_T),
            "W": (a_rows, np.where(j <= t, np.asarray(A)[
                own + t + 1, own + j], 0.0))}
    for what, (mine, wanted) in want.items():
        scale = float(np.max(np.abs(wanted))) + 1e-30
        assert float(np.max(np.abs(mine - wanted))) / scale < 1e-6, what
    assert a_rows.shape == (sub - 1, sub, n)
    assert (n > sub) == bool(np.any(a_front))


def test_an_additive_rule_fails_where_keys_repeat():
    """With every key nearly the same and step sizes near one the state
    holds the last value alone; a rule that ADDS holds their sum."""
    args, _ = operands("repeat", 32)
    o, _ = PATHS["token"](*args)
    mine, _ = PATHS["xla"](*args)
    added, _ = jax.jit(functools.partial(token_by_token, additive=True))(*args)
    size = float(jnp.sqrt(jnp.mean(o ** 2)))
    assert float(jnp.sqrt(jnp.mean((mine - o) ** 2))) / size < 1e-5
    assert float(jnp.sqrt(jnp.mean((added - o) ** 2))) / size > 1.0


def test_no_decay_and_step_one_is_the_plain_delta_rule():
    """``alpha = 1`` and ``beta = 1``: ``S_t = (I - k k^T) S_{t-1} + k v^T``,
    written out with the matrix."""
    (q, k, v, g, beta), _ = operands("plain_delta", 24)
    eye = jnp.eye(SIZE)
    state = jnp.zeros((HEADS, SIZE, SIZE))
    want = []
    for t in range(q.shape[1]):
        k_t, v_t = k[0, t], v[0, t]
        state = jnp.einsum("hij,hjv->hiv", eye - k_t[:, :, None]
                           * k_t[:, None, :], state) \
            + k_t[:, :, None] * v_t[:, None, :]
        want.append(jnp.einsum("hkv,hk->hv", state, q[0, t]))
    for path in ("xla", "token"):
        o, last = PATHS[path](q, k, v, g, beta)
        np.testing.assert_allclose(o[0], jnp.stack(want), atol=2e-6)
        np.testing.assert_allclose(last[0], state, atol=2e-6)


def test_the_kernels_take_whole_chunks_and_say_so():
    args, _ = operands("plain", 24)
    with pytest.raises(ValueError, match="whole chunks"):
        kda_ops.kda_kernels(*args, chunk=16, sub=8, interpret=True)
    assert "128-lane" in kda_ops.untiled(256, 16, 16, 128)
    assert kda_ops.untiled(256, 128, 128, 128) is None
    assert "chunks" in kda_ops.untiled(200, 128, 128, 128)
    assert kda_ops.kda_flops_per_token(32, 128, 128) * 3 == 32 * 294912


def test_the_gated_head_norm_is_norm_then_sigmoid():
    """The norm FIRST, over each head's channels with ONE shared gain, THEN
    a sigmoid gate — not ``ops/ssd.py gated_rmsnorm``'s SiLU in front of a
    norm over all channels with a gain a channel."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    y, z = (jax.random.normal(key, (2, 5, 3, 8)) for key in keys[:2])
    gain = 1.0 + 0.1 * jax.random.normal(keys[2], (8,))
    want = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-5) * gain \
        / (1.0 + jnp.exp(-z))
    np.testing.assert_allclose(
        kda_ops.gated_head_norm(y, z, gain, 1e-5), want, rtol=1e-5, atol=1e-6)
    from easydl_tpu.ops.ssd import gated_rmsnorm

    other = gated_rmsnorm(y, z, jnp.broadcast_to(gain, (3, 8)), 1e-5)
    assert float(jnp.max(jnp.abs(other - want))) > 0.1


# ------------------------------------------------------- in the stack
TEST = dict(size="test", seq_len=24, vocab=64, experts_held=(0, 8))


def _latent_config(**over):
    return describe(**dict(TEST, layer_types=["mla_dense"], **over))


def test_latent_attention_without_bottleneck_or_rotation():
    """``LowRank(q_rank=None)`` under a kind with no rotary scheme: q
    straight from the model's width, the shared key part beside every
    head's own and nothing rotated — against the attention written out."""
    cfg = _latent_config()
    block = transformer.Block(cfg, MLA, "swiglu")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    params = nn.meta.unbox(
        block.init(jax.random.PRNGKey(2), x, True, None)["params"])
    assert "q_a" not in params and "q_norm" not in params
    assert params["q"]["kernel"].shape == (64, 4, 24)
    (_, _), kept = block.apply({"params": params}, x, True, None,
                               mutable=["intermediates"])
    kept = {k: v[0] for k, v in kept["intermediates"].items()}
    u = kept["mla_in"]
    q = jnp.einsum("bsd,dhk->bshk", u, params["q"]["kernel"])
    both = u @ params["kv_a"]["kernel"]
    c, shared = both[..., :16], both[..., 16:]
    c = c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + 1e-5) \
        * params["kv_norm"]
    kv = jnp.einsum("bsr,rhk->bshk", c, params["kv_b"])
    k = jnp.concatenate([kv[..., :16], jnp.broadcast_to(
        shared[:, :, None], (2, 24, 4, 8))], -1)
    scores = jnp.einsum("bqhd,bthd->bhqt", q, k) / np.sqrt(24)
    mask = jnp.tril(jnp.ones((24, 24), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    want = jnp.einsum("bhqt,bthd->bqhd", probs, kv[..., 16:])
    np.testing.assert_allclose(kept["mla_attn"], want, atol=2e-5)
    np.testing.assert_allclose(kept["mla_k_rot"][:, :, 0], shared, atol=1e-6)
    assert "mla_cq" not in kept
    # the count knows the missing bottleneck
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.layer_params((MLA, "swiglu"))


@pytest.mark.parametrize("kind,over", [
    ("no scheme under rotary positions", dict(position="rope")),
    ("no scheme under learned positions", dict(position="learned")),
    ("a scheme of another width", dict(
        position="none", rope=transformer.RopeScheme(rotary_dim=16))),
    ("a window", dict(position="none", window=8)),
    ("a gate", dict(position="none", gate=True)),
    ("sizes that do not add up", dict(position="none", head_size=32)),
])
def test_lowrank_still_refuses(kind, over):
    low = transformer.LowRank(None, 16, 16, 8, 16)
    fields = {k: over.pop(k) for k in ("rope", "window", "gate")
              if k in over}
    with pytest.raises(ValueError, match="low-rank latent attention"):
        transformer.TransformerConfig(
            n_layers=1, layers=((MLA, "gelu"),), d_model=64, n_heads=4,
            attention_kinds=((MLA, transformer.AttentionKind(
                lowrank=low, **fields)),),
            **dict(dict(head_size=24), **over))


def test_the_family_and_what_it_refuses():
    cfg = describe(**TEST)
    assert [layer for layer, _ in cfg.runs] == [
        ("kda", "swiglu"), ("kda", "moe"), (MLA, "moe"), ("kda", "moe")]
    assert published_layer_types("48b-a3b")[:5] == (
        "kda_dense", "kda_sparse", "kda_sparse", "mla_sparse", "kda_sparse")
    assert published_layer_types("48b-a3b").count("mla_sparse") == 7
    family, kind = cfg.mixer_family("kda")
    assert family is transformer.MIXER_FAMILIES["kda"] and kind is None
    assert (family.scope, family.norm, family.needs) == (
        "ssm", "ln_ssm", "kda=KdaConfig")
    assert cfg.counted_layers == 4
    assert cfg.mixer_counters == transformer.KDA_COUNTERS
    assert cfg.counters[-3:] == transformer.KDA_COUNTERS
    assert family.score_flops(cfg, None, 24) == 18.0 * 16 * 16 * 4
    with pytest.raises(ValueError, match="needs kda=KdaConfig"):
        transformer.TransformerConfig(n_layers=1, layers=(("kda", "gelu"),))
    with pytest.raises(NotImplementedError, match="sequence parallelism"):
        transformer.TransformerConfig(
            n_layers=1, layers=(("kda", "gelu"),),
            kda=transformer.KdaConfig(2, 8), attention_fn=lambda *a, **k: 0)
    with pytest.raises(ValueError, match="layers are"):
        describe(size="test", layer_types=["full_attention"])
    big = describe(size="48b-a3b", vocab=20480, experts_held=(0, 8),
                   layer_types=published_layer_types("48b-a3b")[:5])
    assert big.param_count == 602434432
    assert big.layer_params(("kda", "swiglu")) == 103219872


def test_a_rematerialised_stack_is_the_plain_one_and_counts():
    """Loss, counters and every gradient leaf of a stack (a scanned run of
    two delta-rule layers and a latent layer, dense FFNs) under remat
    ``full`` against the stack without; the parameters are the count's."""
    kinds = dict(TEST, layer_types=["kda_dense", "kda_dense", "mla_dense"])
    batch = {"inputs": jnp.arange(48).reshape(2, 24) % 64,
             "targets": (jnp.arange(48).reshape(2, 24) * 7 + 1) % 64}
    out = {}
    for remat in (False, True):
        bundle = get_model("kimi_linear", remat=remat, **kinds)
        params = bundle.init_fn(jax.random.PRNGKey(0))
        out[remat] = jax.jit(jax.value_and_grad(
            lambda p: bundle.loss_fn(p, batch, jax.random.PRNGKey(1)),
            has_aux=True))(params)
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == bundle.param_count_hint
    (loss, metrics), grads = out[False]
    (loss_r, metrics_r), grads_r = out[True]
    assert float(abs(loss - loss_r)) < 1e-6
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(grads_r)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))
    assert float(metrics["kda_chunks"]) == 1.0
    assert 0.0 < float(metrics["kda_decay_mean"]) < 1.0
    assert 0.3 < float(metrics["kda_beta_mean"]) < 0.7
    assert 0.0 < float(metrics["kda_state_rms"]) < 1.0
    for name in transformer.KDA_COUNTERS:
        assert float(metrics[name]) == pytest.approx(float(metrics_r[name]))


def test_the_scopes_are_in_the_program():
    """``conv1d``, ``kda_gates``, ``kda`` and ``gated_norm`` under ``ssm``,
    and no ``rope`` anywhere, in the lowered text."""
    bundle = get_model("kimi_linear", **TEST)
    params = jax.eval_shape(bundle.init_fn, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, 24), jnp.int32)
    text = jax.jit(lambda p, t: bundle.loss_fn(
        p, {"inputs": t, "targets": t}, jax.random.PRNGKey(0))[0]).lower(
            params, tokens).as_text(debug_info=True)
    for scope in ("ssm/conv1d", "ssm/kda_gates", "ssm/kda/", "ssm/gated_norm",
                  "attention/mla_key", "moe/"):
        assert scope in text, scope
    assert "/rope/" not in text
