"""NemotronH (NVIDIA, model type ``nemotron_h``: Nemotron 3 Nano 30B-A3B) in
plain ``jax.numpy`` and float32: forward, loss and gradients, for one chip's
share of the routed experts and of the vocabulary. No kernel, no chunked scan,
no sorted buffer, no grouped product, no sharding, nothing imported from the
program. Every matrix multiplication runs at ``highest`` precision (on a TPU a
float32 matmul is otherwise done in bf16 passes).

Written in the PUBLISHED vocabulary: the model is a list of sub-layers, one a
letter of ``hybrid_override_pattern``, each ``x <- x + mixer(RMSNorm(x))`` with
a norm of its own (eps ``layer_norm_epsilon``), no bias on a map. The program
pairs them into blocks ``(mixer, ffn)``; this file knows nothing of blocks, so
holding the program to it also checks the pairing.

- ``M``, Mamba-2 (Dao and Gu 2024; HF ``modeling_nemotron_h.py``): ``[z, xBC,
  dt] = u W_in``; ``xBC = silu(causal depthwise conv1d(xBC) + bias)``, split
  into ``x`` (``H`` heads of ``P``), ``B`` and ``C`` (``G`` groups of ``N``);
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the **sequential
  recurrence** ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t
  + D x_t`` position by position (a ``lax.scan`` over the sequence, NOT the
  chunked form the program runs), head ``h`` reading the B and C of group ``h
  // (H / G)`` (a gather by that index, no repeat); then ``RMSNorm(y *
  silu(z))`` — the gate first — **group by group**: the mean square over
  each group's ``H P / G`` channels apart (a Python loop over the groups);
  the way back ``W_out``;
- ``*``, attention: q of ``H`` heads, k and v of ``KV`` heads of ``head_dim``,
  no positions applied, causal softmax of ``q k^T / sqrt(head_dim)`` over a
  head's whole ``[S, S]`` matrix, query head ``h`` reading key/value head ``h
  // (H / KV)``, ``W_o``;
- ``E``, experts: ``s = sigmoid(m W_r)`` over ALL experts in float32; the ``k``
  largest of ``s + b`` by ``jnp.argsort`` (``b`` selects and does not weigh:
  it takes no gradient); weights ``scaling * s_e / sum of the chosen s``;
  ``FFN(m) = shared(m) + sum over the chosen e in [lo, hi) of w_e E_e(m)``,
  every expert and the shared one UNGATED: ``relu(m W_up)^2 W_down``. The
  held experts are taken one by one (a ``lax.scan`` over them: each applied to
  every token and weighted by zero where the token did not choose it); chosen
  experts outside ``[lo, hi)`` — the share's range — add nothing;
- after the last sub-layer ``h = RMSNorm_f(x)``, ``logits = h W_head``
  (untied) over the vocabulary held, the loss the mean cross entropy of token
  ``i + 1`` at position ``i``.

Departures: none in the arithmetic. The recurrence is scanned in blocks of
positions under ``jax.checkpoint``, a head's score matrix is made one head at
a time (``jax.lax.map`` under ``jax.checkpoint``) and each sub-layer is
checkpointed: that bounds memory and changes nothing computed.
``expert_layer(..., chosen=)`` takes the chosen sets from outside (routing is
discrete: the check hands the program's sets over so that one near-tie does
not swamp a comparison of states); the weights are then still from the
reference's own scores.

Parameters are a plain dict: ``wte [V, D]``, ``head [D, V]``, ``lnf_g [D]``
and ``layers``, a list with one dict a sub-layer; the letters travel beside it
as a string, ``pattern``. Every sub-layer has ``norm_g [D]``. ``M`` adds
``in_proj [D, 2 H P + 2 G N + H]`` (columns in the published order z, x, B, C,
dt), ``conv_w [K, H P + 2 G N]``, ``conv_b``, ``dt_bias [H]``, ``A_log [H]``,
``D [H]``, ``gnorm_g [H P]``, ``out_proj [H P, D]``; ``*`` adds ``wq [D, H,
hd]``, ``wk, wv [D, KV, hd]``, ``wo [H, hd, D]``; ``E`` adds ``router [D,
E]``, ``bias [E]``, ``e_up [hi - lo, D, f]``, ``e_down [hi - lo, f, D]``,
``s_up [D, f_s]``, ``s_down [f_s, D]``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LETTERS = ("M", "*", "E")


def dot(spec: str, x, w):
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


# ---------------------------------------------------------------- Mamba-2
def in_projection(u, p, hp):
    """``(z [b, s, H P], x [b, s, H, P], B, C [b, s, G, N], dt [b, s, H])``:
    the fused input map, the convolution over ``[x, B, C]`` with its bias and
    SiLU, ``dt`` through softplus."""
    batch, seq, _ = u.shape
    heads, size = hp["mamba_num_heads"], hp["mamba_head_dim"]
    groups, state = hp["n_groups"], hp["ssm_state_size"]
    inner = heads * size
    zxbcdt = dot("bsd,df->bsf", u, p["in_proj"])
    z, xBC, dt = jnp.split(
        zxbcdt, [inner, 2 * inner + 2 * groups * state], axis=-1)
    taps = p["conv_w"].shape[0]
    padded = jnp.pad(xBC, ((0, 0), (taps - 1, 0), (0, 0)))
    xBC = jax.nn.silu(p["conv_b"] + sum(
        padded[:, k:k + seq] * p["conv_w"][k] for k in range(taps)))
    x, B, C = jnp.split(xBC, [inner, inner + groups * state], axis=-1)
    return (z, x.reshape(batch, seq, heads, size),
            B.reshape(batch, seq, groups, state),
            C.reshape(batch, seq, groups, state),
            jax.nn.softplus(dt + p["dt_bias"]))


def recurrence(x, dt, A, B, C, D):
    """``y`` of the Mamba-2 recurrence, one position at a time. ``x [b, s,
    H, P]``, ``dt [b, s, H]``, ``A, D [H]``, ``B, C [b, s, G, N]``; head ``h``
    reads group ``h // (H / G)``."""
    batch, seq, heads, _ = x.shape
    group_of = jnp.arange(heads) // (heads // B.shape[2])

    def step(h, inputs):
        x_t, dt_t, B_t, C_t = inputs  # [b, H, P], [b, H], [b, G, N] x 2
        B_t, C_t = B_t[:, group_of], C_t[:, group_of]  # [b, H, N]
        decay = jnp.exp(dt_t * A)[..., None, None]
        h = decay * h + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :]
        y_t = dot("bhpn,bhn->bhp", h, C_t)
        return h, y_t + D[:, None] * x_t

    block = math.gcd(seq, 64)

    @jax.checkpoint
    def steps(h, inputs):
        return jax.lax.scan(step, h, inputs)

    def blocks(a):  # [b, s, ...] -> [s / block, block, b, ...]
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((seq // block, block) + a.shape[1:])

    h0 = jnp.zeros((batch, heads, x.shape[3], B.shape[3]), jnp.float32)
    _, y = jax.lax.scan(steps, h0, (blocks(x), blocks(dt), blocks(B),
                                    blocks(C)))
    return jnp.moveaxis(y.reshape((seq,) + y.shape[2:]), 0, 1)


def grouped_gated_norm(y, z, gain, groups: int, eps):
    """``RMSNorm(y * silu(z)) * gain`` on ``[b, s, inner]``, the mean square
    taken over each of the ``groups`` runs of ``inner / groups`` channels
    apart, group by group."""
    gated = y * jax.nn.silu(z)
    size = gated.shape[-1] // groups
    out = []
    for g in range(groups):
        part = gated[..., g * size:(g + 1) * size]
        out.append(part * jax.lax.rsqrt(
            jnp.mean(part * part, -1, keepdims=True) + eps))
    return jnp.concatenate(out, -1) * gain


def mamba(u, p, hp):
    z, x, B, C, dt = in_projection(u, p, hp)
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), B, C, p["D"])
    y = grouped_gated_norm(y.reshape(z.shape), z, p["gnorm_g"],
                           hp["n_groups"], hp["eps"])
    return dot("bsf,fd->bsd", y, p["out_proj"])


# -------------------------------------------------------------- attention
def attention(u, p, hp):
    seq = u.shape[1]
    q = dot("bsd,dhk->bshk", u, p["wq"])
    k = dot("bsd,dhk->bshk", u, p["wk"])
    v = dot("bsd,dhk->bshk", u, p["wv"])
    heads, size = q.shape[2:]
    rep = heads // k.shape[2]
    mask = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    scale = 1.0 / math.sqrt(size)

    def one_head(h):
        qh, kh, vh = q[:, :, h], k[:, :, h // rep], v[:, :, h // rep]
        scores = dot("bqd,btd->bqt", qh, kh) * scale
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return dot("bqt,btd->bqd", probs, vh)

    out = jax.lax.map(jax.checkpoint(one_head), jnp.arange(heads))
    return dot("bqhk,hkd->bqd", jnp.moveaxis(out, 0, 2), p["wo"])


# ----------------------------------------------------------------- experts
def router(m, w_router, bias, k: int):
    """``(logits, chosen [.., k])``: float32 logits over all experts and the
    experts of the ``k`` largest ``sigmoid(logits) + bias``, by
    ``jnp.argsort``."""
    logits = dot("bsd,de->bse", m, w_router)
    chosen = jnp.argsort(-(jax.nn.sigmoid(logits) + bias), axis=-1)[..., :k]
    return logits, chosen


def expert_layer(m, p, hp, chosen=None):
    """The share's part of an ``E`` sub-layer on the normed ``m [B, S, D]``:
    ``(y, logits, own chosen sets)``; ``chosen`` from outside replaces the
    reference's own sets."""
    lo, hi = hp["experts_held"]
    logits, own = router(m, p["router"], p["bias"], hp["k"])
    chosen = own if chosen is None else chosen
    scores = jnp.take_along_axis(jax.nn.sigmoid(logits), chosen, axis=-1)
    weights = hp["scaling"] * scores / jnp.sum(scores, -1, keepdims=True)
    y = dot("bsf,fd->bsd", relu2(dot("bsd,df->bsf", m, p["s_up"])),
            p["s_down"])

    def one(y, expert):  # absent experts add nothing: they are not looped
        e, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        return y + w_e[..., None] * dot(
            "bsf,fd->bsd", relu2(dot("bsd,df->bsf", m, w_up)), w_down), None

    y, _ = jax.lax.scan(jax.checkpoint(one), y,
                        (jnp.arange(lo, hi), p["e_up"], p["e_down"]))
    return y, logits, own


# -------------------------------------------------------------- the model
def sublayer(x, p: Dict[str, Any], letter: str, hp, chosen=None):
    """One published sub-layer on ``x [B, S, D]``: ``(x, router logits, own
    chosen sets)`` (the last two None unless the letter is ``E``)."""
    u = rms_norm(x, p["norm_g"], hp["eps"])
    if letter == "M":
        return x + mamba(u, p, hp), None, None
    if letter == "*":
        return x + attention(u, p, hp), None, None
    if letter != "E":
        raise ValueError(f"sub-layer {letter!r}; {LETTERS}")
    y, logits, own = expert_layer(u, p, hp, chosen)
    return x + y, logits, own


def cross_entropy(x, gain, head, targets, hp):
    """Mean cross entropy from a state ``x`` through the final norm and the
    untied head."""
    logits = dot("bsd,dv->bsv", rms_norm(x, gain, hp["eps"]), head)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()


def states(params, pattern: str, tokens, hp, chosen=None):
    """Every sub-layer's output state, in order. ``chosen``: one entry a
    sub-layer (None where it is no ``E`` or routes for itself)."""
    x, out = params["wte"][tokens], []
    for i, (p, letter) in enumerate(zip(params["layers"], pattern)):
        x = jax.checkpoint(functools.partial(
            sublayer, letter=letter, hp=hp))(
                x, p, chosen=None if chosen is None else chosen[i])[0]
        out.append(x)
    return out


def loss(params, pattern: str, tokens, targets, hp):
    with jax.default_matmul_precision("highest"):
        x = states(params, pattern, tokens, hp)[-1]
        return cross_entropy(x, params["lnf_g"], params["head"], targets, hp)


def loss_and_grads(params, pattern: str, tokens, targets, hp):
    """``(loss, d loss / d params)``, ``jax.grad`` of the whole loss in one
    jitted call. The selection biases' gradient is exactly zero: they enter
    through ``argsort`` alone."""
    def f(params, tokens, targets):
        return loss(params, pattern, tokens, targets, hp)

    return jax.jit(jax.value_and_grad(f))(params, tokens, targets)


def hyper(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file's scalars the functions above read as ``hp``.
    Callers close over it; it is never an argument of a jitted function."""
    held = config["kwargs"].get("experts_held") or (
        0, config["n_routed_experts_published"])
    keys = ("mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size")
    return dict({key: int(config[key]) for key in keys},
                eps=float(config["layer_norm_epsilon"]),
                k=int(config["num_experts_per_tok"]),
                scaling=float(config["routed_scaling_factor"]),
                experts_held=(int(held[0]), int(held[1])))
