"""Granite 4.0-H (IBM, model type ``granitemoehybrid`` without experts) in
plain ``jax.numpy`` and float32: forward, loss and gradients. No kernel, no
chunking, no sharding, nothing imported from the program. Every matrix
multiplication runs at ``highest`` precision (on a TPU a float32 matmul is
otherwise done in bf16 passes).

Follows the published model as HF ``modeling_granitemoehybrid.py`` and the
Mamba-2 paper (Dao and Gu 2024) have it:

- ``x0 = embedding_multiplier * embed(tokens)``; no position table, no rotary
  (``position_embedding_type`` "nope");
- every layer: ``x = x + residual_multiplier * mixer(RMSNorm(x))``, then
  ``x = x + residual_multiplier * W_out(silu(a) * b)`` with ``[a, b] = W_in
  RMSNorm(x)`` (the shared SwiGLU MLP, no bias);
- attention mixer: q of ``H`` heads, k and v of ``KV`` heads, no bias, causal
  softmax of ``attention_multiplier * q k^T``, query head ``h`` reads
  key/value head ``h // (H / KV)``, output projection;
- Mamba-2 mixer: ``[z, xBC, dt] = W_in u``; ``xBC = silu(causal depthwise
  conv1d(xBC) + bias)``, split into ``x`` (heads x head size), ``B`` and ``C``
  (groups x state); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  the **sequential recurrence** ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t
  B_t^T``, ``y_t = h_t C_t + D x_t`` position by position (a ``lax.scan``
  over the sequence, NOT the chunked form the program runs);
  ``y = RMSNorm(y * silu(z)) * weight`` over the inner channels (one group);
  output projection;
- final RMSNorm; logits ``= hidden @ embed^T / logits_scaling``, tied; loss is
  the mean next-token cross-entropy.

Departures: dropouts are 0 in the source too; weights are whatever the caller
passes (the program's seeded initial values). The recurrence is scanned in
blocks of positions under ``jax.checkpoint`` and each layer is checkpointed:
that bounds the gradient's memory and changes no arithmetic.

Parameters are a plain dict: ``wte [V, d]``, ``lnf_g [d]`` and ``layers``, a
list with one dict a layer; the layers' kinds ("mamba" | "attention") travel
beside it as a tuple of strings, ``kinds``. Every layer has ``norm_g [d]``,
``ln2_g [d]``, ``w_in [d, 2 ff]``, ``w_out [ff, d]``. A Mamba layer adds ``in_proj [d, 2 inner + 2 G N + H]`` (columns
in the published order z, x, B, C, dt), ``conv_w [K, inner + 2 G N]``,
``conv_b``, ``dt_bias [H]``, ``A_log [H]``, ``D [H]``, ``gnorm_g [inner]``,
``out_proj [inner, d]``; an attention layer ``wq [d, H, hd]``, ``wk, wv [d,
KV, hd]``, ``wo [H, hd, d]``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def embed(tokens, wte, multiplier):
    """``[B, S]`` ids -> ``[B, S, d]``."""
    return multiplier * wte[tokens]


def swiglu(x, p, hp):
    h = rms_norm(x, p["ln2_g"], hp["eps"])
    ab = jnp.einsum("bsd,df->bsf", h, p["w_in"], precision=HIGHEST)
    a, b = jnp.split(ab, 2, axis=-1)
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(a) * b, p["w_out"],
                      precision=HIGHEST)


def attention(u, p, hp):
    seq = u.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", u, p["wq"], precision=HIGHEST)
    k = jnp.einsum("bsd,dhk->bshk", u, p["wk"], precision=HIGHEST)
    v = jnp.einsum("bsd,dhk->bshk", u, p["wv"], precision=HIGHEST)
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = hp["attention_multiplier"] * jnp.einsum(
        "bqhk,bthk->bhqt", q, k, precision=HIGHEST)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqt,bthk->bqhk", probs, v, precision=HIGHEST)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"], precision=HIGHEST)


def recurrence(x, dt, A, B, C, D):
    """``y`` of the Mamba-2 recurrence, one position at a time. ``x [b, s,
    H, P]``, ``dt [b, s, H]``, ``A, D [H]``, ``B, C [b, s, G, N]``."""
    batch, seq, heads, _ = x.shape
    rep = heads // B.shape[2]
    B, C = jnp.repeat(B, rep, axis=2), jnp.repeat(C, rep, axis=2)

    def step(h, inputs):
        x_t, dt_t, B_t, C_t = inputs  # [b, H, P], [b, H], [b, H, N] x 2
        decay = jnp.exp(dt_t * A)[..., None, None]
        h = decay * h + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :]
        y_t = jnp.einsum("bhpn,bhn->bhp", h, C_t, precision=HIGHEST)
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


def mamba(u, p, hp):
    batch, seq, _ = u.shape
    heads, head_dim = hp["mamba_n_heads"], hp["mamba_d_head"]
    groups, state = hp["mamba_n_groups"], hp["mamba_d_state"]
    inner = heads * head_dim
    zxbcdt = jnp.einsum("bsd,df->bsf", u, p["in_proj"], precision=HIGHEST)
    z, xBC, dt = jnp.split(
        zxbcdt, [inner, 2 * inner + 2 * groups * state], axis=-1)
    taps = p["conv_w"].shape[0]
    padded = jnp.pad(xBC, ((0, 0), (taps - 1, 0), (0, 0)))
    xBC = jax.nn.silu(p["conv_b"] + sum(
        padded[:, k:k + seq] * p["conv_w"][k] for k in range(taps)))
    x, B, C = jnp.split(xBC, [inner, inner + groups * state], axis=-1)
    y = recurrence(
        x.reshape(batch, seq, heads, head_dim),
        jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
        B.reshape(batch, seq, groups, state),
        C.reshape(batch, seq, groups, state), p["D"])
    y = y.reshape(batch, seq, inner) * jax.nn.silu(z)
    y = rms_norm(y, p["gnorm_g"], hp["eps"])
    return jnp.einsum("bsf,fd->bsd", y, p["out_proj"], precision=HIGHEST)


def layer(x, p: Dict[str, Any], kind: str, hp):
    """One layer of ``kind`` on ``x: [B, S, d]``; ``p`` is the layer's dict;
    ``hp`` the scalars (``hyper``)."""
    mixer = mamba if kind == "mamba" else attention
    x = x + hp["residual_multiplier"] * mixer(
        rms_norm(x, p["norm_g"], hp["eps"]), p, hp)
    return x + hp["residual_multiplier"] * swiglu(x, p, hp)


def final_hidden(x, lnf_g, eps):
    return rms_norm(x, lnf_g, eps)


def lm_loss(hidden, wte, targets, logits_scaling):
    """Mean cross-entropy of the tied head's logits against ``targets``."""
    logits = jnp.einsum("bsd,vd->bsv", hidden, wte,
                        precision=HIGHEST) / logits_scaling
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -picked.mean()


def hyper(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file's scalars the functions above read as ``hp``:
    ``eps``, the four multipliers and the Mamba sizes. Callers close over
    it; it is never an argument of a jitted function."""
    keys = ("embedding_multiplier", "attention_multiplier",
            "residual_multiplier", "logits_scaling", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_n_groups")
    return dict({k: config[k] for k in keys},
                eps=float(config["rms_norm_eps"]))


def forward(params: Dict[str, Any], kinds: Sequence[str], tokens, hp):
    """Final hidden state ``[B, S, d]`` (after the last RMSNorm)."""
    x = embed(tokens, params["wte"], hp["embedding_multiplier"])
    for p, kind in zip(params["layers"], kinds):
        x = jax.checkpoint(functools.partial(layer, kind=kind, hp=hp))(x, p)
    return final_hidden(x, params["lnf_g"], hp["eps"])


def loss(params: Dict[str, Any], kinds: Sequence[str], tokens, targets, hp):
    return lm_loss(forward(params, kinds, tokens, hp), params["wte"],
                   targets, hp["logits_scaling"])


def loss_and_grads(params: Dict[str, Any], kinds: Sequence[str], tokens,
                   targets, hp):
    """``(loss, d loss / d params)``, one jitted call."""
    def f(params, tokens, targets):
        return loss(params, tuple(kinds), tokens, targets, hp)

    return jax.jit(jax.value_and_grad(f))(params, tokens, targets)
