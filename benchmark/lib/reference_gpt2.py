"""GPT-2 (Radford et al. 2019) in plain ``jax.numpy`` and float32: forward,
loss and gradients. No kernel, no cache, no sharding, nothing imported from
the program. Every matrix multiplication runs at ``highest`` precision (on a
TPU a float32 matmul is otherwise done in bf16 passes).

Follows the published model as openai/gpt-2 ``model.py`` and HF
``modeling_gpt2.py`` have it: learned token and position embeddings, pre-LN
blocks (LayerNorm, causal multi-head attention scaled by 1/sqrt(head size),
output projection, residual; LayerNorm, MLP with the tanh form of GELU,
residual), a final LayerNorm and a head tied to the token embedding; loss is
the mean next-token cross-entropy. Departures, each stated by the
configuration file and passed in: ``layer_norm_epsilon`` (the program runs
flax's default 1e-6, the source says 1e-5) and the padded vocabulary. Dropout
is 0. ``loss`` wraps each block in ``jax.checkpoint`` and scans them: that
bounds memory and compile time and changes no arithmetic.

Parameters are a plain dict (see ``PARAM_KEYS``); the layers' arrays are
stacked on a leading axis. Layouts: ``wq, wk, wv: [L, d, H, hd]``,
``wo: [L, H, hd, d]``, ``w_up: [L, d, ff]``, ``w_down: [L, ff, d]``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

BLOCK_KEYS = ("ln1_g", "ln1_b", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
              "bo", "ln2_g", "ln2_b", "w_up", "b_up", "w_down", "b_down")
PARAM_KEYS = ("wte", "wpe", "blocks", "lnf_g", "lnf_b")
HIGHEST = jax.lax.Precision.HIGHEST


def layer_norm(x, gain, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def embed(tokens, wte, wpe):
    """``[B, S]`` ids -> ``[B, S, d]``."""
    return wte[tokens] + wpe[None, :tokens.shape[1]]


def block(x, p: Dict[str, Any], eps: float):
    """One pre-LN block on ``x: [B, S, d]``; ``p`` holds one layer's arrays
    (``BLOCK_KEYS`` without the leading layer axis)."""
    seq = x.shape[1]
    h = layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"], precision=HIGHEST) + p["bq"]
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"], precision=HIGHEST) + p["bk"]
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"], precision=HIGHEST) + p["bv"]
    scores = jnp.einsum("bqhk,bthk->bhqt", q, k, precision=HIGHEST)
    scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqt,bthk->bqhk", probs, v, precision=HIGHEST)
    x = x + jnp.einsum("bqhk,hkd->bqd", attn, p["wo"],
                       precision=HIGHEST) + p["bo"]
    h = layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
    h = gelu_tanh(jnp.einsum("bsd,df->bsf", h, p["w_up"],
                             precision=HIGHEST) + p["b_up"])
    return x + jnp.einsum("bsf,fd->bsd", h, p["w_down"],
                          precision=HIGHEST) + p["b_down"]


def final_hidden(x, lnf_g, lnf_b, eps: float):
    return layer_norm(x, lnf_g, lnf_b, eps)


def lm_loss(hidden, wte, targets):
    """Mean cross-entropy of the tied head's logits against ``targets``."""
    logits = jnp.einsum("bsd,vd->bsv", hidden, wte, precision=HIGHEST)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -picked.mean()


def forward(params: Dict[str, Any], tokens, eps: float):
    """Final hidden state ``[B, S, d]`` (after the last LayerNorm)."""
    x = embed(tokens, params["wte"], params["wpe"])

    def body(x, layer):
        return jax.checkpoint(functools.partial(block, eps=eps))(x, layer), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    return final_hidden(x, params["lnf_g"], params["lnf_b"], eps)


def loss(params: Dict[str, Any], tokens, targets, eps: float):
    return lm_loss(forward(params, tokens, eps), params["wte"], targets)


def loss_and_grads(params: Dict[str, Any], tokens, targets, eps: float):
    """``(loss, d loss / d params)``, one jitted call."""
    return jax.jit(jax.value_and_grad(loss), static_argnums=3)(
        params, tokens, targets, eps)
