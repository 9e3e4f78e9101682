"""Plain reference of Phi-4-mini-flash-reasoning (SambaY, arXiv:2507.06607,
with differential attention, arXiv:2410.05258): the equations of
``benchmark/configs/phi-4-mini-flash-reasoning.json`` in ``jax.numpy``,
float32 at the highest matmul precision, nothing from the program under test.

Stream ``x [S, D]`` (one sequence at a time); every layer is ``h = x +
Mixer(LN(x))``, ``y = h + MLP(LN'(h))``, LayerNorm with gain and bias, no
positions anywhere. The mixer by PUBLISHED index ``l`` of ``n`` layers:

- even ``l <= n/2``: Mamba-1 — ``[x ; z] = u W_in``; ``x <- silu(conv(x))``, 4
  causal taps a channel and a bias; ``[delta ; B ; C] = x W_x``; ``dt =
  softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``; the recurrence ``h_t =
  exp(dt_t (x) A) h_{t-1} + (dt_t x_t) (x) B_t``, ``y_t = h_t C_t + D x_t``, a
  ``lax.scan`` over positions; out ``(y silu(z)) W_out``. Layer ``n/2``'s
  ``y`` is the MEMORY handed on;
- odd ``l < n/2``: differential attention under a causal window; ``l = n/2 +
  1``: the same, whole, and its K, V are handed on; odd ``l`` behind it: its
  own queries against layer ``n/2 + 1``'s K and V. Pair ``j`` of group ``g =
  j // (heads / kv_heads)``: ``P_c = softmax(q_{2j+c} k_{2g+c}^T / sqrt(d))``
  under the mask, written out; ``V_g = [v_2g ; v_2g+1]``; ``o_j = (1 -
  lambda_init) RMSNorm(P_0 V_g - lambda P_1 V_g)``, ``lambda = exp(lq1.lk1) -
  exp(lq2.lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``;
- even ``l > n/2``: the gated memory unit ``(m silu(u W_1)) W_2``.

Rows go through the attention and the MLP in blocks, so that 16,384 of them
fit beside the weights; a layer is rematerialised in the backward.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

#: rows a block of the attention's score matrix and of the MLP
ROWS = 512


def _highest(fn):
    """``fn`` traced at the highest matmul precision."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return traced


def hyper(config: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers the equations need, from the configuration file."""
    return {
        "layer_ids": tuple(config["layer_ids"]),
        "n_layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "window": config["sliding_window"],
        "d_state": config["mamba_d_state"],
        "dt_rank": config["mamba_dt_rank"],
        "d_conv": config["mamba_d_conv"],
        "mb_per_layer": config["mb_per_layer"],
        "eps": config["layer_norm_eps"],
    }


def lambda_init(layer_id: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer_id)


def kind_of(layer_id: int, hp: Dict[str, Any]) -> str:
    """``mamba`` | ``window`` | ``full`` | ``gmu`` | ``cross``."""
    half = hp["n_layers"] // 2
    if layer_id % hp["mb_per_layer"] == 0:
        return "mamba" if layer_id <= half else "gmu"
    if layer_id < half:
        return "window"
    return "full" if layer_id == half + 1 else "cross"


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * g + b


def _by_rows(fn, x, rows: int = ROWS):
    """``fn`` over ``x [S, ...]`` in blocks of rows, each rematerialised."""
    s = x.shape[0]
    rows = min(rows, s)
    if s % rows:
        return fn(x)
    out = lax.map(jax.checkpoint(fn), x.reshape(s // rows, rows, *x.shape[1:]))
    return out.reshape(s, *out.shape[2:])


def mlp(u, p):
    def rows(u):
        gate, value = jnp.split(u @ p["w1"], 2, axis=-1)
        return (jax.nn.silu(gate) * value) @ p["w2"]
    return _by_rows(rows, u)


def conv_silu(x, w, b):
    """``silu(sum_k w[k] x[t - (K - 1) + k] + b)`` on ``x [S, C]``."""
    taps = w.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    pre = sum(w[k] * padded[k:k + x.shape[0]] for k in range(taps)) + b
    return jax.nn.silu(pre)


@_highest
def recurrence(x, dt, A, B, C, D, chunk: int = 128):
    """The selective scan position by position on ``x, dt [S, C]``, ``A [C,
    N]``, ``B, C [S, N]``, ``D [C]``: ``y [S, C]``. (Chunks only bound what
    the backward keeps.)"""
    def step(h, at):
        x_t, dt_t, b_t, c_t = at
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * x_t)[:, None] * b_t
        return h, h @ c_t

    s = x.shape[0]
    h0 = jnp.zeros(A.shape, jnp.float32)
    if s % chunk:
        return lax.scan(step, h0, (x, dt, B, C))[1] + D * x
    cut = lambda a: a.reshape(s // chunk, chunk, *a.shape[1:])  # noqa: E731
    _, y = lax.scan(jax.checkpoint(lambda h, at: lax.scan(step, h, at)), h0,
                    tuple(cut(a) for a in (x, dt, B, C)))
    return y.reshape(s, -1) + D * x


@_highest
def mamba_operands(u, p, hp):
    """``(x, z, dt, B, C)`` of the Mamba-1 mixer on its normed input."""
    x, z = jnp.split(u @ p["in_proj"], 2, axis=-1)
    x = conv_silu(x, p["conv_w"], p["conv_b"])
    low = x @ p["x_proj"]
    r, n = hp["dt_rank"], hp["d_state"]
    dt = jax.nn.softplus(low[:, :r] @ p["dt_proj"] + p["dt_bias"])
    return x, z, dt, low[:, r:r + n], low[:, r + n:]


def mamba(u, p, hp):
    """``(the mixer's output, the scan's output y)``."""
    x, z, dt, B, C = mamba_operands(u, p, hp)
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), B, C, p["D"])
    return (y * jax.nn.silu(z)) @ p["out_proj"], y


def gmu(u, p, memory):
    return (memory * jax.nn.silu(u @ p["gmu_in"])) @ p["gmu_out"]


def attention_probs(q, k, window: Optional[int], first_row):
    """``softmax`` of ``q k^T / sqrt(d)`` under the causal mask (and the
    window: query ``t`` sees keys ``t - window + 1 .. t``), written out;
    ``q [H, R, d]`` are rows ``first_row ..``, ``k [H, S, d]``."""
    scores = jnp.einsum("hrd,hsd->hrs", q, k) / math.sqrt(q.shape[-1])
    row = first_row + jnp.arange(q.shape[1])[:, None]
    col = jnp.arange(k.shape[1])[None, :]
    seen = col <= row
    if window:
        seen &= col > row - window
    return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)


@_highest
def diff_heads(q, k, v, lam, gain, layer_id: int, hp,
               window: Optional[int]) -> Tuple[Any, Any]:
    """Differential attention's heads on ``q [S, H, d]``, ``k, v [S, G, d]``:
    ``(P_0 V - lambda P_1 V, o)`` as ``[S, H / 2, 2 d]`` each, the second
    normed and scaled."""
    s, heads, d = q.shape
    groups = k.shape[1]
    per = heads // groups  # pairs a value group
    pairs = jnp.arange(heads // 2)
    # score head 2 j + c reads key head 2 (j // per) + c
    key_of = (2 * (pairs // per))[:, None] + jnp.arange(2)[None, :]
    keys = jnp.moveaxis(k[:, key_of.reshape(-1)], 1, 0)       # [H, S, d]
    values = jnp.moveaxis(
        v.reshape(s, groups // 2, 2 * d)[:, pairs // per], 1, 0)  # [H/2,S,2d]

    def rows(args):
        q_rows, first = args
        probs = attention_probs(jnp.moveaxis(q_rows, 1, 0), keys, window,
                                first).reshape(heads // 2, 2, -1, s)
        both = jnp.einsum("jcrs,jsv->jcrv", probs, values)
        return jnp.moveaxis(both[:, 0] - lam * both[:, 1], 0, 1)

    r = min(ROWS, s)
    if s % r:
        before = rows((q, 0))
    else:
        before = lax.map(jax.checkpoint(rows), (
            q.reshape(s // r, r, heads, d), jnp.arange(0, s, r))
        ).reshape(s, heads // 2, 2 * d)
    normed = before * lax.rsqrt(
        jnp.mean(before ** 2, -1, keepdims=True) + hp["eps"]) * gain
    return before, (1.0 - lambda_init(layer_id)) * normed


def lam_of(p, layer_id: int):
    return (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
            - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))
            + lambda_init(layer_id))


def attention(u, p, layer_id: int, hp, kv=None):
    """``(the layer's output, (K, V))`` of a differential attention layer;
    ``kv``: layer ``n/2 + 1``'s, for a cross layer (which has a query map
    alone)."""
    s = u.shape[0]
    heads, groups, d = hp["heads"], hp["kv_heads"], hp["head_dim"]
    if kv is None:
        qkv = u @ p["wqkv"] + p["bqkv"]
        q = qkv[:, :heads * d]
        k = qkv[:, heads * d:(heads + groups) * d].reshape(s, groups, d)
        v = qkv[:, (heads + groups) * d:].reshape(s, groups, d)
    else:
        q = u @ p["wq"] + p["bq"]
        k, v = kv
    window = hp["window"] if kind_of(layer_id, hp) == "window" else None
    _, o = diff_heads(q.reshape(s, heads, d), k, v, lam_of(p, layer_id),
                      p["subln"], layer_id, hp, window)
    return o.reshape(s, -1) @ p["wo"] + p["bo"], (k, v)


@_highest
def layer(x, p, layer_id: int, hp, handed: Dict[str, Any]):
    """One published layer on ``x [S, D]``: ``(x, what it hands on)``."""
    kind = kind_of(layer_id, hp)
    u = layer_norm(x, p["ln1_g"], p["ln1_b"], hp["eps"])
    given = {}
    if kind == "mamba":
        h, given["memory"] = mamba(u, p, hp)
    elif kind == "gmu":
        h = gmu(u, p, handed["memory"])
    else:
        h, kv = attention(u, p, layer_id, hp,
                          handed["kv"] if kind == "cross" else None)
        if kind == "full":
            given["kv"] = kv
    x = x + h
    x = x + mlp(layer_norm(x, p["ln2_g"], p["ln2_b"], hp["eps"]), p)
    return x, given


@_highest
def forward(params, tokens, hp):
    """Every layer's state and the final normed state of ONE sequence
    ``tokens [S]``: ``([x after each layer], normed final state)``."""
    x = params["wte"][tokens]
    handed, states = {}, []
    for p, layer_id in zip(params["layers"], hp["layer_ids"]):
        x, given = jax.checkpoint(
            functools.partial(layer, layer_id=layer_id, hp=hp))(x, p,
                                                                handed=handed)
        handed = {**handed, **given}
        states.append(x)
    return states, layer_norm(x, params["lnf_g"], params["lnf_b"], hp["eps"])


@_highest
def logits_of(final, params):
    return final @ params["wte"].T


@_highest
def cross_entropy(final, params, targets):
    """Mean next-token cross entropy of one sequence, rows in blocks."""
    def rows(args):
        h, t = args
        logp = jax.nn.log_softmax(logits_of(h, params), axis=-1)
        return -jnp.take_along_axis(logp, t[:, None], axis=-1)[:, 0]

    s = final.shape[0]
    r = min(ROWS, s)
    if s % r:
        return jnp.mean(rows((final, targets)))
    return jnp.mean(lax.map(jax.checkpoint(rows), (
        final.reshape(s // r, r, -1), targets.reshape(s // r, r))))


def loss(params, tokens, targets, hp):
    """Mean cross entropy over the sequences ``tokens, targets [n, S]``."""
    return jnp.mean(jnp.stack([
        cross_entropy(forward(params, row, hp)[1], params, t)
        for row, t in zip(tokens, targets)]))


def loss_and_grads(params, tokens, targets, hp):
    return jax.jit(jax.value_and_grad(
        functools.partial(loss, hp=hp)))(params, tokens, targets)
