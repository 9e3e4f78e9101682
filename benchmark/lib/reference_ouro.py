"""Ouro (ByteDance Seed, model type ``ouro``; the LoopLM of arXiv:2510.25741)
in plain ``jax.numpy`` and float32: forward, the expected-exit loss and its
gradients. No kernel, no scan, no chunked head, no sharding, nothing
imported from the program. Every matrix multiplication runs at ``highest``
precision (on a TPU a float32 matmul is otherwise done in bf16 passes).

The model, as the configuration file states it (widths from the published
``config.json``; what that file does not carry is under the configuration's
``assumed``):

- ``x = E[tokens]``: no multiplier, no position table;
- for pass ``t = 1..T`` (``T = total_ut_steps``), for layer ``l = 1..L``,
  the SAME parameters in every pass:
  ``a = RMSNorm_1(x)``; ``q, k, v = a W_q, a W_k, a W_v`` (no biases, ``H``
  heads of ``d``); ``q, k <- RoPE(q, k)``: ``x cos + rotate_half(x) sin``
  with ``rotate_half(x) = [-x2, x1]`` over the two halves of a head, the
  angle of dimensions ``i`` and ``i + d/2`` ``pos * theta ** (-2 i / d)``,
  positions ``0..S-1``, explicit ``cos`` / ``sin`` tables;
  ``o = softmax(q k^T / sqrt(d) + causal) v`` over the whole score matrix;
  ``x <- x + RMSNorm_2(o W_o)``; ``m = RMSNorm_3(x)``;
  ``x <- x + RMSNorm_4((silu(m W_gate) * (m W_up)) W_down)``.
  ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * g``;
- after each pass ``h_t = RMSNorm_f(x)``; ``x <- h_t`` (the normed state
  enters the next pass); ``logits_t = h_t W_head`` (untied);
  ``lambda_t = sigmoid(h_t . w_g + b_g)``;
- a token's exit distribution: ``p_1 = lambda_1``, ``p_t = lambda_t
  prod_{j<t} (1 - lambda_j)`` for ``t < T``, ``p_T = prod_{j<T} (1 -
  lambda_j)``;
- ``loss = mean over tokens of sum_t p_t CE(logits_t, target) - beta H(p)``,
  ``H(p) = -sum_t p_t log max(p_t, 1e-30)``.

Departures: none in the arithmetic. Each layer application is under
``jax.checkpoint`` and the passes' cross-entropies are formed one pass at a
time: that bounds the gradient's memory and changes no arithmetic. Weights
are whatever the caller passes (the program's seeded initial values).

Parameters are a plain dict: ``wte [V, D]``, ``head [D, V]``, ``lnf_g [D]``,
``gate_w [D]``, ``gate_b [1]`` and ``layers``, a list with one dict a layer:
``n1, n2, n3, n4 [D]`` (the norms in the order above), ``wq, wk, wv [D, H,
d]``, ``wo [H, d, D]``, ``w_gate, w_up [D, F]``, ``w_down [F, D]``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope_tables(seq: int, head_dim: int, theta: float):
    """``cos, sin``: ``[seq, head_dim]`` float32."""
    i = jnp.arange(0, head_dim, 2, dtype=jnp.float32)
    inv_freq = 1.0 / (theta ** (i / head_dim))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, cos, sin):
    """``x [B, S, H, d]``."""
    return x * cos[None, :, None, :] + rotate_half(x) * sin[None, :, None, :]


def attention(a, p, hp):
    seq = a.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", a, p["wq"], precision=HIGHEST)
    k = jnp.einsum("bsd,dhk->bshk", a, p["wk"], precision=HIGHEST)
    v = jnp.einsum("bsd,dhk->bshk", a, p["wv"], precision=HIGHEST)
    cos, sin = rope_tables(seq, q.shape[-1], hp["rope_theta"])
    q, k = rope(q, cos, sin), rope(k, cos, sin)
    scores = jnp.einsum("bqhk,bthk->bhqt", q, k,
                        precision=HIGHEST) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqt,bthk->bqhk", probs, v, precision=HIGHEST)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"], precision=HIGHEST)


def swiglu(m, p):
    gate = jnp.einsum("bsd,df->bsf", m, p["w_gate"], precision=HIGHEST)
    up = jnp.einsum("bsd,df->bsf", m, p["w_up"], precision=HIGHEST)
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, p["w_down"],
                      precision=HIGHEST)


def layer(x, p: Dict[str, Any], hp):
    """One application of one layer on ``x: [B, S, D]``."""
    eps = hp["eps"]
    x = x + rms_norm(attention(rms_norm(x, p["n1"], eps), p, hp),
                     p["n2"], eps)
    return x + rms_norm(swiglu(rms_norm(x, p["n3"], eps), p), p["n4"], eps)


def one_pass(x, params, hp):
    """All layers once, then the final norm: ``h_t``. ``params``: anything
    that holds ``layers`` and ``lnf_g``."""
    for p in params["layers"]:
        x = jax.checkpoint(functools.partial(layer, hp=hp))(x, p)
    return rms_norm(x, params["lnf_g"], hp["eps"])


def gate_logit(h, params):
    """``params``: anything that holds ``gate_w`` and ``gate_b``."""
    return jnp.sum(h * params["gate_w"], axis=-1) + params["gate_b"][0]


def states(params, tokens, hp) -> Tuple[List[Any], List[Any]]:
    """``([h_1..h_T], [gate logit 1..T])``."""
    x = params["wte"][tokens]
    hidden, gates = [], []
    for _ in range(hp["total_ut_steps"]):
        x = one_pass(x, params, hp)
        hidden.append(x)
        gates.append(gate_logit(x, params))
    return hidden, gates


def exit_distribution(gates):
    """``p [T, B, S]`` from the passes' gate logits (the last is not
    read: what has not left, leaves there)."""
    stay = jnp.ones_like(gates[0])
    p = []
    for g in gates[:-1]:
        lam = jax.nn.sigmoid(g)
        p.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(p + [stay])


def cross_entropy(h, head, targets):
    """Per-token ``[B, S]`` from one pass's state."""
    logits = jnp.einsum("bsd,dv->bsv", h, head, precision=HIGHEST)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def objective(hidden, gates, head, targets, beta):
    """``(loss, per-pass mean cross-entropy [T], p [T, B, S])``."""
    p = exit_distribution(gates)
    ce = jnp.stack([jax.checkpoint(cross_entropy)(h, head, targets)
                    for h in hidden])
    entropy = -(p * jnp.log(jnp.maximum(p, 1e-30))).sum(0)
    loss = (p * ce).sum(0).mean() - beta * entropy.mean()
    return loss, ce.mean((1, 2)), p


def loss(params, tokens, targets, hp):
    with jax.default_matmul_precision("highest"):
        hidden, gates = states(params, tokens, hp)
        return objective(hidden, gates, params["head"], targets,
                         hp["beta"])[0]


def hyper(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file's scalars the functions above read as ``hp``.
    Callers close over it; it is never an argument of a jitted function."""
    return {"eps": float(config["rms_norm_eps"]),
            "rope_theta": float(config["rope_theta"]),
            "total_ut_steps": int(config["total_ut_steps"]),
            "beta": float(config["kwargs"].get("exit_entropy_weight", 0.05))}


def loss_and_grads(params, tokens, targets, hp):
    """``(loss, d loss / d params)``, ``jax.grad`` of the whole loss in one
    jitted call."""
    def f(params, tokens, targets):
        return loss(params, tokens, targets, hp)

    return jax.jit(jax.value_and_grad(f))(params, tokens, targets)


def loss_and_grads_by_pass(params, tokens, targets, hp):
    """The same ``(loss, d loss / d params)``, the chain rule written out
    over the passes: ONE compiled pass (all layers) run forward ``T`` times
    and its ``jax.vjp`` ``T`` times in reverse, each pass's state receiving
    the gradient of its own head and gate and of the pass it feeds, the
    shared parameters' gradients summed over the passes. At the published
    widths the whole loss's gradient is an executable of 246 MB, which no
    compile cache keeps and which takes two minutes to build in every run;
    a pass's is a quarter of it. ``tests/test_reference_ouro.py`` holds this
    to :func:`loss_and_grads`."""
    stack = {"layers": params["layers"], "lnf_g": params["lnf_g"]}
    ends = {k: params[k] for k in ("head", "gate_w", "gate_b")}

    def forward(x, stack):
        return one_pass(x, stack, hp)

    def backward(x, stack, ct):
        return jax.vjp(forward, x, stack)[1](ct)

    def end(hidden, ends, targets):
        gates = [gate_logit(h, ends) for h in hidden]
        return objective(hidden, gates, ends["head"], targets, hp["beta"])[0]

    with jax.default_matmul_precision("highest"):
        forward_, backward_ = jax.jit(forward), jax.jit(backward)
        xs = [params["wte"][tokens]]
        for _ in range(hp["total_ut_steps"]):
            xs.append(forward_(xs[-1], stack))
        value, (ct_hidden, ct_ends) = jax.jit(jax.value_and_grad(
            end, argnums=(0, 1)))(xs[1:], ends, targets)
        ct_stack, ct_x = None, jnp.zeros_like(xs[0])
        for t in reversed(range(hp["total_ut_steps"])):
            ct_x, ct = backward_(xs[t], stack, ct_hidden[t] + ct_x)
            ct_stack = ct if ct_stack is None else jax.tree.map(
                jnp.add, ct_stack, ct)
        ct_wte = jnp.zeros_like(params["wte"]).at[tokens].add(ct_x)
    return value, dict(ct_ends, wte=ct_wte, **ct_stack)
