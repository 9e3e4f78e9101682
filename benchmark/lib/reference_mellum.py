"""Mellum 2 (JetBrains, model type ``mellum``) in plain ``jax.numpy`` and
float32: forward, next-token loss and its gradients, for one chip's share of
the routed experts and of the vocabulary. No kernel, no scan, no sorted buffer,
no grouped product, no sharding, nothing imported from the program. Every
matrix multiplication runs at ``highest`` precision (on a TPU a float32 matmul
is otherwise done in bf16 passes).

The model, as the configuration file states it (widths from the published
``config.json``; what that file does not settle is under the configuration's
``assumed``):

- ``x = E[tokens]``: no multiplier, no position table;
- layer ``l`` of kind ``layer_types[l]``, ``H`` query heads over ``G``
  key/value heads of ``d`` in BOTH kinds: ``h = RMSNorm_1(x)``; ``q = h W_q``
  as ``[S, H, d]``, ``k = h W_k``, ``v = h W_v`` as ``[S, G, d]``, no biases,
  no q/k norm; rotary on q and k by the kind's scheme (``rope_parameters``)
  over the WHOLE head, dimension ``i`` pairing with ``i + d / 2``, ``x cos +
  rotate_half(x) sin`` with explicit tables; a ``yarn`` scheme's tables are
  HF's ``_compute_yarn_parameters`` (inverse frequencies blended between
  interpolation and extrapolation by a linear ramp over the correction range,
  cosine and sine times the stated ``attention_factor``); query head ``j``
  reads key/value head ``j // (H / G)``; scores ``q k^T / sqrt(d)`` over the
  whole ``[S, S]`` matrix of a head, masked to ``j <= i`` and, in
  ``sliding_attention`` layers, ``i - j < sliding_window`` (query ``i`` sees
  keys ``(i - 1024, i]``); softmax; ``o = P v``; ``x <- x + concat(o) W_o``;
- ``m = RMSNorm_2(x)``; every layer is ``sparse``: router logits ``m W_r`` over
  ALL experts; ``p = softmax(logits)``; the ``k`` largest by ``jnp.argsort``;
  weights ``p_e / sum of the chosen p`` (``norm_topk_prob``), no scaling
  factor; ``x <- x + sum over the chosen e in [lo, hi) of w_e E_e(m)``, every
  expert a SwiGLU ``(silu(m W_gate) * (m W_up)) W_down``; NO shared expert;
  chosen experts outside ``[lo, hi)`` — the share's range — add nothing;
- ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * g``; after the last layer the
  final norm, ``logits = h W_head`` (untied) over the vocabulary held, mean
  next-token cross entropy, no auxiliary loss.

Departures: none in the arithmetic. The heads of a layer are taken one at a
time (``jax.lax.map``, each under ``jax.checkpoint``) so that one ``[S, S]``
score matrix exists at a time, and each layer is under ``jax.checkpoint``:
both bound memory and change no arithmetic. A product with a weight takes
the sequence 128 positions at a time (:func:`product`, ``jax.lax.map`` again;
a position's result does not depend on the others'): the TPU's compiler
builds the whole-sequence product in seconds and the loop's in a fraction.
:class:`Pieces` evaluates the same functions piece by piece, each piece jitted
on its own, because a whole layer with its loop over 16 experts takes the
TPU's compiler minutes. An expert is applied to every token and its result
weighted by zero where the token did not choose it. ``moe(..., chosen=)``
takes the chosen sets from outside (routing is discrete: the check hands the
program's sets over so that one near-tie does not swamp a comparison of
states); the weights are then still from the reference's own probabilities.

Parameters are a plain dict: ``wte [V, D]``, ``head [D, V]``, ``lnf_g [D]``
and ``layers``, a list with one dict a layer: ``n1, n2 [D]``, ``wq [D, H,
d]``, ``wk, wv [D, G, d]``, ``wo [H, d, D]``, ``router [D, E]``, ``e_gate,
e_up [hi - lo, D, f]``, ``e_down [hi - lo, f, D]``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: a product over a sequence takes this many of its positions at a time
ROWS = 128


def product(spec: str, x, w):
    """``jnp.einsum(spec, x, w)`` at ``highest`` precision for ``x [B, S,
    ...]`` and a result ``[B, S, ...]`` whose positions do not meet (every
    product with a weight is one), ``ROWS`` positions at a time."""
    batch, seq = x.shape[:2]
    if seq <= ROWS or seq % ROWS:
        return jnp.einsum(spec, x, w, precision=HIGHEST)
    blocks = jnp.moveaxis(
        x.reshape(batch, seq // ROWS, ROWS, *x.shape[2:]), 1, 0)
    out = jax.lax.map(
        lambda rows: jnp.einsum(spec, rows, w, precision=HIGHEST), blocks)
    return jnp.moveaxis(out, 0, 1).reshape(batch, seq, *out.shape[3:])


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


# --------------------------------------------------------------- rotary
def yarn_inv_freq(rot: int, p: Dict[str, Any]):
    """HF's ``_compute_yarn_parameters`` over ``rot`` rotated dimensions."""
    base, factor = float(p["rope_theta"]), float(p["factor"])
    original = p["original_max_position_embeddings"]

    def correction_dim(rotations):
        return rot * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(p["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(p["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    return interpolation * (1 - extrapolation_factor) \
        + extrapolation * extrapolation_factor


def rope_tables(seq: int, head_dim: int, p: Dict[str, Any]):
    """``(cos, sin, rot)``: ``[seq, rot]`` float32 tables of one entry of
    ``rope_parameters`` and the number of rotated dimensions."""
    rot = head_dim  # no partial_rotary_factor: the whole head
    if p.get("rope_type", "default") == "yarn":
        inv_freq = yarn_inv_freq(rot, p)
        gain = p.get("attention_factor") or 0.1 * math.log(p["factor"]) + 1.0
    else:
        i = jnp.arange(0, rot, 2, dtype=jnp.float32)
        inv_freq, gain = 1.0 / (float(p["rope_theta"]) ** (i / rot)), 1.0
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles) * gain, jnp.sin(angles) * gain, rot


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, cos, sin, rot):
    """``x [B, S, H, d]``: the first ``rot`` dimensions rotated."""
    turned, passed = x[..., :rot], x[..., rot:]
    turned = turned * cos[None, :, None, :] \
        + rotate_half(turned) * sin[None, :, None, :]
    return jnp.concatenate([turned, passed], axis=-1)


# ------------------------------------------------------------ attention
def band_mask(s_q: int, s_k: int, window: Optional[int]):
    """``[s_q, s_k]`` bool, written out: query ``i`` (the last ``s_q`` of
    ``s_k`` positions) sees key ``j`` iff ``j <= i`` and, under a window,
    ``i - j < window``."""
    i = jnp.arange(s_q)[:, None] + (s_k - s_q)
    j = jnp.arange(s_k)[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (i - j < window)
    return mask


def attention_core(q, k, v, window: Optional[int]):
    """``q [B, S, H, d]``, ``k, v [B, S, G, d]`` -> ``[B, S, H, d]``, one
    query head's whole score matrix at a time."""
    heads, groups = q.shape[2], k.shape[2]
    mask = band_mask(q.shape[1], k.shape[1], window)
    scale = 1.0 / math.sqrt(q.shape[-1])

    def one_head(args):
        qh, kh, vh = args  # [B, S, d]
        scores = jnp.einsum("bqd,btd->bqt", qh, kh, precision=HIGHEST) * scale
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqt,btd->bqd", probs, vh, precision=HIGHEST)

    # under checkpoint: a head's probabilities are formed again in the
    # backward pass, not kept for all heads at once
    reads = jnp.arange(heads) // (heads // groups)
    out = jax.lax.map(jax.checkpoint(one_head), (jnp.moveaxis(q, 2, 0),
                                 jnp.moveaxis(k, 2, 0)[reads],
                                 jnp.moveaxis(v, 2, 0)[reads]))
    return jnp.moveaxis(out, 0, 2)


def attention(h, p, kind: str, hp):
    q = product("bsd,dhk->bshk", h, p["wq"])
    k = product("bsd,dhk->bshk", h, p["wk"])
    v = product("bsd,dhk->bshk", h, p["wv"])
    cos, sin, rot = rope_tables(h.shape[1], q.shape[-1], hp["rope"][kind])
    q, k = rope(q, cos, sin, rot), rope(k, cos, sin, rot)
    window = hp["window"] if kind == "sliding_attention" else None
    out = attention_core(q, k, v, window)
    return product("bqhk,hkd->bqd", out, p["wo"])


# ------------------------------------------------------------------ FFNs
def swiglu(m, w_gate, w_up, w_down):
    gate = product("bsd,df->bsf", m, w_gate)
    up = product("bsd,df->bsf", m, w_up)
    return product("bsf,fd->bsd", jax.nn.silu(gate) * up, w_down)


def router(m, w_router, k: int):
    """``(logits, chosen [.., k])``: float32 logits over all experts and
    the experts of the ``k`` largest softmax probabilities, by
    ``jnp.argsort``."""
    logits = product("bsd,de->bse", m, w_router)
    chosen = jnp.argsort(-jax.nn.softmax(logits, axis=-1), axis=-1)[..., :k]
    return logits, chosen


def expert_part(m, w_router, w_gate, w_up, w_down, chosen, e, hp):
    """What routed expert ``e`` adds on ``m [B, S, D]``: its SwiGLU on every
    token, weighted by the token's router weight for it — its softmax
    probability (over ALL experts) over the chosen probabilities' sum — or by
    zero where ``e`` is not among the token's ``chosen``."""
    logits = product("bsd,de->bse", m, w_router)
    probs = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), chosen,
                                axis=-1)
    weights = probs / jnp.sum(probs, -1, keepdims=True)
    w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
    return w_e[..., None] * swiglu(m, w_gate, w_up, w_down)


def moe(m, p, hp, chosen=None):
    """The share's part of a sparse layer on ``m [B, S, D]``: ``(y, logits,
    chosen)``; ``chosen`` from outside replaces the reference's own sets."""
    lo, hi = hp["experts_held"]
    logits, own = router(m, p["router"], hp["k"])
    chosen = own if chosen is None else chosen
    y = jnp.zeros_like(m)  # nothing shared
    for e in range(lo, hi):  # absent experts add nothing
        y = y + expert_part(m, p["router"], p["e_gate"][e - lo],
                            p["e_up"][e - lo], p["e_down"][e - lo], chosen, e,
                            hp)
    return y, logits, own


def attention_residual(x, p, kind: str, hp):
    return x + attention(rms_norm(x, p["n1"], hp["eps"]), p, kind, hp)


def layer(x, p: Dict[str, Any], kind: str, hp, chosen=None):
    """One layer on ``x [B, S, D]``: ``(x, router logits, own chosen
    sets)``."""
    x = attention_residual(x, p, kind, hp)
    m = rms_norm(x, p["n2"], hp["eps"])
    y, logits, own = moe(m, p, hp, chosen)
    return x + y, logits, own


def states(params, tokens, hp, chosen: Optional[List[Any]] = None
           ) -> List[Any]:
    """Every layer's output state, ``[x_1 .. x_L]``."""
    x, out = params["wte"][tokens], []
    for i, (p, kind) in enumerate(zip(params["layers"], hp["layer_types"])):
        x = jax.checkpoint(functools.partial(layer, kind=kind, hp=hp))(
            x, p, chosen=None if chosen is None else chosen[i])[0]
        out.append(x)
    return out


def cross_entropy(x, params, targets, hp):
    """Mean next-token cross entropy from the last layer's state."""
    h = rms_norm(x, params["lnf_g"], hp["eps"])
    logits = product("bsd,dv->bsv", h, params["head"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                         axis=-1)[..., 0])


def loss(params, tokens, targets, hp):
    with jax.default_matmul_precision("highest"):
        return cross_entropy(states(params, tokens, hp)[-1], params, targets,
                             hp)


def hyper(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file's scalars the functions above read as ``hp``.
    Callers close over it; it is never an argument of a jitted function."""
    held = config["kwargs"].get("experts_held") or (0, config["router_width"])
    if not config["norm_topk_prob"]:
        raise ValueError("the reference renormalises the chosen "
                         "probabilities: norm_topk_prob must be true")
    return {"eps": float(config["rms_norm_eps"]),
            "window": int(config["sliding_window"]),
            "rope": config["rope_parameters"],
            "k": int(config["num_experts_per_tok"]),
            "experts_held": (int(held[0]), int(held[1])),
            "layer_types": tuple(config["layer_types"])}


def loss_and_grads(params, tokens, targets, hp):
    """``(loss, d loss / d params)``, ``jax.grad`` of the whole loss in one
    jitted call."""
    def f(params, tokens, targets):
        return loss(params, tokens, targets, hp)

    return jax.jit(jax.value_and_grad(f))(params, tokens, targets)


#: the leaves of a layer's dict that each piece reads
ATTENTION = ("n1", "wq", "wk", "wv", "wo")


@jax.jit
def _scaled(ct, scale):
    return jax.tree.map(lambda c: c * scale, ct)


@jax.jit
def _add_scaled(into, ct, scale):
    return jax.tree.map(lambda t, c: t + c * scale, into, ct)


def _leaves(p: Dict[str, Any], names) -> Dict[str, Any]:
    return {name: p[name] for name in names}


class Pieces:
    """The same model evaluated piece by piece: each piece a small jitted
    function (the attention sub-layer by kind, the norm, the router, ONE
    routed expert with its index an argument, the head's loss), the loops
    over layers and experts in Python, and the gradient's chain rule written
    out over the pieces. At the published widths a whole layer with its loop
    over 16 experts is an executable that takes minutes to build, in every
    run; a piece is built once and cached. The arithmetic is :func:`layer`'s;
    ``tests/test_reference_mellum.py`` holds :meth:`loss_and_grads` to
    :func:`loss_and_grads`."""

    def __init__(self, hp):
        eps = hp["eps"]
        self.hp = hp
        self.lo = hp["experts_held"][0]
        self.attn = jax.jit(lambda x, p, kind: attention_residual(
            x, p, kind, hp), static_argnums=2)
        self.norm = jax.jit(lambda x, g: rms_norm(x, g, eps))
        self.route = jax.jit(lambda m, w: router(m, w, hp["k"]))
        self.part = jax.jit(lambda m, w, chosen, e: expert_part(
            m, w["router"], w["gate"], w["up"], w["down"], chosen, e, hp))
        self.head = jax.jit(lambda x, ends, targets: cross_entropy(
            x, ends, targets, hp))

        def pull(f):  # the piece's vjp, recomputing its forward
            return jax.jit(lambda ct, *args: jax.vjp(f, *args)[1](ct))

        self.attn_vjp = jax.jit(
            lambda ct, x, p, kind: jax.vjp(
                lambda x, p: attention_residual(x, p, kind, hp), x, p)[1](ct),
            static_argnums=3)
        self.norm_vjp = pull(lambda x, g: rms_norm(x, g, eps))
        self.part_vjp = jax.jit(lambda ct, m, w, chosen, e: jax.vjp(
            lambda m, w: expert_part(m, w["router"], w["gate"], w["up"],
                                     w["down"], chosen, e, hp), m, w)[1](ct))
        self.head_grad = jax.jit(jax.value_and_grad(
            lambda x, ends, targets: cross_entropy(x, ends, targets, hp),
            argnums=(0, 1)))

    def _expert(self, p, e):
        i = e - self.lo
        return {"router": p["router"], "gate": p["e_gate"][i],
                "up": p["e_up"][i], "down": p["e_down"][i]}

    def layer(self, x, p, kind, chosen=None):
        """:func:`layer`, piece by piece: ``(x, logits, own chosen sets, the
        state after attention)``. A piece is handed the leaves it reads and
        no others, so that layers of one attention kind share its pieces."""
        x = mid = self.attn(x, _leaves(p, ATTENTION), kind)
        m = self.norm(x, p["n2"])
        logits, own = self.route(m, p["router"])
        chosen = own if chosen is None else chosen
        for e in range(*self.hp["experts_held"]):
            x = x + self.part(m, self._expert(p, e), chosen, e)
        return x, logits, own, mid

    def layer_vjp(self, ct, x, mid, p, kind):
        """``(d x, d p)`` of :meth:`layer` (routing for itself) from ``ct``,
        the cotangent of its output; ``mid`` is the state after attention."""
        m = self.norm(mid, p["n2"])
        chosen = self.route(m, p["router"])[1]
        ct_m = jnp.zeros_like(m)
        ct_p = dict(router=jnp.zeros_like(p["router"]), e_gate=[], e_up=[],
                    e_down=[])
        for e in range(*self.hp["experts_held"]):
            d_m, d_w = self.part_vjp(ct, m, self._expert(p, e), chosen, e)
            ct_m = ct_m + d_m
            ct_p["router"] = ct_p["router"] + d_w["router"]
            for name in ("gate", "up", "down"):
                ct_p[f"e_{name}"].append(d_w[name])
        for name in ("e_gate", "e_up", "e_down"):
            ct_p[name] = jnp.stack(ct_p[name])
        d_mid, ct_p["n2"] = self.norm_vjp(ct_m, mid, p["n2"])
        ct_x, ct_attn = self.attn_vjp(ct + d_mid, x, _leaves(p, ATTENTION),
                                      kind)
        return ct_x, {**ct_attn, **ct_p}

    def loss_and_grads(self, params, tokens, targets, by_row: bool = False):
        """``(loss, d loss / d params)`` as :func:`loss_and_grads` gives
        them, assembled over the pieces. ``by_row``: one sequence at a time
        (the loss is the rows' mean), each layer's gradient added into the
        total as soon as it is formed, so that one whole gradient and one
        layer's exist at a time."""
        rows = len(tokens) if by_row else 1
        value, total = 0.0, None
        for i in range(rows):
            cut = slice(i, i + 1) if by_row else slice(None)
            v, total = self._add_grads(params, tokens[cut], targets[cut],
                                       1.0 / rows, total)
            value = value + v / rows
        return value, total

    def _add_grads(self, params, tokens, targets, scale, total):
        kinds = self.hp["layer_types"]
        ends = {"lnf_g": params["lnf_g"], "head": params["head"]}
        xs, mids = [params["wte"][tokens]], []
        for p, kind in zip(params["layers"], kinds):
            x, _, _, mid = self.layer(xs[-1], p, kind)
            xs.append(x)
            mids.append(mid)
        value, (ct_x, ct_ends) = self.head_grad(xs[-1], ends, targets)
        if total is None:
            total = dict(jax.tree.map(jnp.zeros_like, ends),
                         wte=jnp.zeros_like(params["wte"]),
                         layers=[None] * len(kinds))

        def add(into, ct):
            return _scaled(ct, scale) if into is None \
                else _add_scaled(into, ct, scale)

        for name in ends:
            total[name] = add(total[name], ct_ends[name])
        for l in reversed(range(len(kinds))):
            ct_x, ct_p = self.layer_vjp(ct_x, xs[l], mids[l],
                                        params["layers"][l], kinds[l])
            total["layers"][l] = add(total["layers"][l], ct_p)
            del ct_p
        total["wte"] = total["wte"].at[tokens].add(ct_x * scale)
        return value, total
