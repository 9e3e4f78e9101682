"""SDAR (JetLM, model type ``sdar_moe``) TRAINED BY BLOCK DIFFUSION in plain
``jax.numpy`` and float32: the forward over ``[noised || clean]`` rows, the
masked tokens' loss weighted ``1 / t`` and its gradients, for one chip's share
of the routed experts and of the vocabulary. No kernel, no scan, no sorted
buffer, no grouped product, no sharding, nothing imported from the program;
every matrix multiplication at ``highest`` precision. The expert layer is
Mellum 2's form and its functions are ``reference_mellum``'s (this file
imports that reference, never the program).

What is computed, as the configuration file states it (widths from the
published ``config.json``; what that file does not settle is under the
configuration's ``assumed``). Per sequence ``x0`` of ``L`` tokens, block
length ``B``, ``blk(i) = i // B``, and a DRAW handed in from outside (the
program's own: ``masked [L]`` bool, ``t [L]``, a block's time at each of its
tokens — the noise's distribution is tested apart):

- ``xt_i = MASK if masked_i else x0_i``; the stack sees ``[xt || x0]``, ``2 L``
  rows, with positions ``[0 .. L-1, 0 .. L-1]``; ``x = E[rows]``;
- the mask ``M [2L, 2L]``, WRITTEN OUT from four rules (:func:`block_mask`),
  with ``bq``, ``bk`` the blocks of the query's and the key's positions:
  noised q, noised k: ``bk == bq``; noised q, clean k: ``bk < bq``; clean q,
  clean k: ``bk <= bq``; clean q, noised k: never;
- each of the identical layers: ``h = RMSNorm_1(x)``; ``q = h W_q`` as ``[2L,
  H, d]``, ``k = h W_k``, ``v = h W_v`` as ``[2L, G, d]``, no biases; an RMSNorm
  over each head's ``d`` dimensions on q and on k, one learned gain of ``d``
  each; rotary over the whole head, rotate-half, explicit tables over the
  repeated positions; query head ``j`` reads key/value head ``j // (H / G)``;
  scores ``q k^T / sqrt(d)`` over the whole ``[2L, 2L]`` matrix of a head under
  ``M``, softmax, ``o = P v``; ``x <- x + concat(o) W_o``; ``m = RMSNorm_2(x)``;
  router logits ``m W_r`` over ALL experts, softmax, the ``k`` largest,
  weights ``p_e / sum of the chosen p``; ``x <- x + sum over the chosen e in
  [lo, hi) of w_e SwiGLU_e(m)``; nothing shared;
- the final norm and ``logits = h W_head`` on the NOISED half alone; ``loss =
  (1 / L) sum_i masked_i (1 / t_i) (-log softmax(logits_i)[x0_i])``, the mean
  over the batch's sequences — no shift: a masked position predicts itself.
  The clean half's last-layer states feed nothing and are computed.

Departures: none in the arithmetic. A head's score matrix is taken ``rows``
query rows at a time (``jax.lax.map`` over heads and over blocks of query
rows, each block under ``jax.checkpoint``: a block's whole ``[rows, 2L]``
scores exist at a time, 134 MB at 2,048 x 16,384), a product with a weight
128 positions at a time, and :class:`Pieces` evaluates the same functions
piece by piece, as ``reference_mellum`` does and for its reasons.

Parameters are a plain dict: ``wte [V, D]``, ``head [D, V]``, ``lnf_g [D]``
and ``layers``, a list with one dict a layer: ``n1, n2 [D]``, ``wq [D, H,
d]``, ``wk, wv [D, G, d]``, ``qn, kn [d]``, ``wo [H, d, D]``, ``router [D,
E]``, ``e_gate, e_up [hi - lo, D, f]``, ``e_down [hi - lo, f, D]``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from .reference_mellum import (HIGHEST, _add_scaled, _leaves, _scaled,
                               expert_part, moe, product, rms_norm, rope,
                               router)

#: query rows of a head's score matrix that exist at a time
ROWS = 2048


def block_mask(seq: int, block: int):
    """``[2 seq, 2 seq]`` bool (query, key), written out from the four
    rules: rows ``[0, seq)`` are noised, ``[seq, 2 seq)`` clean."""
    row = jnp.arange(2 * seq)
    clean, blk = row >= seq, (row % seq) // block
    bq, bk = blk[:, None], blk[None, :]
    q_noised, q_clean = ~clean[:, None], clean[:, None]
    k_noised, k_clean = ~clean[None, :], clean[None, :]
    return ((q_noised & k_noised & (bk == bq))
            | (q_noised & k_clean & (bk < bq))
            | (q_clean & k_clean & (bk <= bq)))


def rope_tables(seq: int, head_dim: int, theta: float):
    """``(cos, sin)``, ``[2 seq, head_dim]`` float32, of the positions ``[0
    .. seq-1, 0 .. seq-1]``: a noised token and its clean twin are rotated
    alike."""
    i = jnp.arange(0, head_dim, 2, dtype=jnp.float32)
    inv_freq = 1.0 / (float(theta) ** (i / head_dim))
    positions = jnp.concatenate([jnp.arange(seq, dtype=jnp.float32)] * 2)
    angles = positions[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def attention_core(q, k, v, mask, rows: int = ROWS):
    """``q [B, S, H, d]``, ``k, v [B, S, G, d]``, ``mask [S, S]`` -> ``[B, S,
    H, d]``: one query head's whole score rows, ``rows`` queries at a
    time."""
    batch, s, heads, d = q.shape
    groups = k.shape[2]
    rows = min(rows, s)
    scale = 1.0 / math.sqrt(d)
    reads = jnp.arange(heads) // (heads // groups)
    by_block = mask.reshape(s // rows, rows, s)

    def one_head(args):
        qh, kh, vh = args  # [B, S, d]

        def one_block(block):
            qb, seen = block  # [B, rows, d], [rows, S]
            scores = jnp.einsum("bqd,btd->bqt", qb, kh,
                                precision=HIGHEST) * scale
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("bqt,btd->bqd", probs, vh, precision=HIGHEST)

        out = jax.lax.map(jax.checkpoint(one_block), (
            jnp.moveaxis(qh.reshape(batch, s // rows, rows, d), 1, 0),
            by_block))
        return jnp.moveaxis(out, 0, 1).reshape(batch, s, d)

    out = jax.lax.map(one_head, (jnp.moveaxis(q, 2, 0),
                                 jnp.moveaxis(k, 2, 0)[reads],
                                 jnp.moveaxis(v, 2, 0)[reads]))
    return jnp.moveaxis(out, 0, 2)


def normed_rotated(h, p, hp):
    """``(q, k, v)`` as the score product takes them: the projections, the
    per-head RMSNorm with its gains on q and k, then rotary."""
    q = product("bsd,dhk->bshk", h, p["wq"])
    k = product("bsd,dhk->bshk", h, p["wk"])
    v = product("bsd,dhk->bshk", h, p["wv"])
    q = rms_norm(q, p["qn"], hp["eps"])
    k = rms_norm(k, p["kn"], hp["eps"])
    d = q.shape[-1]
    cos, sin = rope_tables(h.shape[1] // 2, d, hp["theta"])
    return rope(q, cos, sin, d), rope(k, cos, sin, d), v


def attention(h, p, hp):
    q, k, v = normed_rotated(h, p, hp)
    out = attention_core(q, k, v, block_mask(h.shape[1] // 2, hp["block"]),
                         hp["rows"])
    return product("bqhk,hkd->bqd", out, p["wo"])


def attention_residual(x, p, hp):
    return x + attention(rms_norm(x, p["n1"], hp["eps"]), p, hp)


def layer(x, p: Dict[str, Any], hp, chosen=None):
    """One layer on ``x [B, 2L, D]``: ``(x, router logits, own chosen
    sets)``."""
    x = attention_residual(x, p, hp)
    m = rms_norm(x, p["n2"], hp["eps"])
    y, logits, own = moe(m, p, hp, chosen)
    return x + y, logits, own


def rows_of(x0, masked, mask_id: int):
    """``[xt || x0]``: the noised tokens, then the clean ones."""
    return jnp.concatenate([jnp.where(masked, mask_id, x0), x0], axis=1)


def states(params, tokens, hp, chosen: Optional[List[Any]] = None
           ) -> List[Any]:
    """Every layer's output state on ``tokens [B, 2L]``, ``[x_1 .. x_n]``."""
    x, out = params["wte"][tokens], []
    for i, p in enumerate(params["layers"]):
        x = jax.checkpoint(functools.partial(layer, hp=hp))(
            x, p, chosen=None if chosen is None else chosen[i])[0]
        out.append(x)
    return out


def diffusion_loss(x, params, x0, weights, hp):
    """The loss from the last layer's state ``x [B, 2L, D]``: the head on the
    NOISED half, each position's cross entropy to its own clean token times
    ``weights`` (``masked / t``), over all ``B L`` positions."""
    seq = x0.shape[1]
    h = rms_norm(x[:, :seq], params["lnf_g"], hp["eps"])
    logits = product("bsd,dv->bsv", h, params["head"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, x0[..., None], axis=-1)[..., 0]
    return jnp.sum(weights * nll) / x0.size


def loss(params, x0, masked, t, hp):
    """The whole loss under the draw ``masked``, ``t``."""
    with jax.default_matmul_precision("highest"):
        tokens = rows_of(x0, masked, hp["mask_id"])
        return diffusion_loss(states(params, tokens, hp)[-1], params, x0,
                              masked.astype(jnp.float32) / t, hp)


def hyper(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file's scalars the functions above read as ``hp``.
    Callers close over it; it is never an argument of a jitted function."""
    kwargs = config["kwargs"]
    held = kwargs.get("experts_held") or (0, config["router_width"])
    if not config["norm_topk_prob"]:
        raise ValueError("the reference renormalises the chosen "
                         "probabilities: norm_topk_prob must be true")
    return {"eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "k": int(config["num_experts_per_tok"]),
            "experts_held": (int(held[0]), int(held[1])),
            "block": int(config["block_length"]),
            "mask_id": int(config["vocab_size"]) - 1,
            "rows": ROWS}


def loss_and_grads(params, x0, masked, t, hp):
    """``(loss, d loss / d params)``, ``jax.grad`` of the whole loss in one
    jitted call."""
    def f(params, x0, masked, t):
        return loss(params, x0, masked, t, hp)

    return jax.jit(jax.value_and_grad(f))(params, x0, masked, t)


#: the leaves of a layer's dict that the attention piece reads
ATTENTION = ("n1", "wq", "wk", "wv", "qn", "kn", "wo")


class Pieces:
    """The same model evaluated piece by piece, as ``reference_mellum.Pieces``
    and for its reason (a whole layer with its loop over 16 experts is an
    executable that takes minutes to build, in every run): the attention
    sub-layer, the norm, the router, ONE routed expert with its index an
    argument, the noised half's loss; the loops over layers and experts in
    Python and the gradient's chain rule written out over the pieces.
    ``tests/test_reference_sdar.py`` holds :meth:`loss_and_grads` to
    :func:`loss_and_grads`."""

    def __init__(self, hp):
        eps = hp["eps"]
        self.hp = hp
        self.lo = hp["experts_held"][0]
        self.attn = jax.jit(lambda x, p: attention_residual(x, p, hp))
        self.norm = jax.jit(lambda x, g: rms_norm(x, g, eps))
        self.route = jax.jit(lambda m, w: router(m, w, hp["k"]))
        self.part = jax.jit(lambda m, w, chosen, e: expert_part(
            m, w["router"], w["gate"], w["up"], w["down"], chosen, e, hp))
        self.head = jax.jit(lambda x, ends, x0, weights: diffusion_loss(
            x, ends, x0, weights, hp))
        self.attn_vjp = jax.jit(lambda ct, x, p: jax.vjp(
            lambda x, p: attention_residual(x, p, hp), x, p)[1](ct))
        self.norm_vjp = jax.jit(lambda ct, x, g: jax.vjp(
            lambda x, g: rms_norm(x, g, eps), x, g)[1](ct))
        self.part_vjp = jax.jit(lambda ct, m, w, chosen, e: jax.vjp(
            lambda m, w: expert_part(m, w["router"], w["gate"], w["up"],
                                     w["down"], chosen, e, hp), m, w)[1](ct))
        self.head_grad = jax.jit(jax.value_and_grad(
            lambda x, ends, x0, weights: diffusion_loss(
                x, ends, x0, weights, hp), argnums=(0, 1)))

    def _expert(self, p, e):
        i = e - self.lo
        return {"router": p["router"], "gate": p["e_gate"][i],
                "up": p["e_up"][i], "down": p["e_down"][i]}

    def layer(self, x, p, chosen=None):
        """:func:`layer`, piece by piece: ``(x, logits, own chosen sets, the
        state after attention)``."""
        x = mid = self.attn(x, _leaves(p, ATTENTION))
        m = self.norm(x, p["n2"])
        logits, own = self.route(m, p["router"])
        chosen = own if chosen is None else chosen
        for e in range(*self.hp["experts_held"]):
            x = x + self.part(m, self._expert(p, e), chosen, e)
        return x, logits, own, mid

    def layer_vjp(self, ct, x, mid, p, chosen=None):
        """``(d x, d p)`` of :meth:`layer` from ``ct``, the cotangent of its
        output; ``mid`` is the state after attention; ``chosen`` as
        :meth:`layer` took it (None: the layer routes for itself)."""
        m = self.norm(mid, p["n2"])
        if chosen is None:
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
        ct_x, ct_attn = self.attn_vjp(ct + d_mid, x, _leaves(p, ATTENTION))
        return ct_x, {**ct_attn, **ct_p}

    def loss_and_grads(self, params, x0, masked, t, chosen=None):
        """``(loss, d loss / d params)`` as :func:`loss_and_grads` gives them,
        assembled over the pieces one sequence at a time (the loss is the
        sequences' mean), each layer's gradient added into the total as soon
        as it is formed. ``chosen``: None, or the chosen sets from outside,
        ``chosen[sequence][layer]`` — routing is discrete, and at seeded
        weights the masked rows' near-ties are ONE near-tie (every masked
        row is nearly the mask token's vector): a bf16 rounding flips it for
        all of them and the last layer's router gradient with it (0.05 to
        0.85 by the seed on the chip), so the check hands over the sets the
        program's gradient was made under, as it does for the states, and
        holds the selection itself apart."""
        n = len(x0)
        tokens = rows_of(x0, masked, self.hp["mask_id"])
        weights = masked.astype(jnp.float32) / t
        ends = {"lnf_g": params["lnf_g"], "head": params["head"]}
        total = dict(jax.tree.map(jnp.zeros_like, ends),
                     wte=jnp.zeros_like(params["wte"]),
                     layers=[None] * len(params["layers"]))
        value = 0.0
        for i in range(n):
            row = slice(i, i + 1)
            xs, mids = [params["wte"][tokens[row]]], []
            sets = [None] * len(params["layers"]) if chosen is None \
                else chosen[i]
            for p, taken in zip(params["layers"], sets):
                x, _, _, mid = self.layer(xs[-1], p, taken)
                xs.append(x)
                mids.append(mid)
            v, (ct_x, ct_ends) = self.head_grad(xs[-1], ends, x0[row],
                                                weights[row])
            value = value + v / n
            total.update(_add_scaled(_leaves(total, ends), ct_ends, 1.0 / n))
            for l in reversed(range(len(mids))):
                ct_x, ct_p = self.layer_vjp(ct_x, xs[l], mids[l],
                                            params["layers"][l], sets[l])
                total["layers"][l] = _scaled(ct_p, 1.0 / n) \
                    if total["layers"][l] is None \
                    else _add_scaled(total["layers"][l], ct_p, 1.0 / n)
                del ct_p
            total["wte"] = total["wte"].at[tokens[row]].add(ct_x / n)
        return value, total
