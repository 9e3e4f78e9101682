"""Keye-VL-2.0-30B-A3B's language model in plain ``jax.numpy`` and float32:
the forward, the next-token loss PLUS the layers' index losses, and their
gradients, for one chip's share of the routed experts and of the vocabulary.
No kernel, no packed selection, no sorted buffer, no grouped product, no
sharding, nothing imported from the program; every matrix multiplication at
``highest`` precision. The expert layer is Mellum 2's form and its functions
are ``reference_mellum``'s (this file imports that reference, never the
program).

What is computed, as the configuration file states it (widths from the
published ``config.json``; what that file does not settle is under the
configuration's ``assumed``). Per sequence of ``L`` tokens, ``x = E[tokens]``,
and for each of the identical layers, with ``h = RMSNorm_1(x)`` (1e-6):

1. ``q = R(N_q(h W_q))`` as ``[L, 32, 128]``, ``k = R(N_k(h W_k))``, ``v = h
   W_v`` as ``[L, 4, 128]``, no biases; ``N`` an RMSNorm over each head's
   dimensions with one learned gain of 128; ``R`` rotate-half over the whole
   head at ``rope_theta`` — the multimodal rotary WRITTEN OUT
   (:func:`mrope_tables`: ``mrope_section`` [16, 24, 24], frequency ``i``
   takes the position stream of its section; with text alone the three
   streams are the token's index);
2. the index, on ``hbar = stop_gradient(h)``: ``a = R'(hbar W_iq)`` as ``[L,
   16, 64]``; ONE key a token ``b = R'(LayerNorm(hbar W_ik))`` (gain and bias,
   1e-6), 64 wide; ``w = (hbar W_iw) * 16^-1/2 * 64^-1/2`` as ``[L, 16]``;
   ``R'`` rotate-half over the whole 64, the same theta; ``I[t, s] = sum_j
   w[t, j] relu(a[t, j] . b[s])`` for ``s <= t``;
3. ``S_t``: the ``min(t + 1, topk)`` keys ``s <= t`` of largest ``I[t, s]``,
   ties to the lower ``s`` (a stable sort of ``-I``) — or, where the caller
   hands sets in (``selected``: the program's, written out), those;
4. ``o[t, h] = sum_{s in S_t} P[t, h, s] v[s, g(h)]``, ``P[t, h, .] =
   softmax_{s in S_t}(q[t, h] . k[s, g(h)] / sqrt 128)``, query head ``h``
   reading key/value head ``h // 8``; ``x <- x + concat(o) W_o``;
5. ``m = RMSNorm_2(x)``; router logits ``m W_r`` over ALL 128 experts, softmax,
   the 8 largest, weights ``p_e / sum of the chosen p``; ``x <- x + sum over
   the chosen e in [lo, hi) of w_e SwiGLU_e(m)``; nothing shared;
6. the objective: the final norm, ``logits = h W_head``, the mean next-token
   cross entropy, plus ``sum over layers of mean_t KL(p[t, .] || softmax_{s in
   S_t} I[t, s])`` with ``p[t, s] = stop_gradient((1 / 32) sum_h P[t, h, s])``.

Departures: none in the arithmetic. A layer's score rows — the index's and
every head's — are taken ``ROWS`` 512 query rows at a time (``jax.lax.map``
over blocks of query rows, a ``jax.lax.scan`` over the heads inside, each
under ``jax.checkpoint``): a block's ``[512, L]`` scores and its mask, written
out, exist at a time, 33.5 MB at 16,384 — never ``[L, L]``. A product with a
weight is taken 128 positions at a time, and :class:`Pieces` evaluates the
same functions piece by piece, as ``reference_mellum`` does and for its
reasons.

Parameters are a plain dict: ``wte [V, D]``, ``head [D, V]``, ``lnf_g [D]``
and ``layers``, a list with one dict a layer: ``n1, n2 [D]``, ``wq [D, H,
d]``, ``wk, wv [D, G, d]``, ``qn, kn [d]``, ``wo [H, d, D]``, ``iq [D, Hi,
di]``, ``ik [D, di]``, ``ik_g, ik_b [di]``, ``iw [D, Hi]``, ``router [D, E]``,
``e_gate, e_up [hi - lo, D, f]``, ``e_down [hi - lo, f, D]``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .reference_mellum import (HIGHEST, _add_scaled, _leaves, _scaled,
                               expert_part, moe, product, rms_norm, rope,
                               router)

#: query rows whose score rows exist at a time
ROWS = 512


def mrope_tables(seq: int, head_dim: int, theta: float, sections):
    """``(cos, sin)``, ``[seq, head_dim]`` float32, of the multimodal rotary
    with text alone, the three sections written out: of the ``head_dim / 2``
    frequencies the first ``sections[0]`` turn by the temporal position, the
    next ``sections[1]`` by the height's, the last by the width's — and a
    text token's three positions are its index."""
    i = jnp.arange(0, head_dim, 2, dtype=jnp.float32)
    inv_freq = 1.0 / (float(theta) ** (i / head_dim))
    assert sum(sections) == head_dim // 2, (sections, head_dim)
    token = jnp.arange(seq, dtype=jnp.float32)
    streams = (token, token, token)  # temporal, height, width
    angles, at = [], 0
    for stream, n in zip(streams, sections):
        angles.append(stream[:, None] * inv_freq[None, at:at + n])
        at += n
    angles = jnp.concatenate(angles, axis=-1)
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def rope_tables(seq: int, head_dim: int, theta: float):
    """The index's ``R'``: rotate-half over its whole head."""
    return mrope_tables(seq, head_dim, theta, (head_dim // 2, 0, 0))


def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain + bias


def normed_rotated(h, p, hp):
    """Step 1: ``(q, k, v)`` as the score product takes them."""
    q = product("bsd,dhk->bshk", h, p["wq"])
    k = product("bsd,dhk->bshk", h, p["wk"])
    v = product("bsd,dhk->bshk", h, p["wv"])
    q = rms_norm(q, p["qn"], hp["eps"])
    k = rms_norm(k, p["kn"], hp["eps"])
    d = q.shape[-1]
    cos, sin = mrope_tables(h.shape[1], d, hp["theta"], hp["sections"])
    return rope(q, cos, sin, d), rope(k, cos, sin, d), v


def index_inputs(h, p, hp):
    """Step 2's ``(a [B, L, Hi, di], b [B, L, di], w [B, L, Hi])`` from the
    layer's normed input, DETACHED."""
    hbar = jax.lax.stop_gradient(h)
    a = product("bsd,dhk->bshk", hbar, p["iq"])
    b = layer_norm(product("bsd,dk->bsk", hbar, p["ik"]), p["ik_g"],
                   p["ik_b"], hp["eps"])
    n_heads, dim = a.shape[-2:]
    w = product("bsd,dh->bsh", hbar, p["iw"]) * (n_heads ** -0.5
                                                 * dim ** -0.5)
    cos, sin = rope_tables(h.shape[1], dim, hp["theta"])
    return (rope(a, cos, sin, dim),
            rope(b[:, :, None, :], cos, sin, dim)[:, :, 0], w)


def index_scores(a_rows, b, w_rows):
    """``I [B, rows, L]`` of a block of queries against every key, a loop
    over the index's heads."""
    scores = jnp.zeros((*a_rows.shape[:2], b.shape[1]), jnp.float32)
    for j in range(a_rows.shape[2]):
        s = jnp.einsum("bqd,btd->bqt", a_rows[:, :, j], b, precision=HIGHEST)
        scores = scores + w_rows[:, :, j, None] * jnp.maximum(s, 0.0)
    return scores


def select(scores, first: int, topk: int):
    """Step 3 for the queries ``first ..`` of ``scores [B, rows, L]``: bool,
    the mask of these rows written out."""
    rows, seq = scores.shape[1:]
    causal = jnp.arange(seq)[None, :] <= (first + jnp.arange(rows))[:, None]
    order = jnp.argsort(jnp.where(causal[None], -(scores + 0.0), jnp.inf),
                        axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return causal[None] & (rank < topk)


def selected_pairs(seq: int, topk: int) -> int:
    """``sum_t min(t + 1, topk)``: the (query, key) pairs step 3 keeps."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def indexed_attention(q, k, v, a, b, w, hp, selected=None, compare=True):
    """Steps 3 and 4 and the layer's index loss: ``(o [B, L, H, d], sum_t
    KL_t summed over the batch, the (query, key) entries at which the own
    sets and the ones used differ — two for a key replaced)``. ``selected``:
    None — the reference's own sets — or ``[B, L, L]`` bool from outside,
    used in their place (``compare`` False: the own sets are then not made,
    and nothing is counted)."""
    batch, seq, heads, d = q.shape
    groups = k.shape[2]
    rows = min(hp["rows"], seq)
    scale = 1.0 / math.sqrt(d)
    reads = jnp.arange(heads) // (heads // groups)
    kh = jnp.moveaxis(k, 2, 0)[reads]  # [H, B, L, d]
    vh = jnp.moveaxis(v, 2, 0)[reads]

    def blocks(x):  # [B, L, ...] -> [L / rows, B, rows, ...]
        return jnp.moveaxis(
            x.reshape(batch, seq // rows, rows, *x.shape[2:]), 1, 0)

    def one_block(block):
        first, qb, ab, wb, given = block
        scores = index_scores(ab, b, wb)
        if selected is None or compare:
            own = select(scores, first, hp["topk"])
        chosen = own if selected is None else given
        differ = jnp.sum(own != chosen) \
            if selected is not None and compare else jnp.int32(0)

        def one_head(mean, head):
            qh, k_h, v_h = head  # [B, rows, d], [B, L, d]
            s = jnp.einsum("bqd,btd->bqt", qh, k_h, precision=HIGHEST) * scale
            probs = jax.nn.softmax(jnp.where(chosen, s, -jnp.inf), axis=-1)
            out = jnp.einsum("bqt,btd->bqd", probs, v_h, precision=HIGHEST)
            return mean + probs / heads, out

        mean, out = jax.lax.scan(
            jax.checkpoint(one_head), jnp.zeros_like(scores),
            (jnp.moveaxis(qb, 2, 0), kh, vh))
        p = jax.lax.stop_gradient(mean)
        log_i = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf),
                                   axis=-1)
        live = chosen & (p > 0)
        kl = jnp.sum(jnp.where(live, p * (
            jnp.log(jnp.where(live, p, 1.0)) - jnp.where(live, log_i, 0.0)),
            0.0))
        return jnp.moveaxis(out, 0, 2), kl, differ  # [B, rows, H, d]

    given = jnp.zeros((seq // rows, batch, rows, 1), jnp.bool_) \
        if selected is None else blocks(selected)
    out, kl, differ = jax.lax.map(jax.checkpoint(one_block), (
        jnp.arange(0, seq, rows), blocks(q), blocks(a), blocks(w), given))
    return (jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads, d),
            jnp.sum(kl), jnp.sum(differ))


def attention(h, p, hp, selected=None, compare=True):
    """The attention sub-layer on the normed input: ``(y, sum_t KL_t, rows
    whose own set differs)``."""
    q, k, v = normed_rotated(h, p, hp)
    a, b, w = index_inputs(h, p, hp)
    out, kl, differ = indexed_attention(q, k, v, a, b, w, hp, selected,
                                        compare)
    return product("bqhk,hkd->bqd", out, p["wo"]), kl, differ


def attention_residual(x, p, hp, selected=None, compare=True):
    """``(x + attention, the layer's index loss — a mean over ITS tokens —,
    rows whose own set differs)``."""
    y, kl, differ = attention(rms_norm(x, p["n1"], hp["eps"]), p, hp,
                              selected, compare)
    return x + y, kl / (x.shape[0] * x.shape[1]), differ


def layer(x, p: Dict[str, Any], hp, chosen=None, selected=None):
    """One layer on ``x [B, L, D]``: ``(x, router logits, own chosen sets,
    index loss)``."""
    x, kl, _ = attention_residual(x, p, hp, selected)
    m = rms_norm(x, p["n2"], hp["eps"])
    y, logits, own = moe(m, p, hp, chosen)
    return x + y, logits, own, kl


def cross_entropy(x, params, targets, hp):
    """Mean next-token cross entropy from the last layer's state."""
    h = rms_norm(x, params["lnf_g"], hp["eps"])
    logits = product("bsd,dv->bsv", h, params["head"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                         axis=-1)[..., 0])


def loss(params, tokens, targets, hp):
    """Step 6, whole: ``(objective, (next-token loss, index loss))``."""
    with jax.default_matmul_precision("highest"):
        x, own = params["wte"][tokens], 0.0
        for p in params["layers"]:
            x, _, _, kl = jax.checkpoint(functools.partial(layer, hp=hp))(
                x, p)
            own = own + kl
        main = cross_entropy(x, params, targets, hp)
        return main + own, (main, own)


def hyper(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file's scalars the functions above read as ``hp``.
    Callers close over it; it is never an argument of a jitted function."""
    kwargs = config["kwargs"]
    held = kwargs.get("experts_held") or (0, config["router_width"])
    if not config["norm_topk_prob"]:
        raise ValueError("the reference renormalises the chosen "
                         "probabilities: norm_topk_prob must be true")
    index = config["sa_config"]
    if index["indexer_num_kv_heads"] != 1:
        raise ValueError("the reference's index has ONE key a token")
    return {"eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "sections": tuple(config["rope_scaling"]["mrope_section"]),
            "k": int(config["num_experts_per_tok"]),
            "experts_held": (int(held[0]), int(held[1])),
            "topk": int(index["topk"]),
            "rows": min(ROWS, int(index["q_chunk_size"]))}


def loss_and_grads(params, tokens, targets, hp):
    """``((objective, (main, index)), d objective / d params)``, ``jax.grad``
    of the whole loss in one jitted call."""
    def f(params, tokens, targets):
        return loss(params, tokens, targets, hp)

    return jax.jit(jax.value_and_grad(f, has_aux=True))(params, tokens,
                                                        targets)


def selection_faults(a, b, w, selected, topk: int, margin: float,
                     rows: int = ROWS):
    """How many queries' sets ``selected [B, L, L]`` (bool, from outside) are
    NOT ``min(t + 1, topk)`` causal keys with the largest scores of the index
    ``a``, ``b``, ``w`` (this file's arithmetic on whatever inputs it is
    handed): a key outside the causal ones, a wrong count, or a chosen score
    under an unchosen one's by more than ``margin`` times the row's largest
    magnitude (which of two equal scores is taken first is the lower key's,
    and a rounding may order a near-tie either way)."""
    batch, seq = a.shape[:2]
    rows = min(rows, seq)

    def blocks(x):
        return jnp.moveaxis(
            x.reshape(batch, seq // rows, rows, *x.shape[2:]), 1, 0)

    def one_block(block):
        first, ab, wb, chosen = block
        scores = index_scores(ab.astype(jnp.float32), b.astype(jnp.float32),
                              wb.astype(jnp.float32))
        at = first + jnp.arange(rows)
        causal = (jnp.arange(seq)[None, :] <= at[:, None])[None]
        least = jnp.min(jnp.where(chosen, scores, jnp.inf), -1)
        best_left = jnp.max(jnp.where(causal & ~chosen, scores, -jnp.inf), -1)
        size = jnp.max(jnp.where(causal, jnp.abs(scores), 0.0), -1)
        wrong = jnp.any(chosen & ~causal, -1) \
            | (jnp.sum(chosen, -1) != jnp.minimum(at + 1, topk)[None]) \
            | (best_left > least + margin * size)
        return jnp.sum(wrong)

    return jnp.sum(jax.lax.map(one_block, (
        jnp.arange(0, seq, rows), blocks(a), blocks(w), blocks(selected))))


#: the leaves of a layer's dict that the attention piece reads
ATTENTION = ("n1", "wq", "wk", "wv", "qn", "kn", "wo", "iq", "ik", "ik_g",
             "ik_b", "iw")


class Pieces:
    """The same model evaluated piece by piece, as ``reference_mellum.Pieces``
    and for its reason (a whole layer with its loop over 16 experts is an
    executable that takes minutes to build, in every run): the attention
    sub-layer with its index loss, the norm, the router, ONE routed expert
    with its index an argument, the head's loss; the loops over layers and
    experts in Python and the gradient's chain rule written out over the
    pieces. ``tests/test_reference_keye.py`` holds :meth:`loss_and_grads` to
    :func:`loss_and_grads`."""

    def __init__(self, hp):
        eps = hp["eps"]
        self.hp = hp
        self.lo = hp["experts_held"][0]
        self.attn = jax.jit(lambda x, p, selected: attention_residual(
            x, p, hp, selected))
        self.norm = jax.jit(lambda x, g: rms_norm(x, g, eps))
        self.route = jax.jit(lambda m, w: router(m, w, hp["k"]))
        self.part = jax.jit(lambda m, w, chosen, e: expert_part(
            m, w["router"], w["gate"], w["up"], w["down"], chosen, e, hp))
        self.head = jax.jit(lambda x, ends, targets: cross_entropy(
            x, ends, targets, hp))
        # the cotangents of the sub-layer's state and of its index loss
        self.attn_vjp = jax.jit(lambda ct, ct_kl, x, p, selected: jax.vjp(
            lambda x, p: attention_residual(x, p, hp, selected, False)[:2],
            x, p)[1]((ct, ct_kl)))
        self.norm_vjp = jax.jit(lambda ct, x, g: jax.vjp(
            lambda x, g: rms_norm(x, g, eps), x, g)[1](ct))
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

    def layer(self, x, p, chosen=None, selected=None):
        """:func:`layer`, piece by piece: ``(x, logits, own chosen sets, the
        state after attention, the index loss, rows whose own selection is
        not the one used)``."""
        x, kl, differ = self.attn(x, _leaves(p, ATTENTION), selected)
        mid = x
        m = self.norm(x, p["n2"])
        logits, own = self.route(m, p["router"])
        chosen = own if chosen is None else chosen
        for e in range(*self.hp["experts_held"]):
            x = x + self.part(m, self._expert(p, e), chosen, e)
        return x, logits, own, mid, kl, differ

    def layer_vjp(self, ct, x, mid, p, chosen=None, selected=None,
                  ct_kl=1.0):
        """``(d x, d p)`` of :meth:`layer` from ``ct``, the cotangent of its
        output state, and ``ct_kl``, its index loss's; ``mid`` is the state
        after attention; ``chosen`` and ``selected`` as :meth:`layer` took
        them (None: the layer routes and ranks for itself)."""
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
        ct_x, ct_attn = self.attn_vjp(
            ct + d_mid, jnp.float32(ct_kl), x, _leaves(p, ATTENTION),
            selected)
        return ct_x, {**ct_attn, **ct_p}

    def loss_and_grads(self, params, tokens, targets, chosen=None,
                       selected=None):
        """``((objective, (main, index)), d objective / d params)`` as
        :func:`loss_and_grads` gives them, assembled over the pieces one
        sequence at a time (both losses are the sequences' means), each
        layer's gradient added into the total as soon as it is formed.
        ``chosen``: None, or the experts' sets from outside,
        ``chosen[sequence][layer]``; ``selected``: None, or the index's sets
        from outside, ``selected(sequence, layer) -> [1, L, L]`` bool (a
        function: a layer's written-out mask is 268 MB at 16,384 and one
        exists at a time). Both are discrete: the check hands over the sets
        the program's gradient was made under, and holds the selections
        themselves apart."""
        n = len(tokens)
        layers = params["layers"]
        ends = {"lnf_g": params["lnf_g"], "head": params["head"]}
        total = dict(jax.tree.map(jnp.zeros_like, ends),
                     wte=jnp.zeros_like(params["wte"]),
                     layers=[None] * len(layers))
        main = own = 0.0
        for i in range(n):
            row = slice(i, i + 1)
            xs, mids = [params["wte"][tokens[row]]], []
            sets = [None] * len(layers) if chosen is None else chosen[i]

            def ranked(l):
                return None if selected is None else selected(i, l)

            for l, (p, taken) in enumerate(zip(layers, sets)):
                x, _, _, mid, kl, _ = self.layer(xs[-1], p, taken, ranked(l))
                xs.append(x)
                mids.append(mid)
                own = own + kl / n
            v, (ct_x, ct_ends) = self.head_grad(xs[-1], ends, targets[row])
            main = main + v / n
            total.update(_add_scaled(_leaves(total, ends), ct_ends, 1.0 / n))
            for l in reversed(range(len(mids))):
                ct_x, ct_p = self.layer_vjp(ct_x, xs[l], mids[l], layers[l],
                                            sets[l], ranked(l))
                total["layers"][l] = _scaled(ct_p, 1.0 / n) \
                    if total["layers"][l] is None \
                    else _add_scaled(total["layers"][l], ct_p, 1.0 / n)
                del ct_p
            total["wte"] = total["wte"].at[tokens[row]].add(ct_x / n)
        return (main + own, (main, own)), total
