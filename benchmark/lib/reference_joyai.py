"""JoyAI-LLM-Flash (JD, model type ``joyai_llm_flash``; the equations are
DeepSeek-V3's, arXiv:2412.19437 sections 2.1 and 2.2, whose configuration keys
these are) in plain ``jax.numpy`` and float32: forward, both losses and their
gradients, for one chip's share of the routed experts and of the vocabulary.
No kernel, no scan, no sorted buffer, no grouped product, no sharding, nothing
imported from the program. Every matrix multiplication runs at ``highest``
precision (on a TPU a float32 matmul is otherwise done in bf16 passes).

The model, as the configuration file states it (``u = RMSNorm(x)``, eps
1e-6, is each sub-layer's input; no bias anywhere; what the published
``config.json`` does not settle is under the configuration's ``assumed``):

- ``x = E[tokens]``; layer ``l``: ``x <- x + MLA(RMSNorm_1(x))``, then ``x <-
  x + FFN_l(RMSNorm_2(x))``;
- **MLA**, ``H`` heads: ``c_q = RMSNorm(u W_qa)`` (``q_lora_rank``), ``q =
  c_q W_qb`` as ``[S, H, nope + rot]``, a head ``[q_nope ; q_rot]``; ``[c_kv ;
  k_rot] = u W_kva`` (``kv_lora_rank`` + ``rot``), ``c_kv <- RMSNorm(c_kv)``,
  ``c_kv W_kvb`` as ``[S, H, nope + v]``, a head ``[k_nope ; v]``; ``q_rot``
  (each head's) and ``k_rot`` (ONE vector a token) rotated by position with
  INTERLEAVED pairs: dimensions ``(2i, 2i + 1)`` by the angle ``pos * theta
  ** (-2i / rot)``, the tables written out; the rotated key is copied to
  every head, head by head: ``k_h = [k_nope_h ; k_rot]``; scores ``q_h
  k_h^T / sqrt(nope + rot)`` over the whole ``[S, S]`` matrix of a head,
  masked to ``j <= i``, softmax, ``o_h = P v_h`` (``v`` wide); ``MLA(u) =
  concat(o) W_o``;
- a ``dense`` layer: ``FFN(m) = (silu(m W_gate) * (m W_up)) W_down``; a
  ``sparse`` layer: ``s = sigmoid(m W_r)`` over ALL experts; the ``k`` largest
  of ``s + b`` by ``jnp.argsort`` (``b`` a per-expert bias that selects and
  does not weigh: it takes no gradient); weights ``scaling * s_e / sum of the
  chosen s`` (WITHOUT ``b``); ``FFN(m) = shared(m) + sum over the chosen e in
  [lo, hi) of w_e E_e(m)``, every expert and the shared one a SwiGLU; chosen
  experts outside ``[lo, hi)`` — the share's range — add nothing;
- after the last layer ``h = RMSNorm_f(x)``, ``logits = h W_head`` (untied)
  over the vocabulary held, ``L_main`` the mean cross entropy of token ``i +
  1`` at position ``i``;
- **the multi-token-prediction module** (depth 1): ``h'_i = [RMSNorm_e(E
  t_{i+1}) ; RMSNorm_h(h_i)] W_eh``, ONE more sparse layer on ``h'`` (its own
  MLA, router, experts, shared expert; positions as the main stack's), a
  final norm of its own, the SAME head, ``L_mtp`` the mean cross entropy of
  token ``i + 2`` at position ``i`` over the ``S - 1`` positions that have
  one; ``L = L_main + lambda * L_mtp``.

Departures: none in the arithmetic. The module is evaluated on all ``S``
positions; the last, which has no next token, is given the sequence's first
(so that every shape is the main stack's): under the causal mask no other
position sees it and it has no target. The heads of a layer are taken one at
a time (``jax.lax.map``, each under ``jax.checkpoint``) so that one ``[S, S]``
score matrix exists at a time, and each layer is under ``jax.checkpoint``:
both bound memory and change no arithmetic. A product with a weight takes the
sequence 128 positions at a time (:func:`product`). :class:`Pieces` evaluates
the same functions piece by piece, each piece jitted on its own, with the
chain rule written out over the pieces (a whole layer with its loop over the
experts takes the TPU's compiler minutes). An expert is applied to every
token and its result weighted by zero where the token did not choose it.
``moe(..., chosen=)`` takes the chosen sets from outside (routing is
discrete: the check hands the program's sets over so that one near-tie does
not swamp a comparison of states); the weights are then still from the
reference's own scores.

Parameters are a plain dict: ``wte [V, D]``, ``head [D, V]``, ``lnf_g [D]``,
``layers`` (a list with one dict a layer) and ``mtp``: ``ne, nh, nf [D]``,
``w_eh [2 D, D]`` and ``layer``, a sparse layer's dict. A layer: ``n1, n2
[D]``, ``wqa [D, r_q]``, ``qn [r_q]``, ``wqb [r_q, H, nope + rot]``, ``wkva
[D, r_kv + rot]``, ``kvn [r_kv]``, ``wkvb [r_kv, H, nope + v]``, ``wo [H, v,
D]``; a dense layer ``w_gate, w_up [D, F]``, ``w_down [F, D]``; a sparse layer
``router [D, E]``, ``bias [E]``, ``e_gate, e_up [hi - lo, D, f]``, ``e_down
[hi - lo, f, D]``, ``s_gate, s_up [D, f_s]``, ``s_down [f_s, D]``.
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
def rope_tables(seq: int, rot: int, theta: float):
    """``(cos, sin)``, each ``[seq, rot / 2]`` float32: pair ``i`` (the
    dimensions ``2i`` and ``2i + 1``) turns by ``pos * theta ** (-2i /
    rot)``."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32)
                                / rot))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def rotate_pairs(x, cos, sin):
    """``x [B, S, H, rot]`` with every pair ``(x_2i, x_2i+1)`` turned by its
    position's angle: ``(a cos - b sin, b cos + a sin)``."""
    a, b = x[..., 0::2], x[..., 1::2]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape)


# ------------------------------------------------------------ attention
def latents(u, p, hp):
    """``(c_q, c_kv, k_rot)``: the two normed latents and the key's one
    vector a token, not yet rotated."""
    c_q = rms_norm(product("bsd,dr->bsr", u, p["wqa"]), p["qn"], hp["eps"])
    both = product("bsd,dr->bsr", u, p["wkva"])
    rank = p["kvn"].shape[0]
    return c_q, rms_norm(both[..., :rank], p["kvn"], hp["eps"]), \
        both[..., rank:]


def rotated_parts(c_q, k_rot, p, hp):
    """``(q [B, S, H, nope + rot]`` with its last ``rot`` dimensions
    rotated, ``k_rot [B, S, 1, rot]`` rotated``)``."""
    q = product("bsr,rhk->bshk", c_q, p["wqb"])
    cos, sin = rope_tables(q.shape[1], hp["rot"], hp["theta"])
    q = jnp.concatenate([q[..., :hp["nope"]],
                         rotate_pairs(q[..., hp["nope"]:], cos, sin)], -1)
    return q, rotate_pairs(k_rot[:, :, None, :], cos, sin)


def attention_core(q, k_nope, k_rot, v):
    """``q [B, S, H, nope + rot]``, ``k_nope [B, S, H, nope]``, ``k_rot [B,
    S, 1, rot]``, ``v [B, S, H, v]`` -> ``[B, S, H, v]``: one head's whole
    score matrix at a time, the shared rotated key copied beside that
    head's own part."""
    seq = q.shape[1]
    mask = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    scale = 1.0 / math.sqrt(q.shape[-1])
    shared = k_rot[:, :, 0]

    def one_head(args):
        qh, kh, vh = args  # [B, S, .]
        kh = jnp.concatenate([kh, shared], -1)
        scores = jnp.einsum("bqd,btd->bqt", qh, kh, precision=HIGHEST) * scale
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqt,btd->bqd", probs, vh, precision=HIGHEST)

    out = jax.lax.map(jax.checkpoint(one_head), (
        jnp.moveaxis(q, 2, 0), jnp.moveaxis(k_nope, 2, 0),
        jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2)


def mla_heads(u, p, hp):
    """The attention's result in front of ``W_o``: ``[B, S, H, v]``."""
    c_q, c_kv, k_rot = latents(u, p, hp)
    q, k_rot = rotated_parts(c_q, k_rot, p, hp)
    kv = product("bsr,rhk->bshk", c_kv, p["wkvb"])
    return attention_core(q, kv[..., :hp["nope"]], k_rot,
                          kv[..., hp["nope"]:])


def mla(u, p, hp):
    return product("bqhk,hkd->bqd", mla_heads(u, p, hp), p["wo"])


# ------------------------------------------------------------------ FFNs
def swiglu(m, w_gate, w_up, w_down):
    gate = product("bsd,df->bsf", m, w_gate)
    up = product("bsd,df->bsf", m, w_up)
    return product("bsf,fd->bsd", jax.nn.silu(gate) * up, w_down)


def router(m, w_router, bias, k: int):
    """``(logits, chosen [.., k])``: float32 logits over all experts and the
    experts of the ``k`` largest ``sigmoid(logits) + bias``, by
    ``jnp.argsort``."""
    logits = product("bsd,de->bse", m, w_router)
    chosen = jnp.argsort(-(jax.nn.sigmoid(logits) + bias), axis=-1)[..., :k]
    return logits, chosen


def expert_part(m, w_router, w_gate, w_up, w_down, chosen, e, hp):
    """What routed expert ``e`` adds on ``m [B, S, D]``: its SwiGLU on every
    token, weighted by the token's router weight for it — ``scaling`` times
    its sigmoid score (the bias is not in it) over the chosen scores' sum —
    or by zero where ``e`` is not among the token's ``chosen``."""
    logits = product("bsd,de->bse", m, w_router)
    scores = jnp.take_along_axis(jax.nn.sigmoid(logits), chosen, axis=-1)
    weights = hp["scaling"] * scores / jnp.sum(scores, -1, keepdims=True)
    w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
    return w_e[..., None] * swiglu(m, w_gate, w_up, w_down)


def moe(m, p, hp, chosen=None):
    """The share's part of a sparse layer on ``m [B, S, D]``: ``(y, logits,
    chosen)``; ``chosen`` from outside replaces the reference's own sets."""
    lo, hi = hp["experts_held"]
    logits, own = router(m, p["router"], p["bias"], hp["k"])
    chosen = own if chosen is None else chosen
    y = swiglu(m, p["s_gate"], p["s_up"], p["s_down"])
    for e in range(lo, hi):  # absent experts add nothing
        y = y + expert_part(m, p["router"], p["e_gate"][e - lo],
                            p["e_up"][e - lo], p["e_down"][e - lo], chosen, e,
                            hp)
    return y, logits, own


def attention_residual(x, p, hp):
    return x + mla(rms_norm(x, p["n1"], hp["eps"]), p, hp)


def layer(x, p: Dict[str, Any], hp, chosen=None):
    """One layer on ``x [B, S, D]``: ``(x, router logits, own chosen sets)``
    (the last two None in a dense layer)."""
    x = attention_residual(x, p, hp)
    m = rms_norm(x, p["n2"], hp["eps"])
    if "router" not in p:
        return x + swiglu(m, p["w_gate"], p["w_up"], p["w_down"]), None, None
    y, logits, own = moe(m, p, hp, chosen)
    return x + y, logits, own


def states(params, tokens, hp, chosen: Optional[List[Any]] = None
           ) -> List[Any]:
    """Every main layer's output state, ``[x_1 .. x_L]``."""
    x, out = params["wte"][tokens], []
    for i, p in enumerate(params["layers"]):
        x = jax.checkpoint(functools.partial(layer, hp=hp))(
            x, p, chosen=None if chosen is None else chosen[i])[0]
        out.append(x)
    return out


# ------------------------------------------------------------ the module
def next_tokens(tokens):
    """Token ``i + 1`` at position ``i``; the last position, which has none,
    is given the first token (nothing reads it: see the module's
    docstring)."""
    return jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)


def later_targets(targets):
    """``(targets one position on, which positions have one)``: the module's
    target at ``i`` is the main head's at ``i + 1``."""
    seq = targets.shape[1]
    valid = (jnp.arange(seq) < seq - 1)[None, :]
    return jnp.concatenate([targets[:, 1:], targets[:, :1]], axis=1), \
        jnp.broadcast_to(valid, targets.shape)


def merge(x_last, emb_next, lnf_g, m, hp):
    """``h' = [RMSNorm_e(E t_{i+1}) ; RMSNorm_h(h_i)] W_eh`` from the main
    stack's last state ``x_last`` (``h = RMSNorm_f(x_last)``, the state
    AFTER the final norm) and the next tokens' embeddings."""
    h = rms_norm(x_last, lnf_g, hp["eps"])
    joined = jnp.concatenate([rms_norm(emb_next, m["ne"], hp["eps"]),
                              rms_norm(h, m["nh"], hp["eps"])], -1)
    return product("bsd,de->bse", joined, m["w_eh"])


def module_state(params, x_last, tokens, hp, chosen=None):
    """The module's layer's output on ``h'``: ``(x', logits, own chosen)``."""
    m = params["mtp"]
    merged = merge(x_last, params["wte"][next_tokens(tokens)],
                   params["lnf_g"], m, hp)
    return jax.checkpoint(functools.partial(layer, hp=hp))(
        merged, m["layer"], chosen=chosen)


def cross_entropy(x, gain, head, targets, valid, hp):
    """Mean cross entropy over the ``valid`` positions from a state ``x``
    through its final norm ``gain`` and the head."""
    h = rms_norm(x, gain, hp["eps"])
    logits = product("bsd,dv->bsv", h, head)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.sum(valid)


def losses(params, tokens, targets, hp):
    """``(L_main, L_mtp)``."""
    x = states(params, tokens, hp)[-1]
    main = cross_entropy(x, params["lnf_g"], params["head"], targets,
                         jnp.ones(targets.shape, bool), hp)
    later, valid = later_targets(targets)
    x_m = module_state(params, x, tokens, hp)[0]
    return main, cross_entropy(x_m, params["mtp"]["nf"], params["head"],
                               later, valid, hp)


def loss(params, tokens, targets, hp):
    with jax.default_matmul_precision("highest"):
        main, mtp = losses(params, tokens, targets, hp)
        return main + hp["lam"] * mtp


def hyper(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file's scalars the functions above read as ``hp``.
    Callers close over it; it is never an argument of a jitted function."""
    held = config["kwargs"].get("experts_held") or (
        0, config["n_routed_experts_published"])
    return {"eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "nope": int(config["qk_nope_head_dim"]),
            "rot": int(config["qk_rope_head_dim"]),
            "k": int(config["num_experts_per_tok"]),
            "scaling": float(config["routed_scaling_factor"]),
            "experts_held": (int(held[0]), int(held[1])),
            "lam": float(config["kwargs"].get("mtp_weight", 0.3))}


def loss_and_grads(params, tokens, targets, hp):
    """``(loss, d loss / d params)``, ``jax.grad`` of the whole loss in one
    jitted call."""
    def f(params, tokens, targets):
        return loss(params, tokens, targets, hp)

    return jax.jit(jax.value_and_grad(f))(params, tokens, targets)


#: the leaves of a layer's dict that each piece reads
ATTENTION = ("n1", "wqa", "qn", "wqb", "wkva", "kvn", "wkvb", "wo")
DENSE = ("n2", "w_gate", "w_up", "w_down")
SHARED = ("s_gate", "s_up", "s_down")
MERGE = ("ne", "nh", "w_eh")


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
    function (the attention sub-layer, the dense FFN, the norm, the router,
    the shared expert, ONE routed expert with its index an argument, the
    module's join, a head's loss), the loops over layers and experts in
    Python, and the gradient's chain rule written out over the pieces — the
    final state and the embedding each take a cotangent from the main path
    AND from the module. The arithmetic is :func:`layer`'s and
    :func:`losses`'; ``benchmark/tests/test_reference_joyai.py`` holds
    :meth:`loss_and_grads` to :func:`loss_and_grads`."""

    def __init__(self, hp):
        eps = hp["eps"]
        self.hp = hp
        self.lo = hp["experts_held"][0]

        def dense(x, p):
            return x + swiglu(rms_norm(x, p["n2"], eps), p["w_gate"],
                              p["w_up"], p["w_down"])

        def shared(m, p):
            return swiglu(m, p["s_gate"], p["s_up"], p["s_down"])

        def part(m, w, chosen, e):
            return expert_part(m, w["router"], w["gate"], w["up"], w["down"],
                               chosen, e, hp)

        def head(x, ends, targets, valid):
            return cross_entropy(x, ends["g"], ends["head"], targets, valid,
                                 hp)

        def join(x_last, emb_next, lnf_g, m):
            return merge(x_last, emb_next, lnf_g, m, hp)

        def pull(f):  # the piece's vjp, recomputing its forward
            return jax.jit(lambda ct, *args: jax.vjp(f, *args)[1](ct))

        self.attn = jax.jit(lambda x, p: attention_residual(x, p, hp))
        self.dense = jax.jit(dense)
        self.norm = jax.jit(lambda x, g: rms_norm(x, g, eps))
        self.route = jax.jit(lambda m, w, b: router(m, w, b, hp["k"]))
        self.shared = jax.jit(shared)
        self.part = jax.jit(part)
        self.head = jax.jit(head)
        self.join = jax.jit(join)
        self.attn_vjp = pull(lambda x, p: attention_residual(x, p, hp))
        self.dense_vjp = pull(dense)
        self.norm_vjp = pull(lambda x, g: rms_norm(x, g, eps))
        self.shared_vjp = pull(shared)
        self.part_vjp = jax.jit(lambda ct, m, w, chosen, e: jax.vjp(
            lambda m, w: part(m, w, chosen, e), m, w)[1](ct))
        self.head_grad = jax.jit(jax.value_and_grad(head, argnums=(0, 1)))
        self.join_vjp = pull(join)

    def _expert(self, p, e):
        i = e - self.lo
        return {"router": p["router"], "gate": p["e_gate"][i],
                "up": p["e_up"][i], "down": p["e_down"][i]}

    def layer(self, x, p, chosen=None):
        """:func:`layer`, piece by piece: ``(x, logits, own chosen sets, the
        state after attention)``. A piece is handed the leaves it reads and
        no others, so that layers share the attention's pieces whatever
        their FFN."""
        x = mid = self.attn(x, _leaves(p, ATTENTION))
        if "router" not in p:
            return self.dense(x, _leaves(p, DENSE)), None, None, mid
        m = self.norm(x, p["n2"])
        logits, own = self.route(m, p["router"], p["bias"])
        chosen = own if chosen is None else chosen
        x = x + self.shared(m, _leaves(p, SHARED))
        for e in range(*self.hp["experts_held"]):
            x = x + self.part(m, self._expert(p, e), chosen, e)
        return x, logits, own, mid

    def module(self, params, x_last, tokens, chosen=None):
        """:func:`module_state`, piece by piece: ``(x', logits, own chosen
        sets, the state after its attention, h')``."""
        m = params["mtp"]
        merged = self.join(x_last, params["wte"][next_tokens(tokens)],
                           params["lnf_g"], _leaves(m, MERGE))
        return (*self.layer(merged, m["layer"], chosen), merged)

    def layer_vjp(self, ct, x, mid, p):
        """``(d x, d p)`` of :meth:`layer` (routing for itself) from ``ct``,
        the cotangent of its output; ``mid`` is the state after attention.
        The selection bias takes no gradient: zeros."""
        if "router" not in p:
            ct_mid, ct_p = self.dense_vjp(ct, mid, _leaves(p, DENSE))
        else:
            m = self.norm(mid, p["n2"])
            chosen = self.route(m, p["router"], p["bias"])[1]
            ct_m, ct_p = self.shared_vjp(ct, m, _leaves(p, SHARED))
            ct_p = dict(ct_p, router=jnp.zeros_like(p["router"]),
                        bias=jnp.zeros_like(p["bias"]), e_gate=[], e_up=[],
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
            ct_mid = ct + d_mid
        ct_x, ct_attn = self.attn_vjp(ct_mid, x, _leaves(p, ATTENTION))
        return ct_x, {**ct_attn, **ct_p}

    def loss_and_grads(self, params, tokens, targets, by_row: bool = False):
        """``(loss, d loss / d params, (L_main, L_mtp))`` as
        :func:`loss_and_grads` gives the first two, assembled over the
        pieces. ``by_row``: one sequence at a time (the loss is the rows'
        mean), each layer's gradient added into the total as soon as it is
        formed, so that one whole gradient and one layer's exist at a
        time."""
        rows = len(tokens) if by_row else 1
        value, total, parts = 0.0, None, [0.0, 0.0]
        for i in range(rows):
            cut = slice(i, i + 1) if by_row else slice(None)
            (main, mtp), total = self._add_grads(
                params, tokens[cut], targets[cut], 1.0 / rows, total)
            parts = [parts[0] + main / rows, parts[1] + mtp / rows]
            value = value + (main + self.hp["lam"] * mtp) / rows
        return value, total, tuple(parts)

    def _add_grads(self, params, tokens, targets, scale, total):
        lam, m = self.hp["lam"], params["mtp"]
        xs, mids = [params["wte"][tokens]], []
        for p in params["layers"]:
            x, _, _, mid = self.layer(xs[-1], p)
            xs.append(x)
            mids.append(mid)
        main, (ct_x, ct_main) = self.head_grad(
            xs[-1], {"g": params["lnf_g"], "head": params["head"]}, targets,
            jnp.ones(targets.shape, bool))
        # the module: its join, its layer, its own final norm, the same head
        later, valid = later_targets(targets)
        nxt = next_tokens(tokens)
        x_m, _, _, mid_m, merged = self.module(params, xs[-1], tokens)
        mtp, (ct_xm, ct_mtp) = self.head_grad(
            x_m, {"g": m["nf"], "head": params["head"]}, later, valid)
        ct_merged, ct_layer = self.layer_vjp(ct_xm, merged, mid_m, m["layer"])
        ct_last, ct_emb, ct_lnf, ct_join = self.join_vjp(
            ct_merged, xs[-1], params["wte"][nxt], params["lnf_g"],
            _leaves(m, MERGE))
        if total is None:
            total = {"wte": jnp.zeros_like(params["wte"]),
                     "lnf_g": None, "head": None, "mtp": None,
                     "layers": [None] * len(params["layers"])}

        def add(into, ct, by=1.0):
            return _scaled(ct, scale * by) if into is None \
                else _add_scaled(into, ct, scale * by)

        total["head"] = add(add(total["head"], ct_main["head"]),
                            ct_mtp["head"], lam)
        total["lnf_g"] = add(add(total["lnf_g"], ct_main["g"]), ct_lnf, lam)
        total["mtp"] = add(total["mtp"], dict(
            ct_join, nf=ct_mtp["g"], layer=ct_layer), lam)
        ct_x = ct_x + lam * ct_last
        for l in reversed(range(len(params["layers"]))):
            ct_x, ct_p = self.layer_vjp(ct_x, xs[l], mids[l],
                                        params["layers"][l])
            total["layers"][l] = add(total["layers"][l], ct_p)
            del ct_p
        total["wte"] = total["wte"].at[tokens].add(ct_x * scale) \
            .at[nxt].add(ct_emb * (scale * lam))
        return (main, mtp), total
