"""Kimi-Linear-48B-A3B (moonshotai, model type ``kimi_linear``; the layer is
the Kimi Linear report's, arXiv:2510.26692, "Kimi Delta Attention") in plain
``jax.numpy`` and float32: forward, loss and gradients, for one chip's share
of the routed experts and of the vocabulary. No kernel, no chunk, no inverse,
no WY form, no sorted buffer, no grouped product, no sharding, nothing
imported from the program. Every matrix multiplication runs at ``highest``
precision (on a TPU a float32 matmul is otherwise done in bf16 passes).

The model, as the configuration file states it (``u = RMSNorm(x)``, eps 1e-5,
is each sub-layer's input; no bias anywhere; NO positions anywhere; what the
published ``config.json`` does not settle is under the configuration's
``assumed``):

- ``x = E[tokens]``; layer ``l``: ``x <- x + Mixer_l(RMSNorm_1(x))``, then
  ``x <- x + FFN_l(RMSNorm_2(x))``;
- **KDA**, ``H`` heads of ``d_k = d_v``:
  1. three streams ``u W_q``, ``u W_k``, ``u W_v``, each through its OWN
     causal depthwise convolution of 4 taps (FOUR SHIFTED ADDS; tap 3
     multiplies the current token; no bias) and a SiLU; a head ``q_t =
     l2norm(q_t) / sqrt(d_k)``, ``k_t = l2norm(k_t)`` (``x / sqrt(sum x^2 +
     1e-6)``), ``v_t`` as it is;
  2. ``g_t = -exp(A_log_h) softplus((u W_fa) W_fb + dt_bias)`` a channel of
     every head, ``alpha_t = exp(g_t)``; ``beta_t = sigmoid(u W_b)`` a head;
  3. the recurrence TOKEN BY TOKEN, a head, ``S_0 = 0``: ``S' = Diag(alpha_t)
     S_{t-1}``; ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``; ``o_t = S_t^T
     q_t`` — a ``lax.scan`` over ``t`` exactly as written;
  4. ``y_t = (rmsnorm_head(o_t) * gain * sigmoid((u W_ga) W_gb)) W_o``, the
     norm over each head's ``d_v`` (eps 1e-5), ONE gain ``[d_v]`` for all
     heads;
- **MLA without positions**, ``H`` heads: ``q = u W_q`` as ``[S, H, nope +
  rot]`` (no bottleneck); ``[c ; r] = u W_kva``; ``c <- RMSNorm(c)``; ``c
  W_kvb`` as ``[S, H, nope + v]``, a head ``[k_nope ; v]``; ``k_h = [k_nope_h
  ; r]``, ``r`` the same for every head and NOT rotated, as q's last ``rot``
  lanes are not; scores ``q_h k_h^T / sqrt(nope + rot)``, masked to ``j <=
  i``, softmax, ``o_h = P v_h``; ``concat(o) W_o``;
- a ``dense`` layer's FFN: ``(silu(m W_gate) * (m W_up)) W_down``; a
  ``sparse`` layer's: ``s = sigmoid(m W_r)`` over ALL experts; the ``k``
  largest of ``s + b`` by ``jnp.argsort`` (``b`` selects and does not weigh:
  no gradient); weights ``scaling * s_e / sum of the chosen s``; ``shared(m)
  + sum over the chosen e in [lo, hi) of w_e E_e(m)``, every expert a SwiGLU;
  chosen experts outside the share's range add nothing;
- ``h = RMSNorm_f(x)``, ``logits = h W_head`` (untied) over the vocabulary
  held, the mean cross entropy of token ``i + 1`` at position ``i``.

Departures: none in the arithmetic. The recurrence's scan over tokens is
nested in a scan over blocks of :data:`BLOCK` tokens whose inner scan is
under ``jax.checkpoint``, so that its gradient keeps a state a BLOCK and not
a state a token (2 MB each at 32 heads of 128 x 128: 34 GB un-nested at
16,384 tokens). The attention takes one head and :data:`QUERIES` queries at a
time, the mask written out for that block. Each layer is under
``jax.checkpoint``; a product with a weight takes the sequence 128 positions
at a time (:func:`product`). :class:`Pieces` evaluates the same functions
piece by piece, each piece jitted on its own, with the chain rule written out
over the pieces. An expert is applied to every token and its result weighted
by zero where the token did not choose it. ``moe(..., chosen=)`` takes the
chosen sets from outside (routing is discrete); the weights are then still
from the reference's own scores.

Parameters are a plain dict: ``wte [V, D]``, ``head [D, V]``, ``lnf_g [D]``,
``layers`` (a list with one dict a layer). A layer: ``n1, n2 [D]``; a KDA
mixer ``wq, wk, wv [D, H, d]``, ``cq, ck, cv [4, H, d]``, ``wfa [D, d]``,
``wfb [d, H, d]``, ``a_log [H]``, ``dt_bias [H, d]``, ``wb [D, H]``, ``wga [D,
d]``, ``wgb [d, H, d]``, ``gn [d]``, ``wo [H, d, D]``; an MLA mixer ``mq [D, H,
nope + rot]``, ``wkva [D, r + rot]``, ``kvn [r]``, ``wkvb [r, H, nope + v]``,
``mo [H, v, D]``; a dense layer ``w_gate, w_up [D, F]``, ``w_down [F, D]``; a
sparse layer ``router [D, E]``, ``bias [E]``, ``e_gate, e_up [hi - lo, D,
f]``, ``e_down [hi - lo, f, D]``, ``s_gate, s_up [D, f_s]``, ``s_down [f_s,
D]``.
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
#: tokens a block of the recurrence's outer scan (a state is kept a block)
BLOCK = 128
#: queries the attention scores at a time
QUERIES = 1024


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


# ------------------------------------------------------------------ KDA
def conv4(x, taps):
    """Causal depthwise convolution of ``x [B, S, H, d]`` with ``taps [K, H,
    d]`` as K shifted adds: tap ``K - 1`` multiplies the current token, tap
    ``K - 1 - j`` the token ``j`` positions back (zeros in front of the
    sequence)."""
    n, seq = taps.shape[0], x.shape[1]
    y = x * taps[n - 1]
    for back in range(1, n):
        shifted = jnp.pad(x, [(0, 0), (back, 0), (0, 0), (0, 0)])[:, :seq]
        y = y + shifted * taps[n - 1 - back]
    return y


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def kda_inputs(u, p):
    """Steps 1 and 2: ``(q, k, v [B, S, H, d], g [B, S, H, d], beta [B, S,
    H])`` as the recurrence takes them."""
    q, k, v = (jax.nn.silu(conv4(product("bsd,dhk->bshk", u, p[w]), p[c]))
               for w, c in (("wq", "cq"), ("wk", "ck"), ("wv", "cv")))
    d_k = q.shape[-1]
    f = product("bsr,rhk->bshk", product("bsd,dr->bsr", u, p["wfa"]),
                p["wfb"])
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(f + p["dt_bias"])
    beta = jax.nn.sigmoid(product("bsd,dh->bsh", u, p["wb"]))
    return l2norm(q) / math.sqrt(d_k), l2norm(k), v, g, beta


def recurrence(q, k, v, g, beta):
    """Step 3, token by token: ``(o [B, S, H, d_v], the final state [B, H,
    d_k, d_v])``."""
    batch, seq, heads, d_k = q.shape

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        decayed = state * jnp.exp(g_t)[..., None]
        held = jnp.einsum("bhkv,bhk->bhv", decayed, k_t, precision=HIGHEST)
        update = b_t[..., None] * (v_t - held)
        state = decayed + k_t[..., :, None] * update[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=HIGHEST)

    block = BLOCK if seq % BLOCK == 0 else seq

    def by_block(x):  # [B, S, ...] -> [S / block, block, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(seq // block, block, *x.shape[1:])

    @jax.checkpoint
    def one_block(state, xs):
        return jax.lax.scan(token, state, xs)

    state0 = jnp.zeros((batch, heads, d_k, v.shape[-1]), jnp.float32)
    last, out = jax.lax.scan(one_block, state0,
                             tuple(map(by_block, (q, k, v, g, beta))))
    return jnp.moveaxis(out.reshape(seq, *out.shape[2:]), 0, 1), last


def kda_gated(o, u, p, eps):
    """Step 4 in front of ``W_o``: the norm a head with one shared gain,
    then the sigmoid gate."""
    gate = product("bsr,rhk->bshk", product("bsd,dr->bsr", u, p["wga"]),
                   p["wgb"])
    return rms_norm(o, p["gn"], eps) * jax.nn.sigmoid(gate)


def kda(u, p, hp):
    o, _ = recurrence(*kda_inputs(u, p))
    return product("bqhk,hkd->bqd", kda_gated(o, u, p, hp["eps"]), p["wo"])


# ------------------------------------------------------------ attention
def latents(u, p, hp):
    """``(c [B, S, r]`` normed, ``r [B, S, rot]`` as it comes``)``."""
    both = product("bsd,dr->bsr", u, p["wkva"])
    rank = p["kvn"].shape[0]
    return rms_norm(both[..., :rank], p["kvn"], hp["eps"]), both[..., rank:]


def attention_core(q, k_nope, shared, v):
    """``q [B, S, H, nope + rot]``, ``k_nope [B, S, H, nope]``, ``shared [B,
    S, rot]``, ``v [B, S, H, v]`` -> ``[B, S, H, v]``: one head and one block
    of queries at a time, the shared key part copied beside that head's
    own, the mask written out."""
    seq = q.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    rows = QUERIES if seq % QUERIES == 0 else seq
    keys = jnp.arange(seq)[None, :]

    def one_head(args):
        qh, kh, vh = args  # [B, S, .]
        kh = jnp.concatenate([kh, shared], -1)

        def one_block(start):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, rows, axis=1)
            mask = keys <= (start + jnp.arange(rows))[:, None]
            scores = jnp.einsum("bqd,btd->bqt", qb, kh,
                                precision=HIGHEST) * scale
            probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
            return jnp.einsum("bqt,btd->bqd", probs, vh, precision=HIGHEST)

        out = jax.lax.map(jax.checkpoint(one_block),
                          jnp.arange(0, seq, rows))  # [blocks, B, rows, v]
        return jnp.moveaxis(out, 0, 1).reshape(qh.shape[0], seq, -1)

    out = jax.lax.map(jax.checkpoint(one_head), (
        jnp.moveaxis(q, 2, 0), jnp.moveaxis(k_nope, 2, 0),
        jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2)


def mla_heads(u, p, hp):
    """The attention's result in front of ``W_o``: ``[B, S, H, v]``."""
    c, shared = latents(u, p, hp)
    q = product("bsd,dhk->bshk", u, p["mq"])
    kv = product("bsr,rhk->bshk", c, p["wkvb"])
    return attention_core(q, kv[..., :hp["nope"]], shared,
                          kv[..., hp["nope"]:])


def mla(u, p, hp):
    return product("bqhk,hkd->bqd", mla_heads(u, p, hp), p["mo"])


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


def mixer_residual(x, p, hp):
    """``x + Mixer(RMSNorm_1(x))``: KDA where the layer's dict holds its
    leaves, latent attention where it holds those."""
    u = rms_norm(x, p["n1"], hp["eps"])
    return x + (kda(u, p, hp) if "wfa" in p else mla(u, p, hp))


def layer(x, p: Dict[str, Any], hp, chosen=None):
    """One layer on ``x [B, S, D]``: ``(x, router logits, own chosen sets)``
    (the last two None in a dense layer)."""
    x = mixer_residual(x, p, hp)
    m = rms_norm(x, p["n2"], hp["eps"])
    if "router" not in p:
        return x + swiglu(m, p["w_gate"], p["w_up"], p["w_down"]), None, None
    y, logits, own = moe(m, p, hp, chosen)
    return x + y, logits, own


def states(params, tokens, hp, chosen: Optional[List[Any]] = None
           ) -> List[Any]:
    """Every layer's output state, ``[x_1 .. x_L]``."""
    x, out = params["wte"][tokens], []
    for i, p in enumerate(params["layers"]):
        x = jax.checkpoint(functools.partial(layer, hp=hp))(
            x, p, chosen=None if chosen is None else chosen[i])[0]
        out.append(x)
    return out


def cross_entropy(x, gain, head, targets, hp):
    """Mean cross entropy from a state ``x`` through the final norm ``gain``
    and the head."""
    h = rms_norm(x, gain, hp["eps"])
    logits = product("bsd,dv->bsv", h, head)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                         axis=-1)[..., 0])


def loss(params, tokens, targets, hp):
    with jax.default_matmul_precision("highest"):
        x = states(params, tokens, hp)[-1]
        return cross_entropy(x, params["lnf_g"], params["head"], targets, hp)


def hyper(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file's scalars the functions above read as ``hp``.
    Callers close over it; it is never an argument of a jitted function."""
    held = config["kwargs"].get("experts_held") or (
        0, config["num_experts_published"])
    return {"eps": float(config["rms_norm_eps"]),
            "nope": int(config["qk_nope_head_dim"]),
            "rot": int(config["qk_rope_head_dim"]),
            "k": int(config["num_experts_per_token"]),
            "scaling": float(config["routed_scaling_factor"]),
            "experts_held": (int(held[0]), int(held[1]))}


def loss_and_grads(params, tokens, targets, hp):
    """``(loss, d loss / d params)``, ``jax.grad`` of the whole loss in one
    jitted call."""
    def f(params, tokens, targets):
        return loss(params, tokens, targets, hp)

    return jax.jit(jax.value_and_grad(f))(params, tokens, targets)


#: the leaves of a layer's dict that each piece reads
KDA = ("n1", "wq", "wk", "wv", "cq", "ck", "cv", "wfa", "wfb", "a_log",
       "dt_bias", "wb", "wga", "wgb", "gn", "wo")
MLA = ("n1", "mq", "wkva", "kvn", "wkvb", "mo")
DENSE = ("n2", "w_gate", "w_up", "w_down")
SHARED = ("s_gate", "s_up", "s_down")


def mixer_leaves(p: Dict[str, Any]):
    return KDA if "wfa" in p else MLA


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
    function (the mixer sub-layer — one function, traced once a kind of
    mixer — the dense FFN, the norm, the router, the shared expert, ONE
    routed expert with its index an argument, the head's loss), the loops
    over layers and experts in Python, and the gradient's chain rule written
    out over the pieces. The arithmetic is :func:`layer`'s and :func:`loss`'s;
    ``benchmark/tests/test_reference_kimi_linear.py`` holds
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

        def head(x, ends, targets):
            return cross_entropy(x, ends["g"], ends["head"], targets, hp)

        def pull(f):  # the piece's vjp, recomputing its forward
            return jax.jit(lambda ct, *args: jax.vjp(f, *args)[1](ct))

        self.mix = jax.jit(lambda x, p: mixer_residual(x, p, hp))
        self.dense = jax.jit(dense)
        self.norm = jax.jit(lambda x, g: rms_norm(x, g, eps))
        self.route = jax.jit(lambda m, w, b: router(m, w, b, hp["k"]))
        self.shared = jax.jit(shared)
        self.part = jax.jit(part)
        self.head = jax.jit(head)
        self.mix_vjp = pull(lambda x, p: mixer_residual(x, p, hp))
        self.dense_vjp = pull(dense)
        self.norm_vjp = pull(lambda x, g: rms_norm(x, g, eps))
        self.shared_vjp = pull(shared)
        self.part_vjp = jax.jit(lambda ct, m, w, chosen, e: jax.vjp(
            lambda m, w: part(m, w, chosen, e), m, w)[1](ct))
        self.head_grad = jax.jit(jax.value_and_grad(head, argnums=(0, 1)))

    def _expert(self, p, e):
        i = e - self.lo
        return {"router": p["router"], "gate": p["e_gate"][i],
                "up": p["e_up"][i], "down": p["e_down"][i]}

    def layer(self, x, p, chosen=None):
        """:func:`layer`, piece by piece: ``(x, logits, own chosen sets, the
        state after the mixer)``. A piece is handed the leaves it reads and
        no others."""
        x = mid = self.mix(x, _leaves(p, mixer_leaves(p)))
        if "router" not in p:
            return self.dense(x, _leaves(p, DENSE)), None, None, mid
        m = self.norm(x, p["n2"])
        logits, own = self.route(m, p["router"], p["bias"])
        chosen = own if chosen is None else chosen
        x = x + self.shared(m, _leaves(p, SHARED))
        for e in range(*self.hp["experts_held"]):
            x = x + self.part(m, self._expert(p, e), chosen, e)
        return x, logits, own, mid

    def layer_vjp(self, ct, x, mid, p):
        """``(d x, d p)`` of :meth:`layer` (routing for itself) from ``ct``,
        the cotangent of its output; ``mid`` is the state after the mixer.
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
        ct_x, ct_mix = self.mix_vjp(ct_mid, x, _leaves(p, mixer_leaves(p)))
        return ct_x, {**ct_mix, **ct_p}

    def loss_and_grads(self, params, tokens, targets, by_row: bool = False):
        """``(loss, d loss / d params)`` as :func:`loss_and_grads` gives
        them, assembled over the pieces. ``by_row``: one sequence at a time
        (the loss is the rows' mean), each layer's gradient added into the
        total as soon as it is formed."""
        rows = len(tokens) if by_row else 1
        value, total = 0.0, None
        for i in range(rows):
            cut = slice(i, i + 1) if by_row else slice(None)
            one, total = self._add_grads(
                params, tokens[cut], targets[cut], 1.0 / rows, total)
            value = value + one / rows
        return value, total

    def _add_grads(self, params, tokens, targets, scale, total):
        xs, mids = [params["wte"][tokens]], []
        for p in params["layers"]:
            x, _, _, mid = self.layer(xs[-1], p)
            xs.append(x)
            mids.append(mid)
        value, (ct_x, ct_ends) = self.head_grad(
            xs[-1], {"g": params["lnf_g"], "head": params["head"]}, targets)
        if total is None:
            total = {"wte": jnp.zeros_like(params["wte"]),
                     "lnf_g": None, "head": None,
                     "layers": [None] * len(params["layers"])}

        def add(into, ct):
            return _scaled(ct, scale) if into is None \
                else _add_scaled(into, ct, scale)

        total["head"] = add(total["head"], ct_ends["head"])
        total["lnf_g"] = add(total["lnf_g"], ct_ends["g"])
        for l in reversed(range(len(params["layers"]))):
            ct_x, ct_p = self.layer_vjp(ct_x, xs[l], mids[l],
                                        params["layers"][l])
            total["layers"][l] = add(total["layers"][l], ct_p)
            del ct_p
        total["wte"] = total["wte"].at[tokens].add(ct_x * scale)
        return value, total
