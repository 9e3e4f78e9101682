"""ZAYA1 (Zyphra, model type ``zaya``) in plain ``jax.numpy`` and float32:
forward, next-token loss and its gradients, for one chip's share of the routed
experts and of the vocabulary. No kernel, no scan, no sorted buffer, no
grouped product, no sharding, nothing imported from the program. Every matrix
multiplication runs at ``highest`` precision (on a TPU a float32 matmul is
otherwise done in bf16 passes).

The model, as the configuration file states it (widths from the published
``config.json``; the sentences neither that file nor the papers' text settle,
(a) to (i), are under the configuration's ``assumed``). ``h = RMSNorm(x)`` is
each sub-layer's input, ``shift(u)_t = u_{t-1}`` with ``u_{-1} = 0`` written
as a pad:

- ``x = E[tokens]``: no multiplier, no position table;
- CCA, ``H`` query and ``G`` key/value heads of ``d``: ``q~ = h W_q [S, H,
  d]``, ``k~ = h W_k [S, G, d]``; ``v``: the first ``G / 2`` heads ``h
  W_v[:, :G/2]``, the others ``shift(h) W_v[:, G/2:]``; on the ``H + G``
  heads of ``u = [q~, k~]``: ``c1 = a_0 * shift(u) + a_1 * u + b_1`` per
  channel, ``c2 = shift(c1) A_0 + c1 A_1 + b_2`` per head, ``A [d, d]``;
  ``m_q[j] = (q~[j] + k~[j // (H / G)]) / 2``, ``m_k[g]`` the mean of its
  query heads' ``m_q``; ``q = c2_q + m_q``, ``k = c2_k + m_k``; ``q <- q /
  ||q|| sqrt(d)``, ``k <- k / ||k|| sqrt(d) exp(tau_g)``; rotary on the first
  ``rot = d * partial_rotary_factor`` dimensions (rotate-half, dimension
  ``i`` with ``i + rot / 2``, explicit tables); scores ``q k^T / sqrt(d)``
  over the whole ``[S, S]`` matrix of a head, masked to ``j <= i``, softmax,
  ``o = P v``; ``y = concat(o) W_o``;
- every residual add: ``x <- (s_x * x + t_x) + (s_y * y + t_y)``;
- router, on ``m = RMSNorm_2(x)``: ``r = m W_d + b_d + gamma * r_prev``
  (``r_prev`` the previous layer's ``r``, zeros into layer 0; ``r`` goes on
  to the next layer), ``z = W_3 gelu(W_2 gelu(W_1 RMSNorm(r) + b_1) + b_2)``
  (erf GELU), ``p = softmax(z)`` over the experts and the skip choice; the
  choice is ``argmax(p)``, its weight ``p`` at it; choice ``e`` in ``[lo,
  hi)`` adds ``p_e (silu(m G_e) * (m U_e)) D_e``; the skip choice and
  experts outside ``[lo, hi)`` — the share's range — add nothing;
- ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * g``; after the last layer the
  final norm, logits by the embedding's transpose over the vocabulary held,
  mean next-token cross entropy, no auxiliary loss.

Departures: none in the arithmetic. The heads of a layer are taken one at a
time (``jax.lax.map``, each under ``jax.checkpoint``) so that one ``[S, S]``
score matrix exists at a time, and each layer is under ``jax.checkpoint``:
both bound memory and change no arithmetic. A product with a weight takes the
sequence 128 positions at a time (:func:`product`; a position's result does
not depend on the others'): the TPU's compiler builds the whole-sequence
``highest`` product in seconds and the loop's in a fraction. :class:`Pieces`
evaluates the same functions piece by piece, each jitted on its own (every
layer is of one shape, so each piece is built once). An expert is applied to
every token and its result weighted by zero where the token did not choose
it. ``moe(..., chosen=)`` takes the choices from outside (routing is
discrete: the check hands the program's over so that one near-tie does not
swamp a comparison of states); the weights are then still the reference's
own probabilities.

Parameters are a plain dict: ``wte [V, D]``, ``lnf_g [D]`` and ``layers``, a
list with one dict a layer: ``n1, n2 [D]``; ``wq [D, H, d]``, ``wk, wv [D, G,
d]``, ``wo [H, d, D]``, ``conv0 [2, H + G, d]``, ``conv0_b [H + G, d]``,
``conv1 [2, H + G, d, d]``, ``conv1_b [H + G, d]``, ``tau [G]``; ``res_a``
and ``res_m``, each ``[4, D]``: ``s_x, t_x, s_y, t_y`` of the attention's
and the experts' add; ``r_down [D, R]``, ``r_down_b, r_gamma, r_norm, r_b1,
r_b2 [R]``, ``r_w1, r_w2 [R, R]``, ``r_w3 [R, E + 1]``; ``e_gate, e_up [hi -
lo, D, f]``, ``e_down [hi - lo, f, D]``.
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


def shift(u):
    """``u [B, S, ...]`` one position later: position ``t`` holds ``u[t -
    1]``, position 0 zeros."""
    pad = [(0, 0), (1, 0)] + [(0, 0)] * (u.ndim - 2)
    return jnp.pad(u, pad)[:, :-1]


# --------------------------------------------------------------- rotary
def rope_tables(seq: int, head_dim: int, p: Dict[str, Any]):
    """``(cos, sin, rot)``: ``[seq, rot]`` float32 tables of one entry of
    ``rope_parameters`` and the number of rotated dimensions."""
    rot = int(head_dim * p.get("partial_rotary_factor", 1.0))
    i = jnp.arange(0, rot, 2, dtype=jnp.float32)
    inv_freq = 1.0 / (float(p["rope_theta"]) ** (i / rot))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles), rot


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, cos, sin, rot):
    """``x [B, S, H, d]``: the first ``rot`` dimensions rotated."""
    turned, passed = x[..., :rot], x[..., rot:]
    turned = turned * cos[None, :, None, :] \
        + rotate_half(turned) * sin[None, :, None, :]
    return jnp.concatenate([turned, passed], axis=-1)


# ------------------------------------------------------------------ CCA
def attention_core(q, k, v):
    """``q [B, S, H, d]``, ``k, v [B, S, G, d]`` -> ``[B, S, H, d]``, one
    query head's whole causal score matrix at a time."""
    heads, groups, seq = q.shape[2], k.shape[2], q.shape[1]
    mask = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    scale = 1.0 / math.sqrt(q.shape[-1])

    def one_head(args):
        qh, kh, vh = args  # [B, S, d]
        scores = jnp.einsum("bqd,btd->bqt", qh, kh, precision=HIGHEST) * scale
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqt,btd->bqd", probs, vh, precision=HIGHEST)

    reads = jnp.arange(heads) // (heads // groups)
    out = jax.lax.map(jax.checkpoint(one_head), (jnp.moveaxis(q, 2, 0),
                                 jnp.moveaxis(k, 2, 0)[reads],
                                 jnp.moveaxis(v, 2, 0)[reads]))
    return jnp.moveaxis(out, 0, 2)


def convolve(u, p):
    """Both convolutions on ``u [B, S, H + G, d]``."""
    c1 = p["conv0"][0] * shift(u) + p["conv0"][1] * u + p["conv0_b"]
    return (product("bshc,hcd->bshd", shift(c1), p["conv1"][0])
            + product("bshc,hcd->bshd", c1, p["conv1"][1]) + p["conv1_b"])


def values(h, wv):
    """``v [B, S, G, d]``: the first half of the heads from the current
    token, the second from the previous one."""
    half = wv.shape[1] // 2
    return jnp.concatenate([
        product("bsd,dgk->bsgk", h, wv[:, :half]),
        product("bsd,dgk->bsgk", shift(h), wv[:, half:])], axis=2)


def mixed_qk(h, p):
    """``(q [B, S, H, d], k [B, S, G, d])`` in front of the rotation:
    projected down, convolved, the means added, normed."""
    q0 = product("bsd,dhk->bshk", h, p["wq"])
    k0 = product("bsd,dhk->bshk", h, p["wk"])
    heads, groups, d = q0.shape[2], k0.shape[2], q0.shape[3]
    c2 = convolve(jnp.concatenate([q0, k0], axis=2), p)
    reads = jnp.arange(heads) // (heads // groups)
    m_q = 0.5 * (q0 + k0[:, :, reads])
    m_k = jnp.mean(m_q.reshape(*m_q.shape[:2], groups, heads // groups, d), 3)
    q, k = c2[:, :, :heads] + m_q, c2[:, :, heads:] + m_k
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * math.sqrt(d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True) * math.sqrt(d) \
        * jnp.exp(p["tau"])[:, None]
    return q, k


def cca(h, p, hp):
    q, k = mixed_qk(h, p)
    cos, sin, rot = rope_tables(h.shape[1], q.shape[-1], hp["rope"])
    out = attention_core(rope(q, cos, sin, rot), rope(k, cos, sin, rot),
                         values(h, p["wv"]))
    return product("bqhk,hkd->bqd", out, p["wo"])


def merge(x, y, res):
    """A residual add: ``res [4, D]`` holds ``s_x, t_x, s_y, t_y``."""
    return (res[0] * x + res[1]) + (res[2] * y + res[3])


def attention_residual(x, p, hp):
    return merge(x, cca(rms_norm(x, p["n1"], hp["eps"]), p, hp), p["res_a"])


# -------------------------------------------------------------- experts
def router(m, r_prev, p, hp):
    """``(r, logits, probabilities)``: the state handed on, the float32
    logits over the experts and the skip choice, their softmax."""
    r = product("bsd,dr->bsr", m, p["r_down"]) + p["r_down_b"] \
        + p["r_gamma"] * r_prev
    u = rms_norm(r, p["r_norm"], hp["eps"])
    u = jax.nn.gelu(product("bsr,rt->bst", u, p["r_w1"]) + p["r_b1"],
                    approximate=False)
    u = jax.nn.gelu(product("bsr,rt->bst", u, p["r_w2"]) + p["r_b2"],
                    approximate=False)
    logits = product("bsr,rt->bst", u, p["r_w3"])
    return r, logits, jax.nn.softmax(logits, axis=-1)


def swiglu(m, w_gate, w_up, w_down):
    gate = product("bsd,df->bsf", m, w_gate)
    up = product("bsd,df->bsf", m, w_up)
    return product("bsf,fd->bsd", jax.nn.silu(gate) * up, w_down)


def expert_part(m, r_prev, p_router, w_gate, w_up, w_down, chosen, e, hp):
    """What routed expert ``e`` adds on ``m [B, S, D]``: its SwiGLU on every
    token, weighted by the token's probability for it where ``chosen [B,
    S]`` is ``e``, by zero elsewhere."""
    probs = router(m, r_prev, p_router, hp)[2]
    w_e = jnp.where(chosen == e, probs[..., e], 0.0)
    return w_e[..., None] * swiglu(m, w_gate, w_up, w_down)


ROUTER = ("r_down", "r_down_b", "r_gamma", "r_norm", "r_w1", "r_b1", "r_w2",
          "r_b2", "r_w3")
ATTENTION = ("n1", "wq", "wk", "wv", "wo", "conv0", "conv0_b", "conv1",
             "conv1_b", "tau", "res_a")


def _leaves(p: Dict[str, Any], names) -> Dict[str, Any]:
    return {name: p[name] for name in names}


def moe(m, r_prev, p, hp, chosen=None):
    """The share's part of an expert sub-layer on ``m [B, S, D]``: ``(y, r,
    logits, own choices [B, S])``; ``chosen`` from outside replaces the
    reference's own choices."""
    lo, hi = hp["experts_held"]
    p_router = _leaves(p, ROUTER)
    r, logits, probs = router(m, r_prev, p_router, hp)
    own = jnp.argmax(probs, axis=-1)
    chosen = own if chosen is None else chosen
    y = jnp.zeros_like(m)
    for e in range(lo, hi):  # absent experts and the skip choice add nothing
        y = y + expert_part(m, r_prev, p_router, p["e_gate"][e - lo],
                            p["e_up"][e - lo], p["e_down"][e - lo], chosen, e,
                            hp)
    return y, r, logits, own


def layer(x, r_prev, p: Dict[str, Any], hp, chosen=None):
    """One layer on ``x [B, S, D]`` and the router state ``r_prev [B, S,
    R]``: ``(x, r, router logits, own choices)``."""
    x = attention_residual(x, p, hp)
    y, r, logits, own = moe(rms_norm(x, p["n2"], hp["eps"]), r_prev, p, hp,
                            chosen)
    return merge(x, y, p["res_m"]), r, logits, own


def states(params, tokens, hp, chosen: Optional[List[Any]] = None):
    """Every layer's output, ``([x_1 .. x_L], [r_1 .. r_L])``."""
    x = params["wte"][tokens]
    r = jnp.zeros((*x.shape[:2], params["layers"][0]["r_gamma"].shape[0]),
                  jnp.float32)
    xs, rs = [], []
    for i, p in enumerate(params["layers"]):
        x, r = jax.checkpoint(functools.partial(layer, hp=hp))(
            x, r, p, chosen=None if chosen is None else chosen[i])[:2]
        xs.append(x)
        rs.append(r)
    return xs, rs


def cross_entropy(x, ends, targets, hp):
    """Mean next-token cross entropy from the last layer's state; the head
    is the embedding ``ends["wte"]``'s transpose. ``ROWS`` positions'
    logits at a time, as :func:`product` takes them (each block under
    ``jax.checkpoint``: ``[S, V]`` float32 logits and their gradient are
    never whole)."""
    h = rms_norm(x, ends["lnf_g"], hp["eps"])

    def losses(args):
        rows, wanted = args
        logits = jnp.einsum("bsd,vd->bsv", rows, ends["wte"],
                            precision=HIGHEST)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, wanted[..., None], axis=-1)[..., 0]

    batch, seq = targets.shape
    if seq <= ROWS or seq % ROWS:
        return jnp.mean(losses((h, targets)))
    blocks = (jnp.moveaxis(h.reshape(batch, seq // ROWS, ROWS, -1), 1, 0),
              jnp.moveaxis(targets.reshape(batch, seq // ROWS, ROWS), 1, 0))
    return jnp.mean(jax.lax.map(jax.checkpoint(losses), blocks))


def loss(params, tokens, targets, hp):
    with jax.default_matmul_precision("highest"):
        return cross_entropy(states(params, tokens, hp)[0][-1], params,
                             targets, hp)


def hyper(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file's scalars the functions above read as ``hp``.
    Callers close over it; it is never an argument of a jitted function."""
    held = config["kwargs"].get("experts_held") \
        or (0, config["num_experts_published"])
    kind, = set(config["layer_types"])
    return {"eps": float(config["rms_norm_eps"]),
            "rope": config["rope_parameters"][kind],
            "experts_held": (int(held[0]), int(held[1]))}


def loss_and_grads(params, tokens, targets, hp):
    """``(loss, d loss / d params)``, ``jax.grad`` of the whole loss in one
    jitted call."""
    def f(params, tokens, targets):
        return loss(params, tokens, targets, hp)

    return jax.jit(jax.value_and_grad(f))(params, tokens, targets)


@jax.jit
def _scaled(ct, scale):
    return jax.tree.map(lambda c: c * scale, ct)


@jax.jit
def _add_scaled(into, ct, scale):
    return jax.tree.map(lambda t, c: t + c * scale, into, ct)


class Pieces:
    """The same model evaluated piece by piece: each piece a small jitted
    function (the attention sub-layer with its add, the norm, the router,
    ONE routed expert with its index an argument, an add, the head's loss),
    the loops over layers and experts in Python, and the gradient's chain
    rule written out over the pieces. Every layer is of one shape: a piece
    is built once. The arithmetic is :func:`layer`'s;
    ``tests/test_reference_zaya.py`` holds :meth:`loss_and_grads` to
    :func:`loss_and_grads`."""

    def __init__(self, hp):
        eps = hp["eps"]
        self.hp = hp
        self.lo = hp["experts_held"][0]

        def part(m, r_prev, w, chosen, e):
            return expert_part(m, r_prev, w["router"], w["gate"], w["up"],
                               w["down"], chosen, e, hp)

        def state_of(m, r_prev, p_router):
            return router(m, r_prev, p_router, hp)[0]

        self.attn = jax.jit(lambda x, p: attention_residual(x, p, hp))
        self.norm = jax.jit(lambda x, g: rms_norm(x, g, eps))
        self.route = jax.jit(lambda m, r_prev, p: router(m, r_prev, p, hp))
        self.part = jax.jit(part)
        self.merge = jax.jit(merge)
        self.head = jax.jit(lambda x, ends, targets: cross_entropy(
            x, ends, targets, hp))

        def pull(f):  # the piece's vjp, recomputing its forward
            return jax.jit(lambda ct, *args: jax.vjp(f, *args)[1](ct))

        self.attn_vjp = pull(lambda x, p: attention_residual(x, p, hp))
        self.norm_vjp = pull(lambda x, g: rms_norm(x, g, eps))
        self.state_vjp = pull(state_of)
        self.merge_vjp = pull(merge)
        self.part_vjp = jax.jit(lambda ct, m, r_prev, w, chosen, e: jax.vjp(
            lambda m, r_prev, w: part(m, r_prev, w, chosen, e),
            m, r_prev, w)[1](ct))
        self.head_grad = jax.jit(jax.value_and_grad(
            lambda x, ends, targets: cross_entropy(x, ends, targets, hp),
            argnums=(0, 1)))

    def _expert(self, p, e):
        i = e - self.lo
        return {"router": _leaves(p, ROUTER), "gate": p["e_gate"][i],
                "up": p["e_up"][i], "down": p["e_down"][i]}

    def _experts(self, m, r_prev, p, chosen):
        y = jnp.zeros_like(m)
        for e in range(*self.hp["experts_held"]):
            y = y + self.part(m, r_prev, self._expert(p, e), chosen, e)
        return y

    def layer(self, x, r_prev, p, chosen=None):
        """:func:`layer`, piece by piece: ``(x, r, logits, own choices, the
        state after attention)``."""
        mid = self.attn(x, _leaves(p, ATTENTION))
        m = self.norm(mid, p["n2"])
        r, logits, probs = self.route(m, r_prev, _leaves(p, ROUTER))
        own = jnp.argmax(probs, axis=-1)
        y = self._experts(m, r_prev, p, own if chosen is None else chosen)
        return self.merge(mid, y, p["res_m"]), r, logits, own, mid

    def layer_vjp(self, ct, ct_r, x, r_prev, mid, p):
        """``(d x, d r_prev, d p)`` of :meth:`layer` (routing for itself)
        from the cotangents of its state ``ct`` and of the router state it
        hands on ``ct_r``; ``mid`` is the state after attention."""
        p_router = _leaves(p, ROUTER)
        m = self.norm(mid, p["n2"])
        chosen = jnp.argmax(self.route(m, r_prev, p_router)[2], axis=-1)
        y = self._experts(m, r_prev, p, chosen)
        ct_mid, ct_y, ct_res = self.merge_vjp(ct, mid, y, p["res_m"])
        ct_m, ct_prev, ct_router = self.state_vjp(ct_r, m, r_prev, p_router)
        ct_p = {"res_m": ct_res, "e_gate": [], "e_up": [], "e_down": []}
        for e in range(*self.hp["experts_held"]):
            d_m, d_prev, d_w = self.part_vjp(ct_y, m, r_prev,
                                             self._expert(p, e), chosen, e)
            ct_m, ct_prev = ct_m + d_m, ct_prev + d_prev
            ct_router = jax.tree.map(jnp.add, ct_router, d_w["router"])
            for name in ("gate", "up", "down"):
                ct_p[f"e_{name}"].append(d_w[name])
        for name in ("e_gate", "e_up", "e_down"):
            ct_p[name] = jnp.stack(ct_p[name])
        d_mid, ct_p["n2"] = self.norm_vjp(ct_m, mid, p["n2"])
        ct_x, ct_attn = self.attn_vjp(ct_mid + d_mid, x,
                                      _leaves(p, ATTENTION))
        return ct_x, ct_prev, {**ct_attn, **ct_router, **ct_p}

    def loss_and_grads(self, params, tokens, targets, by_row: bool = False):
        """``(loss, d loss / d params)`` as :func:`loss_and_grads` gives
        them, assembled over the pieces. ``by_row``: one sequence at a time
        (the loss is the rows' mean), each layer's gradient added into the
        total as soon as it is formed."""
        rows = len(tokens) if by_row else 1
        value, total = 0.0, None
        for i in range(rows):
            cut = slice(i, i + 1) if by_row else slice(None)
            v, total = self._add_grads(params, tokens[cut], targets[cut],
                                       1.0 / rows, total)
            value = value + v / rows
        return value, total

    def _add_grads(self, params, tokens, targets, scale, total):
        layers = params["layers"]
        ends = {"lnf_g": params["lnf_g"], "wte": params["wte"]}
        xs = [params["wte"][tokens]]
        rs = [jnp.zeros((*tokens.shape, layers[0]["r_gamma"].shape[0]),
                        jnp.float32)]
        mids = []
        for p in layers:
            x, r, _, _, mid = self.layer(xs[-1], rs[-1], p)
            xs.append(x)
            rs.append(r)
            mids.append(mid)
        value, (ct_x, ct_ends) = self.head_grad(xs[-1], ends, targets)
        if total is None:
            total = dict(jax.tree.map(jnp.zeros_like, ends),
                         layers=[None] * len(layers))

        def add(into, ct):
            return _scaled(ct, scale) if into is None \
                else _add_scaled(into, ct, scale)

        for name in ends:
            total[name] = add(total[name], ct_ends[name])
        ct_r = jnp.zeros_like(rs[-1])  # nothing reads the last layer's
        for l in reversed(range(len(layers))):
            ct_x, ct_r, ct_p = self.layer_vjp(ct_x, ct_r, xs[l], rs[l],
                                              mids[l], layers[l])
            total["layers"][l] = add(total["layers"][l], ct_p)
            del ct_p
        total["wte"] = total["wte"].at[tokens].add(ct_x * scale)
        return value, total
