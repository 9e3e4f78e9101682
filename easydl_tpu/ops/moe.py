"""An expert layer that is told which experts it holds.

A fine-grained mixture-of-experts FFN as DeepSeek-V3-class models have it
(Laguna's ``sparse`` layers): a router over ALL ``experts_total`` experts in
float32 (sigmoid scores, the ``k`` largest, their scores renormalised and
scaled), SwiGLU experts, and a shared expert every token passes through.
The layer holds a contiguous range ``experts_held = [lo, hi)`` of the routed
experts — all of them, or one chip's share of an expert-parallel group — and
computes ITS experts' part of the result: for the choices that fall in the
range, rows sorted by expert into a buffer of static size, three grouped
matrix products over the experts held (``jax.lax.ragged_dot``: on a TPU one
Mosaic kernel that walks only the tiles the groups fill), the weighted sum
back per token; plus the shared expert, once. What absent experts would
have added is left out; no code stands in for them or for their traffic.

No token is ever dropped. A token's ``k`` choices are distinct experts, so at
most ``min(k, held)`` of them fall here: the buffer holds ``tokens x min(k,
held)`` rows (:func:`rows_bound`), every choice that falls here has a row, and
the counter ``dropped`` (choices in the range without a row) says so in every
step. On average ``k x held / total`` of a token's choices fall here (one in
Laguna's eight-chip group), so the buffer is mostly unused rows behind the
last group: the grouped products do not visit them.

Under a mesh whose ``ep`` axis is larger than one the expert weights are
sharded over it (rule ``("expert", "ep")``, ``core/sharding.py``): each shard
holds ``held / ep`` experts of the range, routes the tokens it has over all
experts, computes its experts' part, and the parts are summed over ``ep``
(``jax.shard_map``: the grouped products are kernels, which GSPMD cannot
partition). Tokens stay where the batch axes put them: there is no
all-to-all, each ``ep`` shard sees every token of its batch shard.

Gathers both ways. Sorting rows by expert and putting results back are
permutations; their transposes are written out as gathers too
(:func:`_dispatch`, :func:`_combine`), where XLA's own rule for a gather is
a scatter-add.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from easydl_tpu.core.mesh_shapes import BATCH_AXES

#: mesh axis the held experts are sharded over (core/sharding.py rules)
EXPERT_AXIS = "ep"

#: the layer's counters, in the order of the vector it returns
COUNTERS = ("moe_dropped", "moe_rows_per_token", "moe_load_max_over_mean",
            "moe_buffer_fill", "router_entropy")


def rows_bound(tokens: int, k: int, held: int) -> int:
    """Rows of the sorted buffer: every choice that can fall on ``held``
    experts (a token's ``k`` choices are distinct)."""
    return tokens * min(k, held)


def route(h: jax.Array, kernel: jax.Array, k: int, scaling: float):
    """``(logits [T, E] float32, chosen [T, k] int32, weights [T, k]
    float32)`` of tokens ``h [T, D]``: logits in float32 at ``highest``
    precision whatever ``h``'s dtype (on a TPU a float32 product is
    otherwise made of bf16 passes), sigmoid scores, the ``k`` largest, each
    weight its score over the chosen scores' sum times ``scaling``."""
    logits = jnp.dot(h.astype(jnp.float32), kernel.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    top, chosen = jax.lax.top_k(scores, k)
    return logits, chosen, scaling * top / jnp.sum(top, -1, keepdims=True)


# ------------------------------------------------------ permutations as gathers
@jax.custom_vjp
def _dispatch(x, order, place, live):
    """``x [T, D]`` as the buffer's rows ``[R, D]``: row ``r`` is the token
    of choice ``order[r]`` (choices numbered ``token * k + j``)."""
    return x[order // place.shape[1]]


def _dispatch_fwd(x, order, place, live):
    return _dispatch(x, order, place, live), (place, live)


def _dispatch_bwd(res, g):
    place, live = res  # [T, k]: a choice's row, and whether it has one
    picked = jnp.where(live[..., None], g[place], 0)
    return picked.astype(jnp.float32).sum(1).astype(g.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, weights, order, place, live):
    """``y [T, D]``: each token's live choices' rows, weighted and summed
    in float32."""
    picked = jnp.where(live[..., None], rows[place], 0).astype(jnp.float32)
    return (picked * weights[..., None]).sum(1).astype(rows.dtype)


def _combine_fwd(rows, weights, order, place, live):
    return _combine(rows, weights, order, place, live), (
        rows, weights, order, place, live)


def _combine_bwd(res, g):
    rows, weights, order, place, live = res
    k = place.shape[1]
    scale = jnp.where(live, weights, 0.0).reshape(-1)[order]  # [R]
    d_rows = (g[order // k].astype(jnp.float32) * scale[:, None]
              ).astype(rows.dtype)
    picked = jnp.where(live[..., None], rows[place], 0).astype(jnp.float32)
    d_weights = (picked * g[:, None, :].astype(jnp.float32)).sum(-1)
    return d_rows, d_weights, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def routed_experts(h, chosen, weights, w_gate, w_up, w_down, lo):
    """The part of the routed result the experts ``[lo, lo + E)`` give,
    ``E = w_gate.shape[0]``: ``(y [T, D], stats [3])`` with ``stats`` =
    (choices in the range that got no row, choices in the range, the
    largest expert's rows), float32. ``lo`` may be traced (a shard's own
    under ``ep``)."""
    tokens, k = chosen.shape
    held = w_gate.shape[0]
    rows = rows_bound(tokens, k, held)
    with jax.named_scope("dispatch"):
        local = chosen - lo
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True)
        place = jnp.argsort(order).reshape(tokens, k)  # a choice's row
        order = order[:rows]
        live = mine & (place < rows)
        ends = jnp.searchsorted(key[order], jnp.arange(held + 1), side="left")
        sizes = jnp.diff(ends).astype(jnp.int32)
        place = jnp.minimum(place, rows - 1)
        x = _dispatch(h, order, place, live)
    with jax.named_scope("experts"):
        dot = functools.partial(jax.lax.ragged_dot, group_sizes=sizes,
                                preferred_element_type=h.dtype)
        act = nn.silu(dot(x, w_gate)) * dot(x, w_up)
        out = dot(act, w_down)
    with jax.named_scope("combine"):
        y = _combine(out, weights, order, place, live)
    n_mine = jnp.sum(mine).astype(jnp.float32)
    stats = jnp.stack([n_mine - jnp.sum(live).astype(jnp.float32), n_mine,
                       jnp.max(sizes).astype(jnp.float32)])
    return y, stats


def _over_expert_shards(fn, tokens: int, held: int):
    """``fn(h, chosen, weights, w_gate, w_up, w_down, lo)`` per ``ep`` shard
    of the expert weights under the context mesh, the parts summed over
    ``ep`` (and the stats with them: sums summed, the largest group the
    largest anywhere); ``fn`` itself where the mesh has no ``ep`` axis
    larger than one. Tokens are split over the batch axes where they
    divide, as attention's per-shard wrap has it."""
    mesh = jax.sharding.get_abstract_mesh()
    if EXPERT_AXIS not in mesh.axis_names \
            or EXPERT_AXIS in mesh.manual_axes \
            or mesh.shape[EXPERT_AXIS] == 1:
        return fn
    shards = mesh.shape[EXPERT_AXIS]
    if held % shards:
        raise ValueError(f"{held} experts held do not divide over "
                         f"{EXPERT_AXIS}={shards}")
    batch = tuple(a for a in BATCH_AXES if a in mesh.axis_names
                  and a not in mesh.manual_axes and mesh.shape[a] > 1)
    if batch and tokens % math.prod(mesh.shape[a] for a in batch):
        batch = ()
    batch_shards = math.prod(mesh.shape[a] for a in batch)
    every = (EXPERT_AXIS,) + batch

    def shard(h, chosen, weights, w_gate, w_up, w_down, lo):
        lo = lo + jax.lax.axis_index(EXPERT_AXIS) * (held // shards)
        y, stats = fn(h, chosen, weights, w_gate, w_up, w_down, lo)
        # the largest group is of ONE batch shard's tokens: times the batch
        # shards, so that it stands against the summed rows' mean
        return jax.lax.psum(y, EXPERT_AXIS), jnp.concatenate([
            jax.lax.psum(stats[:2], every),
            jax.lax.pmax(stats[2:], every) * batch_shards])

    rows, experts = P(batch or None), P(EXPERT_AXIS)
    return jax.shard_map(
        shard, in_specs=(rows, rows, rows, experts, experts, experts, P()),
        out_specs=(rows, P()), check_vma=False)


class MoeMlp(nn.Module):
    """Router, the routed experts held here, the shared expert: ``x [B, S,
    D]`` (the normed input) to ``(y [B, S, D], counters [5])``, the counters
    in :data:`COUNTERS`' order, float32.

    Scopes (``jax.named_scope``): ``router``, ``dispatch``, ``experts``,
    ``combine``, ``shared_expert``; the caller's ``moe`` scope is around
    them. Where ``intermediates`` is a mutable collection (the benchmark's
    check, tests) the layer also sows what it routed on: ``router_in``,
    ``router_logits``, ``chosen``."""

    experts_total: int
    experts_held: Tuple[int, int]
    d_ff: int
    shared_d_ff: int
    k: int
    scaling: float = 1.0
    #: init scale of the down projections (the dense path's residual scale)
    out_init_scale: float = 1.0
    dtype: str = "float32"

    @nn.compact
    def __call__(self, x):
        batch, seq, d = x.shape
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.experts_total:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.experts_total}")
        held, tokens = hi - lo, batch * seq
        dt = jnp.dtype(self.dtype)
        h = x.astype(dt).reshape(tokens, d)

        def weight(name, shape, axes, scale=1.0):
            return jnp.asarray(self.param(
                name, nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=0.02 * scale), axes),
                shape), dt)

        with jax.named_scope("router"):
            # the router's width is the published one, whatever is held
            kernel = self.param("router", nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("embed", None)),
                (d, self.experts_total))
            logits, chosen, weights = route(h, kernel, self.k, self.scaling)
            self.sow("intermediates", "router_in", h)
            self.sow("intermediates", "router_logits", logits)
            self.sow("intermediates", "chosen", chosen)
            share = jax.nn.sigmoid(logits)
            share = share / jnp.sum(share, -1, keepdims=True)
            entropy = -jnp.mean(jnp.sum(
                share * jnp.log(jnp.maximum(share, 1e-30)), -1))

        inward, outward = ("expert", "embed", "mlp"), ("expert", "mlp", "embed")
        w_gate = weight("w_gate", (held, d, self.d_ff), inward)
        w_up = weight("w_up", (held, d, self.d_ff), inward)
        w_down = weight("w_down", (held, self.d_ff, d), outward,
                        self.out_init_scale)
        y, stats = _over_expert_shards(routed_experts, tokens, held)(
            h, chosen, weights, w_gate, w_up, w_down, jnp.int32(lo))
        if self.shared_d_ff:
            with jax.named_scope("shared_expert"):
                gate = weight("shared_gate", (d, self.shared_d_ff),
                              ("embed", "mlp"))
                up = weight("shared_up", (d, self.shared_d_ff),
                            ("embed", "mlp"))
                down = weight("shared_down", (self.shared_d_ff, d),
                              ("mlp", "embed"), self.out_init_scale)
                y = y + (nn.silu(h @ gate) * (h @ up)) @ down
        dropped, n_mine, largest = stats
        counters = jnp.stack([
            dropped,
            n_mine / tokens,
            largest * held / jnp.maximum(n_mine, 1.0),
            n_mine / rows_bound(tokens, self.k, held),
            entropy])
        return y.reshape(batch, seq, d), counters
