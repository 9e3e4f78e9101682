"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

Long sequences shard over the mesh's ``sp`` axis (easydl_tpu/core/mesh.py
puts ``sp`` innermost with ``tp`` so its collectives ride nearest-neighbour
ICI). Two attention strategies, both pure JAX inside ``shard_map``:

- :func:`ring_attention` — KV blocks rotate around the ring via ``ppermute``
  while each device folds them into an online softmax. The per-device score
  matrix is [s_loc, s_loc] (S²/n² memory), and each ring step is wrapped in
  ``jax.checkpoint`` so the backward *re-permutes* KV instead of storing all
  n rotated copies — the classic two-pass ring backward, expressed as remat
  + XLA autodiff rather than a hand-written VJP.
- :func:`ulysses_attention` — two ``all_to_all``\\ s re-shard [b, s/n, H, d]
  → [b, S, H/n, d] so each device runs *full-sequence* attention over a head
  slice (the Pallas flash kernel applies locally), then shards back. Cheaper
  collectives than the ring when heads ≥ ring size; requires H % n == 0.

Both see sequence shards as contiguous blocks in rank order — exactly what
``shard_map`` with ``P(None, "sp", None, None)`` provides.
:func:`make_sp_attention` builds that wrapper over a mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _block_attend(q, k_blk, v_blk, q_start, k_start, *, causal: bool, scale: float):
    """One (q-shard × kv-block) partial: returns (m, l, acc) statistics.

    q: [b, sq, h, d]; k_blk/v_blk: [b, sk, h, d]; positions are global
    offsets of the shards (k_start is traced — it changes per ring step).
    """
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k_blk,
        preferred_element_type=jnp.float32,
    ) * scale
    sq, sk = q.shape[1], k_blk.shape[1]
    if causal:
        q_pos = q_start + jnp.arange(sq)
        k_pos = k_start + jnp.arange(sk)
        allowed = q_pos[:, None] >= k_pos[None, :]  # [sq, sk]
        logits = jnp.where(allowed[None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)  # [b,h,sq]
    p = jnp.exp(logits - m[..., None])
    if causal:
        # Fully-masked rows have m == NEG_INF and exp(0) == 1 artifacts;
        # zero them through the same mask.
        p = jnp.where(allowed[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)  # [b,h,sq]
    acc = jnp.einsum("bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
    return m, l, acc


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
    *,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Blockwise ring attention over sequence shards (call inside shard_map).

    q/k/v: [batch, s_local, heads, head_dim], the ``axis_name`` shard of the
    global sequence in rank order. Returns the local output shard.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    q32 = q.astype(jnp.float32)

    m = jnp.full((b, h, s_loc), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s_loc), jnp.float32)
    acc = jnp.zeros((b, h, s_loc, d), jnp.float32)

    # n is a static mesh-axis size: unroll. Each step re-derives its KV block
    # by rotating the ORIGINAL shard s hops (single ppermute), inside a
    # checkpoint region so the backward re-communicates instead of saving
    # every rotated copy.
    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def step(q32, kv, carry, s):
        m, l, acc = carry
        perm = [(i, (i + s) % n) for i in range(n)]
        k_s = lax.ppermute(kv[0], axis_name, perm)
        v_s = lax.ppermute(kv[1], axis_name, perm)
        src = (idx - s) % n  # whose sequence block arrived
        m_b, l_b, acc_b = _block_attend(
            q32, k_s, v_s, idx * s_loc, src * s_loc, causal=causal, scale=scale
        )
        m_new = jnp.maximum(m, m_b)
        c_old = jnp.exp(m - m_new)
        c_new = jnp.exp(m_b - m_new)
        l_new = l * c_old + l_b * c_new
        acc_new = acc * c_old[..., None] + acc_b * c_new[..., None]
        return m_new, l_new, acc_new

    for s in range(n):
        m, l, acc = step(q32, (k, v), (m, l, acc), s)

    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [b,h,sq,d]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    impl: str = "auto",
) -> jax.Array:
    """Head-parallel attention via all-to-all (call inside shard_map).

    Re-shards [b, s/n, H, d] → [b, S, H/n, d], runs full-sequence attention
    on the local head group (flash kernel on TPU), and shards back.
    """
    from easydl_tpu.ops.attention import multihead_attention

    n = lax.psum(1, axis_name)
    h = q.shape[2]
    if h % n:
        raise ValueError(f"heads={h} not divisible by sp={n}")

    def seq_gather(x):  # [b, s/n, H, d] -> [b, S, H/n, d]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def seq_scatter(x):  # [b, S, H/n, d] -> [b, s/n, H, d]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    out = multihead_attention(
        seq_gather(q), seq_gather(k), seq_gather(v),
        causal=causal, scale=scale, impl=impl,
    )
    return seq_scatter(out)


def make_sp_attention(
    mesh: Mesh,
    kind: str = "ring",
    axis: str = "sp",
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    impl: str = "auto",
):
    """Wrap a sequence-parallel attention as a ``(q, k, v, causal=...)``
    function over GLOBAL [b,S,h,d] arrays.

    Under jit/GSPMD it runs the ring / Ulysses program via shard_map over
    ``mesh[axis]``; batch stays sharded over the dp axes, sequence over
    ``axis``. The ``causal`` argument here is only the *default* — a model
    passes its own flag per call (TransformerConfig.causal), so a
    bidirectional model can never silently inherit causal masking.
    """
    if kind not in ("ring", "ulysses"):
        raise ValueError(f"unknown sp attention kind {kind!r}")

    spec = P(("dp", "fsdp"), axis, None, None)
    n_batch = mesh.shape["dp"] * mesh.shape["fsdp"]
    n_sp = mesh.shape[axis]
    sharded_cache: dict = {}

    def sharded_for(is_causal: bool):
        if is_causal not in sharded_cache:
            if kind == "ring":
                inner = functools.partial(
                    ring_attention, axis_name=axis, causal=is_causal, scale=scale
                )
            else:
                inner = functools.partial(
                    ulysses_attention, axis_name=axis, causal=is_causal,
                    scale=scale, impl=impl,
                )
            sharded_cache[is_causal] = jax.shard_map(
                lambda q, k, v: inner(q, k, v),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
            )
        return sharded_cache[is_causal]

    default_causal = causal

    def dispatch(q, k, v, causal: Optional[bool] = None):
        is_causal = default_causal if causal is None else causal
        if q.shape[0] % n_batch or q.shape[1] % n_sp:
            # The batch-1 trace inside model.init is the one legitimate
            # non-tiling shape (parameter shapes don't depend on activation
            # values) — run it locally. Any other mismatch is a user error;
            # falling back silently would materialise full S×S attention,
            # the exact blow-up SP exists to avoid.
            if q.shape[0] == 1:
                from easydl_tpu.ops.attention import multihead_attention

                return multihead_attention(
                    q, k, v, causal=is_causal, scale=scale, impl="reference"
                )
            raise ValueError(
                f"sp attention: shapes batch={q.shape[0]}, seq={q.shape[1]} "
                f"don't tile over mesh (batch shards={n_batch}, {axis}={n_sp})"
            )
        return sharded_for(is_causal)(q, k, v)

    return dispatch
