"""Multi-head attention with swappable implementations.

``impl="auto"`` picks the Pallas flash kernel on TPU (large HBM win: the
[B,H,S,S] score matrix never materialises) and the XLA reference path
elsewhere; models call :func:`multihead_attention` and never care which runs.
Which path runs is decided HERE and nowhere else (``ops/flash_attention.py``
holds kernels only), in one order: the platform (``impl="auto"``), a segment
mask, lengths the kernels can tile. Which one ran is never a guess: the
choice, and every drop from the kernel to the reference, is logged once per
process with its reason.

Under a mesh of more than one device the kernel runs per shard: Mosaic
kernels cannot be partitioned by GSPMD, so :func:`multihead_attention`
wraps the call in ``jax.shard_map`` over the context mesh (the one
``Trainer`` enters with ``jax.set_mesh``) — batch over ``dp``/``fsdp``,
heads over ``tp``. What crosses the wrap is the kernels' own view,
``[batch, seq, heads·head_dim]`` (heads over ``tp`` are contiguous lane
ranges of it): a four-dimensional array at that boundary keeps the
compiler from seeing the projections' products and the kernels' operands
as the same rows, and it puts a copy in front of every kernel.

Public shapes follow the [batch, seq, heads, head_dim] convention
throughout; the kernels run on the ``[batch, seq, heads·head_dim]`` view of
the same bytes (``ops/flash_attention.py``).

``v`` may carry a head size of its own (latent attention: scores 192 deep,
values 128 wide); the result has ``v``'s. The kernels take both sizes
(``ops/flash_attention.py``), the reference path's two products likewise.

Grouped-query attention: ``k`` and ``v`` may carry fewer heads than ``q``;
query head ``h`` then reads key/value head ``h // (heads / kv_heads)``. The
kernels take k and v at the key/value heads and read a shared head by its
index (``ops/flash_attention.py _shared``; per shard under a mesh, where a
shard's ratio is the whole's), and their rule sums a group's dk and dv. The
reference path repeats the shared heads to the query's count in front of
its score product and the repeat's transpose sums their gradients.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from easydl_tpu.core.mesh_shapes import BATCH_AXES
from easydl_tpu.ops import index, platform, remat
from easydl_tpu.ops.flash_attention import (
    MAX_BLOCK,
    BlockDiffusion,
    choose_blocks,
    flash_attention,
)
from easydl_tpu.ops.rope import apply_rope, rms_norm, rope_rows, tiles_lanes
from easydl_tpu.utils.logging import get_logger, log_once

log = get_logger("ops", "attention")

#: mesh axis the heads dimension is sharded over (core/sharding.py rules).
HEAD_AXIS = "tp"


def _reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    scale: float,
    segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
    mask: Optional[BlockDiffusion] = None,
    chosen: Optional[jax.Array] = None,
) -> jax.Array:
    """XLA-fused reference path: einsum → mask → softmax → einsum.

    fp32 softmax accumulation regardless of input dtype (bf16-safe).
    ``window`` (causal only) as the kernels have it: query i sees the
    ``window`` keys up to its own. ``mask``: block diffusion's, written out
    (``BlockDiffusion.dense``), as the kernels have it. ``chosen``: a
    learned index's selection written out, ``[batch, queries, keys]`` bool
    (``ops/index.py unpack``), which is causal already.
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    fully_masked = None
    if chosen is not None:
        logits = jnp.where(chosen[:, None], logits,
                           jnp.finfo(jnp.float32).min)
    if mask is not None:
        logits = jnp.where(mask.dense()[None, None], logits,
                           jnp.finfo(jnp.float32).min)
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), jnp.bool_), k=s_k - s_q)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((s_q, s_k), jnp.bool_),
                              k=s_k - s_q - window)
        logits = jnp.where(mask[None, None], logits, jnp.finfo(jnp.float32).min)
        # Bottom-right alignment with s_q > s_k leaves the first s_q - s_k
        # rows with no visible keys; the flash kernel outputs zeros for such
        # rows (its normaliser clamps to ~0), so zero them here too instead
        # of softmax's uniform mean of V — both paths must agree.
        fully_masked = ~mask.any(axis=-1)  # [s_q]
    if segment_ids is not None:
        # segment_ids: [batch, seq] -> mask [batch, 1, q, k]
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        logits = jnp.where(seg_mask[:, None], logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    if fully_masked is not None:
        out = jnp.where(fully_masked[None, :, None, None], 0.0, out)
    return out


def _repeat_kv(q: jax.Array, k: jax.Array, v: jax.Array):
    """``k, v`` with each head repeated up to ``q``'s head count: the
    reference path's alone (the kernels read a shared head by its index)."""
    heads, kv_heads = q.shape[2], k.shape[2]
    if heads == kv_heads:
        return k, v
    if heads % kv_heads or v.shape[2] != kv_heads:
        raise ValueError(f"{heads} query heads over {kv_heads} / "
                         f"{v.shape[2]} key / value heads")
    rep = heads // kv_heads
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


def _per_shard(fn, q: jax.Array, k: jax.Array, whole: int = 0,
               views: int = 3):
    """Wrap ``fn``, a function of q, k, v as ``[batch, seq, heads·head_dim]``
    views (``views`` of them: one where a lone array is rotated) and of
    ``whole`` further arrays every shard takes whole (the
    rotary tables), in ``jax.shard_map`` over the context mesh when that
    mesh spans more than one device, else return it unchanged. ``q`` and
    ``k`` are the ``[batch, seq, heads, head_dim]`` arrays, for their sizes.

    Batch is split over the mesh's batch axes and heads over ``tp`` where
    the sizes divide (the query's heads AND the key/value heads, which may
    be fewer); an axis that does not divide (the batch-1 trace inside
    ``model.init``) is left out of the specs, so those devices compute the
    whole of it — still the kernel, still no GSPMD partitioning of it. Axes
    that are already manual (a caller's own ``shard_map``: the pipeline,
    Ulysses) are per shard already."""
    mesh = jax.sharding.get_abstract_mesh()
    free = [a for a in mesh.axis_names
            if a not in mesh.manual_axes and mesh.shape[a] > 1]
    if not free:
        return fn
    batch = tuple(a for a in BATCH_AXES if a in free)
    heads = HEAD_AXIS if HEAD_AXIS in free else None
    if batch and q.shape[0] % math.prod(mesh.shape[a] for a in batch):
        if q.shape[0] > 1:
            log_once(
                log,
                f"attention: batch {q.shape[0]} does not divide over mesh "
                f"axes {batch}; every shard computes the whole batch")
        batch = ()
    if heads and (q.shape[2] % mesh.shape[heads]
                  or k.shape[2] % mesh.shape[heads]):
        log_once(
            log,
            f"attention: {q.shape[2]} / {k.shape[2]} heads do not divide "
            f"over {heads}={mesh.shape[heads]}; every shard computes all "
            f"heads")
        heads = None
    spec = P(batch or None, None, heads)
    return jax.shard_map(fn, in_specs=(spec,) * views + (P(),) * whole,
                         out_specs=spec, check_vma=False)


def _on_kernels(impl: str) -> bool:
    """Whether ``impl`` asks for the Pallas kernels: said so, or ``auto``
    on a TPU."""
    return impl == "flash" or (impl == "auto" and platform.on_tpu())


def rotate_heads(x: jax.Array, rope: tuple, *,
                 rotary_dim: Optional[int] = None, interleaved: bool = False,
                 impl: str = "auto") -> jax.Array:
    """``x [batch, seq, heads, head_dim]`` rotated by the tables of
    ``ops/rope.py rope_tables``, for a caller that rotates q and k apart
    (latent attention: every head's q, ONE key vector a token) and hands
    :func:`multihead_attention` no tables: by the Pallas kernel on the flash
    kernels' own view, per shard under a mesh, where ``impl`` asks for the
    kernels and the heads fill whole lane tiles; else in ``jax.numpy``.
    Both are the same arithmetic."""
    head_dim = x.shape[-1]
    if not (_on_kernels(impl)
            and tiles_lanes(head_dim, x.shape[2], interleaved)):
        return apply_rope(x, *rope, rot=rotary_dim, interleaved=interleaved)

    def kernel(x, *tables):
        return rope_rows(x, *tables, head_dim=head_dim, rot=rotary_dim,
                         interleaved=interleaved)

    return _per_shard(kernel, x, x, len(rope), views=1)(
        x.reshape(*x.shape[:2], -1), *rope).reshape(x.shape)


def norm_heads(q: jax.Array, k: jax.Array, qk_norm: tuple):
    """``q`` and ``k`` with every head normed under ``qk_norm = (q's gain,
    k's gain, eps)``, in ``jax.numpy`` (``ops/rope.py rms_norm``) under the
    scope ``qk_rmsnorm``: what :func:`multihead_attention` does wherever the
    rotary kernel does not."""
    q_gain, k_gain, eps = qk_norm
    with jax.named_scope("qk_rmsnorm"):
        return rms_norm(q, q_gain, eps), rms_norm(k, k_gain, eps)


@functools.partial(
    jax.named_call, name="multihead_attention"
)
def multihead_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    impl: str = "auto",
    segment_ids: Optional[jax.Array] = None,
    rope: Optional[tuple] = None,
    rotary_dim: Optional[int] = None,
    window: Optional[int] = None,
    mask: Optional[BlockDiffusion] = None,
    qk_norm: Optional[tuple] = None,
) -> jax.Array:
    """Attention over [batch, seq, heads, head_dim] tensors (``v``'s head
    size may be its own, and is the result's).

    Args:
      impl: "auto" | "flash" (Pallas, TPU) | "reference" (XLA einsum).
      rope: None, or the tables of ``ops/rope.py rope_tables``: q and k are
        rotated by position first — beside the flash kernels by the Pallas
        kernel on their own view where a head is whole lane tiles
        (``rope_rows``), else in ``jax.numpy``.
      rotary_dim: the leading dimensions of a head the tables rotate (None:
        all of them).
      window: with ``causal``, query i sees only the ``window`` keys up to
        its own; both paths take the same window.
      mask: without ``causal``, ``ops/flash_attention.py BlockDiffusion``:
        the rows are a sequence's ``[noised || clean]`` halves; both paths
        take the same mask, and ``rope``'s tables carry each half's
        positions.
      qk_norm: None, or ``(q's gain, k's gain, eps)``, each gain
        ``[head_dim]``: every head of q and of k is normed
        (``ops/rope.py rms_norm``) in front of its rotation — inside the
        rotary kernel where that kernel rotates them, else in ``jax.numpy``
        under the scope ``qk_rmsnorm``; the log says which, once.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if window is not None and not causal:
        raise ValueError("attention: a window needs causal=True")
    if mask is not None and (causal or window is not None or segment_ids
                             is not None or q.shape[1] != 2 * mask.seam
                             or k.shape[1] != 2 * mask.seam):
        raise ValueError(
            f"attention: {mask} stands alone over 2 x {mask.seam} rows of q "
            f"and k; got causal={causal}, window={window}, segment_ids "
            f"{'given' if segment_ids is not None else 'None'}, "
            f"{q.shape[1]} / {k.shape[1]} rows (multihead_attention refuses "
            f"it)")
    banded = "" if window is None else f", window {window}"
    if mask is not None:
        banded = f", {mask}"
    rotary_dim = rotary_dim or q.shape[-1]

    def normed(q, k, why):
        # the norm as XLA's operations, wherever the rotary kernel is not
        # what rotates q and k
        if qk_norm is None:
            return q, k
        log_once(log, f"qk norm: jax.numpy, not the rotary kernel: {why}")
        return norm_heads(q, k, qk_norm)

    if impl == "auto":
        impl = "flash" if platform.on_tpu() else "reference"
        if impl == "reference":
            log_once(
                log,
                f"attention: XLA reference path (impl=auto on platform "
                f"{jax.devices()[0].platform!r}, the Pallas flash kernel "
                f"needs a tpu{banded})")
    if impl == "flash":
        why = None
        if segment_ids is not None:
            why = "segment mask requested"
        elif choose_blocks(q.shape[1], k.shape[1], causal,
                           window=window, mask=mask) is None:
            # the wrap below never splits the sequence: asked once, here
            why = (f"lengths q={q.shape[1]} k={k.shape[1]} have no block "
                   f"divisor <= {MAX_BLOCK}/{MAX_BLOCK}")
        if why is None:
            head_dim, value_dim = q.shape[-1], v.shape[-1]
            # rotated beside the kernels, on their own view, where a head
            # is whole lane tiles; else here, in jax.numpy
            tables = rope if rope is not None and tiles_lanes(head_dim) else ()
            gains = qk_norm[:2] if tables and qk_norm is not None else ()
            if gains:
                log_once(log, f"qk norm: inside the rotary kernel "
                              f"(rope_norm_fwd / rope_norm_bwd), heads of "
                              f"{head_dim}, eps {qk_norm[2]}")
            else:
                q, k = normed(q, k, "no rotary tables" if rope is None else
                              f"a head of {head_dim} is not whole lane tiles")
            if rope is not None and not tables:
                q, k = (apply_rope(x, *rope, rot=rotary_dim) for x in (q, k))

            def flat(x):
                return x.reshape(*x.shape[:2], -1)

            def kernel(q, k, v, *whole):
                if tables:
                    # the gains ride behind the two tables, whole on every
                    # shard
                    norms = [(gain, qk_norm[2]) for gain in whole[2:]] \
                        or (None, None)
                    q, k = (rope_rows(x, *whole[:2], head_dim=head_dim,
                                      rot=rotary_dim, norm=norm)
                            for x, norm in zip((q, k), norms))
                q, k, v = (x.reshape(*x.shape[:2], -1, dim) for x, dim in (
                    (q, head_dim), (k, head_dim), (v, value_dim)))
                # k and v at the heads the projections made: the kernels
                # read a shared head by its index
                return flat(flash_attention(q, k, v, causal=causal,
                                            scale=scale, window=window,
                                            mask=mask))

            return _per_shard(kernel, q, k, len(tables) + len(gains))(
                flat(q), flat(k), flat(v), *tables, *gains).reshape(
                    *q.shape[:3], value_dim)
        # the reference path partitions under GSPMD: no per-shard wrap
        log_once(log, f"flash attention: XLA reference path, not the "
                      f"kernel: {why}{banded}")
    elif impl != "reference":
        raise ValueError(f"unknown attention impl {impl!r}")
    q, k = normed(q, k, "the XLA reference path")
    if rope is not None:
        q, k = (apply_rope(x, *rope, rot=rotary_dim) for x in (q, k))
    return _reference_attention(
        q, *_repeat_kv(q, k, v), causal=causal, scale=scale,
        segment_ids=segment_ids, window=window, mask=mask
    )


def indexed_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    a: jax.Array,
    b: jax.Array,
    w: jax.Array,
    *,
    topk: int,
    scale: Optional[float] = None,
    impl: str = "auto",
    rope: Optional[tuple] = None,
    qk_norm: Optional[tuple] = None,
    chunk: int = 512,
    interpret: bool = False,
):
    """Causal attention in which query ``t`` attends to the ``min(t + 1,
    topk)`` keys a learned index ranks highest (``ops/index.py``), and the
    index's own loss. ``q [batch, seq, heads, head_dim]``, ``k``, ``v`` (at
    the key/value heads) as the projections made them; ``rope`` and
    ``qk_norm`` as :func:`multihead_attention` takes them. ``a [batch, seq,
    index heads, dim]``, ``b [batch, seq, dim]``, ``w [batch, seq, index
    heads]``: the index's queries, its one key a token and its head weights,
    rotated and scaled by the caller, made from a DETACHED input.

    Returns ``(out, loss, stats)``: the attention's result; ``mean_t KL(p_t
    || softmax_{S_t} I_t)`` with ``p`` the attention's probabilities, the
    mean of its heads, detached — the loss moves ``a``, ``b`` and ``w`` alone,
    and nothing of ``out`` moves them; ``stats``: ``live_tiles`` (the tiles
    of ``index.TILE`` squared that hold a selected pair) and ``score_squares``
    (the causal scores' sum of squares), float32 scalars over the batch, and
    ``words``, the packed selection itself (``ops/index.py``).

    The path is chosen as :func:`multihead_attention` chooses it: the Pallas
    kernels (``index_select``, ``dsa_fwd`` / ``dsa_bwd``, ``index_kl``; no
    ``[seq, seq]`` array in HBM) where ``impl`` asks for them, else XLA's
    operations under the selection WRITTEN OUT — the same packed words made,
    kept and unpacked. The selection is named for remat on both
    (``ops/remat.py SELECTED``). ``interpret``: the kernels in the Pallas
    interpreter, which only a test passes."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    seq, head_dim = q.shape[1], q.shape[-1]
    kernels = _on_kernels(impl)
    if not kernels and impl not in ("auto", "reference"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if kernels and choose_blocks(seq, seq, True) is None:
        log_once(log, f"indexed attention: XLA reference path, not the "
                      f"kernels: length {seq} has no block divisor <= "
                      f"{MAX_BLOCK}")
        kernels = False
    if kernels:
        mesh = jax.sharding.get_abstract_mesh()
        if any(mesh.shape[a] > 1 for a in mesh.axis_names
               if a not in mesh.manual_axes):
            raise NotImplementedError(
                "indexed attention: the kernels under a mesh of more than "
                "one device (indexed_attention refuses it)")
    else:
        log_once(log, f"indexed attention: XLA reference path under the "
                      f"selection written out (impl={impl!r} on platform "
                      f"{jax.devices()[0].platform!r}), top-{topk} of {seq}")
    if kernels and rope is not None and tiles_lanes(head_dim):
        norms = [(gain, qk_norm[2]) for gain in qk_norm[:2]] \
            if qk_norm is not None else (None, None)
        q, k = (rope_rows(x.reshape(*x.shape[:2], -1), *rope,
                          head_dim=head_dim, norm=norm,
                          interpret=interpret).reshape(x.shape)
                for x, norm in zip((q, k), norms))
    else:
        if qk_norm is not None:
            q, k = norm_heads(q, k, qk_norm)
        if rope is not None:
            q, k = (apply_rope(x, *rope) for x in (q, k))
    with jax.named_scope("index"), jax.named_scope("index_topk"):
        words, lse_i, squares = index.select(
            a, b, w, topk=topk, kernels=kernels, chunk=chunk,
            interpret=interpret)
        words, lse_i = (remat.name(x, remat.SELECTED)
                        for x in (words, lse_i))
    if kernels:
        out, lse = flash_attention(
            q, k, v, causal=True, scale=scale, select=words,
            interpret=interpret)
    else:
        lse = None
        out = _reference_attention(q, *_repeat_kv(q, k, v), causal=False,
                                   scale=scale, chosen=index.unpack(words))
    with jax.named_scope("index_loss"):
        loss = index.kl(a, b, w, q, k, lse, words, lse_i, scale=scale,
                        kernels=kernels, chunk=chunk, interpret=interpret)
    with jax.named_scope("index"):
        stats = {"live_tiles": index.live_tiles(words),
                 "score_squares": jnp.sum(squares), "words": words}
    return out, loss, stats
