"""Rotary positions for q and k: rotate-half pairing over the leading
lanes of a head, or interleaved pairing (dimension ``2i`` with ``2i + 1``)
over its leading or its LAST lanes.

``x cos + rotate_half(x) sin`` with ``rotate_half(x) = [-x2, x1]`` over the
two halves of a head, the angle of dimensions ``i`` and ``i + head_dim / 2``
``pos * theta ** (-2 i / head_dim)``, in float32, rounded back to the
operand's dtype. With the sign folded into the sine table
(``sin_signed = [-sin, sin]``) the rotation is ``x cos + turn(x)
sin_signed``, ``turn`` a circular shift of a head's lanes by half a head.

Interleaved pairing (``interleaved``; DeepSeek-V3's ``rope_interleave``):
dimension ``2i`` pairs with ``2i + 1``, the tables repeat each angle twice
(``cos = [c0, c0, c1, c1, ..]``, ``sin_signed = [-s0, s0, -s1, s1, ..]``) and
``turn`` swaps neighbours: an even lane takes the lane after it, an odd one
the lane before. The rotated part may be the LAST ``rot`` lanes of a head
(``last``: latent attention's 64 rotated dimensions behind 128 without
positions); the lanes outside it meet cosine 1 and sine 0 wherever they lie.

Two forms of the same arithmetic:

- :func:`apply_rope`, ``jax.numpy`` on ``[batch, seq, heads, head_dim]``:
  the reference path's, and any head size's;
- :func:`rope_rows`, a Pallas kernel on the flash kernels' own view
  ``[batch, seq, heads·head_dim]`` for heads of whole 128-lane tiles: the
  shift is one rotation of a vreg's lanes on the XLU, the arrays are read
  and written once as the rows the projections leave and the kernels take.
  Under interleaved pairing a head need not be whole tiles: the kernel
  works on units of ``lcm(head_dim, 128)`` lanes (two heads of 192), whose
  neighbours' swap never leaves a head.
  As XLA operations the half-head slices made the compiler lay q and k out
  with the sequence in the lanes and copy them back in front of every
  kernel (four float32 copies a layer application: PERF.md section 6,
  PR 29). A rotation is orthogonal, so the backward pass is the same kernel
  with the sine negated.

The kernel's optional norm (``rope_rows(..., norm=(gain, eps))``, PR 54): a
layer that norms each head of q and k in front of the rotation
(``AttentionKind.qk_norm``) hands the kernel the gain, and the kernel
normalises a head's lanes before it rotates them — :func:`rms_norm`'s
arithmetic on the float32 slice it already holds, one rounding at the end —
so q and k are read once and written once where XLA's norm made passes of
its own at several times their bytes' time. Its calls are ``rope_norm_fwd``
(ONE result) and ``rope_norm_bwd`` (TWO: ``dx`` and the gain's partial sums
a grid cell, which XLA adds): by those counts ``benchmark/lib/hlo.py
flash_calls`` tells them from the flash kernels', whose names end alike.
Without a gain the calls, their names and their text are what they were.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from easydl_tpu.utils.logging import get_logger, log_once

log = get_logger("ops", "rope")

#: rows (positions) a grid cell takes: [256, 2048] bf16 in and out are 1 MB
#: each, two buffers of each, beside float32 [256, 128] slices; fewer where
#: the array is wider, so that a block stays within _BLOCK_BYTES (64 heads
#: of 128: [128, 8192])
_ROWS = 256
_BLOCK_BYTES = 2 << 20


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the last dimension under ``gain``: statistics, quotient
    and gain in float32, rounded to ``x``'s dtype once. The one ``jax.numpy``
    form; :func:`rope_rows` has the same arithmetic inside its kernel."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
            * gain.astype(jnp.float32)).astype(x.dtype)


def yarn_inv_freq(rot: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's blended inverse frequencies over ``rot`` rotated dimensions
    (``rot // 2`` of them), as HF's ``_compute_yarn_parameters`` forms them:
    dimension ``i`` keeps ``f_i = theta ** (-2 i / rot)`` where it turns
    more than ``beta_fast`` times over the ``original`` length, takes ``f_i /
    factor`` where it turns less than ``beta_slow`` times, and a linear
    blend between the two correction dimensions."""
    def correction_dim(turns):
        return rot * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rot - 1)
    if low == high:
        high += 0.001  # as the source has it: no division by zero
    f = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return f / factor * (1.0 - keep) + f * keep


def rope_tables(seq: int, head_dim: int, theta: float,
                rot: Optional[int] = None, yarn: Optional[dict] = None,
                interleaved: bool = False, last: bool = False):
    """``(cos, sin_signed)``, each float32 ``[seq, head_dim]``, of positions
    ``0..seq-1``. Made once a forward pass for each rotary scheme and handed
    to the layers of that scheme.

    ``rot`` (None: ``head_dim``): only the first ``rot`` dimensions of a head
    are rotated, dimension ``i`` pairing with ``i + rot / 2``; the rest pass
    (cosine 1, sine 0). ``yarn``: ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow`` and
    ``attention_factor`` of a YaRN scheme — :func:`yarn_inv_freq`'s
    frequencies, cosine and sine times the attention factor.
    ``interleaved``: dimension ``2i`` pairs with ``2i + 1``; with ``last``
    the rotated dimensions are a head's last ``rot``."""
    rot = rot or head_dim
    if last and not interleaved:
        raise ValueError("rope_tables: a rotated part behind the passed one "
                         "is written for interleaved pairing alone")
    if yarn is None:
        inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    else:
        inv = yarn_inv_freq(rot, theta, yarn["factor"],
                            yarn["original_max_position_embeddings"],
                            yarn["beta_fast"], yarn["beta_slow"])
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        gain = yarn.get("attention_factor") \
            or 0.1 * math.log(yarn["factor"]) + 1.0
        cos, sin = cos * gain, sin * gain
    passed = head_dim - rot
    if interleaved:
        parts = ([jnp.repeat(cos, 2, axis=-1)],
                 [jnp.stack([-sin, sin], -1).reshape(seq, rot)])
        for part, fill in zip(parts, (jnp.ones, jnp.zeros)):
            if passed:
                part.insert(0 if last else 1, fill((seq, passed), jnp.float32))
        return tuple(jnp.concatenate(part, axis=-1) for part in parts)
    return (jnp.concatenate(
                [cos, cos] + [jnp.ones((seq, passed), jnp.float32)] * (passed > 0),
                axis=-1),
            jnp.concatenate(
                [-sin, sin] + [jnp.zeros((seq, passed), jnp.float32)] * (passed > 0),
                axis=-1))


def _turn(x, rot: int, roll, interleaved: bool = False):
    """A head's lanes with each rotated dimension's partner in its place
    (``i`` <-> ``i + rot / 2`` for ``i < rot / 2``; ``interleaved``: ``2i``
    <-> ``2i + 1``, wherever the rotated part lies); what the lanes outside
    the rotated part hold meets a zero sine. ``roll(x, shift)`` turns the
    last axis."""
    d = x.shape[-1]
    if interleaved:
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
        return jnp.where(lane % 2 == 0, roll(x, d - 1), roll(x, 1))
    if rot == d:
        return roll(x, d // 2)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where(lane < rot // 2, roll(x, d - rot // 2),
                     roll(x, rot // 2))


def apply_rope(x: jax.Array, cos: jax.Array, sin_signed: jax.Array,
               rot: Optional[int] = None, interleaved: bool = False):
    """The rotation on ``[batch, seq, heads, head_dim]`` in ``jax.numpy``."""
    with jax.named_scope("rope"):
        x32 = x.astype(jnp.float32)
        turned = _turn(x32, rot or x.shape[-1],
                       lambda a, shift: jnp.roll(a, shift, axis=-1),
                       interleaved)
        return (x32 * cos[None, :, None, :]
                + turned * sin_signed[None, :, None, :]).astype(x.dtype)


def _roll(a, shift):
    return pltpu.roll(a, shift, 1)


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, head_dim: int,
                 negate: bool, rot: int, interleaved: bool = False):
    # x_ref, o_ref: [rows, heads · d]; cos_ref, sin_ref: [rows, d] float32
    # (interleaved: d is a unit of whole heads AND whole lane tiles)
    cos, sin = cos_ref[...], sin_ref[...]
    if negate:
        sin = -sin
    for g in range(x_ref.shape[1] // head_dim):
        cols = slice(g * head_dim, (g + 1) * head_dim)
        x = x_ref[:, cols].astype(jnp.float32)
        turned = _turn(x, rot, _roll, interleaved)
        o_ref[:, cols] = (x * cos + turned * sin).astype(o_ref.dtype)


def _specs(x, head_dim, block_bytes):
    """``(rows a grid cell, x's block, a table's block)``."""
    _, s, width = x.shape
    rows = next(r for r in (_ROWS, 128, 64, 32, 16, 8, s) if s % r == 0
                and (r <= 8 or r * width * x.dtype.itemsize <= block_bytes))
    return (rows, pl.BlockSpec((None, rows, width), lambda i, j: (i, j, 0)),
            pl.BlockSpec((rows, head_dim), lambda i, j: (j, 0)))


def _call(x, cos, sin_signed, head_dim, negate, interpret, rot, interleaved):
    b, s, _ = x.shape
    rows, mine, table = _specs(x, head_dim, _BLOCK_BYTES)
    return pl.pallas_call(
        functools.partial(_rope_kernel, head_dim=head_dim, negate=negate,
                          rot=rot, interleaved=interleaved),
        grid=(b, s // rows),
        in_specs=[mine, table, table],
        out_specs=mine,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="rope_bwd" if negate else "rope_fwd",
    )(x, cos, sin_signed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _rope(x, cos, sin_signed, head_dim, interpret, rot, interleaved):
    return _call(x, cos, sin_signed, head_dim, False, interpret, rot,
                 interleaved)


def _rope_fwd(x, cos, sin_signed, head_dim, interpret, rot, interleaved):
    return _call(x, cos, sin_signed, head_dim, False, interpret, rot,
                 interleaved), (cos, sin_signed)


def _rope_bwd(head_dim, interpret, rot, interleaved, tables, g):
    # the tables are positions, not parameters: no gradient. The partner
    # map is its own inverse and the sine changes sign across a pair, so
    # the transpose is the same kernel with the sine negated, whatever
    # gain the tables carry.
    return _call(g, *tables, head_dim, True, interpret, rot,
                 interleaved), None, None


_rope.defvjp(_rope_fwd, _rope_bwd)


def _inv_rms(x, eps: float):
    # x: [rows, head_dim] float32 -> each row's 1 / rms, [rows, 1]
    return jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


#: heads of the norming kernels' loop body, written out: a body is traced
#: (on the host's clock, in set-up) at the length it is written, and 32 heads
#: written out cost a SDAR run 4.5 s of ``setup_s``; a loop over groups of 8
#: schedules to 7% more bundles than the 32 (PERF.md section 6, PR 54)
_GROUP = 8


def _by_heads(width: int, head_dim: int, one_head, carry):
    """``one_head(cols, carry) -> carry`` over a block's heads, ``cols`` a
    head's lanes: a loop over groups of ``_GROUP`` heads written out."""
    heads = width // head_dim
    group = math.gcd(heads, _GROUP)

    def some(i, carry):
        for g in range(group):
            start = (i * group + g) * head_dim
            if not isinstance(start, int):
                start = pl.multiple_of(start, 128)
            carry = one_head(pl.ds(start, head_dim), carry)
        return carry

    if heads == group:
        return some(0, carry)
    return jax.lax.fori_loop(0, heads // group, some, carry)


def _norm_fwd_kernel(x_ref, gain_ref, cos_ref, sin_ref, o_ref, *,
                     head_dim: int, rot: int, eps: float):
    # as _rope_kernel, a head's lanes normed under gain_ref [1, d] float32
    # (rms_norm's arithmetic) before they are rotated
    def one_head(cols, _):
        x = x_ref[:, cols].astype(jnp.float32)
        n = x * _inv_rms(x, eps) * gain_ref[...]
        o_ref[:, cols] = (n * cos_ref[...] + _turn(n, rot, _roll)
                          * sin_ref[...]).astype(o_ref.dtype)

    _by_heads(x_ref.shape[1], head_dim, one_head, None)


def _norm_bwd_kernel(x_ref, g_ref, gain_ref, cos_ref, sin_ref, dx_ref,
                     dgain_ref, *, head_dim: int, rot: int, eps: float):
    # x_ref: the rows in front of the norm; g_ref: the rotated rows'
    # cotangent; dgain_ref [1, d] float32: this cell's sum over rows and heads
    rows = x_ref.shape[0]
    # vreg-high sums: adds on the VPU, one reduction over sublanes at the end
    fold = 8 if rows % 8 == 0 else rows

    def one_head(cols, acc):
        x = x_ref[:, cols].astype(jnp.float32)
        dy = g_ref[:, cols].astype(jnp.float32)
        # the rotation's transpose, then the norm's: x's unit rows again
        u = dy * cos_ref[...] - _turn(dy, rot, _roll) * sin_ref[...]
        r = _inv_rms(x, eps)
        unit = x * r
        d = u * gain_ref[...]
        dx_ref[:, cols] = (r * (d - unit * jnp.mean(
            d * unit, -1, keepdims=True))).astype(dx_ref.dtype)
        return acc + (u * unit).reshape(rows // fold, fold, head_dim).sum(0)

    acc = _by_heads(x_ref.shape[1], head_dim, one_head,
                    jnp.zeros((fold, head_dim), jnp.float32))
    dgain_ref[...] = acc.sum(0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("head_dim", "rot", "eps",
                                             "interpret"))
def _norm_fwd_call(x, gain, cos, sin_signed, *, head_dim, rot, eps, interpret):
    # a jit of its own: the kernel's body is traced once a shape, not once
    # a use (the pass, remat's, each program)
    b, s, _ = x.shape
    rows, mine, table = _specs(x, head_dim, _BLOCK_BYTES)
    return pl.pallas_call(
        functools.partial(_norm_fwd_kernel, head_dim=head_dim, rot=rot,
                          eps=eps),
        grid=(b, s // rows),
        in_specs=[mine, pl.BlockSpec((1, head_dim), lambda i, j: (0, 0)),
                  table, table],
        out_specs=mine,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="rope_norm_fwd",
    )(x, gain, cos, sin_signed)


@functools.partial(jax.jit, static_argnames=("head_dim", "rot", "eps",
                                             "interpret"))
def _norm_bwd_call(x, g, gain, cos, sin_signed, *, head_dim, rot, eps,
                   interpret):
    # x, g and dx by block, two buffers each: half the forward's rows
    b, s, _ = x.shape
    rows, mine, table = _specs(x, head_dim, _BLOCK_BYTES // 2)
    return pl.pallas_call(
        functools.partial(_norm_bwd_kernel, head_dim=head_dim, rot=rot,
                          eps=eps),
        grid=(b, s // rows),
        in_specs=[mine, mine, pl.BlockSpec((1, head_dim), lambda i, j: (0, 0)),
                  table, table],
        out_specs=[mine, pl.BlockSpec((None, None, 1, head_dim),
                                      lambda i, j: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, s // rows, 1, head_dim),
                                        jnp.float32)],
        interpret=interpret,
        name="rope_norm_bwd",
    )(x, g, gain, cos, sin_signed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _rope_norm(x, gain, cos, sin_signed, head_dim, interpret, rot, eps):
    return _norm_fwd_call(x, gain, cos, sin_signed, head_dim=head_dim,
                          rot=rot, eps=eps, interpret=interpret)


def _rope_norm_fwd(x, gain, cos, sin_signed, head_dim, interpret, rot, eps):
    # x, the rows in front of the norm, is all the backward needs: a
    # rematerialised block makes it again, nothing new is kept
    return _norm_fwd_call(
        x, gain, cos, sin_signed, head_dim=head_dim, rot=rot, eps=eps,
        interpret=interpret), (x, gain, cos, sin_signed)


def _rope_norm_bwd(head_dim, interpret, rot, eps, kept, g):
    x, gain, cos, sin_signed = kept
    dx, sums = _norm_bwd_call(x, g, gain, cos, sin_signed, head_dim=head_dim,
                              rot=rot, eps=eps, interpret=interpret)
    return dx, sums.sum((0, 1)), None, None


_rope_norm.defvjp(_rope_norm_fwd, _rope_norm_bwd)


def rope_rows(x: jax.Array, cos: jax.Array, sin_signed: jax.Array, *,
              head_dim: int, interpret: bool = False,
              rot: Optional[int] = None, interleaved: bool = False,
              norm: Optional[tuple] = None) -> jax.Array:
    """The rotation on ``[batch, seq, heads·head_dim]``, heads of whole
    128-lane tiles (``head_dim % 128 == 0``; the caller asks
    :func:`tiles_lanes`), as a Pallas kernel. ``rot`` (None: ``head_dim``):
    the first ``rot`` lanes of a head are rotated, the rest pass.
    ``interleaved``: the tables' pairing is ``2i`` with ``2i + 1`` (which
    lanes rotate is the tables' matter), and the heads need only fill whole
    units of ``lcm(head_dim, 128)`` lanes: the tables are repeated to a unit.
    ``norm``: None, or ``(gain [head_dim], eps)`` — each head is normed
    (:func:`rms_norm`) in front of its rotation, inside the kernel
    (``rope_norm_fwd`` / ``rope_norm_bwd``); under interleaved pairing, whose
    units are not heads, by :func:`rms_norm` in front of the kernel.
    ``interpret=True`` runs it in the Pallas interpreter — something only a
    test passes."""
    heads, ragged = divmod(x.shape[-1], head_dim)
    if ragged or not tiles_lanes(head_dim, heads, interleaved):
        raise ValueError(f"rope_rows: head_dim {head_dim} is not whole "
                         f"128-lane tiles of width {x.shape[-1]}")
    if norm is not None and interleaved:
        log_once(log, "rope: interleaved pairing's units are not heads: the "
                      "norm in jax.numpy, in front of the kernel")
        with jax.named_scope("qk_rmsnorm"):
            x = rms_norm(x.reshape(*x.shape[:2], heads, head_dim),
                         *norm).reshape(x.shape)
    with jax.named_scope("rope"):
        if not interleaved:
            if norm is not None:
                gain, eps = norm
                return _rope_norm(
                    x, gain.astype(jnp.float32).reshape(1, head_dim), cos,
                    sin_signed, head_dim, interpret, rot or head_dim,
                    float(eps))
            return _rope(x, cos, sin_signed, head_dim, interpret,
                         rot or head_dim, False)
        unit = math.lcm(head_dim, 128)
        cos, sin_signed = (jnp.tile(t, (1, unit // head_dim))
                           for t in (cos, sin_signed))
        return _rope(x, cos, sin_signed, unit, interpret, unit, True)


def tiles_lanes(head_dim: int, heads: int = 1,
                interleaved: bool = False) -> bool:
    """Whether :func:`rope_rows` takes ``heads`` heads of ``head_dim``."""
    if interleaved:
        return heads * head_dim % math.lcm(head_dim, 128) == 0
    return head_dim % 128 == 0
