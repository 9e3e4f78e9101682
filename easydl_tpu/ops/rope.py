"""Rotary positions (rotate-half pairing) for q and k.

``x cos + rotate_half(x) sin`` with ``rotate_half(x) = [-x2, x1]`` over the
two halves of a head, the angle of dimensions ``i`` and ``i + head_dim / 2``
``pos * theta ** (-2 i / head_dim)``, in float32, rounded back to the
operand's dtype. With the sign folded into the sine table
(``sin_signed = [-sin, sin]``) the rotation is ``x cos + turn(x)
sin_signed``, ``turn`` a circular shift of a head's lanes by half a head.

Two forms of the same arithmetic:

- :func:`apply_rope`, ``jax.numpy`` on ``[batch, seq, heads, head_dim]``:
  the reference path's, and any head size's;
- :func:`rope_rows`, a Pallas kernel on the flash kernels' own view
  ``[batch, seq, heads·head_dim]`` for heads of whole 128-lane tiles: the
  shift is one rotation of a vreg's lanes on the XLU, the arrays are read
  and written once as the rows the projections leave and the kernels take.
  As XLA operations the half-head slices made the compiler lay q and k out
  with the sequence in the lanes and copy them back in front of every
  kernel (four float32 copies a layer application: PERF.md section 6,
  PR 29). A rotation is orthogonal, so the backward pass is the same kernel
  with the sine negated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows (positions) a grid cell takes: [256, 2048] bf16 in and out are 1 MB
#: each, two buffers of each, beside float32 [256, 128] slices
_ROWS = 256


def rope_tables(seq: int, head_dim: int, theta: float):
    """``(cos, sin_signed)``, each float32 ``[seq, head_dim]``, of positions
    ``0..seq-1``. Made once a forward pass and handed to every layer."""
    inv = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                          / head_dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def apply_rope(x: jax.Array, cos: jax.Array, sin_signed: jax.Array):
    """The rotation on ``[batch, seq, heads, head_dim]`` in ``jax.numpy``."""
    with jax.named_scope("rope"):
        x32 = x.astype(jnp.float32)
        turned = jnp.roll(x32, x.shape[-1] // 2, axis=-1)
        return (x32 * cos[None, :, None, :]
                + turned * sin_signed[None, :, None, :]).astype(x.dtype)


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, head_dim: int,
                 negate: bool):
    # x_ref, o_ref: [rows, heads · d]; cos_ref, sin_ref: [rows, d] float32
    cos, sin = cos_ref[...], sin_ref[...]
    if negate:
        sin = -sin
    for g in range(x_ref.shape[1] // head_dim):
        cols = slice(g * head_dim, (g + 1) * head_dim)
        x = x_ref[:, cols].astype(jnp.float32)
        o_ref[:, cols] = (x * cos + pltpu.roll(x, head_dim // 2, 1) * sin
                          ).astype(o_ref.dtype)


def _call(x, cos, sin_signed, head_dim, negate, interpret):
    b, s, width = x.shape
    rows = next(r for r in (_ROWS, 128, 64, 32, 16, 8, s) if s % r == 0)
    mine = pl.BlockSpec((None, rows, width), lambda i, j: (i, j, 0))
    table = pl.BlockSpec((rows, head_dim), lambda i, j: (j, 0))
    return pl.pallas_call(
        functools.partial(_rope_kernel, head_dim=head_dim, negate=negate),
        grid=(b, s // rows),
        in_specs=[mine, table, table],
        out_specs=mine,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="rope_bwd" if negate else "rope_fwd",
    )(x, cos, sin_signed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rope(x, cos, sin_signed, head_dim, interpret):
    return _call(x, cos, sin_signed, head_dim, False, interpret)


def _rope_fwd(x, cos, sin_signed, head_dim, interpret):
    return _call(x, cos, sin_signed, head_dim, False, interpret), (
        cos, sin_signed)


def _rope_bwd(head_dim, interpret, tables, g):
    # the tables are positions, not parameters: no gradient
    return _call(g, *tables, head_dim, True, interpret), None, None


_rope.defvjp(_rope_fwd, _rope_bwd)


def rope_rows(x: jax.Array, cos: jax.Array, sin_signed: jax.Array, *,
              head_dim: int, interpret: bool = False) -> jax.Array:
    """The rotation on ``[batch, seq, heads·head_dim]``, heads of whole
    128-lane tiles (``head_dim % 128 == 0``; the caller asks
    :func:`tiles_lanes`), as a Pallas kernel. ``interpret=True`` runs it in
    the Pallas interpreter — something only a test passes."""
    if not tiles_lanes(head_dim) or x.shape[-1] % head_dim:
        raise ValueError(f"rope_rows: head_dim {head_dim} is not whole "
                         f"128-lane tiles of width {x.shape[-1]}")
    with jax.named_scope("rope"):
        return _rope(x, cos, sin_signed, head_dim, interpret)


def tiles_lanes(head_dim: int) -> bool:
    return head_dim % 128 == 0
