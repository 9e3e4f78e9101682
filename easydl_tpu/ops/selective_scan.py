"""Mamba-1's selective scan (Gu and Dao 2023, arXiv:2312.00752).

The recurrence, per channel ``c`` with a state ``h`` of ``d_state`` numbers:

    h_t[c, n] = exp(dt_t[c] * A[c, n]) * h_{t-1}[c, n] + dt_t[c] * x_t[c] * B_t[n]
    y_t[c]    = sum_n h_t[c, n] * C_t[n] + D[c] * x_t[c]                h_0 = 0

A rate for every channel AND state, a step for every channel: the decay is no
scalar a head as Mamba-2's is (``ops/ssd.py``), so a chunk is no matrix
product and the work is ``d_inner x d_state`` element operations a token, one
``exp`` among them, on the vector and transcendental units. Both paths walk
the sequence in chunks so that no ``[seq, d_inner, d_state]`` array ever
exists (5.4 GB in float32 at 16,384 x 5,120 x 16), and keep for the backward
the operands and each chunk's ENTRY state, ``[batch, chunks, d_state,
d_inner]`` float32 (42 MB a layer at 128 chunks of 128); the states inside a
chunk are made again.

What runs where, chosen from the platform and the shapes alone (the line
``selective_scan: ...`` a process logs once says which, and why):

* **on a TPU, where the shapes tile** (:func:`untiled`: channels in whole
  blocks of :data:`_CHANNELS`, a sequence of whole chunks of 128) **two
  Pallas kernels under one ``jax.custom_vjp``**, ``sscan_fwd`` and
  ``sscan_bwd``, on the grid ``(batch row, block of channels, chunk)``, the
  chunk axis sequential. The state is ``[d_state, channels]`` — the states
  along the sublanes, the channels along the lanes — in a float32 VMEM
  scratch between chunks; a position's ``exp(dt (x) A)`` is made in registers
  and never written. x and y travel TURNED, ``[batch, 1, channels, seq]``,
  the order the convolution's kernels keep x in (``ops/ssd.py``: note C in
  PERF.md), and are turned tile by tile inside the kernels (the XLU); dt is
  ``[batch, seq, channels]`` float32 rows as its product leaves it, B and C
  ``[batch, d_state, seq]``. The backward walks the chunks from the end
  carrying the state's gradient: it makes a chunk's states again from the
  entry state into a VMEM scratch, then walks the chunk's positions back.
  dB and dC come out as one partial sum a block of channels (XLA adds them),
  dA as one a batch row and block. Each call is a ``jax.jit`` of its own
  (``ops/ssd.py _kernel_jit``). Under a mesh whose batch or ``tp`` axes span
  devices the call is per shard (``jax.shard_map``), channels over ``tp``;
* **anywhere else** (the CPU, the ``test`` presets, ragged shapes) **the
  same chunked walk in ``jax.numpy``**: a ``lax.scan`` over the chunks whose
  body (a ``lax.scan`` over positions) is rematerialised, differentiated by
  jax — the reference the kernels are tested against
  (``tests/test_selective_scan.py``).

Precision, the contract of both paths: dt, A, ``exp``, the state, the sums
over the states and every gradient's sum are float32 whatever x, B and C
are; y is rounded once to x's dtype.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from easydl_tpu.ops import platform
from easydl_tpu.ops.attention import HEAD_AXIS
from easydl_tpu.ops.ssd import (_batch_split, _free_axes, _kernel_jit, _made,
                                _turned, _unturned)
from easydl_tpu.utils.logging import get_logger, log_once

log = get_logger("ops", "selective_scan")

#: positions a chunk of the kernels: one lane tile of the turned x and y
_CHUNK = 128
#: channels a grid cell: the state ``[16, 512]`` float32 is 8 vregs of 64
_CHANNELS = 512


def selective_scan_flops_per_token(d_inner: int, d_state: int) -> float:
    """Forward element operations a token and layer, an ``exp`` counted as
    one: per channel and state ``dt * A``, ``exp``, ``a * h``, ``u * B``,
    their sum, ``h * C`` and its sum — seven; per channel ``dt * x``, ``D *
    x`` and its sum — three."""
    return float(d_inner * (7 * d_state + 3))


def chunks(seq: int) -> int:
    """Chunks the scan walks a sequence in (the last may be ragged)."""
    return -(-seq // min(_CHUNK, seq))


def state_bytes_kept(batch: int, seq: int, d_inner: int, d_state: int) -> int:
    """Bytes of the entry states a layer's scan keeps for its backward."""
    return batch * chunks(seq) * d_inner * d_state * 4


# ---------------------------------------------------------------------------
# jax.numpy
# ---------------------------------------------------------------------------


def _scan_reference(x, dt, A, B, C, D, *, q: int):
    """The chunked walk on ``x, dt [batch, seq, channels]``, ``A [channels,
    N]``, ``B, C [batch, seq, N]``, ``D [channels]``; ``seq`` whole chunks of
    ``q``."""
    f32 = jnp.float32
    batch, seq, channels = x.shape
    x32, A = x.astype(f32), A.astype(f32)

    def step(h, at):
        x_t, dt_t, b_t, c_t = at
        h = jnp.exp(dt_t[..., None] * A) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], -1)

    def chunk(h, operands):
        return lax.scan(step, h, operands)

    def chunked(a):  # [batch, seq, w] -> [chunks, q, batch, w]
        return jnp.moveaxis(a.astype(f32), 1, 0).reshape(
            seq // q, q, batch, a.shape[-1])

    h0 = jnp.zeros((batch, channels, A.shape[-1]), f32)
    _, y = lax.scan(jax.checkpoint(chunk), h0,
                    tuple(chunked(a) for a in (x32, dt, B, C)))
    y = jnp.moveaxis(y.reshape(seq, batch, channels), 0, 1)
    return (y + D.astype(f32) * x32).astype(x.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def untiled(channels: int, d_state: int, seq: int):
    """Why the kernels cannot take these shapes, or None: channels in whole
    blocks of :data:`_CHANNELS`, states that are whole sublane tiles, a
    sequence of whole chunks of :data:`_CHUNK`."""
    if channels % _CHANNELS:
        return f"{channels} channels are no whole blocks of {_CHANNELS}"
    if d_state % 8:
        return f"{d_state} states are no whole sublane tiles"
    if seq % _CHUNK:
        return f"a sequence of {seq} is no whole chunks of {_CHUNK}"
    return None


def _states(h, dt_ref, u_ref, a, bt, t: int):
    """The state after position ``t`` of the chunk: ``h [N, channels]``
    decayed by ``exp(dt_t (x) A)`` plus ``u_t (x) B_t`` (``u = dt x``)."""
    decay = jnp.exp(dt_ref[t:t + 1, :] * a)
    return decay * h + u_ref[t:t + 1, :] * bt[:, t:t + 1], decay


def _fwd_kernel(x_ref, dt_ref, a_ref, bt_ref, ct_ref, d_ref, y_ref,
                entry_ref, h_ref, u_ref, rows_ref):
    # x_ref, y_ref: [channels, T] (turned); dt_ref: [T, channels]; a_ref:
    # [N, channels]; bt_ref, ct_ref: [N, T]; d_ref: [1, channels]; entry_ref:
    # [N, channels]; scratch h_ref [N, channels], u_ref, rows_ref [T, channels]
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    entry_ref[...] = h_ref[...]
    x = x_ref[...].astype(jnp.float32).T
    u_ref[...] = dt_ref[...] * x
    a, bt, ct = a_ref[...], bt_ref[...], ct_ref[...]
    h = h_ref[...]
    for t in range(x.shape[0]):
        h, _ = _states(h, dt_ref, u_ref, a, bt, t)
        rows_ref[t:t + 1, :] = jnp.sum(h * ct[:, t:t + 1], axis=0,
                                       keepdims=True)
    h_ref[...] = h
    y_ref[...] = (rows_ref[...] + d_ref[...] * x).T.astype(y_ref.dtype)


def _bwd_kernel(x_ref, dt_ref, a_ref, bt_ref, ct_ref, d_ref, dy_ref,
                entry_ref, dx_ref, ddt_ref, da_ref, dbt_ref, dct_ref,
                g_ref, u_ref, dyt_ref, du_ref, dd_ref, h_all_ref):
    # as the forward's, and: dy_ref, dx_ref [channels, T]; ddt_ref [T,
    # channels]; da_ref [N, channels], one a batch row and block, summed
    # over the chunks here; dbt_ref, dct_ref [N, T], one a block of channels;
    # scratch g_ref [N, channels] (the state's gradient, carried from the
    # chunk behind), u_ref, dyt_ref, du_ref, dd_ref [T, channels], h_all_ref
    # [T, N, channels] (the chunk's states, made again)
    steps = dt_ref.shape[0]
    last = pl.program_id(2) == 0  # the grid walks the chunks from the end

    @pl.when(last)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)
        da_ref[...] = jnp.zeros_like(da_ref)

    x = x_ref[...].astype(jnp.float32).T
    dy = dy_ref[...].astype(jnp.float32).T
    u_ref[...] = dt_ref[...] * x
    dyt_ref[...] = dy
    a, bt, ct = a_ref[...], bt_ref[...], ct_ref[...]
    h = entry_ref[...]
    for t in range(steps):
        h_all_ref[t] = h  # the state position t starts from
        h, _ = _states(h, dt_ref, u_ref, a, bt, t)
    lane = lax.broadcasted_iota(jnp.int32, bt.shape, 1)
    g = g_ref[...]
    da = da_ref[...]
    dbt = jnp.zeros_like(bt)
    dct = jnp.zeros_like(ct)
    for t in reversed(range(steps)):
        before = h_all_ref[t]
        after, decay = _states(before, dt_ref, u_ref, a, bt, t)
        dy_t = dyt_ref[t:t + 1, :]
        dct = jnp.where(lane == t, jnp.sum(after * dy_t, axis=1,
                                           keepdims=True), dct)
        g = g + dy_t * ct[:, t:t + 1]
        dbt = jnp.where(lane == t, jnp.sum(g * u_ref[t:t + 1, :], axis=1,
                                           keepdims=True), dbt)
        du_ref[t:t + 1, :] = jnp.sum(g * bt[:, t:t + 1], axis=0,
                                     keepdims=True)
        # d(dt_t (x) A) = g * h_{t-1} * decay
        rate = g * before * decay
        dd_ref[t:t + 1, :] = jnp.sum(rate * a, axis=0, keepdims=True)
        da = da + rate * dt_ref[t:t + 1, :]
        g = g * decay
    g_ref[...] = g
    da_ref[...] = da
    dbt_ref[...] = dbt
    dct_ref[...] = dct
    du = du_ref[...]
    dx_ref[...] = (du * dt_ref[...] + d_ref[...] * dy).T.astype(dx_ref.dtype)
    ddt_ref[...] = du * x + dd_ref[...]


def _specs(n_chunks: int, n: int, *, reverse: bool):
    """BlockSpecs of a grid cell ``(batch row, block of channels, chunk)``:
    the turned ``[channels, T]`` block of x (y, dy, dx), dt's rows, A's
    block, B's and C's columns, D's row, the chunk's entry state."""
    def at(i):
        return n_chunks - 1 - i if reverse else i

    turned = pl.BlockSpec((None, None, _CHANNELS, _CHUNK),
                          lambda b, j, i: (b, 0, j, at(i)))
    rows = pl.BlockSpec((None, _CHUNK, _CHANNELS),
                        lambda b, j, i: (b, at(i), j))
    rates = pl.BlockSpec((n, _CHANNELS), lambda b, j, i: (0, j))
    cols = pl.BlockSpec((None, n, _CHUNK), lambda b, j, i: (b, 0, at(i)))
    skip = pl.BlockSpec((1, _CHANNELS), lambda b, j, i: (0, j))
    entry = pl.BlockSpec((None, None, n, _CHANNELS),
                         lambda b, j, i: (b, at(i), 0, j))
    return turned, rows, rates, cols, skip, entry


def _params(held: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=held + (16 << 20))


@_kernel_jit
def _fwd(x, dt, a, bt, ct, d, *, interpret: bool):
    batch, _, channels, seq = x.shape
    n, n_chunks = a.shape[0], seq // _CHUNK
    turned, rows, rates, cols, skip, entry = _specs(n_chunks, n,
                                                    reverse=False)
    tile = _CHUNK * _CHANNELS * 4
    return pl.pallas_call(
        _fwd_kernel,
        grid=(batch, channels // _CHANNELS, n_chunks),
        in_specs=[turned, rows, rates, cols, cols, skip],
        out_specs=[turned, entry],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((batch, n_chunks, n, channels),
                                 jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, _CHANNELS), jnp.float32),
                        pltpu.VMEM((_CHUNK, _CHANNELS), jnp.float32),
                        pltpu.VMEM((_CHUNK, _CHANNELS), jnp.float32)],
        compiler_params=_params(8 * tile),
        interpret=interpret,
        name="sscan_fwd",
    )(x, dt, a, bt, ct, d)


@_kernel_jit
def _bwd(x, dt, a, bt, ct, d, dy, entries, *, interpret: bool):
    batch, _, channels, seq = x.shape
    n, n_chunks = a.shape[0], seq // _CHUNK
    blocks = channels // _CHANNELS
    turned, rows, rates, cols, skip, entry = _specs(n_chunks, n, reverse=True)
    tile = _CHUNK * _CHANNELS * 4
    return pl.pallas_call(
        _bwd_kernel,
        grid=(batch, blocks, n_chunks),
        in_specs=[turned, rows, rates, cols, cols, skip, turned, entry],
        out_specs=[
            turned, rows,
            pl.BlockSpec((None, n, _CHANNELS), lambda b, j, i: (b, 0, j)),
            pl.BlockSpec((None, None, n, _CHUNK),
                         lambda b, j, i: (b, j, 0, n_chunks - 1 - i)),
            pl.BlockSpec((None, None, n, _CHUNK),
                         lambda b, j, i: (b, j, 0, n_chunks - 1 - i))],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(dt.shape, jnp.float32),
            jax.ShapeDtypeStruct((batch, n, channels), jnp.float32),
            jax.ShapeDtypeStruct((batch, blocks, n, seq), jnp.float32),
            jax.ShapeDtypeStruct((batch, blocks, n, seq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, _CHANNELS), jnp.float32)] + [
            pltpu.VMEM((_CHUNK, _CHANNELS), jnp.float32)] * 4 + [
            pltpu.VMEM((_CHUNK, n, _CHANNELS), jnp.float32)],
        compiler_params=_params((14 + n) * tile),
        interpret=interpret,
        name="sscan_bwd",
    )(x, dt, a, bt, ct, d, dy, entries)


def _views(x, dt, A, B, C, D):
    """The kernels' views of the operands ``x [batch, seq, H, P]``, ``dt
    [batch, seq, H P]``, ``A [H P, N]``, ``B, C [batch, seq, N]``, ``D [H
    P]``."""
    f32 = jnp.float32
    batch, seq = x.shape[:2]
    (x,) = _made(x)
    return (_turned(x).reshape(batch, 1, -1, seq),
            dt.astype(f32),
            A.astype(f32).T,
            jnp.swapaxes(B.astype(f32), 1, 2),
            jnp.swapaxes(C.astype(f32), 1, 2),
            D.astype(f32).reshape(1, -1))


def _unview(y_t, shape):
    batch, seq, heads, width = shape
    return _unturned(y_t.reshape(batch, heads, width, seq))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan_kernels(x, dt, A, B, C, D, interpret):
    return _scan_kernels_fwd(x, dt, A, B, C, D, interpret)[0]


def _scan_kernels_fwd(x, dt, A, B, C, D, interpret):
    y, entries = _fwd(*_views(x, dt, A, B, C, D), interpret=interpret)
    return _unview(y, x.shape), (x, dt, A, B, C, D, entries)


def _scan_kernels_bwd(interpret, res, dy):
    x, dt, A, B, C, D, entries = res
    f32 = jnp.float32
    batch, seq = x.shape[:2]
    (dy,) = _made(dy)
    dx, ddt, da, dbt, dct = _bwd(
        *_views(x, dt, A, B, C, D), _turned(dy).reshape(batch, 1, -1, seq),
        entries, interpret=interpret)
    # the skip weight's gradient is a plain sum, XLA's
    dskip = jnp.sum(dy.astype(f32) * x.astype(f32), axis=(0, 1))
    dskip = dskip.reshape(-1)
    return (_unview(dx, x.shape), ddt.astype(dt.dtype),
            jnp.sum(da, 0).T.astype(A.dtype),
            jnp.swapaxes(jnp.sum(dbt, 1), 1, 2).astype(B.dtype),
            jnp.swapaxes(jnp.sum(dct, 1), 1, 2).astype(C.dtype),
            dskip.astype(D.dtype))


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def _channel_split(heads: int):
    """``(ways, axis)``: how a call under the context mesh splits its
    channels — the leading dimension of the ``[heads, view]`` pair over
    ``tp`` where it divides into whole blocks, else every shard computes all
    of them."""
    mesh, free = _free_axes()
    if HEAD_AXIS in free and heads % mesh.shape[HEAD_AXIS] == 0:
        return mesh.shape[HEAD_AXIS], HEAD_AXIS
    return 1, None


def _per_shard(fn, x):
    """Wrap ``fn(x, dt, A, B, C, D)`` in ``jax.shard_map`` over the context
    mesh where it spans more than one device, as ``ops/ssd.py _per_shard``
    wraps the SSD kernels: batch over the mesh's batch axes, channels over
    ``tp``, B and C whole."""
    _, free, batch = _batch_split(x.shape[0])
    if not free:
        return fn
    _, over = _channel_split(x.shape[2])
    rows, chan = P(batch or None, None, over), P(over)
    whole = P(batch or None)
    # x's channels are its leading `heads`, dt's, A's and D's the flat ones:
    # the same contiguous split
    return jax.shard_map(fn, in_specs=(rows, rows, chan, whole, whole, chan),
                         out_specs=rows, check_vma=False)


def selective_scan_kernels(x, dt, A, B, C, D, *,
                           interpret: bool = False) -> jax.Array:
    """:func:`selective_scan` by the Pallas kernels whatever the platform,
    on shapes that tile (:func:`untiled`). ``interpret=True`` runs them in
    the Pallas interpreter — something only a test passes."""
    why = untiled(math.prod(x.shape[2:]), B.shape[-1], x.shape[1])
    if why:
        raise ValueError(f"the selective scan's kernels: {why}")

    def kernels(x, dt, A, B, C, D):
        return _scan_kernels(x, dt, A, B, C, D, interpret)

    return _per_shard(kernels, x)(x, dt, A, B, C, D)


def selective_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                   C: jax.Array, D: jax.Array) -> jax.Array:
    """The scan above over whole sequences from a zero state: by the Pallas
    kernels on a TPU where the shapes tile, else by the ``jax.numpy`` walk in
    chunks of :data:`_CHUNK` too (a shorter sequence is one chunk; one that
    is no multiple of it is padded at its end with ``dt = 0`` — no decay, no
    input — and the result cut back). Logs once which path a shape took.

    Args:
      x: ``[batch, seq, heads, view]``, the compute dtype: ``heads x view``
        channels (the convolution's shape).
      dt: ``[batch, seq, heads x view]`` float32 step sizes, already
        positive (softplus): rows by channel, as their product leaves them.
      A: ``[heads x view, d_state]``, negative.
      B, C: ``[batch, seq, d_state]``.
      D: ``[heads x view]`` skip weight.

    Returns ``y`` of ``x``'s shape and dtype.
    """
    batch, seq, heads, view = x.shape
    d_state = B.shape[-1]
    ways, _ = _channel_split(heads)
    why = ("no tpu" if not platform.on_tpu()
           else untiled(heads * view // ways, d_state, seq))
    said = (f"{heads * view} channels of {d_state} states over {seq} "
            f"positions, x {x.dtype.name}, steps, rates and state float32")
    if why is None:
        log_once(log, f"selective_scan: Pallas kernels sscan_fwd / sscan_bwd, "
                      f"{chunks(seq)} chunks of {_CHUNK} a sequence, {said}; a "
                      f"grid cell is one chunk of {_CHANNELS} channels, the "
                      f"state carried in VMEM, every chunk's entry state kept "
                      f"for the backward")
        return selective_scan_kernels(x, dt, A, B, C, D)
    q = min(_CHUNK, seq)
    pad = -seq % q
    log_once(log, f"selective_scan: chunked scan in jax.numpy, not the "
                  f"kernels ({why}), {chunks(seq)} chunks of {q} a "
                  f"sequence, {said}, differentiated by jax (body "
                  f"rematerialised)")
    flat = [x.reshape(batch, seq, -1), dt, B, C]
    if pad:
        flat = [jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in flat]
    y = _scan_reference(*flat[:2], A, *flat[2:], D, q=q)
    return y[:, :seq].reshape(x.shape)
