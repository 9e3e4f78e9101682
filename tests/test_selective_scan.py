"""Mamba-1's selective scan (``ops/selective_scan.py``): the ``jax.numpy``
walk against the recurrence position by position on whole and ragged chunks,
its gradients against jax's own of the plain recurrence, and the two Pallas
kernels under their ``custom_vjp``, interpreted, against the walk."""

import logging

import jax
import jax.numpy as jnp
import pytest
from conftest import normal, out_and_grads

from easydl_tpu.ops import selective_scan as ss
from easydl_tpu.utils import logging as easydl_logging


def _operands(seed, batch, seq, heads, view, n=16):
    x, dt, a, B, C, D, w = normal(
        seed, (batch, seq, heads, view), (batch, seq, heads * view),
        (heads * view, n), (batch, seq, n), (batch, seq, n), (heads * view,),
        (batch, seq, heads, view))
    return (x, jax.nn.softplus(dt - 2.0), -jnp.exp(0.5 * a), B, C, D), w


def _recurrence(x, dt, A, B, C, D):
    """Position by position, nothing chunked: the definition."""
    shape = x.shape
    x = x.reshape(*shape[:2], -1)

    def step(h, at):
        x_t, dt_t, b_t, c_t = at
        h = jnp.exp(dt_t[..., None] * A) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], -1)

    h0 = jnp.zeros((shape[0], x.shape[-1], A.shape[-1]))
    _, y = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return (jnp.moveaxis(y, 0, 1) + D * x).reshape(shape)


def _close(mine, want, rel):
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) <= rel * float(
            jnp.max(jnp.abs(b))) + 1e-12


@pytest.mark.parametrize("seq", [256, 200, 12],
                         ids=["whole", "ragged", "one-short-chunk"])
def test_the_walk_against_the_recurrence(seq):
    args, w = _operands(7, 2, seq, 2, 8)
    scalar = lambda y: jnp.sum(y * w)  # noqa: E731
    mine = jax.jit(out_and_grads(ss.selective_scan, scalar))(*args)
    want = jax.jit(out_and_grads(_recurrence, scalar))(*args)
    _close(mine, want, 2e-6)


def test_the_walk_in_chunks_of_another_length():
    """The walk itself on four chunks of 16: its chunk is no part of the
    answer."""
    (x, dt, A, B, C, D), w = _operands(7, 2, 64, 4, 16)
    scalar = lambda y: jnp.sum(y * w)  # noqa: E731
    mine = jax.jit(out_and_grads(
        lambda x, *a: ss._scan_reference(
            x.reshape(2, 64, -1), *a, q=16).reshape(x.shape),
        scalar))(x, dt, A, B, C, D)
    want = jax.jit(out_and_grads(_recurrence, scalar))(x, dt, A, B, C, D)
    _close(mine, want, 2e-6)


def test_the_kernels_against_the_walk():
    """``sscan_fwd`` and ``sscan_bwd`` interpreted on two chunks of 128 and
    one block of 512 channels: y and all six gradients (the state carried
    across the chunks forward, its gradient backward)."""
    args, w = _operands(11, 1, 256, 8, 64)
    scalar = lambda y: jnp.sum(y * w)  # noqa: E731
    mine = jax.jit(out_and_grads(
        lambda *a: ss.selective_scan_kernels(*a, interpret=True),
        scalar))(*args)
    want = jax.jit(out_and_grads(ss.selective_scan, scalar))(*args)
    _close(mine, want, 2e-6)


@pytest.mark.parametrize("shape,said", [
    ((500, 16, 256), "500 channels are no whole blocks of 512"),
    ((512, 12, 256), "12 states are no whole sublane tiles"),
    ((512, 16, 200), "a sequence of 200 is no whole chunks of 128"),
    ((5120, 16, 16384), None),
])
def test_what_the_kernels_tile(shape, said):
    assert ss.untiled(*shape) == said
    if said:
        with pytest.raises(ValueError, match=said):
            ss.selective_scan_kernels(
                jnp.zeros((1, shape[2], shape[0] // 4, 4)),
                jnp.zeros((1, shape[2], shape[0])),
                jnp.zeros((shape[0], shape[1])),
                jnp.zeros((1, shape[2], shape[1])),
                jnp.zeros((1, shape[2], shape[1])), jnp.zeros((shape[0],)))


def test_the_line_says_which_path_and_why(monkeypatch):
    monkeypatch.setattr(easydl_logging, "_logged_once", set())
    records = []
    handler = logging.Handler()
    handler.emit = lambda record: records.append(record.getMessage())
    ss.log.addHandler(handler)
    try:
        args, _ = _operands(3, 1, 200, 2, 8)
        ss.selective_scan(*args)
    finally:
        ss.log.removeHandler(handler)
    line, = [r for r in records if r.startswith("selective_scan:")]
    assert "chunked scan in jax.numpy, not the kernels (no tpu)" in line
    assert "2 chunks of 128 a sequence, 16 channels of 16 states over 200" in line


def test_the_static_counts():
    assert ss.chunks(16384) == 128 and ss.chunks(100) == 1 \
        and ss.chunks(129) == 2
    assert ss.state_bytes_kept(1, 16384, 5120, 16) == 128 * 5120 * 16 * 4
    assert ss.selective_scan_flops_per_token(5120, 16) == 5120 * 115.0
