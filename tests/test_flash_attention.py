"""Flash-attention kernel numerics vs the XLA reference path: the forward,
what ``ops/attention.py`` does where the kernels cannot serve, what a call
logs, and use inside a jitted transformer step. The backward is
``tests/test_flash_backward.py``, the model's layout
``tests/test_flash_layout.py``, the window ``tests/test_flash_window.py``.
Kernels run in Pallas interpreter mode on CPU (same code path the TPU
compiles): every case is ONE jitted program on inputs drawn on the host.
"""

import ast
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import normal

from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.ops import attention as attention_module
from easydl_tpu.ops import flash_attention as flash_module
from easydl_tpu.ops.attention import _reference_attention, multihead_attention
from easydl_tpu.ops.flash_attention import choose_blocks, flash_attention
from easydl_tpu.utils import logging as easydl_logging


def qkv(seed, b=2, s=128, h=4, d=32, dtype="float32"):
    return normal(seed, *[(b, s, h, d)] * 3, dtype=dtype)


# seed, the inputs' shape and dtype, the block, causal, the tolerance: three
# block splits of 128 rows both ways; 96 rows in one block of 96 (no multiple
# of a tile: the min() clamping); bf16 operands
FORWARD = {
    f"{block}-{causal}": (0, {}, block, causal, 2e-5)
    for causal in (False, True) for block in (32, 64, 128)}
FORWARD["uneven-96"] = (2, dict(s=96, d=64), 96, True, 2e-5)
FORWARD["bf16"] = (3, dict(s=64, dtype="bfloat16"), 32, True, 2e-2)


@pytest.mark.parametrize("case", FORWARD)
def test_forward_matches_reference(case):
    seed, shape, block, causal, tol = FORWARD[case]
    q, k, v = qkv(seed, **shape)
    ref = jax.jit(functools.partial(
        _reference_attention, causal=causal, scale=q.shape[-1] ** -0.5))(
            q, k, v)
    out = jax.jit(functools.partial(
        flash_attention, causal=causal, block_q=block, block_k=block,
        interpret=True))(q, k, v)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_untileable_length_falls_back_to_reference():
    """Lengths with no usable block divisor (520: over a block of 512, not a
    multiple of 128) must not assert — ``multihead_attention`` drops to the
    XLA path."""
    q, k, v = qkv(5, s=520, d=16)
    ref = jax.jit(functools.partial(
        _reference_attention, causal=True, scale=16**-0.5))(q, k, v)
    out = jax.jit(functools.partial(
        multihead_attention, causal=True, impl="flash"))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_kernels_raise_on_lengths_they_cannot_tile():
    """The kernel module has no other path: the question is a function, the
    call a ValueError."""
    q, k, v = qkv(5, s=72, d=16)
    assert choose_blocks(72, 72, True, 48, 48) is None
    assert choose_blocks(520, 520, True) is None
    assert choose_blocks(72, 72, True) == ((72, 72),) * 3
    with pytest.raises(ValueError, match="q=72 k=72 have no block divisor"):
        flash_attention(q, k, v, causal=True, block_q=48, block_k=48,
                        interpret=True)
    with pytest.raises(TypeError, match="segment_ids"):
        flash_attention(q, k, v, causal=True, interpret=True,
                        segment_ids=jnp.zeros((2, 72), jnp.int32))


def test_kernel_module_imports_nothing_from_the_module_that_chooses():
    """``ops/attention.py`` imports the kernels; the kernels' module reaches
    back up for nothing (read from its imports, at any depth)."""
    with open(flash_module.__file__) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module or "")
            imported |= {f"{node.module}.{a.name}" for a in node.names}
    assert imported and not {m for m in imported if "attention" in m}, imported
    names = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
    assert "_reference_attention" not in names


@pytest.fixture
def attention_log(monkeypatch):
    """Messages ``ops/attention.py`` logs during the test, with ``log_once``
    forgetting what earlier tests of this process said."""
    monkeypatch.setattr(easydl_logging, "_logged_once", set())
    records = []
    handler = logging.Handler()
    handler.emit = lambda record: records.append(record.getMessage())
    attention_module.log.addHandler(handler)
    yield records
    attention_module.log.removeHandler(handler)


@pytest.mark.parametrize("mesh", [None, "dp=2,tp=2"], ids=["one-device", "mesh"])
@pytest.mark.parametrize("case,reason", [
    ("segment-mask", "segment mask requested"),
    ("untileable", "lengths q=520 k=520 have no block divisor <= 512/512"),
])
def test_each_drop_from_the_kernel_is_logged_once_with_its_reason(
        attention_log, monkeypatch, eight_devices, case, reason, mesh):
    """``impl="flash"`` where the kernels cannot serve: the reference path's
    result, one log line with the reason however often the call is traced,
    and the kernels never called — under a mesh (no per-shard wrap around
    the reference path) and without."""
    def never(*a, **k):
        raise AssertionError("the kernels were called")

    monkeypatch.setattr(attention_module, "flash_attention", never)
    s = 520 if case == "untileable" else 64
    q, k, v = qkv(7, b=4, s=s, d=16)
    segments = None
    if case == "segment-mask":
        segments = jnp.asarray(np.arange(s) // 24, jnp.int32)[None].repeat(4, 0)
    want = jax.jit(functools.partial(
        _reference_attention, causal=True, scale=0.25))(
            q, k, v, segment_ids=segments)
    call = jax.jit(functools.partial(multihead_attention, causal=True,
                                     impl="flash"))
    if mesh is None:
        got = [call(q, k, v, segment_ids=segments) for _ in range(2)]
    else:
        spec = MeshSpec.parse(mesh)
        with jax.set_mesh(build_mesh(spec, devices=eight_devices[:spec.size])):
            got = [call(q, k, v, segment_ids=segments),
                   jax.jit(lambda *a: call(*a, segment_ids=segments))(q, k, v)]
            assert "shard_map" not in str(jax.make_jaxpr(
                lambda *a: call(*a, segment_ids=segments))(q, k, v))
    for out in got:
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
    assert attention_log == [
        f"flash attention: XLA reference path, not the kernel: {reason}"]


@pytest.mark.parametrize("s,window,said", [
    (64, None, "fwd 32/32 unrolled, dq 32/32 unrolled, dkv 32/32 unrolled,"),
    (256, None, "fwd 32/32 looped, bwd 32/32 looped, one kernel,"),
    (256, 80, "fwd 32/32 looped, bwd 32/32 looped, one kernel,"),
    (256, 32, "fwd band 128/32 beside 32, dq band 128/32 beside 32, "
              "dkv band 128/32 beside 32,"),
], ids=["unrolled", "looped", "looped-window", "band"])
def test_the_logged_line_says_how_many_kernels_the_backward_is(
        monkeypatch, s, window, said):
    """The engagement is static: the line a process logs once for a call's
    shape says ``one kernel`` where (and only where) the backward is."""
    monkeypatch.setattr(easydl_logging, "_logged_once", set())
    records = []
    handler = logging.Handler()
    handler.emit = lambda record: records.append(record.getMessage())
    flash_module.log.addHandler(handler)
    try:
        q, k, v = qkv(1, b=1, s=s, h=1, d=32)
        jax.eval_shape(functools.partial(
            flash_attention, causal=True, block_q=32, block_k=32,
            interpret=True, window=window), q, k, v)
    finally:
        flash_module.log.removeHandler(handler)
    line, = records
    assert f"blocks q/k {said} over lengths" in line, line


@pytest.mark.parametrize("heads,d,pairs,head_bytes,cell", [
    (16, 64, 4, 6 * 1024 * 128, 2),   # gpt2-medium: two heads fill 128 lanes
    (25, 64, 4, 6 * 1024 * 128, 2),   # gpt2-xl's shard: 13 cells, the last half outside
    (12, 64, 1, 6 * 128 * 128, 4),    # BERT at 128: one pair a head
    (12, 64, 2, 6 * 256 * 128, 2),    # two pairs a head
    (6, 64, 1, 6 * 512 * 128, 2),     # three tiles: what divides them
    (16, 64, 1, 6 * 2048 * 256, 2),   # VMEM: float32 [2048, 256] x 6 is the limit
    (8, 128, 4, 6 * 1024 * 256, 1),   # head_dim 128: a head is a tile
    (8, 32, 4, 6 * 1024 * 64, 4),     # head_dim 32: four heads a tile
    (2, 16, 1, 6 * 64 * 32, 2),       # narrower than a tile: the whole width
    (5, 80, 4, 6 * 1024 * 160, 5),    # lcm(80, 128) = 640 lanes > 400: the whole width
], ids=["medium", "xl-shard", "bert-128", "two-pairs", "six-heads", "float32",
        "head-dim-128", "head-dim-32", "narrow", "head-dim-80"])
def test_heads_a_grid_cell(heads, d, pairs, head_bytes, cell):
    from easydl_tpu.ops.flash_attention import _cell_heads

    assert _cell_heads(heads, d, pairs, True, head_bytes) == cell
    # looped at run time: the tile's heads, never more
    import math

    assert _cell_heads(heads, d, pairs, False, head_bytes) \
        == min(heads, math.lcm(d, 128) // d)


def test_inside_jitted_train_step(monkeypatch):
    """Flash path composes with jit + grad in a real model step — on a
    2-device mesh, so the kernel runs per shard inside ``jax.shard_map``."""
    import optax

    from easydl_tpu.ops import attention

    # No chip here: the test, not the program, asks for the interpreter.
    monkeypatch.setattr(
        attention, "flash_attention",
        functools.partial(attention.flash_attention, interpret=True))

    from easydl_tpu.core.mesh import MeshSpec
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.registry import get_model

    bundle = get_model("gpt", size="test", seq_len=64, vocab=256, attention_impl="flash")
    trainer = Trainer(
        init_fn=bundle.init_fn,
        loss_fn=bundle.loss_fn,
        optimizer=optax.adam(1e-3),
        config=TrainConfig(global_batch=8),
        mesh_spec=MeshSpec(dp=2),
    )
    state = trainer.init_state()
    batch = next(iter(bundle.make_data(8)))
    state, metrics = trainer.train_step(state, batch)
    assert np.isfinite(jax.device_get(metrics)["loss"])

    # And matches the reference-attention model numerically.
    bundle_ref = get_model(
        "gpt", size="test", seq_len=64, vocab=256, attention_impl="reference"
    )
    trainer_ref = Trainer(
        init_fn=bundle_ref.init_fn,
        loss_fn=bundle_ref.loss_fn,
        optimizer=optax.adam(1e-3),
        config=TrainConfig(global_batch=8),
        mesh_spec=MeshSpec(dp=2),
    )
    state_ref = trainer_ref.init_state()
    batch_ref = next(iter(bundle_ref.make_data(8)))
    _, metrics_ref = trainer_ref.train_step(state_ref, batch_ref)
    np.testing.assert_allclose(
        jax.device_get(metrics)["loss"],
        jax.device_get(metrics_ref)["loss"],
        rtol=1e-3,
    )
