"""Flash-attention kernel numerics vs the XLA reference path: the forward,
what ``ops/attention.py`` does where the kernels cannot serve, what a call
logs, and use inside a jitted transformer step. The backward is
``tests/test_flash_backward.py``, the model's layout
``tests/test_flash_layout.py``, the window ``tests/test_flash_window.py``.
Kernels run in Pallas interpreter mode on CPU (same code path the TPU
compiles): every case is ONE jitted program on inputs drawn on the host.
"""

import ast
import contextlib
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


def _float32_out_and_lse(q, k, v, causal):
    """``out`` and ``lse`` of ``[B, S, H, d]`` operands by whole score
    matrices in float32 ``jax.numpy``, the mask bottom-right aligned; a row
    that sees no key: zero output, ``lse`` the kernels' poison."""
    q, k, v = (jnp.asarray(x, jnp.float32) for x in (q, k, v))
    s_q, s_k = q.shape[1], k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") * q.shape[-1] ** -0.5
    ahead = jnp.arange(s_k)[None, :] - jnp.arange(s_q)[:, None] - (s_k - s_q)
    seen = ahead <= 0 if causal else jnp.ones((s_q, s_k), bool)
    live = seen.any(-1)[None, None, :, None]
    scores = jnp.where(seen, scores, -jnp.inf)
    lse = jax.nn.logsumexp(jnp.where(live, scores, 0.0), axis=-1,
                           keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(scores - lse), v,
                     precision="highest")
    return (jnp.where(live.transpose(0, 2, 1, 3), out, 0.0),
            jnp.where(live, lse, -flash_module.NEG_INF))


# the looped forward, more than 16 block pairs a head, its pair walked in
# tiles of 128 (blocks of 256: four tiles, two behind; 512: sixteen, eight
# behind): s_q, s_k, heads, d, dv, block_q, block_k (None: the call's own),
# causal, dtype, tolerance
LOOPED_FORWARD = {
    # pairs under the diagonal whole, the pair it crosses placed statically
    "128-blocks-256": (1280, 1280, 1, 128, 128, 256, 256, True, "float32", 2e-5),
    "default-512-bf16": (2560, 2560, 1, 128, 128, None, None, True, "bfloat16", 2e-2),
    "192-128-two-heads": (1280, 1280, 2, 192, 128, 256, 256, True, "float32", 2e-5),
    "192-128-bf16": (1280, 1280, 2, 192, 128, 256, 256, True, "bfloat16", 2e-2),
    "no-mask": (1280, 1280, 1, 128, 128, 256, 256, False, "float32", 2e-5),
    # bottom-right aligned: 512 rows see no key; every row sees 768 keys more
    "more-queries-than-keys": (1536, 1024, 1, 128, 128, 256, 256, True, "float32", 2e-5),
    "more-keys-than-queries": (768, 1536, 1, 128, 128, 256, 256, True, "float32", 2e-5),
    # the diagonal at no static place: every tile of a crossed pair masked
    # under its own traced edge
    "uneven-blocks": (1152, 1280, 1, 128, 128, 128, 256, True, "float32", 2e-5),
    # two heads of 64 a cell, tiles of both
    "two-heads-of-64": (1280, 1280, 2, 64, 64, 256, 256, True, "bfloat16", 2e-2),
}


@pytest.mark.parametrize("case", LOOPED_FORWARD)
def test_the_looped_forwards_out_and_lse_against_float32(case):
    (s_q, s_k, heads, d, dv, block_q, block_k, causal, dtype,
     tol) = LOOPED_FORWARD[case]
    q, k, v = normal(7, (1, s_q, heads, d), (1, s_k, heads, d),
                     (1, s_k, heads, dv), dtype=dtype)
    blocks = choose_blocks(s_q, s_k, causal, block_q, block_k)[0]
    assert flash_module._fwd_tiles(*blocks, 1)[:2] == (128, 128)

    def forward(q, k, v):
        out, lse = flash_module._fwd(
            q.reshape(1, s_q, heads * d), k.reshape(1, s_k, heads * d),
            v.reshape(1, s_k, heads * dv), heads=heads, causal=causal,
            scale=d ** -0.5, block_q=blocks[0], block_k=blocks[1],
            interpret=True)
        return out.reshape(1, s_q, heads, dv), lse

    (out, lse), (want, want_lse) = jax.jit(lambda q, k, v: (
        forward(q, k, v), _float32_out_and_lse(q, k, v, causal)))(q, k, v)
    assert out.dtype == q.dtype and lse.dtype == jnp.float32
    assert lse.shape == (1, heads, s_q, 1)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=tol, rtol=tol)
    # lse: float32 whatever the operands are; bf16 operands round the
    # reference's scores too
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               atol=tol, rtol=1e-5)
    if s_q > s_k:  # the rows before the first key: nothing, and the poison
        dead = s_q - s_k
        assert not np.asarray(out[:, :dead], np.float32).any()
        assert (np.asarray(lse[:, :, :dead]) == -flash_module.NEG_INF).all()


@contextlib.contextmanager
def _flash_log(monkeypatch):
    """What ``ops/flash_attention.py`` logs inside the block, the lines a
    process logs once logged anew."""
    monkeypatch.setattr(easydl_logging, "_logged_once", set())
    records = []
    handler = logging.Handler()
    handler.emit = lambda record: records.append(record.getMessage())
    flash_module.log.addHandler(handler)
    try:
        yield records
    finally:
        flash_module.log.removeHandler(handler)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, the jitted calls' inside."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("d,dv,window,s_q,s_k,name,walk", [
    (128, 128, None, 1280, 1280, "flash_fwd", "looped in tiles of 128/128, 2 behind"),
    (192, 128, None, 1280, 1280, "mla_fwd", "looped in tiles of 128/128, 4 behind"),
    (128, 128, 384, 1024, 1280, "swa_fwd", "looped in tiles of 128/128, 2 behind"),
    (128, 128, None, 1024, 1024, "flash_fwd", "looped in tiles of 128/128, 2 behind"),
    (192, 128, None, 1024, 1024, "mla_fwd", "looped in tiles of 128/128, 4 behind"),
], ids=["flash", "mla", "swa-rectangle", "flash-16-pairs", "mla-16-pairs"])
def test_what_the_benchmarks_readers_tell_the_forward_by(
        monkeypatch, d, dv, window, s_q, s_k, name, walk):
    """``benchmark/lib/hlo.flash_calls`` tells the forward by its name and
    its two results' types: ``[B, S, H·dv]`` of the operands' dtype and a
    float32 ``[B, H, S, 1]``. A problem of at most 16 block pairs a head is
    the same call as a longer one (until PR 60 it was an unrolled body with
    no scratch): three scratch operands, and the logged line says the
    tiles."""
    heads = 2
    q, k, v = normal(1, (1, s_q, heads, d), (1, s_k, heads, d),
                     (1, s_k, heads, dv), dtype="bfloat16")
    with _flash_log(monkeypatch) as records:
        jaxpr = jax.make_jaxpr(functools.partial(
            flash_attention, causal=True, block_q=256, block_k=256,
            interpret=True, window=window))(q, k, v)
    call, = _pallas_calls(jaxpr.jaxpr)
    assert call.params["name"] == name
    assert [(x.aval.shape, x.aval.dtype) for x in call.outvars] == [
        ((1, s_q, heads * dv), jnp.bfloat16),
        ((1, heads, s_q, 1), jnp.float32)]
    scratch = call.params["grid_mapping"].num_scratch_operands
    assert scratch == 3
    line, = records
    assert f"blocks q/k fwd 256/256 {walk}, " in line, line


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


@pytest.mark.parametrize("s,window,block,h,d,said", [
    (64, None, 32, 1, 32, "fwd 32/32 looped, bwd 32/32 looped, one kernel,"),
    # gpt2-medium's call: a 1,024-long head is ONE block pair a grid cell,
    # walked in the same tiles
    (1024, None, None, 2, 64,
     "fwd 1024/1024 looped in tiles of 128/128, 64 behind, "
     "bwd 1024/1024 looped in tiles of 128/128, 64 behind, one kernel,"),
    (256, None, 32, 1, 32, "fwd 32/32 looped, bwd 32/32 looped, one kernel,"),
    (256, 80, 32, 1, 32, "fwd 32/32 looped, bwd 32/32 looped, one kernel,"),
    (256, 32, 32, 1, 32,
     "fwd band 128/32 beside 32, dq band 128/32 beside 32, "
     "dkv band 128/32 beside 32,"),
    # blocks the tile divides: both looped kernels walk a pair in tiles,
    # half of what a cell's heads have in a pair behind
    (1280, None, 256, 1, 128,
     "fwd 256/256 looped in tiles of 128/128, 2 behind, "
     "bwd 256/256 looped in tiles of 128/128, 2 behind, one kernel,"),
    (4096, None, None, 2, 64,
     "fwd 512/512 looped in tiles of 128/128, 16 behind, "
     "bwd 512/512 looped in tiles of 128/128, 16 behind, one kernel,"),
], ids=["four-pairs", "medium-one-pair", "looped", "looped-window", "band",
        "looped-in-tiles", "looped-in-tiles-two-heads-of-64"])
def test_the_logged_line_says_how_many_kernels_the_backward_is(
        monkeypatch, s, window, block, h, d, said):
    """The engagement is static: the line a process logs once for a call's
    shape says ``one kernel`` where (and only where) the backward is — every
    call but the band path's — and the tiles and the depth a kernel walks
    its pair in."""
    q, k, v = qkv(1, b=1, s=s, h=h, d=d)
    with _flash_log(monkeypatch) as records:
        jax.eval_shape(functools.partial(
            flash_attention, causal=True, block_q=block, block_k=block,
            interpret=True, window=window), q, k, v)
    line, = records
    assert f"blocks q/k {said} over lengths" in line, line


@pytest.mark.parametrize("heads,d,pair,cell", [
    (16, 64, (1024, 1024), 2),  # gpt2-medium: two heads fill 128 lanes
    (25, 64, (1024, 1024), 2),  # gpt2-xl's shard: 13 cells, the last half outside
    (12, 64, (128, 128), 4),    # BERT at 128: ONE short pair a head, a wider cell
    (12, 64, (512, 512), 4),    # and at 512
    (12, 64, None, 2),          # more pairs than one a head
    (6, 64, (512, 512), 2),     # three tiles: what divides them
    (25, 64, (512, 512), 2),    # heads the tile does not divide: no wider
    (8, 128, (512, 512), 2),    # head_dim 128: a head is a tile, 256 lanes two
    (8, 128, (1024, 512), 1),   # over a block a side: not short
    (8, 32, (128, 128), 8),     # head_dim 32: four heads a tile, 256 lanes eight
    (2, 16, (64, 64), 2),       # narrower than a tile: the whole width
    (5, 80, (512, 512), 5),     # lcm(80, 128) = 640 lanes > 400: the whole width
], ids=["medium", "xl-shard", "bert-128", "bert-512", "two-pairs", "six-heads",
        "odd-heads", "head-dim-128", "long-pair", "head-dim-32", "narrow",
        "head-dim-80"])
def test_heads_a_grid_cell(heads, d, pair, cell):
    """``_cell_heads``: the heads that fill whole lane tiles, and twice that
    where a head's whole problem is one pair of at most 512 rows a side
    (``_SHORT``'s table has the measurements)."""
    import math

    from easydl_tpu.ops.flash_attention import _cell_heads

    assert _cell_heads(heads, d, None, pair) == cell
    # more pairs than one a head: the tile's heads, never more
    assert _cell_heads(heads, d) == min(heads, math.lcm(d, 128) // d)


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
