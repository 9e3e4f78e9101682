"""Flash-attention kernel numerics vs the XLA reference path — forward and
backward (custom VJP), causal and bidirectional, multiple block splits, and
use inside a jitted transformer step. Kernels run in Pallas interpreter mode
on CPU (same code path the TPU compiles).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydl_tpu.ops.attention import _reference_attention
from easydl_tpu.ops.flash_attention import flash_attention


def rand_qkv(key, b=2, s=128, h=4, d=32, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, s, h, d), dtype)
    v = jax.random.normal(kv, (b, s, h, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [32, 64, 128])
def test_forward_matches_reference(causal, block):
    q, k, v = rand_qkv(jax.random.PRNGKey(0))
    scale = q.shape[-1] ** -0.5
    ref = _reference_attention(q, k, v, causal=causal, scale=scale)
    out = flash_attention(
        q, k, v, causal=causal, block_q=block, block_k=block, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    q, k, v = rand_qkv(jax.random.PRNGKey(1), b=1, s=64, h=2, d=16)
    scale = q.shape[-1] ** -0.5

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, causal=causal, block_q=32, block_k=32, interpret=True
        )
        return (out * jnp.cos(out)).sum()

    def loss_ref(q, k, v):
        out = _reference_attention(q, k, v, causal=causal, scale=scale)
        return (out * jnp.cos(out)).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_uneven_blocks_and_rectangular():
    # seq not equal to block multiples exercises the min() clamping
    q, k, v = rand_qkv(jax.random.PRNGKey(2), s=96, d=64)
    ref = _reference_attention(q, k, v, causal=True, scale=64**-0.5)
    out = flash_attention(q, k, v, causal=True, block_q=96, block_k=96, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_causal_cross_length_bottom_right_aligned():
    """s_q != s_k causal masking must match the reference path's
    bottom-right alignment (tril k=s_k-s_q) — e.g. decode: q_len 32 against a
    64-long KV cache attends all past keys, not just the first 32."""
    key = jax.random.PRNGKey(4)
    kq, kk, kv = jax.random.split(key, 3)
    b, h, d, s_q, s_k = 2, 2, 32, 32, 64
    q = jax.random.normal(kq, (b, s_q, h, d))
    k = jax.random.normal(kk, (b, s_k, h, d))
    v = jax.random.normal(kv, (b, s_k, h, d))
    scale = d**-0.5
    ref = _reference_attention(q, k, v, causal=True, scale=scale)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
        return (o * jnp.cos(o)).sum()

    def loss_ref(q, k, v):
        o = _reference_attention(q, k, v, causal=True, scale=scale)
        return (o * jnp.cos(o)).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_causal_cross_length_sq_gt_sk_dead_rows():
    """s_q > s_k bottom-right-aligned causal: the first s_q - s_k query rows
    attend nothing. Both paths must define such rows as zero output with
    zero gradient (not softmax's uniform mean of V) — and agree on the live
    rows. Exercises dead rows both inside a mixed q-block (block 16 > 8
    dead rows? no: 32 dead rows span blocks) and whole-dead q-blocks."""
    key = jax.random.PRNGKey(6)
    kq, kk, kv = jax.random.split(key, 3)
    b, h, d, s_q, s_k = 2, 2, 16, 64, 32
    q = jax.random.normal(kq, (b, s_q, h, d))
    k = jax.random.normal(kk, (b, s_k, h, d))
    v = jax.random.normal(kv, (b, s_k, h, d))
    scale = d**-0.5
    n_dead = s_q - s_k
    ref = _reference_attention(q, k, v, causal=True, scale=scale)
    # block 16 divides both: dead rows cover 2 whole q-blocks; also run with
    # block 32 so one q-block mixes dead and live rows.
    for bq in (16, 32):
        out = flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=16, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(out[:, :n_dead]), 0.0, err_msg=f"bq={bq} dead rows"
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
            err_msg=f"bq={bq}",
        )

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=32, block_k=16,
                            interpret=True)
        return (o * jnp.cos(o)).sum()

    def loss_ref(q, k, v):
        o = _reference_attention(q, k, v, causal=True, scale=scale)
        return (o * jnp.cos(o)).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(g_flash[0][:, :n_dead]), 0.0,
                               err_msg="dead rows must not leak dq")
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_untileable_length_falls_back_to_reference():
    """Lengths with no usable block divisor (e.g. 72 with block 48 → none
    ≥128-aligned) must not assert — the wrapper falls back to the XLA path."""
    q, k, v = rand_qkv(jax.random.PRNGKey(5), s=72, d=16)
    ref = _reference_attention(q, k, v, causal=True, scale=16**-0.5)
    out = flash_attention(q, k, v, causal=True, block_q=48, block_k=48, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_bf16_inputs():
    q, k, v = rand_qkv(jax.random.PRNGKey(3), dtype=jnp.bfloat16, s=64)
    ref = _reference_attention(q, k, v, causal=True, scale=q.shape[-1] ** -0.5)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2, rtol=2e-2
    )


def test_inside_jitted_train_step(monkeypatch):
    """Flash path composes with jit + grad in a real model step — on a
    2-device mesh, so the kernel runs per shard inside ``jax.shard_map``."""
    import functools

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
