"""Pipeline parallelism (ops/pipeline.py): the GPipe schedule over the
``pp`` mesh axis must be a pure re-scheduling — identical loss and
gradients to the unpipelined model — and train end-to-end through the
standard Trainer. (SURVEY §2.2 listed pp as a reserved axis with no
schedule; this is the schedule.)"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from easydl_tpu.core import MeshSpec, Trainer, TrainConfig, build_mesh
from easydl_tpu.core.sharding import DEFAULT_RULES
from easydl_tpu.models import get_model
from easydl_tpu.ops.pipeline import make_pipeline, pipeline_rules


def bundles(mesh, microbatches=4):
    common = dict(size="test", seq_len=32, vocab=256, dtype="float32")
    plain = get_model("gpt", **common)
    piped = get_model(
        "gpt", **common,
        pipeline_fn=make_pipeline(mesh, microbatches=microbatches),
        pipeline_stages=mesh.shape["pp"],
    )
    return plain, piped


def test_pipeline_matches_plain_loss_and_grads(eight_devices):
    mesh = build_mesh(MeshSpec(dp=2, pp=2), devices=eight_devices[:4])
    plain, piped = bundles(mesh)
    params = jax.jit(plain.init_fn)(jax.random.PRNGKey(0))
    batch = next(iter(plain.make_data(8, seed=1)))
    rng = jax.random.PRNGKey(1)

    def loss_of(bundle):
        def f(p):
            loss, _ = bundle.loss_fn(p, batch, rng)
            return loss
        return f

    with mesh:
        l_plain, g_plain = jax.jit(jax.value_and_grad(loss_of(plain)))(params)
        l_pipe, g_pipe = jax.jit(jax.value_and_grad(loss_of(piped)))(params)
    np.testing.assert_allclose(float(l_plain), float(l_pipe),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_pipe)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_pipeline_trains_through_trainer(eight_devices):
    """The full production path: pjit Trainer over a dp×pp mesh, stacked
    layer params sharded over pp by the pipeline rule table, several steps,
    finite decreasing loss."""
    mesh = build_mesh(MeshSpec(dp=4, pp=2))
    _, piped = bundles(mesh, microbatches=2)
    trainer = Trainer(
        init_fn=piped.init_fn,
        loss_fn=piped.loss_fn,
        optimizer=optax.adam(1e-3),
        config=TrainConfig(global_batch=16,
                           rules=pipeline_rules(DEFAULT_RULES)),
        mesh=mesh,
    )
    state = trainer.init_state()
    # the stacked block params really are stage-sharded over pp
    from easydl_tpu.core.sharding import unbox

    blocks = unbox(state.params)["blocks"]
    leaf = jax.tree.leaves(blocks)[0]
    specs = {str(d.sharding.spec) for d in (leaf,)}
    assert any("pp" in s for s in specs), specs

    before = np.asarray(jax.tree.leaves(unbox(state.params))[0])
    data = iter(piped.make_data(16, seed=0))
    losses = []
    for _ in range(4):
        state, metrics = trainer.train_step(state, next(data))
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    after = np.asarray(jax.tree.leaves(unbox(state.params))[0])
    assert not np.allclose(before, after)  # the optimizer actually stepped
    assert float(metrics["grad_norm"]) > 0


def test_pipeline_config_validation(eight_devices):
    mesh = build_mesh(MeshSpec(dp=2, pp=2), devices=eight_devices[:4])
    piped = get_model(
        "gpt", size="test", seq_len=16, vocab=128,
        pipeline_fn=make_pipeline(mesh, microbatches=2),
        pipeline_stages=3,  # does not divide n_layers=2
    )
    params = jax.jit(piped.init_fn)(jax.random.PRNGKey(0))
    batch = next(iter(piped.make_data(4)))
    with mesh, pytest.raises(ValueError, match="not divisible"):
        jax.jit(lambda p: piped.loss_fn(p, batch, jax.random.PRNGKey(0)))(
            params)
    with pytest.raises(ValueError, match="pp axis"):
        make_pipeline(build_mesh(MeshSpec(dp=8)), microbatches=2)


def test_pipeline_stage_mismatch_fails_loudly(eight_devices):
    mesh = build_mesh(MeshSpec(dp=2, pp=2), devices=eight_devices[:4])
    piped = get_model(
        "gpt", size="test", seq_len=16, vocab=128,
        pipeline_fn=make_pipeline(mesh, microbatches=2),
        pipeline_stages=1,  # != mesh pp size 2
    )
    params = jax.jit(piped.init_fn)(jax.random.PRNGKey(0))
    batch = next(iter(piped.make_data(4)))
    with mesh, pytest.raises(ValueError, match="pp size"):
        jax.jit(lambda p: piped.loss_fn(p, batch, jax.random.PRNGKey(0)))(
            params)


def test_apply_pipeline_config_gates(eight_devices):
    """The entry-point helper: no-op without a pp axis; loud one-line error
    for pipeline-incapable models; kwargs+rules for capable ones."""
    from easydl_tpu.core.sharding import DEFAULT_RULES
    from easydl_tpu.ops.pipeline import apply_pipeline_config

    flat = build_mesh(MeshSpec(dp=8))
    kw, rules = apply_pipeline_config("mlp", {"features": [8]}, flat)
    assert kw == {"features": [8]} and rules == DEFAULT_RULES

    pp_mesh = build_mesh(MeshSpec(dp=4, pp=2))
    with pytest.raises(ValueError, match="does not support pipeline"):
        apply_pipeline_config("mlp", {}, pp_mesh)
    kw, rules = apply_pipeline_config("bert", {"size": "test"}, pp_mesh)
    assert kw["pipeline_stages"] == 2 and callable(kw["pipeline_fn"])
    assert dict(rules)["layers"] == "pp"


def test_pipeline_rejects_train_mode_dropout_loudly(eight_devices):
    """The stage apply passes no rngs, so dropout>0 + pipeline_fn in a
    NON-deterministic (train-mode) apply must fail with a clear error at
    trace time — not an opaque flax missing-rng error deep inside
    shard_map (advisor r4 low #2). Deterministic applies (eval, embedding
    extraction) need no rng and must keep working."""
    from easydl_tpu.models.transformer import Transformer, TransformerConfig

    mesh = build_mesh(MeshSpec(dp=2, pp=2), devices=eight_devices[:4])
    cfg = TransformerConfig(
        vocab=128, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_seq=16,
        dropout=0.1,
        pipeline_fn=make_pipeline(mesh, microbatches=2), pipeline_stages=2,
    )
    model = Transformer(cfg)
    tokens = jnp.zeros((4, 16), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    with mesh, pytest.raises(NotImplementedError, match="dropout"):
        model.apply({"params": params}, tokens, deterministic=False,
                    rngs={"dropout": jax.random.PRNGKey(1)})
    # deterministic apply: allowed (no dropout applied, no rng needed)
    with mesh:
        out = model.apply({"params": params}, tokens, deterministic=True)
    assert np.isfinite(np.asarray(out)).all()


def test_bubble_model_and_parity_across_microbatches(eight_devices):
    """The schedule's only bubble lever is the microbatch count (module
    docstring: a non-interleaved 1F1B reorder would not change the
    fraction). Check the analytic model and that parity holds at every m
    — the schedule is a pure re-ordering regardless of how deep the
    pipeline fill is."""
    from easydl_tpu.ops.pipeline import bubble_fraction, pipeline_ticks

    assert pipeline_ticks(4, 2) == 5
    assert pipeline_ticks(8, 4) == 11
    assert abs(bubble_fraction(4, 2) - 1 / 5) < 1e-9
    assert abs(bubble_fraction(8, 2) - 1 / 9) < 1e-9
    assert bubble_fraction(8, 2) < bubble_fraction(4, 2) < bubble_fraction(2, 2)

    mesh = build_mesh(MeshSpec(dp=2, pp=2), devices=eight_devices[:4])
    plain, _ = bundles(mesh)
    params = jax.jit(plain.init_fn)(jax.random.PRNGKey(0))
    # per-dp-shard batch 8, so microbatches=8 still divides it
    batch = next(iter(plain.make_data(16, seed=3)))
    rng = jax.random.PRNGKey(1)
    with mesh:
        l_ref = float(jax.jit(
            lambda p: plain.loss_fn(p, batch, rng)[0])(params))
    for m in (2, 8):
        _, piped = bundles(mesh, microbatches=m)
        with mesh:
            l_m = float(jax.jit(
                lambda p: piped.loss_fn(p, batch, rng)[0])(params))
        np.testing.assert_allclose(l_ref, l_m, rtol=1e-5, atol=1e-5)
