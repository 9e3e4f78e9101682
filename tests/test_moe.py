"""The expert layer (``ops/moe.py``): routing invariants, no token dropped,
forward and gradients, and Laguna's test size training over an ep-sharded
mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from easydl_tpu.core import sharding as shd
from easydl_tpu.core.mesh import MeshSpec
from easydl_tpu.core.train_loop import TrainConfig, Trainer
from easydl_tpu.models.registry import get_model
from easydl_tpu.ops.moe import (COUNTERS, MoeMlp, route, routed_experts,
                                rows_bound)


def test_routing_invariants():
    tokens, d, total, k = 64, 16, 8, 3
    h = jax.random.normal(jax.random.PRNGKey(0), (tokens, d))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (d, total))
    logits, chosen, weights = route(h.astype(jnp.bfloat16), kernel, k, 2.5)
    assert logits.dtype == jnp.float32 and logits.shape == (tokens, total)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    # k distinct experts a token, the k largest sigmoid scores
    assert all(len(set(row)) == k for row in chosen)
    scores = 1 / (1 + np.exp(-np.asarray(logits)))
    np.testing.assert_array_equal(
        np.sort(chosen, -1), np.sort(np.argsort(-scores, -1)[:, :k], -1))
    # renormalised over the chosen, times the scaling; each its score's share
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
    picked = np.take_along_axis(scores, chosen, -1)
    np.testing.assert_allclose(weights, 2.5 * picked / picked.sum(-1,
                                                                  keepdims=True),
                               rtol=1e-5)
    # at most min(k, held) of a token's choices fall on a share
    assert rows_bound(tokens, k, 2) == 2 * tokens
    assert rows_bound(tokens, k, 8) == k * tokens


def test_routing_drops_nothing():
    """Every token chooses ONE expert, the same one: all of them get a row
    (the old layer kept ``capacity`` of them and dropped the rest)."""
    tokens, d, f, held = 32, 8, 4, 4
    h = jax.random.normal(jax.random.PRNGKey(0), (tokens, d))
    chosen = jnp.zeros((tokens, 1), jnp.int32)
    weights = jnp.ones((tokens, 1), jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    w_gate = jax.random.normal(ks[0], (held, d, f))
    w_up = jax.random.normal(ks[1], (held, d, f))
    w_down = jax.random.normal(ks[2], (held, f, d))
    y, stats = routed_experts(h, chosen, weights, w_gate, w_up, w_down, 0)
    dropped, mine, largest = (float(x) for x in stats)
    assert (dropped, mine, largest) == (0.0, tokens, tokens)
    want = (jax.nn.silu(h @ w_gate[0]) * (h @ w_up[0])) @ w_down[0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    # a share that holds none of the chosen experts adds exactly nothing
    y, stats = routed_experts(h, chosen, weights, w_gate, w_up, w_down, 4)
    assert not np.asarray(y).any() and float(stats[1]) == 0.0


def test_moe_mlp_forward_and_grads():
    layer = MoeMlp(experts_total=8, experts_held=(0, 8), d_ff=32,
                   shared_d_ff=16, k=2, scaling=2.5)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 8))
    params = layer.init(jax.random.PRNGKey(2), x)

    def loss(params, x):
        y, counters = layer.apply(params, x)
        return (y ** 2).mean(), counters

    (val, counters), grads = jax.value_and_grad(loss, has_aux=True)(params, x)
    assert np.isfinite(float(val))
    named = dict(zip(COUNTERS, np.asarray(counters)))
    assert named["moe_dropped"] == 0.0 and named["moe_rows_per_token"] == 2.0
    assert named["moe_load_max_over_mean"] >= 1.0
    assert 0.0 < named["moe_buffer_fill"] <= 1.0
    assert 0.0 < named["router_entropy"] <= np.log(8) + 1e-6
    grads = shd.unbox(grads)["params"]
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))
    # the router receives gradient (the weights depend on it), and so does
    # every expert that got a row
    assert np.abs(np.asarray(grads["router"])).sum() > 0
    assert np.abs(np.asarray(grads["w_down"])).sum() > 0
    assert np.abs(np.asarray(grads["shared_down"])).sum() > 0


def test_laguna_trains_on_ep_mesh(eight_devices):
    """Laguna's test size, all 16 experts held, sharded over ep=4 with the
    batch over dp=2: each shard computes its four experts' part and the
    parts are summed — the same loss as one device gives, a finite,
    falling loss, nothing dropped."""
    kwargs = dict(size="test", seq_len=32, vocab=256)
    bundle = get_model("laguna", **kwargs)

    def trainer(spec):
        return Trainer(
            init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
            optimizer=optax.adam(1e-3),
            config=TrainConfig(global_batch=8, compute_dtype=jnp.float32),
            mesh_spec=spec)

    sharded = trainer(MeshSpec(dp=2, ep=4))
    state = sharded.init_state()
    flat = shd.flatten_dict(shd.unbox(state.params))
    held = {k: v for k, v in flat.items() if k.endswith("moe/w_gate")}
    assert held, list(flat)[:8]
    for key, w in held.items():
        assert "ep" in str(w.sharding.spec), (key, w.sharding.spec)
        assert w.shape[1] == 16  # every expert held, four a shard

    # one batch six times over: something to learn
    batches = [next(iter(bundle.make_data(8, seed=0)))] * 6
    losses, metrics = [], []
    for batch in batches:
        state, m = sharded.train_step(state, batch)
        losses.append(float(m["loss"]))
        metrics.append({k: float(v) for k, v in m.items()})
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert all(m["moe_dropped"] == 0.0 for m in metrics)
    # all experts held: each of a token's 2 choices has a row somewhere
    assert all(abs(m["moe_rows_per_token"] - 2.0) < 1e-6 for m in metrics)

    one = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adam(1e-3),
        config=TrainConfig(global_batch=8, compute_dtype=jnp.float32),
        mesh_spec=MeshSpec(dp=8))
    _, first = one.train_step(one.init_state(), batches[0])
    assert float(first["loss"]) == np.float32(losses[0]) or abs(
        float(first["loss"]) - losses[0]) < 1e-4
