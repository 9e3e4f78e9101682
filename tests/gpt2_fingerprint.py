"""A fingerprint of the GPT-2 description of ``models/transformer.py``: the
parameter tree (names, shapes, logical axes), the seeded initial values, the
loss and every gradient leaf at the test size, float32 on the CPU.

``tests/goldens/gpt2_stack.json`` was written by this file on the commit
before the stack became a layer pattern (PR 25, parent 5027f6c):

    JAX_PLATFORMS=cpu PYTHONPATH=. python tests/gpt2_fingerprint.py > tests/goldens/gpt2_stack.json

``tests/test_hybrid_stack.py`` holds the refactored stack to it.
"""

from __future__ import annotations

import json

KWARGS = dict(size="test", seq_len=32, vocab=256)


def fingerprint(**extra):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from easydl_tpu.core.sharding import flatten_dict, unbox
    from easydl_tpu.models.registry import get_model

    bundle = get_model("gpt", **KWARGS, **extra)
    boxed = bundle.init_fn(jax.random.PRNGKey(7))
    axes = flatten_dict(nn.get_partition_spec(boxed))
    params = unbox(boxed)
    tokens = np.random.default_rng(3).integers(0, 256, (4, 33), dtype=np.int32)
    batch = {"inputs": jnp.asarray(tokens[:, :-1]),
             "targets": jnp.asarray(tokens[:, 1:])}
    (loss, _), grads = jax.value_and_grad(bundle.loss_fn, has_aux=True)(
        params, batch, jax.random.PRNGKey(0))

    def leaf(x):
        x = np.asarray(x, np.float64)
        return {"shape": list(x.shape), "sum": float(x.sum()),
                "abs": float(np.abs(x).sum())}

    flat_p, flat_g = flatten_dict(params), flatten_dict(grads)
    return {"loss": float(loss),
            "axes": {k: list(v) for k, v in sorted(axes.items())},
            "params": {k: leaf(v) for k, v in sorted(flat_p.items())},
            "grads": {k: leaf(v) for k, v in sorted(flat_g.items())}}


def _step_sha256(bundle, spec, devices):
    """SHA-256 of ``bundle``'s lowered train step (StableHLO text without
    locations, the private functions' running numbers taken off their
    names) under ``spec`` on ``devices``: 8 sequences as two microbatches,
    AdamW. Equal text is the same program, op for op. The numbers
    (``@tril_217``) count what jax lowered before, operations or not: a
    ``checkpoint_name`` lowers to nothing and still moves them (PR 30)."""
    import hashlib
    import re

    import jax
    import jax.numpy as jnp
    import optax

    from easydl_tpu.core.mesh import build_mesh
    from easydl_tpu.core.train_loop import TrainConfig, Trainer

    trainer = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(1e-3),
        config=TrainConfig(global_batch=8, grad_accum=2),
        mesh=build_mesh(spec, devices=devices))
    tokens = jax.ShapeDtypeStruct((8, SMALL["seq_len"]), jnp.int32)
    text = trainer.step_fn.lower(
        trainer.abstract_state(),
        {"inputs": tokens, "targets": tokens}).as_text()
    text = re.sub(r"(@[A-Za-z_][\w.]*?)_\d+\b", r"\1", text)
    return hashlib.sha256(text.encode()).hexdigest()


#: the benchmark cells' program at a small size: bf16, two microbatches
SMALL = dict(size="test", seq_len=64, vocab=1024, dtype="bfloat16",
             remat=True)


def step_program_sha256():
    """GPT-2 under remat ``dots`` on one device (full logits by the rule)."""
    import jax

    from easydl_tpu.core.mesh import MeshSpec
    from easydl_tpu.models.registry import get_model

    bundle = get_model("gpt", remat_policy="dots", **SMALL)
    return _step_sha256(bundle, MeshSpec(), jax.devices()[:1])


def hybrid_step_program_sha256():
    """The hybrid's test description under remat ``full``. The caller
    steers the head's two constants (``conftest.py fused_head``) so that
    this size gets the fused head in several chunks, as the cell's does."""
    import jax

    from easydl_tpu.core.mesh import MeshSpec
    from easydl_tpu.models.registry import get_model

    bundle = get_model("granite_hybrid", remat_policy="full", **SMALL)
    return _step_sha256(bundle, MeshSpec(), jax.devices()[:1])


def gpt2_fsdp4_step_program_sha256():
    """GPT-2 under ``MeshSpec(fsdp=4)`` on four of the forced host devices
    with ``attention_impl="flash"``: the kernels called per shard. The
    caller puts the kernels in interpret mode (``ops.attention``'s
    ``flash_attention`` patched), as every whole-model CPU test does."""
    import jax

    from easydl_tpu.core.mesh import MeshSpec
    from easydl_tpu.models.registry import get_model

    bundle = get_model("gpt", remat_policy="dots", attention_impl="flash",
                       **SMALL)
    return _step_sha256(bundle, MeshSpec(fsdp=4), jax.devices()[:4])


if __name__ == "__main__":
    print(json.dumps(dict(fingerprint(),
                          step_program_sha256=step_program_sha256()),
                     indent=1))
