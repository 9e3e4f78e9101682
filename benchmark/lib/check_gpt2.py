"""The comparison that decides ``correct`` for a GPT-2 configuration: the
program's loss, final hidden state and (where one device can hold them)
gradients on a few seeded sequences at the published widths, against
``reference_gpt2``. Runs before the trainer's state exists and keeps nothing
on the device afterwards: the step program of a cell fills the chip.

What is compared, and how:

- errors are relative root-mean-square errors, ``|a - r|_2 / |r|_2``: an
  aggregate over a million entries repeats from seed to seed, where a
  largest-entry error is a draw from a tail;
- the tolerances live in the configuration file under ``check`` with the
  error that was measured on the chip when they were set. They are about
  twice that error: computing in bf16 where the file says float32, or in int8
  where it says bf16, moves the error by several times and fails;
- the reference runs on ONE device. Its forward goes layer by layer, one
  layer's weights gathered from the (possibly sharded) state at a time, so a
  model that only fits across chips is still checked; its gradients need the
  whole model in float32 twice over and are taken only where the file says
  ``"gradients": true``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np

from . import reference_gpt2 as ref


def to_reference(params: Any) -> Dict[str, Any]:
    """The program's (unboxed) parameter tree under the reference's names.
    The only place that knows how ``models/transformer.py`` names things."""
    b = params["blocks"]
    return {
        "wte": params["tok_emb"]["embedding"],
        "wpe": params["pos_emb"],
        "lnf_g": params["ln_f"]["scale"], "lnf_b": params["ln_f"]["bias"],
        "blocks": {
            "ln1_g": b["ln_attn"]["scale"], "ln1_b": b["ln_attn"]["bias"],
            "wq": b["q"]["kernel"], "bq": b["q"]["bias"],
            "wk": b["k"]["kernel"], "bk": b["k"]["bias"],
            "wv": b["v"]["kernel"], "bv": b["v"]["bias"],
            "wo": b["out"]["kernel"], "bo": b["out"]["bias"],
            "ln2_g": b["ln_mlp"]["scale"], "ln2_b": b["ln_mlp"]["bias"],
            "w_up": b["up"]["kernel"], "b_up": b["up"]["bias"],
            "w_down": b["down"]["kernel"], "b_down": b["down"]["bias"],
        },
    }


def rel_rms(a, r):
    """``|a - r|_2 / |r|_2`` in float32."""
    import jax.numpy as jnp

    a, r = a.astype(jnp.float32), r.astype(jnp.float32)
    return jnp.sqrt(jnp.sum((a - r) ** 2) / jnp.sum(r ** 2))


def _gradient_errors(grads, grads_ref):
    """``rel_rms`` of every leaf, and of the whole gradient as one vector.
    A leaf whose true gradient is zero has no relative error — the key bias
    shifts every score of a row alike and softmax ignores it — so leaves
    under a thousandth of the largest leaf's norm read 0."""
    import jax
    import jax.numpy as jnp

    sq_err = jax.tree.map(
        lambda a, r: jnp.sum((a.astype(jnp.float32) - r) ** 2),
        grads, grads_ref)
    sq_ref = jax.tree.map(lambda r: jnp.sum(r ** 2), grads_ref)
    floor = 1e-6 * jnp.max(jnp.stack(jax.tree.leaves(sq_ref)))
    per_leaf = jax.tree.map(
        lambda e, r: jnp.where(r > floor, jnp.sqrt(e / r), 0.0),
        sq_err, sq_ref)
    overall = jnp.sqrt(sum(jax.tree.leaves(sq_err))
                       / sum(jax.tree.leaves(sq_ref)))
    return per_leaf, overall


def _program_model(kwargs: Dict[str, Any]):
    """The program's ``Transformer`` as ``models/gpt.py make_gpt`` builds it
    from these factory arguments — needed for the final hidden state, which
    the model bundle does not hand out."""
    from easydl_tpu.models.gpt import SIZES
    from easydl_tpu.models.transformer import Transformer, TransformerConfig

    n_layers, d_model, n_heads = SIZES[kwargs["size"]]
    return Transformer(TransformerConfig(
        vocab=kwargs["vocab"], d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=4 * d_model, max_seq=kwargs["seq_len"],
        causal=True, dropout=0.0, remat=kwargs.get("remat", False),
        remat_policy=kwargs.get("remat_policy", "full"),
        attention_impl=kwargs.get("attention_impl", "auto"),
        dtype=kwargs.get("dtype", "float32"), tied_head=True))


def check(config: Dict[str, Any], bundle: Any, trainer: Any,
          seed: int) -> Dict[str, Any]:
    """Run the comparison; returns ``{"ok": bool, "errors": {...},
    "tolerances": {...}}``. ``trainer`` gives the mesh, the parameter
    shardings and the compute dtype the cell's step will use."""
    import jax
    import jax.numpy as jnp

    from easydl_tpu.core import sharding as shd
    from easydl_tpu.core.train_loop import cast_floating

    spec, kwargs = config["check"], config["kwargs"]
    seq, vocab = kwargs["seq_len"], kwargs["vocab"]
    eps = float(config["layer_norm_epsilon"])
    mesh = trainer.mesh
    dev0 = mesh.devices.flat[0]
    rows = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    n = int(spec["sequences"])
    window = np.random.default_rng(seed + 1).integers(
        0, vocab, (n, seq + 1), dtype=np.int32)
    # The batch axis is sharded over dp x fsdp: repeat the sequences until
    # they divide it. Equal rows leave the mean loss what it was.
    reps = 1 if n % rows == 0 else rows
    tiled = np.tile(window, (reps, 1))
    batch = jax.tree.map(
        lambda x: jax.device_put(x, shd.batch_sharding(mesh)),
        {"inputs": tiled[:, :-1], "targets": tiled[:, 1:]})
    rng = jax.random.PRNGKey(seed)
    dtype = trainer.config.compute_dtype
    model = _program_model(kwargs)

    # Everything that differs from seed to seed is an ARGUMENT of the jitted
    # functions below, never a constant closed over: a constant would make
    # every seed its own program, compiled anew in every run.
    def program_loss(params, batch, rng):
        loss, _ = bundle.loss_fn(cast_floating(params, dtype), batch, rng)
        return loss.astype(jnp.float32)

    def program_hidden(params, tokens):
        return model.apply({"params": cast_floating(params, dtype)},
                           tokens, return_hidden=True)[:n]

    with jax.set_mesh(mesh):
        params = jax.jit(bundle.init_fn,
                         out_shardings=trainer.state_shardings().params)(rng)
        if spec.get("gradients"):
            loss_p, grads_p = jax.jit(jax.value_and_grad(program_loss))(
                params, batch, rng)
        else:
            loss_p, grads_p = jax.jit(program_loss)(params, batch, rng), None
        hidden_p = jax.device_put(
            jax.jit(program_hidden)(params, batch["inputs"]), dev0)

    plain = to_reference(shd.unbox(params))
    tokens = jax.device_put(window[:, :-1], dev0)
    targets = jax.device_put(window[:, 1:], dev0)
    one = functools.partial(jax.device_put, device=dev0)
    x = jax.jit(ref.embed)(tokens, one(plain["wte"]), one(plain["wpe"]))
    block = jax.jit(ref.block, static_argnames="eps")
    layer_of = jax.jit(lambda blocks, i: jax.tree.map(lambda a: a[i], blocks))
    n_layer = plain["blocks"]["wq"].shape[0]
    for i in range(n_layer):
        x = block(x, one(layer_of(plain["blocks"], i)), eps=eps)
    hidden_r = jax.jit(ref.final_hidden, static_argnames="eps")(
        x, one(plain["lnf_g"]), one(plain["lnf_b"]), eps=eps)
    loss_r = jax.jit(ref.lm_loss)(hidden_r, one(plain["wte"]), targets)

    errors = {"loss_abs": abs(float(loss_p) - float(loss_r)),
              "hidden_rel_rms": float(jax.jit(rel_rms)(hidden_p, hidden_r))}
    if grads_p is not None:
        _, grads_r = ref.loss_and_grads(plain, tokens, targets, eps)
        per_leaf, overall = jax.device_get(jax.jit(_gradient_errors)(
            to_reference(shd.unbox(grads_p)), grads_r))
        worst = max(jax.tree_util.tree_leaves_with_path(per_leaf),
                    key=lambda kv: kv[1])
        errors["grad_rel_rms_worst"] = float(worst[1])
        errors["grad_worst_leaf"] = jax.tree_util.keystr(worst[0])
        errors["grad_rel_rms_all"] = float(overall)
    tolerances = dict(spec["tolerances"])
    values = {"program_loss": float(loss_p), "reference_loss": float(loss_r)}
    finite = all(np.isfinite(v) for v in errors.values()
                 if isinstance(v, float))
    ok = finite and all(errors[k] <= tol for k, tol in tolerances.items())
    return {"ok": bool(ok), "errors": errors, "tolerances": tolerances,
            **values}
