"""The comparison that decides ``correct`` for a Granite 4.0-H configuration:
the program's loss and final hidden state on seeded sequences of the
configuration's length at the published widths, and its gradients on what
one chip's reference can hold, against ``reference_granite_hybrid`` (float32,
the Mamba-2 layers as the sequential recurrence). Runs before the trainer's
state exists and keeps nothing on the device afterwards: the step program of
the cell fills the chip.

What is compared, and how:

- loss and hidden state on ``check.sequences`` whole sequences, given to the
  program as ONE batch — with two that is the cell's microbatch, so the loss
  goes through the head the step uses (the fused chunked one at ``[2, 4096,
  100352]``). The reference takes them one at a time: its score matrix is
  2 GiB a sequence;
- gradients on the first ``check.gradient_prefix`` positions of the same
  sequences. The model has no positions, so a prefix is the same model; the
  reference's sequential scan and full logits fit one chip there. At that
  shape the program's own rule picks full logits, so both heads are held to
  the reference in every run;
- errors are relative root-mean-square errors, per gradient leaf in the
  REFERENCE's layout: the program's split input projections and convolutions
  are concatenated column by column into the published fused ones
  (``to_reference``, a linear map, so it carries gradients as it carries
  weights);
- tolerances live in the configuration file under ``check`` with the error
  measured on the chip when they were set and the reason for each: the two
  aggregates (hidden state, whole gradient) repeat to 0.7% between seeds and
  are held close enough that the scan's decay sums in bf16 fail (measured,
  PR 25); the worst single leaf and the loss at a few times their reading.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import numpy as np

from . import reference_granite_hybrid as ref
from .check_gpt2 import _gradient_errors, rel_rms

def to_reference(params: Any, layer_types: List[str]) -> Dict[str, Any]:
    """The program's (unboxed) parameter tree under the reference's names
    and layouts. The only place that knows how ``models/transformer.py``
    names things: runs of equal layers are ``blocks_<i>`` (``blocks`` where
    there is one run), stacked on a leading axis."""
    import jax.numpy as jnp

    runs: List[List[Any]] = []
    for kind in layer_types:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    flat = lambda a, keep: a.reshape(a.shape[:keep] + (-1,))  # noqa: E731
    layers = []
    for i, (kind, count) in enumerate(runs):
        run = params["blocks" if len(runs) == 1 else f"blocks_{i}"]
        for j in range(count):
            b = {k: ({kk: vv[j] for kk, vv in v.items()}
                     if isinstance(v, dict) else v[j])
                 for k, v in run.items()}
            layer = {
                "ln2_g": b["ln_mlp"]["scale"],
                "w_in": jnp.concatenate(
                    [b["gate"]["kernel"], b["up"]["kernel"]], axis=1),
                "w_out": b["down"]["kernel"],
            }
            if kind == "mamba":
                layer.update(
                    norm_g=b["ln_ssm"]["scale"],
                    in_proj=jnp.concatenate(
                        [flat(b[f"in_{n}"]["kernel"], 1)
                         for n in ("z", "x", "B", "C", "dt")], axis=1),
                    conv_w=jnp.concatenate(
                        [flat(b[f"conv_{n}"], 1) for n in "xBC"], axis=1),
                    conv_b=jnp.concatenate(
                        [flat(b[f"conv_{n}_bias"], 0) for n in "xBC"]),
                    dt_bias=b["dt_bias"], A_log=b["A_log"], D=b["D"],
                    gnorm_g=flat(b["norm_gated"], 0),
                    out_proj=b["out"]["kernel"].reshape(
                        -1, b["out"]["kernel"].shape[-1]))
            else:
                layer.update(
                    norm_g=b["ln_attn"]["scale"], wq=b["q"]["kernel"],
                    wk=b["k"]["kernel"], wv=b["v"]["kernel"],
                    wo=b["out"]["kernel"])
            layers.append(layer)
    return {"wte": params["tok_emb"]["embedding"],
            "lnf_g": params["ln_f"]["scale"], "layers": layers}


def _program_model(kwargs: Dict[str, Any]):
    """The program's ``Transformer`` as ``models/granite_hybrid.py`` builds
    it from these factory arguments — needed for the final hidden state,
    which the model bundle does not hand out."""
    from easydl_tpu.models.granite_hybrid import describe
    from easydl_tpu.models.transformer import Transformer

    return Transformer(describe(**kwargs))


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
    kinds = list(config["layer_types"])
    hp = ref.hyper(config)
    mesh = trainer.mesh
    dev0 = mesh.devices.flat[0]
    rows = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    n = int(spec["sequences"])
    prefix = min(int(spec.get("gradient_prefix", seq)), seq)
    window = np.random.default_rng(seed + 1).integers(
        0, vocab, (n, seq + 1), dtype=np.int32)
    # The batch axis is sharded over dp x fsdp: repeat the sequences until
    # they divide it. Equal rows leave the mean loss what it was.
    reps = 1 if n % rows == 0 else rows

    def batch_of(tokens):
        tiled = np.tile(tokens, (reps, 1))
        return jax.tree.map(
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
        whole = batch_of(window)
        loss_p = jax.jit(program_loss)(params, whole, rng)
        hidden_p = jax.device_put(
            jax.jit(program_hidden)(params, whole["inputs"]), dev0)
        grads_p = None
        if spec.get("gradients"):
            _, grads_p = jax.jit(jax.value_and_grad(program_loss))(
                params, batch_of(window[:, :prefix + 1]), rng)

    one = functools.partial(jax.device_put, device=dev0)
    plain = jax.tree.map(one, to_reference(shd.unbox(params), kinds))
    layer = {kind: jax.jit(functools.partial(ref.layer, kind=kind, hp=hp))
             for kind in set(kinds)}
    embed = jax.jit(functools.partial(
        ref.embed, multiplier=hp["embedding_multiplier"]))
    final = jax.jit(functools.partial(ref.final_hidden, eps=hp["eps"]))
    lm_loss = jax.jit(functools.partial(
        ref.lm_loss, logits_scaling=hp["logits_scaling"]))
    hidden_r, loss_r = [], []
    for row in window:  # one sequence at a time
        x = embed(one(row[None, :-1]), plain["wte"])
        for p, kind in zip(plain["layers"], kinds):
            x = layer[kind](x, p)
        hidden_r.append(final(x, plain["lnf_g"]))
        loss_r.append(float(lm_loss(hidden_r[-1], plain["wte"],
                                    one(row[None, 1:]))))
    hidden_r = jnp.concatenate(hidden_r)
    loss_r = float(np.mean(loss_r))

    errors = {"loss_abs": abs(float(loss_p) - loss_r),
              "hidden_rel_rms": float(jax.jit(rel_rms)(hidden_p, hidden_r))}
    del hidden_p, hidden_r
    if grads_p is not None:
        mine = jax.tree.map(one, to_reference(shd.unbox(grads_p), kinds))
        del grads_p
        _, grads_r = ref.loss_and_grads(
            plain, kinds, one(window[:, :prefix]),
            one(window[:, 1:prefix + 1]), hp)
        per_leaf, overall = jax.device_get(
            jax.jit(_gradient_errors)(mine, grads_r))
        worst = max(jax.tree_util.tree_leaves_with_path(per_leaf),
                    key=lambda kv: kv[1])
        errors["grad_rel_rms_worst"] = float(worst[1])
        errors["grad_worst_leaf"] = jax.tree_util.keystr(worst[0])
        errors["grad_rel_rms_all"] = float(overall)
    tolerances = dict(spec["tolerances"])
    values = {"program_loss": float(loss_p), "reference_loss": loss_r}
    finite = all(np.isfinite(v) for v in errors.values()
                 if isinstance(v, float))
    ok = finite and all(errors[k] <= tol for k, tol in tolerances.items())
    return {"ok": bool(ok), "errors": errors, "tolerances": tolerances,
            **values}
