"""The comparison that decides ``correct`` for an Ouro (LoopLM)
configuration: the program on seeded sequences of the configuration's length
at the published widths against ``reference_ouro`` (float32, Python loops
over passes and layers, whole score matrices, full logits). Runs before the
trainer's state exists and keeps nothing on the device afterwards: the step
program of the cell fills the chip.

What is compared, and how:

- on ``check.sequences`` whole sequences, given to the program as ONE batch:
  the loss (through the head the shape rule picks there, the fused weighted
  one at the cell's size), **each pass's normed state** (relative
  root-mean-square error, one number a pass: a pass that drifts is seen
  where it starts) and the exit distribution (largest absolute difference
  over tokens and passes). The reference takes the sequences one at a time:
  its score matrix is 1 GiB a sequence;
- the two parts the configuration states as float32 are also held to the
  reference on the SAME inputs, because behind 1-3% of bf16 activations a
  bf16 table or a bf16 gate cannot be seen (measured, PR 29): the rotary
  tables entry by entry, and the exit distribution the program forms from
  its OWN gate logits against the reference's formula on those logits;
- on the first ``check.gradient_prefix`` positions of the same sequences
  (causal, rotary from position 0: a prefix is the same model) the
  gradients, per leaf in the REFERENCE's layout (``to_reference``, a linear
  map, so it carries gradients as it carries weights), as the whole
  gradient and as the worst leaf. The program's side goes **through the head
  the step uses**: the rule is asked at the whole sequences' shape and its
  answer is handed to the objective (``models/lm.py looplm_objective(fused=)``),
  so the fused head with row weights is held to the reference in every run
  (at the prefix's own shape the rule would pick full logits);
- tolerances live in the configuration file under ``check`` with the error
  measured on the chip when they were set and the reason for each.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np

from . import reference_ouro as ref
from .check_gpt2 import _gradient_errors


def to_reference(params: Any) -> Dict[str, Any]:
    """The program's (unboxed) parameter tree under the reference's names.
    The only place that knows how ``models/transformer.py`` names things:
    the layers are one run, ``blocks``, stacked on a leading axis, applied
    in every pass."""
    run = params["blocks"]
    layers = []
    for j in range(run["q"]["kernel"].shape[0]):
        layers.append({
            "n1": run["ln_attn"]["scale"][j],
            "n2": run["ln_attn_out"]["scale"][j],
            "n3": run["ln_mlp"]["scale"][j],
            "n4": run["ln_mlp_out"]["scale"][j],
            "wq": run["q"]["kernel"][j], "wk": run["k"]["kernel"][j],
            "wv": run["v"]["kernel"][j], "wo": run["out"]["kernel"][j],
            "w_gate": run["gate"]["kernel"][j],
            "w_up": run["up"]["kernel"][j],
            "w_down": run["down"]["kernel"][j]})
    return {"wte": params["tok_emb"]["embedding"],
            "head": params["head"]["kernel"],
            "lnf_g": params["ln_f"]["scale"],
            "gate_w": params["exit_gate"], "gate_b": params["exit_gate_bias"],
            "layers": layers}


def _program_model(kwargs: Dict[str, Any]):
    """The program's ``Transformer`` as ``models/ouro.py`` builds it from
    these factory arguments — needed for the passes' states, which the model
    bundle does not hand out."""
    from easydl_tpu.models.ouro import describe
    from easydl_tpu.models.transformer import Transformer

    described = {k: v for k, v in kwargs.items()
                 if k != "exit_entropy_weight"}
    return Transformer(describe(**described))


def _table_error(config: Dict[str, Any], seq: int) -> float:
    """Largest absolute difference between the rotary tables the program's
    stack makes (``models/transformer.py`` calls ``rope_tables``; the sign
    of the rotation is folded into its sine) and the reference's."""
    import jax.numpy as jnp

    from easydl_tpu.models import transformer

    d, theta = config["head_dim"], float(config["rope_theta"])
    cos_p, sin_p = transformer.rope_tables(seq, d, theta)
    cos_r, sin_r = ref.rope_tables(seq, d, theta)
    sign = jnp.where(jnp.arange(d) < d // 2, -1.0, 1.0)
    return float(jnp.maximum(jnp.max(jnp.abs(cos_p - cos_r)),
                             jnp.max(jnp.abs(sin_p * sign - sin_r))))


def check(config: Dict[str, Any], bundle: Any, trainer: Any,
          seed: int) -> Dict[str, Any]:
    """Run the comparison; returns ``{"ok": bool, "errors": {...},
    "tolerances": {...}}``. ``trainer`` gives the mesh, the parameter
    shardings and the compute dtype the cell's step will use."""
    import jax
    import jax.numpy as jnp

    from easydl_tpu.core import sharding as shd
    from easydl_tpu.core.train_loop import cast_floating
    from easydl_tpu.models import lm

    spec, kwargs = config["check"], config["kwargs"]
    seq, vocab = kwargs["seq_len"], kwargs["vocab"]
    hp = ref.hyper(config)
    passes = hp["total_ut_steps"]
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

    def program_states(params, tokens):
        out = model.apply({"params": cast_floating(params, dtype)}, tokens,
                          return_hidden=True)
        return (out.hidden[:, :n], lm.exit_distribution(out.gate)[:, :n],
                out.gate[:, :n])

    def program_prefix_loss(params, batch, fused):
        """The bundle's loss with the head the STEP's shape gets."""
        cast = cast_floating(params, dtype)
        out = model.apply({"params": cast}, batch["inputs"],
                          return_hidden=True)
        head = jnp.asarray(shd.unbox(cast["head"]["kernel"]),
                           out.hidden.dtype).T
        loss, _ = lm.looplm_objective(
            out.hidden, out.gate, head, batch["targets"], beta=hp["beta"],
            fused=fused)
        return loss.astype(jnp.float32)

    with jax.set_mesh(mesh):
        params = jax.jit(bundle.init_fn,
                         out_shardings=trainer.state_shardings().params)(rng)
        whole = batch_of(window)
        loss_p = jax.jit(program_loss)(params, whole, rng)
        states_p, exits_p, gates_p = jax.device_put(
            jax.jit(program_states)(params, whole["inputs"]), dev0)
        grads_p = None
        if spec.get("gradients"):
            fused = lm.fused_head_by_shape(n * reps, seq, vocab,
                                           heads=passes)
            _, grads_p = jax.jit(jax.value_and_grad(functools.partial(
                program_prefix_loss, fused=fused)))(
                    params, batch_of(window[:, :prefix + 1]))

    one = functools.partial(jax.device_put, device=dev0)
    plain = jax.tree.map(one, to_reference(shd.unbox(params)))
    one_pass = jax.jit(functools.partial(ref.one_pass, hp=hp))
    gate_logit = jax.jit(ref.gate_logit)
    objective = jax.jit(functools.partial(ref.objective, beta=hp["beta"]))
    errors: Dict[str, Any] = {
        # the two parts the configuration states as float32, each held to
        # the reference's own arithmetic on the SAME inputs, where bf16
        # would show a thousand times over: the tables the stack rotates
        # by, and the exit distribution of the program's own gate logits
        "rope_table_abs": _table_error(config, seq),
        "exit_given_gate_abs": float(jnp.max(jnp.abs(
            exits_p - jax.jit(ref.exit_distribution)(list(gates_p))))),
    }
    state_sq = np.zeros((2, passes))   # squared error and norm, by pass
    exit_abs, loss_r = 0.0, []
    for i, row in enumerate(window):  # one sequence at a time
        x = plain["wte"][one(row[None, :-1])]
        hidden, gates = [], []
        for t in range(passes):
            x = one_pass(x, plain)
            hidden.append(x)
            gates.append(gate_logit(x, plain))
            mine = states_p[t, i:i + 1].astype(jnp.float32)
            state_sq[0, t] += float(jnp.sum((mine - x) ** 2))
            state_sq[1, t] += float(jnp.sum(x ** 2))
        loss_i, _, p = objective(hidden, gates, plain["head"],
                                 one(row[None, 1:]))
        loss_r.append(float(loss_i))
        exit_abs = max(exit_abs, float(jnp.max(jnp.abs(
            exits_p[:, i:i + 1] - p))))
        del hidden, gates, x, p
    loss_r = float(np.mean(loss_r))
    errors["loss_abs"] = abs(float(loss_p) - loss_r)
    for t in range(passes):
        errors[f"state_rel_rms_pass_{t}"] = float(
            np.sqrt(state_sq[0, t] / state_sq[1, t]))
    errors["exit_abs"] = exit_abs
    del states_p, exits_p, gates_p
    if grads_p is not None:
        mine = jax.tree.map(one, to_reference(shd.unbox(grads_p)))
        del grads_p
        _, grads_r = ref.loss_and_grads_by_pass(
            plain, one(window[:, :prefix]), one(window[:, 1:prefix + 1]), hp)
        per_leaf, overall = jax.device_get(
            jax.jit(_gradient_errors)(mine, grads_r))
        worst = max(jax.tree_util.tree_leaves_with_path(per_leaf),
                    key=lambda kv: kv[1])
        errors["grad_rel_rms_worst"] = float(worst[1])
        errors["grad_worst_leaf"] = jax.tree_util.keystr(worst[0])
        errors["grad_rel_rms_all"] = float(overall)
        errors["grad_head_fused"] = bool(fused)
    tolerances = dict(spec["tolerances"])
    values = {"program_loss": float(loss_p), "reference_loss": loss_r}
    finite = all(np.isfinite(v) for v in errors.values()
                 if isinstance(v, float))
    ok = finite and all(errors[k] <= tol for k, tol in tolerances.items())
    return {"ok": bool(ok), "errors": errors, "tolerances": tolerances,
            **values}
