"""The comparison that decides ``correct`` for a Kimi Linear configuration:
the program on seeded sequences of the configuration's length at the
published widths against ``reference_kimi_linear`` (float32, Python loops over
layers, heads and experts, the delta rule TOKEN BY TOKEN, the convolutions as
four shifted adds, the attention's mask written out), both holding the same
share: the experts ``kwargs.experts_held`` and the sliced vocabulary. Runs
before the trainer's state exists and keeps nothing on the device afterwards:
the step program of the cell fills the chip.

What is compared, and how:

- on ``check.sequences`` whole sequences, given to the program as ONE batch
  through the model bundle's own loss: the loss against the reference's, the
  final normed state, the counters (``moe_dropped`` has to read 0);
- **every layer's state**: the program's own ``Block`` modules applied one
  layer after another on the program's own state, each held to the
  reference's chain as a relative root-mean-square error (one number a
  layer) and as the largest single TOKEN's relative error over all of them;
- **the delta rule on equal inputs** (a KDA layer hands out its normed
  input, q, k, v, the log-decays and step sizes as the kernels got them, the
  kernels' result and final state: ``models/transformer.py`` sows ``kda_*``
  where ``intermediates`` is mutable): q, k, v, g and beta against the
  reference's steps 1 and 2 on the program's own input and weights
  (``kda_inputs_token_rel_max``, the worst position: a dropped tap of a
  convolution, a missing norm or a scalar decay shows here); the kernels'
  ``o`` against the reference's recurrence TOKEN BY TOKEN on the program's
  own q, k, v, g and beta (``kda_out_token_rel_max``, the worst position, and
  ``kda_out_rel_rms``: an additive update, a state or cumulative decays
  carried in bf16 show here) and the final state (``kda_state_rel_rms``);
- **latent attention on equal inputs** (``mla_*``): the normed latent, q
  and the key's shared part as they reach the kernels — NOT rotated
  (``mla_latent_token_rel_max``: a rotation of the key part is wrong at
  every position but 0); the attention's result before ``W_o`` from the
  program's own q and key part (``mla_attn_token_rel_max``);
- **routing is discrete**, as ``check_joyai`` has it: the router's float32
  logits on equal inputs (``router_logits_rel``), the selection held to the
  program's own logits and bias under ``check_mellum``'s near-tie rule
  (``chosen_not_top8_share``, has to read 0), the tokens whose chosen set
  differs from the reference's counted and their share bounded, the
  reference's layer then evaluated with the program's sets;
- on the same whole sequences the gradient of the bundle's loss, per leaf in
  the REFERENCE's layout (``to_reference``, a linear map), as the whole
  gradient and as the worst leaf — every leaf, none left out; the selection
  biases', whose reference gradient is exactly zero, have to be exactly zero;
- the reference is evaluated piece by piece (``reference_kimi_linear.
  Pieces``), one sequence at a time;
- tolerances live in the configuration file under ``check`` with the error
  measured on the chip when they were set and the reason for each.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List

import numpy as np

from . import reference_kimi_linear as ref
from .check_joyai import _routing_errors, _runs, _worst_position
from .check_zaya import _gradient_errors, _rel_errors


def layer_to_reference(one: Any) -> Dict[str, Any]:
    """One layer's (unstacked) leaves of the program's tree under the
    reference's names."""
    out = {"n2": one["ln_mlp"]["scale"]}
    if "f_a" in one:
        out.update(
            n1=one["ln_ssm"]["scale"], wq=one["q"]["kernel"],
            wk=one["k"]["kernel"], wv=one["v"]["kernel"], cq=one["conv_q"],
            ck=one["conv_k"], cv=one["conv_v"], wfa=one["f_a"]["kernel"],
            wfb=one["f_b"]["kernel"], a_log=one["A_log"],
            dt_bias=one["dt_bias"], wb=one["b"]["kernel"],
            wga=one["g_a"]["kernel"], wgb=one["g_b"]["kernel"],
            gn=one["norm_gated"], wo=one["out"]["kernel"])
    else:
        out.update(
            n1=one["ln_attn"]["scale"], mq=one["q"]["kernel"],
            wkva=one["kv_a"]["kernel"], kvn=one["kv_norm"],
            wkvb=one["kv_b"], mo=one["out"]["kernel"])
    if "moe" in one:
        moe = one["moe"]
        out.update(router=moe["router"], bias=moe["router_bias"],
                   e_gate=moe["w_gate"], e_up=moe["w_up"],
                   e_down=moe["w_down"], s_gate=moe["shared_gate"],
                   s_up=moe["shared_up"], s_down=moe["shared_down"])
    else:
        out.update(w_gate=one["gate"]["kernel"], w_up=one["up"]["kernel"],
                   w_down=one["down"]["kernel"])
    return out


def to_reference(params: Any) -> Dict[str, Any]:
    """The program's (unboxed) parameter tree under the reference's names.
    With :func:`layer_to_reference` the only place that knows how
    ``models/transformer.py`` names things: one run of layers a
    ``blocks_<i>``, stacked on a leading axis."""
    import jax

    layers = []
    for name in _runs(params):
        run = params[name]
        layers += [layer_to_reference(jax.tree.map(lambda a: a[j], run))
                   for j in range(run["ln_mlp"]["scale"].shape[0])]
    return {"wte": params["tok_emb"]["embedding"],
            "head": params["head"]["kernel"],
            "lnf_g": params["ln_f"]["scale"], "layers": layers}


def _program_layers(cfg, params) -> List[Any]:
    """``[(mixer, ffn, one layer's parameters)]`` of the stack in order,
    from the stacked runs of the program's (unboxed) tree."""
    import jax

    at = jax.jit(lambda tree, j: jax.tree.map(lambda a: a[j], tree))
    out = []
    for name, ((mixer, ffn), count) in zip(_runs(params), cfg.runs):
        out += [(mixer, ffn, at(params[name], j)) for j in range(count)]
    return out


def _kda_errors(kept, p_layer):
    """A KDA layer's parts on equal inputs: ``(steps 1 and 2's worst
    position, the recurrence's worst position, its relative rms, the final
    state's relative rms)`` of what the program's layer handed out
    (``kept``) against the reference's arithmetic on the program's own
    normed input and weights (``p_layer``: the program's leaves, its bf16
    copy) and, for the recurrence, on the program's own q, k, v, g, beta."""
    import jax.numpy as jnp

    f32 = jnp.float32
    p = {k: v.astype(f32) for k, v in layer_to_reference(p_layer).items()
         if k in ref.KDA}
    names = ("kda_q", "kda_k", "kda_v", "kda_g", "kda_beta")
    mine = [kept[name].astype(f32) for name in names]
    want = ref.kda_inputs(kept["kda_in"].astype(f32), p)
    inputs = jnp.max(jnp.stack([
        _worst_position([a], [b]) for a, b in zip(mine, want)]))
    o, last = ref.recurrence(*mine)
    gap = kept["kda_o"].astype(f32) - o
    state_gap = kept["kda_state"].astype(f32) - last
    return (inputs, _worst_position([kept["kda_o"]], [o]),
            jnp.sqrt(jnp.sum(gap ** 2) / jnp.sum(o ** 2)),
            jnp.sqrt(jnp.sum(state_gap ** 2) / jnp.sum(last ** 2)))


def _mla_errors(kept, p_layer, hp):
    """The latent layer's parts on equal inputs: ``(the latent, q and the
    shared key part as they come; the attention's result)``, each the worst
    position's relative error."""
    import jax.numpy as jnp

    f32 = jnp.float32
    p = {k: v.astype(f32) for k, v in layer_to_reference(p_layer).items()
         if k in ref.MLA}
    nope = hp["nope"]
    u = kept["mla_in"].astype(f32)
    c, shared = ref.latents(u, p, hp)
    q = ref.product("bsd,dhk->bshk", u, p["mq"])
    latent = jnp.max(jnp.stack([
        _worst_position([a], [b]) for a, b in (
            (kept["mla_ckv"], c), (kept["mla_k_rot"][:, :, 0], shared),
            (kept["mla_q"], q))]))
    kv = ref.product("bsr,rhk->bshk", kept["mla_ckv"].astype(f32), p["wkvb"])
    attn = ref.attention_core(
        kept["mla_q"].astype(f32), kv[..., :nope],
        kept["mla_k_rot"][:, :, 0].astype(f32), kv[..., nope:])
    return latent, _worst_position([kept["mla_attn"]], [attn])


def check(config: Dict[str, Any], bundle: Any, trainer: Any,
          seed: int) -> Dict[str, Any]:
    """Run the comparison; returns ``{"ok": bool, "errors": {...},
    "tolerances": {...}, "counters": {...}}``. ``trainer`` gives the mesh,
    the parameter shardings and the compute dtype the cell's step will
    use."""
    import jax
    import jax.numpy as jnp

    from easydl_tpu.core import sharding as shd
    from easydl_tpu.core.train_loop import cast_floating
    from easydl_tpu.models import transformer
    from easydl_tpu.models.kimi_linear import describe

    spec, kwargs = config["check"], config["kwargs"]
    seq, vocab = kwargs["seq_len"], kwargs["vocab"]
    hp = ref.hyper(config)
    cfg = describe(**kwargs)
    mesh = trainer.mesh
    dev0 = mesh.devices.flat[0]
    rows = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    n = int(spec["sequences"])
    window = np.random.default_rng(seed + 1).integers(
        0, vocab, (n, seq + 1), dtype=np.int32)
    reps = 1 if n % rows == 0 else rows

    def batch_of(tokens):
        tiled = np.tile(tokens, (reps, 1))
        return jax.tree.map(
            lambda x: jax.device_put(x, shd.batch_sharding(mesh)),
            {"inputs": tiled[:, :-1], "targets": tiled[:, 1:]})

    rng = jax.random.PRNGKey(seed)
    dtype = trainer.config.compute_dtype
    model = transformer.Transformer(cfg)

    # Everything that differs from seed to seed is an ARGUMENT of the jitted
    # functions below, never a constant closed over.
    def program_loss(params, batch, rng):
        loss, metrics = bundle.loss_fn(cast_floating(params, dtype), batch,
                                       rng)
        return loss.astype(jnp.float32), metrics

    def program_final(params, tokens):
        return model.apply({"params": cast_floating(params, dtype)}, tokens,
                           return_hidden=True)[:n]

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def program_layer(mixer, ffn, p, x):
        """One of the program's own blocks, what its mixer held and what its
        expert layer routed on."""
        (y, _), kept = transformer.Block(cfg, mixer, ffn).apply(
            {"params": p}, x, True, None, mutable=["intermediates"])
        kept = kept["intermediates"]
        return y, {k: v[0] for k, v in kept.get("moe", {}).items()}, \
            {k: v[0] for k, v in kept.items()
             if k.startswith(("mla_", "kda_"))}

    rel_errors = jax.jit(_rel_errors)
    routing_errors = jax.jit(_routing_errors)
    kda_errors = jax.jit(_kda_errors)
    mla_errors = jax.jit(functools.partial(_mla_errors, hp=hp))
    t_start = time.perf_counter()
    errors: Dict[str, Any] = {}
    with jax.set_mesh(mesh):
        params = jax.jit(bundle.init_fn,
                         out_shardings=trainer.state_shardings().params)(rng)
        whole = batch_of(window)
        (loss_p, metrics), grads_p = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True))(params, whole, rng)
        final_p = jax.device_put(
            jax.jit(program_final)(params, whole["inputs"]), dev0)
    counters = {name: float(metrics[name])
                for name in cfg.counters + ("kda_chunks",)}
    errors["moe_dropped"] = counters["moe_dropped"]
    took = {"program_s": time.perf_counter() - t_start}

    one = functools.partial(jax.device_put, device=dev0)
    unboxed = jax.tree.map(one, shd.unbox(params))
    plain = jax.jit(to_reference)(unboxed)
    cast = jax.jit(functools.partial(cast_floating, dtype=dtype))(unboxed)
    del params, unboxed
    layers_p = _program_layers(cfg, cast)
    pieces = ref.Pieces(hp)
    depth = cfg.n_layers
    state_sq = np.zeros((2, depth))  # squared error and norm, by layer
    final_sq = np.zeros(2)
    token_rel_max = logits_rel = 0.0
    kda_worst, mla_worst = np.zeros(4), np.zeros(2)
    differ = not_top8 = tokens_routed = 0
    loss_r = []
    for i, row in enumerate(window):  # one sequence at a time
        tokens, targets = one(row[None, :-1]), one(row[None, 1:])
        x_p = jnp.take(cast["tok_emb"]["embedding"], tokens, axis=0)
        x_r = plain["wte"][tokens]
        for l, ((mixer, ffn, p_p), p_r) in enumerate(
                zip(layers_p, plain["layers"])):
            x_p, routed, kept = program_layer(mixer, ffn, p_p, x_p)
            chosen = routed["chosen"].reshape(1, seq, -1) if routed else None
            x_r, _, own, _ = pieces.layer(x_r, p_r, chosen)
            if routed:
                off, wrong, other = jax.device_get(routing_errors(
                    routed, p_p, own))
                logits_rel = max(logits_rel, float(off))
                not_top8 += int(wrong)
                differ += int(other)
                tokens_routed += seq
            if mixer == "kda":
                kda_worst = np.maximum(kda_worst, jax.device_get(
                    kda_errors(kept, p_p)))
            else:
                mla_worst = np.maximum(mla_worst, jax.device_get(
                    mla_errors(kept, p_p)))
            gap, size, token = jax.device_get(rel_errors(x_p, x_r))
            state_sq[:, l] += gap, size
            token_rel_max = max(token_rel_max, float(token))
        h_r = pieces.norm(x_r, plain["lnf_g"])
        final_sq += jax.device_get(rel_errors(final_p[i:i + 1], h_r))[:2]
        loss_r.append(float(pieces.head(
            x_r, {"g": plain["lnf_g"], "head": plain["head"]}, targets)))
        del x_p, x_r, h_r
    loss_r = float(np.mean(loss_r))
    errors["loss_abs"] = abs(float(loss_p) - loss_r)
    for l in range(depth):
        errors[f"state_rel_rms_layer_{l}"] = float(
            np.sqrt(state_sq[0, l] / state_sq[1, l]))
    errors["state_rel_rms_final"] = float(np.sqrt(final_sq[0] / final_sq[1]))
    errors["token_rel_max"] = token_rel_max
    errors["router_logits_rel"] = logits_rel
    for name, value in zip(("kda_inputs_token_rel_max",
                            "kda_out_token_rel_max", "kda_out_rel_rms",
                            "kda_state_rel_rms"), kda_worst):
        errors[name] = float(value)
    errors["mla_latent_token_rel_max"] = float(mla_worst[0])
    errors["mla_attn_token_rel_max"] = float(mla_worst[1])
    errors["chosen_not_top8_share"] = not_top8 / max(tokens_routed, 1)
    errors["chosen_sets_differ_share"] = differ / max(tokens_routed, 1)
    del final_p, cast, layers_p
    took["states_s"] = time.perf_counter() - t_start - took["program_s"]
    # on the HOST while the reference's gradient is made: at 16,384 tokens a
    # mixer's piece takes 6 GB beside the parameters and the sum of gradients
    mine = jax.device_get(
        jax.jit(to_reference)(jax.tree.map(one, shd.unbox(grads_p))))
    del grads_p
    # one sequence at a time; the loss is their mean
    _, grads_r = pieces.loss_and_grads(
        plain, one(window[:, :-1]), one(window[:, 1:]), by_row=True)
    per_leaf, overall = jax.device_get(
        jax.jit(_gradient_errors)(jax.tree.map(one, mine), grads_r))
    worst = max(jax.tree_util.tree_leaves_with_path(per_leaf),
                key=lambda kv: kv[1])
    errors["grad_rel_rms_worst"] = float(worst[1])
    errors["grad_worst_leaf"] = jax.tree_util.keystr(worst[0])
    errors["grad_rel_rms_all"] = float(overall)
    errors["grad_leaves"] = len(jax.tree.leaves(per_leaf))
    took["whole_s"] = time.perf_counter() - t_start
    tolerances = dict(spec["tolerances"])
    values = {"program_loss": float(loss_p), "reference_loss": loss_r,
              "took": took}
    finite = all(np.isfinite(v) for v in errors.values()
                 if isinstance(v, float))
    ok = finite and all(errors[k] <= tol for k, tol in tolerances.items())
    return {"ok": bool(ok), "errors": errors, "tolerances": tolerances,
            "counters": counters, **values}
