"""The comparison that decides ``correct`` for a NemotronH configuration: the
program on seeded sequences of the configuration's length at the published
widths against ``reference_nemotron_h`` (float32, the published sub-layers one
by one, Mamba-2 as the sequential recurrence, whole score matrices a head, a
loop over the held experts), both holding the same share: the experts
``kwargs.experts_held`` and the sliced vocabulary. Runs before the trainer's
state exists and keeps nothing on the device afterwards: the step program of
the cell fills the chip.

What is compared, and how:

- on ``check.sequences`` whole sequences, given to the program as ONE batch
  through the model bundle's own loss (the head the step uses): the loss
  against the reference's, the final normed state, the counters
  (``moe_dropped`` has to read 0);
- **every block's state**: the program's own ``Block`` modules applied one
  after another on the program's own state, each held to the reference's
  chain of published SUB-LAYERS at the block's end (a block is one sub-layer or
  two: the pairing is the program's, the reference knows none) as a relative
  root-mean-square error, and as the largest single TOKEN's relative error
  over all of them;
- **the Mamba-2 mixer's parts on equal inputs** (the mixer hands out its
  normed input, z, the convolved x, B and C, dt, the scan's result and the
  gated norm's: ``models/transformer.py`` sows ``ssm_*`` where
  ``intermediates`` is mutable), each as the worst single POSITION's relative
  error against the reference's arithmetic on the program's own input and
  weights: the input map, convolution and SiLU
  (``ssm_conv_token_rel_max``); the scan against the sequential recurrence on
  the program's own x, dt, B and C (``ssd_token_rel_max``: a head reading
  another group's B or C shows here); the gated norm group by group on the
  program's own y and z (``gated_norm_token_rel_max``: a norm over all 4,096
  channels at once shows here);
- **routing is discrete.** The expert layer hands out what it routed on. The
  router's float32 logits are held to the reference's arithmetic on EQUAL
  inputs (largest difference over largest logit); the selection to the
  program's own logits and bias (``chosen_not_top6_share``, has to read 0);
  the tokens whose chosen set differs between the program (on its bf16
  states) and the reference (on its float32 states) are counted and their
  share bounded; the reference's layer is then evaluated with the program's
  sets, weights from its own scores;
- the gradient of the bundle's loss on the first ``check.gradient_prefix``
  positions of the same sequences (the model has no positions, so a prefix is
  the same model; the reference's sequential recurrence and its backward fit
  one chip there), per leaf in the REFERENCE's layout (``to_reference``, a
  linear map: the program's split input maps and convolutions joined column
  by column into the published fused ones), as the whole gradient and as the
  worst leaf — every leaf, none left out; the selection biases', whose
  reference gradient is exactly zero, have to be exactly zero;
- tolerances live in the configuration file under ``check`` with the error
  measured on the chip when they were set and the reason for each.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from . import reference_nemotron_h as ref
from .check_joyai import _routing_errors, _runs, _worst_position
from .check_zaya import _gradient_errors, _rel_errors

_LETTER = {"mamba2": "M", "attention": "*"}


def sublayers_to_reference(one: Any, mixer: str, ffn: str
                           ) -> List[Tuple[str, Dict[str, Any]]]:
    """One block's (unstacked) leaves of the program's tree as the published
    sub-layers it is made of: ``[(letter, the reference's dict)]``, one entry
    or two."""
    import jax.numpy as jnp

    def flat(a, keep):
        return a.reshape(a.shape[:keep] + (-1,))

    if mixer == "mamba2":
        first = {
            "norm_g": one["ln_ssm"]["scale"],
            "in_proj": jnp.concatenate(
                [flat(one[f"in_{n}"]["kernel"], 1)
                 for n in ("z", "x", "B", "C", "dt")], axis=1),
            "conv_w": jnp.concatenate(
                [flat(one[f"conv_{n}"], 1) for n in "xBC"], axis=1),
            "conv_b": jnp.concatenate(
                [flat(one[f"conv_{n}_bias"], 0) for n in "xBC"]),
            "dt_bias": one["dt_bias"], "A_log": one["A_log"], "D": one["D"],
            "gnorm_g": flat(one["norm_gated"], 0),
            "out_proj": one["out"]["kernel"].reshape(
                -1, one["out"]["kernel"].shape[-1])}
    else:
        first = {"norm_g": one["ln_attn"]["scale"], "wq": one["q"]["kernel"],
                 "wk": one["k"]["kernel"], "wv": one["v"]["kernel"],
                 "wo": one["out"]["kernel"]}
    out = [(_LETTER[mixer], first)]
    if ffn == "moe":
        moe = one["moe"]
        out.append(("E", {
            "norm_g": one["ln_mlp"]["scale"], "router": moe["router"],
            "bias": moe["router_bias"], "e_up": moe["w_up"],
            "e_down": moe["w_down"], "s_up": moe["shared_up"],
            "s_down": moe["shared_down"]}))
    elif ffn != "none":
        raise ValueError(f"a NemotronH block's FFN is moe or none, not {ffn}")
    return out


def _blocks(cfg, params) -> List[Tuple[str, str, Any]]:
    """``[(mixer, ffn, one block's parameters)]`` in order, from the stacked
    runs of the program's (unboxed) tree."""
    import jax

    out = []
    for name, ((mixer, ffn), count) in zip(_runs(params), cfg.runs):
        out += [(mixer, ffn, jax.tree.map(lambda a: a[j], params[name]))
                for j in range(count)]
    return out


def to_reference(params: Any, cfg) -> Dict[str, Any]:
    """The program's (unboxed) parameter tree under the reference's names
    and layouts, a list of published sub-layers. With
    :func:`sublayers_to_reference` the only place that knows how
    ``models/transformer.py`` names things."""
    layers = [p for mixer, ffn, one in _blocks(cfg, params)
              for _, p in sublayers_to_reference(one, mixer, ffn)]
    return {"wte": params["tok_emb"]["embedding"],
            "head": params["head"]["kernel"],
            "lnf_g": params["ln_f"]["scale"], "layers": layers}


def pattern_of(cfg) -> str:
    """The published letters of the blocks the program runs."""
    return "".join(_LETTER[mixer] + ("E" if ffn == "moe" else "")
                   for mixer, ffn in cfg.pattern)


def _ssm_errors(kept, p_ref, hp):
    """The Mamba-2 mixer's parts on equal inputs: ``(input map and
    convolution, scan, gated norm)``, each the worst position's relative
    error of what the program's mixer handed out (``kept``) against the
    reference's arithmetic on the program's own operands and weights
    (``p_ref``: the program's leaves, its bf16 copy, in the reference's
    layout)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    p = {k: v.astype(f32) for k, v in p_ref.items()}
    mine = {k: v.astype(f32) for k, v in kept.items()}
    z, x, B, C, dt = ref.in_projection(mine["ssm_in"], p, hp)
    conv = _worst_position(
        [mine["ssm_z"], mine["ssm_x"], mine["ssm_B"], mine["ssm_C"]],
        [z, x, B, C])
    y = ref.recurrence(mine["ssm_x"], mine["ssm_dt"], -jnp.exp(p["A_log"]),
                       mine["ssm_B"], mine["ssm_C"], p["D"])
    scan = _worst_position([mine["ssm_y"]], [y])
    flat = mine["ssm_y"].shape[:2] + (-1,)
    normed = ref.grouped_gated_norm(
        mine["ssm_y"].reshape(flat), mine["ssm_z"].reshape(flat),
        p["gnorm_g"], hp["n_groups"], hp["eps"])
    return conv, scan, _worst_position([mine["ssm_normed"]], [normed])


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
    from easydl_tpu.models.nemotron_h import describe

    spec, kwargs = config["check"], config["kwargs"]
    seq, vocab = kwargs["seq_len"], kwargs["vocab"]
    hp = ref.hyper(config)
    cfg = describe(**kwargs)
    letters = pattern_of(cfg)
    if letters != config["hybrid_override_pattern"]:
        raise SystemExit(f"benchmark: the program's blocks spell {letters}, "
                         f"the configuration "
                         f"{config['hybrid_override_pattern']}")
    mesh = trainer.mesh
    dev0 = mesh.devices.flat[0]
    rows = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    n = int(spec["sequences"])
    prefix = min(int(spec.get("gradient_prefix", seq)), seq)
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
    def program_block(mixer, ffn, p, x):
        """One of the program's own blocks, what its mixer's parts held and
        what its expert layer routed on."""
        (y, _), kept = transformer.Block(cfg, mixer, ffn).apply(
            {"params": p}, x, True, None, mutable=["intermediates"])
        kept = kept["intermediates"]
        return y, {k: v[0] for k, v in kept.get("moe", {}).items()}, \
            {k: v[0] for k, v in kept.items() if k.startswith("ssm_")}

    sub = {letter: jax.jit(functools.partial(ref.sublayer, letter=letter,
                                             hp=hp))
           for letter in set(letters)}
    rel_errors = jax.jit(_rel_errors)
    routing_errors = jax.jit(_routing_errors)
    ssm_errors = jax.jit(functools.partial(_ssm_errors, hp=hp))
    final = jax.jit(lambda x, g: ref.rms_norm(x, g, hp["eps"]))
    head_loss = jax.jit(functools.partial(ref.cross_entropy, hp=hp))
    t_start = time.perf_counter()
    errors: Dict[str, Any] = {}
    with jax.set_mesh(mesh):
        params = jax.jit(bundle.init_fn,
                         out_shardings=trainer.state_shardings().params)(rng)
        whole = batch_of(window)
        loss_p, metrics = jax.jit(program_loss)(params, whole, rng)
        final_p = jax.device_put(
            jax.jit(program_final)(params, whole["inputs"]), dev0)
        (_, _), grads_p = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True))(
                params, batch_of(window[:, :prefix + 1]), rng)
    counters = {name: float(metrics[name]) for name in cfg.counters}
    errors["moe_dropped"] = counters["moe_dropped"]
    took = {"program_s": time.perf_counter() - t_start}

    one = functools.partial(jax.device_put, device=dev0)
    unboxed = jax.tree.map(one, shd.unbox(params))
    to_plain = jax.jit(functools.partial(to_reference, cfg=cfg))
    plain = to_plain(unboxed)
    cast = jax.jit(functools.partial(cast_floating, dtype=dtype))(unboxed)
    del params, unboxed
    blocks_p = _blocks(cfg, cast)
    n_blocks = len(blocks_p)
    # each block's bf16 leaves as the published sub-layers it is made of
    subs_p = [sublayers_to_reference(p, mixer, ffn)
              for mixer, ffn, p in blocks_p]
    state_sq = np.zeros((2, n_blocks))  # squared error and norm, by block
    final_sq = np.zeros(2)
    token_rel_max = logits_rel = 0.0
    ssm_worst = np.zeros(3)
    differ = not_top = tokens_routed = 0
    loss_r = []
    for i, row in enumerate(window):  # one sequence at a time
        tokens, targets = one(row[None, :-1]), one(row[None, 1:])
        x_p = jnp.take(cast["tok_emb"]["embedding"], tokens, axis=0)
        x_r = plain["wte"][tokens]
        at = 0  # the reference's sub-layer
        for b, (mixer, ffn, p_p) in enumerate(blocks_p):
            x_p, routed, kept = program_block(mixer, ffn, p_p, x_p)
            for letter, p_cast in subs_p[b]:
                chosen = routed["chosen"].reshape(1, seq, -1) \
                    if letter == "E" else None
                x_r, _, own = sub[letter](x_r, plain["layers"][at],
                                          chosen=chosen)
                at += 1
                if letter == "E":
                    off, wrong, other = jax.device_get(routing_errors(
                        routed, p_p, own))
                    logits_rel = max(logits_rel, float(off))
                    not_top += int(wrong)
                    differ += int(other)
                    tokens_routed += seq
                elif letter == "M":
                    ssm_worst = np.maximum(ssm_worst, jax.device_get(
                        ssm_errors(kept, p_cast)))
            gap, size, token = jax.device_get(rel_errors(x_p, x_r))
            state_sq[:, b] += gap, size
            token_rel_max = max(token_rel_max, float(token))
        h_r = final(x_r, plain["lnf_g"])
        final_sq += jax.device_get(rel_errors(final_p[i:i + 1], h_r))[:2]
        loss_r.append(float(head_loss(x_r, plain["lnf_g"], plain["head"],
                                      targets)))
        del x_p, x_r, h_r
    loss_r = float(np.mean(loss_r))
    errors["loss_abs"] = abs(float(loss_p) - loss_r)
    for b in range(n_blocks):
        errors[f"state_rel_rms_block_{b}"] = float(
            np.sqrt(state_sq[0, b] / state_sq[1, b]))
    errors["state_rel_rms_final"] = float(np.sqrt(final_sq[0] / final_sq[1]))
    errors["token_rel_max"] = token_rel_max
    errors["router_logits_rel"] = logits_rel
    errors["ssm_conv_token_rel_max"] = float(ssm_worst[0])
    errors["ssd_token_rel_max"] = float(ssm_worst[1])
    errors["gated_norm_token_rel_max"] = float(ssm_worst[2])
    errors["chosen_not_top6_share"] = not_top / max(tokens_routed, 1)
    errors["chosen_sets_differ_share"] = differ / max(tokens_routed, 1)
    del final_p, cast, blocks_p, subs_p
    took["states_s"] = time.perf_counter() - t_start - took["program_s"]
    mine = to_plain(jax.tree.map(one, shd.unbox(grads_p)))
    del grads_p
    _, grads_r = ref.loss_and_grads(
        plain, letters, one(window[:, :prefix]),
        one(window[:, 1:prefix + 1]), hp)
    per_leaf, overall = jax.device_get(
        jax.jit(_gradient_errors)(mine, grads_r))
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
