"""The comparison that decides ``correct`` for a JoyAI-LLM configuration: the
program on seeded sequences of the configuration's length at the published
widths against ``reference_joyai`` (float32, Python loops over layers, heads
and experts, whole score matrices, the rotary tables written out, the shared
rotated key copied head by head), both holding the same share: the experts
``kwargs.experts_held`` and the sliced vocabulary. Runs before the trainer's
state exists and keeps nothing on the device afterwards: the step program of
the cell fills the chip.

What is compared, and how:

- on ``check.sequences`` whole sequences, given to the program as ONE batch
  through the model bundle's own loss (both heads in one call of the fused
  head): the loss, ``loss_main`` and ``loss_mtp`` each against the
  reference's, the main stack's and the module's final normed states, the
  counters (``moe_dropped`` has to read 0);
- **every layer's state and the module's**: the program's own ``Block``
  modules applied one layer after another on the program's own state, then
  the program's own final norm, ``MtpMerge`` and the module's block, each
  held to the reference's chain as a relative root-mean-square error (one
  number a layer, the module's last) and as the largest single TOKEN's
  relative error over all of them;
- **latent attention on equal inputs** (the attention sub-layer hands out
  its normed input, both normed latents, q and the key's shared vector as
  the kernels got them, and the kernels' result: ``models/transformer.py``
  sows ``mla_*`` where ``intermediates`` is mutable), each as the worst single
  POSITION's relative error against the reference's arithmetic on the
  program's own input and weights: ``c_q`` and ``c_kv``
  (``mla_latent_token_rel_max``); the rotated 64 of every q head and the
  key's rotated vector (``mla_rotated_token_rel_max``: a wrong pairing, or a
  head's rotation given to the shared key, is wrong at every position but 0,
  a rotation of the wrong lanes likewise); the attention's result before
  ``W_o`` from the program's own q and key vector
  (``mla_attn_token_rel_max``: a softmax scale of ``128 ** -0.5`` shows
  here);
- **routing is discrete.** The expert layer hands out what it routed on
  (``router_in``, ``router_logits``, ``chosen``). The router's float32 logits
  are held to the reference's arithmetic on EQUAL inputs, as the largest
  difference over the largest logit. **The selection is held to the
  program's own logits and bias** (``chosen_not_top8_share``, has to read 0):
  no unchosen expert's ``sigmoid(logit) + b`` may lie above a chosen one's by
  more than ``TOP8_MARGIN``. The tokens whose chosen set differs between the
  program (on its bf16 states) and the reference (on its float32 states) are
  counted and their share bounded; the reference's layer is then evaluated
  with the program's sets, weights from its own scores;
- on the same whole sequences the gradient of the bundle's loss, per leaf in
  the REFERENCE's layout (``to_reference``, a linear map), as the whole
  gradient and as the worst leaf — every leaf, none left out; the selection
  biases', whose reference gradient is exactly zero, have to be exactly
  zero;
- the reference is evaluated piece by piece (``reference_joyai.Pieces``), one
  sequence at a time: its score matrix is 268 MB a head;
- tolerances live in the configuration file under ``check`` with the error
  measured on the chip when they were set and the reason for each.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List

import numpy as np

from . import reference_joyai as ref
from .check_zaya import _gradient_errors, _rel_errors

#: a chosen expert's float32 ``sigmoid(logit) + b`` may lie this far under an
#: unchosen one's and still count among the largest: sixteen roundings at 0.5
TOP8_MARGIN = 1e-6


def not_top_k(logits, bias, chosen):
    """How many tokens' ``chosen [T, k]`` are NOT ``k`` distinct experts
    with the largest ``sigmoid(logits) + bias`` of ``logits [T, E]``, written
    out: the smallest chosen score against the largest unchosen one."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(logits.astype(jnp.float32)) \
        + bias.astype(jnp.float32)
    taken = jnp.any(chosen[..., None] == jnp.arange(scores.shape[-1]), -2)
    least = jnp.min(jnp.where(taken, scores, jnp.inf), -1)
    best_left = jnp.max(jnp.where(taken, -jnp.inf, scores), -1)
    wrong = (jnp.sum(taken, -1) != chosen.shape[-1]) \
        | (best_left > least + TOP8_MARGIN)
    return jnp.sum(wrong)


def layer_to_reference(one: Any) -> Dict[str, Any]:
    """One layer's (unstacked) leaves of the program's tree under the
    reference's names."""
    out = {"n1": one["ln_attn"]["scale"], "n2": one["ln_mlp"]["scale"],
           "wqa": one["q_a"]["kernel"], "qn": one["q_norm"],
           "wqb": one["q_b"]["kernel"], "wkva": one["kv_a"]["kernel"],
           "kvn": one["kv_norm"], "wkvb": one["kv_b"],
           "wo": one["out"]["kernel"]}
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


def _runs(params: Any) -> List[str]:
    """The names of the stacked runs of layers, in order."""
    return sorted((k for k in params if k.startswith("blocks")),
                  key=lambda k: int(k.split("_")[1]) if "_" in k else 0)


def to_reference(params: Any) -> Dict[str, Any]:
    """The program's (unboxed) parameter tree under the reference's names.
    With :func:`layer_to_reference` the only place that knows how
    ``models/transformer.py`` names things: one run of layers a
    ``blocks_<i>``, stacked on a leading axis; the module's leaves beside
    them."""
    import jax

    layers = []
    for name in _runs(params):
        run = params[name]
        layers += [layer_to_reference(jax.tree.map(lambda a: a[j], run))
                   for j in range(run["q_a"]["kernel"].shape[0])]
    merge = params["mtp_merge"]
    return {"wte": params["tok_emb"]["embedding"],
            "head": params["head"]["kernel"],
            "lnf_g": params["ln_f"]["scale"], "layers": layers,
            "mtp": {"ne": merge["ln_emb"]["scale"],
                    "nh": merge["ln_state"]["scale"],
                    "w_eh": merge["join"]["kernel"],
                    "nf": params["mtp_ln_f"]["scale"],
                    "layer": layer_to_reference(params["mtp_block"])}}


def _program_layers(cfg, params) -> List[Any]:
    """``[(ffn, one layer's parameters)]`` of the main stack in order, from
    the stacked runs of the program's (unboxed) tree."""
    import jax

    at = jax.jit(lambda tree, j: jax.tree.map(lambda a: a[j], tree))
    out = []
    for name, ((_, ffn), count) in zip(_runs(params), cfg.runs):
        out += [(ffn, at(params[name], j)) for j in range(count)]
    return out


def _routing_errors(routed, p_layer, own):
    """Of an expert layer with the program's leaves ``p_layer`` (its bf16
    copy): the float32 logits against the reference's arithmetic on the
    program's own inputs (largest difference over largest logit), the tokens
    whose chosen set is not the largest of the program's own logits and
    bias, and the tokens whose set is not the reference's ``own``."""
    import jax.numpy as jnp

    moe = p_layer["moe"]
    logits = jnp.einsum(
        "td,de->te", routed["router_in"].astype(jnp.float32),
        moe["router"].astype(jnp.float32), precision=ref.HIGHEST)
    chosen = routed["chosen"]
    differ = jnp.any(jnp.sort(own.reshape(chosen.shape), -1)
                     != jnp.sort(chosen, -1), -1)
    return (jnp.max(jnp.abs(routed["router_logits"] - logits))
            / jnp.max(jnp.abs(logits)),
            not_top_k(routed["router_logits"], moe["router_bias"], chosen),
            jnp.sum(differ))


def _worst_position(mine, want):
    """The worst single position's relative error of ``mine`` against
    ``want``, each a list of ``[1, S, ...]`` arrays taken side by side."""
    import jax.numpy as jnp

    seq = want[0].shape[1]

    def flat(xs):
        return jnp.concatenate(
            [x.astype(jnp.float32).reshape(seq, -1) for x in xs], -1)

    mine, want = flat(mine), flat(want)
    return jnp.sqrt(jnp.max(jnp.sum((mine - want) ** 2, -1)
                            / jnp.sum(want ** 2, -1)))


def _mla_errors(kept, p_layer, hp):
    """The attention sub-layer's parts on equal inputs: ``(latents, rotated
    parts, the attention's result)``, each the worst position's relative
    error of what the program's layer handed out (``kept``) against the
    reference's arithmetic on the program's own normed input and weights
    (``p_layer``: the program's leaves, its bf16 copy)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    p = {k: v.astype(f32) for k, v in layer_to_reference(p_layer).items()
         if k in ref.ATTENTION}
    nope = hp["nope"]
    c_q, c_kv, k_rot = ref.latents(kept["mla_in"].astype(f32), p, hp)
    latent = _worst_position([kept["mla_cq"], kept["mla_ckv"]], [c_q, c_kv])
    # from the program's own latents on: the rotation alone, then the scores
    q, k_rot = ref.rotated_parts(kept["mla_cq"].astype(f32), k_rot, p, hp)
    rotated = _worst_position(
        [kept["mla_q"][..., nope:], kept["mla_k_rot"]],
        [q[..., nope:], k_rot])
    kv = ref.product("bsr,rhk->bshk", kept["mla_ckv"].astype(f32), p["wkvb"])
    attn = ref.attention_core(
        kept["mla_q"].astype(f32), kv[..., :nope],
        kept["mla_k_rot"].astype(f32), kv[..., nope:])
    return latent, rotated, _worst_position([kept["mla_attn"]], [attn])


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
    from easydl_tpu.models.joyai import MLA, describe

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
        out = model.apply({"params": cast_floating(params, dtype)}, tokens,
                          return_hidden=True)
        return out.hidden[:n], out.mtp[:n]

    @functools.partial(jax.jit, static_argnums=0)
    def program_layer(ffn, p, x, rope):
        """One of the program's own blocks, what its attention held and
        what its expert layer routed on."""
        (y, _), kept = transformer.Block(cfg, MLA, ffn).apply(
            {"params": p}, x, True, rope, mutable=["intermediates"])
        kept = kept["intermediates"]
        return y, {k: v[0] for k, v in kept.get("moe", {}).items()}, \
            {k: v[0] for k, v in kept.items() if k.startswith("mla_")}

    @jax.jit
    def program_merge(p, x_last, tokens):
        """The program's own final norm and ``MtpMerge`` on its own last
        state and its own embedding of the next tokens."""
        h = transformer._norm(cfg, None, dtype=dtype).apply(
            {"params": p["ln_f"]}, x_last)
        emb = jnp.take(p["tok_emb"]["embedding"],
                       transformer._next_tokens(tokens), axis=0)
        return transformer.MtpMerge(cfg).apply(
            {"params": p["mtp_merge"]}, emb, h)

    rel_errors = jax.jit(_rel_errors)
    routing_errors = jax.jit(_routing_errors)
    mla_errors = jax.jit(functools.partial(_mla_errors, hp=hp))
    t_start = time.perf_counter()
    tables = jax.jit(lambda: cfg.attention_kind(MLA).rope.tables(
        seq, cfg.head_dim))()
    errors: Dict[str, Any] = {}
    with jax.set_mesh(mesh):
        params = jax.jit(bundle.init_fn,
                         out_shardings=trainer.state_shardings().params)(rng)
        whole = batch_of(window)
        (loss_p, metrics), grads_p = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True))(params, whole, rng)
        final_p, final_mtp_p = jax.device_put(
            jax.jit(program_final)(params, whole["inputs"]), dev0)
    counters = {name: float(metrics[name])
                for name in cfg.counters + ("loss_main", "loss_mtp")}
    errors["moe_dropped"] = counters["moe_dropped"]
    took = {"program_s": time.perf_counter() - t_start}

    one = functools.partial(jax.device_put, device=dev0)
    unboxed = jax.tree.map(one, shd.unbox(params))
    plain = jax.jit(to_reference)(unboxed)
    cast = jax.jit(functools.partial(cast_floating, dtype=dtype))(unboxed)
    del params, unboxed
    layers_p = _program_layers(cfg, cast) + [("moe", cast["mtp_block"])]
    layers_r = plain["layers"] + [plain["mtp"]["layer"]]
    tables = jax.tree.map(one, tables)
    pieces = ref.Pieces(hp)
    n_main = cfg.n_layers
    state_sq = np.zeros((2, n_main + 1))  # squared error and norm, by layer
    final_sq, final_mtp_sq = np.zeros(2), np.zeros(2)
    token_rel_max = logits_rel = 0.0
    mla_worst = np.zeros(3)
    differ = not_top8 = tokens_routed = 0
    loss_r = []
    for i, row in enumerate(window):  # one sequence at a time
        tokens, targets = one(row[None, :-1]), one(row[None, 1:])
        x_p = jnp.take(cast["tok_emb"]["embedding"], tokens, axis=0)
        x_r = plain["wte"][tokens]
        for l, ((ffn, p_p), p_r) in enumerate(zip(layers_p, layers_r)):
            if l == n_main:  # the module: both sides join their own states
                last_r = x_r
                x_p = program_merge(cast, x_p, tokens)
                x_r = pieces.join(
                    x_r, plain["wte"][ref.next_tokens(tokens)],
                    plain["lnf_g"], ref._leaves(plain["mtp"], ref.MERGE))
            x_p, routed, kept = program_layer(ffn, p_p, x_p, tables)
            chosen = routed["chosen"].reshape(1, seq, -1) if routed else None
            x_r, _, own, _ = pieces.layer(x_r, p_r, chosen)
            if routed:
                off, wrong, other = jax.device_get(routing_errors(
                    routed, p_p, own))
                logits_rel = max(logits_rel, float(off))
                not_top8 += int(wrong)
                differ += int(other)
                tokens_routed += seq
            mla_worst = np.maximum(mla_worst, jax.device_get(
                mla_errors(kept, p_p)))
            gap, size, token = jax.device_get(rel_errors(x_p, x_r))
            state_sq[:, l] += gap, size
            token_rel_max = max(token_rel_max, float(token))
        h_r = pieces.norm(last_r, plain["lnf_g"])
        final_sq += jax.device_get(rel_errors(final_p[i:i + 1], h_r))[:2]
        h_r = pieces.norm(x_r, plain["mtp"]["nf"])
        final_mtp_sq += jax.device_get(
            rel_errors(final_mtp_p[i:i + 1], h_r))[:2]
        later, valid = ref.later_targets(targets)
        loss_r.append([
            float(pieces.head(last_r, {"g": plain["lnf_g"],
                                       "head": plain["head"]}, targets,
                              jnp.ones(targets.shape, bool))),
            float(pieces.head(x_r, {"g": plain["mtp"]["nf"],
                                    "head": plain["head"]}, later, valid))])
        del x_p, x_r, h_r, last_r
    main_r, mtp_r = (float(x) for x in np.mean(loss_r, 0))
    loss_r = main_r + hp["lam"] * mtp_r
    errors["loss_abs"] = abs(float(loss_p) - loss_r)
    errors["loss_main_abs"] = abs(counters["loss_main"] - main_r)
    errors["loss_mtp_abs"] = abs(counters["loss_mtp"] - mtp_r)
    for l in range(n_main):
        errors[f"state_rel_rms_layer_{l}"] = float(
            np.sqrt(state_sq[0, l] / state_sq[1, l]))
    errors["state_rel_rms_mtp_layer"] = float(
        np.sqrt(state_sq[0, n_main] / state_sq[1, n_main]))
    errors["state_rel_rms_final"] = float(np.sqrt(final_sq[0] / final_sq[1]))
    errors["state_rel_rms_mtp_final"] = float(
        np.sqrt(final_mtp_sq[0] / final_mtp_sq[1]))
    errors["token_rel_max"] = token_rel_max
    errors["router_logits_rel"] = logits_rel
    errors["mla_latent_token_rel_max"] = float(mla_worst[0])
    errors["mla_rotated_token_rel_max"] = float(mla_worst[1])
    errors["mla_attn_token_rel_max"] = float(mla_worst[2])
    errors["chosen_not_top8_share"] = not_top8 / max(tokens_routed, 1)
    errors["chosen_sets_differ_share"] = differ / max(tokens_routed, 1)
    del final_p, final_mtp_p, cast, layers_p
    took["states_s"] = time.perf_counter() - t_start - took["program_s"]
    mine = jax.jit(to_reference)(jax.tree.map(one, shd.unbox(grads_p)))
    del grads_p
    # one sequence at a time; the loss is their mean
    _, grads_r, _ = pieces.loss_and_grads(
        plain, one(window[:, :-1]), one(window[:, 1:]), by_row=True)
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
              "reference_loss_main": main_r, "reference_loss_mtp": mtp_r,
              "took": took}
    finite = all(np.isfinite(v) for v in errors.values()
                 if isinstance(v, float))
    ok = finite and all(errors[k] <= tol for k, tol in tolerances.items())
    return {"ok": bool(ok), "errors": errors, "tolerances": tolerances,
            "counters": counters, **values}
