"""The comparison that decides ``correct`` for a ZAYA1 configuration: the
program on seeded sequences of the configuration's length at the published
widths against ``reference_zaya`` (float32, Python loops over layers and
experts, whole score matrices, the shifts written as pads), both holding the
same share: the experts ``kwargs.experts_held`` and the sliced vocabulary.
Runs before the trainer's state exists and keeps nothing on the device
afterwards: the step program of the cell fills the chip.

What is compared, and how:

- on ``check.sequences`` whole sequences, given to the program as ONE batch
  through the model bundle's own loss (the fused tied head where the
  program's rule picks it): the loss, the final normed state, the counters
  (``moe_dropped`` has to read 0);
- **every layer's state and router state**: the program's own ``Block``
  modules applied one layer after another on the program's own carry — the
  residual stream AND the router state that runs through the depth — each
  held to the reference's layer chain as a relative root-mean-square error
  (one number a layer for the stream, the worst layer's for the router
  state), and as the largest single TOKEN's relative error over all layers;
- **routing is discrete.** The expert layer hands out what it routed on
  (``router_in``, ``router_state_in``, ``router_logits``, ``chosen``:
  ``ops/moe.py`` sows them where ``intermediates`` is mutable). The router's
  float32 logits are held to the reference's arithmetic on EQUAL inputs (the
  program's own normed input, incoming state and router weights), as the
  largest difference over the largest logit (seeded logits are a few
  hundredths: an absolute limit would let bf16 logits through). **The
  choice is held to the program's own logits** (``chosen_not_top1_share``,
  has to read 0): no probability may lie above the chosen one's by more than
  ``TOP1_MARGIN``. The tokens whose choice differs between the program (on
  its bf16 states) and the reference (on its float32 states) are counted and
  their share bounded; the reference's layer is then evaluated with the
  program's choices, weights from its own probabilities, so that one
  near-tie does not swamp the comparison of states;
- **the convolutions and the value shift on equal inputs**: the attention
  sub-layer hands out its normed input and the q, k, v it gives the kernels
  (``latent_in``, ``latent_q``, ``latent_k``, ``latent_v``:
  ``models/transformer.py`` sows them likewise); the reference's
  ``mixed_qk`` and ``values`` on that input, as the worst single POSITION's
  relative error (``cca_mix_token_rel_max``): a shift that wraps round
  instead of padding is wrong at position 0 alone, one in 8,192, and no mean
  shows it;
- on the same whole sequences the gradient of the bundle's loss, per leaf in
  the REFERENCE's layout (``to_reference``, a linear map), as the whole
  gradient and as the worst leaf — every leaf, none left out for being
  small; a leaf whose reference gradient is exactly zero (layer 0's
  ``r_gamma``: it multiplies zeros) has to be exactly zero;
- the reference is evaluated piece by piece (``reference_zaya.Pieces``), one
  sequence at a time: its score matrix is 268 MB a head;
- tolerances live in the configuration file under ``check`` with the error
  measured on the chip when they were set and the reason for each.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List

import numpy as np

from . import reference_zaya as ref

#: another choice's float32 probability may lie this far above the chosen
#: one's and the chosen still count as the largest
TOP1_MARGIN = 1e-6


def not_top_1(logits, chosen):
    """How many tokens' ``chosen [T, 1]`` is NOT a choice with the largest
    softmax probability of ``logits [T, C]``."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    mine = jnp.take_along_axis(probs, chosen, -1)[:, 0]
    return jnp.sum(jnp.max(probs, -1) > mine + TOP1_MARGIN)


def _rel_errors(x_p, x_r):
    """Squared error and squared size of ``x_p`` against the reference's
    ``x_r`` ``[1, S, ...]``, and the largest single position's relative
    error."""
    import jax.numpy as jnp

    x_p = x_p.astype(jnp.float32).reshape(x_r.shape[1], -1)
    x_r = x_r.reshape(x_r.shape[1], -1)
    gap = jnp.sum((x_p - x_r) ** 2, -1)
    size = jnp.sum(x_r ** 2, -1)
    return jnp.sum(gap), jnp.sum(size), jnp.sqrt(jnp.max(gap / size))


def _gradient_errors(grads, grads_ref):
    """Relative root-mean-square error of every leaf (0 where the
    reference's is exactly zero and the program's too, infinite where only
    the reference's is), and of the whole gradient as one vector."""
    import jax
    import jax.numpy as jnp

    sq_err = jax.tree.map(
        lambda a, r: jnp.sum((a.astype(jnp.float32) - r) ** 2),
        grads, grads_ref)
    sq_ref = jax.tree.map(lambda r: jnp.sum(r ** 2), grads_ref)
    per_leaf = jax.tree.map(
        lambda e, r: jnp.where(r > 0, jnp.sqrt(e / jnp.where(r > 0, r, 1.0)),
                               jnp.where(e > 0, jnp.inf, 0.0)),
        sq_err, sq_ref)
    overall = jnp.sqrt(sum(jax.tree.leaves(sq_err))
                       / sum(jax.tree.leaves(sq_ref)))
    return per_leaf, overall


def layer_to_reference(one: Any) -> Dict[str, Any]:
    """One layer's (unstacked) leaves of the program's tree under the
    reference's names."""
    import jax.numpy as jnp

    moe = one["moe"]

    def res(ln):
        return jnp.stack([one[f"{ln}_res_{name}"] for name in (
            "scale_x", "bias_x", "scale_y", "bias_y")])

    return {"n1": one["ln_attn"]["scale"], "n2": one["ln_mlp"]["scale"],
            "wq": one["q"]["kernel"], "wk": one["k"]["kernel"],
            "wv": one["v"]["kernel"], "wo": one["out"]["kernel"],
            "conv0": one["conv0"], "conv0_b": one["conv0_bias"],
            "conv1": one["conv1"], "conv1_b": one["conv1_bias"],
            "tau": one["temperature"],
            "res_a": res("ln_attn"), "res_m": res("ln_mlp"),
            "r_down": moe["router_down"], "r_down_b": moe["router_down_bias"],
            "r_gamma": moe["router_gamma"], "r_norm": moe["router_norm"],
            "r_w1": moe["router_w1"], "r_b1": moe["router_b1"],
            "r_w2": moe["router_w2"], "r_b2": moe["router_b2"],
            "r_w3": moe["router_w3"],
            "e_gate": moe["w_gate"], "e_up": moe["w_up"],
            "e_down": moe["w_down"]}


def to_reference(params: Any) -> Dict[str, Any]:
    """The program's (unboxed) parameter tree under the reference's names.
    With :func:`layer_to_reference` the only place that knows how
    ``models/transformer.py`` names things: the layers are one run,
    ``blocks``, stacked on a leading axis."""
    import jax

    run = params["blocks"]
    return {"wte": params["tok_emb"]["embedding"],
            "lnf_g": params["ln_f"]["scale"],
            "layers": [layer_to_reference(jax.tree.map(lambda a: a[j], run))
                       for j in range(run["q"]["kernel"].shape[0])]}


def _program_layers(cfg, params) -> List[Any]:
    """One layer's parameters at a time, in order, from the program's
    (unboxed) stacked run."""
    import jax

    at = jax.jit(lambda tree, j: jax.tree.map(lambda a: a[j], tree))
    return [at(params["blocks"], j) for j in range(cfg.n_layers)]


def _routing_errors(routed, p_r, own, hp):
    """Of an expert layer with the program's leaves ``p_r`` (its bf16 copy):
    the float32 logits against the reference's arithmetic on the program's
    own inputs and weights (largest difference over largest logit), the tokens whose choice is not
    the largest of the program's own logits, and the tokens whose choice is
    not the reference's ``own``."""
    import jax.numpy as jnp

    f32 = jnp.float32
    p_r = layer_to_reference(p_r)
    _, logits, _ = ref.router(
        routed["router_in"].astype(f32)[None],
        routed["router_state_in"].astype(f32)[None],
        {k: p_r[k].astype(f32) for k in ref.ROUTER}, hp)
    chosen = routed["chosen"]
    return (jnp.max(jnp.abs(routed["router_logits"] - logits[0]))
            / jnp.max(jnp.abs(logits)),
            not_top_1(routed["router_logits"], chosen),
            jnp.sum(own.reshape(-1) != chosen[:, 0]))


def _mix_error(mixed, p_r):
    """The program's q, k, v in front of the rotation against the
    reference's on the program's own normed input and weights (``p_r``: the
    program's leaves, its bf16 copy): the worst position's relative
    error."""
    import jax.numpy as jnp

    f32 = jnp.float32
    p_r = {k: v.astype(f32) for k, v in layer_to_reference(p_r).items()
           if k in ref.ATTENTION}
    h = mixed["latent_in"].astype(f32)
    q, k = ref.mixed_qk(h, p_r)
    v = ref.values(h, p_r["wv"])
    seq = h.shape[1]

    def flat(*xs):
        return jnp.concatenate([x.astype(f32).reshape(seq, -1) for x in xs],
                               -1)

    mine = flat(mixed["latent_q"], mixed["latent_k"], mixed["latent_v"])
    want = flat(q, k, v)
    return jnp.sqrt(jnp.max(jnp.sum((mine - want) ** 2, -1)
                            / jnp.sum(want ** 2, -1)))


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
    from easydl_tpu.models.zaya import describe

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
    kind, = {mixer for mixer, _ in cfg.pattern}

    # Everything that differs from seed to seed is an ARGUMENT of the jitted
    # functions below, never a constant closed over.
    def program_loss(params, batch, rng):
        loss, metrics = bundle.loss_fn(cast_floating(params, dtype), batch,
                                       rng)
        return loss.astype(jnp.float32), metrics

    def program_final(params, tokens):
        return model.apply({"params": cast_floating(params, dtype)}, tokens,
                           return_hidden=True)[:n]

    @jax.jit
    def program_layer(p, carry, rope):
        """One of the program's own blocks on its own carry, and what its
        attention mixed and its expert layer routed on."""
        (carry, _), kept = transformer.Block(cfg, kind, "moe").apply(
            {"params": p}, carry, True, rope, mutable=["intermediates"])
        kept = kept["intermediates"]
        return carry, {k: v[0] for k, v in kept["moe"].items()}, \
            {k: v[0] for k, v in kept.items() if k.startswith("latent_")}

    rel_errors = jax.jit(_rel_errors)
    routing_errors = jax.jit(functools.partial(_routing_errors, hp=hp))
    mix_error = jax.jit(_mix_error)
    t_start = time.perf_counter()
    scheme = cfg.attention_kind(kind).rope
    tables = jax.jit(lambda: transformer.rope_tables(
        seq, cfg.head_dim, scheme.theta, scheme.rotary_dim or None))()
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
                for name in cfg.counters + ("router_state_rms",)}
    errors["moe_dropped"] = counters["moe_dropped"]
    took = {"program_s": time.perf_counter() - t_start}

    one = functools.partial(jax.device_put, device=dev0)
    unboxed = jax.tree.map(one, shd.unbox(params))
    plain = jax.jit(to_reference)(unboxed)
    cast = jax.jit(functools.partial(cast_floating, dtype=dtype))(unboxed)
    del params, unboxed
    layers_p = _program_layers(cfg, cast)
    tables = jax.tree.map(one, tables)
    pieces = ref.Pieces(hp)
    n_layers = cfg.n_layers
    state_sq = np.zeros((2, n_layers))   # squared error and norm, by layer
    router_sq = np.zeros((2, n_layers))
    final_sq = np.zeros(2)
    token_rel_max = logits_rel = mix_rel_max = 0.0
    differ = not_top1 = tokens_routed = 0
    loss_r = []
    for i, row in enumerate(window):  # one sequence at a time
        tokens = one(row[None, :-1])
        x_p = jnp.take(cast["tok_emb"]["embedding"], tokens, axis=0)
        carry = (x_p, jnp.zeros((1, seq, cfg.router_state_width),
                                jnp.float32))
        x_r, r_r = plain["wte"][tokens], carry[1]
        for l, (p_p, p_r) in enumerate(zip(layers_p, plain["layers"])):
            carry, routed, mixed = program_layer(p_p, carry, tables)
            chosen = routed["chosen"].reshape(1, seq)
            x_r, r_r, _, own, _ = pieces.layer(x_r, r_r, p_r, chosen)
            off, wrong, other = jax.device_get(routing_errors(
                routed, p_p, own))
            logits_rel = max(logits_rel, float(off))
            not_top1 += int(wrong)
            differ += int(other)
            tokens_routed += seq
            mix_rel_max = max(mix_rel_max, float(mix_error(mixed, p_p)))
            gap, size, token = jax.device_get(rel_errors(carry[0], x_r))
            state_sq[:, l] += gap, size
            token_rel_max = max(token_rel_max, float(token))
            router_sq[:, l] += jax.device_get(rel_errors(carry[1], r_r))[:2]
        h_r = pieces.norm(x_r, plain["lnf_g"])
        final_sq += jax.device_get(rel_errors(final_p[i:i + 1], h_r))[:2]
        loss_r.append(float(pieces.head(
            x_r, {"lnf_g": plain["lnf_g"], "wte": plain["wte"]},
            one(row[None, 1:]))))
        del carry, x_p, x_r, r_r, h_r
    loss_r = float(np.mean(loss_r))
    errors["loss_abs"] = abs(float(loss_p) - loss_r)
    for l in range(n_layers):
        errors[f"state_rel_rms_layer_{l}"] = float(
            np.sqrt(state_sq[0, l] / state_sq[1, l]))
    errors["router_state_rel_rms"] = float(
        np.max(np.sqrt(router_sq[0] / router_sq[1])))
    errors["state_rel_rms_final"] = float(np.sqrt(final_sq[0] / final_sq[1]))
    errors["token_rel_max"] = token_rel_max
    errors["router_logits_rel"] = logits_rel
    errors["cca_mix_token_rel_max"] = mix_rel_max
    errors["chosen_not_top1_share"] = not_top1 / max(tokens_routed, 1)
    errors["chosen_differ_share"] = differ / max(tokens_routed, 1)
    del final_p, cast, layers_p
    took["states_s"] = time.perf_counter() - t_start - took["program_s"]
    mine = jax.jit(to_reference)(jax.tree.map(one, shd.unbox(grads_p)))
    del grads_p
    # one sequence at a time; the loss is their mean
    _, grads_r = pieces.loss_and_grads(
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
              "took": took}
    finite = all(np.isfinite(v) for v in errors.values()
                 if isinstance(v, float))
    ok = finite and all(errors[k] <= tol for k, tol in tolerances.items())
    return {"ok": bool(ok), "errors": errors, "tolerances": tolerances,
            "counters": counters, **values}
