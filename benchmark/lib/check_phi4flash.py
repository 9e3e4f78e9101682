"""The comparison that decides ``correct`` for a Phi-4-mini-flash
configuration: the program on seeded sequences of the configuration's length
at the published widths against ``reference_phi4flash`` (float32 at the
highest precision, the recurrence position by position, every softmax over a
written-out mask), both holding the same published layers ``layer_ids`` and
the same slice of the tied vocabulary. Runs before the trainer's state
exists and keeps nothing on the device afterwards.

What is compared, and how:

- on ``check.sequences`` whole sequences, given to the program as ONE batch
  through the model bundle's own loss (the fused chunked head the step
  uses): the loss against the reference's, the final normed state, and the
  largest single token's relative error of the LOGITS;
- **every layer's state**: the program's own ``Block`` modules applied one
  after another on the program's own state, what one hands on (layer 16's
  scan output, layer 17's keys and values) given to the blocks behind it as
  the stack gives it, each held to the reference's chain as a relative
  root-mean-square error and as the worst single token's;
- **the parts on equal inputs** (the mixers hand out what they were given
  and gave: ``models/transformer.py`` sows ``scan_*``, ``diff_*``,
  ``gmu_memory`` where ``intermediates`` is mutable), each the worst single
  POSITION's relative error against the reference's arithmetic on the
  program's own operands and weights: the Mamba-1 mixer's operands (input
  map, convolution and SiLU, step sizes, B and C:
  ``scan_operands_token_rel_max``); the scan's ``y`` against the recurrence
  position by position (``scan_token_rel_max``); the memory as the unit
  receives it against the scan's output as layer 16 made it (``memory_abs``:
  the same array, 0); a differential head's output before and after the
  inner norm against two written-out softmaxes a pair on the program's own
  q, k, v (``diff_before_norm_`` / ``diff_out_token_rel_max``: the window's
  edge, the pairing, the value's halves and ``lambda_init`` show here) and,
  behind the norm, as the relative root-mean-square error over the sequence
  too (``diff_out_rel_rms``, which has the limit: the norm divides a pair's
  difference by its own size, so ONE pair whose two softmaxes nearly cancel
  at one position carries its bf16 rounding whole into that position's
  error, and the largest of 16,384 positions swings sevenfold by the seed);
  the cross layer's keys and values against layer 17's (``cross_kv_abs``:
  the same arrays, 0);
- the gradient of that loss on the same whole sequences (ONE call gives
  the loss and its gradient: one compile and one forward, not two), per
  leaf in the REFERENCE's layout (``to_reference``, a linear map: the
  program's split maps joined column by column into the published fused
  ones), as the whole gradient and as the worst UNIT — every leaf is in
  one: a leaf is a unit of its own, but an attention layer's four lambda
  vectors and inner gain are one together (:func:`_units`): the vectors'
  gradients are all multiples of ONE scalar, dL/dlambda, a sum of 42M terms
  of either sign, so their relative error is a ratio of two zero-mean
  numbers and has no bound (0.04-0.2 by the seed and 4.2 on one seed of
  nine), while the five together cannot vanish. A wrong dL/dlambda does
  not hide behind the gain's gradient there: at the published widths the
  vectors' gradient is 0.2 to 13 times the gain's (0.03 in one layer of
  that ninth seed), and its sign turned reads 1.0 to 1.9 in every
  attention layer's unit (``dlambda_flipped``: the configuration file's
  ``check.measured``);
- tolerances live in the configuration file under ``check`` with the error
  measured on the chip when they were set and the reason for each.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List

import numpy as np

from . import reference_phi4flash as ref
from .check_joyai import _runs, _worst_position
from .check_zaya import _gradient_errors, _rel_errors


def layer_to_reference(one: Any, kind: str) -> Dict[str, Any]:
    """One layer's (unstacked) leaves of the program's tree under the
    reference's names, the split maps joined into the published fused ones."""
    import jax.numpy as jnp

    def flat(a, keep):
        return a.reshape(a.shape[:keep] + (-1,))

    ln = one["ln_ssm" if kind in ("mamba", "gmu") else "ln_attn"]
    p = {"ln1_g": ln["scale"], "ln1_b": ln["bias"],
         "ln2_g": one["ln_mlp"]["scale"], "ln2_b": one["ln_mlp"]["bias"],
         "w1": jnp.concatenate([one["gate"]["kernel"], one["up"]["kernel"]],
                               axis=1),
         "w2": one["down"]["kernel"]}
    out = one["out"]["kernel"]
    if kind == "mamba":
        p.update(
            in_proj=jnp.concatenate([flat(one["in_x"]["kernel"], 1),
                                     flat(one["in_z"]["kernel"], 1)], axis=1),
            conv_w=flat(one["conv_x"], 1), conv_b=flat(one["conv_x_bias"], 0),
            x_proj=one["x_proj"]["kernel"].reshape(
                -1, one["x_proj"]["kernel"].shape[-1]),
            dt_proj=one["dt_proj"]["kernel"], dt_bias=one["dt_bias"],
            A_log=one["A_log"], D=one["D"],
            out_proj=out.reshape(-1, out.shape[-1]))
    elif kind == "gmu":
        p.update(gmu_in=flat(one["in_gate"]["kernel"], 1),
                 gmu_out=out.reshape(-1, out.shape[-1]))
    else:
        names = ("q",) if kind == "cross" else ("q", "k", "v")
        w = jnp.concatenate([flat(one[n]["kernel"], 1) for n in names], 1)
        b = jnp.concatenate([flat(one[n]["bias"], 0) for n in names])
        p["wq" if kind == "cross" else "wqkv"] = w
        p["bq" if kind == "cross" else "bqkv"] = b
        p.update({name: one[name] for name in (
            "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")})
        p.update(subln=one["sub_norm"], wo=out.reshape(-1, out.shape[-1]),
                 bo=one["out"]["bias"])
    return p


def _layers(cfg, params) -> List[Any]:
    """One layer's parameters each, in order, from the stacked runs."""
    import jax

    return [jax.tree.map(lambda a: a[j], params[name])
            for name, (_, count) in zip(_runs(params), cfg.runs)
            for j in range(count)]


def to_reference(params: Any, cfg, hp) -> Dict[str, Any]:
    """The program's (unboxed) parameter tree under the reference's names
    and layouts. With :func:`layer_to_reference` the only place that knows
    how ``models/transformer.py`` names things."""
    return {"wte": params["tok_emb"]["embedding"],
            "lnf_g": params["ln_f"]["scale"], "lnf_b": params["ln_f"]["bias"],
            "layers": [layer_to_reference(one, ref.kind_of(i, hp))
                       for one, i in zip(_layers(cfg, params),
                                         hp["layer_ids"])]}


#: an attention layer's differential parameters, one unit of the gradient's
#: comparison (the module's docstring)
_DIFF_UNIT = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln")


def _units(tree: Dict[str, Any]) -> Dict[str, Any]:
    """A gradient in the reference's layout with each attention layer's
    :data:`_DIFF_UNIT` leaves joined into one, ``diff``."""
    import jax.numpy as jnp

    def joined(layer):
        if _DIFF_UNIT[0] not in layer:
            return layer
        rest = {k: v for k, v in layer.items() if k not in _DIFF_UNIT}
        return dict(rest, diff=jnp.concatenate(
            [layer[k].reshape(-1) for k in _DIFF_UNIT]))
    return dict(tree, layers=[joined(layer) for layer in tree["layers"]])


def _part_errors(kind, layer_id, kept, p, hp, earlier):
    """The parts of one layer on equal inputs (the module's docstring):
    ``{name: error}``. ``kept``: what the program's mixer handed out; ``p``:
    the layer's weights as the program computed with them (its bf16 copy),
    in the reference's layout; ``earlier``: what the givers in front of it
    handed out."""
    import jax.numpy as jnp

    f32 = jnp.float32
    p = {k: v.astype(f32) for k, v in p.items()}
    mine = {k: v.astype(f32)[0] for k, v in kept.items()}
    seq = next(iter(mine.values())).shape[0]
    flat = lambda a: a.reshape(seq, -1)  # noqa: E731
    worst = lambda a, b: _worst_position([a[None]], [b[None]])  # noqa: E731
    out = {}
    if kind == "mamba":
        x, z, dt, B, C = ref.mamba_operands(mine["scan_in"], p, hp)
        out["scan_operands_token_rel_max"] = _worst_position(
            [flat(mine[f"scan_{n}"])[None] for n in ("x", "z", "dt", "B", "C")],
            [a[None] for a in (x, z, dt, B, C)])
        y = ref.recurrence(flat(mine["scan_x"]), mine["scan_dt"],
                           -jnp.exp(p["A_log"]), mine["scan_B"],
                           mine["scan_C"], p["D"])
        out["scan_token_rel_max"] = worst(flat(mine["scan_y"]), y)
    elif kind == "gmu":
        out["memory_abs"] = jnp.max(jnp.abs(
            mine["gmu_memory"] - earlier["scan_y"].astype(f32)[0]))
    else:
        if kind == "cross":
            out["cross_kv_abs"] = jnp.maximum(*(jnp.max(jnp.abs(
                mine[f"diff_{n}"] - earlier[f"diff_{n}"].astype(f32)[0]))
                for n in "kv"))
        before, after = ref.diff_heads(
            mine["diff_q"], mine["diff_k"], mine["diff_v"],
            ref.lam_of(p, layer_id), p["subln"], layer_id, hp,
            hp["window"] if kind == "window" else None)
        out["diff_before_norm_token_rel_max"] = worst(
            flat(mine["diff_before_norm"]), flat(before))
        out["diff_out_token_rel_max"] = worst(flat(mine["diff_out"]),
                                              flat(after))
        out["diff_out_rel_rms"] = jnp.sqrt(
            jnp.sum((mine["diff_out"] - after) ** 2) / jnp.sum(after ** 2))
    return out


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
    from easydl_tpu.models.phi4flash import describe

    spec, kwargs = config["check"], config["kwargs"]
    seq, vocab = kwargs["seq_len"], kwargs["vocab"]
    hp = ref.hyper(config)
    cfg = describe(**kwargs)
    if tuple(kwargs["layer_ids"]) != hp["layer_ids"]:
        raise SystemExit(f"benchmark: the program holds the layers "
                         f"{kwargs['layer_ids']}, the configuration "
                         f"{list(hp['layer_ids'])}")
    kinds = [ref.kind_of(i, hp) for i in hp["layer_ids"]]
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

    @functools.partial(jax.jit, static_argnums=(0,))
    def program_block(i, p, x, handed):
        """The program's ``i``-th block on its own state and what the blocks
        in front of it handed on: ``(x, what it hands on, what its mixer
        held)``."""
        (mixer, ffn), (carried, gives) = cfg.pattern[i], layer_handoffs[i]
        carry = (x, {name: handed[name] for name in carried}) if carried \
            else x
        (y, aux), kept = transformer.Block(
            cfg, mixer, ffn, carried, gives).apply(
                {"params": p}, carry, True, None, mutable=["intermediates"])
        return (y[0] if carried else y, aux[1] if gives else {},
                {k: v[0] for k, v in kept["intermediates"].items()})

    # a layer's handoffs are its run's (every run here may be longer than
    # one layer: the last layer's gift is what the runs behind receive)
    layer_handoffs = [pair for pair, (_, count) in zip(cfg.handoffs, cfg.runs)
                      for _ in range(count)]
    ref_layer = {i: jax.jit(functools.partial(ref.layer, layer_id=i, hp=hp))
                 for i in hp["layer_ids"]}
    rel_errors = jax.jit(_rel_errors)
    parts = {(kind, i): jax.jit(functools.partial(
        _part_errors, kind, i, hp=hp)) for kind, i in zip(
            kinds, hp["layer_ids"])}
    final = jax.jit(lambda x, g, b: ref.layer_norm(x, g, b, hp["eps"]))
    head_loss = jax.jit(ref.cross_entropy)

    @jax.jit
    def logit_errors(final_p, wte_p, final_r, wte_r):
        """The worst token's relative error of the logits, rows in blocks."""
        def rows(args):
            h_p, h_r = args
            mine = jnp.dot(h_p, wte_p.T, preferred_element_type=jnp.float32)
            want = ref.logits_of(h_r, {"wte": wte_r})
            return jnp.max(jnp.sum((mine - want) ** 2, -1)
                           / jnp.sum(want ** 2, -1))
        r = min(ref.ROWS, seq)
        cut = lambda a: a.reshape(seq // r, r, -1)  # noqa: E731
        return jnp.sqrt(jnp.max(jax.lax.map(rows, (cut(final_p),
                                                   cut(final_r)))))

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
    counters = {name: float(metrics[name]) for name in (
        "kv_readers", "memory_readers", "sscan_chunks",
        "sscan_state_bytes_kept")}
    took = {"program_s": time.perf_counter() - t_start}

    one = functools.partial(jax.device_put, device=dev0)
    unboxed = jax.tree.map(one, shd.unbox(params))
    to_plain = jax.jit(functools.partial(to_reference, cfg=cfg, hp=hp))
    plain = to_plain(unboxed)
    cast = jax.jit(functools.partial(cast_floating, dtype=dtype))(unboxed)
    del params, unboxed
    layers_p = _layers(cfg, cast)
    cast_plain = [jax.jit(functools.partial(layer_to_reference, kind=kind))(p)
                  for p, kind in zip(layers_p, kinds)]
    n_layers = len(layers_p)
    state_sq = np.zeros((2, n_layers))
    final_sq = np.zeros(2)
    token_rel_max = logits_rel = 0.0
    part_worst: Dict[str, float] = {}
    loss_r = []
    for i, row in enumerate(window):  # one sequence at a time
        tokens, targets = one(row[None, :-1]), one(row[1:])
        x_p = jnp.take(cast["tok_emb"]["embedding"], tokens, axis=0)
        x_r = plain["wte"][tokens[0]]
        handed_p, handed_r, earlier = {}, {}, {}
        for b, (kind, layer_id) in enumerate(zip(kinds, hp["layer_ids"])):
            x_p, given_p, kept = program_block(b, layers_p[b], x_p, handed_p)
            handed_p = {**handed_p, **given_p}
            x_r, given_r = ref_layer[layer_id](x_r, plain["layers"][b],
                                               handed=handed_r)
            handed_r = {**handed_r, **given_r}
            for name, value in jax.device_get(parts[kind, layer_id](
                    kept, cast_plain[b], earlier=earlier)).items():
                part_worst[name] = max(part_worst.get(name, 0.0),
                                       float(value))
            # what a giver's mixer held, for the takers behind it
            if given_p:
                earlier = {**earlier, **{k: v for k, v in kept.items()
                                         if k in ("scan_y", "diff_k",
                                                  "diff_v")}}
            gap, size, token = jax.device_get(rel_errors(x_p, x_r[None]))
            state_sq[:, b] += gap, size
            token_rel_max = max(token_rel_max, float(token))
            del kept
        h_r = final(x_r, plain["lnf_g"], plain["lnf_b"])
        final_sq += jax.device_get(rel_errors(final_p[i:i + 1],
                                              h_r[None]))[:2]
        logits_rel = max(logits_rel, float(logit_errors(
            final_p[i], cast["tok_emb"]["embedding"], h_r, plain["wte"])))
        loss_r.append(float(head_loss(h_r, plain, targets)))
        del x_p, x_r, h_r, handed_p, handed_r, earlier
    loss_r = float(np.mean(loss_r))
    errors["loss_abs"] = abs(float(loss_p) - loss_r)
    for b in range(n_layers):
        errors[f"state_rel_rms_layer_{b}"] = float(
            np.sqrt(state_sq[0, b] / state_sq[1, b]))
    errors["state_rel_rms_final"] = float(np.sqrt(final_sq[0] / final_sq[1]))
    errors["token_rel_max"] = token_rel_max
    errors["logits_token_rel_max"] = logits_rel
    errors.update(part_worst)
    del final_p, cast, layers_p, cast_plain
    took["states_s"] = time.perf_counter() - t_start - took["program_s"]
    mine = to_plain(jax.tree.map(one, shd.unbox(grads_p)))
    del grads_p
    _, grads_r = ref.loss_and_grads(
        plain, one(window[:, :-1]), one(window[:, 1:]), hp)
    per_leaf, overall = jax.device_get(
        jax.jit(lambda a, b: _gradient_errors(_units(a), _units(b)))(
            mine, grads_r))
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
