"""The comparison that decides ``correct`` for an SDAR configuration trained by
block diffusion: the program on seeded sequences of the configuration's length
at the published widths against ``reference_sdar`` (float32, Python loops over
layers and experts, whole score rows under the mask written out from its four
rules), both holding the same share — the experts ``kwargs.experts_held`` and
the sliced vocabulary — and both under the SAME draw: the program's own
(``models/lm.py block_diffusion_noise`` of the key the loss is handed), which
the reference takes as ``masked`` and ``t``. Runs before the trainer's state
exists and keeps nothing on the device afterwards.

What is compared, and how (``lib/check_mellum.py``'s scheme):

- on ``check.sequences`` whole sequences, given to the program as ONE batch
  through the model bundle's own loss (which noises them inside): the loss,
  the final normed state of BOTH halves, the expert layers' counters
  (``moe_dropped`` has to read 0) and the objective's — the masked share the
  loss reports against the draw's own (``masked_share_abs``);
- **every layer's state, on both halves**: the program's own ``Block``
  modules applied one layer after another on the program's own states (the
  kernels under the block mask and the expert layer the step runs), each
  held to the reference's layer chain as a relative root-mean-square error, a
  number a layer, and as the largest single ROW's relative error;
- **routing is discrete**, held as Mellum 2's is (the float32 logits on
  equal inputs, the selection against the program's own logits, the share of
  rows whose chosen set differs from the reference's; the reference's layer
  then takes the program's sets);
- the parts the kernels decide alone, on equal inputs: both rotary tables
  with their repeated positions entry by entry, and **the mask** as the worst
  single POSITION's relative error of the attention's result before ``W_o``
  (``mask_position_rel_max``: unit-scale q, k and v through the path the
  step's layers take, all ``2 L`` rows, against the written-out mask) with
  the positions either side of a block's edge, of a kernel block's edge and
  of the halves' seam reported apart (``mask_edge_rel_max``): a row that sees
  one key too many or too few there moves by far more than any mean shows;
- the gradient of the bundle's loss, every leaf in the REFERENCE's layout
  (``to_reference``), as the whole gradient and as the worst leaf, on the
  whole sequences — under the sets that the gradient's OWN evaluation chose
  (:func:`routing_kept`). At seeded weights every masked row is nearly the
  mask token's one vector through all six layers, so the rows' near-ties at
  the eighth place are ONE near-tie, and two evaluations that differ by a
  bf16 rounding (the program's and the float32 reference's; the whole step's
  and a layer alone) flip it for all of them at once: the last layer's
  router, whose cotangent lives on the masked rows alone, read 0.05 to 0.85
  by the seed under the reference's own sets AND under the sets of the
  layer-by-layer pass (the first chip runs, PR 51);
- tolerances live in the configuration file under ``check`` with the error
  measured on the chip when they were set and the reason for each.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Dict

import numpy as np

from . import reference_sdar as ref
from .check_gpt2 import _gradient_errors
from .check_laguna import _program_layers, _state_errors
from .check_mellum import _routing_errors


@contextlib.contextmanager
def routing_kept(into: list):
    """While open, a ``Transformer.apply`` that keeps a mutable collection
    keeps ``intermediates`` too and appends them to ``into``: what the expert
    layers routed on (``ops/moe.py`` sows ``chosen``), out of the SAME
    evaluation as the loss around it — stacked by layer, as the scanned run
    hands them out. The check steers this here; the program has no option
    for it."""
    from easydl_tpu.models.transformer import Transformer

    apply = Transformer.apply

    def kept(self, variables, *args, mutable=False, **kwargs):
        if not mutable:
            return apply(self, variables, *args, mutable=mutable, **kwargs)
        out, state = apply(self, variables, *args,
                           mutable=[*mutable, "intermediates"], **kwargs)
        state = dict(state)
        into.append(state.pop("intermediates"))
        return out, state

    Transformer.apply = kept
    try:
        yield
    finally:
        Transformer.apply = apply


def to_reference(params: Any) -> Dict[str, Any]:
    """The program's (unboxed) parameter tree under the reference's names.
    The only place that knows how ``models/transformer.py`` names things:
    the one run of layers is ``blocks``, stacked on a leading axis."""
    run = params["blocks"]
    moe = run["moe"]
    layers = [{"n1": run["ln_attn"]["scale"][j],
               "n2": run["ln_mlp"]["scale"][j],
               "wq": run["q"]["kernel"][j], "wk": run["k"]["kernel"][j],
               "wv": run["v"]["kernel"][j], "wo": run["out"]["kernel"][j],
               "qn": run["q_norm"][j], "kn": run["k_norm"][j],
               "router": moe["router"][j], "e_gate": moe["w_gate"][j],
               "e_up": moe["w_up"][j], "e_down": moe["w_down"][j]}
              for j in range(run["q"]["kernel"].shape[0])]
    return {"wte": params["tok_emb"]["embedding"],
            "head": params["head"]["kernel"],
            "lnf_g": params["ln_f"]["scale"], "layers": layers}


def program_tables(cfg, seq: int):
    """The rotary tables the program's stack makes for ``2 seq`` rows (the
    same calls as ``models/transformer.py``'s), under ``jit`` as the step
    makes them."""
    import jax
    import jax.numpy as jnp

    (name, kind), = cfg.attention_kinds
    return jax.jit(lambda: {name: tuple(
        jnp.concatenate([table, table])
        for table in kind.rope.tables(seq, cfg.head_dim))})()


def _table_error(tables, hp, head_dim: int, seq: int) -> float:
    """Largest absolute difference between the program's rotary tables (the
    rotation's sign folded into the sine) and the reference's."""
    import jax
    import jax.numpy as jnp

    def worst(tables):
        (cos_p, sin_p), = tables.values()
        cos_r, sin_r = ref.rope_tables(seq, head_dim, hp["theta"])
        sign = jnp.where(jnp.arange(head_dim) < head_dim // 2, -1.0, 1.0)
        return jnp.maximum(jnp.max(jnp.abs(cos_p - cos_r)),
                           jnp.max(jnp.abs(sin_p * sign - sin_r)))

    return float(jax.jit(worst)(tables))


def edge_rows(seq: int, block: int, kernel_block: int = 512):
    """The rows either side of a mask block's edge, of a kernel block's edge
    and of the halves' seam, in both halves."""
    at = {0, block - 1, block, block + 1, seq - block - 1, seq - block,
          seq - 1}
    for edge in (kernel_block, seq // 2):
        at |= {edge - block - 1, edge - block, edge - 1, edge, edge + block - 1,
               edge + block}
    at = {p for p in at if 0 <= p < seq}
    return sorted(at | {p + seq for p in at})


def mask_position_errors(cfg, hp, seed: int, dtype, seq: int
                         ) -> Dict[str, float]:
    """The program's attention path at the layers' head shape on unit-scale
    q, k and v against the written-out mask, as each POSITION's relative
    error (over its heads): the worst of all ``2 seq`` rows and the worst of
    the edges' rows."""
    import jax
    import jax.numpy as jnp

    from easydl_tpu.ops import multihead_attention
    from easydl_tpu.ops.flash_attention import BlockDiffusion

    block = hp["block"]
    edges = jnp.array(edge_rows(seq, block))

    def error(key):
        kq, kk, kv = jax.random.split(key, 3)
        shape = (1, 2 * seq, cfg.n_heads, cfg.head_dim)
        kv_shape = (1, 2 * seq, cfg.kv_heads, cfg.head_dim)
        q, k, v = (jax.random.normal(kq, shape).astype(dtype),
                   jax.random.normal(kk, kv_shape).astype(dtype),
                   jax.random.normal(kv, kv_shape).astype(dtype))
        mine = multihead_attention(
            q, k, v, impl=cfg.attention_impl,
            mask=BlockDiffusion(block, seq)).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = ref.attention_core(
                *(x.astype(jnp.float32) for x in (q, k, v)),
                ref.block_mask(seq, block), hp["rows"])
        by_position = jnp.sqrt(jnp.sum((mine - want) ** 2, (0, 2, 3))
                               / jnp.sum(want ** 2, (0, 2, 3)))
        return jnp.max(by_position), jnp.max(by_position[edges])

    whole, edge = jax.jit(error)(jax.random.PRNGKey(seed))
    return {"mask_position_rel_max": float(whole),
            "mask_edge_rel_max": float(edge)}


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
    from easydl_tpu.models.lm import block_diffusion_noise
    from easydl_tpu.models.sdar import describe
    from easydl_tpu.models.transformer import Block, Transformer

    spec, kwargs = config["check"], config["kwargs"]
    seq, vocab = kwargs["seq_len"], kwargs["vocab"]
    hp = ref.hyper(config)
    cfg = describe(**kwargs)
    mesh = trainer.mesh
    dev0 = mesh.devices.flat[0]
    rows = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    n = int(spec["sequences"])
    x0 = np.random.default_rng(seed + 1).integers(
        0, vocab, (n, seq), dtype=np.int32)
    reps = 1 if n % rows == 0 else rows
    rng = jax.random.PRNGKey(seed)
    dtype = trainer.config.compute_dtype
    model = Transformer(cfg)
    (mixer, _), = {layer for layer in cfg.pattern}

    # Everything that differs from seed to seed is an ARGUMENT of the jitted
    # functions below, never a constant closed over.
    def program_loss(params, batch, rng):
        """The bundle's loss, its metrics and — out of the same evaluation —
        the sets its expert layers chose, ``[layers, sequences, rows, k]``."""
        kept = []
        with routing_kept(kept):
            loss, metrics = bundle.loss_fn(cast_floating(params, dtype),
                                           batch, rng)
        chosen, = kept[0]["blocks"]["moe"]["chosen"]
        chosen = chosen.reshape(chosen.shape[0], -1, 2 * seq,
                                chosen.shape[-1])
        return loss.astype(jnp.float32), (metrics, chosen[:, :n])

    def program_final(params, tokens):
        return model.apply({"params": cast_floating(params, dtype)}, tokens,
                           return_hidden=True)

    @jax.jit
    def program_layer(p, x, rope):
        """One of the program's own blocks, and what its expert layer
        routed on."""
        (y, _), kept = Block(cfg, mixer, "moe").apply(
            {"params": p}, x, True, rope, mutable=["intermediates"])
        return y, {k: v[0] for k, v in kept["intermediates"]["moe"].items()}

    draw = jax.jit(functools.partial(
        block_diffusion_noise, block=cfg.block_diffusion, mask_id=vocab - 1))
    state_errors = jax.jit(_state_errors)
    routing_errors = jax.jit(_routing_errors)
    t_start = time.perf_counter()
    tables = program_tables(cfg, seq)
    errors: Dict[str, Any] = {
        "rope_table_abs": _table_error(tables, hp, cfg.head_dim, seq),
        **mask_position_errors(cfg, hp, seed, dtype, seq),
    }
    with jax.set_mesh(mesh):
        params = jax.jit(bundle.init_fn,
                         out_shardings=trainer.state_shardings().params)(rng)
        whole = jax.tree.map(
            lambda x: jax.device_put(x, shd.batch_sharding(mesh)),
            {"inputs": np.tile(x0, (reps, 1)),
             "targets": np.tile(x0, (reps, 1))})
        (loss_p, (metrics, chosen_p)), grads_p = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True))(params, whole, rng)
        chosen_p = jax.device_put(chosen_p, dev0)
        # the draw the loss made: the same function of the same key and shape
        _, masked, t = draw(rng, whole["inputs"])
        masked, t = (jax.device_put(x[:n], dev0) for x in (masked, t))
        tokens = ref.rows_of(jax.device_put(x0, dev0), masked, hp["mask_id"])
        final_p = jax.device_put(
            jax.jit(program_final)(params, jnp.tile(tokens, (reps, 1)))[:n],
            dev0)
    counters = {name: float(metrics[name]) for name in cfg.counters + (
        "diffusion_masked_share", "diffusion_mean_t", "flash_live_pairs",
        "flash_block_pairs")}
    errors["moe_dropped"] = counters["moe_dropped"]
    errors["masked_share_abs"] = abs(counters["diffusion_masked_share"]
                                     - float(jnp.mean(masked)))
    took = {"program_s": time.perf_counter() - t_start}

    one = functools.partial(jax.device_put, device=dev0)
    unboxed = jax.tree.map(one, shd.unbox(params))
    plain = jax.jit(to_reference)(unboxed)
    cast = jax.jit(functools.partial(cast_floating, dtype=dtype))(unboxed)
    # three float32 trees are what fits beside the reference's work: the
    # reference's copy, the program's gradient and the reference's
    del params, unboxed
    layers_p = _program_layers(cfg, cast)
    rope = jax.tree.map(one, tables)[mixer]
    pieces = ref.Pieces(hp)
    n_layers = len(layers_p)
    state_sq = np.zeros((2, n_layers))   # squared error and norm, by layer
    final_sq = np.zeros(2)
    row_rel_max = logits_abs = 0.0
    differ = not_top8 = rows_routed = 0
    loss_r = []
    weights = masked.astype(jnp.float32) / t
    for i in range(n):  # one sequence at a time
        row = tokens[i:i + 1]
        x_p = jnp.take(cast["tok_emb"]["embedding"], row, axis=0)
        x_r = plain["wte"][row]
        for l, ((_, _, p_p), p_r) in enumerate(zip(layers_p,
                                                   plain["layers"])):
            x_p, routed = program_layer(p_p, x_p, rope)
            chosen = routed["chosen"].reshape(1, 2 * seq, -1)
            x_r, _, own, _ = pieces.layer(x_r, p_r, chosen)
            off, wrong, other = jax.device_get(routing_errors(
                routed, p_p["moe"]["router"], own))
            logits_abs = max(logits_abs, float(off))
            not_top8 += int(wrong)
            differ += int(other)
            rows_routed += 2 * seq
            gap, size, worst = jax.device_get(state_errors(x_p, x_r))
            state_sq[:, l] += gap, size
            row_rel_max = max(row_rel_max, float(worst))
        h_r = pieces.norm(x_r, plain["lnf_g"])
        final_sq += jax.device_get(state_errors(final_p[i:i + 1], h_r))[:2]
        loss_r.append(float(pieces.head(
            x_r, plain, one(x0[i:i + 1]), weights[i:i + 1])))
        del x_p, x_r, h_r
    loss_r = float(np.mean(loss_r))
    errors["loss_abs"] = abs(float(loss_p) - loss_r)
    for l in range(n_layers):
        errors[f"state_rel_rms_layer_{l}"] = float(
            np.sqrt(state_sq[0, l] / state_sq[1, l]))
    errors["state_rel_rms_final"] = float(np.sqrt(final_sq[0] / final_sq[1]))
    errors["row_rel_max"] = row_rel_max
    errors["router_logits_abs"] = logits_abs
    errors["chosen_not_top8_share"] = not_top8 / max(rows_routed, 1)
    errors["chosen_sets_differ_share"] = differ / max(rows_routed, 1)
    del final_p, cast, layers_p
    took["states_s"] = time.perf_counter() - t_start - took["program_s"]
    mine = jax.jit(to_reference)(jax.tree.map(one, shd.unbox(grads_p)))
    del grads_p
    sets = [[chosen_p[l, i:i + 1] for l in range(n_layers)]
            for i in range(n)]
    _, grads_r = pieces.loss_and_grads(plain, one(x0), masked, t, sets)
    per_leaf, overall = jax.device_get(
        jax.jit(_gradient_errors)(mine, grads_r))
    worst = max(jax.tree_util.tree_leaves_with_path(per_leaf),
                key=lambda kv: kv[1])
    errors["grad_rel_rms_worst"] = float(worst[1])
    errors["grad_worst_leaf"] = jax.tree_util.keystr(worst[0])
    errors["grad_worst_leaves"] = {
        jax.tree_util.keystr(path): round(float(value), 5)
        for path, value in sorted(
            jax.tree_util.tree_leaves_with_path(per_leaf),
            key=lambda kv: -kv[1])[:6]}
    errors["grad_rel_rms_all"] = float(overall)
    took["whole_s"] = time.perf_counter() - t_start
    tolerances = dict(spec["tolerances"])
    values = {"program_loss": float(loss_p), "reference_loss": loss_r,
              "took": took}
    finite = all(np.isfinite(v) for v in errors.values()
                 if isinstance(v, float))
    ok = finite and all(errors[k] <= tol for k, tol in tolerances.items())
    return {"ok": bool(ok), "errors": errors, "tolerances": tolerances,
            "counters": counters, **values}
