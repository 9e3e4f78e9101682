"""The comparison that decides ``correct`` for a Keye configuration (learned
sparse attention): the program on seeded sequences of the configuration's
length at the published widths against ``reference_keye`` (float32, Python
loops over layers, heads and experts, the index's scores, its top-k and the
mask written out a block of 512 query rows at a time), both holding the same
share — the experts ``kwargs.experts_held`` and the sliced vocabulary. Runs
before the trainer's state exists and keeps nothing on the device afterwards.

What is compared, and how (``lib/check_sdar.py``'s scheme):

- on ``check.sequences`` whole sequences, given to the program as ONE batch
  through the model bundle's own loss: the objective (the next-token loss
  PLUS the layers' index losses) and the index loss apart, the final normed
  state, the expert layers' counters (``moe_dropped`` has to read 0) and the
  index's;
- **every layer's state**: the program's own ``Block`` modules applied one
  layer after another on the program's own states (the index's kernels, the
  attention kernels under the packed selection and the expert layer the step
  runs), each held to the reference's layer chain as a relative
  root-mean-square error, a number a layer, and as the largest single ROW's;
- **both selections are discrete** and held apart from the states. The
  experts as Mellum 2's are. The index's: every query's set, UNPACKED from
  the words the program kept, has to be ``min(t + 1, topk)`` causal keys none
  of whose scores — the reference's arithmetic on the program's OWN index
  inputs — lies under an unchosen key's by more than thirty-two roundings of
  the row's largest (``index_chosen_not_topk_share``, has to read 0: one key
  too many or too few, a key from the future, scores rounded before the
  ranking or a stale tie rule cannot hide among the near-ties below); the
  chosen keys that are not in the reference's own set on ITS states are
  reported as ``index_sets_differ_share`` (of all chosen keys) and bounded; the reference's layer
  then takes the PROGRAM's sets, so the states, the loss and the gradients
  compare arithmetic under equal selections;
- the parts the kernels decide alone, on equal inputs: the rotary tables —
  the attention's against the multimodal rotary's three sections written
  out, the index's against its own — entry by entry, and **the selection as a
  mask** as the worst single POSITION's relative error of the attention's
  result before ``W_o`` (``select_position_rel_max``: unit-scale q, k, v and
  index inputs through the path the step's layers take, against the
  reference under the same sets written out) with the positions either side
  of ``topk``, of a kernel block's edge and of an index cell's edge reported
  apart (``select_edge_rel_max``): a pair outside ``S_t`` that leaked into a
  row moves it by far more than any mean shows;
- the gradient of the bundle's objective, every leaf in the REFERENCE's
  layout (``to_reference``), as the whole gradient and as the worst leaf, on
  the whole sequences — under the experts' sets AND the index's sets that the
  gradient's OWN evaluation made (``check_sdar.routing_kept``);
- tolerances live in the configuration file under ``check`` with the error
  measured on the chip when they were set and the reason for each.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict

import numpy as np

from . import reference_keye as ref
from .check_gpt2 import _gradient_errors
from .check_laguna import _program_layers, _state_errors
from .check_mellum import _routing_errors
from .check_sdar import routing_kept

#: a chosen key's score may lie this far under an unchosen one's, as a share
#: of the row's largest magnitude, and still count among the largest:
#: thirty-two float32 roundings
TOPK_MARGIN = 32 * 2.0 ** -24


def to_reference(params: Any) -> Dict[str, Any]:
    """The program's (unboxed) parameter tree under the reference's names.
    The only place that knows how ``models/transformer.py`` names things:
    the one run of layers is ``blocks``, stacked on a leading axis."""
    run = params["blocks"]
    moe = run["moe"]
    n_heads = run["index_w"]["kernel"].shape[-1]
    layers = [{"n1": run["ln_attn"]["scale"][j],
               "n2": run["ln_mlp"]["scale"][j],
               "wq": run["q"]["kernel"][j], "wk": run["k"]["kernel"][j],
               "wv": run["v"]["kernel"][j], "wo": run["out"]["kernel"][j],
               "qn": run["q_norm"][j], "kn": run["k_norm"][j],
               "iq": run["index_q"]["kernel"][j].reshape(
                   run["index_q"]["kernel"].shape[1], n_heads, -1),
               "ik": run["index_k"]["kernel"][j],
               "ik_g": run["index_k_norm"]["scale"][j],
               "ik_b": run["index_k_norm"]["bias"][j],
               "iw": run["index_w"]["kernel"][j],
               "router": moe["router"][j], "e_gate": moe["w_gate"][j],
               "e_up": moe["w_up"][j], "e_down": moe["w_down"][j]}
              for j in range(run["q"]["kernel"].shape[0])]
    return {"wte": params["tok_emb"]["embedding"],
            "head": params["head"]["kernel"],
            "lnf_g": params["ln_f"]["scale"], "layers": layers}


def program_tables(cfg, seq: int):
    """The rotary tables the program's stack makes for ``seq`` rows (the same
    calls as ``models/transformer.py``'s: the attention's two, the index's
    two behind them), under ``jit`` as the step makes them."""
    import jax

    from easydl_tpu.ops.rope import rope_tables

    (name, kind), = cfg.attention_kinds
    return jax.jit(lambda: {name: kind.rope.tables(seq, cfg.head_dim)
                            + rope_tables(seq, kind.index.head_dim,
                                          kind.rope.theta)})()


def _table_error(tables, hp, head_dim: int, index_dim: int, seq: int) -> float:
    """Largest absolute difference between the program's rotary tables (the
    rotation's sign folded into the sine) and the reference's: the
    attention's against the three sections written out, the index's against
    its own."""
    import jax
    import jax.numpy as jnp

    def worst(tables):
        (cos_p, sin_p, cos_i, sin_i), = tables.values()
        out = 0.0
        for mine, dim, want in (
                ((cos_p, sin_p), head_dim, ref.mrope_tables(
                    seq, head_dim, hp["theta"], hp["sections"])),
                ((cos_i, sin_i), index_dim, ref.rope_tables(
                    seq, index_dim, hp["theta"]))):
            sign = jnp.where(jnp.arange(dim) < dim // 2, -1.0, 1.0)
            out = jnp.maximum(out, jnp.maximum(
                jnp.max(jnp.abs(mine[0] - want[0])),
                jnp.max(jnp.abs(mine[1] * sign - want[1]))))
        return out

    return float(jax.jit(worst)(tables))


def edge_rows(seq: int, topk: int, block: int = 512, cell: int = 256):
    """The rows either side of ``topk`` (the last query that sees every
    causal key), of a kernel block's edge and of an index cell's."""
    at = {0, 1, seq - 2, seq - 1}
    for edge in (topk, block, cell, seq // 2, seq - block):
        at |= {edge - 2, edge - 1, edge, edge + 1}
    return sorted(p for p in at if 0 <= p < seq)


def selection_position_errors(cfg, hp, seed: int, dtype, seq: int
                              ) -> Dict[str, float]:
    """The program's indexed attention at the layers' head shapes on
    unit-scale inputs against the reference under the SAME sets written out,
    as each POSITION's relative error (over its heads) — the worst of all
    rows and of the edges' rows — and how many of those sets are not the
    top-k of their own scores."""
    import jax
    import jax.numpy as jnp

    from easydl_tpu.ops import index
    from easydl_tpu.ops.attention import indexed_attention

    (_, kind), = cfg.attention_kinds
    ix = kind.index
    edges = jnp.array(edge_rows(seq, ix.topk))

    def error(key):
        keys = jax.random.split(key, 6)
        q = jax.random.normal(keys[0], (1, seq, cfg.n_heads, cfg.head_dim))
        k, v = (jax.random.normal(key, (1, seq, cfg.kv_heads, cfg.head_dim))
                for key in keys[1:3])
        a = jax.random.normal(keys[3], (1, seq, ix.n_heads, ix.head_dim))
        b = jax.random.normal(keys[4], (1, seq, ix.head_dim))
        w = jax.random.normal(keys[5], (1, seq, ix.n_heads)) \
            * (ix.n_heads ** -0.5 * ix.head_dim ** -0.5)
        q, k, v, a, b = (x.astype(dtype) for x in (q, k, v, a, b))
        mine, _, found = indexed_attention(
            q, k, v, a, b, w, topk=ix.topk, impl=cfg.attention_impl,
            chunk=ix.kv_chunk)
        selected = index.unpack(found["words"])
        with jax.default_matmul_precision("highest"):
            want, _, _ = ref.indexed_attention(
                *(x.astype(jnp.float32) for x in (q, k, v, a, b)), w, hp,
                selected, compare=False)
            # (how many keys a query selects is the CONFIGURATION's to say)
            faults = ref.selection_faults(a, b, w, selected, hp["topk"],
                                          TOPK_MARGIN)
        by_position = jnp.sqrt(
            jnp.sum((mine.astype(jnp.float32) - want) ** 2, (0, 2, 3))
            / jnp.sum(want ** 2, (0, 2, 3)))
        return jnp.max(by_position), jnp.max(by_position[edges]), faults

    whole, edge, faults = jax.jit(error)(jax.random.PRNGKey(seed))
    return {"select_position_rel_max": float(whole),
            "select_edge_rel_max": float(edge)}, int(faults)


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
    from easydl_tpu.models.keye import describe
    from easydl_tpu.models.transformer import Block, Transformer
    from easydl_tpu.ops import index

    spec, kwargs = config["check"], config["kwargs"]
    seq, vocab = kwargs["seq_len"], kwargs["vocab"]
    hp = ref.hyper(config)
    cfg = describe(**kwargs)
    (mixer, kind), = cfg.attention_kinds
    mesh = trainer.mesh
    dev0 = mesh.devices.flat[0]
    rows = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    n = int(spec["sequences"])
    ids = np.random.default_rng(seed + 1).integers(
        0, vocab, (n, seq + 1), dtype=np.int32)
    reps = 1 if n % rows == 0 else rows
    rng = jax.random.PRNGKey(seed)
    dtype = trainer.config.compute_dtype
    model = Transformer(cfg)

    # Everything that differs from seed to seed is an ARGUMENT of the jitted
    # functions below, never a constant closed over.
    def program_loss(params, batch, rng):
        """The bundle's objective, its metrics and — out of the same
        evaluation — the sets its expert layers chose ``[layers, sequences,
        rows, k]`` and its indexes selected ``[layers, sequences, L / 32,
        L]``, packed."""
        kept = []
        with routing_kept(kept):
            loss, metrics = bundle.loss_fn(cast_floating(params, dtype),
                                           batch, rng)
        run = kept[0]["blocks"]
        chosen, = run["moe"]["chosen"]
        chosen = chosen.reshape(chosen.shape[0], -1, seq, chosen.shape[-1])
        words, = run["ranked_words"]
        return loss.astype(jnp.float32), (metrics, chosen[:, :n],
                                          words[:, :n])

    def program_final(params, tokens):
        return model.apply({"params": cast_floating(params, dtype)}, tokens,
                           return_hidden=True)

    @jax.jit
    def program_layer(p, x, rope):
        """One of the program's own blocks, what its expert layer routed on
        and what its index ranked."""
        (y, _), kept = Block(cfg, mixer, "moe").apply(
            {"params": p}, x, True, rope, mutable=["intermediates"])
        kept = kept["intermediates"]
        return (y, {k: v[0] for k, v in kept["moe"].items()},
                {k: kept[f"ranked_{k}"][0] for k in "abw"},
                index.unpack(kept["ranked_words"][0]))

    unpack = jax.jit(index.unpack)
    faults_of = jax.jit(functools.partial(
        ref.selection_faults, topk=hp["topk"], margin=TOPK_MARGIN))
    state_errors = jax.jit(_state_errors)
    routing_errors = jax.jit(_routing_errors)
    t_start = time.perf_counter()
    tables = program_tables(cfg, seq)
    positions, faults = selection_position_errors(cfg, hp, seed, dtype, seq)
    ranked = seq
    errors: Dict[str, Any] = {
        "rope_table_abs": _table_error(tables, hp, cfg.head_dim,
                                       kind.index.head_dim, seq),
        **positions}
    with jax.set_mesh(mesh):
        params = jax.jit(bundle.init_fn,
                         out_shardings=trainer.state_shardings().params)(rng)
        whole = jax.tree.map(
            lambda x: jax.device_put(x, shd.batch_sharding(mesh)),
            {"inputs": np.tile(ids[:, :-1], (reps, 1)),
             "targets": np.tile(ids[:, 1:], (reps, 1))})
        (loss_p, (metrics, chosen_p, words_p)), grads_p = jax.jit(
            jax.value_and_grad(program_loss, has_aux=True))(params, whole,
                                                            rng)
        chosen_p, words_p = (jax.device_put(x, dev0)
                             for x in (chosen_p, words_p))
        tokens = jax.device_put(ids[:, :-1], dev0)
        targets = jax.device_put(ids[:, 1:], dev0)
        final_p = jax.device_put(
            jax.jit(program_final)(params, jnp.tile(tokens, (reps, 1)))[:n],
            dev0)
    counters = {name: float(metrics[name]) for name in (
        *(c for c in cfg.counters if c.startswith(("moe_", "router_"))),
        "loss_main", "index_loss", "index_live_tiles", "index_tiles",
        "index_selected_pairs", "index_causal_pairs", "index_score_rms")}
    errors["moe_dropped"] = counters["moe_dropped"]
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
    differ = not_top8 = rows_routed = sets_differ = 0
    main_r, own_r = [], 0.0
    for i in range(n):  # one sequence at a time
        row = tokens[i:i + 1]
        x_p = jnp.take(cast["tok_emb"]["embedding"], row, axis=0)
        x_r = plain["wte"][row]
        for l, ((_, _, p_p), p_r) in enumerate(zip(layers_p,
                                                   plain["layers"])):
            x_p, routed, ranks, selected = program_layer(p_p, x_p, rope)
            chosen = routed["chosen"].reshape(1, seq, -1)
            faults += int(faults_of(ranks["a"], ranks["b"], ranks["w"],
                                    selected))
            x_r, _, own, _, kl, other = pieces.layer(x_r, p_r, chosen,
                                                     selected)
            del selected
            own_r += float(kl) / n
            sets_differ += int(other)
            ranked += seq
            off, wrong, moved = jax.device_get(routing_errors(
                routed, p_p["moe"]["router"], own))
            logits_abs = max(logits_abs, float(off))
            not_top8 += int(wrong)
            differ += int(moved)
            rows_routed += seq
            gap, size, worst = jax.device_get(state_errors(x_p, x_r))
            state_sq[:, l] += gap, size
            row_rel_max = max(row_rel_max, float(worst))
        h_r = pieces.norm(x_r, plain["lnf_g"])
        final_sq += jax.device_get(state_errors(final_p[i:i + 1], h_r))[:2]
        main_r.append(float(pieces.head(x_r, plain, targets[i:i + 1])))
        del x_p, x_r, h_r
    loss_r = float(np.mean(main_r)) + own_r
    errors["loss_abs"] = abs(float(loss_p) - loss_r)
    errors["index_loss_abs"] = abs(counters["index_loss"] - own_r)
    for l in range(n_layers):
        errors[f"state_rel_rms_layer_{l}"] = float(
            np.sqrt(state_sq[0, l] / state_sq[1, l]))
    errors["state_rel_rms_final"] = float(np.sqrt(final_sq[0] / final_sq[1]))
    errors["row_rel_max"] = row_rel_max
    errors["router_logits_abs"] = logits_abs
    errors["chosen_not_top8_share"] = not_top8 / max(rows_routed, 1)
    errors["chosen_sets_differ_share"] = differ / max(rows_routed, 1)
    errors["index_chosen_not_topk_share"] = faults / ranked
    # a key replaced is two entries of the masks' difference
    errors["index_sets_differ_share"] = sets_differ / (
        2.0 * ref.selected_pairs(seq, hp["topk"]) * n_layers * n)
    del final_p, cast, layers_p
    took["states_s"] = time.perf_counter() - t_start - took["program_s"]
    mine = jax.jit(to_reference)(jax.tree.map(one, shd.unbox(grads_p)))
    del grads_p
    sets = [[chosen_p[l, i:i + 1] for l in range(n_layers)]
            for i in range(n)]
    _, grads_r = pieces.loss_and_grads(
        plain, tokens, targets, sets,
        lambda i, l: unpack(words_p[l, i:i + 1]))
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
              "reference_index_loss": own_r, "took": took}
    finite = all(np.isfinite(v) for v in errors.values()
                 if isinstance(v, float))
    ok = finite and all(errors[k] <= tol for k, tol in tolerances.items())
    return {"ok": bool(ok), "errors": errors, "tolerances": tolerances,
            "counters": counters, **values}
