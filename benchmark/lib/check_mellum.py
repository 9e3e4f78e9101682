"""The comparison that decides ``correct`` for a Mellum 2 configuration: the
program on seeded sequences of the configuration's length at the published
widths against ``reference_mellum`` (float32, Python loops over layers and
experts, whole score matrices with the mask written out), both holding the
same share: the experts ``kwargs.experts_held`` and the sliced vocabulary.
Runs before the trainer's state exists and keeps nothing on the device
afterwards: the step program of the cell fills the chip.

What is compared, and how (``lib/check_laguna.py``'s scheme, for a softmax
router with nothing shared and a window wider than a block):

- on ``check.sequences`` whole sequences, given to the program as ONE batch
  through the model bundle's own loss: the loss, the final normed state, the
  expert layers' counters (``moe_dropped`` has to read 0);
- **every layer's state**: the program's own ``Block`` modules applied one
  layer after another on the program's own states (the kernels and the
  expert layer the step runs), each held to the reference's layer chain as a
  relative root-mean-square error, one number a layer, and as the largest
  single TOKEN's relative error over all layers (a token that lost an
  expert's row is one token in sixteen thousand: no mean shows it);
- **routing is discrete.** The expert layer hands out what it routed on
  (``router_in``, ``router_logits``, ``chosen``: ``ops/moe.py`` sows them
  where ``intermediates`` is mutable). The router's float32 logits are held
  to the reference's arithmetic on EQUAL inputs (the program's own normed
  input and router weights): bf16 logits read thousands of times the
  tolerance. **The selection is held to the program's own logits**
  (``chosen_not_top8_share``, has to read 0): every token's chosen set has
  to be eight distinct experts none of whose softmax probabilities lies under
  an unchosen expert's (by more than ``TOP8_MARGIN``: which of two equal
  probabilities is taken is the program's to decide) — a wrong tie rule, or a
  stale score handed to ``top_k``, cannot hide in the share of near-ties
  below. The tokens whose chosen set differs between the program (on its
  bf16 states) and the reference (on its float32 states) are counted and
  their share bounded; the reference's layer is then evaluated with the
  program's chosen sets, weights from its own probabilities, so that one
  near-tie does not swamp the comparison of states;
- the parts the kernels decide alone, each on equal inputs where an error
  cannot hide behind 1% of bf16 activations: both rotary tables entry by
  entry (an unscaled YaRN table is off by 0.28), and the window's band as the
  worst single POSITION's relative error (``window_position_rel_max``:
  near-uniform attention over large values through the path the step's
  window layers take, four windows long; a band off by one key moves every
  row by 3%, a neighbour block dropped or a piece's mask misplaced moves a
  handful of rows by far more and no mean over 4,096 rows shows it — the
  positions ``window - 1 .. window + 1``, the first rows of the second
  neighbour block and of the second grid cell are among the rows, and their
  own worst is reported beside the whole's as ``window_edge_rel_max``);
- on the same whole sequences (one shape for the reference's pieces, and
  one program for the loss and its gradient) the gradient of the bundle's
  loss, per leaf in the REFERENCE's layout (``to_reference``, a linear
  map), as the whole gradient and as the worst leaf; the reference routes
  for itself there;
- the reference is evaluated piece by piece (``reference_mellum.Pieces``:
  a whole layer with its loop over 16 experts takes the TPU's compiler
  minutes in every run; a piece is built once and cached), one sequence at
  a time: its score matrix is 268 MB a head;
- tolerances live in the configuration file under ``check`` with the error
  measured on the chip when they were set and the reason for each.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict

import numpy as np

from . import reference_mellum as ref
from .check_gpt2 import _gradient_errors
from .check_laguna import _program_layers, _state_errors, _tables


#: a chosen expert's float32 softmax probability may lie this far under an
#: unchosen one's and still count among the largest: thirty-two roundings at
#: 1/64
TOP8_MARGIN = 3e-8


def not_top_k(logits, chosen):
    """How many tokens' ``chosen [T, k]`` are NOT ``k`` distinct experts
    with the largest softmax probabilities of ``logits [T, E]``, written
    out: the smallest chosen one against the largest unchosen one."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.softmax(logits.astype(jnp.float32), -1)
    taken = jnp.any(chosen[..., None] == jnp.arange(scores.shape[-1]), -2)
    least = jnp.min(jnp.where(taken, scores, jnp.inf), -1)
    best_left = jnp.max(jnp.where(taken, -jnp.inf, scores), -1)
    wrong = (jnp.sum(taken, -1) != chosen.shape[-1]) \
        | (best_left > least + TOP8_MARGIN)
    return jnp.sum(wrong)


def _routing_errors(routed, w_router, own):
    """Of an expert layer: the float32 logits against the reference's
    arithmetic on the program's own inputs, the tokens whose chosen set is
    not the largest of those logits, and the tokens whose set is not the
    reference's ``own``."""
    import jax.numpy as jnp

    logits = jnp.einsum(
        "td,de->te", routed["router_in"].astype(jnp.float32),
        w_router.astype(jnp.float32), precision=ref.HIGHEST)
    chosen = routed["chosen"]
    differ = jnp.any(jnp.sort(own.reshape(chosen.shape), -1)
                     != jnp.sort(chosen, -1), -1)
    return (jnp.max(jnp.abs(routed["router_logits"] - logits)),
            not_top_k(routed["router_logits"], chosen), jnp.sum(differ))


def to_reference(params: Any) -> Dict[str, Any]:
    """The program's (unboxed) parameter tree under the reference's names.
    The only place that knows how ``models/transformer.py`` names things:
    one run of layers a ``blocks_<i>``, stacked on a leading axis."""
    layers = []
    for name in sorted((k for k in params if k.startswith("blocks")),
                       key=lambda k: int(k.split("_")[1]) if "_" in k else 0):
        run = params[name]
        for j in range(run["q"]["kernel"].shape[0]):
            one = {"n1": run["ln_attn"]["scale"][j],
                   "n2": run["ln_mlp"]["scale"][j],
                   "wq": run["q"]["kernel"][j], "wk": run["k"]["kernel"][j],
                   "wv": run["v"]["kernel"][j], "wo": run["out"]["kernel"][j]}
            moe = run["moe"]
            one.update(router=moe["router"][j], e_gate=moe["w_gate"][j],
                       e_up=moe["w_up"][j], e_down=moe["w_down"][j])
            layers.append(one)
    return {"wte": params["tok_emb"]["embedding"],
            "head": params["head"]["kernel"],
            "lnf_g": params["ln_f"]["scale"], "layers": layers}


def _table_error(tables, config: Dict[str, Any], seq: int) -> float:
    """Largest absolute difference between the program's rotary ``tables``
    (the rotation's sign folded into the sine, pass-through dimensions
    cosine 1 and sine 0) and the reference's, over both schemes."""
    import jax
    import jax.numpy as jnp

    d = config["head_dim"]

    def worst(tables):
        out = []
        for name, (cos_p, sin_p) in tables.items():
            cos_r, sin_r, rot = ref.rope_tables(
                seq, d, config["rope_parameters"][name])
            sign = jnp.where(jnp.arange(rot) < rot // 2, -1.0, 1.0)
            out += [jnp.max(jnp.abs(cos_p[:, :rot] - cos_r)),
                    jnp.max(jnp.abs(sin_p[:, :rot] * sign - sin_r))]
            if rot < d:
                out += [jnp.max(jnp.abs(cos_p[:, rot:] - 1.0)),
                        jnp.max(jnp.abs(sin_p[:, rot:]))]
        return jnp.max(jnp.stack(out))

    return float(jax.jit(worst)(tables))


def _window_position_errors(cfg, config: Dict[str, Any], seed: int, dtype,
                            seq: int) -> Dict[str, float]:
    """The program's attention path at the window layers' head shape on
    near-zero scores and unit values against the written-out band, as each
    POSITION's relative error (over its heads): the worst of all positions
    and the worst of the band's edges."""
    import jax
    import jax.numpy as jnp

    from easydl_tpu.ops import multihead_attention

    window = int(config["sliding_window"])
    seq = min(seq, 4 * window)
    heads = cfg.n_heads
    edges = sorted({p for p in (window - 1, window, window + 1,
                                2 * window - 1, 2 * window, 2 * window + 1,
                                seq - 1) if 0 <= p < seq})

    def error(key):
        kq, kk, kv = jax.random.split(key, 3)
        q = 0.01 * jax.random.normal(kq, (1, seq, heads, cfg.head_dim))
        k = 0.01 * jax.random.normal(kk, (1, seq, cfg.kv_heads, cfg.head_dim))
        v = jax.random.normal(kv, (1, seq, cfg.kv_heads, cfg.head_dim))
        mine = multihead_attention(
            *(x.astype(dtype) for x in (q, k, v)), causal=True,
            impl=cfg.attention_impl, window=window).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = ref.attention_core(q, k, v, window=window)
        by_position = jnp.sqrt(jnp.sum((mine - want) ** 2, (0, 2, 3))
                               / jnp.sum(want ** 2, (0, 2, 3)))
        return jnp.max(by_position), jnp.max(by_position[jnp.array(edges)])

    whole, edge = jax.jit(error)(jax.random.PRNGKey(seed))
    return {"window_position_rel_max": float(whole),
            "window_edge_rel_max": float(edge)}


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
    from easydl_tpu.models.mellum import describe
    from easydl_tpu.models.transformer import Block, Transformer

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
    model = Transformer(cfg)

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
    def program_layer(mixer, ffn, p, x, rope):
        """One of the program's own blocks, and what its expert layer
        routed on."""
        (y, _), kept = Block(cfg, mixer, ffn).apply(
            {"params": p}, x, True, rope, mutable=["intermediates"])
        moe = kept.get("intermediates", {}).get("moe", {})
        return y, {k: v[0] for k, v in moe.items()}

    state_errors = jax.jit(_state_errors)
    routing_errors = jax.jit(_routing_errors)
    t_start = time.perf_counter()
    tables = _tables(cfg, seq)
    errors: Dict[str, Any] = {
        "rope_table_abs": _table_error(tables, config, seq),
        **_window_position_errors(cfg, config, seed, dtype, seq),
    }
    with jax.set_mesh(mesh):
        params = jax.jit(bundle.init_fn,
                         out_shardings=trainer.state_shardings().params)(rng)
        whole = batch_of(window)
        (loss_p, metrics), grads_p = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True))(params, whole, rng)
        final_p = jax.device_put(
            jax.jit(program_final)(params, whole["inputs"]), dev0)
    counters = {name: float(metrics[name]) for name in cfg.counters}
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
    tables = jax.tree.map(one, tables)
    pieces = ref.Pieces(hp)
    n_layers = len(layers_p)
    state_sq = np.zeros((2, n_layers))   # squared error and norm, by layer
    final_sq = np.zeros(2)
    token_rel_max = logits_abs = 0.0
    differ = not_top8 = tokens_routed = 0
    loss_r = []
    for i, row in enumerate(window):  # one sequence at a time
        tokens = one(row[None, :-1])
        x_p = jnp.take(cast["tok_emb"]["embedding"], tokens, axis=0)
        x_r = plain["wte"][tokens]
        for l, ((mixer, ffn, p_p), p_r, kind) in enumerate(zip(
                layers_p, plain["layers"], hp["layer_types"])):
            x_p, routed = program_layer(mixer, ffn, p_p, x_p,
                                        tables[mixer])
            chosen = routed["chosen"].reshape(1, seq, -1) \
                if routed else None
            x_r, _, own, _ = pieces.layer(x_r, p_r, kind, chosen)
            if routed:
                off, wrong, other = jax.device_get(routing_errors(
                    routed, p_p["moe"]["router"], own))
                logits_abs = max(logits_abs, float(off))
                not_top8 += int(wrong)
                differ += int(other)
                tokens_routed += seq
            gap, size, token = jax.device_get(state_errors(x_p, x_r))
            state_sq[:, l] += gap, size
            token_rel_max = max(token_rel_max, float(token))
        h_r = pieces.norm(x_r, plain["lnf_g"])
        final_sq += jax.device_get(state_errors(final_p[i:i + 1], h_r))[:2]
        loss_r.append(float(pieces.head(x_r, plain, one(row[None, 1:]))))
        del x_p, x_r, h_r
    loss_r = float(np.mean(loss_r))
    errors["loss_abs"] = abs(float(loss_p) - loss_r)
    for l in range(n_layers):
        errors[f"state_rel_rms_layer_{l}"] = float(
            np.sqrt(state_sq[0, l] / state_sq[1, l]))
    errors["state_rel_rms_final"] = float(np.sqrt(final_sq[0] / final_sq[1]))
    errors["token_rel_max"] = token_rel_max
    errors["router_logits_abs"] = logits_abs
    errors["chosen_not_top8_share"] = not_top8 / max(tokens_routed, 1)
    errors["chosen_sets_differ_share"] = differ / max(tokens_routed, 1)
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
    took["whole_s"] = time.perf_counter() - t_start
    tolerances = dict(spec["tolerances"])
    values = {"program_loss": float(loss_p), "reference_loss": loss_r,
              "took": took}
    finite = all(np.isfinite(v) for v in errors.values()
                 if isinstance(v, float))
    ok = finite and all(errors[k] <= tol for k, tol in tolerances.items())
    return {"ok": bool(ok), "errors": errors, "tolerances": tolerances,
            "counters": counters, **values}
