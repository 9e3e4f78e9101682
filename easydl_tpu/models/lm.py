"""The causal-LM bundle of one description of the stack
(``models/transformer.py``), shared by the families that are descriptions of
it (``gpt.py``, ``granite_hybrid.py``, ``ouro.py`` hold published numbers and
a factory each): the plain next-token loss through full logits or the fused
chunked head, the rule that picks between them, and the looped language
model's expected-exit objective.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax

from easydl_tpu.core.data import SyntheticTokens
from easydl_tpu.models.registry import ModelBundle
from easydl_tpu.models.transformer import (INDEX_COUNTERS, Transformer,
                                           TransformerConfig)
from easydl_tpu.ops import index as index_ops
from easydl_tpu.ops.flash_attention import BlockDiffusion, choose_blocks
from easydl_tpu.ops.fused_xent import fused_softmax_xent, local_batch
from easydl_tpu.ops import selective_scan as sscan
from easydl_tpu.utils.logging import get_logger, log_once


def lm_loss(logits, targets, ignore_id: int = -1):
    """Mean next-token cross-entropy (fp32 accumulation)."""
    logits = logits.astype(jnp.float32)
    mask = (targets != ignore_id).astype(jnp.float32)
    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.maximum(targets, 0)
    )
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (losses * mask).sum() / denom
    return loss, denom


#: The chunked fused head (ops/fused_xent.py) takes the place of full logits
#: when ONE device's share of a microbatch's ``[B, S, V]`` float32 logits
#: would pass this: an eighth of a v5e chip's 16 GB. It is chosen for the
#: room: GPT-2 at 8 x 1024 x 50304 (1.5 GiB) keeps full logits, the Granite
#: hybrid at 2 x 4096 x 100352 (3.1 GiB, beside 1.5 GiB of bf16 logits) does
#: not fit them; a looped model's microbatch forms one set of logits a pass
#: and is asked about all of them (Ouro at 4 x [2, 4096, 49152]: 6 GiB).
#: The fused head forms the loss and both gradients in one
#: pass over each chunk's logits; whether it also beats full logits where
#: both fit has not been measured since it stopped recomputing them. Which
#: head a shape gets is decided here and nowhere else; how the fused head
#: cuts the sequence into chunks is its own matter
#: (``fused_xent.chunk_positions``).
FUSED_HEAD_LOGITS_BYTES = 2 * 1024 ** 3
#: ... or when ONE sequence's float32 logits alone would pass half of that:
#: a microbatch of sequences can be cut to fewer, one sequence cannot, and
#: it is the long sequence beside a chip full of state that has no room
#: (16,384 x 25,008: 1.53 GiB, and a copy of it, in a step that then needed
#: 18.9 GB of 15.75: PERF.md section 6, PR 53). No cell of the benchmark
#: under the first rule passes the second (the widest, 8,192 x 24,576, is
#: 0.75 GiB a sequence).
FUSED_HEAD_SEQUENCE_BYTES = 1024 ** 3

log = get_logger("models", "lm")


def fused_head_by_shape(batch: int, seq: int, vocab: int,
                        heads: int = 1) -> bool:
    """The rule above, for ``heads`` sets of logits of ``[batch, seq,
    vocab]`` as the loss function sees them under the context mesh (the one
    ``Trainer`` enters): the batch is split over the mesh's batch axes where
    it divides."""
    return (4 * heads * local_batch(batch) * seq * vocab
            > FUSED_HEAD_LOGITS_BYTES
            or 4 * seq * vocab > FUSED_HEAD_SEQUENCE_BYTES)


def exit_distribution(gate_logits: jax.Array) -> jax.Array:
    """``p [T, B, S]`` float32 from the passes' gate logits ``[T, B, S]``: a
    token leaves after pass ``t`` with ``p_t = lambda_t prod_{j<t} (1 -
    lambda_j)``, ``lambda = sigmoid(gate)``, and after the last pass with
    what is left (that pass's logit is not read)."""
    stay = jnp.ones(gate_logits.shape[1:], jnp.float32)
    p = []
    for gate in gate_logits[:-1]:
        leave = jax.nn.sigmoid(gate.astype(jnp.float32))
        p.append(leave * stay)
        stay = stay * (1.0 - leave)
    return jnp.stack(p + [stay])


def looplm_objective(states: jax.Array, gate_logits: jax.Array,
                     head: jax.Array, targets: jax.Array, *, beta: float,
                     fused: bool, logit_scale: float = 1.0,
                     ignore_id: int = -1):
    """The looped language model's training objective (Ouro's stage I,
    arXiv:2510.25741): the expected next-token loss under the exit gate's
    distribution over the passes, less ``beta`` times that distribution's
    entropy. ``(loss, metrics)``.

    ``states``: the passes' normed states ``[T, B, S, D]``;
    ``gate_logits``: ``[T, B, S]`` float32 (the last pass's is not read:
    whatever has not left, leaves there); ``head``: ``[V, D]`` in the
    states' dtype.
    The distribution is :func:`exit_distribution`'s; gate, distribution and
    entropy are float32.

    ``fused`` is the head the caller's shape rule picked
    (:func:`fused_head_by_shape` with ``heads`` the passes): the fused
    chunked head takes all passes in ONE call on the states joined along
    the sequence, each row weighted by its ``p_t`` — one pass over each
    chunk's logits, one carried head gradient, and the weights' gradient is
    each row's own loss. Else one set of full float32 logits a pass.
    """
    passes, batch = states.shape[:2]
    mask = (targets != ignore_id).astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    with jax.named_scope("exit_gate"):
        p = exit_distribution(gate_logits)
        entropy = -(p * jnp.log(jnp.maximum(p, 1e-30))).sum(0)
        steps = (p * jnp.arange(1, passes + 1, dtype=jnp.float32
                                )[:, None, None]).sum(0)
    if fused:
        with jax.named_scope("lm_head_loss"):
            # the count the op divides by is of rows, `passes` a token
            loss, _, rows = fused_softmax_xent(
                jnp.moveaxis(states, 0, 1).reshape(
                    batch, -1, states.shape[-1]), head,
                jnp.tile(targets, (1, passes)),
                weights=jnp.moveaxis(p, 0, 1).reshape(batch, -1),
                ignore_id=ignore_id, logit_scale=logit_scale)
            expected = loss * passes
            by_pass = rows.reshape(batch, passes, -1).sum((0, 2))
    else:
        log_once(log, f"lm head: full logits {passes} x "
                      f"{[*targets.shape, head.shape[0]]} in float32")
        by_pass = []
        for t in range(passes):
            h = states[t]
            with jax.named_scope(f"pass_{t}"):
                with jax.named_scope("lm_head"):
                    logits = lax.dot_general(
                        h, head, (((2,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * logit_scale
                with jax.named_scope("loss"):
                    by_pass.append(
                        optax.softmax_cross_entropy_with_integer_labels(
                            logits, jnp.maximum(targets, 0)) * mask)
        by_pass = jnp.stack(by_pass)  # [T, B, S]
        expected = (p * by_pass).sum() / denom
        by_pass = by_pass.sum((1, 2))
    mean_entropy = (entropy * mask).sum() / denom
    metrics = {f"loss_pass_{t}": by_pass[t] / denom for t in range(passes)}
    metrics.update(perplexity=jnp.exp(expected),
                   exit_step_mean=(steps * mask).sum() / denom,
                   exit_entropy=mean_entropy)
    return expected - beta * mean_entropy, metrics


def mtp_objective(states, head: jax.Array, targets: jax.Array, *,
                  weight: float, logit_scale: float = 1.0,
                  ignore_id: int = -1):
    """A multi-token-prediction model's training objective (DeepSeek-V3,
    arXiv:2412.19437 section 2.2, depth 1): ``CE(main, t_{i+1}) + weight *
    CE(module, t_{i+2})``, each a mean over its own valid positions. ``(loss,
    metrics)``, the metrics ``loss_main``, ``loss_mtp`` and ``perplexity``
    (the main head's).

    ``states``: ``models/transformer.py MtpStates`` — the main stack's
    normed state and the module's, ``[B, S, D]`` each; ``targets [B, S]`` are
    the main head's: the module's are those moved one position on, its last
    position has none. Both heads are ONE call of the fused chunked head
    (``ops/fused_xent.py``) on the two states joined along the sequence, as
    :func:`looplm_objective` joins a looped model's passes: one head leaf,
    one carried head gradient, each row weighted by its objective's share —
    and the only form written: there is no full-logits branch to pick by
    shape (two sets of ``[B, S, V]`` float32 logits beside a chip full of
    state would not fit where this is trained)."""
    batch, seq = targets.shape
    later = jnp.concatenate(
        [targets[:, 1:], jnp.full((batch, 1), ignore_id, targets.dtype)], 1)
    joined = jnp.concatenate([targets, later], 1)
    # valid rows of each head, and of both: what the op divides its
    # weighted sum by
    n_main, n_mtp, rows = (
        jnp.maximum((t != ignore_id).sum().astype(jnp.float32), 1.0)
        for t in (targets, later, joined))
    share = jnp.concatenate([
        jnp.broadcast_to(rows / n_main, targets.shape),
        jnp.broadcast_to(weight * rows / n_mtp, targets.shape)], 1)
    with jax.named_scope("lm_head_loss"):
        loss, _, by_row = fused_softmax_xent(
            jnp.concatenate([states.hidden, states.mtp], 1), head, joined,
            weights=lax.stop_gradient(share), ignore_id=ignore_id,
            logit_scale=logit_scale)
    loss_main = by_row[:, :seq].sum() / n_main
    loss_mtp = by_row[:, seq:].sum() / n_mtp
    return loss, {"perplexity": jnp.exp(loss_main), "loss_main": loss_main,
                  "loss_mtp": loss_mtp}


#: the least time a block is noised at: ``t ~ U(DIFFUSION_EPS, 1)``
DIFFUSION_EPS = 1e-3


def block_diffusion_noise(rng: jax.Array, tokens: jax.Array, *, block: int,
                          mask_id: int):
    """Block diffusion's draw for ``tokens [B, L]`` (the clean ``x0``), a
    pure function of ``rng`` and the shape: ``(xt, masked, t)`` — for each
    sequence and block of ``block`` tokens a time ``t ~ U(DIFFUSION_EPS,
    1)``, each token of the block masked independently with probability
    ``t`` (the linear schedule, ``alpha_t = 1 - t``), ``xt`` the tokens with
    ``mask_id`` at the masked places; ``masked [B, L]`` bool and ``t [B, L]``
    float32, a block's time at each of its tokens. Which positions count is
    ``masked``, never ``xt == mask_id``: the data may hold that id."""
    batch, seq = tokens.shape
    key_t, key_m = jax.random.split(rng)
    t = jax.random.uniform(key_t, (batch, seq // block), jnp.float32,
                           DIFFUSION_EPS, 1.0)
    t = jnp.repeat(t, block, axis=1)
    masked = jax.random.uniform(key_m, (batch, seq), jnp.float32) < t
    return jnp.where(masked, mask_id, tokens), masked, t


def block_diffusion_objective(hidden: jax.Array, head: jax.Array,
                              tokens: jax.Array, masked: jax.Array,
                              t: jax.Array, *, logit_scale: float = 1.0):
    """Block diffusion's training objective (BD3-LM, arXiv:2503.09573; the
    linear schedule's weight ``1 / t``): ``(1 / (B L)) sum over the masked
    positions of (1 / t) * -log softmax(logits_i)[x0_i]`` — no shift: a
    masked position predicts its own token. ``(loss, metrics)``.

    ``hidden``: the NOISED half's normed final states ``[B, L, D]`` (the
    clean half's feed nothing and are not read); ``head``: ``[V, D]``;
    ``tokens``, ``masked``, ``t``: ``x0`` and :func:`block_diffusion_noise`'s
    draw. One call of the fused chunked head (``ops/fused_xent.py``), each
    row weighted ``masked / t`` — the only form written, as
    :func:`mtp_objective`'s."""
    share = masked.astype(jnp.float32)
    with jax.named_scope("lm_head_loss"):
        loss, _, _ = fused_softmax_xent(
            hidden, head, tokens, weights=lax.stop_gradient(share / t),
            logit_scale=logit_scale)
    return loss, {"diffusion_masked_share": jnp.mean(share),
                  "diffusion_mean_t": jnp.mean(t)}


def lm_bundle(cfg: TransformerConfig, name: str, *,
              exit_entropy_weight: float = 0.0) -> ModelBundle:
    """The causal-LM bundle of one description of the stack: init, loss
    (full logits, or the fused chunked head where
    :func:`fused_head_by_shape` says so; a gated stack's is
    :func:`looplm_objective` with ``beta = exit_entropy_weight``), eval,
    data and the hints. Where the description has ``moe`` layers the loss's
    metrics carry their counters (``ops/moe.py counters``): ``moe_dropped``
    summed over the layers, the others their mean — ``moe_rows_per_token``,
    ``moe_load_max_over_mean``, ``moe_buffer_fill`` (landed rows over the
    bound), ``router_entropy``, ``moe_overflow``, the share of the
    step's expert-layer calls whose landed rows needed more than one piece
    of the sort, ``moe_tile_fill``, the landed rows over the rows of the
    row tiles the grouped products visited, ``moe_row_fill``, the landed
    rows over the rows the pieces' chunk loops made for them, and, where
    the router has a skip choice, ``moe_skipped``,
    the share of tokens that took it, and, where it is the linear softmax,
    ``router_chosen_mass``, the softmax mass on the chosen experts before
    the renormalisation; where the router's state runs through
    the depth, ``router_state_rms``, its size after the last layer.

    Under ``cfg.block_diffusion`` the loss is
    :func:`block_diffusion_objective`'s: the batch's ``inputs`` are ``x0``
    (its ``targets`` are not read), noised INSIDE the loss from the ``rng``
    it is handed — ``Trainer``'s ``fold_in(state.rng, state.step)``, so a
    restored step draws the noise it drew — the stack run on ``[xt || x0]``,
    the vocabulary's last row standing for the mask token; the metrics
    carry ``diffusion_masked_share`` and ``diffusion_mean_t``. ``eval_fn`` is
    the same loss under one fixed key.

    Where attention layers stand behind a learned index
    (``AttentionKind.index``) the objective is the next-token loss PLUS the
    layers' index losses (``ops/index.py kl``, a layer's mean over its
    tokens, summed over the layers, weight 1) — the one loss term that leaves
    a layer: it comes out with the counters and is added here. The metrics
    carry ``loss_main`` and ``index_loss`` apart, the static
    ``index_selected_pairs`` / ``index_causal_pairs`` (a head and sequence)
    and ``index_tiles`` (the causal tiles of the step's index layers),
    ``index_live_tiles`` of them (those that hold a selected pair) and
    ``index_score_rms`` over the causal pairs. ``eval_fn`` is the next-token
    loss alone."""
    model = Transformer(cfg)
    seq_len, vocab = cfg.max_seq, cfg.vocab
    n_sparse = sum(1 for _, ffn in cfg.every_layer if ffn == "moe")
    summed_index = INDEX_COUNTERS if cfg.index_layers else ()

    def head_of(params, dtype):
        """The head as ``[V, D]`` in the compute dtype — exactly what
        tok_emb.attend's dtype promotion does on the logits path. A
        bf16×f32 dot_general promotes to an f32 matmul, which would take
        the [B,chunk,V] matmul off the bf16 MXU path."""
        head = (params["tok_emb"]["embedding"] if cfg.tied_head
                else params["head"]["kernel"])
        if hasattr(head, "unbox"):  # boxed (LogicallyPartitioned) params
            head = head.unbox()
        head = jnp.asarray(head, dtype=dtype)
        return head if cfg.tied_head else head.T

    def gated_loss(params, batch):
        out = model.apply({"params": params}, batch["inputs"],
                          return_hidden=True)
        return looplm_objective(
            out.hidden, out.gate, head_of(params, out.hidden.dtype),
            batch["targets"], beta=exit_entropy_weight,
            fused=fused_head_by_shape(*batch["inputs"].shape, vocab,
                                      heads=cfg.loops),
            logit_scale=1.0 / cfg.logits_scaling)

    def init_fn(rng):
        rows = 2 * seq_len if cfg.block_diffusion else seq_len
        tokens = jnp.zeros((1, rows), jnp.int32)
        return model.init(rng, tokens)["params"]

    def diffusion_loss(params, batch, rng, mutable=False):
        """``(loss, mutated collections or None, the objective's
        metrics)``."""
        x0 = batch["inputs"]
        with jax.named_scope("noise"):
            xt, masked, t = block_diffusion_noise(
                rng, x0, block=cfg.block_diffusion, mask_id=vocab - 1)
            rows = jnp.concatenate([xt, x0], axis=1)
        out = model.apply(
            {"params": params}, rows, return_hidden=True,
            **({"mutable": ["counters"]} if mutable else {}))
        hidden, mut = out if mutable else (out, None)
        loss, metrics = block_diffusion_objective(
            hidden[:, :x0.shape[1]], head_of(params, hidden.dtype), x0,
            masked, t, logit_scale=1.0 / cfg.logits_scaling)
        # static: the kernel block pairs the block mask's calls visit, of
        # all (``ops/flash_attention.py choose_blocks``; 0 of 0 where the
        # lengths have no block and the XLA reference path runs)
        mask = BlockDiffusion(cfg.block_diffusion, x0.shape[1])
        blocks = choose_blocks(rows.shape[1], rows.shape[1], False, mask=mask)
        live, every = mask.block_pairs(blocks[0][0]) if blocks else (0, 0)
        log_once(log, f"diffusion: blocks of {cfg.block_diffusion} over "
                      f"{x0.shape[1]} tokens, {rows.shape[1]} rows a "
                      f"sequence; flash_live_pairs {live} of "
                      f"flash_block_pairs {every}; metrics "
                      f"diffusion_masked_share, diffusion_mean_t")
        metrics.update(flash_live_pairs=jnp.float32(live),
                       flash_block_pairs=jnp.float32(every))
        return loss, mut, metrics

    def _lm_loss_from(params, batch, mutable=False):
        """``(loss, the mutated collections or None, the heads' metrics or
        None for a plain head)``: LM loss via the fused chunked head or
        full logits.

        The fused path asks the stack for hidden states and applies the tied
        head chunk-by-chunk (ops/fused_xent.py) — the full [B,S,V] f32
        logits buffer never exists.
        """
        mut = None
        if cfg.mtp is not None:
            # the module's objective has one form: the fused head on the
            # joined states (:func:`mtp_objective`)
            out = model.apply(
                {"params": params}, batch["inputs"], return_hidden=True,
                **({"mutable": ["counters"]} if mutable else {}))
            states, mut = out if mutable else (out, None)
            loss, heads = mtp_objective(
                states, head_of(params, states.hidden.dtype),
                batch["targets"], weight=cfg.mtp.weight,
                logit_scale=1.0 / cfg.logits_scaling)
            return loss, mut, heads
        if fused_head_by_shape(*batch["inputs"].shape, vocab):
            out = model.apply(
                {"params": params}, batch["inputs"], return_hidden=True,
                **({"mutable": ["counters"]} if mutable else {}),
            )
            hidden = out[0] if mutable else out
            mut = out[1] if mutable else None
            if cfg.loops > 1:  # ungated: the last pass's state
                hidden = hidden.hidden[-1]
            with jax.named_scope("lm_head_loss"):
                loss, _ = fused_softmax_xent(
                    hidden, head_of(params, hidden.dtype), batch["targets"],
                    logit_scale=1.0 / cfg.logits_scaling,
                )
        else:
            log_once(log, f"lm head: full logits "
                          f"{[*batch['inputs'].shape, vocab]} in float32")
            out = model.apply(
                {"params": params}, batch["inputs"],
                **({"mutable": ["counters"]} if mutable else {}),
            )
            logits = out[0] if mutable else out
            mut = out[1] if mutable else None
            with jax.named_scope("loss"):
                loss, _ = lm_loss(logits, batch["targets"])
        return loss, mut, None

    def handed_counters(batch):
        """Static, from shapes, where the stack's layers read earlier
        layers' values or scan by Mamba-1 (none elsewhere): the layers that
        read an earlier layer's keys and values or its memory, the chunks a
        selective scan walks a sequence in and the bytes of entry states a
        layer's scan keeps for its backward."""
        rows, seq = batch["inputs"].shape
        m = cfg.mamba1
        if m is None:
            return {}
        counted = dict(
            kv_readers=cfg.readers("kv"), memory_readers=cfg.readers("memory"),
            sscan_chunks=sscan.chunks(seq),
            sscan_state_bytes_kept=sscan.state_bytes_kept(
                rows, seq, m.d_inner, m.d_state))
        return {name: jnp.float32(n) for name, n in counted.items()}

    def metrics_of(loss, heads, counters=None):
        """The heads' metrics (a plain head's: its perplexity) and the
        expert layers' counters."""
        return {**(heads or {"perplexity": jnp.exp(loss)}), **(counters or {})}

    def index_counters(summed, batch):
        """The index layers' counters from their sums over the layers, and
        the static counts beside them."""
        rows, seq = batch["inputs"].shape
        (topk,) = {kind.index.topk for _, kind in cfg.attention_kinds
                   if kind.index is not None}
        pairs = index_ops.causal_pairs(seq)
        return {
            "index_loss": summed["index_loss"],
            "index_live_tiles": summed["index_live_tiles"],
            "index_tiles": jnp.float32(
                cfg.index_layers * rows * index_ops.tiles(seq)),
            "index_selected_pairs": jnp.float32(
                index_ops.selected_pairs(seq, topk)),
            "index_causal_pairs": jnp.float32(pairs),
            "index_score_rms": jnp.sqrt(
                summed["index_score_squares"]
                / (cfg.index_layers * rows * pairs))}

    def loss_fn(params, batch, rng):
        if cfg.exit_gate:
            return gated_loss(params, batch)
        if cfg.counters:
            loss, mut, heads = diffusion_loss(params, batch, rng, True) \
                if cfg.block_diffusion \
                else _lm_loss_from(params, batch, mutable=True)
            summed = mut["counters"]["moe"][0]
            counters = {name: summed[i] / (
                1 if name == "moe_dropped" else cfg.counted_layers
                if name in cfg.mixer_counters else n_sparse)
                        for i, name in enumerate(cfg.counters)
                        if name not in summed_index}
            if cfg.kda is not None and cfg.mixer_counters:
                # static: the chunks a layer's recurrence walks
                counters["kda_chunks"] = jnp.float32(
                    -(-batch["inputs"].shape[1] // cfg.kda.chunk))
            if cfg.router_state_width:
                counters["router_state_rms"] = \
                    mut["counters"]["router_state_rms"][0]
            if cfg.index_layers:
                own = {name: summed[cfg.counters.index(name)]
                       for name in summed_index}
                counters.update(index_counters(own, batch), loss_main=loss)
                metrics = metrics_of(loss, heads, counters)
                return loss + own["index_loss"], metrics
            return loss, metrics_of(loss, heads, counters)
        loss, _, heads = diffusion_loss(params, batch, rng) \
            if cfg.block_diffusion else _lm_loss_from(params, batch)
        return loss, metrics_of(loss, heads, handed_counters(batch))

    def eval_fn(params, batch, rng):
        if cfg.exit_gate:
            return gated_loss(params, batch)
        # a diffusion model's evaluation draws its noise from one fixed key
        loss, _, heads = diffusion_loss(params, batch, jax.random.PRNGKey(0)) \
            if cfg.block_diffusion else _lm_loss_from(params, batch)
        return loss, metrics_of(loss, heads)

    def make_data(global_batch: int, seed: int = 0):
        return SyntheticTokens(global_batch, seq_len=seq_len, vocab=vocab, seed=seed)

    return ModelBundle(
        name=name,
        init_fn=init_fn,
        loss_fn=loss_fn,
        make_data=make_data,
        eval_fn=eval_fn,
        param_count_hint=cfg.param_count,
        # the description's own count: a layer without a score matrix adds
        # no 12 d s (core/mfu.py's GPT formula is this for all-attention)
        flops_per_sample_hint=cfg.train_flops_per_token(seq_len) * seq_len,
    )


