"""Shared transformer stack for the LM families (GPT-2, BERT).

TPU-first choices:
- every parameter carries logical axis names (``embed``/``heads``/``kv``/
  ``mlp``/``vocab``) so one rule table retargets the model across DP, FSDP,
  TP and SP meshes with zero model edits (core/sharding.py);
- blocks run under ``nn.scan`` — one traced layer, XLA unrolls on device —
  keeping compile time flat in depth; the scan axis is a logical ``layers``
  axis (mapped to ``pp`` for pipeline-style stage sharding, or None);
- optional ``nn.remat`` per block trades FLOPs for HBM (gradient
  rematerialisation — the standard long-sequence memory lever);
- attention goes through :func:`easydl_tpu.ops.multihead_attention` which
  swaps in the Pallas flash kernel on TPU;
- activations are annotated with ``nn.with_logical_constraint`` so GSPMD
  shards the sequence dim over ``sp`` when sequence parallelism is on.

The reference has no model code at all (SURVEY.md §0); these models exist to
hit the BASELINE configs 3-4 (BERT-base, GPT-2 345M).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from easydl_tpu.ops import multihead_attention

Init = nn.initializers.Initializer


def _dense(
    features,
    kernel_axes,
    bias_axes,
    name=None,
    use_bias=True,
    init_scale=1.0,
    axis=-1,
    dtype=None,
):
    return nn.DenseGeneral(
        features,
        axis=axis,
        use_bias=use_bias,
        dtype=dtype,  # compute dtype; params stay f32 (param_dtype default)
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(stddev=0.02 * init_scale), kernel_axes
        ),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), bias_axes
        ),
        name=name,
    )


def _layernorm(name, dtype=None):
    # LayerNorm statistics always accumulate in f32 (flax does this when
    # dtype is low-precision); only the output is cast to ``dtype``.
    return nn.LayerNorm(
        use_bias=True,
        dtype=dtype,
        scale_init=nn.with_logical_partitioning(
            nn.initializers.ones_init(), ("embed",)
        ),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), ("embed",)
        ),
        name=name,
    )


@dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 50304            # GPT-2 vocab padded to a multiple of 128 (MXU tiling)
    d_model: int = 1024
    n_heads: int = 16
    n_layers: int = 24
    d_ff: int = 4096
    max_seq: int = 1024
    causal: bool = True
    dropout: float = 0.0
    remat: bool = False
    #: remat granularity: "full" recomputes the whole block (min memory);
    #: "dots" keeps matmul outputs and recomputes only elementwise/softmax
    #: (jax dots_saveable policy — ~8% faster on TPU when HBM allows).
    remat_policy: str = "full"
    attention_impl: str = "auto"
    #: compute/activation dtype ("float32" | "bfloat16"). Params stay f32;
    #: matmuls and activations run in this dtype (bf16 halves HBM traffic —
    #: the usual TPU bottleneck) and the loss upcasts logits to f32.
    dtype: str = "float32"
    #: sequence-parallel attention override: a ``(q, k, v) -> out`` callable
    #: (e.g. from :func:`easydl_tpu.ops.sequence_parallel.make_sp_attention`)
    #: replacing the local attention — ring/Ulysses over the mesh's sp axis.
    attention_fn: Optional[Callable] = None
    #: tie the LM head to the token embedding (GPT-2 does)
    tied_head: bool = True
    #: pipeline parallelism over the mesh's ``pp`` axis: ``pipeline_fn``
    #: (from :func:`easydl_tpu.ops.pipeline.make_pipeline`, closing over the
    #: mesh like ``attention_fn`` does) runs the block stack as a GPipe
    #: fill-drain schedule; ``pipeline_stages`` is the pp size (must divide
    #: ``n_layers``). Params stay the same stacked [n_layers, ...] layout —
    #: the stage split is purely a ``layers → pp`` sharding rule.
    pipeline_fn: Optional[Callable] = None
    pipeline_stages: int = 0
    #: mixture-of-experts: replace each block's FFN with ``moe_experts``
    #: expert FFNs routed top-``moe_k`` (0 = dense). Experts shard over the
    #: mesh's ``ep`` axis (easydl_tpu/ops/moe.py).
    moe_experts: int = 0
    moe_k: int = 2
    moe_capacity_factor: float = 1.25

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def param_count(self) -> int:
        if self.moe_experts:
            ffn = (
                self.moe_experts * 2 * self.d_model * self.d_ff  # expert FFNs
                + self.d_model * self.moe_experts                # router
            )
        else:
            ffn = 2 * self.d_model * self.d_ff
        per_block = (
            4 * self.d_model * self.d_model      # qkv + out projections
            + ffn
            + 4 * self.d_model                   # biases-ish + 2 LN
        )
        emb = self.vocab * self.d_model + self.max_seq * self.d_model
        head = 0 if self.tied_head else self.vocab * self.d_model
        return emb + self.n_layers * per_block + head


class Block(nn.Module):
    """Pre-LN transformer block (attention + MLP).

    Returns ``(x, None)`` — the (carry, per-step-output) pair ``nn.scan``
    expects; standalone callers unpack the first element.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        # NB: ``deterministic`` is positional — nn.scan drops kwargs.
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))

        # The two scopes put every operation of a block, residual adds,
        # GELU and logical constraints included, under `attention` or `ffn`
        # in the compiled program's op_name paths (read by the device
        # trace's reducers); flax's module names sit inside them.
        with jax.named_scope("attention"):
            h = _layernorm("ln_attn", dtype=dt)(x)
            qkv_shape = (cfg.n_heads, cfg.head_dim)
            q = _dense(qkv_shape, ("embed", "heads", "kv"), ("heads", "kv"),
                       name="q", dtype=dt)(h)
            k = _dense(qkv_shape, ("embed", "heads", "kv"), ("heads", "kv"),
                       name="k", dtype=dt)(h)
            v = _dense(qkv_shape, ("embed", "heads", "kv"), ("heads", "kv"),
                       name="v", dtype=dt)(h)
            q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "kv"))
            k = nn.with_logical_constraint(k, ("batch", "seq", "heads", "kv"))
            v = nn.with_logical_constraint(v, ("batch", "seq", "heads", "kv"))
            if cfg.attention_fn is not None:  # sequence-parallel (ring/Ulysses)
                attn = cfg.attention_fn(q, k, v, causal=cfg.causal)
            else:
                attn = multihead_attention(
                    q, k, v, causal=cfg.causal, impl=cfg.attention_impl
                )
            attn = _dense(
                cfg.d_model,
                ("heads", "kv", "embed"),
                ("embed",),
                name="out",
                init_scale=(2 * cfg.n_layers) ** -0.5,  # GPT-2 residual scaling
                axis=(-2, -1),
                dtype=dt,
            )(attn)
            if cfg.dropout and not deterministic:
                attn = nn.Dropout(cfg.dropout, deterministic=False)(attn)
            x = x + attn

        aux = jnp.zeros((), jnp.float32)
        with jax.named_scope("ffn"):
            h = _layernorm("ln_mlp", dtype=dt)(x)
            if cfg.moe_experts:
                from easydl_tpu.ops.moe import MoeMlp

                h, aux = MoeMlp(
                    num_experts=cfg.moe_experts,
                    d_ff=cfg.d_ff,
                    k=cfg.moe_k,
                    capacity_factor=cfg.moe_capacity_factor,
                    out_init_scale=(2 * cfg.n_layers) ** -0.5,
                    dtype=cfg.dtype,
                    name="moe",
                )(h)
            else:
                h = _dense(cfg.d_ff, ("embed", "mlp"), ("mlp",), name="up",
                           dtype=dt)(h)
                h = nn.gelu(h)
                h = _dense(
                    cfg.d_model, ("mlp", "embed"), ("embed",), name="down",
                    init_scale=(2 * cfg.n_layers) ** -0.5,
                    dtype=dt,
                )(h)
            if cfg.dropout and not deterministic:
                h = nn.Dropout(cfg.dropout, deterministic=False)(h)
            x = x + h
        return nn.with_logical_constraint(x, ("batch", "seq", "embed")), aux


class Transformer(nn.Module):
    """Token-in, logits-out decoder/encoder stack.

    ``return_hidden=True`` skips the head matmul and yields the post-LN
    hidden states ``[B, S, D]`` instead of logits — the input contract of
    the chunked fused LM loss (ops/fused_xent.py), which applies the (tied)
    head chunk-by-chunk so the full ``[B, S, V]`` f32 logits tensor never
    exists.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, *, deterministic: bool = True,
                 return_hidden: bool = False):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        tok_emb = nn.Embed(
            cfg.vocab,
            cfg.d_model,
            dtype=dt,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")
            ),
            name="tok_emb",
        )
        pos_emb = self.param(
            "pos_emb",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.01), ("seq", "embed")
            ),
            (cfg.max_seq, cfg.d_model),
        )
        seq = tokens.shape[1]
        x = tok_emb(tokens) + jnp.asarray(pos_emb, dt)[None, :seq]
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))

        block_cls = Block
        if cfg.remat:
            if cfg.remat_policy not in ("full", "dots"):
                raise ValueError(
                    f"remat_policy must be 'full' or 'dots', got "
                    f"{cfg.remat_policy!r}"
                )
            policy = (
                jax.checkpoint_policies.dots_saveable
                if cfg.remat_policy == "dots" else None
            )
            block_cls = nn.remat(Block, prevent_cse=False, policy=policy)
        # One traced block, scanned over a stacked 'layers' param axis.
        scan_kwargs = dict(
            variable_axes={"params": 0},
            split_rngs={"params": True, "dropout": True},
            in_axes=(nn.broadcast,),
            metadata_params={nn.PARTITION_NAME: "layers"},
        )
        scanned = nn.scan(block_cls, length=cfg.n_layers,
                          **scan_kwargs)(cfg, name="blocks")
        if cfg.pipeline_fn is None or self.is_initializing():
            # plain (or init) path: params are created here with the
            # stacked [n_layers, ...] layout the pipeline also expects
            x, layer_aux = scanned(x, deterministic)
        else:
            if cfg.moe_experts:
                raise NotImplementedError("MoE inside the pipeline")
            if cfg.dropout and not deterministic:
                # The stage apply below passes no rngs, so a non-
                # deterministic dropout>0 apply would otherwise die with an
                # opaque flax missing-'dropout'-rng error deep inside
                # shard_map tracing. v1 pipeline scope is dropout-free at
                # train time — say so. (Deterministic applies — eval,
                # embedding extraction — need no rng and stay allowed.)
                raise NotImplementedError(
                    f"dropout={cfg.dropout} with pipeline_fn: the pipeline "
                    "path applies stages without rngs (v1 trains "
                    "dropout-free; deterministic applies are fine)"
                )
            if cfg.n_layers % cfg.pipeline_stages:
                raise ValueError(
                    f"n_layers={cfg.n_layers} not divisible by "
                    f"pipeline_stages={cfg.pipeline_stages}"
                )
            fn_stages = getattr(cfg.pipeline_fn, "stages", None)
            if fn_stages is not None and fn_stages != cfg.pipeline_stages:
                # A mismatch would otherwise surface as an opaque scan
                # axis-size error deep inside shard_map tracing.
                raise ValueError(
                    f"pipeline_stages={cfg.pipeline_stages} != the "
                    f"pipeline_fn's mesh pp size {fn_stages}"
                )
            # Apply the SAME stacked params through the GPipe schedule: a
            # standalone scan of length n_layers/pp has an identical param
            # tree structure, so each stage applies its [L/pp, ...] slice.
            chunk = nn.scan(
                block_cls, length=cfg.n_layers // cfg.pipeline_stages,
                **scan_kwargs,
            )(cfg)
            stacked = nn.meta.unbox(self.variables["params"]["blocks"])

            def apply_stage(stage_params, h):
                y, _ = chunk.apply({"params": stage_params}, h, deterministic)
                return y

            # block_remat tells the pipeline whether the blocks already
            # carry nn.remat (then its own stage checkpoint would double
            # the backward recompute)
            x = cfg.pipeline_fn(apply_stage, stacked, x,
                                block_remat=cfg.remat)
            layer_aux = jnp.zeros((cfg.n_layers,), jnp.float32)
        # Per-layer MoE load-balance losses (zeros for dense blocks); read
        # back by MoE loss fns via mutable=["intermediates"] — a no-op sow
        # for plain apply() calls.
        self.sow("intermediates", "moe_aux_loss", jnp.sum(layer_aux))

        x = _layernorm("ln_f", dtype=dt)(x)
        if return_hidden:
            return x
        with jax.named_scope("lm_head"):
            if cfg.tied_head:
                logits = tok_emb.attend(x)
            else:
                logits = _dense(
                    cfg.vocab, ("embed", "vocab"), (), name="head",
                    use_bias=False,
                )(x)
        return logits
