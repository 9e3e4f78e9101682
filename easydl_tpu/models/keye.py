"""Keye-VL-2.0-30B-A3B's LANGUAGE MODEL (Kwai-Keye; HF model type
``KeyeVL2``): an autoregressive fine-grained mixture-of-experts decoder with
SDAR-30B-A3B's widths exactly — every layer sparse, a linear softmax router
over all experts, the top ``k`` renormalised, small SwiGLU experts and nothing
shared; GQA 32 over 4 heads of 128 with an RMSNorm over each head's
dimensions on q and k, rotary over the whole head, pre-norm RMSNorm, no
biases, untied head — whose attention is SPARSE BY A LEARNED INDEX: in front
of every layer's attention stands DeepSeek Sparse Attention's lightning
indexer (``sa_config``: 16 index heads of 64 against ONE index key a token,
top-2,048; ``models/transformer.py LearnedIndex``, ``ops/index.py``), so
query ``t`` attends to the ``min(t + 1, 2048)`` causal keys the index ranks
highest, and the index is trained by its own loss, a layer's ``mean_t KL(p_t
|| softmax_{S_t} I_t)`` against the attention's probabilities (``models/lm.py
lm_bundle``: the objective is the next-token loss plus the layers' sum). One
description of ``models/transformer.py``'s stack; nothing here but the
published numbers and Mellum 2's one start of its own, the embedding table at
unit scale (``embedding_init_std``), for SDAR's reason.

The vision tower is not here (the published ``config`` this follows is the
language model's; ``core/data.py`` has no image input path): the traffic is
text. With text alone the three position streams of the multimodal rotary
(``rope_scaling.mrope_section`` [16, 24, 24]) are equal, and the rotation is
the default one (``tests/test_index.py`` holds the written-out sections to
``ops/rope.py rope_tables``).

``size="vl-2.0-30b-a3b"`` is the language model as published
(huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B, ``config.json``): 48 identical
layers, 2048 wide, 128 experts of 768, top-8. A chip runs a share of it, as
SDAR's factory takes it: ``layer_types`` states the depth in the published
vocabulary (``full_attention`` is the one kind), ``experts_held`` the
contiguous range of routed experts this chip holds of each layer (the router
keeps its published width), ``vocab`` its slice of the vocabulary.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

from easydl_tpu.models.lm import lm_bundle
from easydl_tpu.models.registry import ModelBundle, register_model
from easydl_tpu.models.transformer import (AttentionKind, LearnedIndex,
                                           MoeConfig, RopeScheme,
                                           TransformerConfig)
from easydl_tpu.ops.moe import ROUTERS

_KIND = "full_attention"

#: name -> widths; keys as the published ``config.json`` has them
#: (``sa_config``'s under their own names)
SIZES: Dict[str, Dict[str, Any]] = {
    "vl-2.0-30b-a3b": dict(
        hidden_size=2048, head_dim=128, num_attention_heads=32,
        num_key_value_heads=4, num_experts=128, num_experts_per_tok=8,
        moe_intermediate_size=768, norm_topk_prob=True, rope_theta=10000000.0,
        rms_norm_eps=1e-6, num_hidden_layers=48, indexer_num_heads=16,
        indexer_head_dim=64, topk=2048, q_chunk_size=512, kv_chunk_size=512),
    # tiny, for tests and dry runs: every mechanism — 4 query heads over 2
    # key/value heads of 128 (a head the rotary kernel takes), the q/k norm,
    # an index of 2 heads of 64 whose top-k is SMALLER than a test's
    # sequence, a softmax router over 16 experts top-4, nothing shared
    "test": dict(
        hidden_size=64, head_dim=128, num_attention_heads=4,
        num_key_value_heads=2, num_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=32, norm_topk_prob=True, rope_theta=10000000.0,
        rms_norm_eps=1e-6, num_hidden_layers=3, indexer_num_heads=2,
        indexer_head_dim=64, topk=96, q_chunk_size=256, kv_chunk_size=256),
}


def describe(
    size: str = "vl-2.0-30b-a3b",
    seq_len: int = 16384,
    vocab: int = 151936,
    layer_types: Optional[Sequence[str]] = None,
    experts_held: Optional[Tuple[int, int]] = None,
    remat: bool = False,
    remat_policy: str = "full",
    attention_impl: str = "auto",
    dtype: str = "float32",
) -> TransformerConfig:
    """The stack's description of the language model of ``size`` at
    ``seq_len`` tokens."""
    w = SIZES[size]
    kinds = tuple(layer_types or (_KIND,) * w["num_hidden_layers"])
    if set(kinds) != {_KIND}:
        raise ValueError(f"Keye's layers are all {_KIND!r} with a sparse "
                         f"FFN; got {kinds}")
    lo, hi = experts_held or (0, w["num_experts"])
    return TransformerConfig(
        vocab=vocab,
        d_model=w["hidden_size"],
        n_heads=w["num_attention_heads"],
        n_kv_heads=w["num_key_value_heads"],
        head_size=w["head_dim"],
        n_layers=len(kinds),
        d_ff=0,  # no dense layer
        max_seq=seq_len,
        remat=remat,
        remat_policy=remat_policy,
        attention_impl=attention_impl,
        dtype=dtype,
        tied_head=False,
        layers=tuple((_KIND, "moe") for _ in kinds),
        norm="rmsnorm",
        norm_eps=w["rms_norm_eps"],
        position="none",  # the attention kind brings its rotary scheme
        bias=False,
        # a stand-in for a trained model's token-specific stream, not a
        # published number (``TransformerConfig.embedding_init_std``)
        embedding_init_std=1.0,
        attention_kinds=((_KIND, AttentionKind(
            rope=RopeScheme(theta=float(w["rope_theta"])), qk_norm=True,
            index=LearnedIndex(
                n_heads=w["indexer_num_heads"],
                head_dim=w["indexer_head_dim"], topk=w["topk"],
                q_chunk=w["q_chunk_size"], kv_chunk=w["kv_chunk_size"]))),),
        moe=MoeConfig(
            experts_total=w["num_experts"], experts_held=(int(lo), int(hi)),
            k=w["num_experts_per_tok"], d_ff=w["moe_intermediate_size"],
            router=ROUTERS[2]),  # ``norm_topk_prob``: the renormalised form
    )


@register_model("keye")
def make_keye(**description) -> ModelBundle:
    """``description``: the arguments of :func:`describe`. The objective is
    the next-token loss plus the layers' index losses (``models/lm.py
    lm_bundle``)."""
    cfg = describe(**description)
    size = description.get("size", "vl-2.0-30b-a3b")
    lo, hi = cfg.moe.experts_held
    return lm_bundle(cfg, f"keye-{size}-{cfg.n_layers}l-e{lo}-{hi}")
