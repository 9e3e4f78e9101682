"""NemotronH (NVIDIA; HF model type ``nemotron_h``; Nemotron 3 Nano
30B-A3B): a stack whose layers are ONE sub-layer each, from its own RMSNorm
to its own residual add, in the order ``hybrid_override_pattern`` spells:
``M`` a Mamba-2 mixer (``n_groups`` B/C groups, the gated norm behind the scan
taken over each group's channels apart), ``*`` grouped-query attention without
positions, ``E`` a fine-grained mixture-of-experts layer (a sigmoid router
over all experts that selects by its scores plus a per-expert bias which takes
no gradient and weighs by the scores without it, top-k renormalised and
scaled; every expert and the shared one UNGATED, two matrices,
``relu(h W_up)^2 W_down``: ``mlp_hidden_act`` ``relu2``), ``-`` a dense MLP
(the published pattern has none, and this module raises on it). Pre-norm
RMSNorm, no bias on a map, untied head. One description of
``models/transformer.py``'s stack; nothing here but the published numbers and
the pattern's parser.

The stack's layer is a block ``(mixer, ffn)``: ``x += mixer(norm(x)); x +=
ffn(norm(x))``. Two published sub-layers ``M E`` or ``* E`` are exactly one
block; a mixer that another mixer follows is a block whose FFN is ``none``
(:func:`blocks_of`). So ``MEMEM*EME`` is five blocks.

``size="nano-30b-a3b"`` is NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 as published
(huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``config.json``):
52 sub-layers 2688 wide (23 ``M``, 23 ``E``, 6 ``*``), Mamba-2 of 64 heads of
64 with a state of 128 in 8 groups and chunks of 128, 32 query heads of 128
over 2 key/value heads, 128 experts of 1,856, top-6, one shared expert of
3,712, 131,072-row vocabulary. A chip runs a share of it:
``hybrid_override_pattern`` states the depth in the published vocabulary,
``experts_held`` the contiguous range of routed experts this chip holds of
each expert layer (the router keeps its published width), ``vocab`` its slice
of the vocabulary.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from easydl_tpu.models.lm import lm_bundle
from easydl_tpu.models.registry import ModelBundle, register_model
from easydl_tpu.models.transformer import (Layer, MoeConfig, SsmConfig,
                                           TransformerConfig)

_PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
#: the pattern's letters that open a block, and the stack's mixer for each
_MIXER = {"M": "mamba2", "*": "attention"}

#: name -> widths; keys as the published ``config.json`` has them
SIZES: Dict[str, Dict[str, Any]] = {
    "nano-30b-a3b": dict(
        hidden_size=2688, num_attention_heads=32, num_key_value_heads=2,
        head_dim=128, mamba_num_heads=64, mamba_head_dim=64,
        ssm_state_size=128, n_groups=8, conv_kernel=4, chunk_size=128,
        n_routed_experts=128, num_experts_per_tok=6,
        moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
        n_shared_experts=1, routed_scaling_factor=2.5, mlp_hidden_act="relu2",
        layer_norm_epsilon=1e-5,
        hybrid_override_pattern=_PUBLISHED_PATTERN),
    # tiny, for tests and dry runs: every mechanism — two B/C groups of two
    # heads, 4 query heads over 2 key/value heads, 16 experts top-3 of a
    # ragged width (24: no multiple of 16, as 1,856 is none of 128) and a
    # shared one, a mixer behind a mixer
    "test": dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, mamba_num_heads=4, mamba_head_dim=16,
        ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=16,
        n_routed_experts=16, num_experts_per_tok=3,
        moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
        n_shared_experts=1, routed_scaling_factor=2.5, mlp_hidden_act="relu2",
        layer_norm_epsilon=1e-5, hybrid_override_pattern="MEM*EME"),
}


def blocks_of(pattern: str) -> Tuple[Layer, ...]:
    """``hybrid_override_pattern`` as the stack's blocks ``(mixer, ffn)``: a
    mixer (``M`` | ``*``) opens a block, an ``E`` behind it is that block's
    FFN (``moe``), a mixer behind a mixer leaves the first block's FFN
    ``none``. ``MEMEM*EME`` is ``(mamba2, moe) x 2, (mamba2, none),
    (attention, moe), (mamba2, moe)``. Raises on ``-`` (a dense MLP layer:
    the published pattern has none and the stack's ``swiglu`` is not its
    form), on any other letter, and on an ``E`` that follows no mixer (the
    pattern's first letter, or a second ``E`` in a row): the stack has no
    block that is an FFN alone."""
    blocks, open_mixer = [], None
    for i, letter in enumerate(pattern):
        if letter in _MIXER:
            if open_mixer is not None:
                blocks.append((open_mixer, "none"))
            open_mixer = _MIXER[letter]
        elif letter == "E":
            if open_mixer is None:
                raise ValueError(
                    f"hybrid_override_pattern {pattern!r}: the E at {i} "
                    f"follows no mixer; the stack's block is (mixer, ffn)")
            blocks.append((open_mixer, "moe"))
            open_mixer = None
        elif letter == "-":
            raise NotImplementedError(
                f"hybrid_override_pattern {pattern!r}: '-' at {i} is a dense "
                f"MLP layer, which Nemotron 3 Nano's pattern does not have "
                f"and this description does not build")
        else:
            raise ValueError(f"hybrid_override_pattern {pattern!r}: unknown "
                             f"letter {letter!r} at {i}; M, *, E")
    if open_mixer is not None:
        blocks.append((open_mixer, "none"))
    return tuple(blocks)


def describe(
    size: str = "nano-30b-a3b",
    seq_len: int = 8192,
    vocab: int = 131072,
    hybrid_override_pattern: Optional[str] = None,
    experts_held: Optional[Tuple[int, int]] = None,
    remat: bool = False,
    remat_policy: str = "full",
    attention_impl: str = "auto",
    dtype: str = "float32",
) -> TransformerConfig:
    """The stack's description of a NemotronH of ``size``. Two starts are
    this description's own words and not the source's: zero biases for the
    convolutions and zero column sums for the ``relu2`` down maps
    (``SsmConfig.conv_bias_zero``, ``MoeConfig.down_zero_sums``). They stand
    in for the load-driven selection bias of a trained model, which no
    trainer here updates: without them the rows on the held experts, and the
    step, follow the seed."""
    w = SIZES[size]
    blocks = blocks_of(hybrid_override_pattern
                       or w["hybrid_override_pattern"])
    lo, hi = experts_held or (0, w["n_routed_experts"])
    return TransformerConfig(
        vocab=vocab,
        d_model=w["hidden_size"],
        n_heads=w["num_attention_heads"],
        n_kv_heads=w["num_key_value_heads"],
        head_size=w["head_dim"],
        n_layers=len(blocks),
        d_ff=w["moe_intermediate_size"],  # read by no block: no dense FFN
        max_seq=seq_len,
        causal=True,
        remat=remat,
        remat_policy=remat_policy,
        attention_impl=attention_impl,
        dtype=dtype,
        tied_head=False,
        layers=blocks,
        norm="rmsnorm",
        norm_eps=w["layer_norm_epsilon"],
        position="none",
        bias=False,
        ssm=SsmConfig(
            n_heads=w["mamba_num_heads"], head_dim=w["mamba_head_dim"],
            d_state=w["ssm_state_size"], n_groups=w["n_groups"],
            d_conv=w["conv_kernel"], chunk=w["chunk_size"],
            grouped_norm=True, conv_bias_zero=True),
        moe=MoeConfig(
            experts_total=w["n_routed_experts"],
            experts_held=(int(lo), int(hi)), k=w["num_experts_per_tok"],
            d_ff=w["moe_intermediate_size"],
            shared_d_ff=w["n_shared_experts"]
            * w["moe_shared_expert_intermediate_size"],
            scaling=w["routed_scaling_factor"], selection_bias=True,
            expert_form=w["mlp_hidden_act"], down_zero_sums=True),
    )


@register_model("nemotron_h")
def make_nemotron_h(**description) -> ModelBundle:
    """``description``: the arguments of :func:`describe`. The head is the
    fused chunked one wherever full logits would not fit
    (``models/lm.py fused_head_by_shape``)."""
    cfg = describe(**description)
    size = description.get("size", "nano-30b-a3b")
    lo, hi = cfg.moe.experts_held
    return lm_bundle(cfg, f"nemotron-h-{size}-{cfg.n_layers}b-e{lo}-{hi}")
