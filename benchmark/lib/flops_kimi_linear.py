"""Operations and bytes of one chip's share of a Kimi Linear model
(moonshotai, model type ``kimi_linear``) computed from a configuration file's
published keys. Counts only: checked by hand in
``tests/test_flops_kimi_linear.py``; nothing is imported from the program.

ACTIVE operations, as ``lib/flops_joyai.py`` counts them: of a sparse layer's
routed experts only the rows that were routed to the experts held here count
(``rows_per_token``: ``num_experts_per_token x held / published`` on average,
0.25 in the benchmark's cell), and of the latent attention layer's scores
only the pairs the causal mask keeps, ``2 x (192 + 128)`` FLOPs a pair and
head forward. A KDA layer's recurrence is counted as the MATHEMATICS of the
delta rule needs it and nothing an implementation adds (:func:`kda_cost`):
three ``d_k x d_v`` products a token and head forward, twice that backward —
no term in a chunk length.
"""

from __future__ import annotations

from typing import Any, Dict

from lib.flops_laguna import seen_pairs


def score_dim(config: Dict[str, Any]) -> int:
    return config["qk_nope_head_dim"] + config["qk_rope_head_dim"]


def _kda(config: Dict[str, Any]):
    lin = config["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def kda_products(config: Dict[str, Any]) -> int:
    """A KDA mixer's matrix products: q, k, v and the way back; the decay's
    and the output gate's low-rank maps (through one head's width); the step
    sizes' map."""
    d = config["hidden_size"]
    heads, size, _ = _kda(config)
    inner = heads * size
    return 4 * d * inner + 2 * (d + inner) * size + d * heads


def kda_params(config: Dict[str, Any]) -> int:
    """A KDA mixer whole: its products, three convolutions' taps, ``A_log``
    a head, ``dt_bias`` a channel, the head norm's one gain."""
    heads, size, taps = _kda(config)
    return kda_products(config) + 3 * taps * heads * size + heads \
        + heads * size + size


def mla_products(config: Dict[str, Any]) -> int:
    """The latent attention's matrix products: q straight to the heads (no
    bottleneck), down to the latent with the shared key part beside it, up
    to the heads' ``[k_nope ; v]``, the way back from the values."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    r_kv = config["kv_lora_rank"]
    return (d * heads * score_dim(config)
            + d * (r_kv + config["qk_rope_head_dim"])
            + r_kv * heads * (config["qk_nope_head_dim"]
                              + config["v_head_dim"])
            + heads * config["v_head_dim"] * d)


def mla_params(config: Dict[str, Any]) -> int:
    return mla_products(config) + config["kv_lora_rank"]  # the latent's norm


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert: three SwiGLU matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def ffn_products(config: Dict[str, Any], kind: str, experts: float) -> float:
    """A dense layer's SwiGLU, or a sparse layer's router (at its published
    width), shared experts and ``experts`` routed experts."""
    d = config["hidden_size"]
    if kind.endswith("dense"):
        return 3 * d * config["intermediate_size"]
    return (d * config["num_experts_published"]
            + (config["num_shared_experts"] + experts)
            * expert_params(config))


def layer_products(config: Dict[str, Any], kind: str,
                   experts: float) -> float:
    mixer = kda_products if kind.startswith("kda") else mla_products
    return mixer(config) + ffn_products(config, kind, experts)


def layer_params(config: Dict[str, Any], kind: str, experts: float) -> float:
    """A layer whole: its mixer, its FFN (a sparse layer's selection bias
    with it), two norms."""
    mixer = kda_params if kind.startswith("kda") else mla_params
    bias = 0 if kind.endswith("dense") else config["num_experts_published"]
    return mixer(config) + ffn_products(config, kind, experts) + bias \
        + 2 * config["hidden_size"]


def param_count(config: Dict[str, Any]) -> int:
    """Held here: embedding and untied head over the vocabulary held, the
    layers with ``num_experts`` routed experts each, the final norm."""
    d = config["hidden_size"]
    return int(2 * config["vocab_size"] * d + d + sum(
        layer_params(config, kind, config["num_experts"])
        for kind in config["layer_types"]))


def kda_cost(config: Dict[str, Any]) -> Dict[str, float]:
    """What the delta rule needs a token and KDA layer, forward and backward
    (``kda_roofline``): FLOPs — ``S'^T k``, the rank-one update and ``S^T
    q``, ``6 d_k d_v`` a head forward and twice that backward; bytes — q, k,
    v and o in bf16, g and beta in float32, read or written once forward;
    the same and o's gradient read, and the five gradients written, once
    backward. No chunk length is in it: whatever kernel does the work, the
    share reads the same work. The chunk form spends more FLOPs than the
    recurrence to reach the MXU and its diagonal sub-blocks are VPU work
    with no published peak: the share reads low."""
    heads, size, _ = _kda(config)
    forward = 6.0 * size * size * heads
    rows = heads * size
    read = 3 * rows * 2 + rows * 4 + heads * 4     # q, k, v; g; beta
    passes = (read + rows * 2) + (read + rows * 2) + (read - rows * 2
                                                      + rows * 2)
    return {"flops": 3.0 * forward, "bytes": float(passes),
            "layers": sum(kind.startswith("kda")
                          for kind in config["layer_types"])}


def train_flops_per_token(config: Dict[str, Any], seq_len: int,
                          rows_per_token: float) -> float:
    """ACTIVE model FLOPs of one training token, forward and backward,
    recomputed operations not counted: 6 per active parameter of the matrix
    products (the routed experts' at ``rows_per_token`` rows a token and
    sparse layer; the untied embedding is a lookup, the head one product),
    ``6 x (192 + 128)`` per (pair, head) the causal mask keeps in every
    latent attention layer, a token's share, and :func:`kda_cost`'s FLOPs in
    every KDA layer."""
    kinds = config["layer_types"]
    products = config["vocab_size"] * config["hidden_size"] + sum(
        layer_products(config, kind, rows_per_token) for kind in kinds)
    pair = 6.0 * config["num_attention_heads"] * (
        score_dim(config) + config["v_head_dim"])
    latent = sum(kind.startswith("mla") for kind in kinds)
    recurrent = kda_cost(config)
    return (6.0 * products + latent * pair * seen_pairs(seq_len) / seq_len
            + recurrent["layers"] * recurrent["flops"])
