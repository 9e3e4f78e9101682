"""Operations and bytes of one chip's share of a JoyAI-LLM (JD, model type
``joyai_llm_flash``) computed from a configuration file's published keys.
Counts only: checked by hand in ``tests/test_flops_joyai.py``; nothing is
imported from the program.

ACTIVE operations, as ``lib/flops_laguna.py`` counts them: of a sparse
layer's routed experts only the rows that were routed to the experts held
here count (``rows_per_token``, the layer's own counter: ``num_experts_per_tok
x held / published`` on average, 0.5 in the benchmark's cell), and of an
attention layer's scores only the pairs the causal mask keeps. Latent
attention scores ``qk_nope_head_dim + qk_rope_head_dim`` deep and weighs
values ``v_head_dim`` wide: a pair and head costs ``2 x (192 + 128)`` FLOPs
forward, not ``4 x head_dim``. The multi-token-prediction module is one more
sparse layer, a ``2 d -> d`` join, and the head a second time.

The kernels' own cost (:func:`mla_flash_cost`) follows what runs: the rotated
key vector is copied to every head in HBM in front of the kernels, so k is
read at ``heads x 192`` lanes like q, 32 times the one vector a token.
"""

from __future__ import annotations

from typing import Any, Dict

from lib.flops_laguna import seen_pairs

#: products a latent-attention flash call makes, by what they contract
#: over: ``scores`` are ``S = K Q^T`` and the gradients through it (``dQ =
#: dS K``, ``dK = dS^T Q``), 192 deep; ``values`` are ``P V``, ``dP = V
#: dO^T`` and ``dV = P^T dO``, 128 deep. dq and dkv each form S and dP again;
#: ``bwd``, the looped backward's one call (PR 39), forms them once: three
#: products at the scores' depth and two at the values'.
#: Arrays read and written, at the scores' width (q, k, dq, dk) and at the
#: values' (v, O, dO, dv); float32 rows a head (``lse``).
MLA_CALLS: Dict[str, Dict[str, int]] = {
    "fwd": {"scores": 1, "values": 1, "at_scores": 2, "at_values": 2,
            "vecs": 1},
    "dq": {"scores": 2, "values": 1, "at_scores": 3, "at_values": 3,
           "vecs": 1},
    "dkv": {"scores": 2, "values": 2, "at_scores": 3, "at_values": 4,
            "vecs": 1},
    "bwd": {"scores": 3, "values": 2, "at_scores": 4, "at_values": 4,
            "vecs": 1},
}


def score_dim(config: Dict[str, Any]) -> int:
    return config["qk_nope_head_dim"] + config["qk_rope_head_dim"]


def mla_params(config: Dict[str, Any]) -> int:
    """The attention sub-layer: down to both latents (the key's rotated
    vector beside the second), a norm's gain each, up to the heads' q and to
    their ``[k_nope ; v]``, the way back from the values."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    r_q, r_kv = config["q_lora_rank"], config["kv_lora_rank"]
    return ((d + 1 + heads * score_dim(config)) * r_q
            + d * (r_kv + config["qk_rope_head_dim"]) + r_kv
            + r_kv * heads * (config["qk_nope_head_dim"]
                              + config["v_head_dim"])
            + heads * config["v_head_dim"] * d)


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert: three SwiGLU matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def ffn_params(config: Dict[str, Any], kind: str, experts: float) -> float:
    """A dense layer's SwiGLU, or a sparse layer's router (at its published
    width, with its selection bias), shared experts and ``experts`` routed
    experts."""
    d = config["hidden_size"]
    if kind == "dense":
        return 3 * d * config["intermediate_size"]
    return ((d + 1) * config["n_routed_experts_published"]
            + (config["n_shared_experts"] + experts) * expert_params(config))


def layer_params(config: Dict[str, Any], kind: str, experts: float) -> float:
    """A layer: latent attention, its FFN, two norms."""
    return mla_params(config) + ffn_params(config, kind, experts) \
        + 2 * config["hidden_size"]


def module_params(config: Dict[str, Any], experts: float) -> float:
    """The multi-token-prediction module's own: a sparse layer, the join
    ``[2 d, d]``, three norms."""
    d = config["hidden_size"]
    return layer_params(config, "sparse", experts) + 2 * d * d + 3 * d


def param_count(config: Dict[str, Any]) -> int:
    """Held here: embedding and untied head over the vocabulary held, the
    layers with ``n_routed_experts`` routed experts each, the final norm,
    the module where ``kwargs.mtp`` holds it."""
    d = config["hidden_size"]
    held = config["n_routed_experts"]
    n = 2 * config["vocab_size"] * d + d + sum(
        layer_params(config, kind, held) for kind in config["layer_types"])
    if config["kwargs"].get("mtp", True):
        n += module_params(config, held)
    return int(n)


def train_flops_per_token(config: Dict[str, Any], seq_len: int,
                          rows_per_token: float) -> float:
    """ACTIVE model FLOPs of one training token, forward and backward,
    recomputed operations not counted: 6 per active parameter of the matrix
    products (the routed experts' at ``rows_per_token`` rows a token and
    sparse layer; the untied embedding is a lookup; the head counts once for
    the main stack and once for the module) and ``6 x (192 + 128)`` per
    (pair, head) the causal mask keeps in every attention layer, the
    module's among them, a token's share."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    module = config["kwargs"].get("mtp", True)
    products = config["vocab_size"] * d * (2 if module else 1) + sum(
        layer_params(config, kind, rows_per_token)
        for kind in config["layer_types"])
    if module:
        products += module_params(config, rows_per_token)
    layers = len(config["layer_types"]) + int(module)
    pair = 6.0 * heads * (score_dim(config) + config["v_head_dim"])
    return 6.0 * products + layers * pair * seen_pairs(seq_len) / seq_len


def mla_flash_cost(kind: str, batch: int, seq: int, heads: int,
                   score: int, value: int,
                   bytes_per_el: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes one causal latent-attention flash call of
    ``kind`` (``fwd``, ``dq``, ``dkv``, ``bwd``) needs on ``[batch, seq, heads x
    score]`` q and k (the shared rotated key copied to every head: what the
    kernels read) and ``[batch, seq, heads x value]`` v, O, dO: 2 FLOPs a
    pair the mask keeps and lane of each product."""
    call = MLA_CALLS[kind]
    flops = batch * heads * 2.0 * seen_pairs(seq) * (
        call["scores"] * score + call["values"] * value)
    bytes_ = batch * heads * seq * (
        (call["at_scores"] * score + call["at_values"] * value)
        * bytes_per_el + call["vecs"] * 4)
    return {"flops": flops, "bytes": float(bytes_)}
