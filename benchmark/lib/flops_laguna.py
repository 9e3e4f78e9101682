"""Operations and bytes of one chip's share of a Laguna (poolside, model type
``laguna``) computed from a configuration file's published keys. Counts only:
checked by hand in ``tests/test_flops_laguna.py``; nothing is imported from
the program.

ACTIVE operations: of a sparse layer's routed experts only the rows that
were routed to the experts held here count (``rows_per_token``, the layer's
own counter: ``num_experts_per_tok x held / published`` on average, 1 in the
benchmark's cell), and of an attention layer's scores only the pairs the
mask keeps: ``j <= i`` in a full layer, ``0 <= i - j < sliding_window`` in a
window layer. The benchmark's older counts (``lib/flops.py``,
``lib/flops_looplm.py``) take a full layer's scores as ``12 x width x
sequence``, twice what a causal kernel needs; a share read here is of what
is needed, so a kernel that visits the whole triangle where a band would do
reads low, not high.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from lib.flops import FLASH_CALLS  # products and arrays of each flash call


def layers(config: Dict[str, Any]) -> List[Tuple[str, str, int]]:
    """``[(attention kind, mlp kind, query heads)]``, one a layer held."""
    return list(zip(config["layer_types"], config["mlp_layer_types"],
                    config["num_attention_heads_per_layer"]))


def runs(config: Dict[str, Any]) -> List[Tuple[str, str, int]]:
    """The layers as the program's runs of equal layers: ``[(attention
    kind, mlp kind, count)]``; run ``i`` is the program's ``blocks_<i>``."""
    out: List[List[Any]] = []
    for kind, mlp, _ in layers(config):
        if out and out[-1][:2] == [kind, mlp]:
            out[-1][2] += 1
        else:
            out.append([kind, mlp, 1])
    return [tuple(run) for run in out]


def attention_params(config: Dict[str, Any], heads: int) -> int:
    """q and o at ``heads`` query heads, k and v at the key/value heads, the
    per-head gate (``gating``)."""
    d, hd = config["hidden_size"], config["head_dim"]
    gate = d * heads if config.get("gating") else 0
    return 2 * d * heads * hd + 2 * d * config["num_key_value_heads"] * hd \
        + gate


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert: three SwiGLU matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def ffn_params(config: Dict[str, Any], mlp: str, experts: float) -> float:
    """A dense layer's SwiGLU, or a sparse layer's router (at its published
    width), shared expert and ``experts`` routed experts."""
    d = config["hidden_size"]
    if mlp == "dense":
        return 3 * d * config["intermediate_size"]
    return (d * config["router_width"]
            + 3 * d * config["shared_expert_intermediate_size"]
            + experts * expert_params(config))


def param_count(config: Dict[str, Any]) -> int:
    """Held here: embedding and untied head over the vocabulary held, the
    layers with ``num_experts`` routed experts each, two norms a layer and
    the final norm."""
    d = config["hidden_size"]
    n = 2 * config["vocab_size"] * d + d
    for _, mlp, heads in layers(config):
        n += attention_params(config, heads) + 2 * d \
            + int(ffn_params(config, mlp, config["num_experts"]))
    return n


def seen_pairs(seq: int, window: int = 0) -> int:
    """(query, key) pairs a causal mask keeps over ``seq`` positions; under
    a ``window`` each query keeps at most that many keys."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def train_flops_per_token(config: Dict[str, Any], seq_len: int,
                          rows_per_token: float) -> float:
    """ACTIVE model FLOPs of one training token, forward and backward,
    recomputed operations not counted: 6 per active parameter of the matrix
    products (the routed experts' at ``rows_per_token`` rows a token and
    sparse layer; the untied embedding is a lookup) and 12 per (pair, head
    dimension) the mask keeps, a token's share."""
    d, hd = config["hidden_size"], config["head_dim"]
    total = 6.0 * config["vocab_size"] * d
    for kind, mlp, heads in layers(config):
        window = config["sliding_window"] if kind == "sliding_attention" \
            else 0
        total += 6.0 * (attention_params(config, heads)
                        + ffn_params(config, mlp, rows_per_token))
        total += 12.0 * heads * hd * seen_pairs(seq_len, window) / seq_len
    return total


def flash_band_cost(kind: str, batch: int, seq: int, width: int,
                    head_dim: int, window: int,
                    bytes_per_el: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes one windowed flash call of ``kind`` (``fwd``,
    ``dq``, ``dkv``) needs on ``[batch, seq, width]`` operands (``width`` =
    heads x head_dim, key/value heads repeated to the query's, as the
    kernels are handed them): 2 FLOPs a pair and lane of each product, over
    the band's pairs alone."""
    call = FLASH_CALLS[kind]
    flops = batch * call["matmuls"] * 2.0 * seen_pairs(seq, window) * width
    mats = call["mats_in"] + call["mats_out"]
    bytes_ = batch * (mats * seq * width * bytes_per_el
                      + call["vecs"] * seq * (width // head_dim) * 4)
    return {"flops": flops, "bytes": float(bytes_)}
