"""Operations and bytes of one chip's share of an SDAR (JetLM, model type
``sdar_moe``) TRAINED BY BLOCK DIFFUSION, computed from a configuration file's
published keys and its block length. Counts only: checked by hand in
``tests/test_flops_sdar.py``; nothing is imported from the program.

A data token is TWO rows through every layer (its noised and its clean one)
and one through the head (the noised half alone). Of an attention layer's
scores only the pairs the block mask keeps count — ``L² + L B`` of the ``4
L²`` of a sequence's ``2 L`` rows (:func:`live_pairs`) — and of a layer's
routed experts only the rows routed to the experts held here
(``rows_per_row``, the layer's own counter, a ROW: ``num_experts_per_tok x
held / published`` on average, 1 in the benchmark's cell). A share read here
is of what is needed, so a kernel that multiplied dead pairs reads low, never
high.
"""

from __future__ import annotations

from typing import Any, Dict

from lib.flops import FLASH_CALLS  # products and arrays of each flash call


def attention_products(config: Dict[str, Any]) -> int:
    """q and o at the query heads, k and v at the key/value heads; no bias."""
    d, hd = config["hidden_size"], config["head_dim"]
    return 2 * d * config["num_attention_heads"] * hd \
        + 2 * d * config["num_key_value_heads"] * hd


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert: three SwiGLU matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_params(config: Dict[str, Any]) -> int:
    """One layer as held here: attention, the two gains of the q/k norm, two
    norms, the router at its published width, ``num_experts`` experts."""
    d = config["hidden_size"]
    return (attention_products(config) + 2 * config["head_dim"] + 2 * d
            + d * config["router_width"]
            + config["num_experts"] * expert_params(config))


def param_count(config: Dict[str, Any]) -> int:
    """Held here: embedding and untied head over the vocabulary held, the
    layers, the final norm."""
    d = config["hidden_size"]
    return 2 * config["vocab_size"] * d + d \
        + len(config["layer_types"]) * layer_params(config)


def live_pairs(seq: int, block: int) -> int:
    """(query, key) pairs the block mask keeps over a sequence's ``2 seq``
    rows, a head: clean-clean by blocks ``seq (seq + block) / 2``,
    noised-clean strictly before ``seq (seq - block) / 2``, noised-noised a
    block each ``seq block``."""
    return seq * (seq + block) // 2 + seq * (seq - block) // 2 + seq * block


def train_flops_per_token(config: Dict[str, Any], seq_len: int,
                          rows_per_row: float) -> float:
    """ACTIVE model FLOPs of one training DATA token, forward and backward,
    recomputed operations not counted: 6 per active parameter of the matrix
    products — a layer's twice (two rows a token; the routed experts' at
    ``rows_per_row`` rows a row), the head's once (the untied embedding is a
    lookup) — and ``6 x 2 x head_dim`` per (live pair, query head), a
    token's share of ``live_pairs``."""
    d = config["hidden_size"]
    layer = attention_products(config) + d * config["router_width"] \
        + rows_per_row * expert_params(config)
    n_layers = len(config["layer_types"])
    pair = 6.0 * config["num_attention_heads"] * 2 * config["head_dim"]
    return (6.0 * (2 * n_layers * layer + config["vocab_size"] * d)
            + pair * n_layers
            * live_pairs(seq_len, config["block_length"]) / seq_len)


def flash_block_cost(kind: str, batch: int, rows: int, heads: int,
                     kv_heads: int, head_dim: int, block: int,
                     bytes_per_el: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes one call of ``kind`` (``fwd`` or ``bwd``) under
    the block mask needs on ``[batch, rows, heads x head_dim]`` q, ``rows``
    = twice the sequence: 2 FLOPs a LIVE pair and lane of each product (two
    forward: ``4 d`` a pair and head; five in the one-call backward: ``10
    d``); q, O (and dO, dq) at the query heads, k and v (and dk, dv) at the
    ``kv_heads`` a grouped kernel could not avoid reading or writing (the
    program repeats them to the query heads in HBM: the share reads low for
    it, never high), float32 ``lse`` a row and head."""
    call = FLASH_CALLS[kind]
    flops = batch * heads * 2.0 * live_pairs(rows // 2, block) \
        * call["matmuls"] * head_dim
    arrays = 2 if kind == "fwd" else 4  # q, O | k, v; and their gradients'
    bytes_ = batch * rows * (
        arrays * (heads + kv_heads) * head_dim * bytes_per_el + heads * 4)
    return {"flops": flops, "bytes": float(bytes_)}
