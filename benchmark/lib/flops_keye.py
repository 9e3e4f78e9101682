"""Operations and bytes of one chip's share of Keye-VL-2.0-30B-A3B's language
model (HF model type ``KeyeVL2``): grouped-query attention behind a learned
index over keys, computed from a configuration file's published keys. Counts
only: checked by hand in ``tests/test_flops_keye.py``; nothing is imported
from the program.

Every count is of what the MATHEMATICS needs, not of what an implementation
visits, so that a share read here is the same work whatever kernel does it,
passes 100% under none, and rises when a kernel skips dead tiles: an
attention layer's scores by the SELECTED pairs (``sum_t min(t + 1, topk)`` a
head: :func:`selected_pairs`), the index's scores by the CAUSAL pairs (to rank
a query's keys every one of them has to be scored: :func:`causal_pairs`), the
index's own loss and its gradients by the selected pairs (it is a sum over
``S_t``), and of a layer's routed experts only the rows routed to the experts
held here (``rows_per_row``).
"""

from __future__ import annotations

from typing import Any, Dict

from lib.flops import FLASH_CALLS  # products and arrays of each flash call


def attention_products(config: Dict[str, Any]) -> int:
    """q and o at the query heads, k and v at the key/value heads; no bias."""
    d, hd = config["hidden_size"], config["head_dim"]
    return 2 * d * config["num_attention_heads"] * hd \
        + 2 * d * config["num_key_value_heads"] * hd


def index_products(config: Dict[str, Any]) -> int:
    """The index's three maps: its queries, its one key, its head weights."""
    sa = config["sa_config"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return config["hidden_size"] * (heads * dim + dim + heads)


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert: three SwiGLU matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_params(config: Dict[str, Any]) -> int:
    """One layer as held here: attention, the two gains of the q/k norm, the
    index with its key's LayerNorm (gain and bias), two norms, the router at
    its published width, ``num_experts`` experts."""
    d = config["hidden_size"]
    return (attention_products(config) + 2 * config["head_dim"]
            + index_products(config)
            + 2 * config["sa_config"]["indexer_head_dim"] + 2 * d
            + d * config["router_width"]
            + config["num_experts"] * expert_params(config))


def param_count(config: Dict[str, Any]) -> int:
    """Held here: embedding and untied head over the vocabulary held, the
    layers, the final norm."""
    d = config["hidden_size"]
    return 2 * config["vocab_size"] * d + d \
        + len(config["layer_types"]) * layer_params(config)


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs the selection holds, a head and sequence: ``sum_t
    min(t + 1, topk)``."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def train_flops_per_token(config: Dict[str, Any], seq_len: int,
                          rows_per_row: float) -> float:
    """ACTIVE model FLOPs of one training token, forward and backward,
    recomputed operations not counted: 6 per active parameter of the matrix
    products the language model's loss trains (attention, router, the routed
    experts at ``rows_per_row`` rows a row, the head; the untied embedding is
    a lookup) and 4 per parameter of the index's maps (their input is
    detached: a forward and a weight gradient, no input gradient); ``6 x 2 x
    head_dim`` per (selected pair, query head); for the index ``2 x heads x
    dim`` per CAUSAL pair (its scores, forward) and ``4 x heads x dim`` per
    SELECTED pair (its loss's gradient to queries and key) — a token's share
    of each count."""
    d = config["hidden_size"]
    sa = config["sa_config"]
    n_layers = len(config["layer_types"])
    layer = attention_products(config) + d * config["router_width"] \
        + rows_per_row * expert_params(config)
    chosen = selected_pairs(seq_len, sa["topk"]) / seq_len
    causal = causal_pairs(seq_len) / seq_len
    index = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    pairs = 6.0 * config["num_attention_heads"] * 2 * config["head_dim"] \
        * chosen + index * (2.0 * causal + 4.0 * chosen)
    return (6.0 * (n_layers * layer + config["vocab_size"] * d)
            + n_layers * (4.0 * index_products(config) + pairs))


def flash_selected_cost(kind: str, batch: int, seq: int, heads: int,
                        kv_heads: int, head_dim: int, topk: int,
                        bytes_per_el: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes one attention call of ``kind`` (``fwd`` or ``bwd``)
    under a selection needs on ``[batch, seq, heads x head_dim]`` q: 2 FLOPs
    a SELECTED pair and lane of each product (two forward, five in the
    one-call backward); q, O (and dO, dq) at the query heads, k and v (and
    dk, dv) at the key/value heads, float32 ``lse`` a row and head, and the
    selection itself, a bit a pair, read once."""
    call = FLASH_CALLS[kind]
    flops = batch * heads * 2.0 * selected_pairs(seq, topk) \
        * call["matmuls"] * head_dim
    arrays = 2 if kind == "fwd" else 4  # q, O | k, v; and their gradients'
    bytes_ = batch * (seq * (
        arrays * (heads + kv_heads) * head_dim * bytes_per_el + heads * 4)
        + seq * seq // 8)
    return {"flops": flops, "bytes": float(bytes_)}


def index_select_cost(config: Dict[str, Any], seq: int,
                      bytes_per_el: int = 2) -> Dict[str, float]:
    """What scoring and ranking ONE sequence's keys in ONE layer needs: ``2 x
    heads x dim`` FLOPs a CAUSAL pair; the queries, the key and the float32
    weights read, the selection (a bit a pair) and two float32 rows
    written."""
    sa = config["sa_config"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {"flops": 2.0 * heads * dim * causal_pairs(seq),
            "bytes": float(seq * ((heads + 1) * dim * bytes_per_el
                                  + heads * 4 + 8) + seq * seq // 8)}


def index_loss_cost(config: Dict[str, Any], seq: int,
                    bytes_per_el: int = 2) -> Dict[str, float]:
    """What ONE sequence's index loss and its gradients need in ONE layer, by
    the SELECTED pairs: the attention's scores again for its probabilities
    (``2 x query heads x head_dim``), the index's scores (``2 x heads x
    dim``) and the two products of the gradient to its queries and its key
    (``4 x heads x dim``); read: the index's three inputs, the attention's q
    and k, its ``lse`` rows, the selection; written: the three
    gradients."""
    sa = config["sa_config"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    q_heads, kv_heads = (config["num_attention_heads"],
                         config["num_key_value_heads"])
    hd = config["head_dim"]
    pair = 2.0 * q_heads * hd + 6.0 * heads * dim
    index_in = (heads + 1) * dim * bytes_per_el + heads * 4
    return {"flops": pair * selected_pairs(seq, sa["topk"]),
            "bytes": float(seq * (
                2 * index_in + (q_heads + kv_heads) * hd * bytes_per_el
                + q_heads * 4 + 8) + seq * seq // 8)}
