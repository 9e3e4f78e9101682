"""Operations and bytes of one chip's share of a Mellum 2 (JetBrains, model
type ``mellum``) computed from a configuration file's published keys. Counts
only: checked by hand in ``tests/test_flops_mellum.py``; nothing is imported
from the program.

ACTIVE operations: of a layer's routed experts only the rows that were routed
to the experts held here count (``rows_per_token``, the layer's own counter:
``num_experts_per_tok x held / published`` on average, 2 in the benchmark's
cell), and of an attention layer's scores only the pairs the mask keeps: ``j
<= i`` in a full layer, ``0 <= i - j < sliding_window`` in a window layer. A
share read here is of what is needed, so a kernel that computes keys outside
the band (a sub-block of 256 queries multiplies 1,280 keys for the 1,024 a
row sees) reads low, not high.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from lib.flops import FLASH_CALLS  # products and arrays of each flash call


def layers(config: Dict[str, Any]) -> List[Tuple[str, str]]:
    """``[(attention kind, mlp kind)]``, one a layer held."""
    return list(zip(config["layer_types"], config["mlp_layer_types"]))


def runs(config: Dict[str, Any]) -> List[Tuple[str, str, int]]:
    """The layers as the program's runs of equal layers: ``[(attention
    kind, mlp kind, count)]``; run ``i`` is the program's ``blocks_<i>``."""
    out: List[List[Any]] = []
    for kind, mlp in layers(config):
        if out and out[-1][:2] == [kind, mlp]:
            out[-1][2] += 1
        else:
            out.append([kind, mlp, 1])
    return [tuple(run) for run in out]


def attention_params(config: Dict[str, Any]) -> int:
    """q and o at the query heads, k and v at the key/value heads; no bias,
    no gate, no q/k norm."""
    d, hd = config["hidden_size"], config["head_dim"]
    return 2 * d * config["num_attention_heads"] * hd \
        + 2 * d * config["num_key_value_heads"] * hd


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert: three SwiGLU matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def expert_layer_products(config: Dict[str, Any], experts: float) -> float:
    """A sparse layer's matrix products: the router at its published width
    and ``experts`` routed experts; nothing shared."""
    return (config["hidden_size"] * config["router_width"]
            + experts * expert_params(config))


def param_count(config: Dict[str, Any]) -> int:
    """Held here: embedding and untied head over the vocabulary held, the
    layers with ``num_experts`` routed experts each, two norms a layer and
    the final norm."""
    d = config["hidden_size"]
    layer = attention_params(config) + 2 * d + int(
        expert_layer_products(config, config["num_experts"]))
    return 2 * config["vocab_size"] * d + d + len(layers(config)) * layer


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
    layer; the untied embedding is a lookup; the head once) and ``6 x 2 x
    head_dim`` per (pair, query head) the mask keeps — the band's in a
    window layer — a token's share."""
    products = config["vocab_size"] * config["hidden_size"]
    pairs = 0.0
    for kind, _ in layers(config):
        window = config["sliding_window"] if kind == "sliding_attention" \
            else 0
        products += attention_params(config) \
            + expert_layer_products(config, rows_per_token)
        pairs += seen_pairs(seq_len, window) / seq_len
    pair = 6.0 * config["num_attention_heads"] * 2 * config["head_dim"]
    return 6.0 * products + pair * pairs


def flash_band_cost(kind: str, batch: int, seq: int, width: int,
                    head_dim: int, window: int, neighbour: float = 0.5,
                    bytes_per_el: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes one band call of ``kind`` (``fwd``, ``dq``,
    ``dkv``) needs on ``[batch, seq, width]`` operands (``width`` = heads x
    head_dim, key/value heads repeated to the query's, as the kernels are
    handed them): 2 FLOPs a pair and lane of each product, over the band's
    pairs alone; the bytes as the kernels read them — every operand's own
    rows once and, of the operands a cell reads a neighbour block of (k and
    v in the forward and dq; q, O and dO in dk/dv), ``neighbour`` times
    more: 1,024 rows beside a cell's 2,048."""
    call = FLASH_CALLS[kind]
    flops = batch * call["matmuls"] * 2.0 * seen_pairs(seq, window) * width
    mats = call["mats_in"] + call["mats_out"] \
        + neighbour * (3 if kind == "dkv" else 2)
    bytes_ = batch * (mats * seq * width * bytes_per_el
                      + call["vecs"] * seq * (width // head_dim) * 4)
    return {"flops": flops, "bytes": float(bytes_)}
