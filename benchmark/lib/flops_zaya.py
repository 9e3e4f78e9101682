"""Operations of one chip's share of a ZAYA1 (Zyphra, model type ``zaya``)
computed from a configuration file's published keys. Counts only: checked by
hand in ``tests/test_flops_zaya.py``; nothing is imported from the program.

ACTIVE operations, as ``lib/flops_laguna.py`` counts them: of a layer's
routed experts only the rows that were routed to the experts held here count
(``rows_per_token``, the layer's own counter: ``held / router_width`` on
average with one choice a token over the experts and the skip choice, 8 / 17
in the benchmark's cell), and of an attention layer's scores only the pairs
the causal mask keeps. The flash kernels' own FLOPs and bytes are the
functions the benchmark already has for them (``lib/flops.py
flash_causal_cost`` on the call's shape): no kernel is new here.
"""

from __future__ import annotations

from typing import Any, Dict

from lib.flops_laguna import seen_pairs


def cca_params(config: Dict[str, Any]) -> int:
    """The attention sub-layer: q and the way back up at the query heads'
    latent, k and v at the key/value heads'; a tap and a bias a channel of
    [q, k] (``cca_time0``), a ``[head_dim, head_dim]`` matrix a tap and head
    and a bias a channel (``cca_time1``); a temperature a key/value head."""
    d, hd = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, \
        config["num_key_value_heads"] * hd
    return (2 * d * q + 2 * d * kv
            + (config["cca_time0"] + 1) * (q + kv)
            + (config["cca_time1"] * hd + 1) * (q + kv)
            + config["num_key_value_heads"])


def router_params(config: Dict[str, Any]) -> int:
    """The down-projection with its bias, the state's gain, the norm, two
    square maps with biases and the map to ``router_width`` outputs."""
    d, r = config["hidden_size"], config["router_hidden_size"]
    return (d + 1) * r + 2 * r + 2 * (r + 1) * r + r * config["router_width"]


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert: three SwiGLU matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_params(config: Dict[str, Any], experts: float) -> float:
    """A layer with ``experts`` routed experts: CCA, router, experts, two
    norms and the two adds' four vectors each."""
    return cca_params(config) + router_params(config) \
        + experts * expert_params(config) + 10 * config["hidden_size"]


def param_count(config: Dict[str, Any]) -> int:
    """Held here: the tied embedding over the vocabulary held, the layers
    with ``num_experts`` routed experts each, the final norm."""
    d = config["hidden_size"]
    return int(config["vocab_size"] * d + d + len(config["layer_types"])
               * layer_params(config, config["num_experts"]))


def train_flops_per_token(config: Dict[str, Any], seq_len: int,
                          rows_per_token: float) -> float:
    """ACTIVE model FLOPs of one training token, forward and backward,
    recomputed operations not counted: 6 per active parameter of the matrix
    products (the routed experts' at ``rows_per_token`` rows a token and
    layer; the tied head's product counts, the lookup is the same matrix)
    and 12 per (pair, head dimension) the causal mask keeps, a token's
    share."""
    d = config["hidden_size"]
    width = config["num_attention_heads"] * config["head_dim"]
    n_layers = len(config["layer_types"])
    return (6.0 * (config["vocab_size"] * d + d
                   + n_layers * layer_params(config, rows_per_token))
            + 12.0 * n_layers * width * seen_pairs(seq_len) / seq_len)
