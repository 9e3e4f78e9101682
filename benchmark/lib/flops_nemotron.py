"""Operations and bytes of one chip's share of a NemotronH (NVIDIA, model type
``nemotron_h``) computed from a configuration file's published keys. Counts
only: checked by hand in ``tests/test_flops_nemotron.py``; nothing is imported
from the program.

ACTIVE operations of the MATRIX PRODUCTS, counted low wherever a count is in
doubt: of an ``E`` sub-layer's routed experts only the rows that were routed to
the experts held here (``rows_per_token``, the layer's own counter:
``num_experts_per_tok x held / published`` on average, 0.375 in the benchmark's
cell; the step's share of the peak is read with NONE), an ungated relu2 expert
TWO matrices; of the attention sub-layer's scores only the pairs the causal
mask keeps; the Mamba-2 scan as ``lib/flops_ssd.py`` counts its four products
at 8 groups and chunks of 128 (the ``C B^T`` scores once a GROUP); norms,
convolutions, the per-head leaves, the selection bias and every elementwise
pass count nothing.
"""

from __future__ import annotations

from typing import Any, Dict

from lib import flops_ssd
from lib.flops_laguna import seen_pairs


def ssd_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """The scan's sizes, under ``lib/flops_ssd.py``'s names, from the
    published keys."""
    return {"n_heads": config["mamba_num_heads"],
            "head_dim": config["mamba_head_dim"],
            "d_state": config["ssm_state_size"],
            "n_groups": config["n_groups"],
            "chunk": config["chunk_size"]}


def mamba_products(config: Dict[str, Any]) -> int:
    """Parameters of an ``M`` sub-layer's matrix products: the input map to
    ``[z, x, B, C, dt]`` and the way back."""
    d = config["hidden_size"]
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    bc = config["n_groups"] * config["ssm_state_size"]
    return d * (2 * inner + 2 * bc + config["mamba_num_heads"]) + inner * d


def mamba_params(config: Dict[str, Any]) -> int:
    """All of an ``M`` sub-layer's parameters: the products', the depthwise
    convolution over ``[x, B, C]`` with its bias, ``dt_bias``, ``A_log`` and
    ``D`` a head, the gated norm's gain, the sub-layer's norm."""
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    bc = config["n_groups"] * config["ssm_state_size"]
    return (mamba_products(config)
            + (config["conv_kernel"] + 1) * (inner + 2 * bc)
            + 3 * config["mamba_num_heads"] + inner + config["hidden_size"])


def attention_products(config: Dict[str, Any]) -> int:
    """q and the way back at the query heads, k and v at the key/value
    heads."""
    d, hd = config["hidden_size"], config["head_dim"]
    return 2 * d * config["num_attention_heads"] * hd \
        + 2 * d * config["num_key_value_heads"] * hd


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert: TWO matrices (ungated relu2)."""
    return 2 * config["hidden_size"] * config["moe_intermediate_size"]


def expert_layer_products(config: Dict[str, Any], experts: float) -> float:
    """An ``E`` sub-layer's matrix products: the router at its published
    width, the shared expert's two matrices, ``experts`` routed experts."""
    d = config["hidden_size"]
    return (d * config["n_routed_experts_published"]
            + config["n_shared_experts"] * 2 * d
            * config["moe_shared_expert_intermediate_size"]
            + experts * expert_params(config))


def param_count(config: Dict[str, Any]) -> int:
    """Held here: embedding and untied head over the vocabulary held, the
    sub-layers of ``hybrid_override_pattern`` with ``n_routed_experts``
    routed experts in each ``E``, a norm a sub-layer and the final one."""
    d = config["hidden_size"]
    per = {"M": mamba_params(config),
           "*": attention_products(config) + d,
           "E": int(expert_layer_products(config, config["n_routed_experts"]))
           + config["n_routed_experts_published"] + d}
    return 2 * config["vocab_size"] * d + d + sum(
        per[letter] for letter in config["hybrid_override_pattern"])


def train_flops_per_token(config: Dict[str, Any], seq_len: int,
                          rows_per_token: float) -> float:
    """ACTIVE model FLOPs of one training token, forward and backward,
    recomputed operations not counted: 6 per parameter of the matrix
    products (the routed experts' at ``rows_per_token`` rows a token and
    ``E`` sub-layer; the untied embedding is a lookup; the head once), ``6 x
    2 x head_dim`` per (pair, query head) the causal mask keeps in every
    ``*`` sub-layer, a token's share, and three forwards of the scan in every
    ``M``."""
    pattern = config["hybrid_override_pattern"]
    per = {"M": mamba_products(config), "*": attention_products(config),
           "E": expert_layer_products(config, rows_per_token)}
    products = config["vocab_size"] * config["hidden_size"] + sum(
        per[letter] for letter in pattern)
    pair = 6.0 * config["num_attention_heads"] * 2 * config["head_dim"]
    scan = 3.0 * flops_ssd.ssd_forward_flops_per_token(**ssd_shape(config))
    return (6.0 * products
            + pattern.count("*") * pair * seen_pairs(seq_len) / seq_len
            + pattern.count("M") * scan)


def ssd_train_cost_per_token(config: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs and HBM bytes of one ``M`` sub-layer's scan for one training
    token, forward and backward, at the configuration's groups and chunk
    (``lib/flops_ssd.py``: B and C are read and written at ``n_groups x
    ssm_state_size`` lanes)."""
    return flops_ssd.ssd_train_cost_per_token(**ssd_shape(config))
