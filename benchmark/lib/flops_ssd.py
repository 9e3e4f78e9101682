"""Operations and bytes of the Mamba-2 scan (``ops/ssd.py``, scope ``ssd``)
and of a Granite 4.0-H hybrid, computed from shapes. Counts only: checked by
hand in ``tests/test_flops_ssd.py``; nothing is imported from the program.

The scan in its chunked form, for a chunk of ``Q`` positions, ``H`` heads of
``P``, ``G`` groups with a state of ``N``:

- scores ``C B^T``: ``2 Q^2 N`` a group;
- inside the chunk ``(L o C B^T)(dt X)``: ``2 Q^2 P`` a head;
- the state the chunk leaves, ``B^T (decay o dt X)``: ``2 Q P N`` a head;
- the part it inherits, ``C h``: ``2 Q P N`` a head.

The causal half of the two ``Q^2`` products is counted in full, as the
attention convention counts a score matrix. The backward pass needs twice
the forward's products (a gradient for each operand), so a training pass is
three forwards; a recomputed forward is not counted. The elementwise work
(decay matrix, exponentials, cumulative sums) is not counted as FLOPs: it
runs on the VPU beside the MXU, and the roofline below is what the MXU and
the HBM alone would need.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence


def ssd_forward_flops_per_token(n_heads: int, head_dim: int, d_state: int,
                                n_groups: int, chunk: int) -> float:
    return (2.0 * chunk * d_state * n_groups
            + 2.0 * chunk * head_dim * n_heads
            + 4.0 * head_dim * d_state * n_heads)


def ssd_bytes_per_token(n_heads: int, head_dim: int, d_state: int,
                        n_groups: int, bytes_per_el: int = 2
                        ) -> Dict[str, float]:
    """HBM bytes a token the scan cannot avoid. Forward: read ``x``, ``B``,
    ``C`` (compute dtype) and ``dt`` (float32), write ``y``. Backward: read
    those and ``dy``, write ``dx``, ``dB``, ``dC`` and ``ddt``. ``A`` and
    ``D`` are a few hundred bytes a layer."""
    x = n_heads * head_dim * bytes_per_el
    bc = n_groups * d_state * bytes_per_el
    dt = n_heads * 4
    return {"fwd": float(2 * x + 2 * bc + dt),
            "bwd": float(3 * x + 4 * bc + 2 * dt)}


def ssd_train_cost_per_token(n_heads: int, head_dim: int, d_state: int,
                             n_groups: int, chunk: int) -> Dict[str, float]:
    """FLOPs and bytes of one Mamba-2 layer's scan for one training token,
    forward and backward."""
    fwd = ssd_forward_flops_per_token(n_heads, head_dim, d_state, n_groups,
                                      chunk)
    bytes_ = ssd_bytes_per_token(n_heads, head_dim, d_state, n_groups)
    return {"flops": 3.0 * fwd, "bytes": bytes_["fwd"] + bytes_["bwd"]}


def ssd_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """The scan's sizes from a configuration file's published keys."""
    return {"n_heads": config["mamba_n_heads"],
            "head_dim": config["mamba_d_head"],
            "d_state": config["mamba_d_state"],
            "n_groups": config["mamba_n_groups"],
            "chunk": config["mamba_chunk_size"]}


def hybrid_param_count(config: Dict[str, Any],
                       layer_types: Sequence[str] = ()) -> int:
    """Hand count of a Granite 4.0-H with a tied head and no bias but the
    convolution's, from the published keys; ``layer_types`` defaults to the
    file's. A Mamba layer: the input projection to ``[z, x, B, C, dt]``, the
    depthwise convolution over ``[x, B, C]`` with its bias, ``dt_bias``,
    ``A_log`` and ``D`` a head, the gated norm's weight, the output
    projection. An attention layer: q and the output projection at the
    model width, k and v at the key/value heads. Every layer: two RMSNorm
    weights and the SwiGLU MLP's three matrices."""
    d, ff = config["hidden_size"], config["shared_intermediate_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    h, p = config["mamba_n_heads"], config["mamba_d_head"]
    inner = h * p
    bc = config["mamba_n_groups"] * config["mamba_d_state"]
    shared = 2 * d + 3 * d * ff
    mamba = (d * (2 * inner + 2 * bc + h)
             + (config["mamba_d_conv"] + 1) * (inner + 2 * bc)
             + 3 * h + inner + inner * d)
    attention = 2 * d * d + 2 * d * kv * (d // heads)
    layers = list(layer_types or config["layer_types"])
    n_mamba = sum(1 for kind in layers if kind == "mamba")
    return (config["vocab_size"] * d + d
            + n_mamba * (mamba + shared)
            + (len(layers) - n_mamba) * (attention + shared))


def hybrid_train_flops_per_token(n_params: int, config: Dict[str, Any],
                                 seq_len: int) -> float:
    """Model FLOPs of one training token of the hybrid, recomputation not
    counted: 6 a parameter, ``12 * width * sequence`` for each ATTENTION
    layer (scores and weighted values, in full), three forwards of the scan
    for each Mamba-2 layer."""
    layers = list(config["layer_types"])
    n_mamba = sum(1 for kind in layers if kind == "mamba")
    return (6.0 * n_params
            + 12.0 * (len(layers) - n_mamba) * config["hidden_size"] * seq_len
            + 3.0 * n_mamba * ssd_forward_flops_per_token(**ssd_shape(config)))
