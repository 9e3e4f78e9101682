"""Operations and bytes of a looped language model (Ouro, arXiv:2510.25741)
computed from a configuration file's published keys. Counts only: checked by
hand in ``tests/test_flops_looplm.py``; nothing is imported from the program.

A looped model pays its layers, their attention scores and its head once a
PASS (``total_ut_steps`` passes over the same parameters), and its
parameters once: parameters are cheap, compute is dear.
"""

from __future__ import annotations

from typing import Any, Dict


def layer_params(config: Dict[str, Any]) -> int:
    """One layer: q, k, v, o (no biases, ``heads x head_dim`` = hidden), the
    three SwiGLU matrices, four RMSNorm gains (sandwich)."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    inner = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return 2 * d * inner + 2 * d * kv + 3 * d * ff + 4 * d


def param_count(config: Dict[str, Any]) -> int:
    """Embedding, ``len(layer_types)`` layers, the final norm, the untied
    head and the exit gate (a linear unit: hidden + 1). Independent of the
    number of passes."""
    d, vocab = config["hidden_size"], config["vocab_size"]
    head = 0 if config["tie_word_embeddings"] else vocab * d
    return (vocab * d + len(config["layer_types"]) * layer_params(config)
            + d + head + d + 1)


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs of one training token, forward and backward, recomputed
    operations not counted: a pass is 6 per layer and head parameter (the
    matrix multiplications; an untied embedding is a lookup and counts
    nothing) plus ``12 x hidden x sequence`` a layer for the scores and the
    weighted values (counted in full, as the convention has it); times the
    passes."""
    d, vocab = config["hidden_size"], config["vocab_size"]
    layers = len(config["layer_types"])
    per_pass = (6.0 * (layers * layer_params(config) + vocab * d)
                + 12.0 * layers * d * seq_len)
    return config["total_ut_steps"] * per_pass


def rope_bytes(batch: int, seq: int, width: int, head_dim: int,
               bytes_per_el: int = 2) -> float:
    """HBM bytes one rotary call on ``[batch, seq, width]`` cannot avoid:
    the array read and written once, the two float32 ``[seq, head_dim]``
    tables read once a batch row."""
    return float(2 * batch * seq * width * bytes_per_el
                 + batch * 2 * seq * head_dim * 4)


def rope_train_bytes_per_token(config: Dict[str, Any],
                               bytes_per_el: int = 2) -> float:
    """Rotary bytes of one training token, all passes: q and k forward and
    their gradients backward, a read and a write each."""
    inner = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return float(config["total_ut_steps"] * len(config["layer_types"])
                 * 2 * 2 * (inner + kv) * bytes_per_el)


def norm_train_bytes_per_token(config: Dict[str, Any],
                               bytes_per_el: int = 2) -> float:
    """Bytes of the four sandwich norms a layer for one training token, all
    passes, were each norm a pass of its own: forward a read and a write,
    backward two reads (the input, the incoming gradient) and a write. XLA
    fuses norms into their neighbours, so this is a ceiling on traffic, not
    a floor on time."""
    d = config["hidden_size"]
    return float(config["total_ut_steps"] * len(config["layer_types"])
                 * 4 * 5 * d * bytes_per_el)
