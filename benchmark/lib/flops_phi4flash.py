"""Operations and bytes of a Phi-4-mini-flash step, from the configuration
file's shapes alone (``lib/flops.py``'s rule: counts that can be checked by
hand): the matrix products' parameters by kind of layer, the pairs a
differential attention call's mask keeps at score heads ``d`` deep against
values ``2 d`` wide, and the Mamba-1 selective scan's element operations, its
``exp`` count and its HBM bytes."""

from __future__ import annotations

from typing import Any, Dict, List

from lib.flops import FLASH_CALLS
from lib.flops_laguna import seen_pairs
from lib.reference_phi4flash import hyper, kind_of


def kinds(config: Dict[str, Any]) -> List[str]:
    """``mamba`` | ``window`` | ``full`` | ``gmu`` | ``cross`` of each held
    layer, by its published index."""
    hp = hyper(config)
    return [kind_of(i, hp) for i in hp["layer_ids"]]


def mixer_params(config: Dict[str, Any], kind: str) -> int:
    """Parameters of a mixer's MATRIX products (no bias, norm, tap, rate or
    lambda vector: those multiply nothing by a matrix)."""
    d, hd = config["hidden_size"], \
        config["hidden_size"] // config["num_attention_heads"]
    inner = config["mamba_expand"] * d
    if kind == "mamba":  # in (x, z), x_proj, dt_proj, out
        low = config["mamba_dt_rank"] + 2 * config["mamba_d_state"]
        return (2 * d * inner + inner * low
                + config["mamba_dt_rank"] * inner + inner * d)
    if kind == "gmu":
        return 2 * d * inner
    q = config["num_attention_heads"] * hd
    kv = 0 if kind == "cross" else 2 * config["num_key_value_heads"] * hd
    return d * (q + kv) + q * d  # a pair's value is its two heads wide


def matrix_params(config: Dict[str, Any]) -> int:
    """Parameters that multiply a token in a matrix product: the mixers',
    every layer's MLP and the tied head once (the embedding is a lookup)."""
    d = config["hidden_size"]
    return sum(mixer_params(config, kind) + 3 * d * config["intermediate_size"]
               for kind in kinds(config)) + config["vocab_size"] * d


def param_count(config: Dict[str, Any]) -> int:
    """The real tree's count, by hand: the matrices, and per layer two
    LayerNorms; a Mamba-1 layer's taps and bias, dt bias, A and D; an
    attention layer's biases, four lambda vectors and inner gain; the final
    norm."""
    d, hd = config["hidden_size"], \
        config["hidden_size"] // config["num_attention_heads"]
    inner = config["mamba_expand"] * d
    n = matrix_params(config) + 2 * d
    for kind in kinds(config):
        n += 4 * d
        if kind == "mamba":
            n += inner * (config["mamba_d_conv"] + 1 + 1
                          + config["mamba_d_state"] + 1)
        elif kind != "gmu":
            kv = 0 if kind == "cross" else \
                2 * config["num_key_value_heads"] * hd
            n += config["num_attention_heads"] * hd + kv + d + 4 * hd + 2 * hd
    return n


def diff_pair_flops(config: Dict[str, Any], matmuls_scores: int,
                    matmuls_values: int) -> float:
    """FLOPs a (query, key) pair costs over all score heads: ``2 d`` a
    product at the scores' depth, ``2 x 2 d`` at the values' width."""
    hd = config["hidden_size"] // config["num_attention_heads"]
    return config["num_attention_heads"] * 2.0 * hd * (
        matmuls_scores + 2 * matmuls_values)


def selective_scan_cost(config: Dict[str, Any]) -> Dict[str, float]:
    """The selective scan's cost a token and layer, forward AND backward,
    recomputation not counted: ``flops`` (element operations, an ``exp`` one
    of them: seven a channel and state and three a channel forward, the
    backward twice that), ``exp`` (forward one a channel and state, the
    backward makes it again: two), ``bytes`` (forward x, dt, y and B, C read
    or written once, a chunk's entry state written; backward x, dt, dy and
    the entry state read, dx and ddt written, B and C read, dB and dC written
    once a block of 512 channels), and ``layers``, how many layers scan."""
    inner = config["mamba_expand"] * config["hidden_size"]
    n = config["mamba_d_state"]
    forward = inner * (7 * n + 3)
    entry = inner * n * 4 / 128.0          # a chunk of 128 positions
    fwd_bytes = inner * (2 + 4 + 2) + 2 * n * 2 + entry
    bwd_bytes = inner * (2 + 4 + 2 + 2 + 4) + 2 * n * 2 + entry \
        + 2 * n * 4 * (inner // 512)
    return {"flops": 3.0 * forward, "exp": 3.0 * inner * n,
            "bytes": fwd_bytes + bwd_bytes,
            "layers": sum(1 for kind in kinds(config) if kind == "mamba")}


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs of one training token, forward and backward, recomputed
    operations not counted: 6 a parameter of the matrix products; for each
    attention layer 3 x 2 FLOPs a pair its mask keeps (causal, or the
    window's band) and lane of the two products, scores ``d`` deep and
    values ``2 d`` wide, a token's share; three forwards of each scan's
    element operations."""
    total = 6.0 * matrix_params(config)
    for kind in kinds(config):
        if kind in ("window", "full", "cross"):
            window = config["sliding_window"] if kind == "window" else 0
            total += 3.0 * diff_pair_flops(config, 1, 1) \
                * seen_pairs(seq_len, window) / seq_len
    scan = selective_scan_cost(config)
    return total + scan["layers"] * scan["flops"]


def flash_diff_cost(config: Dict[str, Any], kind: str, batch: int, seq: int,
                    window: int = 0,
                    bytes_per_el: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes one differential attention call of ``kind``
    (``fwd``, ``bwd``, ``dq``, ``dkv``) needs: products over the pairs the
    mask keeps, the score products ``d`` deep and the value products ``2 d``
    wide, a pair's scores counted ONCE; q (dq) at the score heads, O, dO at
    twice their width, k and v (dk, dv) at the key/value heads a kernel that
    read a group's once could not avoid (the program repeats them to the
    score heads in HBM: the share reads low for it, never high), float32
    ``lse`` a row and score head."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["hidden_size"] // heads
    # products at the scores' depth | at the values' width
    products = {"fwd": (1, 1), "bwd": (3, 2), "dq": (2, 1), "dkv": (2, 2)}
    flops = batch * seen_pairs(seq, window) * diff_pair_flops(
        config, *products[kind])
    q, o, k = heads * hd, 2 * heads * hd, 2 * kv * hd   # k AND v
    arrays = {"fwd": q + k + o, "bwd": 2 * q + 2 * k + 2 * o,
              "dq": 2 * q + k + 2 * o, "dkv": q + 2 * k + 2 * o}[kind]
    bytes_ = batch * seq * (arrays * bytes_per_el
                            + FLASH_CALLS[kind]["vecs"] * heads * 4)
    return {"flops": float(flops), "bytes": float(bytes_)}
