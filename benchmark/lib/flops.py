"""Operations and bytes the algorithm needs, computed from shapes.

Everything here is a count, so it can be checked by hand and tested on the
CPU. Nothing is read from the program but the shapes of its parameter tree.
"""

from __future__ import annotations

import math
from typing import Any, Dict


def count_params(shape_tree: Any) -> int:
    """Number of scalars in a tree of arrays or ``ShapeDtypeStruct``s — the
    real tree (``jax.eval_shape`` of the model's ``init_fn``), not an
    estimate (``TransformerConfig.param_count`` guesses the biases)."""
    import jax

    return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(shape_tree))


def gpt2_param_count(n_layer: int, n_embd: int, n_inner: int, vocab: int,
                     n_positions: int) -> int:
    """Hand count of a GPT-2 with a tied head: token and position
    embeddings; per block two LayerNorms (scale + bias), q, k, v and output
    projections with biases, and the two MLP matrices with biases; the final
    LayerNorm."""
    d = n_embd
    block = (2 * 2 * d                 # ln_attn, ln_mlp
             + 4 * (d * d + d)         # q, k, v, out
             + d * n_inner + n_inner   # up
             + n_inner * d + d)        # down
    return vocab * d + n_positions * d + n_layer * block + 2 * d


def train_flops_per_token(n_params: int, n_layer: int, n_embd: int,
                          seq_len: int) -> float:
    """Model FLOPs of one training token, forward and backward, recomputed
    operations not counted (PaLM, appendix B): 6 per parameter for the
    matrix multiplications, plus 12 * layers * width * sequence for the
    attention scores and the weighted values (counted in full, as the
    convention has it, although a causal kernel needs half of them)."""
    return 6.0 * n_params + 12.0 * n_layer * n_embd * seq_len


#: matrix multiplications of [seq, head_dim] x [head_dim, seq] size that
#: each flash call needs: forward S = QK^T and O = PV; dq recomputes S, then
#: dP = dO V^T and dQ = dS K; dkv recomputes S and dP, then dV = P^T dO and
#: dK = dS^T Q; ``bwd``, the looped backward's one call (PR 39), forms a live
#: pair's S^T and dP^T ONCE and makes dV, dK and dQ from them: five products
#: where dq and dkv together make seven, q, k, v, O, dO read and dq, dk, dv
#: written once each, the ``lse`` rows read (``delta`` is formed inside).
#: Operands read and written, in arrays of [seq, head_dim]
#: (bf16) and of [seq] (f32: the log-sum-exp, and delta = rowsum(dO * O)).
FLASH_CALLS: Dict[str, Dict[str, int]] = {
    "fwd": {"matmuls": 2, "mats_in": 3, "mats_out": 1, "vecs": 1},
    "dq": {"matmuls": 3, "mats_in": 4, "mats_out": 1, "vecs": 2},
    "dkv": {"matmuls": 4, "mats_in": 4, "mats_out": 2, "vecs": 2},
    "bwd": {"matmuls": 5, "mats_in": 5, "mats_out": 3, "vecs": 1},
}


def flash_causal_cost(kind: str, batch_heads: int, seq: int, head_dim: int,
                      bytes_per_el: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes one causal flash call of ``kind`` (``fwd``,
    ``dq``, ``dkv``, ``bwd``) needs on ``[batch_heads, seq, head_dim]`` operands.
    A full score matrix multiplication is ``2 * seq^2 * head_dim``; the
    causal mask leaves ``seq * (seq + 1) / 2`` of its ``seq^2`` entries."""
    call = FLASH_CALLS[kind]
    causal_share = (seq + 1) / (2.0 * seq)
    flops = (batch_heads * call["matmuls"] * 2.0 * seq * seq * head_dim
             * causal_share)
    mats = call["mats_in"] + call["mats_out"]
    bytes_ = batch_heads * (mats * seq * head_dim * bytes_per_el
                            + call["vecs"] * seq * 4)
    return {"flops": flops, "bytes": float(bytes_)}


def flash_gqa_cost(kind: str, batch: int, seq: int, heads: int,
                   kv_heads: int, head_dim: int,
                   bytes_per_el: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes one causal flash call of ``kind`` (``fwd`` or
    ``bwd``) needs on ``[batch, seq, heads x head_dim]`` q under
    grouped-query attention: 2 FLOPs a pair the mask keeps and lane of each
    product (two forward, five in the one-call backward); q, O (and dO, dq)
    at the query heads, k and v (and dk, dv) at the ``kv_heads`` a grouped
    kernel could not avoid reading or writing (the program repeats them to
    the query heads in HBM: the share reads low for it, never high),
    float32 ``lse`` a row and head."""
    call = FLASH_CALLS[kind]
    pairs = seq * (seq + 1) // 2
    flops = batch * heads * 2.0 * pairs * call["matmuls"] * head_dim
    arrays = 2 if kind == "fwd" else 4  # q, O | k, v; and their gradients'
    bytes_ = batch * seq * (
        arrays * (heads + kv_heads) * head_dim * bytes_per_el + heads * 4)
    return {"flops": flops, "bytes": float(bytes_)}


def roofline_seconds(flops: float, bytes_: float, peak_flops: float,
                     peak_bytes_per_s: float) -> Dict[str, Any]:
    """The least time the chip could take, and which bound sets it."""
    t_compute, t_memory = flops / peak_flops, bytes_ / peak_bytes_per_s
    return {"seconds": max(t_compute, t_memory),
            "bound": "compute" if t_compute >= t_memory else "memory"}
