"""Phi-4-mini-flash-reasoning (Microsoft; HF model type ``phi4flash``;
huggingface.co/microsoft/Phi-4-mini-flash-reasoning, ``config.json``): SambaY,
the decoder-hybrid-decoder architecture of arXiv:2507.06607, with
differential attention (arXiv:2410.05258). 32 layers, 2,560 wide, every layer
a mixer and a SwiGLU MLP of 10,240 under LayerNorm, no positions anywhere, a
tied 200,064-row head; 3.85B parameters. The mixer by published index ``l``
(``mb_per_layer`` 2; the second half reads the first's):

====================  ======================================================
even ``l`` up to 16   Mamba-1 (``ops/selective_scan.py``); layer 16's scan
                      output is the MEMORY of the units behind it
odd ``l`` up to 15    differential attention under a causal window of 512
17                    differential attention, causal, whole; its keys and
                      values are the cross layers'
even ``l`` from 18    a gated memory unit on layer 16's scan output
odd ``l`` from 19     differential CROSS attention: its own queries against
                      layer 17's keys and values, causal
====================  ======================================================

One description of ``models/transformer.py``'s stack; nothing here but the
published numbers and the rule above. ``lambda_init`` of a differential
layer follows its PUBLISHED index, ``0.8 - 0.6 exp(-0.3 l)``, so a chip that
runs a share of the depth names the published indices it holds
(``layer_ids``); the kinds, the window and ``lambda_init`` follow from them.
A share has to hold layer 16 in front of any unit and layer 17 in front of
any cross layer (``TransformerConfig`` refuses it by name otherwise).
``vocab`` is this chip's slice of the tied vocabulary.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

from easydl_tpu.models.lm import lm_bundle
from easydl_tpu.models.registry import ModelBundle, register_model
from easydl_tpu.models.transformer import (AttentionKind, Mamba1Config,
                                           TransformerConfig)

#: name -> widths; keys as the published ``config.json`` has them, and
#: Mamba's defaults, which it does not give (``d_state`` 16, ``d_conv`` 4,
#: ``expand`` 2, ``dt_rank`` ceil(hidden / 16))
SIZES: Dict[str, Dict[str, Any]] = {
    "mini-flash-reasoning": dict(
        hidden_size=2560, intermediate_size=10240, num_attention_heads=40,
        num_key_value_heads=20, num_hidden_layers=32, sliding_window=512,
        mb_per_layer=2, layer_norm_eps=1e-5, mamba_d_state=16,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=160, channel_view=64),
    # tiny, for tests and dry runs: widths a sixteenth, the same six kinds
    "test": dict(
        hidden_size=160, intermediate_size=640, num_attention_heads=8,
        num_key_value_heads=4, num_hidden_layers=32, sliding_window=8,
        mb_per_layer=2, layer_norm_eps=1e-5, mamba_d_state=16,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=10, channel_view=16),
}


def lambda_init(layer_id: int) -> float:
    """Differential attention's ``lambda_init`` at a published layer."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer_id)


def mixer_of(layer_id: int, n_layers: int, mb_per_layer: int = 2) -> str:
    """The mixer of published layer ``layer_id``: ``mamba1`` | ``gmu`` |
    ``window`` | ``full`` | ``cross`` (the three attention kinds)."""
    half = n_layers // 2
    if layer_id % mb_per_layer == 0:
        return "mamba1" if layer_id <= half else "gmu"
    if layer_id < half:
        return "window"
    return "full" if layer_id == half + 1 else "cross"


def describe(
    size: str = "mini-flash-reasoning",
    seq_len: int = 16384,
    vocab: int = 200064,
    layer_ids: Optional[Sequence[int]] = None,
    remat: bool = False,
    remat_policy: str = "full",
    attention_impl: str = "auto",
    dtype: str = "float32",
) -> TransformerConfig:
    """The stack's description of a Phi-4-mini-flash of ``size`` holding the
    published layers ``layer_ids`` (None: all of them)."""
    w = SIZES[size]
    n = w["num_hidden_layers"]
    ids = tuple(range(n)) if layer_ids is None else tuple(
        int(i) for i in layer_ids)
    if list(ids) != sorted(set(ids)) or not ids or ids[0] < 0 or ids[-1] >= n:
        raise ValueError(f"layer_ids are ascending published indices in "
                         f"[0, {n}); got {ids}")
    kinds, layers = [], []
    for i in ids:
        mixer = mixer_of(i, n, w["mb_per_layer"])
        if mixer in ("mamba1", "gmu"):
            layers.append((mixer, "swiglu"))
            continue
        # a kind a layer: lambda_init is the published index's
        name = f"{mixer}_{i}"
        kinds.append((name, AttentionKind(
            window=w["sliding_window"] if mixer == "window" else 0,
            diff=lambda_init(i), bias=True,
            kv={"full": "gives", "cross": "takes"}.get(mixer, ""))))
        layers.append((name, "swiglu"))
    d = w["hidden_size"]
    return TransformerConfig(
        vocab=vocab,
        d_model=d,
        n_heads=w["num_attention_heads"],
        n_kv_heads=w["num_key_value_heads"],
        n_layers=len(ids),
        d_ff=w["intermediate_size"],
        max_seq=seq_len,
        causal=True,
        remat=remat,
        remat_policy=remat_policy,
        attention_impl=attention_impl,
        dtype=dtype,
        tied_head=True,
        layers=tuple(layers),
        norm="layernorm",
        norm_eps=w["layer_norm_eps"],
        position="none",
        bias=False,  # the attention kinds carry theirs
        attention_kinds=tuple(kinds),
        mamba1=Mamba1Config(
            d_inner=w["mamba_expand"] * d, d_state=w["mamba_d_state"],
            dt_rank=w["mamba_dt_rank"], d_conv=w["mamba_d_conv"],
            view=w["channel_view"]),
    )


@register_model("phi4flash")
def make_phi4flash(**description) -> ModelBundle:
    """``description``: the arguments of :func:`describe`. The loss's
    metrics carry the static counters ``sscan_chunks``,
    ``sscan_state_bytes_kept``, ``kv_readers`` and ``memory_readers``
    (``models/lm.py lm_bundle``)."""
    cfg = describe(**description)
    size = description.get("size", "mini-flash-reasoning")
    ids = description.get("layer_ids")
    held = "all" if ids is None else "-".join(str(i) for i in ids)
    return lm_bundle(cfg, f"phi-4-{size}-l{held}")
