"""One stack for the LM families, described by data
(``TransformerConfig``): GPT-2 and BERT (LayerNorm, learned positions,
multi-head attention, GELU, biases) are its defaults, the hybrids, the looped
and the expert models of ``models/<name>.py`` other descriptions of it. Per
layer the description names a mixer (a family of :data:`MIXER_FAMILIES` or
one of its own attention kinds) and an FFN (``gelu`` | ``swiglu`` | ``moe``
| ``none``: a layer that is its mixer alone); consecutive layers of one kind
are one ``nn.scan``.

TPU-first choices:
- every parameter carries logical axis names (``embed``/``heads``/``kv``/
  ``mlp``/``vocab``) so one rule table retargets the model across DP, FSDP,
  TP and SP meshes with zero model edits (core/sharding.py);
- blocks run under ``nn.scan`` — one traced layer, XLA unrolls on device —
  keeping compile time flat in depth; the scan axis is a logical ``layers``
  axis (mapped to ``pp`` for pipeline-style stage sharding, or None);
- optional ``nn.remat`` per block trades FLOPs for HBM (gradient
  rematerialisation — the standard long-sequence memory lever);
- attention goes through :func:`easydl_tpu.ops.multihead_attention` which
  swaps in the Pallas flash kernel on TPU;
- activations are annotated with ``nn.with_logical_constraint`` so GSPMD
  shards the sequence dim over ``sp`` when sequence parallelism is on.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from easydl_tpu.ops import multihead_attention, remat
from easydl_tpu.ops.attention import (indexed_attention, norm_heads,
                                      rotate_heads)
from easydl_tpu.ops.flash_attention import BlockDiffusion
from easydl_tpu.ops import moe as moe_ops
from easydl_tpu.ops.kda import gated_head_norm, kda, kda_flops_per_token
from easydl_tpu.ops.moe import MoeMlp
from easydl_tpu.ops.rope import apply_rope, rms_norm, rope_tables
from easydl_tpu.ops.selective_scan import (selective_scan,
                                           selective_scan_flops_per_token)
from easydl_tpu.ops.ssd import (causal_conv1d, causal_conv1d_silu,
                                gated_rmsnorm, ssd_flops_per_token, ssd_scan)
from easydl_tpu.utils.logging import get_logger, log_once

log = get_logger("models", "transformer")


def _matrix_dot_general(lhs, rhs, dimension_numbers, precision=None,
                        preferred_element_type=None):
    """``lax.dot_general`` for an attention projection, whose weight carries
    heads and head size as two dimensions (``[embed, heads, kv]`` in,
    ``[heads, kv, embed]`` out), as ONE plain matrix product over their
    merged dimension, its result left as those rows: ``[..., heads·kv]``
    (:class:`_RowsDense` gives it the features' shape).
    The same numbers; but the compiler lays a ``[batch, seq, heads·kv]``
    product out row by row, the layout the flash kernels take and give,
    where it gives a ``[batch, seq, heads, kv]`` product of 64-wide rows the
    sequence as its minor dimension and a transposing copy on the way to
    every kernel (PERF.md section 6, PR 28)."""
    (lhs_c, rhs_c), batch = dimension_numbers
    n = len(lhs_c)
    assert batch == ((), ()) and tuple(rhs_c) == tuple(range(n)) and \
        tuple(lhs_c) == tuple(range(lhs.ndim - n, lhs.ndim)), dimension_numbers
    return jax.lax.dot_general(
        lhs.reshape(lhs.shape[:lhs.ndim - n] + (-1,)),
        rhs.reshape(math.prod(rhs.shape[:n]), math.prod(rhs.shape[n:])),
        (((lhs.ndim - n,), (0,)), ((), ())), precision=precision,
        preferred_element_type=preferred_element_type)


class _RowsDense(nn.DenseGeneral):
    """``nn.DenseGeneral`` for an attention projection — its parameters under
    their names, with their shapes, axes and initial values — that keeps the
    result as rows of ONE merged feature dimension until it is finished:
    the product (:func:`_matrix_dot_general`), the bias added to those rows
    (``bias_on_rows``; the parent class is built with ``use_bias=False``),
    the name remat keeps it by (``dots`` always, ``full`` where its chooser
    does: ``ops/remat.py name_rows``), and only then the features' shape.
    A bias added to ``[batch, seq, heads, 64]`` made the kept sum a
    four-dimensional array, which the compiler lays out with the sequence as
    its minor dimension: a transposing copy in front of every kernel, in the
    forward and in the backward (PERF.md section 6, PR 30)."""

    bias_on_rows: bool = False

    @nn.compact
    def __call__(self, x):
        # the parent's own body, not its wrapped method: flax would put a
        # second scope of this module's name into every operation's path
        # (`q/q/dot_general`), which the trace's readers and tables know
        # as `q/dot_general`
        rows = nn.DenseGeneral.__call__.__wrapped__(self, x)
        features = self.features if isinstance(self.features, tuple) \
            else (self.features,)
        if self.bias_on_rows:
            bias = self.param("bias", self.bias_init, features,
                              self.param_dtype)
            rows = rows + jnp.asarray(bias, rows.dtype).reshape(-1)
        # remat's: ``dots`` keeps the finished rows by name; under ``full``
        # they are a candidate at the width they contract (``ops/remat.py``)
        axes = self.axis if isinstance(self.axis, tuple) else (self.axis,)
        return remat.name_rows(
            rows, math.prod(x.shape[axis] for axis in axes), self.name
        ).reshape(rows.shape[:-1] + features)


def _dense(features, kernel_axes, bias_axes, name=None, use_bias=True,
           init_scale=1.0, axis=-1, dtype=None, rows=False):
    """``nn.DenseGeneral``; with ``rows`` :class:`_RowsDense`, which is told
    of the bias apart (its parent class adds none)."""
    if rows:
        cls, bias = _RowsDense, dict(use_bias=False, bias_on_rows=use_bias,
                                     dot_general=_matrix_dot_general)
    else:
        cls, bias = nn.DenseGeneral, dict(use_bias=use_bias)
    return cls(
        features, axis=axis, name=name,
        dtype=dtype,  # compute dtype; params stay f32 (param_dtype default)
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(stddev=0.02 * init_scale), kernel_axes),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), bias_axes),
        **bias)


def _norm(cfg, name, dtype=None):
    """The description's norm (``layernorm`` with bias | ``rmsnorm``) over
    the model width."""
    # Statistics always accumulate in f32 (flax does this when dtype is
    # low-precision); only the output is cast to ``dtype``.
    if cfg.norm not in ("layernorm", "rmsnorm"):
        raise ValueError(f"norm must be 'layernorm' or 'rmsnorm', got "
                         f"{cfg.norm!r}")
    over = dict(epsilon=cfg.norm_eps, dtype=dtype, name=name,
                scale_init=nn.with_logical_partitioning(
                    nn.initializers.ones_init(), ("embed",)))
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(**over)
    return nn.LayerNorm(use_bias=True, bias_init=nn.with_logical_partitioning(
        nn.initializers.zeros_init(), ("embed",)), **over)


#: one layer of the description: (mixer, ffn). A mixer is a name a layer may
#: write of :data:`MIXER_FAMILIES` (the ONE table of the kinds of mixer, which
#: every question about a mixer is put to: ``TransformerConfig.mixer_family``)
#: or of one of the description's ``attention_kinds``. The FFN ``none`` is a
#: layer that is its mixer alone (NemotronH's ``M`` or ``*`` followed by
#: another mixer): no second norm, no second add.
Layer = Tuple[str, str]
#: what a layer may hand to the layers behind it (``TransformerConfig.
#: handoffs``, a family's ``takes`` / ``gives``): a Mamba-1 layer's scan
#: output, an attention layer's keys and values
HANDED = ("memory", "kv")
FFNS = ("gelu", "swiglu", "moe", "none")


@dataclass(frozen=True)
class RopeScheme:
    """One rotary scheme (``ops/rope.py rope_tables``): ``rotary_dim``
    leading dimensions of a head are rotated (0: all), with YaRN's blended
    frequencies and attention factor where ``yarn`` holds its numbers
    (pairs, so that the description stays hashable). The pairing is
    rotate-half, or ``interleaved`` (dimension ``2i`` with ``2i + 1``); the
    rotated part is the head's leading dimensions, or its ``last``."""

    theta: float = 10000.0
    rotary_dim: int = 0
    yarn: Optional[Tuple[Tuple[str, float], ...]] = None
    interleaved: bool = False
    last: bool = False

    def tables(self, seq: int, head_dim: int):
        """``ops/rope.py rope_tables`` of this scheme (pairing and place by
        keyword, and only where they are not the default: the benchmark's
        tests stand a five-argument ``rope_tables`` in)."""
        place = {name: True for name in ("interleaved", "last")
                 if getattr(self, name)}
        return rope_tables(seq, head_dim, self.theta, self.rotary_dim or None,
                           dict(self.yarn) if self.yarn else None, **place)


@dataclass(frozen=True)
class LatentMix:
    """What compressed convolutional attention (CCA, arXiv:2510.04476) does
    to q, k and v between their projections DOWN to the heads' latent
    (``heads x head_dim`` and ``kv_heads x head_dim``, narrower than the
    model) and the attention computed there: two causal convolutions over
    the sequence on q and k, ``taps[0]`` taps per channel then ``taps[1]``
    taps mixing the channels of each head, both with a bias; the mean of the
    un-convolved q and k added back to both; the second half of the
    key/value heads taken from the PREVIOUS token (``value_shift``); q and k
    L2-normed to ``sqrt(head_dim)``, k times a learned temperature a
    key/value head (``qk_norm``)."""

    taps: Tuple[int, int] = (2, 2)
    value_shift: bool = True
    qk_norm: bool = True


@dataclass(frozen=True)
class LowRank:
    """Multi-head latent attention (MLA; DeepSeek-V2 / V3, arXiv:2412.19437
    section 2.1.1): q through a bottleneck of ``q_rank`` with an RMSNorm
    inside it (``None``: no bottleneck, q straight from the model's width to
    ``heads x head_size``: ``q_lora_rank`` null), k and v up from ONE shared
    latent of ``kv_rank`` with an RMSNorm of its own. A head scores with
    ``nope_dim`` dimensions without
    positions beside ``rope_dim`` rotated ones (the kind's rotary scheme:
    its rotary dimensions, the head's last), and the rotated key part is ONE
    vector a token, made beside the latent and shared by every head; a
    head's value is ``value_dim`` wide, so the way back up reads ``heads x
    value_dim``. The description's ``head_size`` is ``nope_dim +
    rope_dim``. A kind without a rotary scheme (``AttentionKind.rope`` None,
    in a description without positions) rotates NOTHING: q's last
    ``rope_dim`` lanes and the key's one shared vector are used as they come
    (Kimi Linear's ``mla_use_nope``)."""

    q_rank: Optional[int]
    kv_rank: int
    nope_dim: int
    rope_dim: int
    value_dim: int


@dataclass(frozen=True)
class LearnedIndex:
    """A learned index in front of an attention (DeepSeek Sparse Attention's
    lightning indexer; ``ops/index.py``): ``n_heads`` index queries of
    ``head_dim`` a token against ONE index key a token, a learned weight a
    head, ``I[t, s] = sum_j w[t, j] relu(a[t, j] . b[s])``; query ``t``
    attends to the ``min(t + 1, topk)`` causal keys of largest ``I``. Its
    three maps read the layer's normed input DETACHED, its key passes a
    LayerNorm (gain and bias), queries and key are rotated (rotate-half
    over the whole ``head_dim``, the kind's theta), the weights are scaled
    ``n_heads ** -0.5 * head_dim ** -0.5``, and it is trained by its own
    loss, a layer's ``mean_t KL(p_t || softmax_{S_t} I_t)`` against the
    attention's probabilities (``models/lm.py`` adds the layers' sum to the
    objective). ``q_chunk`` / ``kv_chunk``: the published tiling of the
    scores; selection is by token, so they change no result (the kernels
    take ``kv_chunk`` keys at a time and ``ops/index.py QUERIES``
    queries)."""

    n_heads: int
    head_dim: int
    topk: int
    q_chunk: int = 512
    kv_chunk: int = 512


#: the counters a delta-rule layer hands out behind the expert layers' (summed
#: over the layers; ``models/lm.py`` gives their mean over the kda layers and
#: the static ``kda_chunks`` beside them): the mean ``alpha`` over tokens and
#: channels, the mean step size, the final state's root mean square — what
#: says, from a run's log, whether the state forgets, saturates or blows up
KDA_COUNTERS = ("kda_decay_mean", "kda_beta_mean", "kda_state_rms")


#: the counters an index layer hands out beside the expert layers' (summed
#: over the layers): its loss, the tiles that hold a selected pair, the
#: causal scores' sum of squares
INDEX_COUNTERS = ("index_loss", "index_live_tiles", "index_score_squares")


@dataclass(frozen=True)
class AttentionKind:
    """An attention layer's own numbers, where a stack has more than one
    kind: query heads (0: the description's), a causal window in keys (0:
    none; ``ops/flash_attention.py choose_blocks`` has the kernels' band
    path for it), a rotary scheme (None: the description's ``position``), a
    per-head sigmoid gate on the attention output, (``latent``) the
    mixing of q, k and v inside the heads' latent, (``lowrank``) q, k
    and v made through low-rank latents in place of one full-rank map
    each, and (``qk_norm``) an RMSNorm over each head's dimensions on q and
    on k in front of their rotation, one learned gain of ``head_dim``
    for all of q's heads and one for k's (Qwen3's and SDAR's; a ``latent``
    or ``lowrank`` kind norms q and k in its own way and refuses this
    one). The gains go to ``multihead_attention`` with q and k: where the
    rotary kernel rotates them it norms them too, in the same pass over the
    rows (``ops/rope.py rope_rows``: ``rope_norm_fwd`` / ``rope_norm_bwd``);
    elsewhere the norm is ``jax.numpy``'s under the scope ``qk_rmsnorm``.

    ``diff`` (None: plain softmax attention) makes the kind DIFFERENTIAL
    attention (arXiv:2410.05258) and is its ``lambda_init``: score heads pair
    by neighbours, pair ``j`` of key/value group ``g = j // (heads /
    kv_heads)`` scores ``q_{2j+c} k_{2g+c}^T`` for ``c`` in 0, 1 against ONE
    value ``[v_{2g} ; v_{2g+1}]``, twice a head wide; ``o_j = (1 - diff) *
    RMSNorm(P_0 V - lambda * P_1 V)`` with ``lambda = exp(lq1 . lk1) - exp(lq2
    . lk2) + diff``, four learned vectors of a head's size and one gain of
    twice that a layer. ``kv``: ``"gives"`` — the layer hands its keys and
    values (after their bias) to the layers behind it — or ``"takes"`` — it
    has a query and an output map alone and reads the nearest giver's
    (SambaY's cross-decoder, arXiv:2507.06607). ``bias``: this kind's four
    projections carry a bias though the description's (``bias``) do not.
    ``index``: a :class:`LearnedIndex` in front of the attention — which keys
    a query sees is then DATA of the step (plain causal kinds alone)."""

    n_heads: int = 0
    window: int = 0
    rope: Optional[RopeScheme] = None
    gate: bool = False
    latent: Optional[LatentMix] = None
    lowrank: Optional[LowRank] = None
    qk_norm: bool = False
    diff: Optional[float] = None
    kv: str = ""
    bias: bool = False
    index: Optional[LearnedIndex] = None


@dataclass(frozen=True)
class MoeConfig:
    """Widths of the ``moe`` FFNs (``ops/moe.py``): the published count of
    routed experts ``experts_total``, the contiguous range of them held
    here, experts a token, an expert's and the shared expert's inner widths
    and their form (``ops/moe.py EXPERT_FORMS``: a ``swiglu`` expert is
    three matrices, ``3 * d * d_ff`` parameters; an ungated ``relu2`` expert
    two, ``2 * d * d_ff``),
    the scale on the renormalised router weights; the router's form
    (``ops/moe.py ROUTERS``: the linear router over sigmoid scores or over
    the softmax, the MLP) and, of the MLP form, its width — also the
    width of the router state the layers hand on through the scan's carry —
    and whether it has a skip choice beside the experts."""

    experts_total: int
    experts_held: Tuple[int, int]
    k: int
    d_ff: int
    shared_d_ff: int = 0
    scaling: float = 1.0
    router: str = moe_ops.ROUTERS[0]
    router_hidden: int = 0
    skip_choice: bool = False
    #: the linear router selects by its scores plus a per-expert bias that
    #: takes no gradient and weighs by the scores without it (``noaux_tc``)
    selection_bias: bool = False
    expert_form: str = moe_ops.EXPERT_FORMS[0]
    #: the experts' and the shared expert's down maps start with zero column
    #: sums (``ops/moe.py MoeMlp.down_zero_sums``: NemotronH's description,
    #: whose ``relu2`` hidden units are all positive)
    down_zero_sums: bool = False

    @property
    def matrices(self) -> int:
        """Matrices an expert (routed or shared) is made of."""
        return 3 if self.expert_form == moe_ops.EXPERT_FORMS[0] else 2

    @property
    def choices(self) -> int:
        """The router's outputs: the experts and the skip choice."""
        return self.experts_total + int(self.skip_choice)

    @property
    def state_width(self) -> int:
        """Width of the router state a layer takes from the layer before
        it (0: the router has none)."""
        return self.router_hidden if self.router == moe_ops.ROUTERS[1] else 0


@dataclass(frozen=True)
class MtpConfig:
    """A multi-token-prediction module of depth 1 (DeepSeek-V3,
    arXiv:2412.19437 section 2.2): the main stack's normed final state at
    position ``i`` and the embedding of token ``i + 1``, each normed, joined
    through a ``2 d_model -> d_model`` map, ONE more layer ``(mixer, ffn)``
    of the description's kinds, a final norm of its own; the main model's
    embedding and head are used again (one leaf each). ``weight`` is the
    second objective's, lambda: ``loss = CE(main, t_{i+1}) + weight *
    CE(module, t_{i+2})`` (``models/lm.py mtp_objective``)."""

    mixer: str
    ffn: str = "moe"
    weight: float = 0.3


@dataclass(frozen=True)
class SsmConfig:
    """Widths of the Mamba-2 mixer (ops/ssd.py); the inner width is
    ``n_heads * head_dim``. ``n_groups`` are B's and C's (a group's heads
    share them); ``grouped_norm``: the gated norm behind the scan takes its
    mean square over each of those groups' channels apart (Mamba-2's and
    NemotronH's ``group_size = inner / n_groups``), not over all the inner
    channels at once (GraniteMoeHybrid's, whatever its ``n_groups``; with
    one group both are the same program). ``conv_bias_zero``: the
    convolutions' biases start at zero and not at torch's Conv1d default,
    uniform in ``+-1 / sqrt(d_conv)``, where GraniteMoeHybrid's code leaves
    them — constants in front of a SiLU are a token-independent part of the
    mixer's output, which a router behind it turns into a load that follows
    the seed (``MoeConfig.down_zero_sums``)."""

    n_heads: int = 64
    head_dim: int = 64
    d_state: int = 128
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 256
    grouped_norm: bool = False
    conv_bias_zero: bool = False


@dataclass(frozen=True)
class KdaConfig:
    """Widths of the ``kda`` mixers (Kimi Delta Attention, ``ops/kda.py``):
    ``n_heads`` heads whose keys and values are both ``head_dim`` wide, a
    state ``[head_dim, head_dim]`` a head; ``d_conv`` taps of the three
    convolutions; the decay's and the output gate's low-rank maps pass
    through ``head_dim`` (the published layer's ``head_v_dim``); ``chunk``
    tokens a chunk of the recurrence (``ops/kda.py``: 128 measured against
    64, PERF.md section 6, PR 64)."""

    n_heads: int = 32
    head_dim: int = 128
    d_conv: int = 4
    chunk: int = 128


@dataclass(frozen=True)
class Mamba1Config:
    """Widths of the ``mamba1`` mixers and the ``gmu`` units behind them
    (``ops/selective_scan.py``): ``d_inner`` channels, each with ``d_state``
    states of its own decay rate, a step size a channel made through a
    bottleneck of ``dt_rank``, ``d_conv`` taps. ``view``: the channels travel
    as ``[.., d_inner / view, view]``, the shape the convolution's kernels
    keep turned (``ops/ssd.py``: note C)."""

    d_inner: int
    d_state: int = 16
    dt_rank: int = 0
    d_conv: int = 4
    view: int = 64

    @property
    def channels(self) -> Tuple[int, int]:
        return self.d_inner // self.view, self.view


@dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 50304            # GPT-2 vocab padded to a multiple of 128 (MXU tiling)
    d_model: int = 1024
    n_heads: int = 16
    n_layers: int = 24
    d_ff: int = 4096
    max_seq: int = 1024
    causal: bool = True
    dropout: float = 0.0
    remat: bool = False
    #: remat granularity: "full" recomputes the whole block (min memory);
    #: "dots" keeps what costs a matrix product to make again — every
    #: product, q, k, v and `out` after their bias — and the flash forward's
    #: `lse`, and recomputes the elementwise rest. Both keep what is dear to
    #: make again — the flash forward's `out` and `lse`, under "full" the
    #: FFN's first products — where the compiled step leaves it room
    #: (``ops/remat.py`` has the rule and why).
    remat_policy: str = "full"
    attention_impl: str = "auto"
    #: compute/activation dtype ("float32" | "bfloat16"). Params stay f32;
    #: matmuls and activations run in this dtype (bf16 halves HBM traffic —
    #: the usual TPU bottleneck) and the loss upcasts logits to f32.
    dtype: str = "float32"
    #: sequence-parallel attention override: a ``(q, k, v) -> out`` callable
    #: (``ops/sequence_parallel.py make_sp_attention``) replacing the local
    #: attention — ring/Ulysses over the mesh's sp axis.
    attention_fn: Optional[Callable] = None
    #: tie the LM head to the token embedding (GPT-2 does)
    tied_head: bool = True
    #: pipeline parallelism over the mesh's ``pp`` axis: ``pipeline_fn``
    #: (``ops/pipeline.py make_pipeline``, closing over the mesh as
    #: ``attention_fn`` does) runs the block stack as a GPipe fill-drain
    #: schedule; ``pipeline_stages`` is the pp size (must divide ``n_layers``).
    #: Params keep the stacked [n_layers, ...] layout — the stage split is
    #: purely a ``layers → pp`` sharding rule.
    pipeline_fn: Optional[Callable] = None
    pipeline_stages: int = 0
    # ---- the description. The defaults below are GPT-2's (and BERT's).
    #: per layer (mixer, ffn); None = ``n_layers`` x ("attention", "gelu").
    #: When given, its length is the depth and ``n_layers`` must agree.
    layers: Optional[Tuple[Layer, ...]] = None
    norm: str = "layernorm"       # | "rmsnorm"
    norm_eps: float = 1e-6
    #: "learned": a table added to the embedding | "none" | "rope": q and k
    #: of every attention layer rotated by position (rotate-half over the
    #: whole head, angles ``pos * rope_theta ** (-2 i / head_dim)``)
    position: str = "learned"
    rope_theta: float = 10000.0
    #: where a sub-layer is normed: "pre" (its input) | "sandwich" (its input
    #: AND its output, before the residual add: four norms a layer)
    norm_placement: str = "pre"
    #: the runs of layers applied this many times over the SAME parameters,
    #: the final norm after every pass, the normed state both that pass's
    #: output and the next pass's input (a looped language model)
    loops: int = 1
    #: one linear unit on each pass's normed state (float32): the logit of
    #: leaving after that pass (``models/lm.py looplm_objective``)
    exit_gate: bool = False
    #: key/value heads (grouped-query attention); 0 = ``n_heads``
    n_kv_heads: int = 0
    bias: bool = True             # on the projections and the FFN
    embedding_multiplier: float = 1.0
    #: the token embedding table starts normal at this scale. 0.02 as every
    #: other map; 1.0 where a token's own vector has to outweigh what
    #: attention adds to it at seeded weights (Mellum 2's: beside 0.02
    #: embeddings a causal average of normed values is most of the stream,
    #: and every router's load follows the seed: PERF.md section 6, PR 45)
    embedding_init_std: float = 0.02
    #: the softmax scale; None = ``head_dim ** -0.5``
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    #: logits are divided by this
    logits_scaling: float = 1.0
    #: widths of the ``mamba2`` mixers, where the description has any
    ssm: Optional[SsmConfig] = None
    #: widths of the ``mamba1`` mixers and ``gmu`` units, where it has any
    mamba1: Optional[Mamba1Config] = None
    #: widths of the ``kda`` mixers, where the description has any
    kda: Optional[KdaConfig] = None
    #: a head's size where it is not ``d_model // n_heads`` (0: it is)
    head_size: int = 0
    #: named attention kinds a layer's mixer may be, ``(name, kind)`` pairs
    attention_kinds: Tuple[Tuple[str, AttentionKind], ...] = ()
    #: widths of the ``moe`` FFNs, where the description has any
    moe: Optional[MoeConfig] = None
    #: each residual add is ``(s_x * x + t_x) + (s_y * y + t_y)``, four
    #: learned vectors of the model's width a sub-layer (ones and zeros at
    #: the start), in float32
    residual_scale: bool = False
    #: a multi-token-prediction module behind the stack, where the
    #: description has one
    mtp: Optional[MtpConfig] = None
    #: block diffusion's block length (0: none; BD3-LM, arXiv:2503.09573): the
    #: stack is then given ``[noised || clean]`` rows, TWICE ``max_seq`` of
    #: them a sequence, every attention layer runs under
    #: ``ops/flash_attention.py BlockDiffusion(block_diffusion, rows / 2)``
    #: in place of a causal mask, and both halves carry the positions ``0 ..
    #: rows / 2 - 1`` (``models/lm.py block_diffusion_objective`` builds the
    #: rows and reads the noised half)
    block_diffusion: int = 0

    def __post_init__(self):
        if self.layers is not None and len(self.layers) != self.n_layers:
            raise ValueError(f"{len(self.layers)} layers described, "
                             f"n_layers={self.n_layers}")
        kinds = dict(self.attention_kinds)
        mixers = tuple(name for name, family in MIXER_FAMILIES.items()
                       if family.written) + tuple(kinds)
        for mixer, ffn in self.every_layer:
            if mixer not in mixers or ffn not in FFNS:
                raise ValueError(f"unknown layer kind {(mixer, ffn)}; mixers "
                                 f"{mixers}, FFNs {FFNS}")
            needs = self.mixer_family(mixer)[0].needs
            if needs and getattr(self, needs.split("=")[0]) is None:
                raise ValueError(f"a {mixer} layer needs {needs}(...)")
            if ffn == "moe" and self.moe is None:
                raise ValueError("a moe layer needs moe=MoeConfig(...)")
        if self.position not in ("learned", "none", "rope"):
            raise ValueError(f"position must be 'learned', 'none' or 'rope', "
                             f"got {self.position!r}")
        if self.norm_placement not in ("pre", "sandwich"):
            raise ValueError(f"norm_placement must be 'pre' or 'sandwich', "
                             f"got {self.norm_placement!r}")
        if self.loops < 1:
            raise ValueError(f"loops must be at least 1, got {self.loops}")
        if self.loops > 1 and self.pipeline_fn is not None:
            raise NotImplementedError("a looped stack inside the pipeline")
        for heads in {self.n_heads} | {
                kind.n_heads or self.n_heads for kind in kinds.values()}:
            if heads % self.kv_heads:
                raise ValueError(f"{heads} heads do not divide into "
                                 f"{self.kv_heads} key/value heads")
        for mixer in dict.fromkeys(m for m, _ in self.every_layer):
            family, kind = self.mixer_family(mixer)
            if kind is None:  # a family written by name: its own check
                family.check(self, mixer, None)
        if self.has_moe and (self.loops > 1 or self.pipeline_fn is not None):
            raise NotImplementedError(
                "moe layers in a looped stack or inside the pipeline")
        for name, kind in self.attention_kinds:
            self.mixer_family(name)[0].check(self, name, kind)
        takers = [(i, mixer, name) for i, (mixer, _) in
                  enumerate(self.every_layer)
                  for name in HANDED if name == self._takes(mixer)]
        for i, mixer, name in takers:
            if not any(self._gives(m) == name
                       for m, _ in self.every_layer[:i]):
                raise ValueError(
                    f"layer {i} ({mixer!r}) takes {name!r} and no layer in "
                    f"front of it gives it: a 'gmu' reads the 'mamba1' layer "
                    f"before it, an attention kind with kv='takes' one with "
                    f"kv='gives' (TransformerConfig.__post_init__ refuses "
                    f"it)")
        if takers and (self.loops > 1 or self.pipeline_fn is not None or
                       self.attention_fn is not None or self.mtp):
            raise NotImplementedError(
                "a layer that reads an earlier layer's memory or keys and "
                "values in a looped, pipelined or sequence-parallel stack or "
                "in front of a multi-token-prediction module")
        if self.block_diffusion:
            kinds_used = [kind for family, kind in (
                self.mixer_family(mixer) for mixer, _ in self.every_layer)
                          if family.scope == "attention"]
            refused = [what for what, found in (
                ("causal=True", self.causal),
                ("a mamba2, mamba1 or gmu layer",
                 len(kinds_used) < len(self.every_layer)),
                ("a window", any(k.window for k in kinds_used)),
                ("a latent or lowrank attention kind",
                 any(k.latent or k.lowrank for k in kinds_used)),
                ("learned positions", self.position == "learned"),
                ("a looped or gated stack", self.loops > 1 or self.exit_gate),
                ("a multi-token-prediction module", self.mtp is not None),
                ("attention_fn (sequence parallelism)",
                 self.attention_fn is not None),
                ("pipeline_fn", self.pipeline_fn is not None),
                ("a router state through the depth",
                 bool(self.router_state_width)),
                ("dropout", bool(self.dropout))) if found]
            if refused or self.max_seq % self.block_diffusion:
                raise NotImplementedError(
                    f"block_diffusion={self.block_diffusion} over max_seq="
                    f"{self.max_seq} with {', '.join(refused) or 'a length'}"
                    f" it does not divide: the block mask is neither causal "
                    f"nor a window and stands on plain attention layers "
                    f"alone (TransformerConfig.__post_init__ refuses it)")
        if self.mtp is not None and (
                self.loops > 1 or self.exit_gate or self.pipeline_fn
                is not None or self.attention_fn is not None or
                self.router_state_width or
                self.mixer_family(self.mtp.mixer)[0].scope != "attention" or
                self.embedding_multiplier != 1.0):
            raise NotImplementedError(
                "a multi-token-prediction module behind a looped, gated, "
                "pipelined or sequence-parallel stack, one whose router "
                "state runs through the depth or whose embedding has a "
                "multiplier, or on a mamba2, mamba1 or gmu layer")

    @property
    def head_dim(self) -> int:
        if self.head_size:
            return self.head_size
        if self.d_model % self.n_heads:
            raise ValueError(f"d_model={self.d_model} does not divide into "
                             f"n_heads={self.n_heads} heads")
        return self.d_model // self.n_heads

    def attention_kind(self, mixer: str) -> AttentionKind:
        """The numbers of an attention mixer; plain ``attention`` is the
        kind with none of its own."""
        return dict(self.attention_kinds).get(mixer, AttentionKind())

    def mixer_family(self, mixer: str):
        """A layer's mixer as ``(family, kind)``, the one place that says
        which entry of :data:`MIXER_FAMILIES` a mixer is: a name a layer may
        write of the table is that family with no kind; plain ``attention``
        and every other name are an attention kind, whose own fields say
        which attention family it is."""
        family = MIXER_FAMILIES.get(mixer)
        if family is not None and family.written and mixer != "attention":
            return family, None
        kind = self.attention_kind(mixer)
        return MIXER_FAMILIES["diff" if kind.diff is not None else "lowrank"
                              if kind.lowrank else "attention"], kind

    def _takes(self, mixer: str) -> str:
        """What of :data:`HANDED` a layer of ``mixer`` reads ('': nothing)."""
        family, kind = self.mixer_family(mixer)
        return family.takes(kind)

    def _gives(self, mixer: str) -> str:
        """What of :data:`HANDED` a layer of ``mixer`` can hand on."""
        family, kind = self.mixer_family(mixer)
        return family.gives(kind)

    @property
    def handoffs(self) -> Tuple[Tuple[Tuple[str, ...], Tuple[str, ...]], ...]:
        """For each run of :attr:`runs`, ``(carried, gives)``: the names of
        :data:`HANDED` that come to the run beside the residual stream
        (given in front of it, taken by it or behind it) and those its
        layers give — a run gives a name where a layer behind it takes it
        before another run gives it again. Empty pairs everywhere but in a
        stack whose layers read earlier layers' values."""
        mixers = [mixer for (mixer, _), _ in self.runs]
        gives = []
        for i, mixer in enumerate(mixers):
            name, behind = self._gives(mixer), mixers[i + 1:]
            upto = next((j for j, m in enumerate(behind)
                         if self._gives(m) == name), len(behind))
            taken = name and any(self._takes(m) == name
                                 for m in behind[:upto + 1])
            gives.append((name,) if taken else ())
        return tuple(
            (tuple(name for name in HANDED
                   if any(name in given for given in gives[:i])
                   and any(self._takes(m) == name for m in mixers[i:])),
             gives[i]) for i in range(len(mixers)))

    def readers(self, name: str) -> int:
        """Layers that read an earlier layer's ``name`` of :data:`HANDED`."""
        return sum(1 for mixer, _ in self.pattern
                   if self._takes(mixer) == name)

    @property
    def every_layer(self) -> Tuple[Layer, ...]:
        """The pattern, and the multi-token-prediction module's layer."""
        return self.pattern + (
            ((self.mtp.mixer, self.mtp.ffn),) if self.mtp else ())

    @property
    def has_moe(self) -> bool:
        return any(ffn == "moe" for _, ffn in self.every_layer)

    @property
    def router_state_width(self) -> int:
        """Width of the router state that runs through the depth beside the
        residual stream (the layer scan's carry is then a pair); 0: none."""
        return self.moe.state_width if self.has_moe else 0

    @property
    def counters(self) -> Tuple[str, ...]:
        """Names of the counters the stack sows (``counters`` collection,
        ``moe``): the expert layers' own, summed over the layers, and
        behind them an index's (:data:`INDEX_COUNTERS`)."""
        moe = moe_ops.counters(self.moe.skip_choice, self.moe.router) \
            if self.has_moe else ()
        return moe + (INDEX_COUNTERS if self.index_layers else ()) \
            + self.mixer_counters

    @property
    def mixer_counters(self) -> Tuple[str, ...]:
        """The counters the stack's mixer families hand out themselves
        (``MixerFamily.counters``: a delta-rule layer's), behind the expert
        layers' and an index's."""
        return tuple(dict.fromkeys(
            name for mixer, _ in self.pattern
            for name in self.mixer_family(mixer)[0].counters))

    @property
    def counted_layers(self) -> int:
        """Layers whose mixer hands out counters of its own."""
        return sum(1 for mixer, _ in self.pattern
                   if self.mixer_family(mixer)[0].counters)

    @property
    def index_layers(self) -> int:
        """Layers whose attention stands behind a learned index."""
        return sum(1 for mixer, _ in self.pattern
                   if self.attention_kind(mixer).index is not None)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def pattern(self) -> Tuple[Layer, ...]:
        return self.layers or (("attention", "gelu"),) * self.n_layers

    @property
    def runs(self) -> Tuple[Tuple[Layer, int], ...]:
        """The pattern as runs of equal layers: ``((mixer, ffn), count)``."""
        out = []
        for layer in self.pattern:
            if out and out[-1][0] == layer:
                out[-1][1] += 1
            else:
                out.append([layer, 1])
        return tuple((layer, n) for layer, n in out)

    def layer_params(self, layer: Layer, active: bool = False) -> int:
        """Parameters of one layer. Exact for the bias-free kinds; a layer
        with biases counts ``4 * d_model`` for its biases and norms, as this
        estimate always has. ``active``: of a ``moe`` layer's routed
        experts only what a token meets on average, ``k * held / choices`` of
        them (all ``k`` where every expert is held and none is skipped) —
        what the matrix products of a step are counted from. An expert is
        ``3 * d * d_ff`` (SwiGLU) or ``2 * d * d_ff`` (ungated relu2:
        ``MoeConfig.matrices``); the FFN ``none`` counts nothing, and one
        norm less."""
        mixer, ffn = layer
        d = self.d_model
        family, kind = self.mixer_family(mixer)
        n = family.params(self, kind)
        if ffn == "swiglu":
            n += 3 * d * self.d_ff
        elif ffn == "moe":
            m = self.moe
            held = m.experts_held[1] - m.experts_held[0]
            routed = m.k * held / m.choices if active else held
            r = m.state_width
            router = d * m.experts_total if not r else (
                (d + 1) * r + 2 * r          # down and bias, gain, norm
                + 2 * (r + 1) * r + r * m.choices)
            n += (router + (m.experts_total if m.selection_bias else 0)
                  + m.matrices * d * m.shared_d_ff
                  + round(routed * m.matrices * d * m.d_ff))
        elif ffn != "none":
            n += 2 * d * self.d_ff
        subs = 1 if ffn == "none" else 2            # sub-layers: norms, adds
        if self.norm_placement == "sandwich":
            n += subs * d                           # the output norms
        if self.residual_scale:
            n += 4 * subs * d
        # biases-ish + the sub-layers' norms
        return n + (2 * subs * d if self.bias else subs * d)

    @property
    def param_count(self) -> int:
        emb = self.vocab * self.d_model
        if self.position == "learned":
            emb += self.max_seq * self.d_model
        if self.norm == "rmsnorm":
            emb += self.d_model   # final norm (a LayerNorm's sits in the
            #                       layers' "biases-ish" estimate)
        head = 0 if self.tied_head else self.vocab * self.d_model
        gate = self.d_model + 1 if self.exit_gate else 0
        return (emb + sum(self.layer_params(l) for l in self.pattern) + head
                + gate + self._mtp_params())

    def _mtp_params(self, active: bool = False) -> int:
        """The multi-token-prediction module's own parameters: its layer,
        the map that joins state and embedding, three norms."""
        if self.mtp is None:
            return 0
        d = self.d_model
        return (self.layer_params((self.mtp.mixer, self.mtp.ffn), active)
                + 2 * d * d + 3 * d)

    def train_flops_per_token(self, seq_len: int) -> float:
        """Training FLOPs a token, forward and backward, recomputation not
        counted: 6 per parameter for the matrix multiplications (PaLM
        appendix B) and each layer's ``score_flops`` of its
        :class:`MixerFamily` — an attention layer's scores and weighted
        values, ``12 * heads * head_dim`` a key at equal head sizes, ``seq``
        keys counted in full as the convention has it or the ``window`` keys
        a windowed layer's band holds; three times a scan's forward count;
        not ``12 L d s`` for layers that have no score matrix. An untied
        embedding is a lookup and counts nothing. A looped stack pays its
        layers, their scores and its head once a pass: ``loops`` does not
        move ``param_count`` and multiplies this. Of a ``moe`` layer's routed
        experts only the ACTIVE ones count (:meth:`layer_params`). A
        multi-token-prediction module pays its layer, its join and the head
        a second time. Under ``block_diffusion`` a token is TWO rows through
        every layer (its noised and its clean one) and one through the head,
        and sees ``seq + block`` keys a layer (``seq² + seq · block`` live
        pairs a sequence)."""
        head = self.vocab * self.d_model
        held = sum(self.layer_params(l) for l in self.pattern) + head
        looped = sum(self.layer_params(l, active=True)
                     for l in self.pattern) + head
        lookup = 0 if self.tied_head else head
        once = self.param_count - held - lookup
        scores = 0.0
        # (the module's layer is an attention layer: ``__post_init__``)
        for mixer, _ in self.every_layer:
            family, kind = self.mixer_family(mixer)
            scores += family.score_flops(self, kind, seq_len)
        # the module's layer at its active count, and the head once more
        module = self._mtp_params(active=True) + head if self.mtp else 0
        once -= self._mtp_params()
        if self.block_diffusion:  # the layers twice a token, the head once
            looped = 2 * (looped - head) + head
        return 6.0 * (once + module) + self.loops * (6.0 * looped + scores)


def _uniform_init(fan_in: int):
    """torch's Conv1d default for a filter's taps: uniform in ``+-1 /
    sqrt(fan_in)``."""
    bound = fan_in ** -0.5

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    """Mamba-2's own start of a head's rate: ``log A`` with A from U(1, 16)
    (the delta rule's decay starts the same way)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Mamba's own start of a step's bias: dt from ``exp(U(log 1e-3, log
    1e-1))``, at least 1e-4, through the inverse of softplus."""
    dt0 = jnp.exp(jax.random.uniform(
        key, shape, dtype, jnp.log(1e-3), jnp.log(1e-1)))
    dt0 = jnp.maximum(dt0, 1e-4)
    return dt0 + jnp.log(-jnp.expm1(-dt0))


# The mixers and the FFN are functions of the block, not methods of it: flax
# wraps a Module's methods in a named scope of their own (``blocks._ffn``),
# which would put a new component into every operation's path.
def _projection(block, features, kernel_axes, bias_axes, name,
                residual=False, axis=-1, rows=False, bias=False):
    """``bias``: this projection carries one though the description's do
    not (``AttentionKind.bias``)."""
    cfg = block.cfg
    return _dense(
        features, kernel_axes, bias_axes, name=name,
        use_bias=cfg.bias or bias,
        # GPT-2 residual scaling on the projections that write the
        # residual stream
        init_scale=(2 * cfg.n_layers) ** -0.5 if residual else 1.0,
        axis=axis, dtype=jnp.dtype(cfg.dtype), rows=rows)


def _shift(x, by: int = 1):
    """``x [batch, seq, ...]`` moved ``by`` positions later: position ``t``
    reads ``t - by``, the first ``by`` read zeros."""
    pad = [(0, 0), (by, 0)] + [(0, 0)] * (x.ndim - 2)
    return jax.lax.slice_in_dim(jnp.pad(x, pad), 0, x.shape[1], axis=1)


def _latent_mix(block, mix, q, k, v):
    """:class:`LatentMix` on the projections ``q [B, S, H, d]``, ``k, v [B,
    S, G, d]``: what goes to the attention. The convolutions' taps, the
    mean, the norm and the temperature in float32; the products that mix a
    head's channels in the compute dtype, the taps' summed in float32."""
    f32 = jnp.float32
    (n_q, d), n_kv = q.shape[2:], k.shape[2]
    heads = n_q + n_kv

    def param(name, init, shape, axes):
        return block.param(name, nn.with_logical_partitioning(init, axes),
                           shape).astype(f32)

    # torch's Conv1d default for the taps. The biases start at zero, as
    # every bias of this stack does: a constant in q is a score every query
    # gives a key alike, and at seeded weights the routers' load on the
    # experts held then follows the seed (PERF.md section 6, PR 35)
    with jax.named_scope("cca_conv"):
        # q's and k's heads are groups alike: ten heads of channels
        u = jnp.concatenate([q, k], 2)
        t0, t1 = mix.taps
        zeros = nn.initializers.zeros_init()
        per_channel = param("conv0", _uniform_init(t0), (t0, heads, d),
                            (None, "heads", "kv"))
        bias0 = param("conv0_bias", zeros, (heads, d), ("heads", "kv"))
        c1 = causal_conv1d(u.astype(f32), per_channel, bias0).astype(u.dtype)
        per_head = block.param("conv1", nn.with_logical_partitioning(
            _uniform_init(t1 * d), (None, "heads", "kv", None)),
            (t1, heads, d, d))
        bias1 = param("conv1_bias", zeros, (heads, d), ("heads", "kv"))
        c2 = bias1 + sum(
            jnp.einsum("bshc,hcd->bshd", _shift(c1, t1 - 1 - i),
                       per_head[i].astype(u.dtype)).astype(f32)
            for i in range(t1))
        # the mean of the un-convolved q and k, under GQA: a query head's
        # with its key/value head's, a key/value head's the mean of its
        # query heads' means
        per = n_q // n_kv
        q32 = q.astype(f32).reshape(*q.shape[:2], n_kv, per, d)
        mean_q = 0.5 * (q32 + k.astype(f32)[:, :, :, None])
        q = c2[:, :, :n_q] + mean_q.reshape(q.shape)
        k = c2[:, :, n_q:] + jnp.mean(mean_q, 3)
    if mix.value_shift:
        with jax.named_scope("value_shift"):
            # a product with a weight commutes with the shift: the second
            # half of the heads shifted, not the layer's input
            half = n_kv // 2
            v = jnp.concatenate([v[:, :, :half], _shift(v[:, :, half:])], 2)
    if mix.qk_norm:
        with jax.named_scope("qk_norm"):
            tau = param("temperature", nn.initializers.zeros_init(), (n_kv,),
                        ("heads",))
            q = q * jax.lax.rsqrt(jnp.mean(q * q, -1, keepdims=True))
            k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True)) \
                * jnp.exp(tau)[:, None]
    return q.astype(v.dtype), k.astype(v.dtype), v


def _gain(block, name, width):
    """A norm's gain ``name [width]``, whole on every shard (a latent's or a
    head's norm: no axis of it is a mesh's)."""
    return block.param(name, nn.with_logical_partitioning(
        nn.initializers.ones_init(), (None,)), (width,))


def _rms(block, name, x, eps):
    """RMSNorm over the last dimension with a gain ``name`` of its own."""
    return rms_norm(x, _gain(block, name, x.shape[-1]), eps)


def _latent_attention(block, kind, h, rope):
    """:class:`LowRank` attention on the normed input ``h``. Scopes:
    ``mla_down`` (the two maps into the latents; the key's rotated vector
    comes out of the second), ``mla_norm``, ``mla_up`` (q's heads; the
    heads' keys without positions and values, two products on the two
    halves of ONE leaf ``kv_b [kv_rank, heads, nope_dim + value_dim]``, so
    that both leave as the rows the kernels take), ``rope`` (q's heads by
    the kernel on their rows; the one key vector in ``jax.numpy``),
    ``mla_key`` (the rotated key vector copied beside every head's
    ``nope_dim``: the published code's form, and the kernels' operand),
    the kernels ``mla_fwd`` / ``mla_bwd``, ``mla_out`` (the way back up from the
    attention's result ``[B, S, heads, value_dim]``)."""
    cfg, low = block.cfg, kind.lowrank
    n_heads = kind.n_heads or cfg.n_heads
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("mla_down"):
        c_q = None if low.q_rank is None else _projection(
            block, low.q_rank, ("embed", None), (None,), "q_a")(h)
        c_kv = _projection(block, low.kv_rank + low.rope_dim,
                           ("embed", None), (None,), "kv_a")(h)
        c_kv, k_rot = c_kv[..., :low.kv_rank], c_kv[..., low.kv_rank:]
    with jax.named_scope("mla_norm"):
        if c_q is not None:
            c_q = _rms(block, "q_norm", c_q, cfg.norm_eps)
        c_kv = _rms(block, "kv_norm", c_kv, cfg.norm_eps)
    with jax.named_scope("mla_up"):
        # without a bottleneck q comes straight from the model's width
        q = _projection(block, (n_heads, cfg.head_dim), (None, "heads", "kv"),
                        ("heads", "kv"), "q_b", rows=True)(c_q) \
            if c_q is not None else _projection(
                block, (n_heads, cfg.head_dim), ("embed", "heads", "kv"),
                ("heads", "kv"), "q", rows=True)(h)
        kv_b = jnp.asarray(block.param(
            "kv_b", nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (None, "heads", "kv")),
            (low.kv_rank, n_heads, low.nope_dim + low.value_dim)), dt)
        k_nope, v = (
            _matrix_dot_general(c_kv, half, (((2,), (0,)), ((), ())))
            .reshape(*c_kv.shape[:2], n_heads, -1)
            for half in (kv_b[..., :low.nope_dim], kv_b[..., low.nope_dim:]))
    q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "kv"))
    if kind.rope is None:
        # no positions: q's last lanes and the shared key vector as they come
        k_rot = k_rot[:, :, None, :]
    else:
        # one pair of tables: the key's lone vector is a head's rotated part
        q = rotate_heads(q, rope, rotary_dim=low.rope_dim,
                         interleaved=kind.rope.interleaved,
                         impl=cfg.attention_impl)
        k_rot = apply_rope(k_rot[:, :, None, :],
                           *(t[:, -low.rope_dim:] for t in rope),
                           interleaved=kind.rope.interleaved)
    with jax.named_scope("mla_key"):
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rot, (*k_nope.shape[:3], low.rope_dim))], -1)
    k = nn.with_logical_constraint(k, ("batch", "seq", "heads", "kv"))
    v = nn.with_logical_constraint(v, ("batch", "seq", "heads", "kv"))
    attn = multihead_attention(
        q, k, v, causal=cfg.causal, impl=cfg.attention_impl,
        scale=cfg.attention_multiplier)
    # what the latents held and the kernels were given and gave (sown only
    # where a caller makes `intermediates` mutable: a check, a test)
    for name, value in (("in", h), ("cq", c_q), ("ckv", c_kv), ("q", q),
                        ("k_rot", k_rot), ("attn", attn)):
        if value is not None:
            block.sow("intermediates", f"mla_{name}", value)
    with jax.named_scope("mla_out"):
        return _projection(block, cfg.d_model, ("heads", "kv", "embed"),
                           ("embed",), "out", residual=True, axis=(-2, -1),
                           rows=True)(attn)


def _attention(block, kind, h, rope=None):
    """Plain attention of ``kind`` (its heads, window, rotary scheme, gate,
    latent mix and q/k norm) on the normed input ``h``."""
    cfg = block.cfg
    n_heads = kind.n_heads or cfg.n_heads
    rotary_dim = kind.rope.rotary_dim or None if kind.rope else None
    heads, kv = ("embed", "heads", "kv"), ("heads", "kv")
    if kind.index is not None:  # its tables ride behind the attention's
        rope, index_rope = rope[:2], rope[2:]
    # the four products around the kernels as matrix products on rows: the
    # kernels' layout (the Mamba-2 mixer's same-shaped projections feed no
    # kernel and measured SLOWER that way: PERF.md section 6, PR 28). With a
    # latent mix they are the way down into the heads' latent and back up
    # (`cca_down`, `cca_up`), and the mix stands between them and the kernels
    scope = jax.named_scope if kind.latent \
        else lambda name: contextlib.nullcontext()
    with scope("cca_down"):
        q = _projection(block, (n_heads, cfg.head_dim), heads, kv, "q",
                        rows=True)(h)
        k = _projection(block, (cfg.kv_heads, cfg.head_dim), heads, kv, "k",
                        rows=True)(h)
        v = _projection(block, (cfg.kv_heads, cfg.head_dim), heads, kv, "v",
                        rows=True)(h)
    if kind.latent:
        q, k, v = _latent_mix(block, kind.latent, q, k, v)
        # what the mix was given and gave
        for name, value in (("in", h), ("q", q), ("k", k), ("v", v)):
            block.sow("intermediates", f"latent_{name}", value)
    qk_norm = None
    if kind.qk_norm:
        # over each head's dimensions, float32, one gain for q and one for
        # k: the attention's to apply, in front of the rotation
        qk_norm = (_gain(block, "q_norm", cfg.head_dim),
                   _gain(block, "k_norm", cfg.head_dim), cfg.norm_eps)
    q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "kv"))
    k = nn.with_logical_constraint(k, ("batch", "seq", "heads", "kv"))
    v = nn.with_logical_constraint(v, ("batch", "seq", "heads", "kv"))
    # the rows are a sequence's [noised || clean] halves under block
    # diffusion's mask
    mask = BlockDiffusion(cfg.block_diffusion, h.shape[1] // 2) \
        if cfg.block_diffusion else None
    index_stats = None
    if kind.index is not None:
        ia, ib, iw = _index_inputs(block, kind.index, h, index_rope)
        attn, index_loss, found = indexed_attention(
            q, k, v, ia, ib, iw, topk=kind.index.topk,
            scale=cfg.attention_multiplier, impl=cfg.attention_impl,
            rope=rope, qk_norm=qk_norm, chunk=kind.index.kv_chunk)
        index_stats = jnp.stack([index_loss, found["live_tiles"],
                                 found["score_squares"]])
        for name, value in (("a", ia), ("b", ib), ("w", iw),
                            ("words", found["words"])):
            block.sow("intermediates", f"ranked_{name}", value)
    elif cfg.attention_fn is not None:  # sequence-parallel (ring/Ulysses)
        if kind.window:
            raise NotImplementedError(
                "a windowed attention layer under sequence parallelism")
        if qk_norm:
            q, k = norm_heads(q, k, qk_norm)
        if rope is not None:  # q and k are whole here: positions from 0
            q, k = (apply_rope(x, *rope, rot=rotary_dim) for x in (q, k))
        attn = cfg.attention_fn(q, k, v, causal=cfg.causal)
    else:
        attn = multihead_attention(
            q, k, v, causal=cfg.causal, impl=cfg.attention_impl,
            scale=cfg.attention_multiplier, rope=rope, rotary_dim=rotary_dim,
            window=kind.window or None, mask=mask, qk_norm=qk_norm,
        )
    # what the kernels were given (the rows behind the norm, in front of the
    # rotation: made here for the sow alone) and gave
    if kind.qk_norm and block.is_mutable_collection("intermediates"):
        if cfg.attention_fn is None:
            q, k = norm_heads(q, k, qk_norm)
        for name, value in (("in", h), ("q", q), ("k", k), ("out", attn)):
            block.sow("intermediates", f"attn_{name}", value)
    if kind.gate:
        # one sigmoid a head on the layer's normed input, float32
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(_projection(
                block, n_heads, ("embed", "heads"), ("heads",), "gate_heads"
            )(h).astype(jnp.float32))
            attn = (attn * gate[..., None]).astype(attn.dtype)
    with scope("cca_up"):
        out = _projection(block, cfg.d_model, ("heads", "kv", "embed"),
                          ("embed",), "out", residual=True, axis=(-2, -1),
                          rows=True)(attn)
    return out if index_stats is None else (out, index_stats)


def _index_inputs(block, ix: LearnedIndex, h, rope):
    """The index's queries ``[batch, seq, heads, dim]``, its one key a token
    ``[batch, seq, dim]`` (both rotated) and its head weights ``[batch, seq,
    heads]`` float32, scaled — all from the layer's normed input ``h``
    DETACHED: nothing of the index moves what is in front of it."""
    cfg = block.cfg
    with jax.named_scope("index"):
        h = jax.lax.stop_gradient(h)

        def mapped(name, width):
            return _dense(width, ("embed", None), (None,), name=name,
                          use_bias=False, dtype=jnp.dtype(cfg.dtype))(h)

        a = mapped("index_q", ix.n_heads * ix.head_dim).reshape(
            *h.shape[:2], ix.n_heads, ix.head_dim)
        b = nn.LayerNorm(epsilon=cfg.norm_eps, dtype=jnp.dtype(cfg.dtype),
                         name="index_k_norm")(mapped("index_k", ix.head_dim))
        w = mapped("index_w", ix.n_heads).astype(jnp.float32) \
            * (ix.n_heads ** -0.5 * ix.head_dim ** -0.5)
        a = apply_rope(a, *rope)
        b = apply_rope(b[:, :, None, :], *rope)[:, :, 0]
        # ONE value of each for every reader — the ranking, the index's loss,
        # whoever is handed them (`intermediates`): left to the compiler, a
        # reader may be given the rotation fused in at another precision
        # than its neighbour's (a bf16 rounding apart: on the chip the words
        # were then not the top-k of the inputs handed out for 72% of the
        # queries: PERF.md section 6, PR 61)
        a, b, w = jax.lax.optimization_barrier((a, b, w))
    return a, b, w


def _mamba2(block, u):
    """The Mamba-2 mixer on the normed input ``u``. The published fused
    input projection ``[z, xBC, dt]`` is five projections here and the
    depthwise convolution three — the same mathematics, column by
    column — so that heads shard over ``tp`` and B, C stay whole. The scan
    under ``ssd`` and the convolutions under ``conv1d`` (each with its bias
    and SiLU) are ``ops/ssd.py``'s: their Pallas kernels on a TPU where the
    mixer's widths tile, else ``jax.numpy``; the gated norm is XLA's."""
    cfg, m = block.cfg, block.cfg.ssm
    dt_ = jnp.dtype(cfg.dtype)
    heads, kv = ("embed", "heads", "kv"), ("heads", "kv")
    group = ("embed", "ssm_group", "ssm_state")
    z = _projection(block, (m.n_heads, m.head_dim), heads, kv, "in_z")(u)
    x = _projection(block, (m.n_heads, m.head_dim), heads, kv, "in_x")(u)
    B = _projection(block, (m.n_groups, m.d_state), group, group[1:],
                    "in_B")(u)
    C = _projection(block, (m.n_groups, m.d_state), group, group[1:],
                    "in_C")(u)
    dt = _projection(block, m.n_heads, ("embed", "heads"), ("heads",),
                     "in_dt")(u)
    # one candidate for what remat ``full`` keeps, as an FFN's first products
    z, x, B, C, dt = remat.name_products((z, x, B, C, dt), u.shape[-1],
                                         "maps")

    def conv(name, a, axes):
        # torch's Conv1d default, fan_in the taps of a depthwise filter
        init = _uniform_init(m.d_conv)
        w = block.param(f"conv_{name}", nn.with_logical_partitioning(
            init, (None,) + axes), (m.d_conv,) + a.shape[2:])
        b = block.param(f"conv_{name}_bias", nn.with_logical_partitioning(
            nn.initializers.zeros_init() if m.conv_bias_zero else init,
            axes), a.shape[2:])
        return causal_conv1d_silu(a, w, b)

    with jax.named_scope("conv1d"):
        x = conv("x", x, kv)
        B = conv("B", B, group[1:])
        C = conv("C", C, group[1:])

    def per_head(name, init):
        return block.param(name, nn.with_logical_partitioning(
            init, ("heads",)), (m.n_heads,))

    # Mamba-2's own: dt's bias (`_dt_bias_init`), A from U(1, 16), D ones
    dt_bias = per_head("dt_bias", _dt_bias_init)
    a_log = per_head("A_log", _a_log_init)
    skip = per_head("D", nn.initializers.ones_init())
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
    with jax.named_scope("ssd"):
        y = ssd_scan(x, dt, -jnp.exp(a_log.astype(jnp.float32)), B, C,
                     skip, chunk=m.chunk)
    with jax.named_scope("gated_norm"):
        gain = block.param("norm_gated", nn.with_logical_partitioning(
            nn.initializers.ones_init(), kv), (m.n_heads, m.head_dim))
        normed = gated_rmsnorm(y, z, gain, cfg.norm_eps,
                               m.n_groups if m.grouped_norm else 1).astype(dt_)
    # what the mixer's parts were given and gave
    for name, value in (("in", u), ("z", z), ("x", x), ("B", B), ("C", C),
                        ("dt", dt), ("y", y), ("normed", normed)):
        block.sow("intermediates", f"ssm_{name}", value)
    return _projection(block, cfg.d_model, ("heads", "kv", "embed"),
                       ("embed",), "out", residual=True, axis=(-2, -1)
                       )(normed)


def _kda(block, u):
    """The Kimi Delta Attention mixer (arXiv:2510.26692; ``fla``'s
    ``KimiDeltaAttention``) on the normed input ``u``: ``(the layer's output,
    its three counters of KDA_COUNTERS)``. Scopes: ``conv1d`` (q's, k's and
    v's OWN causal convolutions, no bias, a SiLU behind each: ``ops/ssd.py``'s
    kernels), ``kda_gates`` (the decay a channel through its low-rank map and
    softplus, the step sizes, the L2 norms of q and k a head), ``kda`` (the
    recurrence, ``ops/kda.py``: its Pallas kernels on a TPU where the widths
    tile, else ``jax.numpy``) and ``gated_norm`` (the RMSNorm a head with one
    shared gain, THEN a sigmoid gate through its low-rank map). The decays,
    the step sizes, the norms' statistics and the state are float32."""
    cfg, m = block.cfg, block.cfg.kda
    f32, dt_ = jnp.float32, jnp.dtype(cfg.dtype)
    heads, kv = ("embed", "heads", "kv"), ("heads", "kv")
    shape = (m.n_heads, m.head_dim)
    q, k, v = remat.name_products(tuple(
        _projection(block, shape, heads, kv, name)(u)
        for name in ("q", "k", "v")), u.shape[-1], "maps")

    with jax.named_scope("conv1d"):
        # torch's Conv1d default, fan_in the taps of a depthwise filter
        q, k, v = (causal_conv1d_silu(a, block.param(
            f"conv_{name}", nn.with_logical_partitioning(
                _uniform_init(m.d_conv), (None,) + kv), (m.d_conv,) + shape))
            for name, a in (("q", q), ("k", k), ("v", v)))

    def low_rank(name):
        """``u`` through ``head_dim`` and up to a value a channel."""
        down = _projection(block, m.head_dim, ("embed", None), (None,),
                           f"{name}_a")(u)
        return _projection(block, shape, (None, "heads", "kv"), kv,
                           f"{name}_b")(down)

    with jax.named_scope("kda_gates"):
        a_log = block.param("A_log", nn.with_logical_partitioning(
            _a_log_init, ("heads",)), (m.n_heads,))
        dt_bias = block.param("dt_bias", nn.with_logical_partitioning(
            _dt_bias_init, kv), shape)
        g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
            low_rank("f").astype(f32) + dt_bias)
        beta = jax.nn.sigmoid(_projection(
            block, m.n_heads, ("embed", "heads"), ("heads",), "b")(u)
            .astype(f32))

        def l2norm(x, scale=1.0):
            x = x.astype(f32)
            return (x * (jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                       + 1e-6) * scale)).astype(dt_)

        q, k = l2norm(q, m.head_dim ** -0.5), l2norm(k)
    with jax.named_scope("kda"):
        o, last = kda(q, k, v, g, beta, chunk=m.chunk, impl="xla"
                      if cfg.attention_impl == "reference" else "auto")
    with jax.named_scope("gated_norm"):
        gain = _gain(block, "norm_gated", m.head_dim)
        gate = low_rank("g")
        normed = gated_head_norm(o, gate, gain, cfg.norm_eps).astype(dt_)
    # what the mixer's parts were given and gave
    for name, value in (("in", u), ("q", q), ("k", k), ("v", v), ("g", g),
                        ("beta", beta), ("o", o), ("state", last),
                        ("gate", gate), ("normed", normed)):
        block.sow("intermediates", f"kda_{name}", value)
    counted = jax.lax.stop_gradient(jnp.stack([
        jnp.mean(jnp.exp(g)), jnp.mean(beta),
        jnp.sqrt(jnp.mean(jnp.square(last)))]))
    return _projection(block, cfg.d_model, ("heads", "kv", "embed"),
                       ("embed",), "out", residual=True, axis=(-2, -1)
                       )(normed), counted


def _diff_lambda(vector, init: float):
    """A differential layer's lambda from its four learned vectors
    (``vector(name)``) and ``lambda_init``: a scalar, float32."""
    return (jnp.exp(jnp.sum(vector("lambda_q1") * vector("lambda_k1")))
            - jnp.exp(jnp.sum(vector("lambda_q2") * vector("lambda_k2")))
            + init)


def _diff_attention(block, kind, h, kv=None):
    """Differential attention (:class:`AttentionKind` ``diff``) on the
    normed input ``h``: ``(the layer's output, the keys and values it made
    or None)``. ``kv``: a giver's ``(k, v)`` — the kind takes them and has
    no key or value map. ONE attention call of score heads ``head_dim`` deep
    against values twice that wide (``ops/flash_attention.py``: the calls
    named ``diff_*``, ``swa_*`` under a window), each softmax computed once:
    score head ``2 j + c`` is handed key head ``2 g + c`` and the value
    ``[v_2g ; v_2g+1]`` of its group, repeated in front of the call as
    grouped-query attention's are. Everything between the call's result and
    the output map — the difference under lambda, the RMSNorm over a pair's
    value, ``1 - lambda_init`` — stands under ``diff_combine``, float32."""
    cfg = block.cfg
    f32 = jnp.float32
    n_heads, groups, d = kind.n_heads or cfg.n_heads, cfg.kv_heads, \
        cfg.head_dim
    heads, axes = ("embed", "heads", "kv"), ("heads", "kv")
    q = _projection(block, (n_heads, d), heads, axes, "q", rows=True,
                    bias=kind.bias)(h)
    given = None
    if kv is None:
        k, v = (_projection(block, (groups, d), heads, axes, name, rows=True,
                            bias=kind.bias)(h) for name in ("k", "v"))
        given = (k, v)
    else:
        k, v = kv
    # per value group (two key heads): heads / kv_heads pairs of score heads
    per = n_heads // groups
    lead = k.shape[:2]
    k_heads = jnp.broadcast_to(
        k.reshape(*lead, groups // 2, 1, 2, d),
        (*lead, groups // 2, per, 2, d)).reshape(*lead, n_heads, d)
    v_heads = jnp.broadcast_to(
        v.reshape(*lead, groups // 2, 1, 2 * d),
        (*lead, groups // 2, 2 * per, 2 * d)).reshape(*lead, n_heads, 2 * d)
    q, k_heads, v_heads = (nn.with_logical_constraint(
        a, ("batch", "seq", "heads", "kv")) for a in (q, k_heads, v_heads))
    attn = multihead_attention(
        q, k_heads, v_heads, causal=cfg.causal, impl=cfg.attention_impl,
        scale=cfg.attention_multiplier, window=kind.window or None)
    with jax.named_scope("diff_combine"):
        def vector(name):
            return block.param(name, nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.1), (None,)), (d,)).astype(f32)

        lam = _diff_lambda(vector, kind.diff)
        pair = attn.astype(f32).reshape(*lead, n_heads // 2, 2, 2 * d)
        before = pair[..., 0, :] - lam * pair[..., 1, :]
        gain = block.param("sub_norm", nn.with_logical_partitioning(
            nn.initializers.ones_init(), (None,)), (2 * d,)).astype(f32)
        normed = before * jax.lax.rsqrt(
            jnp.mean(before * before, -1, keepdims=True) + cfg.norm_eps)
        out = (normed * gain * (1.0 - kind.diff)).astype(attn.dtype)
    # what the call was given and gave
    for name, value in (("in", h), ("q", q), ("k", k), ("v", v),
                        ("attn", attn), ("before_norm", before),
                        ("out", out)):
        block.sow("intermediates", f"diff_{name}", value)
    return _projection(block, cfg.d_model, ("heads", "kv", "embed"),
                       ("embed",), "out", residual=True, axis=(-2, -1),
                       rows=True, bias=kind.bias)(out), given


def _mamba1(block, u):
    """The Mamba-1 mixer (arXiv:2312.00752) on the normed input ``u``:
    ``(the layer's output, the scan's output y)`` — ``y`` with its ``D x``
    term and before the ``z`` gate is what a gated memory unit behind the
    layer multiplies. The published fused input map ``[x ; z]`` is two
    projections here, the same mathematics column by column; the channels
    travel as ``[.., d_inner / view, view]`` (:class:`Mamba1Config`). The
    convolution under ``conv1d`` (its bias and SiLU with it) is
    ``ops/ssd.py``'s and the scan under ``selective_scan``
    ``ops/selective_scan.py``'s: their Pallas kernels on a TPU where the
    widths tile, else ``jax.numpy``. The step sizes, the rates and the scan's
    state are float32."""
    cfg, m = block.cfg, block.cfg.mamba1
    f32 = jnp.float32
    heads, kv = ("embed", "heads", "kv"), ("heads", "kv")
    x = _projection(block, m.channels, heads, kv, "in_x")(u)
    z = _projection(block, m.channels, heads, kv, "in_z")(u)
    x, z = remat.name_products((x, z), u.shape[-1], "maps")

    conv_init = _uniform_init(m.d_conv)  # fan_in a depthwise filter's taps
    with jax.named_scope("conv1d"):
        w = block.param("conv_x", nn.with_logical_partitioning(
            conv_init, (None,) + kv), (m.d_conv,) + m.channels)
        b = block.param("conv_x_bias", nn.with_logical_partitioning(
            conv_init, kv), m.channels)
        x = causal_conv1d_silu(x, w, b)
    # the step's bottleneck, B_t and C_t from the convolved x in one map
    low = _projection(block, m.dt_rank + 2 * m.d_state,
                      ("heads", "kv", None), (None,), "x_proj",
                      axis=(-2, -1))(x)
    B, C = (low[..., m.dt_rank + i * m.d_state:
                m.dt_rank + (i + 1) * m.d_state] for i in (0, 1))
    # the steps, the rates and the skip weight by channel, flat: dt's rows
    # `[batch, seq, d_inner]` are the scan's operand as the product leaves them
    dt = _projection(block, m.d_inner, (None, "heads"), ("heads",),
                     "dt_proj")(low[..., :m.dt_rank])

    def channel(name, init, *more):
        return block.param(name, nn.with_logical_partitioning(
            init, ("heads",) + (None,) * len(more)), (m.d_inner,) + more)

    # Mamba's own: dt's bias (`_dt_bias_init`), A = -(1 .. d_state) a
    # channel, D ones
    def a_log_init(key, shape, dtype=jnp.float32):
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[-1] + 1, dtype=dtype)), shape)

    dt_bias = channel("dt_bias", _dt_bias_init)
    a_log = channel("A_log", a_log_init, m.d_state)
    skip = channel("D", nn.initializers.ones_init())
    dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
    with jax.named_scope("selective_scan"):
        y = selective_scan(x, dt, -jnp.exp(a_log.astype(f32)), B, C, skip)
    for name, value in (("in", u), ("x", x), ("z", z), ("B", B), ("C", C),
                        ("dt", dt), ("y", y)):
        block.sow("intermediates", f"scan_{name}", value)
    gated = y * nn.silu(z)
    return _projection(block, cfg.d_model, ("heads", "kv", "embed"),
                       ("embed",), "out", residual=True, axis=(-2, -1)
                       )(gated), y


def _gmu(block, u, memory):
    """The gated memory unit (SambaY, arXiv:2507.06607) on the normed input
    ``u``: ``(memory * silu(u W_1)) W_2``, ``memory`` an earlier Mamba-1
    layer's scan output as that layer handed it on."""
    cfg, m = block.cfg, block.cfg.mamba1
    with jax.named_scope("gmu"):
        gate = _projection(block, m.channels, ("embed", "heads", "kv"),
                           ("heads", "kv"), "in_gate")(u)
        block.sow("intermediates", "gmu_memory", memory)
        return _projection(block, cfg.d_model, ("heads", "kv", "embed"),
                           ("embed",), "out", residual=True, axis=(-2, -1)
                           )(memory * nn.silu(gate))


class MixerFamily(NamedTuple):
    """One kind of mixer: everything the stack asks about it. ``scope``: the
    ``jax.named_scope`` its layers run under (``attention`` | ``ssm``, which
    the trace's readers find in the program's ``op_name`` paths),
    ``inner(kind)`` one inside it or ``''``; ``norm``: the mixer norm's
    parameter; ``apply(block, kind, h, rope, handed) -> (y, given)``: the
    mixer on the normed input ``h``, ``handed`` and ``given`` ``{name of
    HANDED: value}``; ``params(cfg, kind)``: its parameters
    (``layer_params``); ``score_flops(cfg, kind, seq_len)``: its training
    FLOPs a token beside the products with parameters
    (``train_flops_per_token``); ``takes(kind)`` / ``gives(kind)``: its name
    of :data:`HANDED` or ``''``; ``needs``: the description's field that
    holds its widths, as ``field=Class``; ``check(cfg, name, kind)`` refuses
    the pairings that are its own (``__post_init__``); ``written``: a layer
    may write the family's name as its mixer (an attention kind alone
    reaches the others); ``counters``: names of the numbers its layers hand
    out beside their result."""

    scope: str
    norm: str
    apply: Callable
    params: Callable
    score_flops: Callable
    inner: Callable = lambda kind: ""
    takes: Callable = lambda kind: ""
    gives: Callable = lambda kind: ""
    needs: str = ""
    check: Callable = lambda cfg, name, kind: None
    written: bool = True
    #: the counters its layers hand out (``given["counters"]``, one number
    #: each, summed over the layers beside the expert layers')
    counters: Tuple[str, ...] = ()


def _attention_params(cfg, kind):
    d, heads = cfg.d_model, kind.n_heads or cfg.n_heads
    held = cfg.kv_heads * cfg.head_dim
    n = 2 * d * heads * cfg.head_dim + 2 * d * held
    if kind.qk_norm:
        n += 2 * cfg.head_dim  # q's gain and k's
    if kind.gate:
        n += d * heads
    if kind.index:
        # its queries, its one key with a LayerNorm's gain and bias, its
        # head weights
        ix = kind.index
        n += d * (ix.n_heads * ix.head_dim + ix.head_dim + ix.n_heads) \
            + 2 * ix.head_dim
    if kind.latent:
        # a tap and a bias a channel, a [head_dim, head_dim] matrix a tap and
        # head and a bias a channel; a temperature a kv head
        mix, channels = kind.latent, heads * cfg.head_dim + held
        n += (mix.taps[0] + 1) * channels \
            + (mix.taps[1] * cfg.head_dim + 1) * channels \
            + (cfg.kv_heads if mix.qk_norm else 0)
    return n


def _lowrank_params(cfg, kind):
    d, low, heads = cfg.d_model, kind.lowrank, kind.n_heads or cfg.n_heads
    # down, the norm's gain and up, for q (one map where it has no
    # bottleneck) and for k / v; the way back
    return ((d * heads * cfg.head_dim if low.q_rank is None
             else (d + 1 + heads * cfg.head_dim) * low.q_rank)
            + d * (low.kv_rank + low.rope_dim) + low.kv_rank
            + low.kv_rank * heads * (low.nope_dim + low.value_dim)
            + heads * low.value_dim * d)


def _diff_params(cfg, kind):
    d, inner = cfg.d_model, (kind.n_heads or cfg.n_heads) * cfg.head_dim
    held = 0 if kind.kv == "takes" else 2 * cfg.kv_heads * cfg.head_dim
    # q and out (the pairs' values are as wide as their score heads
    # together), k and v, the four lambda vectors, the inner gain
    n = 2 * d * inner + d * held + 6 * cfg.head_dim
    return n + (inner + held + d if kind.bias and not cfg.bias else 0)


def _mamba2_params(cfg, kind):
    d, m = cfg.d_model, cfg.ssm
    inner, bc = m.n_heads * m.head_dim, m.n_groups * m.d_state
    return (d * (2 * inner + 2 * bc + m.n_heads)      # z, x, B, C, dt
            + (m.d_conv + 1) * (inner + 2 * bc)       # conv and bias
            + 3 * m.n_heads + inner + inner * d)      # dt_bias A D norm out


def _mamba1_params(cfg, kind):
    d, m = cfg.d_model, cfg.mamba1
    return (3 * d * m.d_inner                            # in, z and out
            + (m.d_conv + 1) * m.d_inner                 # taps, bias
            + m.d_inner * (m.dt_rank + 2 * m.d_state)    # dt, B, C
            + (m.dt_rank + 1) * m.d_inner                # dt up, bias
            + (m.d_state + 1) * m.d_inner)               # A, D


def _scores(sizes):
    """An attention family's ``score_flops``: ``S = Q K^T`` at the scores'
    head size and ``P V`` at the values', ``sizes(cfg, kind)`` their sum,
    over the keys a query sees."""
    return lambda cfg, kind, seq_len: (
        6.0 * (kind.n_heads or cfg.n_heads) * sizes(cfg, kind) * (
            seq_len + cfg.block_diffusion if cfg.block_diffusion
            else min(kind.window or seq_len, seq_len)))


def _check_attention(cfg, name, kind):
    if kind.qk_norm and (kind.latent or kind.lowrank):
        raise ValueError(
            f"attention kind {name!r}: qk_norm on a latent or "
            f"lowrank kind, which norms q and k in its own way "
            f"(TransformerConfig.__post_init__ refuses the pair)")
    if kind.kv or kind.bias:  # which stand on a diff kind alone
        _check_diff(cfg, name, kind)
    if kind.index is not None:
        refused = [what for what, found in (
            ("causal=False", not cfg.causal),
            ("a window", bool(kind.window)),
            ("a latent mix", kind.latent is not None),
            ("a gate", kind.gate),
            ("no rotary scheme of its own", kind.rope is None),
            ("block diffusion", bool(cfg.block_diffusion)),
            ("attention_fn (sequence parallelism)",
             cfg.attention_fn is not None),
            ("pipeline_fn", cfg.pipeline_fn is not None),
            ("a looped or gated stack", cfg.loops > 1 or cfg.exit_gate),
            ("a multi-token-prediction module", cfg.mtp is not None),
            ("layers of another kind beside it",
             len(set(cfg.pattern)) > 1)) if found]
        if refused:
            raise NotImplementedError(
                f"attention kind {name!r}: a learned index with "
                f"{', '.join(refused)}: it stands in front of plain causal "
                f"attention, in a stack of equal layers "
                f"(TransformerConfig.__post_init__ refuses it)")


def _check_lowrank(cfg, name, kind):
    low = kind.lowrank
    # no scheme of its own is NO rotation, in a description without positions
    rotation = cfg.position == "none" if kind.rope is None \
        else kind.rope.rotary_dim == low.rope_dim
    if low.nope_dim + low.rope_dim != cfg.head_dim or not rotation or \
            kind.latent or kind.gate or kind.window or \
            cfg.kv_heads != (kind.n_heads or cfg.n_heads):
        raise ValueError(
            f"attention kind {name!r}: low-rank latent attention scores with "
            f"head_size = nope_dim + rope_dim, rotates rope_dim by its own "
            f"scheme (or, with none, nothing: then the description's "
            f"position is 'none'), has as many key/value heads as query "
            f"heads and no window, gate or mix")
    _check_attention(cfg, name, kind)


def _check_diff(cfg, name, kind):
    if kind.kv not in ("", "gives", "takes") or kind.diff is None or \
            kind.latent or kind.lowrank or kind.gate or kind.qk_norm or \
            kind.rope or cfg.position == "rope" or \
            (kind.n_heads or cfg.n_heads) % 2 or cfg.kv_heads % 2 or \
            (kind.kv == "takes" and kind.window):
        raise ValueError(
            f"attention kind {name!r}: differential attention (diff="
            f"{kind.diff}, kv={kind.kv!r}) pairs an even number of score "
            f"heads over an even number of key/value heads, carries no "
            f"rotary scheme, gate, q/k norm or latent, kv is '', 'gives' or "
            f"'takes', a taker has no window, and kv or bias stand on a diff "
            f"kind alone (TransformerConfig.__post_init__ refuses it)")


def _apply_attention(block, kind, h, rope, handed):
    """Plain attention; behind a learned index it hands out the index's
    counters too (``given["index"]``: the block puts them behind the FFN's)."""
    out = _attention(block, kind, h, rope)
    return (out[0], {"index": out[1]}) if kind.index is not None else (out, {})


def _attention_scores(cfg, kind, seq_len):
    """A plain attention's ``score_flops``; behind an index a query sees
    ``topk`` keys, and the index scores every one of the ``seq_len`` (counted
    in full, as the convention has the causal scores)."""
    if kind.index is None:
        return _scores(lambda cfg, kind: 2 * cfg.head_dim)(cfg, kind, seq_len)
    ix = kind.index
    return 6.0 * ((kind.n_heads or cfg.n_heads) * 2 * cfg.head_dim
                  * min(ix.topk, seq_len)
                  + ix.n_heads * ix.head_dim * seq_len)


def _apply_diff(block, kind, h, rope, handed):
    out, kv = _diff_attention(
        block, kind, h, handed.get("kv") if kind.kv == "takes" else None)
    return out, {"kv": kv}


def _apply_kda(block, kind, h, rope, handed):
    """The delta-rule mixer; its counters ride out as the index's do
    (``given["counters"]``: the block puts them behind the FFN's)."""
    out, counted = _kda(block, h)
    return out, {"counters": counted}


def _check_kda(cfg, name, kind):
    if cfg.attention_fn is not None or cfg.loops > 1 or \
            cfg.pipeline_fn is not None:
        raise NotImplementedError(
            f"a {name} layer under sequence parallelism (attention_fn), in a "
            f"looped stack or inside the pipeline: the recurrence's state is "
            f"not handed across a boundary between the sequence's shards "
            f"(TransformerConfig.__post_init__ refuses it)")


def _kda_params(cfg, kind):
    d, m = cfg.d_model, cfg.kda
    inner = m.n_heads * m.head_dim
    return (4 * d * inner + 3 * m.d_conv * inner       # q, k, v, out; taps
            + 2 * (d + inner) * m.head_dim             # the two low-rank maps
            + m.n_heads + inner                        # A_log, dt_bias
            + d * m.n_heads + m.head_dim)              # beta, the norm's gain


def _apply_mamba1(block, kind, h, rope, handed):
    out, y = _mamba1(block, h)
    return out, {"memory": y}


#: THE table: the kinds of mixer there are. ``lowrank`` and ``diff`` are the
#: attention kinds with ``AttentionKind.lowrank`` / ``.diff``. A body is
#: looked up when it is called (a test stands its own in). A new mixer is one
#: entry here, its kernel under ``ops/`` and its numbers in
#: ``models/<name>.py`` — nothing else of this module (docs/operations.md).
_ATTENTION = dict(scope="attention", norm="ln_attn")
_SSM = dict(scope="ssm", norm="ln_ssm")
MIXER_FAMILIES = {
    "attention": MixerFamily(
        apply=_apply_attention,
        params=_attention_params,
        score_flops=_attention_scores,
        check=_check_attention, **_ATTENTION),
    "lowrank": MixerFamily(
        apply=lambda block, kind, h, rope, handed: (
            _latent_attention(block, kind, h, rope), {}),
        params=_lowrank_params, score_flops=_scores(
            lambda cfg, kind: cfg.head_dim + kind.lowrank.value_dim),
        check=_check_lowrank, written=False, **_ATTENTION),
    "diff": MixerFamily(  # a pair's value is two heads wide
        apply=_apply_diff, params=_diff_params,
        score_flops=_scores(lambda cfg, kind: 3 * cfg.head_dim),
        inner=lambda kind: "cross" if kind.kv == "takes" else "",
        takes=lambda kind: "kv" if kind.kv == "takes" else "",
        gives=lambda kind: "kv" if kind.kv == "gives" else "",
        check=_check_diff, written=False, **_ATTENTION),
    "mamba2": MixerFamily(
        apply=lambda block, kind, h, rope, handed: (_mamba2(block, h), {}),
        params=_mamba2_params,
        score_flops=lambda cfg, kind, seq_len: 3.0 * ssd_flops_per_token(
            cfg.ssm.n_heads, cfg.ssm.head_dim, cfg.ssm.d_state,
            cfg.ssm.n_groups, cfg.ssm.chunk),
        needs="ssm=SsmConfig", **_SSM),
    "mamba1": MixerFamily(
        apply=_apply_mamba1, params=_mamba1_params,
        score_flops=lambda cfg, kind, seq_len: 3.0 *
        selective_scan_flops_per_token(cfg.mamba1.d_inner,
                                       cfg.mamba1.d_state),
        gives=lambda kind: "memory", needs="mamba1=Mamba1Config", **_SSM),
    "gmu": MixerFamily(
        apply=lambda block, kind, h, rope, handed: (
            _gmu(block, h, handed["memory"]), {}),
        params=lambda cfg, kind: 2 * cfg.d_model * cfg.mamba1.d_inner,
        score_flops=lambda cfg, kind, seq_len: 0.0,
        takes=lambda kind: "memory", needs="mamba1=Mamba1Config", **_SSM),
    "kda": MixerFamily(  # forward and twice that backward, no chunk in it
        apply=_apply_kda, params=_kda_params,
        score_flops=lambda cfg, kind, seq_len: 3.0 * kda_flops_per_token(
            cfg.kda.n_heads, cfg.kda.head_dim, cfg.kda.head_dim),
        needs="kda=KdaConfig", check=_check_kda, counters=KDA_COUNTERS,
        **_SSM),
}


def _ffn(block, h, state=None):
    """``(y, aux, state)``: the FFN on the normed input ``h``; an expert
    layer whose router has a state takes the previous layer's and gives its
    own, any other layer hands ``state`` on as it came."""
    cfg = block.cfg
    aux = jnp.zeros((), jnp.float32)
    # the first products are candidates for what remat ``full`` keeps
    # (``ops/remat.py``): the width they contract is what a byte of them costs
    width = h.shape[-1]
    if block.ffn == "swiglu":
        gate = _projection(block, cfg.d_ff, ("embed", "mlp"), ("mlp",),
                           "gate")(h)
        up = _projection(block, cfg.d_ff, ("embed", "mlp"), ("mlp",), "up")(h)
        gate, up = remat.name_products((gate, up), width)
        h = nn.silu(gate) * up
    elif block.ffn == "moe":
        m = cfg.moe
        y, aux, routed = MoeMlp(
            experts_total=m.experts_total, experts_held=m.experts_held,
            d_ff=m.d_ff, shared_d_ff=m.shared_d_ff, k=m.k, scaling=m.scaling,
            out_init_scale=(2 * cfg.n_layers) ** -0.5, dtype=cfg.dtype,
            router=m.router, router_hidden=m.router_hidden,
            router_eps=cfg.norm_eps, skip_choice=m.skip_choice,
            selection_bias=m.selection_bias, expert_form=m.expert_form,
            down_zero_sums=m.down_zero_sums,
            # under block diffusion every masked row is nearly the mask
            # token's ONE vector, so the rows' near-ties at the k-th place
            # are one near-tie: a rematerialised forward that rounds another
            # way would re-route them all, and the backward weigh experts
            # the pass did not run (``MoeMlp.keep_routing``)
            keep_routing=bool(cfg.block_diffusion), name="moe",
        )(h, state)
        return y, aux, state if routed is None else routed
    else:
        up, = remat.name_products((_projection(
            block, cfg.d_ff, ("embed", "mlp"), ("mlp",), "up")(h),), width)
        h = nn.gelu(up)
    h = _projection(block, cfg.d_model, ("mlp", "embed"), ("embed",), "down",
                    residual=True)(h)
    return h, aux, state


class Block(nn.Module):
    """One layer of the stack: a mixer and an FFN — or, with the FFN
    ``none``, the mixer alone — each from its norm to the residual add
    (under ``norm_placement="sandwich"`` each sub-layer's output is normed
    once more in front of the add). What the mixer is — its scope, norm,
    body, what it takes and gives — is its entry of :data:`MIXER_FAMILIES`
    (``cfg.mixer_family``): the block asks nothing about it by name.

    ``rope`` is ``None`` or the rotary tables ``(cos, sin)`` of
    :func:`easydl_tpu.ops.rope.rope_tables`, made once for all layers.

    Returns ``(x, aux)`` — the (carry, per-step-output) pair ``nn.scan``
    expects; standalone callers unpack the first element. Where the
    description's router has a state that runs through the depth
    (``cfg.router_state_width``) the carry is the pair ``(x, state)``, coming
    and going: the residual stream and the router state ``[batch, seq,
    width]`` in float32, which the layer's FFN reads and writes.
    """

    cfg: TransformerConfig
    mixer: str = "attention"
    ffn: str = "gelu"
    #: names of :data:`HANDED` that come beside the residual stream, the
    #: carry then ``(x, {name: value})``, and those this layer gives: the
    #: second result is then ``(aux, {name: value})``, which a scan stacks by
    #: layer and the stack hands to the runs behind
    #: (``TransformerConfig.handoffs``)
    carried: Tuple[str, ...] = ()
    gives: Tuple[str, ...] = ()

    @nn.compact
    def __call__(self, x, deterministic: bool = True, rope=None):
        # NB: ``deterministic`` and ``rope`` are positional — nn.scan drops
        # kwargs.
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        state, handed = None, {}
        if self.carried:
            x, handed = x
        if cfg.router_state_width:
            x, state = x
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))

        def residual(x, h, ln):
            if cfg.norm_placement == "sandwich":
                h = _norm(cfg, f"{ln}_out", dtype=dt)(h)
            if cfg.dropout and not deterministic:
                h = nn.Dropout(cfg.dropout, deterministic=False)(h)
            if cfg.residual_multiplier != 1.0:
                h = h * jnp.asarray(cfg.residual_multiplier, h.dtype)
            if cfg.residual_scale:
                with jax.named_scope("residual_scale"):
                    s_x, t_x, s_y, t_y = (self.param(
                        f"{ln}_res_{name}", nn.with_logical_partitioning(
                            init, ("embed",)), (cfg.d_model,)
                    ).astype(jnp.float32) for name, init in (
                        ("scale_x", nn.initializers.ones_init()),
                        ("bias_x", nn.initializers.zeros_init()),
                        ("scale_y", nn.initializers.ones_init()),
                        ("bias_y", nn.initializers.zeros_init())))
                    return ((s_x * x.astype(jnp.float32) + t_x)
                            + (s_y * h.astype(jnp.float32) + t_y)
                            ).astype(x.dtype)
            return x + h

        # The scopes put every operation of a layer, residual adds,
        # activations and logical constraints included, under its family's
        # (`attention` | `ssm`) and under `ffn` in the compiled program's
        # op_name paths, which the device trace's reducers read; flax's
        # module names sit inside them.
        with remat.block(cfg.remat_policy if cfg.remat else None) as said:
            family, kind = cfg.mixer_family(self.mixer)
            inner = family.inner(kind)
            with jax.named_scope(family.scope), (
                    jax.named_scope(inner) if inner
                    else contextlib.nullcontext()):
                h, given = family.apply(
                    self, kind, _norm(cfg, family.norm, dtype=dt)(x), rope,
                    handed)
                index_aux = given.pop("index", None)
                own_aux = given.pop("counters", None)
                x = residual(x, h, family.norm)
            # `ffn` is the dense FFN's scope; an expert layer is `moe`, with
            # the scopes of ops/moe.py inside it; a layer that is its mixer
            # alone has neither, nor a second norm
            if self.ffn == "none":
                aux = jnp.zeros((), jnp.float32)
            else:
                with jax.named_scope("moe" if self.ffn == "moe" else "ffn"):
                    h, aux, state = _ffn(
                        self, _norm(cfg, "ln_mlp", dtype=dt)(x), state)
                    x = residual(x, h, "ln_mlp")
            if index_aux is not None:  # behind the expert layer's counters
                aux = jnp.concatenate([aux, index_aux]) \
                    if self.ffn == "moe" else index_aux
            if cfg.mixer_counters:
                # a stack whose mixers count mixes kinds of layer: every one
                # hands out the whole vector, zeros where it counts nothing
                n_own = len(cfg.mixer_counters)
                aux = jnp.concatenate([
                    aux if self.ffn == "moe" else jnp.zeros(
                        (len(cfg.counters) - n_own,), jnp.float32),
                    jnp.zeros((n_own,), jnp.float32) if own_aux is None
                    else own_aux])
        if cfg.remat and not self.is_initializing():
            log_once(log, f"remat {cfg.remat_policy}: a ({self.mixer}, "
                          f"{self.ffn}) layer at {tuple(x.shape)}, "
                          f"{said.line()}")
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        x = (x, state) if cfg.router_state_width else x
        if self.gives:
            aux = (aux, {name: given[name] for name in self.gives})
        return (x, handed) if self.carried else x, aux


def _pipelined(stack, block_cls, scan_kwargs, mixer, ffn, x, deterministic,
               rope):
    """The one run of the stack through ``cfg.pipeline_fn``'s GPipe
    schedule, on the stacked params the plain path created."""
    cfg = stack.cfg  # (no moe layer comes here: ``__post_init__``)
    if cfg.dropout and not deterministic:
        # The stage apply below passes no rngs: without this a dropout > 0
        # apply dies of an opaque missing-'dropout'-rng error deep inside
        # shard_map tracing. (Deterministic applies need no rng.)
        raise NotImplementedError(
            f"dropout={cfg.dropout} with pipeline_fn: the pipeline "
            "path applies stages without rngs (v1 trains "
            "dropout-free; deterministic applies are fine)")
    if cfg.n_layers % cfg.pipeline_stages:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by "
                         f"pipeline_stages={cfg.pipeline_stages}")
    fn_stages = getattr(cfg.pipeline_fn, "stages", None)
    if fn_stages is not None and fn_stages != cfg.pipeline_stages:
        # else an opaque scan axis-size error deep inside shard_map tracing
        raise ValueError(f"pipeline_stages={cfg.pipeline_stages} != the "
                         f"pipeline_fn's mesh pp size {fn_stages}")
    # Apply the SAME stacked params through the GPipe schedule: a
    # standalone scan of length n_layers/pp has an identical param
    # tree structure, so each stage applies its [L/pp, ...] slice.
    chunk = nn.scan(block_cls, length=cfg.n_layers // cfg.pipeline_stages,
                    **scan_kwargs)(cfg, mixer, ffn)
    stacked = nn.meta.unbox(stack.variables["params"]["blocks"])

    def apply_stage(stage_params, h):
        y, _ = chunk.apply({"params": stage_params}, h, deterministic, rope)
        return y

    # block_remat: the blocks already carry nn.remat (the pipeline's own
    # stage checkpoint would then double the backward recompute)
    x = cfg.pipeline_fn(apply_stage, stacked, x, block_remat=cfg.remat)
    return x, jnp.zeros((cfg.n_layers,), jnp.float32)


class LoopStates(NamedTuple):
    """What a looped or gated stack gives under ``return_hidden``, stacked
    by pass: the normed states ``[T, B, S, D]``, and (``exit_gate``) the
    gate logits ``[T, B, S]`` in float32, else None. While the parameters
    are being made (``init``) only one pass is run."""

    hidden: jax.Array
    gate: Optional[jax.Array]


def _next_tokens(tokens):
    """Token ``i + 1`` at position ``i``; the last position, which has none,
    takes the sequence's first (nothing reads what it then computes: the
    causal mask hides it from every other position, and it has no
    target)."""
    return jnp.roll(tokens, -1, axis=1)


class MtpMerge(nn.Module):
    """What a multi-token-prediction module's layer is given: the next
    tokens' embeddings and the main stack's normed final state, each normed
    once more, joined (the embedding first) through a ``2 d_model ->
    d_model`` map without a bias. flax names every operation of it
    ``mtp_merge``, the name it is made under."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, emb_next, state):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        joined = jnp.concatenate([_norm(cfg, "ln_emb", dtype=dt)(emb_next),
                                  _norm(cfg, "ln_state", dtype=dt)(state)],
                                 -1)
        return _dense(cfg.d_model, ("mlp", "embed"), ("embed",), name="join",
                      use_bias=False, dtype=dt)(joined)


class MtpStates(NamedTuple):
    """What a stack with a multi-token-prediction module gives under
    ``return_hidden``: the main stack's normed final state ``[B, S, D]``
    (position ``i`` predicts token ``i + 1``) and the module's ``[B, S, D]``
    (position ``i`` predicts token ``i + 2``; its last position was fed the
    sequence's FIRST token for want of a next one, sees no later position
    under the causal mask and has no target)."""

    hidden: jax.Array
    mtp: jax.Array


class Transformer(nn.Module):
    """Token-in, logits-out decoder/encoder stack.

    ``return_hidden=True`` skips the head matmul and yields the post-LN
    hidden states ``[B, S, D]`` instead of logits — the input contract of
    the chunked fused LM loss (ops/fused_xent.py), which applies the
    head chunk-by-chunk so the full ``[B, S, V]`` f32 logits tensor never
    exists. A looped (``loops > 1``) or gated stack yields
    :class:`LoopStates`, every pass's; without ``return_hidden`` its logits
    are the last pass's. A stack with a multi-token-prediction module yields
    :class:`MtpStates`; without ``return_hidden`` its logits are the main
    stack's and the module is not computed (its parameters are made at
    ``init`` all the same).
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, *, deterministic: bool = True,
                 return_hidden: bool = False):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        tok_emb = nn.Embed(
            cfg.vocab, cfg.d_model, dtype=dt, name="tok_emb",
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=cfg.embedding_init_std),
                ("vocab", "embed")))
        seq = tokens.shape[1]
        x = tok_emb(tokens)
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, dt)
        if cfg.position == "learned":
            pos_emb = self.param("pos_emb", nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.01), ("seq", "embed")),
                (cfg.max_seq, cfg.d_model))
            x = x + jnp.asarray(pos_emb, dt)[None, :seq]
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))

        block_cls = Block
        if cfg.remat:
            if cfg.remat_policy not in ("full", "dots"):
                raise ValueError(f"remat_policy must be 'full' or 'dots', "
                                 f"got {cfg.remat_policy!r}")
            # "full" keeps what ops/remat.py's chooser keeps of the block's
            # candidates, and nothing else. `prevent_cse=False` is
            # right inside a loop, which a scanned run is. A run of ONE layer
            # is no loop once XLA has unrolled it, and its second forward is
            # merged with its first: where NO run of the stack is a loop
            # (Phi-4-mini-flash's six) remat would do nothing at all, so
            # there the barrier stands (16.9 GB for 12.3 GiB compiled)
            block_cls = nn.remat(
                Block, policy=remat.policy(cfg.remat_policy),
                prevent_cse=cfg.n_layers > 1 and all(
                    count == 1 for _, count in cfg.runs))
        # One traced block a run of equal layers, scanned over a stacked
        # 'layers' param axis: `blocks` where the whole stack is one run
        # (GPT-2, BERT), `blocks_<i>` where the pattern has several.
        # (`intermediates`, where a caller makes it mutable — a check, a
        # test — come out of a run stacked by layer, as the params lie)
        scan_kwargs = dict(
            variable_axes={"params": 0, "intermediates": 0},
            split_rngs={"params": True, "dropout": True},
            in_axes=(nn.broadcast, nn.broadcast),
            metadata_params={nn.PARTITION_NAME: "layers"},
        )
        runs = cfg.runs
        if cfg.pipeline_fn is not None and len(runs) > 1:
            raise NotImplementedError(
                "pipeline_fn over a stack of more than one run of layers")
        # one pair of tables a rotary scheme, made once for all its layers
        if cfg.block_diffusion and seq % (2 * cfg.block_diffusion):
            raise ValueError(
                f"block_diffusion={cfg.block_diffusion}: the stack takes "
                f"[noised || clean] rows, twice a whole number of blocks; "
                f"got {seq}")
        # under block diffusion a noised token and its clean twin are
        # rotated alike: positions [0 .. seq/2 - 1, 0 .. seq/2 - 1]
        held = seq // 2 if cfg.block_diffusion else seq
        ropes = {"attention": rope_tables(held, cfg.head_dim, cfg.rope_theta)
                 if cfg.position == "rope" else None}
        for name, kind in cfg.attention_kinds:
            ropes[name] = ropes["attention"] if kind.rope is None else \
                kind.rope.tables(held, cfg.head_dim)
            if kind.index is not None:
                # the index's own tables behind the attention's: rotate-half
                # over its whole head, the kind's theta
                ropes[name] += rope_tables(held, kind.index.head_dim,
                                           kind.rope.theta)
        if cfg.block_diffusion:
            ropes = {name: tables and tuple(
                jnp.concatenate([table, table]) for table in tables)
                     for name, tables in ropes.items()}

        def pass_end(stack, x):
            """The final norm, and the exit gate's logit on the normed
            state (or None)."""
            x = _norm(cfg, "ln_f", dtype=dt)(x)
            gate = None
            if cfg.exit_gate:
                w = stack.param("exit_gate", nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=0.02), ("embed",)),
                    (cfg.d_model,))
                b = stack.param(
                    "exit_gate_bias", nn.with_logical_partitioning(
                        nn.initializers.zeros_init(), (None,)), (1,))
                # float32 whatever the compute dtype: a sum of products on
                # the VPU, not a matrix product
                with jax.named_scope("exit_gate"):
                    gate = jnp.sum(x.astype(jnp.float32)
                                   * w.astype(jnp.float32), -1) \
                        + b.astype(jnp.float32)
            return x, gate

        def one_pass(stack, x):
            """The runs of layers once and the final norm: ``(x, (x, gate
            logit, aux))``, a scan body over passes."""
            # zeros for dense layers; the expert layers' counters summed
            # (ops/moe.py counters) where the description has any
            aux = jnp.zeros((len(cfg.counters),) if cfg.counters else (),
                            jnp.float32)
            if cfg.router_state_width:
                # the router state beside the residual stream: zeros into
                # the first layer
                x = (x, jnp.zeros((*x.shape[:2], cfg.router_state_width),
                                  jnp.float32))
            # what earlier layers handed on (``cfg.handoffs``): a run takes
            # beside the stream what it or a run behind it reads, and what
            # its last layer gives goes to the runs behind
            handed = {}
            for i, (((mixer, ffn), count), (carried, gives)) in enumerate(
                    zip(runs, cfg.handoffs)):
                rope = ropes.get(mixer)
                if cfg.pipeline_fn is None or stack.is_initializing():
                    # plain (or init) path: params are created here, in
                    # the stacked [n_layers, ...] layout the pipeline expects
                    if carried:
                        x = (x, {name: handed[name] for name in carried})
                    run_name = "blocks" if len(runs) == 1 else f"blocks_{i}"
                    # what the block keeps is held once a layer and pass
                    with remat.run(run_name, count * cfg.loops):
                        x, layer_aux = nn.scan(
                            block_cls, length=count, **scan_kwargs)(
                                cfg, mixer, ffn, *((carried, gives) if carried
                                                   or gives else ()),
                                name=run_name)(x, deterministic, rope)
                    if carried:
                        x, _ = x
                    if gives:
                        layer_aux, given = layer_aux
                        handed.update(jax.tree.map(lambda a: a[-1], given))
                else:
                    x, layer_aux = _pipelined(
                        stack, block_cls, scan_kwargs, mixer, ffn, x,
                        deterministic, rope)
                aux = aux + (jnp.sum(layer_aux, 0) if layer_aux.ndim > 1
                             else jnp.sum(layer_aux))
            if cfg.router_state_width:
                x, state = x
                # the carry's size after the last layer, beside the layers'
                # counters
                stack.sow("counters", "router_state_rms", jnp.sqrt(jnp.mean(
                    jnp.square(state))))
            # Between passes only the normed state is kept (bf16): the
            # final norm's and the gate's float32 intermediates, 4 x [B, S,
            # D] a pass, are recomputed in the backward pass.
            x, gate = (nn.remat(pass_end, prevent_cse=False)
                       if cfg.remat and cfg.loops > 1 else pass_end)(stack, x)
            return x, (x, gate, aux)

        if cfg.loops == 1 or self.is_initializing():
            x, (_, gate, aux) = one_pass(self, x)
            states = x[None]
            gates = None if gate is None else gate[None]
        else:
            # A looped stack is ONE traced pass scanned `loops` times over
            # the same (broadcast) parameters: each parameter's gradient is
            # summed in the scan's carry, where a Python loop over passes
            # kept every pass's stacked gradients alive to the end (2.7 GiB
            # a pass at Ouro's cell). The normed state is that pass's output
            # and the next one's input; the outputs are stacked by pass.
            x, (states, gates, aux) = nn.scan(
                one_pass, variable_broadcast="params",
                split_rngs={"params": False, "dropout": True},
                length=cfg.loops)(self, x)
            aux = jnp.sum(aux)
        mtp = None
        if cfg.mtp is not None and (return_hidden or self.is_initializing()):
            # The module, outside the scan over the main runs: the normed
            # final state at position i joined with the embedding of token
            # i + 1 (the SAME embedding; the last position takes the first
            # token: `roll`), one more layer, a final norm of its own.
            mixer, ffn = cfg.mtp.mixer, cfg.mtp.ffn
            with jax.named_scope("mtp"):
                mtp = MtpMerge(cfg, name="mtp_merge")(
                    tok_emb(_next_tokens(tokens)), x)
                with remat.run("mtp_block", 1):
                    mtp, layer_aux = block_cls(
                        cfg, mixer, ffn, name="mtp_block")(
                            mtp, deterministic, ropes.get(mixer))
                mtp = _norm(cfg, "mtp_ln_f", dtype=dt)(mtp)
            if ffn == "moe":
                aux = aux + layer_aux
        # The expert layers' counters, summed over the layers; read back by
        # the loss function via mutable=["counters"] — a no-op sow for plain
        # apply() calls.
        if cfg.counters:
            self.sow("counters", "moe", aux)

        if return_hidden:
            if cfg.mtp is not None:
                return MtpStates(x, mtp)
            if cfg.loops == 1 and not cfg.exit_gate:
                return x
            return LoopStates(states, gates)
        with jax.named_scope("lm_head"):
            if cfg.tied_head:
                logits = tok_emb.attend(x)
            else:
                logits = _dense(cfg.vocab, ("embed", "vocab"), (),
                                name="head", use_bias=False, dtype=dt)(x)
            if cfg.logits_scaling != 1.0:
                logits = logits / jnp.asarray(cfg.logits_scaling,
                                              logits.dtype)
        return logits
