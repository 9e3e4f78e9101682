"""Pipeline parallelism: a GPipe fill–drain schedule over the ``pp`` axis.

The reference has no pipeline parallelism (SURVEY §2.2 — the ``pp`` mesh
axis was reserved with no dedicated schedule); this module supplies the
schedule, TPU-first: the transformer's blocks are ALREADY an ``nn.scan``
over a stacked ``layers`` parameter axis, so stage sharding is just mapping
``layers → pp`` in the rule table — each pp rank then physically holds its
``n_layers/pp`` consecutive layers, and :func:`pipeline_blocks` runs the
classic GPipe schedule inside one ``shard_map``:

- the stage-local activation hops to the next stage over ``ppermute``
  (neighbour ICI traffic — exactly what pipeline parallelism exists to
  exploit);
- ``lax.scan`` over ``M + pp - 1`` ticks (static trip count: XLA-friendly
  control flow); the first ``pp-1`` and last ``pp-1`` ticks are the usual
  GPipe bubble;
- microbatching splits only the *forward pathway* inside the pipeline;
  loss/optimizer see the reassembled full batch, so training math is
  identical to the unpipelined model (the parity test asserts this).

Embedding, final LN, head and loss stay OUTSIDE the shard_map under plain
GSPMD; the pipeline output is replicated over ``pp`` via a masked psum of
the last stage's result.

Scope (v1): stage-local weights are unsharded inside the pipeline (no
tp/fsdp of a stage's own matrices — :func:`pipeline_rules` maps the weight
axes to None); dropout-free paths; dense FFNs (no MoE inside the
pipeline).

On 1F1B (why there is no ``schedule="1f1b"`` flag): under jax autodiff
the user writes only the FORWARD schedule; the backward is the transpose
XLA derives — for this scan-over-ticks + ppermute formulation that
transpose is itself a reverse-order pipeline, i.e. the backward is
already pipelined. Non-interleaved 1F1B has the SAME bubble fraction as
GPipe, ``(pp-1)/(m+pp-1)`` (see :func:`bubble_fraction`); what it buys in
a hand-scheduled framework is peak activation memory O(pp) instead of
O(m), and here ``jax.checkpoint`` around the stage apply already bounds
the stored state to the per-tick boundary activations. The variant that
genuinely cuts the bubble — the circular/interleaved schedule (v chunks
per rank, bubble ``(pp-1)/(v·m+pp-1)``) — needs chunk c resident on rank
``c mod pp``, i.e. a STRIDED layer placement; with the stacked
``[n_layers, ...]`` parameter layout this round's checkpoints use, that
means either relaying out saved states or an every-step weight all-to-all
inside the pipeline. Deliberately deferred rather than shipped as a flag
whose measured effect would be nil (the honest lever exposed instead:
raise ``microbatches`` — the bubble amortizes as 1/m, and the parity
tests hold at any m).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_ticks(microbatches: int, pp: int) -> int:
    """Static trip count of the schedule's scan: ``m`` work ticks plus the
    ``pp-1`` fill/drain ticks (the GPipe bubble)."""
    return microbatches + pp - 1


def bubble_fraction(microbatches: int, pp: int) -> float:
    """Idle fraction of the fill–drain schedule: ``(pp-1)/(m+pp-1)``.

    The knob that shrinks it is ``microbatches`` (1/m amortization); a
    non-interleaved 1F1B reordering would NOT change this number (see the
    module docstring)."""
    return (pp - 1) / pipeline_ticks(microbatches, pp)


def pipeline_rules(base) -> tuple:
    """Rule table for a pipelined model: stage-shard the stacked ``layers``
    axis over ``pp``; un-shard the weight/activation feature axes (the
    stage-local weights live whole on their stage in v1)."""
    drop = {"embed", "mlp", "heads", "kv", "qkv", "vocab", "seq"}
    out = []
    for name, target in base:
        if name == "layers":
            out.append((name, "pp"))
        elif name in drop:
            out.append((name, None))
        else:
            out.append((name, target))
    return tuple(out)


#: model families whose factories accept the pipeline (they share the
#: nn.scan transformer stack). A stack with ``moe`` layers (laguna) is
#: excluded: an expert layer inside the pipeline is a NotImplementedError
#: in the model.
PIPELINE_CAPABLE = ("gpt", "bert")


def apply_pipeline_config(model: str, model_kwargs: dict, mesh: Mesh,
                          microbatches: int = 2):
    """Entry-point helper: when ``mesh`` has a real ``pp`` axis, extend the
    model kwargs with the pipeline (``pipeline_fn`` closes over the mesh,
    so it can't travel through a serialized job config — the zoo runner and
    the elastic worker both call this after building their mesh).

    No-op (returning the kwargs and the default rules unchanged) when the
    mesh has no pp axis. A pp axis with a model family that can't pipeline
    raises a one-line config error — the alternative is an unexplained
    ``TypeError`` from the model factory deep in a worker crash-loop.

    Returns ``(model_kwargs, rules)`` — the rule table switches to
    :func:`pipeline_rules` so the stacked layer params stage-shard."""
    from easydl_tpu.core.sharding import DEFAULT_RULES

    pp = mesh.shape.get("pp", 1)
    if pp < 2:
        return model_kwargs, DEFAULT_RULES
    if model not in PIPELINE_CAPABLE:
        raise ValueError(
            f"mesh has pp={pp} but model {model!r} does not support "
            f"pipeline parallelism (capable: {', '.join(PIPELINE_CAPABLE)})"
        )
    out = dict(model_kwargs)
    out.setdefault("pipeline_fn", make_pipeline(mesh, microbatches))
    out.setdefault("pipeline_stages", pp)
    return out, pipeline_rules(DEFAULT_RULES)


def make_pipeline(mesh: Mesh, microbatches: int,
                  remat: Optional[bool] = None) -> Callable:
    """Build the ``pipeline_fn`` a :class:`TransformerConfig` carries
    (mirroring the ``attention_fn`` pattern): closes over the mesh so the
    model stays mesh-agnostic.

    Returns ``fn(apply_stage, stage_params, x, block_remat=False) -> y``
    where ``stage_params`` is the stacked ``[n_layers, ...]`` block tree
    (sharded ``layers → pp``) and ``x`` is the embedded activation
    ``[B, S, D]``. ``fn.stages`` carries the mesh's pp size so the model
    can validate its ``pipeline_stages`` against it.

    ``remat`` default (None) is automatic: the stage apply is wrapped in
    ``jax.checkpoint`` only when the caller says the blocks are NOT already
    remat-wrapped (``block_remat``) — stacking both would recompute the
    whole stage forward twice in the backward pass.
    """
    pp = mesh.shape["pp"]
    if pp < 2:
        raise ValueError(f"pipeline needs a pp axis of ≥2 (mesh has {pp})")

    def fn(apply_stage: Callable, stage_params: Any, x: jax.Array,
           block_remat: bool = False):
        outer_remat = remat if remat is not None else not block_remat
        return pipeline_blocks(mesh, apply_stage, stage_params, x,
                               microbatches=microbatches, remat=outer_remat)

    fn.stages = pp
    return fn


def pipeline_blocks(mesh: Mesh, apply_stage: Callable, stage_params: Any,
                    x: jax.Array, microbatches: int,
                    remat: bool = True) -> jax.Array:
    """Run ``apply_stage`` as a ``pp``-stage GPipe pipeline over ``x``.

    ``apply_stage(local_params, h) -> h`` applies one stage's layer chunk
    (the caller builds it from an ``nn.scan`` of length ``n_layers/pp``).
    ``stage_params`` leaves carry the stacked layer axis first and must be
    sharded over ``pp`` on that axis; ``x`` is batch-sharded over
    ``(dp, fsdp)`` and replicated over ``pp``.
    """
    pp = mesh.shape["pp"]
    batch_spec = P(("dp", "fsdp"))
    param_spec = jax.tree.map(lambda _: P("pp"), stage_params)
    stage_apply = jax.checkpoint(apply_stage) if remat else apply_stage

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(param_spec, batch_spec),
        out_specs=batch_spec,
        check_vma=False,
    )
    def run(p_local, x_local):
        import flax.linen as nn

        stage = jax.lax.axis_index("pp")
        batch = x_local.shape[0]
        if batch % microbatches:
            raise ValueError(
                f"per-shard batch {batch} not divisible by "
                f"microbatches={microbatches}"
            )
        mb = batch // microbatches
        xs = x_local.reshape((microbatches, mb) + x_local.shape[1:])
        ticks = microbatches + pp - 1

        def tick(carry, t):
            buf, out = carry
            # stage 0 ingests microbatch t (clamped past the drain phase);
            # later stages consume what the previous tick handed them
            mb_in = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, microbatches - 1), 0, keepdims=False
            )
            inp = jnp.where(stage == 0, mb_in, buf)
            with nn.logical_axis_rules(()):
                # inside shard_map the model's logical constraints must be
                # no-ops (there is no GSPMD context here); empty rules make
                # with_logical_constraint the identity
                y = stage_apply(p_local, inp)
            # hand the activation to the next stage (ring: the wrap-around
            # edge feeds stage 0, which ignores it — it reads xs instead)
            nxt = jax.lax.ppermute(
                y, "pp", [(i, (i + 1) % pp) for i in range(pp)]
            )
            # the last stage emits microbatch t-(pp-1) once it's real
            oidx = t - (pp - 1)
            valid = (stage == pp - 1) & (oidx >= 0)
            out = jnp.where(
                valid,
                jax.lax.dynamic_update_index_in_dim(
                    out, y, jnp.clip(oidx, 0, microbatches - 1), 0
                ),
                out,
            )
            return (nxt, out), None

        (_, outs), _ = jax.lax.scan(
            tick, (jnp.zeros_like(xs[0]), jnp.zeros_like(xs)),
            jnp.arange(ticks),
        )
        y = outs.reshape(x_local.shape)
        # replicate the last stage's assembled output to every pp rank so
        # the head/loss outside the shard_map see one consistent value
        return jax.lax.psum(
            jnp.where(stage == pp - 1, y, jnp.zeros_like(y)), "pp"
        )

    return run(stage_params, x)
