"""Sharding rules: logical parameter axes → mesh axes.

Models annotate parameters with *logical* axis names
(``nn.with_logical_partitioning``); one rule table maps those names onto the
mesh axes of :mod:`easydl_tpu.core.mesh`. Changing a job from pure DP to
FSDP+TP is a rule/mesh change only — no model edits — which is exactly what
elastic resharding needs: the master rebuilds the mesh at a new world size and
re-derives every sharding from the same rules.

For models without annotations (plain flax params), :func:`infer_shardings`
applies a size-threshold FSDP heuristic.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import numpy as np
from flax import traverse_util
from flax.core import FrozenDict
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: logical axis → mesh axis (or tuple of mesh axes, or None = replicated).
#: The vocabulary follows the t5x/maxtext convention.
DEFAULT_RULES: Tuple[Tuple[str, Any], ...] = (
    ("batch", ("dp", "fsdp")),
    ("embed", "fsdp"),          # d_model dim of weights: sharded for FSDP
    ("mlp", "tp"),              # FFN hidden dim
    ("heads", "tp"),            # attention heads
    ("kv", None),               # per-head dim: replicated
    ("ssm_group", None),        # Mamba-2 B / C groups (fewer than tp): whole
    ("ssm_state", None),        # Mamba-2 state dim: replicated
    ("qkv", "tp"),
    ("vocab", "tp"),
    ("seq", "sp"),              # sequence dim of activations
    ("expert", "ep"),
    ("conv_in", None),
    ("conv_out", "fsdp"),
    ("stage", "pp"),
    ("layers", None),           # nn.scan'd block axis (stacked layer params)
    ("table", None),            # sparse embedding tables live on host PS
    ("table_vocab", "fsdp"),    # on-device embedding tables: shard the vocab dim
)


def logical_axis_rules(rules: Sequence[Tuple[str, Any]] = DEFAULT_RULES):
    """Context manager enabling the rules for flax's spmd machinery."""
    return nn.spmd.logical_axis_rules(rules)


def mesh_sharding(mesh: Mesh, spec: Optional[P]) -> NamedSharding:
    return NamedSharding(mesh, spec if spec is not None else P())


def state_shardings(
    abstract_state: Any,
    mesh: Mesh,
    rules: Sequence[Tuple[str, Any]] = DEFAULT_RULES,
) -> Any:
    """NamedSharding tree for a (possibly nn.Partitioned-annotated) state tree.

    ``abstract_state`` is typically the result of ``jax.eval_shape`` over the
    init function, with flax ``Partitioned`` metadata boxes intact.
    """
    logical_specs = nn.get_partition_spec(abstract_state)
    return nn.logical_to_mesh_sharding(logical_specs, mesh, list(rules))


def infer_shardings(
    params: Any,
    mesh: Mesh,
    axis: str = "fsdp",
    min_size: int = 2**14,
) -> Any:
    """FSDP heuristic for unannotated params: shard the largest dimension that
    divides evenly by ``mesh.shape[axis]``; small params stay replicated."""
    n = mesh.shape[axis]

    def spec_for(x) -> NamedSharding:
        shape = getattr(x, "shape", ())
        if n > 1 and np.prod(shape, dtype=np.int64) >= min_size:
            order = sorted(range(len(shape)), key=lambda i: -shape[i])
            for dim in order:
                if shape[dim] % n == 0:
                    spec = [None] * len(shape)
                    spec[dim] = axis
                    return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return jax.tree.map(spec_for, params)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for a [global_batch, ...] input: batch over the dp axes."""
    return NamedSharding(mesh, P(("dp", "fsdp")))


def unbox(tree: Any) -> Any:
    """Strip flax ``Partitioned`` metadata boxes, keeping raw arrays."""
    return nn.meta.unbox(tree)


def flatten_dict(params: Any) -> dict:
    if isinstance(params, FrozenDict):
        params = params.unfreeze()
    return {"/".join(map(str, k)): v for k, v in traverse_util.flatten_dict(params).items()}


def unflatten_dict(flat: dict) -> dict:
    return traverse_util.unflatten_dict({tuple(k.split("/")): v for k, v in flat.items()})
