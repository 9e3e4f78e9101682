"""The parallelism API surface: one import site for every axis the mesh
supports.

The framework scales a model by composing named mesh axes
(SURVEY.md §2.2; "How to Scale Your Model"'s recipe — pick a mesh,
annotate shardings, let GSPMD insert the collectives):

- ``dp``   — data parallelism (batch sharded; gradient psum over ICI)
- ``fsdp`` — fully-sharded data parallelism (params sharded on ``embed``;
  GSPMD inserts the all-gather/reduce-scatter pair)
- ``tp``   — tensor parallelism (``mlp``/``heads``/``vocab`` sharded)
- ``sp``   — sequence/context parallelism (ring attention over
  ``ppermute``, or Ulysses head-all-to-all) for long context
- ``ep``   — expert parallelism (MoE experts sharded; all-to-all
  dispatch/combine)
- ``pp``   — pipeline parallelism (layer stack stage-sharded; GPipe
  fill–drain inside one ``shard_map``)

The implementations live where they are used — mesh/sharding in
``easydl_tpu.core``, the schedule/kernel machinery in ``easydl_tpu.ops``
— and this package is the supported import path that composes them:
``MeshSpec(dp=2, fsdp=2, tp=2)`` + the rule table + the per-axis factory
functions below are everything a model needs to run on any mesh shape
(the multichip dryrun exercises each axis family exactly through these
names).
"""

from easydl_tpu.core.mesh import MeshSpec, build_mesh  # noqa: F401
from easydl_tpu.core.sharding import (  # noqa: F401
    DEFAULT_RULES,
    state_shardings,
)
from easydl_tpu.ops.moe import MoeMlp, route, routed_experts  # noqa: F401
from easydl_tpu.ops.pipeline import (  # noqa: F401
    apply_pipeline_config,
    bubble_fraction,
    make_pipeline,
    pipeline_rules,
    pipeline_ticks,
)
from easydl_tpu.ops.sequence_parallel import (  # noqa: F401
    make_sp_attention,
    ring_attention,
    ulysses_attention,
)

__all__ = [
    "MeshSpec",
    "build_mesh",
    "DEFAULT_RULES",
    "state_shardings",
    "make_sp_attention",
    "ring_attention",
    "ulysses_attention",
    "make_pipeline",
    "pipeline_rules",
    "pipeline_ticks",
    "bubble_fraction",
    "apply_pipeline_config",
    "MoeMlp",
    "route",
    "routed_experts",
]
