"""The pjit training core: sharded state init + compiled train step.

TPU-native replacement for the reference's (unspecified) PS pull/push hot loop
(SURVEY.md §3.4): one ``jax.jit``-compiled step over an explicit
``jax.sharding.Mesh``; GSPMD inserts the gradient ``psum`` (and any FSDP
all-gather/reduce-scatter) over ICI. The Trainer is model-agnostic: it takes
pure functions (``init_fn``, ``loss_fn``) and never inspects model internals,
so the elastic master can rebuild it at a new world size from the same
functions and rules.

Design notes (TPU):
- parameters/optimizer state stay fp32; compute casts to bf16 (MXU-native)
  via :func:`cast_floating` inside the loss.
- gradient accumulation is a ``lax.scan`` over microbatches — static trip
  count, no Python loop in the traced step.
- state is donated, so buffers are reused in place (HBM headroom).
- flax ``Partitioned`` metadata boxes are kept in the state; logical-axis
  rules map them to mesh axes (see :mod:`easydl_tpu.core.sharding`).
- every compiled function is traced under ``jax.set_mesh(mesh)``: GSPMD
  cannot partition a Mosaic kernel, so ops that hold one (ops/attention.py)
  read the context mesh and run the kernel per shard in ``jax.shard_map``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from easydl_tpu.core import sharding as shd
from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.utils.logging import get_logger

log = get_logger("core", "trainer")

LossFn = Callable[..., Tuple[jax.Array, Dict[str, jax.Array]]]
InitFn = Callable[[jax.Array], Any]


def cast_floating(tree: Any, dtype: jnp.dtype) -> Any:
    """Cast floating-point leaves (keeps integer/bool leaves intact)."""

    def cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree.map(cast, tree)


class _OnMesh:
    """A jitted function that is called, and lowered, under
    ``jax.set_mesh(mesh)`` — so code traced inside it can ask
    ``jax.sharding.get_abstract_mesh()`` which mesh it is compiled for."""

    def __init__(self, fn, mesh: Mesh):
        self._fn, self._mesh = fn, mesh

    def __call__(self, *args):
        with jax.set_mesh(self._mesh):
            return self._fn(*args)

    def lower(self, *args):
        with jax.set_mesh(self._mesh):
            return self._fn.lower(*args)


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any
    rng: jax.Array

    @property
    def int_step(self) -> int:
        return int(jax.device_get(self.step))


@dataclass
class TrainConfig:
    global_batch: int = 32
    grad_accum: int = 1
    compute_dtype: Any = jnp.bfloat16
    seed: int = 0
    rules: Sequence[Tuple[str, Any]] = field(default_factory=lambda: shd.DEFAULT_RULES)
    donate_state: bool = True

    def __post_init__(self) -> None:
        if self.global_batch % max(self.grad_accum, 1):
            raise ValueError(
                f"global_batch={self.global_batch} not divisible by grad_accum={self.grad_accum}"
            )


class Trainer:
    """Builds and runs the compiled train step on a mesh.

    Args:
      init_fn: ``rng -> params`` (flax ``Partitioned`` boxes welcome).
      loss_fn: ``(params, batch, rng) -> (loss, aux_metrics)``. Called with
        params cast to ``config.compute_dtype``.
      optimizer: an optax ``GradientTransformation``.
      mesh: an existing Mesh, or None to build one from ``mesh_spec``.
    """

    def __init__(
        self,
        init_fn: InitFn,
        loss_fn: LossFn,
        optimizer: optax.GradientTransformation,
        config: TrainConfig,
        mesh: Optional[Mesh] = None,
        mesh_spec: Optional[MeshSpec] = None,
    ):
        self.config = config
        self.mesh = mesh if mesh is not None else build_mesh(mesh_spec or MeshSpec())
        self.init_fn = init_fn
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self._state_shardings: Any = None
        self._step_fn = None
        self._abstract: Any = None
        self._host_step = 0  # train_step calls so far (trace annotation)
        #: the last ``train_step`` call's host side, seconds: placing the
        #: batch (``shard_s``) and handing the step to the runtime
        #: (``dispatch_s``); overwritten by every call
        self.host_seconds: Dict[str, float] = {}

    # ------------------------------------------------------------------ init
    def _abstract_state(self) -> TrainState:
        def make(rng):
            params = self.init_fn(rng)
            opt_state = self.optimizer.init(params)
            return TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params,
                opt_state=opt_state,
                rng=rng,
            )

        # Old-style uint32 PRNG keys: checkpointable as plain arrays.
        rng = jax.random.PRNGKey(self.config.seed)
        if self._abstract is None:  # eval_shape re-traces init+opt: cache it
            self._abstract = jax.eval_shape(make, rng)
        return self._abstract, make, rng

    def state_shardings(self) -> Any:
        if self._state_shardings is None:
            abstract, _, _ = self._abstract_state()
            self._state_shardings = shd.state_shardings(
                abstract, self.mesh, self.config.rules
            )
        return self._state_shardings

    def init_state(self) -> TrainState:
        """Shard-aware init: the jit's out_shardings place every parameter
        shard directly on its device — no host-side full materialisation."""
        abstract, make, rng = self._abstract_state()
        shardings = self.state_shardings()
        t0 = time.perf_counter()
        state = _OnMesh(jax.jit(make, out_shardings=shardings), self.mesh)(rng)
        log.info(
            "initialised state on mesh [%s] in %.2fs (%d params)",
            ", ".join(f"{k}={v}" for k, v in self.mesh.shape.items() if v > 1) or "1 device",
            time.perf_counter() - t0,
            sum(x.size for x in jax.tree.leaves(shd.unbox(abstract.params))),
        )
        return state

    def abstract_state(self) -> TrainState:
        """Shape/dtype tree of the state (no allocation) — what checkpoint
        restore matches leaves against."""
        return self._abstract_state()[0]

    def restore_from(self, checkpoint, step: Optional[int] = None) -> TrainState:
        """Restore ``step`` (default: latest) from a CheckpointManager onto
        THIS trainer's mesh — the save may have used any other mesh shape
        (reshard-on-restore). The single public entry for resuming: the
        elastic worker, the evaluator, and the zoo runner all come through
        here."""
        if step is None:
            step = checkpoint.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoints under {checkpoint.directory}"
                )
        return checkpoint.restore(step, self.abstract_state(), self.state_shardings())

    # ------------------------------------------------------------------ step
    def _build_step(self):
        accum = max(self.config.grad_accum, 1)
        compute_dtype = self.config.compute_dtype
        loss_fn = self.loss_fn
        optimizer = self.optimizer

        # The named scopes below are metadata on the compiled program's
        # operations (their op_name path): the device trace's readers tell
        # cast, accumulation, optimizer and gradient norm apart by them.
        def forward(params, batch, rng):
            with jax.named_scope("cast_params"):
                params = cast_floating(params, compute_dtype)
            loss, aux = loss_fn(params, batch, rng)
            return loss.astype(jnp.float32), aux

        grad_fn = jax.value_and_grad(forward, has_aux=True)

        def single(params, batch, rng):
            (loss, aux), grads = grad_fn(params, batch, rng)
            return loss, aux, grads

        def accumulated(params, batch, rng):
            # [global, ...] -> [accum, global/accum, ...]
            def split(x):
                return x.reshape((accum, x.shape[0] // accum) + x.shape[1:])

            microbatches = jax.tree.map(split, batch)

            def body(carry, xs):
                loss_sum, aux_sum, grad_sum = carry
                mb, i = xs
                loss, aux, grads = single(params, mb, jax.random.fold_in(rng, i))
                with jax.named_scope("accumulate"):
                    return (
                        loss_sum + loss,
                        jax.tree.map(jnp.add, aux_sum, aux),
                        jax.tree.map(jnp.add, grad_sum, grads),
                    ), None

            loss0, aux0, grads0 = single(
                params, jax.tree.map(lambda x: x[0], microbatches), jax.random.fold_in(rng, 0)
            )
            rest = jax.tree.map(lambda x: x[1:], microbatches)
            (loss_sum, aux_sum, grad_sum), _ = jax.lax.scan(
                body, (loss0, aux0, grads0), (rest, jnp.arange(1, accum)),
            )
            scale = 1.0 / accum
            with jax.named_scope("accumulate"):
                return (
                    loss_sum * scale,
                    jax.tree.map(lambda a: a * scale, aux_sum),
                    jax.tree.map(lambda g: g * scale, grad_sum),
                )

        def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict[str, jax.Array]]:
            step_rng = jax.random.fold_in(state.rng, state.step)
            if accum > 1:
                loss, aux, grads = accumulated(state.params, batch, step_rng)
            else:
                loss, aux, grads = single(state.params, batch, step_rng)
            with jax.named_scope("optimizer"):
                updates, new_opt_state = optimizer.update(grads, state.opt_state, state.params)
                new_params = optax.apply_updates(state.params, updates)
            with jax.named_scope("grad_norm"):
                grad_norm = optax.global_norm(grads)
            metrics = {"loss": loss, "grad_norm": grad_norm, **aux}
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt_state,
            )
            return new_state, metrics

        shardings = self.state_shardings()
        batch_shd = shd.batch_sharding(self.mesh)
        replicated = NamedSharding(self.mesh, P())
        return _OnMesh(jax.jit(
            train_step,
            in_shardings=(shardings, batch_shd),
            out_shardings=(shardings, replicated),
            donate_argnums=(0,) if self.config.donate_state else (),
        ), self.mesh)

    @property
    def step_fn(self):
        if self._step_fn is None:
            self._step_fn = self._build_step()
        return self._step_fn

    def shard_batch(self, host_batch: Any) -> Any:
        """Place a host (numpy) batch onto the mesh, batch-sharded."""
        sharding_ = shd.batch_sharding(self.mesh)
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(sharding_, x), host_batch
        )

    def train_step(self, state: TrainState, host_batch: Any):
        """One step from a host batch. The annotations cost nothing without
        a profiler session; inside one they are host spans on the device
        trace's own clock. The step number is this Trainer's count of calls
        — never a fetch from the device. The two inner spans' lengths stay
        on ``host_seconds`` for the caller's own record."""
        t0 = time.perf_counter()
        with jax.profiler.StepTraceAnnotation("train_step",
                                              step_num=self._host_step):
            with jax.profiler.TraceAnnotation("easydl/shard_batch"):
                batch = self.shard_batch(host_batch)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("easydl/dispatch"):
                out = self.step_fn(state, batch)
        self.host_seconds["shard_s"] = t1 - t0
        self.host_seconds["dispatch_s"] = time.perf_counter() - t1
        self._host_step += 1
        return out

    # ------------------------------------------------------------------ eval
    def build_eval_step(self, eval_fn: LossFn):
        """Compile an eval step (no grads, no donation)."""
        compute_dtype = self.config.compute_dtype

        def eval_step(state: TrainState, batch):
            _, aux = eval_fn(cast_floating(state.params, compute_dtype), batch, state.rng)
            return aux

        return _OnMesh(jax.jit(
            eval_step,
            in_shardings=(self.state_shardings(), shd.batch_sharding(self.mesh)),
            out_shardings=NamedSharding(self.mesh, P()),
        ), self.mesh)
