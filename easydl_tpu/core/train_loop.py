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
- where the devices state their memory, what the model's rematerialised
  blocks keep is fitted to what the compiled step leaves of it
  (:class:`_FittedStep`; the rule is ``ops/remat.py``'s).
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import time
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from easydl_tpu.core import sharding as shd
from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.ops import platform, remat
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


def _held_by_a_device(tree: Any) -> int:
    """The most bytes of ``tree``'s arrays that one device holds (shapes
    alone hold none)."""
    held: Dict[Any, int] = {}
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            for shard in leaf.addressable_shards:
                held[shard.device] = held.get(shard.device, 0) \
                    + shard.data.nbytes
    return max(held.values(), default=0)


def compiled_bytes(lowered: Any) -> int:
    """What a lowered program, compiled, takes of a device while it runs:
    temporaries + arguments + results - what the results alias (the
    executable stays with the lowering: its ``compile()`` is done)."""
    mem = lowered.compile().memory_analysis()
    return int(mem.temp_size_in_bytes + mem.argument_size_in_bytes
               + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def _text_hash(lowered: Any) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


class _Fitted(NamedTuple):
    """A step traced under a chooser: the jitted function, its lowering, the
    chooser and what the lowering compiled to (None: not compiled)."""
    fn: Any
    lowered: Any
    chooser: remat.Chooser
    size: Optional[int] = None


class _FittedStep:
    """The jitted train step, called and lowered under ``jax.set_mesh``, with
    what its rematerialised blocks keep (``ops/remat.py``'s candidates:
    dearest FLOP a byte first) fitted to the device's memory before its first
    use. Nothing a caller passes: the limit is the devices' own
    ``bytes_limit`` (``ops/platform.memory_stats``); where they state none —
    the CPU — no chooser is open, no candidate is kept and the step is traced
    once, as ever.

    The step is traced first with NOTHING kept. A program that offers no
    candidate (remat ``dots`` at GPT-2's shapes, no transformer at all) is
    done there, uncompiled, and asks no other process anything. Else it is
    compiled: ``memory_analysis()`` gives its size, exactly, and the room is
    ``bytes_limit`` less what the process holds beside the step's own
    arguments less ``remat.MARGIN_BYTES`` less that size. Where the rule
    keeps nothing in that room (Ouro's and the hybrid's cells) the step
    stands: one trace, one compile. Where it keeps something the step is
    traced and compiled ONCE more under that choice. A kept value costs the
    compiled step a little less than its bytes (XLA packs 8% of it into
    space the step had: Phi-4-mini-flash's cell), so the choice errs to the
    safe side; by the sum ``memory_analysis()`` gives, a value a scan stacks
    can cost up to twice its bytes, so a choice whose compiled size is over
    the budget after all is made ONCE more in the room at the price that
    compile showed (a third trace and compile, on a cold start); one that is
    still over, or that the compiler refuses, leaves the step that keeps
    nothing standing, and the log says so. The choice is a function of the program and the limit
    alone: a resume chooses as the run it resumes did.

    A start with room so pays a second trace, lowering and compile. So the
    choice a step settled on is REMEMBERED beside the persistent compile
    cache (``_memo``: one small file a step, found by the step's arguments,
    the mesh and the limit; in it the kept keys, the room and the budget
    they were chosen in and the hash of the text they lowered to) and is the
    first trace's plan at the next start: where the budget is the same and
    that trace lowers to the remembered text, it IS the choice — a warm
    start traces and loads once. A memory that is another program's or
    another budget's fails that and costs its trace."""

    def __init__(self, build: Callable[[], Any], mesh: Mesh):
        self._build, self._mesh = build, mesh
        self._fn: Any = None
        self._chooser: Optional[remat.Chooser] = None

    @contextlib.contextmanager
    def _under(self, chooser: Optional[remat.Chooser]):
        """What the step is traced under: its mesh and what it keeps."""
        with jax.set_mesh(self._mesh), remat.choosing(chooser):
            yield

    def __call__(self, *args):
        self._fit(args)
        with self._under(self._chooser):
            return self._fn(*args)

    def lower(self, *args):
        # the lowering the choice was made on is compiled already
        lowered = self._fit(args)
        if lowered is None:
            with self._under(self._chooser):
                lowered = self._fn.lower(*args)
        return lowered

    def _traced(self, chooser: remat.Chooser, args) -> _Fitted:
        """A step built anew — nothing of an earlier trace is reused — and
        lowered under ``chooser``."""
        fn = self._build()
        with self._under(chooser):
            return _Fitted(fn, fn.lower(*args), chooser)

    def _memo(self, args, limit: int) -> Optional[str]:
        """Where the choice for this step is remembered: a file beside the
        persistent compile cache, named for the step's arguments (shapes,
        dtypes), mesh and limit; None where no such cache is kept, or the
        step is one program across processes (one choice: no process's own
        memory)."""
        directory = jax.config.jax_compilation_cache_dir
        if not directory or not jax.config.jax_enable_compilation_cache \
                or jax.process_count() > 1:
            return None
        what = repr((jax.__version__, limit, remat.MARGIN_BYTES,
                     remat.FLOOR_FLOP_PER_BYTE, dict(self._mesh.shape),
                     [(jax.tree_util.keystr(path), tuple(leaf.shape),
                       str(leaf.dtype)) for path, leaf
                      in jax.tree_util.tree_leaves_with_path(args)]))
        return os.path.join(directory, "easydl-remat-fit-"
                            + hashlib.sha256(what.encode()).hexdigest()[:32])

    def _remembered(self, memo: str, budget: int,
                    args) -> Optional[_Fitted]:
        """The step under the choice ``memo`` holds, compiled, if that choice
        was made in this ``budget`` and the step lowers to the text it was
        made for; else None."""
        try:
            with open(memo) as f:
                said = json.load(f)
            plan = frozenset(tuple(key) for key in said["kept"])
            room, text = int(said["room"]), str(said["text"])
            if said["budget"] != budget:
                return None
        except (OSError, ValueError, TypeError, KeyError):
            return None  # no memory of this step, or none that can be read
        step = self._traced(remat.Chooser(room, plan), args)
        if step.chooser.kept != plan or _text_hash(step.lowered) != text:
            return None
        return step._replace(size=compiled_bytes(step.lowered))

    def _remember(self, memo: str, budget: int, step: _Fitted) -> None:
        """Leaves ``step``'s choice at ``memo``. A directory that cannot be
        written costs the next start its second trace and this one nothing:
        the memory is a saving, and a step that has fitted must run."""
        try:
            os.makedirs(os.path.dirname(memo), exist_ok=True)
            with open(f"{memo}.{os.getpid()}", "w") as f:
                json.dump({"kept": sorted(step.chooser.kept),
                           "room": step.chooser.room, "budget": budget,
                           "text": _text_hash(step.lowered)}, f)
            os.replace(f.name, memo)
        except OSError as error:
            log.warning("train step: the choice is not remembered beside "
                        "the compile cache (%s): the next start traces the "
                        "step twice", error)

    @staticmethod
    def _agreed(limit: int, beside: int) -> Tuple[int, int]:
        """One program across processes is one choice: the least limit and
        the most held beside the step of any process."""
        if jax.process_count() == 1:
            return limit, beside
        from jax.experimental import multihost_utils
        # (each in two halves: the bytes pass 32 bits)
        said = multihost_utils.process_allgather(np.array(
            [n >> part & 0xFFFFF for n in (limit, beside)
             for part in (20, 0)], np.int32)).astype(np.int64)
        limits, besides = (said[:, 0] << 20) + said[:, 1], \
            (said[:, 2] << 20) + said[:, 3]
        return int(limits.min()), int(besides.max())

    def _fit(self, args):
        """Settles ``_fn`` and ``_chooser`` on first use; returns the
        lowering of ``args`` it settled on."""
        if self._fn is not None:
            return None
        stats = [platform.memory_stats(d) for d in self._mesh.devices.flat]
        if not all(stat and "bytes_limit" in stat for stat in stats):
            if platform.on_tpu():
                log.warning(
                    "train step: the TPU's devices state no memory limit "
                    "(memory_stats() gives no bytes_limit): remat is given "
                    "no room and keeps NO candidate — the flash results a "
                    "block kept whatever the room before the rule saw room "
                    "are made again in every backward")
            self._fn = self._build()
            return None
        limit = min(stat["bytes_limit"] for stat in stats)
        # in whole 64 MiB, so that a resume that holds a few bytes more or
        # fewer beside its state chooses as the run before it did
        beside = max(stat.get("bytes_in_use", 0) for stat in stats) \
            - _held_by_a_device(args)
        beside = max(-(-beside // (64 << 20)) * (64 << 20), 0)
        budget = limit - beside - remat.MARGIN_BYTES
        memo = self._memo(args, limit)
        step = memo and self._remembered(memo, budget, args)
        if not step:
            step = self._traced(remat.Chooser(0, frozenset()), args)
            if step.chooser.seen:
                # only a program with something to choose asks the others
                limit, beside = self._agreed(limit, beside)
                budget = limit - beside - remat.MARGIN_BYTES
                step = self._chosen(step, args, budget)
                if memo:
                    self._remember(memo, budget, step)
        if step.size is not None:
            log.info(
                "train step: compiled to %.3f GiB a device of a limit of "
                "%.3f GiB (%.3f held beside the step, a margin of %.3f); "
                "remat %s",
                step.size / 2**30, limit / 2**30, beside / 2**30,
                remat.MARGIN_BYTES / 2**30, step.chooser.said())
        self._fn = step.fn
        # every later trace of the step replays the choice
        self._chooser = remat.Chooser(step.chooser.room, step.chooser.kept)
        return step.lowered

    def _chosen(self, nothing: _Fitted, args, budget: int) -> _Fitted:
        """The rule's choice in ``budget`` bytes, from the step lowered with
        ``nothing`` kept, compiled. ``nothing``'s chooser leaves with the
        room the step's size left: what it could have kept."""
        nothing = nothing._replace(size=compiled_bytes(nothing.lowered))
        room = nothing.chooser.room = max(budget - nothing.size, 0)
        want = nothing.chooser.fill(room)
        if not want:
            return nothing
        for again in (False, True):
            step = self._traced(remat.Chooser(room, want), args)
            kept = step.chooser.kept_bytes
            try:
                step = step._replace(size=compiled_bytes(step.lowered))
            except jax.errors.JaxRuntimeError as refused:
                # whatever the compiler says of it: the step it took stands
                log.warning("train step: keeping %.3f GiB the step is "
                            "refused by the compiler (%s)", kept / 2**30,
                            str(refused)[:200])
                break
            if step.size <= budget:
                return step
            log.warning("train step: keeping %.3f GiB the step compiles to "
                        "%.3f GiB, over the %.3f the limit leaves it",
                        kept / 2**30, step.size / 2**30, budget / 2**30)
            # what was kept cost the compiled step more than its bytes (the
            # sum counts a value a scan stacks up to twice): the rule ONCE
            # more, in the room as that price leaves it
            less = nothing.chooser.fill(
                room * kept // max(step.size - nothing.size, kept))
            if again or not less or less == want:
                break
            want = less
        log.warning("train step: the step that keeps nothing stands")
        return nothing


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
        return jax.jit(
            train_step,
            in_shardings=(shardings, batch_shd),
            out_shardings=(shardings, replicated),
            donate_argnums=(0,) if self.config.donate_state else (),
        )

    @property
    def step_fn(self):
        if self._step_fn is None:
            self._step_fn = _FittedStep(self._build_step, self.mesh)
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
