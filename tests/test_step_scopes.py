"""Names inside the program: the scopes of the train step, the names of the
flash kernels, and the host annotations of ``Trainer.train_step``.

The device trace's readers (``benchmark/lib/scope_reduce.py``) tell forward
from backward from recomputed, attention from FFN from head and loss, and the
trainer's own work (cast, accumulation, optimizer, gradient norm) apart by
the name stack of each operation. These tests hold the program to the names:
on the CPU, from the lowered step's own locations — the compiled program's
``op_name`` metadata is made from them.
"""

from __future__ import annotations

import functools
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.core.train_loop import TrainConfig, Trainer
from easydl_tpu.models.registry import get_model
from easydl_tpu.ops.flash_attention import flash_attention

BATCH, SEQ, VOCAB = 8, 64, 1024


def _trainer(grad_accum: int) -> Trainer:
    bundle = get_model("gpt", size="test", seq_len=SEQ, vocab=VOCAB,
                       dtype="bfloat16", remat=True, remat_policy="dots")
    return Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adamw(1e-3),
        config=TrainConfig(global_batch=BATCH, grad_accum=grad_accum),
        mesh=build_mesh(MeshSpec(), devices=jax.devices()[:1]))


def _lowered(grad_accum: int):
    trainer = _trainer(grad_accum)
    tokens = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
    return trainer.step_fn.lower(
        trainer.abstract_state(), {"inputs": tokens, "targets": tokens})


def _paths(grad_accum: int):
    """Name-stack paths in the lowered step, as jax writes them into the
    operations' locations (the primitive's name at the end)."""
    return set(re.findall(r'loc\("([^"]*)"',
                          _lowered(grad_accum).as_text(debug_info=True)))


@pytest.fixture(scope="module")
def step_paths():
    """``grad_accum -> _paths`` of the step with the head the shape rule
    gives this size (full logits), lowered once a module."""
    return functools.cache(_paths)


def _composed(paths):
    """The lowered module keeps a scanned block's body as a function of its
    own, with paths relative to the call: join every outer path that ends in
    a call with every inner one, as XLA does when it inlines them."""
    outer = {p for p in paths if p.startswith("jit(train_step)")}
    inner = paths - outer
    calls = {p for p in outer if p.endswith("closed_call")}
    return outer | {f"{c}/{i}" for c in calls for i in inner}


#: scope -> regexes one of the step's paths must match, one a pass it runs
#: in. ``[^/]*`` inside ``jvp(...)``: jax folds the first name into the
#: transformation's mark (``jvp(loss)``).
FWD, BWD = r"/jvp\([^/]*\)", r"/transpose\(jvp\([^/]*\)\)"
SCOPES = {
    "optimizer": [r"^jit\(train_step\)/optimizer\b"],
    "grad_norm": [r"^jit\(train_step\)/grad_norm\b"],
    "cast_params": [r"/jvp\(cast_params\)", r"/transpose\(jvp\(cast_params\)\)"],
    "attention": [FWD + r".*/blocks/attention\b",
                  BWD + r".*/checkpoint/blocks/attention\b",
                  BWD + r".*/rematted_computation/blocks/attention\b"],
    "ffn": [FWD + r".*/blocks/ffn\b",
            BWD + r".*/checkpoint/blocks/ffn\b",
            BWD + r".*/rematted_computation/blocks/ffn\b"],
    "lm_head": [r"/jvp\(Transformer\)/lm_head/tok_emb\.attend",
                r"/transpose\(jvp\(Transformer\)\)/lm_head/tok_emb\.attend"],
    "loss": [r"/jvp\(loss\)", r"/transpose\(jvp\(loss\)\)"],
}


@pytest.mark.parametrize("grad_accum", [1, 2])
@pytest.mark.parametrize("scope", sorted(SCOPES))
def test_scope_under_the_pass_it_belongs_to(step_paths, scope, grad_accum):
    paths = _composed(step_paths(grad_accum))
    for pattern in SCOPES[scope]:
        assert any(re.search(pattern, p) for p in paths), (scope, pattern)
    if scope in ("optimizer", "grad_norm"):  # outside the differentiated fn
        own = [p for p in paths if re.search(SCOPES[scope][0], p)]
        assert not any("jvp(" in p for p in own), own


@pytest.mark.parametrize("grad_accum", [1, 2])
def test_accumulate_scope_only_where_the_step_accumulates(step_paths,
                                                          grad_accum):
    paths = step_paths(grad_accum)
    own = [p for p in paths if re.search(r"(^|/)accumulate(/|$)", p)]
    assert bool(own) == (grad_accum > 1)
    # the carry's adds and the final scale, not the microbatch's own step
    assert not any("jvp(" in p for p in own), own
    if grad_accum > 1:
        # the final scale, and the scan's carry (a body of its own)
        assert any(p.startswith("jit(train_step)/accumulate") for p in own)
        assert any(re.match(r"(.*/body/)?accumulate(/|$)", p) for p in own)


def test_fused_head_and_loss_are_one_scope(fused_head):
    fused_head()
    paths = _paths(1)
    assert any("lm_head_loss" in p for p in paths)
    assert not any(re.search(r"\blm_head/|jvp\(loss\)", p) for p in paths)


@pytest.mark.parametrize("grad_accum", [1, 2])
def test_fused_head_runs_in_the_forward_pass_and_recomputes_nothing(
        fused_head, grad_accum):
    """The one-pass head (a ``custom_vjp``): its three products a chunk are
    the forward rule's and read as ``jvp(lm_head_loss)``; the backward rule
    only scales the two finished gradients; nothing of it is recomputed.
    Read from the COMPILED step's ``op_name``s, which XLA composes whole."""
    fused_head(chunk_rows=16 * BATCH // grad_accum)  # 16 positions a chunk
    text = _lowered(grad_accum).compile().as_text()
    own = {p for p in re.findall(r'op_name="([^"]*)"', text)
           if "lm_head_loss" in p}
    products = [p for p in own if p.endswith("/dot_general")]
    assert products and all(
        re.search(r"/jvp\(lm_head_loss\)/while/body/", p) for p in products)
    assert not any("rematted_computation" in p or "checkpoint" in p
                   for p in own), own
    backward = {p.rsplit("/", 1)[1] for p in own
                if "transpose(jvp(lm_head_loss))" in p}
    assert backward <= {"mul", "convert_element_type"}, backward


@pytest.mark.parametrize("kernel,there", [
    ("flash_fwd", True), ("flash_bwd", True),
    # the two backward kernels a short head had until PR 60
    ("flash_bwd_dq|flash_bwd_dkv", False)])
def test_flash_kernels_carry_their_names(kernel, there):
    x = jnp.ones((1, 128, 2, 32), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=True).sum(),
        argnums=(0, 1, 2)))(x, x, x)
    assert bool(re.search(rf"\bname=({kernel})\b", str(jaxpr))) == there


def test_train_step_annotates_itself_under_a_profiler_session(tmp_path):
    from jax.profiler import ProfileData

    trainer = _trainer(1)
    state = trainer.init_state()
    batch = {"inputs": np.zeros((BATCH, SEQ), np.int32),
             "targets": np.zeros((BATCH, SEQ), np.int32)}
    state, metrics = trainer.train_step(state, batch)  # compiles
    float(metrics["loss"])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(2):
            state, metrics = trainer.train_step(state, batch)
            float(metrics["loss"])
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    events = [e for line in host.lines for e in line.events
              if e.name in ("train_step", "easydl/shard_batch",
                            "easydl/dispatch")]
    by_name = {}
    for event in events:
        by_name.setdefault(event.name, []).append(event)
    assert {k: len(v) for k, v in by_name.items()} == {
        "train_step": 2, "easydl/shard_batch": 2, "easydl/dispatch": 2}
    # the step number is the Trainer's own count of calls (one before the
    # session), and the two inner spans lie inside the step's
    steps = sorted(by_name["train_step"], key=lambda e: e.start_ns)
    assert [int(dict(e.stats)["step_num"]) for e in steps] == [1, 2]
    first = steps[0]
    for name in ("easydl/shard_batch", "easydl/dispatch"):
        inner = min(by_name[name], key=lambda e: e.start_ns)
        assert first.start_ns <= inner.start_ns
        assert inner.start_ns + inner.duration_ns <= \
            first.start_ns + first.duration_ns


def test_compile_watch_counts_trace_lower_and_backend():
    import time

    from easydl_tpu.utils.profiling import CompileWatch

    watch = CompileWatch()
    before = watch.totals()
    assert set(before) == {"trace_s", "lower_s", "backend_s",
                           "cache_retrieval_s", "cache_hits", "cache_misses"}
    inner = jax.jit(lambda x: jnp.where(x > 0, jnp.tanh(x), x) * 3.0)

    def outer(x):  # jitted functions traced inside another's trace
        for _ in range(20):
            x = inner(x) + jnp.take(x, jnp.arange(3), axis=0).sum()
        return x

    t0 = time.perf_counter()
    jax.jit(outer)(jnp.ones((7, 3))).block_until_ready()
    wall = time.perf_counter() - t0
    since = watch.since(before)
    assert since["trace_s"] > 0 and since["lower_s"] > 0
    assert since["backend_s"] > 0
    # covered time, not a sum over nested traces: the phases fit the wall
    assert since["trace_s"] + since["lower_s"] + since["backend_s"] <= wall
    assert isinstance(since["cache_misses"], int)
    assert watch.since(watch.totals()) == {
        "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
        "cache_retrieval_s": 0.0, "cache_hits": 0, "cache_misses": 0}
