"""The main path's kernels, compiled for a DESCRIBED v5e — no chip needed.

The TPU compiler is installed wherever jax[tpu] is, and compiles for a
topology that is described, not attached. That catches what interpret mode
cannot: a tile Mosaic refuses, too much VMEM, and — the reason the
multi-chip step had never compiled for the hardware it is named after — a
Mosaic kernel GSPMD is asked to partition. A compile that passes is not a
run; these guard the build, chip_smoke.py proves the run.

Real widths: [8, 1024, 16, 64] bf16, the per-microbatch attention shape of
GPT-2 345M at seq 1024, which the kernels take as [8, 1024, 1024]: the
model's own layout, two heads of 64 to a 128-lane block. (The whole-step
compiles take 10-16 s each and live in scripts/rehearse_tpu_compile.py, not
in tier-1; the two-layer stack at the bottom of this file stands for them.)
"""

from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.ops.attention import multihead_attention
from easydl_tpu.ops.flash_attention import flash_attention

SHAPE = (8, 1024, 16, 64)


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # no libtpu here, or it cannot describe a v5e
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A described-TPU executable is written to the persistent cache but
    cannot be read back without a chip (the next compile warns and
    recompiles) — turn the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _mosaic_calls(compiled) -> list:
    """Result shapes of the Mosaic kernels in a compiled program."""
    return [line.split(" = ", 1)[1].split(" custom-call(")[0]
            for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _loss(attend):
    return lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum()


@pytest.mark.parametrize("backward,n_kernels", [(False, 1), (True, 3)],
                         ids=["forward", "forward+backward"])
def test_flash_compiles_on_one_v5e_device(v5e_2x2, backward, n_kernels):
    x = jax.ShapeDtypeStruct(SHAPE, jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e_2x2[0]))
    fn = _loss(lambda q, k, v: flash_attention(q, k, v, causal=True))
    if backward:
        fn = jax.grad(fn, argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(x, x, x).compile()
    calls = _mosaic_calls(compiled)
    assert len(calls) == n_kernels, calls
    assert all("bf16[8,1024,1024]" in c for c in calls), calls  # [B, S, H·d]


@pytest.mark.parametrize("shape,blocks", [
    # [batch, seq, heads·head_dim] = [8, 1024, 1024]: gpt2-medium's call;
    # [4, 1024, 1600]: gpt2-xl's per shard under fsdp=4 — 25 heads, an odd
    # count: 13 lane blocks, the last half outside the array
    ((8, 1024, 16, 64), ((512, 512), (512, 512), (256, 256))),
    ((4, 1024, 25, 64), ((512, 512), (512, 512), (256, 256))),
    # the hybrid's, key/value heads repeated: the looped side
    ((2, 4096, 32, 64), ((512, 512), (512, 512), (512, 512))),
    # head_dim 128: one head a block, nothing to slice
    ((8, 1024, 8, 128), ((512, 512), (512, 512), (256, 256))),
    # Ouro's microbatches, 16 heads of 128 at 4,096: the looped side
    ((1, 4096, 16, 128), ((512, 512), (512, 512), (512, 512))),
    ((2, 4096, 16, 128), ((512, 512), (512, 512), (512, 512))),
], ids=["8x1024x1024", "4x1024x1600", "2x4096x2048", "head-dim-128",
        "ouro-1x4096x16x128", "ouro-2x4096x16x128"])
def test_chosen_blocks_compile_at_the_benchmark_shapes(v5e_2x2, shape, blocks):
    """The (block_q, block_k) the forward, dq and dk/dv kernels choose for
    the benchmark's calls, bf16 causal — a later change to the choice shows
    here — and that Mosaic takes the kernels at those sizes: three on the
    unrolled side, on the looped side the forward and ONE backward call
    whose three results are dq, dk and dv (its dq summed in a float32
    scratch of the whole sequence: interpret mode cannot say whether that
    fits)."""
    from easydl_tpu.ops.flash_attention import _unrolled, choose_blocks

    _, seq, _, _ = shape
    assert choose_blocks(seq, seq, True) == blocks
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e_2x2[0]))
    fn = jax.grad(_loss(lambda q, k, v: flash_attention(q, k, v, causal=True)),
                  argnums=(0, 1, 2))
    calls = _mosaic_calls(jax.jit(fn).lower(x, x, x).compile())
    mine = f"bf16[{shape[0]},{seq},{shape[2] * shape[3]}]"
    looped = not _unrolled(seq // blocks[2][0], seq // blocks[2][1])
    assert sorted(c.count(mine) for c in calls) == (
        [1, 3] if looped else [1, 1, 2]), calls


def test_the_kernels_compile_at_two_head_sizes(v5e_2x2):
    """JoyAI-LLM-Flash's call: ``[2, 8192, 32 x 192]`` q and k against ``[2,
    8192, 32 x 128]`` v, bf16 causal, the looped side. Two heads to a cell:
    384-lane blocks whose heads are lane slices at 0 and 192 (one and a half
    tiles: interpret mode cannot say whether Mosaic takes them), the whole
    sequence's k and v of a cell twice in VMEM (21 MB: over the compiler's
    own limit, so the calls name theirs; the one backward call holds q, O,
    dO and dq's block twice and dq's float32 sum: 55 MB, stated from its
    shapes); and q's rotation on rows of 192-lane heads."""
    from easydl_tpu.ops.flash_attention import choose_blocks
    from easydl_tpu.ops.rope import rope_rows, rope_tables

    one = SingleDeviceSharding(v5e_2x2[0])
    q = jax.ShapeDtypeStruct((2, 8192, 32, 192), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16, sharding=one)
    assert choose_blocks(8192, 8192, True) == ((512, 512),) * 3
    fn = jax.grad(_loss(lambda q, k, v: flash_attention(q, k, v, causal=True)),
                  argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(q, q, v).compile()
    calls = _mosaic_calls(compiled)
    assert len(calls) == 2, calls  # the forward; dq, dk, dv from ONE call
    assert sorted(c.count("bf16[2,8192,6144]") for c in calls) == [0, 2]
    assert sorted(c.count("bf16[2,8192,4096]") for c in calls) == [1, 1]
    text = compiled.as_text()
    assert "jvp(mla_fwd)" in text and "jvp(mla_bwd)" in text
    assert "mla_bwd_dq" not in text and "mla_bwd_dkv" not in text

    def rotate(x):
        tables = rope_tables(8192, 192, 32e6, 64, interleaved=True, last=True)
        return rope_rows(x, *tables, head_dim=192, rot=64, interleaved=True)

    rows = jax.ShapeDtypeStruct((2, 8192, 32 * 192), jnp.bfloat16,
                                sharding=one)
    rotated, = _mosaic_calls(jax.jit(rotate).lower(rows).compile())
    assert rotated.startswith("bf16[2,8192,6144]")


@pytest.mark.parametrize("form", ["rows", "rows_transposed_pair", "weights"])
@pytest.mark.parametrize("rows, groups, contract, cols", [
    (15488, 8, 2048, 2048),    # ZAYA1's cell: a piece over 8 experts of 2048
    (32768, 32, 2048, 512),    # Laguna's: 32 experts, the way in
    (32768, 32, 512, 2048),    # and out
], ids=["zaya-8x2048x2048", "laguna-32x2048x512", "laguna-32x512x2048"])
def test_grouped_products_compile_at_the_cells_shapes(v5e_2x2, rows, groups,
                                                     contract, cols, form):
    """The expert layer's three kernel forms at the two cells' shapes, bf16,
    with the tiles ``choose_tiles`` gives them: Mosaic takes each (the
    weight block resident, up to 34 MiB of VMEM for ZAYA1's weight
    gradient) as one kernel under its own name."""
    from easydl_tpu.ops import moe

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=SingleDeviceSharding(v5e_2x2[0]))

    x, y = arg(rows, contract), arg(rows, cols)
    w, sizes = arg(groups, contract, cols), arg(groups, dtype=jnp.int32)
    fn, args, name, result = {
        "rows": (lambda x, w, s: moe.grouped_rows([x], [w], s, False, False)[0],
                 (x, w, sizes), "grouped_rows", (rows, cols)),
        "rows_transposed_pair": (
            lambda y, w, s: moe.grouped_rows([y, y], [w, w], s, True, False)[0],
            (y, w, sizes), "grouped_rows_t", (rows, contract)),
        "weights": (lambda x, y, s: moe.grouped_weights(x, y, s, False),
                    (x, y, sizes), "grouped_weights", (groups, contract, cols)),
    }[form]
    compiled = jax.jit(fn).lower(*args).compile()
    call, = _mosaic_calls(compiled)
    assert call.startswith("bf16[" + ",".join(map(str, result)) + "]"), call
    assert f"/{name}/pallas_call" in compiled.as_text()


@pytest.mark.parametrize("spec,per_device", [
    (MeshSpec(dp=4), "bf16[2,1024,1024]"),         # 2 rows x 16 heads
    (MeshSpec(dp=2, tp=2), "bf16[4,1024,512]"),    # 4 rows x 8 heads
], ids=["dp=4", "dp=2,tp=2"])
def test_sharded_attention_compiles_per_shard(v5e_2x2, spec, per_device):
    """Under a mesh ``multihead_attention`` runs the kernel per shard
    (jax.shard_map over the context mesh): Mosaic calls at per-device
    shapes, and no all-gather of q/k/v in front of them."""
    mesh = build_mesh(spec, devices=v5e_2x2)
    x = jax.ShapeDtypeStruct(
        SHAPE, jnp.bfloat16,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None)))
    fn = jax.grad(_loss(lambda q, k, v: multihead_attention(
        q, k, v, causal=True, impl="flash")), argnums=(0, 1, 2))
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn).lower(x, x, x).compile()
    calls = _mosaic_calls(compiled)
    assert len(calls) == 3, calls
    assert all(per_device in c for c in calls), calls
    assert "all-gather" not in compiled.as_text()


@pytest.fixture(scope="module")
def named_texts(v5e_2x2):
    """Compiled text of forward + backward attention, on one described chip
    and under ``shard_map`` on the described 2x2, compiled as the program's
    entry points compile: one frame per location (``configure_compile_cache``
    sets the same) — XLA then writes each operation's whole name stack."""
    from jax.experimental.compilation_cache import compilation_cache

    cache = jax.config.jax_enable_compilation_cache
    frames = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    compilation_cache.reset_cache()
    try:
        one = jax.ShapeDtypeStruct(
            SHAPE, jnp.bfloat16, sharding=SingleDeviceSharding(v5e_2x2[0]))
        alone = jax.jit(jax.grad(_loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True)), argnums=(0, 1, 2))).lower(
                one, one, one).compile().as_text()
        mesh = build_mesh(MeshSpec(dp=4), devices=v5e_2x2)
        x = jax.ShapeDtypeStruct(
            SHAPE, jnp.bfloat16,
            sharding=NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None)))
        with jax.set_mesh(mesh):
            sharded = jax.jit(jax.grad(_loss(lambda q, k, v:
                multihead_attention(q, k, v, causal=True, impl="flash")),
                argnums=(0, 1, 2))).lower(x, x, x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        jax.config.update("jax_traceback_in_locations_limit", frames)
        compilation_cache.reset_cache()
    return {"one_chip": alone, "shard_map": sharded}


@pytest.mark.parametrize("where", ["one_chip", "shard_map"])
@pytest.mark.parametrize("kernel,passes", [
    ("flash_fwd", "jvp("), ("flash_bwd_dq", "transpose(jvp("),
    ("flash_bwd_dkv", "transpose(jvp(")])
def test_kernels_are_told_by_name_in_the_compiled_text(named_texts, where,
                                                       kernel, passes):
    """Each Mosaic call's own line names its kernel, on its path (op_name)
    and as the instruction's name — no result type has to be looked at."""
    lines = [line for line in named_texts[where].splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(lines) == 3
    mine = [line for line in lines
            if f"{kernel}/pallas_call" in line or f"({kernel})" in line]
    assert len(mine) == 1, lines
    line = mine[0]
    op_name = line.split('op_name="', 1)[1].split('"', 1)[0]
    assert kernel in op_name and passes in op_name
    assert kernel in line.split(" = ", 1)[0]       # the instruction's name
    if where == "shard_map":
        assert "shard_map" in op_name
    others = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} - {kernel}
    assert not any(o + "/" in op_name or f"({o})" in op_name for o in others)


def test_bare_kernel_under_a_mesh_is_refused(v5e_2x2):
    """Why the wrap exists: GSPMD cannot partition a Mosaic kernel. If this
    ever compiles, ``ops/attention._per_shard`` can go."""
    mesh = build_mesh(MeshSpec(dp=4), devices=v5e_2x2)
    x = jax.ShapeDtypeStruct(
        SHAPE, jnp.bfloat16,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None)))
    fn = _loss(lambda q, k, v: flash_attention(q, k, v, causal=True))
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(fn).lower(x, x, x).compile()


@pytest.mark.parametrize("frames", [10, 1])
def test_mosaic_payload_and_the_python_call_stack(v5e_2x2, frames):
    """The bytes of a Mosaic kernel inside its program — which the
    persistent compile cache keys on — hold MLIR locations. With jax's
    default they carry ten frames of the Python call stack, so the same step
    traced from two callers (a fresh run vs a resumed one) never shares a
    cache entry; ``configure_compile_cache`` cuts them to one frame, and
    then they are equal."""
    import re

    x = jax.ShapeDtypeStruct(SHAPE, jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e_2x2[0]))
    fn = _loss(lambda q, k, v: flash_attention(q, k, v, causal=True))

    def payload(f):
        return re.findall(r'backend_config = "((?:[^"\\]|\\.)*)"',
                          jax.jit(f).lower(x, x, x).as_text())

    def from_another_stack():
        return payload(lambda q, k, v: fn(q, k, v))

    before = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", frames)
    try:
        a, b = payload(fn), from_another_stack()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", before)
    assert len(a) == len(b) == 1
    assert (a == b) == (frames == 1)


@pytest.mark.parametrize("where,batch", [("one_chip", 8), ("shard_map", 2)])
def test_the_benchmarks_reader_still_tells_the_kernels_by_their_results(
        named_texts, where, batch):
    """``benchmark/lib/hlo.flash_calls`` (not this PR's to edit) tells the
    three calls by result types: the forward's ``(3-D array, float32 array
    whose last dimension is 1)``, dq's one 3-D array, dkv's two. On the
    model's layout it reads ``[8, 1024, 1024]`` as 8 batch·heads of
    head_dim 1024 — the same ``batch_heads x head_dim`` product, which is
    all ``flops.flash_causal_cost`` takes from them."""
    import importlib
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    calls = importlib.import_module("lib.hlo").flash_calls(named_texts[where])
    assert sorted(c["kind"] for c in calls) == ["dkv", "dq", "fwd"]
    for call in calls:
        assert (call["batch_heads"], call["seq"], call["head_dim"]) \
            == (batch, 1024, 1024), call
        assert {"fwd": "flash_fwd", "dq": "flash_bwd_dq",
                "dkv": "flash_bwd_dkv"}[call["kind"]] in call["name"]


def _attention_instructions(text: str) -> list:
    """``[(pass, opcode, result type, path inside attention)]`` of every
    top-level instruction under the ``attention`` scope of a compiled
    program."""
    import re

    found, fused = [], False
    for line in text.splitlines():
        header = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if header:  # a fusion's body is not a device operation of its own
            fused = "fused" in header.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\(?[^=]*?\)?) ([\w\-]+)\(", line)
        if fused or not m or "/attention/" not in line:
            continue
        path = line.split('op_name="', 1)[1].split('"', 1)[0]
        which = ("remat" if "rematted_computation" in path
                 else "bwd" if "transpose(jvp(" in path else "fwd")
        found.append((which, m.group(2), m.group(1),
                      path.split("/attention/", 1)[1]))
    return found


def _two_layer_gpt2(devices, remat_policy: str, spec: MeshSpec = MeshSpec()):
    """Compiled text of GPT-2 medium's stack cut to two layers (1024 wide,
    16 heads of 64, bf16, a 1,024-row head) under ``remat_policy``: loss and
    gradients of 8 sequences of 1,024 on one described chip, or under
    ``spec`` the whole train step of 16 (the ``Trainer`` places the
    parameters), one frame per location as the entry points compile."""
    import flax.linen as nn
    import optax

    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.lm import lm_bundle
    from easydl_tpu.models.transformer import TransformerConfig

    frames = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 1)
    try:
        bundle = lm_bundle(TransformerConfig(
            vocab=1024, d_model=1024, n_heads=16, n_layers=2, d_ff=4096,
            remat=True, remat_policy=remat_policy, attention_impl="flash",
            dtype="bfloat16"), "gpt2-medium-two-layers")
        if spec.size > 1:
            trainer = Trainer(
                init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
                optimizer=optax.sgd(1e-3),
                config=TrainConfig(global_batch=16, grad_accum=1),
                mesh=build_mesh(spec, devices=devices[:spec.size]))
            tokens = jax.ShapeDtypeStruct((16, 1024), jnp.int32)
            return trainer.step_fn.lower(
                trainer.abstract_state(),
                {"inputs": tokens, "targets": tokens}).compile().as_text()
        one = SingleDeviceSharding(devices[0])
        params = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one),
            jax.eval_shape(lambda: nn.unbox(
                bundle.init_fn(jax.random.PRNGKey(0)))))
        tokens = jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=one)
        return jax.jit(jax.grad(
            lambda p, batch: bundle.loss_fn(p, batch, jax.random.PRNGKey(0))[0]
        )).lower(params, {"inputs": tokens, "targets": tokens}
                 ).compile().as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", frames)


@pytest.fixture(scope="module")
def two_layer_texts(v5e_2x2):
    """``text(stack)``: compiled text of the two-layer GPT-2 stack
    (``_two_layer_gpt2``) under remat ``dots``, under ``full``, or under
    ``dots`` with ``fsdp=4`` on the described 2x2 (the kernels per shard,
    inside a ``shard_map``); each compiled once, when first asked for."""
    import functools

    stacks = {"dots": ("dots", MeshSpec()), "full": ("full", MeshSpec()),
              "dots-fsdp4": ("dots", MeshSpec(fsdp=4))}

    @functools.lru_cache(maxsize=None)
    def text(stack):
        return _two_layer_gpt2(v5e_2x2, *stacks[stack])

    return text


@pytest.fixture(scope="module")
def two_layer_stack(two_layer_texts):
    """The instructions under the ``attention`` scope of GPT-2 medium's
    stack cut to two layers (1024 wide, 16 heads of 64, remat ``dots``,
    bf16, a 1,024-row head), loss and gradients, compiled for one described
    chip: ``[(pass, opcode, result type, path inside attention)]`` of every
    top-level instruction."""
    return _attention_instructions(two_layer_texts("dots"))


def _big(result: str) -> bool:
    """Whether a result type holds an array as large as q: 8 x 1024 x 1024."""
    import math
    import re

    return any(math.prod(int(x) for x in dims.split(",") if x) >= 8 * 1024 * 1024
               for _, dims in re.findall(r"(\w+)\[([\d,]*)\]", result))


@pytest.mark.parametrize("stack,rows", [("dots", 8), ("full", 8),
                                        ("dots-fsdp4", 4)])
def test_kernels_in_the_stack_take_and_give_the_models_layout(
        two_layer_texts, stack, rows):
    """The Mosaic calls of the whole compiled text (the layers are a scan:
    one instruction a layer), on ``[batch, seq, heads·head_dim]`` rows: the
    forward, its second run under ``rematted_computation`` (remat ``dots``
    names the forward's ``out`` and does not keep it: ``ops/remat.py``), dq
    and dkv."""
    calls = [(which, result)
             for which, opcode, result, path in _attention_instructions(
                 two_layer_texts(stack)) if opcode == "custom-call"]
    assert sorted(which for which, _ in calls) == ["bwd", "bwd", "fwd", "remat"]
    for _, result in calls:
        assert f"bf16[{rows},1024,1024]{{2,1,0" in result, result


@pytest.mark.parametrize("stack,rows,kept", [
    ("dots", 8, True), ("full", 8, False), ("dots-fsdp4", 4, True)])
def test_dots_keeps_lse_as_rows_and_adds_no_bias_of_q_k_v_twice(
        two_layer_texts, stack, rows, kept):
    """What remat ``dots`` keeps by name, read in the compiled text. The
    layers' stack of ``lse`` as dense rows, ``f32[2, batch, 16, 1024]`` (named
    inside the differentiation rule, through the ``shard_map`` too; no
    ``[.., 1024, 1]`` column is stacked: 64 MB of lane padding a layer). And
    a projection's result AFTER its bias: nothing named after q, k or v's
    product or ``add`` stands under ``rematted_computation`` (kept before
    it, three fusions a layer added the biases again and wrote q, k, v a
    second time). Under ``full`` nothing is kept: the projections are
    recomputed, bias and all."""
    text = two_layer_texts(stack)
    assert (f"f32[2,{rows},16,1024]" in text) == kept
    assert f"f32[2,{rows},16,1024,1]" not in text
    again = [path for which, _, _, path in _attention_instructions(text)
             if which == "remat" and path.split("/")[0] in ("q", "k", "v")
             and path.endswith(("/add", "/dot_general"))]
    assert bool(again) == (not kept), again


@pytest.mark.parametrize("which", ["fwd", "remat", "bwd"])
def test_no_copy_between_the_projections_and_the_kernels(two_layer_stack, which):
    """No whole-array ``copy`` or ``transpose`` under the ``attention`` scope
    in the forward and in the recomputation: q, k, v leave their projections
    (matrix products, ``models/transformer._matrix_dot_general``) as
    ``[8, 1024, 1024]`` rows, the layout the kernels take, and O enters
    ``out`` as the kernel gave it. The backward keeps what XLA puts in
    front of the q, k, v WEIGHT-gradient products and nothing else: the
    weights are stored ``{1,3,2,0}`` (``[heads, kv, embed]`` physically), so
    that product wants dq, dk, dv transposed, whoever made them."""
    moved = [(opcode, result, path)
             for w, opcode, result, path in two_layer_stack
             if w == which and opcode in ("copy", "transpose") and _big(result)]
    if which != "bwd":
        assert not moved, moved
        return
    assert all(path in ("q/dot_general", "k/dot_general", "v/dot_general")
               for _, _, path in moved), moved
    assert len(moved) <= 6, moved


@pytest.mark.parametrize("program", ["medium_4x8", "worker_4x8"])
def test_the_medium_steps_fit_the_chip(v5e_2x2, program):
    """The memory gate on what remat ``dots`` keeps: the step of
    ``gpt2-medium.steady`` (4 x 8 x 1,024, AdamW) and the elastic worker's
    (``optax.adam``), compiled whole for the described chip, take 15.292 GiB
    of its 15.75 (the parent's 15.296; with the flash forward's ``out`` kept
    too 15.668) and at most 15.35, and hold the forward kernel twice in each
    copy of the step's body. The other cells' steps (XL's shard, the
    hybrid's, Ouro's: 13-35 s each) are programs of the same script with
    limits of their own: ``scripts/rehearse_tpu_compile.py``."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "rehearse_tpu_compile.py")
    spec = importlib.util.spec_from_file_location("rehearse_tpu_compile", path)
    rehearse = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rehearse)
    compiled, gib = rehearse.compile_program(program, v5e_2x2)
    assert gib <= rehearse.PROGRAMS[program][-1] == 15.35, gib
    calls = rehearse.mosaic_calls(compiled.as_text())
    # the step's body stands twice (the first microbatch, then the scan)
    assert sorted(name.strip("%").split(".")[0] for name, _ in calls) \
        == sorted(["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd",
                   "flash_fwd"] * 2), calls


@pytest.fixture(scope="module")
def two_layer_rotary_stack(v5e_2x2):
    """As ``two_layer_stack``, for Ouro's description cut to two layers and
    two passes (2048 wide, 16 heads of 128, rotary, sandwich norms, remat
    ``full``, bf16, one 4,096-token sequence, a 1,024-row head): every
    top-level instruction under ``attention``."""
    import flax.linen as nn

    from easydl_tpu.models.ouro import make_ouro

    frames = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 1)
    try:
        one = SingleDeviceSharding(v5e_2x2[0])
        bundle = make_ouro(
            size="2.6b", seq_len=4096, vocab=1024, dtype="bfloat16",
            remat=True, remat_policy="full", attention_impl="flash",
            layer_types=["full_attention"] * 2, total_ut_steps=2)
        params = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one),
            jax.eval_shape(lambda: nn.unbox(
                bundle.init_fn(jax.random.PRNGKey(0)))))
        tokens = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one)
        text = jax.jit(jax.grad(
            lambda p, batch: bundle.loss_fn(p, batch, jax.random.PRNGKey(0))[0]
        )).lower(params, {"inputs": tokens, "targets": tokens}
                 ).compile().as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", frames)
    return _attention_instructions(text)


@pytest.mark.parametrize("which", ["fwd", "remat"])
def test_rotary_puts_no_copy_between_the_projections_and_the_kernels(
        two_layer_rotary_stack, which):
    """q and k go from their projections through the rotary kernel to the
    flash kernels as ``[1, 4096, 2048]`` rows: in the forward and in the
    recomputation no whole-array ``copy`` or ``transpose`` stands under
    ``attention`` (as XLA operations on half-head slices the rotation
    brought four float32 copies a layer back: PERF.md section 6, PR 29),
    and the Mosaic calls there are the flash forward and two rotations."""
    import math
    import re

    def big(result):
        return any(math.prod(int(x) for x in dims.split(",") if x)
                   >= 4096 * 2048
                   for _, dims in re.findall(r"(\w+)\[([\d,]*)\]", result))

    mine = [(opcode, result, path)
            for w, opcode, result, path in two_layer_rotary_stack
            if w == which]
    moved = [x for x in mine if x[0] in ("copy", "transpose") and big(x[1])]
    assert not moved, moved
    kernels = sorted(path.split("/")[-2] for opcode, _, path in mine
                     if opcode == "custom-call")
    assert kernels == ["flash_fwd", "rope_fwd", "rope_fwd"], kernels
    for opcode, result, path in mine:
        if opcode == "custom-call":
            assert "bf16[1,4096,2048]{2,1,0" in result, result


# ------------------------------------------------- what remat full keeps
def _scanned_run(devices, factory: str, **description) -> list:
    """``_attention_instructions`` of loss and gradients of a two-layer
    scanned run of ``factory``'s description at the cell's widths (2 x
    8,192 tokens, bf16, remat ``full``, a 1,024-row head; the expert layer's
    kernels compiled as on the chip), for one described chip."""
    import flax.linen as nn

    from easydl_tpu.models.registry import get_model
    from easydl_tpu.ops import moe

    frames = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 1)
    on_tpu, moe._on_tpu = moe._on_tpu, lambda: True
    try:
        one = SingleDeviceSharding(devices[0])
        bundle = get_model(
            factory, seq_len=8192, vocab=1024, dtype="bfloat16", remat=True,
            remat_policy="full", attention_impl="flash", **description)
        params = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one),
            jax.eval_shape(lambda: nn.unbox(
                bundle.init_fn(jax.random.PRNGKey(0)))))
        tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one)
        text = jax.jit(jax.grad(
            lambda p, batch: bundle.loss_fn(p, batch, jax.random.PRNGKey(0))[0]
        )).lower(params, {"inputs": tokens, "targets": tokens}
                 ).compile().as_text()
    finally:
        moe._on_tpu = on_tpu
        jax.config.update("jax_traceback_in_locations_limit", frames)
    return _attention_instructions(text)


@pytest.mark.parametrize("factory,description,forward,rows,reader", [
    ("joyai", dict(size="llm-flash", layer_types=["sparse"] * 2, mtp=False,
                   experts_held=(0, 16)), "mla_fwd", 4096, "mla_out"),
    ("zaya", dict(size="8b", layer_types=["hybrid"] * 2,
                  experts_held=(0, 8)), "flash_fwd", 1024, "cca_up"),
], ids=["joyai-llm-flash", "zaya1-8b"])
def test_full_keeps_the_flash_forwards_results_of_a_dear_call(
        v5e_2x2, factory, description, forward, rows, reader):
    """A scanned run at the cell's shape under remat ``full``
    (``ops/remat.py``'s rule picks the call: 10,084 and 8,067 FLOP a byte):
    the forward kernel stands once, in the forward pass, and not again under
    ``rematted_computation``; the ONE backward call (three results: the
    looped side) reads the kept ``out`` and ``lse``, and no ``*_bwd_dq``
    stands beside it.
    And nothing moves the kept ``out`` between the layers' stack and the
    projection that reads it (``mla_out``, ``cca_up``): no ``copy`` or
    ``transpose`` of an ``out``-sized array in the recomputation (PR 30: XLA
    wrote such a slice twice and transposed it)."""
    found = _scanned_run(v5e_2x2, factory, **description)
    back = forward.replace("fwd", "bwd")
    calls = sorted((which, path.split("/")[-2], result.count("bf16[2,8192,"))
                   for which, opcode, result, path in found
                   if opcode == "custom-call"
                   and path.split("/")[-2].startswith((forward, back)))
    assert calls == [("bwd", back, 3), ("fwd", forward, 1)], calls
    moved = [(opcode, result, path) for which, opcode, result, path in found
             if which == "remat" and opcode in ("copy", "transpose")
             and f"[2,8192,{rows}]" in result]
    assert not [m for m in moved if reader in m[2]
                or "multihead_attention" in m[2]], moved


# ---------------------------------------------------------------- Laguna
@pytest.mark.parametrize("heads,window,rot,names", [
    (64, 512, None, ("swa_fwd", "swa_bwd_dq", "swa_bwd_dkv")),
    (48, None, 64, ("flash_fwd", "flash_bwd")),
], ids=["window-512-64-heads", "full-48-heads-partial-yarn"])
def test_lagunas_attention_kinds_compile_at_8k(v5e_2x2, heads, window, rot,
                                               names):
    """Laguna-XS.2's two attention kinds at the cell's shape, forward and
    backward: 64 / 48 query heads over 8 key/value heads of 128 at 8,192,
    the window layers' kernels (the band path: three) under names of their
    own, the full layers' backward ONE call (the looped side), the rotary
    kernel on 64 of a head's 128 lanes — within the kernels' VMEM."""
    from easydl_tpu.ops.rope import rope_tables

    one = SingleDeviceSharding(v5e_2x2[0])
    q = jax.ShapeDtypeStruct((1, 8192, heads, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        rope = rope_tables(8192, 128, 10000.0, rot)
        return multihead_attention(
            q, k, v, causal=True, impl="flash", rope=rope, rotary_dim=rot,
            window=window).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in names + ("rope_fwd", "rope_bwd"):
        assert any(f"/{name}/" in line for line in calls), name
    other = ("flash_fwd", "swa_fwd")[window is None]
    assert not any(f"/{other}/" in line for line in calls)
    assert not any("/flash_bwd_dq/" in line for line in calls)


def test_the_expert_layers_kernels_compile_at_the_cells_size(v5e_2x2,
                                                            monkeypatch):
    """The routed experts at the cell's size (16,384 tokens, top-8, 32 of
    256 experts of 512 held: pieces of 32,768 rows, a quarter of the bound),
    value and gradients. The grouped products are Mosaic kernels of the
    compiler's own — three forward, nine backward (the piece's products made
    again, and six of the gradients'), for the first piece and again in the
    loop over the others, less what the compiler shares between the first
    piece's two passes — and the sums back to the tokens are ours,
    ``rows_to_tokens``, one a piece and pass; nothing has the bound's
    131,072 rows."""
    from easydl_tpu.ops import moe

    # jax.devices() is the CPU here: the kernel as the chip compiles it
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(v5e_2x2[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(h, weights, w_gate, w_up, w_down, chosen):
        y, _ = moe.routed_experts(h, chosen, weights, w_gate, w_up, w_down,
                                  0, 256)
        return y.astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))
                       ).lower(
        s((16384, 2048)), s((16384, 8), jnp.float32), s((32, 2048, 512)),
        s((32, 2048, 512)), s((32, 512, 2048)),
        s((16384, 8), jnp.int32)).compile()
    calls = _mosaic_calls(compiled)
    sums = [c for c in calls if c.startswith("f32[16384,2048]")]
    products = [c for c in calls if c.startswith("bf16[")]
    assert len(sums) == 4 and 18 <= len(products) <= 24, calls
    assert all(c.startswith(("bf16[32768,", "bf16[32,")) for c in products)
    assert compiled.as_text().count("rows_to_tokens/pallas_call") >= 4
    assert "131072" not in "".join(calls)
