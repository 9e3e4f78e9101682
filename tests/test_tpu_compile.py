"""The main path's kernels, compiled for a DESCRIBED v5e — no chip needed.

The TPU compiler is installed wherever jax[tpu] is, and compiles for a
topology that is described, not attached. That catches what interpret mode
cannot: a tile Mosaic refuses, too much VMEM, and — the reason the
multi-chip step had never compiled for the hardware it is named after — a
Mosaic kernel GSPMD is asked to partition. A compile that passes is not a
run; these guard the build, chip_smoke.py proves the run.

Real widths: [8, 1024, 16, 64] bf16, the per-microbatch attention shape of
GPT-2 345M at seq 1024, which the kernels take as [8, 1024, 1024]: the
model's own layout, two heads of 64 to a 128-lane block. This file holds
the kernels alone (attention's, the rotary's, the expert layer's); the
two-layer stacks, the scanned runs and the two medium steps' memory gate are
``tests/test_tpu_compile_stack.py``. (The other whole-step compiles take
10-75 s each and live in scripts/rehearse_tpu_compile.py, not in tier-1.)
The described chip (``v5e_2x2``) and ``no_persistent_cache`` are
``conftest.py``'s.
"""

from __future__ import annotations

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from easydl_tpu.core.mesh import MeshSpec, build_mesh
from easydl_tpu.ops.attention import multihead_attention
from easydl_tpu.ops.flash_attention import flash_attention

SHAPE = (8, 1024, 16, 64)


pytestmark = pytest.mark.usefixtures("no_persistent_cache")


def _mosaic_calls(compiled, kernel: str = "") -> list:
    """Result shapes of the Mosaic kernels in a compiled program: those
    whose name starts with ``kernel``, or all but the expert layer's empty
    ``unwritten`` (``ops/moe.py``: an array for a chunk loop to write into,
    no work)."""
    return [line.split(" = ", 1)[1].split(" custom-call(")[0]
            for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and ("%unwritten" in line.split(" = ")[0]) == (
                kernel == "unwritten")
            and "%" + kernel in line.split(" = ")[0]]


def _operand_shapes(text: str, kernel: str) -> list:
    """The operands' shapes (``bf16[1,16384,512]``) of the compiled text's
    one Mosaic call under the name ``kernel``."""
    shape = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.-]+) = (\w+\[[\d,]*\])",
                            text, re.M))
    line, = (line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and f"/{kernel}/" in line)
    operands = re.search(r"custom-call\(([^)]*)\)", line).group(1)
    return [shape[name] for name in re.findall(r"%[\w.-]+", operands)]


def _loss(attend):
    return lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum()


@pytest.mark.parametrize("backward,n_kernels", [(False, 1), (True, 2)],
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
    # — ONE block pair of 1,024 x 1,024 a grid cell since PR 60
    ((8, 1024, 16, 64), ((1024, 1024),) * 3),
    ((4, 1024, 25, 64), ((1024, 1024),) * 3),
    # gpt2-medium.reshape-resume's per shard under fsdp=2,tp=2: 8 heads
    ((16, 1024, 8, 64), ((1024, 1024),) * 3),
    # the hybrid's, key/value heads repeated
    ((2, 4096, 32, 64), ((512, 512), (512, 512), (512, 512))),
    # head_dim 128: one head a block, nothing to slice
    ((8, 1024, 8, 128), ((1024, 1024),) * 3),
    # a 2,048-long head: four pairs of 1,024
    ((2, 2048, 16, 128), ((1024, 1024),) * 3),
    # Ouro's microbatches, 16 heads of 128 at 4,096
    ((1, 4096, 16, 128), ((512, 512), (512, 512), (512, 512))),
    ((2, 4096, 16, 128), ((512, 512), (512, 512), (512, 512))),
], ids=["8x1024x1024", "4x1024x1600", "16x1024x512", "2x4096x2048",
        "head-dim-128", "2x2048x2048", "ouro-1x4096x16x128",
        "ouro-2x4096x16x128"])
def test_chosen_blocks_compile_at_the_benchmark_shapes(v5e_2x2, shape, blocks):
    """The (block_q, block_k) the forward and the backward choose for the
    benchmark's calls, bf16 causal — a later change to the choice shows
    here — and that Mosaic takes the kernels at those sizes: the forward and
    ONE backward call whose three results are dq, dk and dv (its dq summed
    in a float32 scratch of the whole sequence: interpret mode cannot say
    whether that fits), at gpt2-medium's and gpt2-xl's 1,024 as at 4,096."""
    from easydl_tpu.ops.flash_attention import choose_blocks

    _, seq, _, _ = shape
    assert choose_blocks(seq, seq, True) == blocks
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e_2x2[0]))
    fn = jax.grad(_loss(lambda q, k, v: flash_attention(q, k, v, causal=True)),
                  argnums=(0, 1, 2))
    calls = _mosaic_calls(jax.jit(fn).lower(x, x, x).compile())
    mine = f"bf16[{shape[0]},{seq},{shape[2] * shape[3]}]"
    assert sorted(c.count(mine) for c in calls) == [1, 3], calls


def test_the_kernels_compile_at_two_head_sizes(v5e_2x2):
    """JoyAI-LLM-Flash's call: ``[2, 8192, 32 x 192]`` q and k against ``[2,
    8192, 32 x 128]`` v, bf16 causal, the looped side. Two heads to a cell:
    384-lane blocks whose heads are lane slices at 0 and 192 (one and a half
    tiles: interpret mode cannot say whether Mosaic takes them), the whole
    sequence's k and v of a cell twice in VMEM (21 MB: over the compiler's
    own limit, so the calls name theirs; the one backward call holds q, O,
    dO and dq's block twice and the float32 sums of dq, dk and dv: 56 MB,
    stated from its shapes); and q's rotation on rows of 192-lane heads."""
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
    # the looped calls are jits of their own (a body in tiles is traced
    # once a shape, not once a use): a call's name is a scope inside its jit
    assert "jvp(jit(_fwd_call))/mla_fwd/" in text
    assert "transpose(jvp(jit(_bwd_call)))/mla_bwd/" in text
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
    (65536, 16, 2304, 896),    # Mellum 2's: 16 experts, seven lane tiles in
    (65536, 16, 896, 2304),    # and out
], ids=["zaya-8x2048x2048", "laguna-32x2048x512", "laguna-32x512x2048",
        "mellum-16x2304x896", "mellum-16x896x2304"])
def test_grouped_products_compile_at_the_cells_shapes(v5e_2x2, rows, groups,
                                                     contract, cols, form):
    """The expert layer's three kernel forms at three cells' shapes, bf16,
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
    assert len(calls) == 2, calls  # the forward; dq, dk, dv from ONE call
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
    ("flash_fwd", "jvp("), ("flash_bwd", "transpose(jvp(")])
def test_kernels_are_told_by_name_in_the_compiled_text(named_texts, where,
                                                       kernel, passes):
    """Each Mosaic call's own line names its kernel, on its path (op_name)
    and as the instruction's name — no result type has to be looked at."""
    lines = [line for line in named_texts[where].splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(lines) == 2
    mine = [line for line in lines
            if f"{kernel}/pallas_call" in line or f"({kernel})" in line]
    assert len(mine) == 1, lines
    line = mine[0]
    op_name = line.split('op_name="', 1)[1].split('"', 1)[0]
    assert kernel in op_name and passes in op_name
    assert kernel in line.split(" = ", 1)[0]       # the instruction's name
    if where == "shard_map":
        assert "shard_map" in op_name
    others = {"flash_fwd", "flash_bwd"} - {kernel}
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
    then they are equal. (The kernels' calls are ``jax.jit``s of their own,
    which remember a shape's trace: each caller here starts with none, as a
    fresh process does.)"""
    import re

    x = jax.ShapeDtypeStruct(SHAPE, jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e_2x2[0]))
    fn = _loss(lambda q, k, v: flash_attention(q, k, v, causal=True))

    def payload(f):
        jax.clear_caches()
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
    """``benchmark/lib/hlo.flash_calls`` tells the two calls by the NAME
    the program gives each (its end is the kind: ``fwd``, ``bwd``)
    and lists a call where its results are as many as that kind gives (the
    test's name is older than that). The sizes are the first result's as
    they stand: on the model's layout ``[8, 1024, 1024]`` reads as 8
    batch·heads of head_dim 1024 — the same ``batch_heads x head_dim``
    product, which is all ``flops.flash_causal_cost`` takes from them."""
    import importlib
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    calls = importlib.import_module("lib.hlo").flash_calls(named_texts[where])
    assert sorted(c["kind"] for c in calls) == ["bwd", "fwd"]
    for call in calls:
        assert (call["batch_heads"], call["seq"], call["head_dim"]) \
            == (batch, 1024, 1024), call
        assert {"fwd": "flash_fwd",
                "bwd": "flash_bwd"}[call["kind"]] in call["name"]


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


# -------------------------------------------------------------------- SDAR
def test_sdars_attention_compiles_at_16k_rows_under_the_block_mask(v5e_2x2):
    """SDAR's attention at the cell's shape, forward and backward: one
    sequence's ``[noised || clean]`` 16,384 rows, 32 query heads over 4
    key/value heads of 128, under block diffusion's mask at a block length
    of 4 — the looped kernels under the mask's names, the backward ONE call,
    the rotary kernel over tables that repeat the positions; within the
    kernels' VMEM with 16,384 rows of k and v resident."""
    from easydl_tpu.ops.flash_attention import BlockDiffusion, choose_blocks
    from easydl_tpu.ops.rope import rope_tables

    mask = BlockDiffusion(4, 8192)
    assert choose_blocks(16384, 16384, False, mask=mask) == ((512, 512),) * 3
    assert mask.block_pairs(512) == (288, 1024)
    one = SingleDeviceSharding(v5e_2x2[0])
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        rope = tuple(jnp.concatenate([table, table])
                     for table in rope_tables(8192, 128, 1e6))
        return multihead_attention(
            q, k, v, impl="flash", rope=rope, mask=mask
        ).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in ("bd_fwd", "bd_bwd", "rope_fwd", "rope_bwd"):
        assert any(f"/{name}/" in line for line in calls), name
    for other in ("flash_fwd", "flash_bwd", "bd_bwd_dq"):
        assert not any(f"/{other}/" in line for line in calls), other
    # k and v reach both kernels at the FOUR key/value heads (read by index:
    # no array of either at the query's 32 heads stands in front of them);
    # the backward's other three are q, O and dO
    wide, narrow = "bf16[1,16384,4096]", "bf16[1,16384,512]"
    assert _operand_shapes(text, "bd_fwd") == [wide, narrow, narrow]
    assert _operand_shapes(text, "bd_bwd")[:5] == [wide, narrow, narrow,
                                                   wide, wide]


def test_keyes_indexed_attention_compiles_at_16k_rows(v5e_2x2):
    """Keye's attention at the cell's shape, forward and backward: 16,384
    rows, 32 query heads over 4 key/value heads of 128 behind an index of 16
    heads of 64, top-2,048 — the index's two kernels (the 256-query cell's
    ``[16384, 256]`` scratch of ordered scores; the loss with its three
    gradients), the looped kernels under the packed selection's names, the
    norm inside the rotary kernel; no ``[16384, 16384]`` array in HBM."""
    from easydl_tpu.ops.attention import indexed_attention
    from easydl_tpu.ops.rope import rope_tables

    one = SingleDeviceSharding(v5e_2x2[0])

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def loss(q, k, v, a, b, w, gain):
        out, own, _ = indexed_attention(
            q, k, v, a, b, w, topk=2048, impl="flash",
            rope=rope_tables(16384, 128, 1e7), qk_norm=(gain, gain, 1e-6))
        return out.astype(jnp.float32).sum() + own

    text = jax.jit(jax.grad(loss, argnums=range(6))).lower(
        shape(1, 16384, 32, 128), shape(1, 16384, 4, 128),
        shape(1, 16384, 4, 128), shape(1, 16384, 16, 64), shape(1, 16384, 64),
        shape(1, 16384, 16, dtype=jnp.float32),
        shape(128, dtype=jnp.float32)).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in ("index_select", "index_kl", "dsa_fwd", "dsa_bwd",
                 "rope_norm_fwd", "rope_norm_bwd"):
        assert sum(f"/{name}/" in line for line in calls) >= 1, name
    for other in ("flash_fwd", "flash_bwd"):
        assert not any(f"/{other}/" in line for line in calls), other
    # the selection reaches both attention kernels a bit a pair, a Q-block a
    # leading index; nothing [L, L] and wider than a bit stands anywhere
    assert "s32[1,32,512,512]" in _operand_shapes(text, "dsa_fwd")
    assert "s32[1,32,512,512]" in _operand_shapes(text, "dsa_bwd")
    assert not re.search(r"(f32|bf16|s8|pred)\[(1,)?(\d+,)?16384,16384\]",
                         text)


def test_the_delta_rules_kernels_compile_at_the_cells_shape(v5e_2x2):
    """Kimi Linear's recurrence at the cell's shape, forward and backward:
    16,384 tokens, 32 heads of 128 x 128 state in chunks of 128 — ``kda_fwd``
    (the differentiated one, which keeps each chunk's entry state) and
    ``kda_bwd`` on the turned layout the convolutions give, rank 4 (so
    ``lib/hlo.flash_calls`` never lists them); no ``[L, L]`` array and no
    state a token in HBM, the states float32."""
    from easydl_tpu.ops.kda import kda_kernels

    one = SingleDeviceSharding(v5e_2x2[0])

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def loss(q, k, v, g, beta):
        o, last = kda_kernels(q, k, v, g, beta, chunk=128)
        return o.astype(jnp.float32).sum() + last.sum()

    rows = shape(1, 16384, 32, 128)
    text = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        rows, rows, rows, shape(1, 16384, 32, 128, dtype=jnp.float32),
        shape(1, 16384, 32, dtype=jnp.float32)).compile().as_text()
    turned, decays = "bf16[1,32,16384,128]", "f32[1,32,16384,128]"
    steps = "f32[1,128,16,2,128]"
    assert _operand_shapes(text, "kda_fwd") == [turned] * 3 + [decays, steps]
    assert _operand_shapes(text, "kda_bwd") == [
        turned] * 3 + [decays, steps, "f32[1,32,128,128,128]", turned,
                       "f32[1,32,128,128]"]
    assert not re.search(r"\[(1,)?(\d+,)?16384,16384\]", text)
    assert not re.search(r"\[(1,)?(32,)?16384,128,128\]", text)


def test_sdars_attention_norms_q_and_k_inside_the_rotary_kernel(v5e_2x2):
    """As the test above, with the kind's gains handed in
    (``multihead_attention(qk_norm=)``): at 16,384 rows of 32 | 4 heads of
    128 the per-head RMSNorm on q and k runs inside the rotary kernel,
    forward and backward — ``rope_norm_fwd`` / ``rope_norm_bwd`` within
    Mosaic's VMEM, the backward's three arrays of a block at half the
    forward's rows — no bare rotary call and no operation under
    ``qk_rmsnorm`` is left, and the gains' gradients are there."""
    from easydl_tpu.ops.flash_attention import BlockDiffusion
    from easydl_tpu.ops.rope import rope_tables

    mask = BlockDiffusion(4, 8192)
    one = SingleDeviceSharding(v5e_2x2[0])
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16, sharding=one)
    gain = jax.ShapeDtypeStruct((128,), jnp.float32, sharding=one)

    def loss(q, k, v, q_gain, k_gain):
        rope = tuple(jnp.concatenate([table, table])
                     for table in rope_tables(8192, 128, 1e6))
        return multihead_attention(
            q, k, v, impl="flash", rope=rope, mask=mask,
            qk_norm=(q_gain, k_gain, 1e-6)).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        q, kv, kv, gain, gain).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name, count in (("bd_fwd", 1), ("bd_bwd", 1), ("rope_norm_fwd", 2),
                        ("rope_norm_bwd", 2)):
        assert sum(f"/{name}/" in line for line in calls) == count, name
    for other in ("rope_fwd", "rope_bwd"):
        assert not any(f"/{other}/" in line for line in calls), other
    assert "qk_rmsnorm" not in text


# ---------------------------------------------------------------- Mellum 2
@pytest.mark.parametrize("window,yarn,names", [
    (1024, None, ("swa_fwd", "swa_bwd_dq", "swa_bwd_dkv")),
    (None, dict(factor=16.0, original_max_position_embeddings=8192,
                beta_fast=32.0, beta_slow=1.0,
                attention_factor=1.2772588722239782),
     ("flash_fwd", "flash_bwd")),
], ids=["window-1024", "full-whole-head-yarn"])
def test_mellums_attention_kinds_compile_at_8k(v5e_2x2, window, yarn, names):
    """Mellum 2's two attention kinds at the cell's shape, forward and
    backward: 32 query heads over 4 key/value heads of 128 at 8,192. The
    window of 1,024 is wider than a block and takes the BAND path all the
    same — three kernels under the window's names, cells of 2,048 rows
    beside a neighbour of 1,024, within the kernels' VMEM — where it fell
    through to the looped ``swa_bwd`` before; the full layer's backward is
    ONE call, its rotary the kernel over the whole head."""
    from easydl_tpu.ops.flash_attention import Band, choose_blocks
    from easydl_tpu.ops.rope import rope_tables

    assert choose_blocks(8192, 8192, True, window=1024) == (
        Band(rows=2048, sub=256, reach=1024),) * 3
    one = SingleDeviceSharding(v5e_2x2[0])
    q = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((2, 8192, 4, 128), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        rope = rope_tables(8192, 128, 500000.0, None, yarn)
        return multihead_attention(
            q, k, v, causal=True, impl="flash", rope=rope,
            window=window).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in names + ("rope_fwd", "rope_bwd"):
        assert any(f"/{name}/" in line for line in calls), name
    other = ("flash_fwd", "swa_fwd")[window is None]
    assert not any(f"/{other}/" in line for line in calls)
    assert not any("/swa_bwd/" in line for line in calls)
    assert not any("/flash_bwd_dq/" in line for line in calls)


def test_the_expert_layers_kernels_compile_at_the_cells_size(v5e_2x2,
                                                            described_tpu):
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
    # (described_tpu)
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
    # the three HBM-to-HBM gathers a piece (the rows by token, forward and
    # backward, and the cotangent's rows) and the two of the choices'
    # weights write into unwritten pieces, in chunk loops of nine; the
    # first piece's and the loop's
    unwritten = _mosaic_calls(compiled, "unwritten")
    assert sorted(c.split("{")[0] for c in unwritten) == [
        "bf16[32768,2048]"] * 6 + ["f32[32768]"] * 4, unwritten
    assert sum(" while(" in line and "live_rows/while\"" in line
               for line in compiled.as_text().splitlines()) == 10
    assert moe.chunk_rows(32768) == 3712


def test_ungated_experts_of_a_ragged_width_compile_at_the_cells_size(
        v5e_2x2, described_tpu):
    """Nemotron 3 Nano's routed experts at its cell's size (16,384 tokens,
    top-6, 8 of 128 ungated relu2 experts of 1,856 held: pieces of 12,288
    rows), value and gradients: 1,856 columns are 14.5 lane tiles and the
    first expert width that is no multiple of 128 — the grouped kernels take
    it as two column blocks of 1,024 (the second holds 832) one way and as a
    contraction of 1,856 whole the other; two products forward and five
    backward a piece where a SwiGLU expert has three and eight."""
    from easydl_tpu.ops import moe

    one = SingleDeviceSharding(v5e_2x2[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(h, weights, w_up, w_down, chosen):
        y, _ = moe.routed_experts(h, chosen, weights, None, w_up, w_down,
                                  0, 128)
        return y.astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(
        s((16384, 2688)), s((16384, 6), jnp.float32), s((8, 2688, 1856)),
        s((8, 1856, 2688)), s((16384, 6), jnp.int32)).compile()
    calls = _mosaic_calls(compiled)
    products = [c for c in calls if c.startswith("bf16[")]
    # a piece's two forward and five backward products (the up product made
    # again is the forward's where the compiler sees them equal), for the
    # first piece and again in the loop over the others
    assert 10 <= len(products) <= 14, calls
    assert all(c.startswith(("bf16[12288,", "bf16[8,")) for c in products)
    assert any(c.startswith("bf16[12288,1856]") for c in products)
    assert any(c.startswith("bf16[8,2688,1856]") for c in products)
    assert len(_mosaic_calls(compiled, "unwritten")) == 10
    assert moe.choose_tiles(12288, 8, 2688, 1856, 2) == (128, 1024)


def test_a_causal_forward_at_16384_rows_states_its_vmem(v5e_2x2, monkeypatch):
    """PERF.md section 7, SDAR's (h): a ``causal`` forward at 16,384 rows of
    heads of 128 holds 17.3 MB of k and v, two buffers each, and failed the
    compiler's own 16 MB; the looped forward now states what it holds where
    that passes it, and a call that fitted names no parameters, as before."""
    from easydl_tpu.ops import flash_attention as fa

    stated = []
    params = fa.pltpu.CompilerParams
    monkeypatch.setattr(fa.pltpu, "CompilerParams", lambda **kw: (
        stated.append(kw), params(**kw))[1])

    def forward(batch, seq):
        x = jax.ShapeDtypeStruct((batch, seq, 4, 128), jnp.bfloat16,
                                 sharding=SingleDeviceSharding(v5e_2x2[0]))
        return jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True)).lower(x, x, x)

    calls = _mosaic_calls(forward(1, 16384).compile(), "flash_fwd")
    assert len(calls) == 1 and "bf16[1,16384,512]" in calls[0], calls
    (limit,) = stated
    assert limit["vmem_limit_bytes"] > (17 << 20) + fa._DEFAULT_VMEM // 2
    forward(2, 8192)
    assert len(stated) == 1  # 9.7 MB held: the compiler's own limit


@pytest.mark.parametrize("window,names", [
    (None, {"diff_fwd": 1, "diff_bwd": 1}),
    (512, {"swa_fwd": 1, "swa_bwd_dq": 1, "swa_bwd_dkv": 1}),
], ids=["whole", "window-512"])
def test_differential_attention_compiles_at_64_against_128(v5e_2x2, window,
                                                           names):
    """Score heads 64 deep against values 128 wide at 16,384 rows: the
    looped forward and the one-kernel backward (``diff_*``), and under the
    window the band path's three kernels with v, O and dO twice as wide as q
    and k."""
    def of(heads, dim):
        return jax.ShapeDtypeStruct((1, 16384, heads, dim), jnp.bfloat16,
                                    sharding=SingleDeviceSharding(v5e_2x2[0]))

    fn = jax.grad(_loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window)), argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(of(8, 64), of(8, 64), of(8, 128)).compile()
    instructions = [line.split(" = ")[0] for line in
                    compiled.as_text().splitlines()
                    if 'custom_call_target="tpu_custom_call"' in line]
    assert len(instructions) == len(names), instructions
    for name in names:  # `%jvp_swa_fwd_.1`, `%diff_bwd.2`
        assert sum(bool(re.search(rf"(^|_|%){name}[_.]", i))
                   for i in instructions) == 1, (name, instructions)
    assert any("bf16[1,16384,1024]" in c
               for c in _mosaic_calls(compiled))  # 8 values of 128


def test_the_selective_scan_kernels_compile_at_the_cells_shape(v5e_2x2):
    """``sscan_fwd`` and ``sscan_bwd`` at 16,384 positions of 5,120 channels
    and 16 states: the turned x and y, dt's rows, the chunks' entry states."""
    from easydl_tpu.ops import selective_scan as ss

    def of(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=SingleDeviceSharding(v5e_2x2[0]))

    operands = (of(1, 16384, 80, 64, dtype=jnp.bfloat16), of(1, 16384, 5120),
                of(5120, 16), of(1, 16384, 16, dtype=jnp.bfloat16),
                of(1, 16384, 16, dtype=jnp.bfloat16), of(5120))
    fn = jax.grad(lambda *a: ss.selective_scan_kernels(*a).astype(
        jnp.float32).sum(), argnums=tuple(range(6)))
    compiled = jax.jit(fn).lower(*operands).compile()
    forward, = _mosaic_calls(compiled, "sscan_fwd")
    backward, = _mosaic_calls(compiled, "sscan_bwd")
    assert "bf16[1,1,5120,16384]" in forward \
        and "f32[1,128,16,5120]" in forward, forward
    assert "f32[1,16384,5120]" in backward \
        and "f32[1,10,16,16384]" in backward, backward
