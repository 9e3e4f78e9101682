"""The main path's kernels, compiled for a DESCRIBED v5e — no chip needed.

The TPU compiler is installed wherever jax[tpu] is, and compiles for a
topology that is described, not attached. That catches what interpret mode
cannot: a tile Mosaic refuses, too much VMEM, and — the reason the
multi-chip step had never compiled for the hardware it is named after — a
Mosaic kernel GSPMD is asked to partition. A compile that passes is not a
run; these guard the build, chip_smoke.py proves the run.

Real widths: [8, 1024, 16, 64] bf16, the per-microbatch attention shape of
GPT-2 345M at seq 1024. (The whole-step compiles take 10-16 s each and live
in scripts/rehearse_tpu_compile.py, not in tier-1.)
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
    assert all("[128,1024," in c for c in calls), calls  # [B*H, S, ...]


@pytest.mark.parametrize("shape,blocks", [
    # [batch·heads, seq, head_dim] = [128, 1024, 64]: gpt2-medium's call;
    # [100, 1024, 64]: gpt2-xl's per shard under fsdp=4
    ((8, 1024, 16, 64), ((512, 512), (512, 512), (256, 256))),
    ((4, 1024, 25, 64), ((512, 512), (512, 512), (256, 256))),
], ids=["128x1024x64", "100x1024x64"])
def test_chosen_blocks_compile_at_the_benchmark_shapes(v5e_2x2, shape, blocks):
    """The (block_q, block_k) the forward, dq and dk/dv kernels choose for
    the benchmark's two calls, bf16 causal — a later change to the choice
    shows here — and that Mosaic takes the three kernels at those sizes."""
    from easydl_tpu.ops.flash_attention import choose_blocks

    _, seq, _, _ = shape
    assert choose_blocks(seq, seq, True) == blocks
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e_2x2[0]))
    fn = jax.grad(_loss(lambda q, k, v: flash_attention(q, k, v, causal=True)),
                  argnums=(0, 1, 2))
    calls = _mosaic_calls(jax.jit(fn).lower(x, x, x).compile())
    assert len(calls) == 3, calls
    assert all(f"[{shape[0] * shape[2]},1024," in c for c in calls), calls


@pytest.mark.parametrize("spec,per_device", [
    (MeshSpec(dp=4), "[32,1024,"),          # 2 rows x 16 heads
    (MeshSpec(dp=2, tp=2), "[32,1024,"),    # 4 rows x 8 heads
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
