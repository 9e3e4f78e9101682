"""Stacks and whole steps compiled for a DESCRIBED v5e — no chip needed
(the kernels alone: ``tests/test_tpu_compile.py``): GPT-2 medium's stack cut
to two layers under remat ``dots`` / ``full`` / ``fsdp=4``, Ouro's cut to two
layers and two passes, a two-layer scanned run of JoyAI-LLM-Flash's and
ZAYA1's descriptions at the cells' widths, and the two medium steps' memory
gate (``scripts/rehearse_tpu_compile.py``'s programs). Each program is
compiled ONCE, by a module-scoped fixture or by the one test that reads it;
no two compile the same ``(policy, mesh, model)``. The last two groups are
whole programs at the cells' sizes that no other test reads: marked ``slow``
since PR 40 (``pytest -m slow tests/test_tpu_compile_stack.py``, beside the
script's memory gate, after a change to the block, the kernels' rule, the
head or what remat keeps); the two-layer stacks stand for them in tier-1.
"""

from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from easydl_tpu.core.mesh import MeshSpec, build_mesh

pytestmark = pytest.mark.usefixtures("no_persistent_cache")


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
    """Compiled text of :func:`_two_layer_gpt2_lowered`'s program."""
    return _two_layer_gpt2_lowered(devices, remat_policy,
                                   spec).compile().as_text()


def _two_layer_gpt2_lowered(devices, remat_policy: str,
                            spec: MeshSpec = MeshSpec()):
    """GPT-2 medium's stack cut to two layers (1024 wide, 16 heads of 64,
    bf16, a 1,024-row head) under ``remat_policy``, lowered: loss and
    gradients of 8 sequences of 1,024 on one described chip, or under
    ``spec`` the whole train step of 16 (the ``Trainer`` places the
    parameters), one frame per location as the entry points compile."""
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
                {"inputs": tokens, "targets": tokens})
        return _loss_and_gradients_lowered(bundle, devices, (8, 1024))
    finally:
        jax.config.update("jax_traceback_in_locations_limit", frames)


def _loss_and_gradients_lowered(bundle, devices, tokens_shape):
    """The gradient of ``bundle``'s loss lowered for one described chip, with
    no chooser open unless the caller opened one (``ops/remat.py``)."""
    import flax.linen as nn

    one = SingleDeviceSharding(devices[0])
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one),
        jax.eval_shape(lambda: nn.unbox(
            bundle.init_fn(jax.random.PRNGKey(0)))))
    tokens = jax.ShapeDtypeStruct(tokens_shape, jnp.int32, sharding=one)
    return jax.jit(jax.grad(
        lambda p, batch: bundle.loss_fn(p, batch, jax.random.PRNGKey(0))[0]
    )).lower(params, {"inputs": tokens, "targets": tokens})


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
    names the forward's ``out`` and does not keep it: ``ops/remat.py``), and
    the ONE backward call (dq and dkv until PR 60)."""
    calls = [(which, result)
             for which, opcode, result, path in _attention_instructions(
                 two_layer_texts(stack)) if opcode == "custom-call"]
    assert sorted(which for which, _ in calls) == ["bwd", "fwd", "remat"]
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


@pytest.fixture(scope="module")
def rehearse():
    """``scripts/rehearse_tpu_compile.py`` as a module."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "rehearse_tpu_compile.py")
    spec = importlib.util.spec_from_file_location("rehearse_tpu_compile", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.slow  # a whole step, 45-120 s: ``scripts/rehearse_tpu_compile.py``
@pytest.mark.parametrize("program", ["medium_4x8", "worker_4x8"])
def test_the_medium_steps_fit_the_chip(v5e_2x2, rehearse, program):
    """The memory gate on what remat ``dots`` keeps: the step of
    ``gpt2-medium.steady`` (4 x 8 x 1,024, AdamW) and the elastic worker's
    (``optax.adam``), compiled whole for the described chip, take 15.292 GiB
    of its 15.75 (the parent's 15.296; with the flash forward's ``out`` kept
    too 15.668) and at most 15.35, and hold the forward kernel twice in each
    copy of the step's body. The other cells' steps (XL's shard, the
    hybrid's, Ouro's: 13-35 s each) are programs of the same script with
    limits of their own: ``scripts/rehearse_tpu_compile.py``."""
    compiled, gib = rehearse.compile_program(program, v5e_2x2)
    assert gib <= rehearse.PROGRAMS[program][-1] == 15.35, gib
    calls = rehearse.mosaic_calls(compiled.as_text())
    # the step's body stands twice (the first microbatch, then the scan);
    # the script's own gate holds the same counts
    assert rehearse.kernel_counts(calls) \
        == rehearse.ATTENTION_KERNELS[program] \
        == {"flash_bwd": 2, "flash_fwd": 4}, calls


@pytest.mark.slow  # a whole step, ~45 s: ``scripts/rehearse_tpu_compile.py``
def test_phi4flash_s_step_holds_its_four_new_kernels_and_fits(v5e_2x2,
                                                              rehearse):
    """Phi-4-mini-flash's cell (six published layers, ONE sequence of 16,384
    tokens, AdamW) compiled whole for the described chip: the selective
    scan's two kernels and differential attention's looped two by name
    beside the band path's and the convolution's, under the chip's 15.75
    GiB and the script's own limit (14.730 compiled with four FFNs' gate and
    up kept, PR 58; 12.326 with none; 16.9 GB and no fit without the remat
    barrier around each run of one layer)."""
    compiled, gib = rehearse.compile_program("phi4flash_1x1", v5e_2x2)
    assert gib <= rehearse.PROGRAMS["phi4flash_1x1"][-1] < 15.75, gib
    counts = rehearse.kernel_counts(rehearse.mosaic_calls(compiled.as_text()))
    assert counts == {**rehearse.ATTENTION_KERNELS["phi4flash_1x1"],
                      **rehearse.MAMBA_KERNELS["phi4flash_1x1"]}, counts
    assert {"sscan_fwd", "sscan_bwd", "diff_fwd", "diff_bwd"} <= set(counts)


@pytest.mark.parametrize("program,more,said", [
    ("zaya_1x2", {}, None),
    ("zaya_1x2", {"flash_fwd": 1},  # the scanned run made ``out`` again
     "zaya_1x2: attention kernels {'flash_bwd': 1, 'flash_fwd': 2}, not "
     "{'flash_bwd': 1, 'flash_fwd': 1}"),
    ("joyai_1x2", {"mla_fwd": 1}, "joyai_1x2: attention kernels"),
    # the two backward kernels a short head had until PR 60
    ("medium_4x8", {"flash_bwd_dq": 2, "flash_bwd_dkv": 2, "flash_bwd": -2},
     "medium_4x8: attention kernels {'flash_bwd_dkv': 2, 'flash_bwd_dq': 2, "
     "'flash_fwd': 4}, not {'flash_bwd': 2, 'flash_fwd': 4}"),
    ("hybrid_4x2", {}, None),
    ("nemotron_1x2", {}, None),
    ("hybrid_4x2", {"ssd_fwd": -4, "ssd_bwd": -2},  # the jax.numpy scan
     "hybrid_4x2: Mamba-2 kernels {'conv1d_bwd': 6, 'conv1d_fwd': 12}, not "
     "{'conv1d_bwd': 6, 'conv1d_fwd': 12, 'ssd_bwd': 2, 'ssd_fwd': 4}"),
    ("nemotron_1x2", {"ssd_fwd": -3},  # a run that does not make it again
     "nemotron_1x2: Mamba-2 kernels {'conv1d_bwd': 9, 'conv1d_fwd': 12, "
     "'ssd_bwd': 3, 'ssd_fwd': 3}, not {'conv1d_bwd': 9, 'conv1d_fwd': 12, "
     "'ssd_bwd': 3, 'ssd_fwd': 6}"),
    ("zaya_1x2", {"ssd_fwd": 1}, "zaya_1x2: Mamba-2 kernels {'ssd_fwd': 1}, "
                                 "not {}"),
    ("hybrid_4x2", {"conv1d_fwd": -12, "conv1d_bwd": -6},  # jax.numpy's
     "hybrid_4x2: Mamba-2 kernels {'ssd_bwd': 2, 'ssd_fwd': 4}, not "
     "{'conv1d_bwd': 6, 'conv1d_fwd': 12, 'ssd_bwd': 2, 'ssd_fwd': 4}"),
    ("nemotron_1x2", {"conv1d_fwd": 6},  # every run made them again
     "nemotron_1x2: Mamba-2 kernels {'conv1d_bwd': 9, 'conv1d_fwd': 18, "),
    ("nemotron_1x2", {"conv1d_bwd": -3},  # a layer's sums by XLA
     "nemotron_1x2: Mamba-2 kernels {'conv1d_bwd': 6, 'conv1d_fwd': 12, "),
    ("mellum_1x2", {}, None),
    # the window of 1,024 on the looped kernels, as before the band held it
    ("mellum_1x2", {"swa_bwd_dq": -1, "swa_bwd_dkv": -1, "swa_bwd": 1},
     "mellum_1x2: attention kernels {'flash_bwd': 1, 'flash_fwd': 1, "
     "'swa_bwd': 1, 'swa_fwd': 1}, not {'flash_bwd': 1, 'flash_fwd': 1, "
     "'swa_bwd_dkv': 1, 'swa_bwd_dq': 1, 'swa_fwd': 1}"),
    ("sdar_1x1", {}, None),
    ("sdar_1x1", {"bd_fwd": 1},  # the scanned run made ``out`` again
     "sdar_1x1: attention kernels {'bd_bwd': 1, 'bd_fwd': 2}, not "
     "{'bd_bwd': 1, 'bd_fwd': 1}"),
    # the mask's calls under the causal kernels' names: another program
    ("sdar_1x1", {"bd_fwd": -1, "bd_bwd": -1, "flash_fwd": 1, "flash_bwd": 1},
     "sdar_1x1: attention kernels {'flash_bwd': 1, 'flash_fwd': 1}, not "
     "{'bd_bwd': 1, 'bd_fwd': 1}"),
    ("phi4flash_1x1", {}, None),
    ("phi4flash_1x1", {"sscan_fwd": -4, "sscan_bwd": -2},  # jax.numpy's scan
     "phi4flash_1x1: Mamba-2 kernels {'conv1d_bwd': 2, 'conv1d_fwd': 4}, "
     "not {'conv1d_bwd': 2, 'conv1d_fwd': 4, 'sscan_bwd': 2, 'sscan_fwd': "
     "4}"),
    # the window layer at two head sizes on the looped kernels, not the band
    ("phi4flash_1x1", {"swa_fwd": -2, "swa_bwd_dq": -1, "swa_bwd_dkv": -1,
                       "diff_fwd": 2, "diff_bwd": 1},
     "phi4flash_1x1: attention kernels {'diff_bwd': 3, 'diff_fwd': 4}, not"),
    ("kimi_1x1", {}, None),
    ("kimi_1x1", {"kda_fwd": -4, "kda_bwd": -3},  # jax.numpy's chunks
     "kimi_1x1: Mamba-2 kernels {'conv1d_bwd': 9, 'conv1d_fwd': 12}, not "
     "{'conv1d_bwd': 9, 'conv1d_fwd': 12, 'kda_bwd': 3, 'kda_fwd': 4}"),
], ids=["as-gated", "a-forward-more", "joyai-a-forward-more",
        "another-backward", "hybrid-as-gated", "nemotron-as-gated",
        "hybrid-the-numpy-scan", "nemotron-a-scan-forward-less",
        "a-scan-kernel-where-none-is", "hybrid-the-numpy-convolutions",
        "nemotron-a-convolution-doubled", "nemotron-a-convolution-missing",
        "mellum-as-gated", "mellum-the-looped-window", "sdar-as-gated",
        "sdar-a-forward-more", "sdar-the-causal-kernels",
        "phi4flash-as-gated", "phi4flash-the-numpy-scan",
        "phi4flash-the-looped-window", "kimi-as-gated",
        "kimi-the-numpy-chunks"])
def test_the_script_fails_on_other_attention_kernels_than_a_cells(
        v5e_2x2, rehearse, monkeypatch, capsys, program, more, said):
    """The script is where the whole steps at the cells' sizes are gated
    (the ``slow`` tests of this file read the same programs): every cell
    with a memory limit has its attention kernels by name, the two with
    Mamba-2 layers the mixer's (the scan's ``ssd_fwd``, ``ssd_bwd``: PR 43;
    the convolutions' ``conv1d_fwd``, ``conv1d_bwd``: PR 44), and a
    program that holds others — a forward kernel more is a scanned run that
    made ``out`` and ``lse`` again, no scan or convolution kernel is the
    ``jax.numpy`` path, a doubled or missing call says which — makes the
    exit code 1 naming the cell. The
    compile is stood in for: the gate is what is tested, in no time."""
    import types

    assert set(rehearse.ATTENTION_KERNELS) == {
        name for name, program in rehearse.PROGRAMS.items() if program[-1]}
    assert set(rehearse.MAMBA_KERNELS) == {"hybrid_4x2", "nemotron_1x2",
                                           "phi4flash_1x1", "kimi_1x1"}
    counts = dict(rehearse.ATTENTION_KERNELS[program], rows_to_tokens=6,
                  **rehearse.MAMBA_KERNELS.get(program, {}))
    for kernel, n in more.items():
        counts[kernel] = counts.get(kernel, 0) + n
    text = "\n".join(
        f'  %{kernel}.{i} = bf16[2,8192,1024]{{2,1,0}} custom-call(%x), '
        f'custom_call_target="tpu_custom_call"'
        for kernel, n in counts.items() for i in range(n))
    fits = types.SimpleNamespace(argument_size_in_bytes=2**30,
                                 temp_size_in_bytes=2**30)
    compiled = types.SimpleNamespace(memory_analysis=lambda: fits,
                                     as_text=lambda: text)
    monkeypatch.setattr(rehearse, "compile_program",
                        lambda name, devices: (compiled, 2.0))
    monkeypatch.setattr(rehearse.sys, "argv", ["rehearse", program])
    if said is None:
        rehearse.main()
    else:
        with pytest.raises(SystemExit) as exit_:
            rehearse.main()
        assert said in str(exit_.value), exit_.value
    assert f"{program}: " in capsys.readouterr().out


# A compiled step's text in small: a chunk loop of ``ops/moe.py _live_rows``
# whose body copies the carried buffer, large and small copies, a transpose
# and a bitcast fusion at the top level, and a copy inside a fusion's body.
_COMPILED = """\
%fused_computation.1 (p: bf16[65536,2304]) -> bf16[65536,2304] {
  %p = bf16[65536,2304]{1,0} parameter(0)
  ROOT %copy.9 = bf16[65536,2304]{1,0:T(8,128)(2,1)} copy(%p)
}

%body.7 (arg: (s32[], bf16[65536,2304], bf16[16384,2304])) -> (s32[], bf16[65536,2304], bf16[16384,2304]) {
  %arg = (s32[]{:T(128)}, bf16[65536,2304]{1,0}, bf16[16384,2304]{1,0}) parameter(0)
  %gte.1 = bf16[65536,2304]{1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %copy.3 = bf16[65536,2304]{1,0:T(8,128)(2,1)} copy(%gte.1), metadata={op_name="jit(train_step)/blocks_1/moe/moe/jit(_piece_forward)/dispatch/live_rows/while/body/dynamic_update_slice"}
  %copy.4 = bf16[7296,2304]{1,0:T(8,128)(2,1)S(1)} copy(%gte.1)
  ROOT %tuple.1 = (s32[], bf16[65536,2304], bf16[16384,2304]) tuple(%c, %copy.3, %h)
}

ENTRY %main.1 (a: bf16[16384,2304]) -> bf16[65536,2304] {
  %while.5 = (s32[]{:T(128)}, bf16[65536,2304]{1,0:T(8,128)(2,1)}, bf16[16384,2304]{1,0:T(8,128)(2,1)}) while(%tuple.0), condition=%cond.7, body=%body.7, metadata={op_name="jit(train_step)/blocks_1/moe/moe/jit(_piece_forward)/dispatch/live_rows/while"}
  %copy.1 = bf16[65536,896]{1,0:T(8,128)(2,1)} copy(%x)
  %copy.2 = bf16[128,896]{1,0:T(8,128)(2,1)} copy(%y)
  %transpose.1 = f32[2,8192,2304]{2,1,0:T(8,128)} transpose(%z), dimensions={1,0,2}
  %convert_bitcast_fusion.3 = bf16[2,8192,4096]{2,1,0:T(8,128)(2,1)} fusion(%q), kind=kLoop, calls=%fused_computation.1
  ROOT %fusion.8 = bf16[65536,2304]{1,0:T(8,128)(2,1)} fusion(%w), kind=kLoop, calls=%fused_computation.1
}
"""


def test_the_census_counts_what_xla_moves_and_what_a_chunk_loop_copies(
        rehearse):
    """``movement``: the top-level copies, transposes and bitcast fusions of
    a megabyte or more by kind and type (a fusion's body is one pass, a
    small copy is none), and every copy of a carried array inside the body
    of a ``while`` under ``live_rows`` — the expert layer's chunk loops have
    to write their piece-sized buffers in place (PR 46)."""
    moves, in_loops = rehearse.movement(_COMPILED)
    assert moves == {"convert_bitcast_fusion bf16[2,8192,4096]": 1,
                     "copy bf16[65536,2304]": 1, "copy bf16[65536,896]": 1,
                     "copy bf16[7296,2304]": 1,  # a loop's body counts too
                     "transpose f32[2,8192,2304]": 1}
    assert in_loops == ["%body.7: copy.3 bf16[65536,2304]"]
    # the same body under a `while` of another name is no chunk loop
    assert rehearse.movement(_COMPILED.replace("live_rows/while", "while"))[
        1] == []


@pytest.mark.parametrize("mine, parents, said", [
    ({"expert_layers": False, "lowered_sha256": "a" * 64},
     {"lowered_sha256": "a" * 64}, []),
    ({"expert_layers": False, "lowered_sha256": "a" * 64},
     {"lowered_sha256": "b" * 64},
     ["cell: lowers to aaaaaaaaaaaa, the parent to bbbbbbbbbbbb"]),
    # an expert program's text differs by design: what it moves may not grow
    ({"expert_layers": True, "lowered_sha256": "a" * 64,
      "chunk_loop_copies": [], "moves": {"copy bf16[65536,896]": 2}},
     {"lowered_sha256": "b" * 64, "moves": {"copy bf16[65536,896]": 3,
                                            "transpose f32[8,8]": 1}}, []),
    ({"expert_layers": True, "lowered_sha256": "a" * 64,
      "chunk_loop_copies": [], "moves": {"copy bf16[65536,896]": 3}},
     {"lowered_sha256": "b" * 64, "moves": {"copy bf16[65536,896]": 1}},
     ["cell: 2 x copy bf16[65536,896] more than the parent"]),
    ({"expert_layers": True, "lowered_sha256": "a" * 64, "moves": {},
      "chunk_loop_copies": ["%body.7: copy.3 bf16[65536,2304]"]},
     {"lowered_sha256": "b" * 64, "moves": {}},
     ["cell: a chunk loop copies %body.7: copy.3 bf16[65536,2304]"]),
], ids=["the-parents-text", "another-text", "experts-moving-less",
        "experts-moving-more", "a-chunk-loop-that-copies"])
def test_a_census_against_the_parents(rehearse, mine, parents, said):
    """``against``: a program without expert layers has to lower to the
    parent commit's text; one with them may copy nothing inside a chunk
    loop and move no large array the parent's program did not."""
    assert rehearse.against("cell", mine, parents) == said


def test_a_programs_hash_forgets_where_its_kernels_were_written():
    """``program_sha256``: two lowered programs that differ in a Mosaic
    payload's source locations alone (the same kernel written on another
    line: every PR that edits ``ops/moe.py`` moves its kernels' lines, and a
    checkout elsewhere their paths) and in jax's running numbers on private
    functions hash alike; another kernel does not."""
    import importlib.util

    from jax.experimental import pallas as pl

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "rehearse_tpu_compile.py")
    spec = importlib.util.spec_from_file_location("rehearse_hash", path)
    rehearse = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rehearse)

    def text(kernel):
        call = pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            name="add")
        return jax.jit(call).trace(
            jax.ShapeDtypeStruct((8, 128), jnp.float32)).lower(
                lowering_platforms=("tpu",)).as_text()

    def one(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1.0

    def same(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1.0

    def other(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 2.0

    texts = [text(kernel) for kernel in (one, same, other)]
    assert "tpu_custom_call" in texts[0] and texts[0] != texts[1]
    hashes = [rehearse.program_sha256(t) for t in texts]
    assert hashes[0] == hashes[1] != hashes[2]
    numbered = "func.func private @tril_217() {\n}"
    assert rehearse.program_sha256(numbered) == rehearse.program_sha256(
        numbered.replace("217", "9"))


@pytest.mark.parametrize("parents_text, said", [
    ("the step", None), ("another step", "ouro_4x1: lowers to ")],
    ids=["the-parents-program", "another-program"])
def test_the_script_holds_a_program_to_the_parents_census(
        v5e_2x2, rehearse, monkeypatch, tmp_path, capsys, parents_text, said):
    """``--census`` writes each program's census and ``--against`` gates it
    on the parent commit's (the compile is stood in for): a program without
    expert layers that lowers to another text makes the exit code 1."""
    import json
    import types

    fits = types.SimpleNamespace(argument_size_in_bytes=2**30,
                                 temp_size_in_bytes=2**30)
    compiled = types.SimpleNamespace(
        memory_analysis=lambda: fits,
        as_text=lambda: "\n".join(
            f'  %{kernel}.{i} = bf16[1,4096,2048]{{2,1,0}} custom-call(%x), '
            f'custom_call_target="tpu_custom_call"'
            for kernel, n in rehearse.ATTENTION_KERNELS["ouro_4x1"].items()
            for i in range(n)))
    monkeypatch.setattr(
        rehearse, "lower_program", lambda name, devices:
        types.SimpleNamespace(as_text=lambda: "the step"))
    monkeypatch.setattr(rehearse, "compile_program",
                        lambda name, devices, lowered=None: (compiled, 2.0))
    parents, census = tmp_path / "parent.json", tmp_path / "change.json"
    parents.write_text(json.dumps({"ouro_4x1": {
        "lowered_sha256": rehearse.program_sha256(parents_text)}}))
    monkeypatch.setattr(rehearse.sys, "argv", [
        "rehearse", "--census", str(census), "--against", str(parents),
        "ouro_4x1"])
    if said is None:
        rehearse.main()
    else:
        with pytest.raises(SystemExit) as exit_:
            rehearse.main()
        assert said in str(exit_.value), exit_.value
    written = json.loads(census.read_text())["ouro_4x1"]
    assert written["lowered_sha256"] == rehearse.program_sha256("the step")
    assert written["expert_layers"] is False and written["moves"] == {}
    assert "ouro_4x1: lowered " in capsys.readouterr().out


def _two_layer_rotary_lowered(devices):
    """Ouro's description cut to two layers and two passes (2048 wide, 16
    heads of 128, rotary, sandwich norms, remat ``full``, bf16, one
    4,096-token sequence, a 1,024-row head), loss and gradients lowered for
    one described chip."""
    from easydl_tpu.models.ouro import make_ouro

    frames = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 1)
    try:
        bundle = make_ouro(
            size="2.6b", seq_len=4096, vocab=1024, dtype="bfloat16",
            remat=True, remat_policy="full", attention_impl="flash",
            layer_types=["full_attention"] * 2, total_ut_steps=2)
        return _loss_and_gradients_lowered(bundle, devices, (1, 4096))
    finally:
        jax.config.update("jax_traceback_in_locations_limit", frames)


@pytest.fixture(scope="module")
def two_layer_rotary_stack(v5e_2x2):
    """As ``two_layer_stack``, for :func:`_two_layer_rotary_lowered`'s
    program: every top-level instruction under ``attention``."""
    return _attention_instructions(
        _two_layer_rotary_lowered(v5e_2x2).compile().as_text())


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
#: the room a scanned run's candidates are given where a test compiles the
#: run alone: stated, as the ``Trainer`` states what its compiled step leaves
#: (no chooser open, nothing is kept: ``ops/remat.py``)
ROOM = 4 << 30


def _scanned_lowered(devices, factory: str, batch: int = 2, plan=None,
                     **description):
    """Loss and gradients of a two-layer scanned run of ``factory``'s
    description at the cell's widths (``batch`` x 8,192 tokens, bf16, remat
    ``full`` with :data:`ROOM` for what it keeps — or held to the keys of
    ``plan``, as the ``Trainer`` holds a trace to a choice — a 1,024-row
    head; the expert layer's kernels compiled as on the chip: the caller
    asks ``described_tpu``), lowered for one described chip."""
    from easydl_tpu.models.registry import get_model
    from easydl_tpu.ops import remat

    frames = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 1)
    try:
        bundle = get_model(
            factory, seq_len=8192, vocab=1024, dtype="bfloat16", remat=True,
            remat_policy="full", attention_impl="flash", **description)
        with remat.choosing(remat.Chooser(ROOM, plan)):
            return _loss_and_gradients_lowered(bundle, devices, (batch, 8192))
    finally:
        jax.config.update("jax_traceback_in_locations_limit", frames)


#: compiled texts by (factory, batch, description): two tier-1 tests read
#: SDAR's, and a compile at a cell's widths is 45 s
_TEXTS: dict = {}


def _scanned_text(devices, factory: str, batch: int = 2,
                  **description) -> str:
    """The compiled text of :func:`_scanned_lowered`'s program."""
    key = (factory, batch, repr(sorted(description.items())))
    if key not in _TEXTS:
        _TEXTS[key] = _scanned_lowered(devices, factory, batch,
                                       **description).compile().as_text()
    return _TEXTS[key]


def _scanned_run(devices, factory: str, **description) -> list:
    """``_attention_instructions`` of :func:`_scanned_text`'s program."""
    return _attention_instructions(_scanned_text(devices, factory,
                                                 **description))


def test_sdars_stack_norms_inside_the_rotary_kernel_and_its_flash_calls_are_bds(
        v5e_2x2, described_tpu):
    """A two-layer scanned run of SDAR's description at the cell's widths
    (one sequence, 16,384 rows, remat ``full``): every layer's q/k norm is
    the rotary kernel's — ``rope_norm_fwd`` in the forward and in the
    recomputation, ``rope_norm_bwd`` in the backward, on q and on k; no bare
    rotary call, nothing under ``qk_rmsnorm`` — and
    ``benchmark/lib/hlo.flash_calls``, which tells a flash kernel by the end
    of its name and its count of results, lists ``bd_fwd`` / ``bd_bwd``
    alone: the fused calls' one and two results are not a flash call's."""
    import importlib
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    flash_calls = importlib.import_module("lib.hlo").flash_calls

    text = _scanned_text(v5e_2x2, "sdar", **SDAR_TWO)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in ("rope_norm_fwd", "rope_norm_bwd"):
        assert any(f"/{name}/" in line for line in calls), name
    for other in ("rope_fwd", "rope_bwd"):
        assert not any(f"/{other}/" in line for line in calls), other
    assert "qk_rmsnorm" not in text
    assert sorted({call["kernel"] for call in flash_calls(text)}) == [
        "bd_bwd", "bd_fwd"]


SDAR_TWO = dict(batch=1, size="30b-a3b-chat", block_length=4,
                layer_types=["full_attention"] * 2, experts_held=(0, 16))


@pytest.mark.parametrize("factory,description,lanes,again", [
    # Mellum 2's window layers: the band's results are kept too (1,891 FLOP
    # a byte, over the floor since PR 59); the rotation of the kept q and of
    # the kept k is made again
    ("mellum", dict(size="2-12b-a2.5b", layer_types=["sliding_attention"] * 2,
                    experts_held=(0, 16)), (4096, 2304),
     ["rope_fwd", "rope_fwd"]),
    # SDAR's under the block mask: the call's results are kept, and the
    # backward's norming rotation reads the kept rows of q and k
    ("sdar", SDAR_TWO, (4096, 2048), ["rope_norm_fwd", "rope_norm_fwd"]),
], ids=["band", "block-diffusion"])
def test_whoever_reads_a_kept_q_or_output_maps_result_reads_the_kept_rows(
        v5e_2x2, described_tpu, factory, description, lanes, again):
    """A two-layer scanned run at the cell's widths under remat ``full``
    with room for every candidate (PR 59: q, k, v and the output map's
    result kept as rows): in the recomputation none of the four products
    stands again, the kernels there are those that read the kept rows, and
    nothing moves a q-sized or a result-sized array between the layers'
    stack, the rotary kernel and the flash call — no ``copy`` or
    ``transpose`` of it in the recomputation (PR 30: XLA wrote such a slice
    twice and transposed it), and in the backward none but the turned
    operands of the four weight-gradient products."""
    text = _scanned_text(v5e_2x2, factory, **description)
    found = _attention_instructions(text)
    assert {which for which, *_ in found} == {"fwd", "remat", "bwd"}
    remade = [path for which, _, _, path in found if which == "remat"
              and path.split("/")[0] in ("q", "k", "v", "out")
              and "dot_general" in path]
    assert not remade, remade
    assert [path for which, _, _, path in found if which == "fwd"
            and path.split("/")[:2] == ["q", "dot_general"]]
    kernels = sorted(path.split("/")[-2] for which, opcode, _, path in found
                     if which == "remat" and opcode == "custom-call")
    assert kernels == again, kernels
    rows = description.get("batch", 2) * 8192
    big = tuple(f"[{rows // 8192},8192,{n}]" for n in lanes) \
        + tuple(f"[{n},{rows}]" for n in lanes)
    moved = [(which, opcode, result, path)
             for which, opcode, result, path in found
             if opcode in ("copy", "transpose")
             and any(shape in result for shape in big)]
    assert not [m for m in moved if m[0] != "bwd"], moved
    # a weight gradient takes the layer's input TURNED: XLA's own order
    assert {m[3] for m in moved} <= {f"{name}/dot_general"
                                     for name in ("q", "k", "v", "out")}


@pytest.mark.parametrize("factory,description,forward,rows,reader", [
    ("joyai", dict(size="llm-flash", layer_types=["sparse"] * 2, mtp=False,
                   experts_held=(0, 16)), "mla_fwd", 4096, "mla_out"),
    ("zaya", dict(size="8b", layer_types=["hybrid"] * 2,
                  experts_held=(0, 8)), "flash_fwd", 1024, "cca_up"),
], ids=["joyai-llm-flash", "zaya1-8b"])
@pytest.mark.slow  # a run at the cell's widths, 60-70 s each: with the script
def test_full_keeps_the_flash_forwards_results_of_a_dear_call(
        v5e_2x2, described_tpu, factory, description, forward, rows, reader):
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
