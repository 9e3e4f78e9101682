#!/usr/bin/env python3
"""Compile the real-size train steps for a DESCRIBED v5e 2x2 — no chip needed.

The third rehearsal of /opt/skills/guides/on-chip-measurement (section 2):
the TPU compiler is installed here and compiles for a topology that is
described, not attached. What it refuses here (a Mosaic kernel GSPMD cannot
partition, a program that does not fit 16 GB) costs no chip time. Compiles
chip_smoke.py's programs (GPT-2 345M, seq 1024, bf16, remat "dots", flash
attention) and the benchmark cells' steps, and prints, per program, the
bytes on each device, the Mosaic calls (how many of them the flash forward)
and their per-device operand shapes, and the collectives in front of them.
A cell's step is also a gate: it has a limit in GiB a device and the
attention kernels it holds by name (``ATTENTION_KERNELS``: how often the
forward kernel stands says what remat kept) and the Mamba-2 mixer's
(``MAMBA_KERNELS``), and a program over its limit or with other kernels makes
the exit code 1. A compile that passes is not a run. What a ``full`` step's
blocks keep is fitted by the ``Trainer`` to the chip's memory
(``ops/remat.py``): the described chip is SAID to have a v5e's 15.748 GiB
(``V5E_BYTES``), the step's log line (``train step: compiled to ...``) says
what was kept and what was first left out, and a ``full`` cell with room is
compiled twice: with nothing kept, for its size, then with what that leaves
room for (a cell with room for nothing, Ouro's or the hybrid's, once).

Against another commit (``--census`` / ``--against``; PR 46): each program's
lowered text hashed without what moves with a checkout (the Mosaic payloads'
source locations, jax's running numbers on private functions) and its
compiled text's data movement counted (top-level ``copy`` / ``transpose`` /
``*bitcast_fusion`` of a megabyte or more, and copies of a carried array
inside the expert layer's chunk loops, ``ops/moe.py _live_rows``). A program
with no expert layer has to lower to the other commit's text; one with
expert layers may hold no copy inside a chunk loop and no large movement
the other commit's program lacks. Exit code 1 like the other gates.

Usage: JAX_PLATFORMS=cpu python scripts/rehearse_tpu_compile.py
           [--tree DIR] [--census OUT.json] [--against PARENT.json] [NAME ...]
       (names: the keys of PROGRAMS; default: all. ``--tree``: import the
       package from that checkout, e.g. ``git archive`` of the parent, and
       write its census: ``--tree .chiprun_tree/parent --census
       /root/scratch/parent.json``, then ``--census /root/scratch/change.json
       --against /root/scratch/parent.json`` on this tree)
"""

from __future__ import annotations

import argparse
import base64
import collections
import hashlib
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: get_model's arguments. attention_impl="flash", stated: "auto" asks
#: jax.devices(), which is the CPU here, and would compile the reference path.
_CHIP = dict(dtype="bfloat16", remat=True, attention_impl="flash")
MEDIUM = ("gpt", dict(size="345m", seq_len=1024, remat_policy="dots", **_CHIP))
XL = ("gpt", dict(size="1558m", seq_len=1024, remat_policy="dots", **_CHIP))
HYBRID = ("granite_hybrid", dict(
    size="micro", seq_len=4096, remat_policy="full",
    layer_types=["mamba"] * 5 + ["attention"], **_CHIP))
OURO = ("ouro", dict(size="2.6b", seq_len=4096, remat_policy="full",
                     layer_types=["full_attention"] * 8, **_CHIP))
LAGUNA = ("laguna", dict(
    size="xs.2", seq_len=8192, vocab=12544, remat_policy="full",
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"], experts_held=(0, 32), **_CHIP))
ZAYA = ("zaya", dict(
    size="8b", seq_len=8192, vocab=32784, remat_policy="full",
    layer_types=["hybrid"] * 6, experts_held=(0, 8), **_CHIP))
JOYAI = ("joyai", dict(
    size="llm-flash", seq_len=8192, vocab=16160, remat_policy="full",
    layer_types=["dense"] + ["sparse"] * 4, experts_held=(0, 16), **_CHIP))
NEMOTRON = ("nemotron_h", dict(
    size="nano-30b-a3b", seq_len=8192, vocab=16384, remat_policy="full",
    hybrid_override_pattern="MEMEM*EME", experts_held=(0, 8), **_CHIP))

MELLUM = ("mellum", dict(
    size="2-12b-a2.5b", seq_len=8192, vocab=24576, remat_policy="full",
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    experts_held=(0, 16), **_CHIP))
SDAR = ("sdar", dict(
    size="30b-a3b-chat", seq_len=8192, vocab=18992, block_length=4,
    remat_policy="full", layer_types=["full_attention"] * 6,
    experts_held=(0, 16), **_CHIP))
KEYE = ("keye", dict(
    size="vl-2.0-30b-a3b", seq_len=16384, vocab=18992, remat_policy="full",
    layer_types=["full_attention"] * 6, experts_held=(0, 16), **_CHIP))
KIMI = ("kimi_linear", dict(
    size="48b-a3b", seq_len=16384, vocab=20480, remat_policy="full",
    layer_types=["kda_dense", "kda_sparse", "kda_sparse", "mla_sparse",
                 "kda_sparse"], experts_held=(0, 8), **_CHIP))
PHI4FLASH = ("phi4flash", dict(
    size="mini-flash-reasoning", seq_len=16384, vocab=25008,
    remat_policy="full", layer_ids=[0, 1, 16, 17, 18, 19], **_CHIP))
#: a v5e chip's ``bytes_limit`` as its allocator states it: 15.748 GiB (my
#: chip run, PR 58, call p58a)
V5E_BYTES = 16909336064
#: name -> (model, mesh shape key, global batch, grad_accum, optimizer,
#: GiB a device the step may take or None). A chip has 15.75 GiB; a step's
#: limit is its own compiled size and a little: medium's steps 15.292
#: (15.668 with the flash forward's `out` kept as well: PERF.md section 6,
#: PR 30), XL's shard 14.111, the hybrid's 15.482 (15.601 before the scan's
#: kernels, PR 43; 15.590 before the convolutions', PR 44), Ouro's 15.488, Laguna's
#: share 14.851 (15.227 before the expert layer's sort went in pieces,
#: PR 32; 15.006 before its grouped products were kernels of the repo's own,
#: PR 36), ZAYA1's share 14.767 (15.000 before PR 36: the backward keeps no
#: float32 copy of the cotangent's rows and no third result of the experts;
#: 14.869 before PR 38), JoyAI-LLM-Flash's share 14.354 (13.798 before
#: PR 38). PR 38: remat `full` keeps the flash forward's `out` and `lse` of
#: the calls ops/remat.py's rule picks — JoyAI's four scanned layers hold
#: 4 x 136 MB more (+0.556 GiB), ZAYA1's six hold 6 x 34 MB and the
#: backward's body no longer makes the kernel's results beside them (-0.102
#: GiB); Laguna's two picked layers are runs of one whose `out` was live
#: from forward to backward already (14.850); the rule picks nothing in the
#: other five. PR 39: the looped flash backward is one call whose three
#: results stand beside its five operands at once, where dq could be used
#: and freed before dk and dv were made: JoyAI's share 14.486 (+0.132 GiB,
#: about one `[2, 8192, 4096]` array; its limit went 14.45 -> 14.55), the
#: other seven to the digit (their peaks lie elsewhere) — a change to the
#: shared block, kernels or policy may not grow them unseen. PR 56: the
#: flash kernels read a shared key/value head by its index and no k or v at
#: the query's heads stands in front of them: Laguna's share 14.850 ->
#: 13.968, Nemotron's 13.849 -> 13.615, Mellum 2's 11.932 -> 11.713, SDAR's
#: 14.470 -> 14.334 (their limits follow); ZAYA1's and the hybrid's to the
#: digit (their peaks lie elsewhere). PR 59: a ``full`` block in a scanned
#: run offers its attention's rows, its scan mixer's input maps and its
#: shared expert's products too, the floor is 1,800, and the room is filled:
#: the limits of the six cells with a scanned run and room are what the
#: chooser's budget (15.748 less the 0.25 margin) lets a step reach — Mellum
#: 2's share 11.713 -> 13.834, SDAR's 14.334 -> 14.953 (its first choice,
#: 15.613, is over the budget and is made once more), Nemotron's 13.615 ->
#: 15.327, Laguna's 13.968 -> 14.382 (the first, 15.978, over), ZAYA1's
#: 14.767 -> 15.399, JoyAI's 14.486 -> 15.391; Phi-4-mini-flash's six runs of
#: one offer nothing new and keep 14.730. By this sum
#: a value a scanned run stacks costs up to TWICE its bytes (Mellum 2's
#: `out`: 0.211 GiB kept, +0.422) where the compiler's buffer assignment
#: allocates the bytes (11.49 -> 11.71 GiB)
PROGRAMS = {
    "one": (MEDIUM, "dp=1", 8, 1, "adamw", None),    # chip_smoke train/resume/elastic
    "one_accum": (MEDIUM, "dp=1", 32, 8, "adamw", None),  # chip_smoke --mesh, the comparison
    "dp4": (MEDIUM, "dp=4", 32, 1, "adamw", None),
    "fsdp2tp2": (MEDIUM, "fsdp=2,tp=2", 32, 1, "adamw", None),
    # the benchmark's cells: gpt2-medium.steady, .kill-resume (the elastic
    # worker's optax.adam), gpt2-xl.fsdp4-steady, the hybrid's, Ouro's
    "medium_4x8": (MEDIUM, "dp=1", 32, 4, "adamw", 15.35),
    "worker_4x8": (MEDIUM, "dp=1", 32, 4, "adam", 15.35),
    "xl_fsdp4": (XL, "fsdp=4", 16, 1, "adamw", 14.2),
    "hybrid_4x2": (HYBRID, "dp=1", 8, 4, "adamw", 15.61),
    "ouro_4x1": (OURO, "dp=1", 4, 4, "adamw", 15.50),
    "laguna_1x2": (LAGUNA, "dp=1", 2, 1, "adamw", 14.5),
    "zaya_1x2": (ZAYA, "dp=1", 2, 1, "adamw", 15.5),
    "joyai_1x2": (JOYAI, "dp=1", 2, 1, "adamw", 15.5),
    # PR 42: 16.155 by this script's sum, MORE than the chip's 15.75, and it
    # ran: memory_analysis' arguments + temporaries over-count (the
    # compiler's own buffer assignment allocated 14.91 GiB for that step,
    # 14.26 live at the peak; for `one` the sum prints 12.64 where 10.44 are
    # allocated: docs/operations.md). PR 43: 14.158 — the scan's kernels keep
    # a layer's operands and its chunks' entry states, where the backward of
    # the jax.numpy scan held the chunks' float32 intermediates of all 64
    # steps beside them (temporaries 8.70 -> 6.70 GiB). PR 44: 13.926 — the
    # convolutions' backward kernel makes the pre-activation again in VMEM
    # where XLA's kept float32 copies. The limit is the sum and a little, as
    # the others' (16.3 until PR 43, 14.3 until PR 44)
    "nemotron_1x2": (NEMOTRON, "dp=1", 2, 1, "adamw", 15.45),
    "mellum_1x2": (MELLUM, "dp=1", 2, 1, "adamw", 13.95),
    # ONE sequence of 8,192 tokens a step: 16,384 [noised || clean] rows
    "sdar_1x1": (SDAR, "dp=1", 1, 1, "adamw", 15.1),
    # ONE sequence of 16,384 tokens a step in one microbatch. PR 58: 12.152
    # with nothing kept (12.326 with the two whole-sequence differential
    # calls' results, as before) leaves 3.35 GiB here; the two calls and
    # four of its six FFNs' gate and up (2.82 GiB) take 2.58 of it — every
    # other cell's choice is what it kept before (Ouro and the hybrid
    # nothing; Laguna's and JoyAI-LLM-Flash's dense layer keeps its gate and
    # up as well, in a size that does not move)
    "phi4flash_1x1": (PHI4FLASH, "dp=1", 1, 1, "adamw", 14.85),
    # ONE sequence of 16,384 tokens a step in one microbatch, behind a
    # learned index: the packed selections and the index loss's gradients
    # are kept whatever the room (PR 61)
    "keye_1x1": (KEYE, "dp=1", 1, 1, "adamw", 15.3),
    # ONE sequence of 16,384 tokens a step in one microbatch through four
    # delta-rule layers and one latent layer without positions (PR 64)
    "kimi_1x1": (KIMI, "dp=1", 1, 1, "adamw", 15.3),
}

#: name -> the attention kernels (``flash_*``, ``mla_*``, ``swa_*``) a cell's
#: compiled step holds, by name: the second gate, exit code 1 like the first.
#: A forward kernel stands once where its ``out`` and ``lse`` are kept and
#: twice where remat makes them again: medium's two copies of the step's body
#: (the first microbatch, then the scan) hold it twice each under ``dots``;
#: under ``full`` ZAYA1's six scanned layers hold it ONCE and JoyAI-LLM-Flash's
#: four scanned layers once beside the dense layer's two (PR 38: 2 and 4 if
#: the scanned run made it again). ``flash_bwd`` / ``mla_bwd`` is the backward
#: as ONE call (PR 39; the GPT-2 programs' too since PR 60, which took the
#: unrolled ``flash_bwd_dq`` + ``flash_bwd_dkv`` away); ``swa_bwd_dq`` +
#: ``swa_bwd_dkv`` are the band path's.
ATTENTION_KERNELS = {
    "medium_4x8": {"flash_bwd": 2, "flash_fwd": 4},
    "worker_4x8": {"flash_bwd": 2, "flash_fwd": 4},
    "xl_fsdp4": {"flash_bwd": 1, "flash_fwd": 2},
    "hybrid_4x2": {"flash_bwd": 2, "flash_fwd": 2},
    "ouro_4x1": {"flash_bwd": 2, "flash_fwd": 4},
    "laguna_1x2": {"flash_bwd": 2, "flash_fwd": 2, "swa_bwd_dkv": 1,
                   "swa_bwd_dq": 1, "swa_fwd": 2},
    "zaya_1x2": {"flash_bwd": 1, "flash_fwd": 1},
    "joyai_1x2": {"mla_bwd": 3, "mla_fwd": 3},
    "nemotron_1x2": {"flash_bwd": 1, "flash_fwd": 1},
    # the three window-1,024 layers are one scanned run on the band path
    # (``swa_fwd`` once since PR 59: 1,891 FLOP a byte is over the floor and
    # its results are kept), the full layer keeps its forward's results
    "mellum_1x2": {"flash_bwd": 1, "flash_fwd": 1, "swa_bwd_dkv": 1,
                   "swa_bwd_dq": 1, "swa_fwd": 1},
    # six scanned layers under the block mask, the forward's results kept
    "sdar_1x1": {"bd_bwd": 1, "bd_fwd": 1},
    # differential attention, scores 64 deep against values 128 wide: the
    # window layer on the band path (made again), the whole-sequence layer
    # and the cross layer on the looped side, their results kept
    "phi4flash_1x1": {"diff_bwd": 2, "diff_fwd": 2, "swa_bwd_dkv": 1,
                      "swa_bwd_dq": 1, "swa_fwd": 2},
    # six scanned layers under the index's packed selection, the forward's
    # results kept (weighed by the causal pairs the kernel visits: 16,133
    # FLOP a byte), k and v beside them
    "keye_1x1": {"dsa_bwd": 1, "dsa_fwd": 1},
    # the ONE latent layer is a run of one between the delta rule's, its
    # forward's results kept (20,166 FLOP a byte)
    "kimi_1x1": {"mla_bwd": 1, "mla_fwd": 1},
}

#: name -> the Mamba mixers' kernels (ops/ssd.py: Mamba-2's scan ``ssd_*``,
#: PR 43; the convolutions' ``conv1d_*``, PR 44; ops/selective_scan.py:
#: Mamba-1's scan ``sscan_*``, PR 53) a cell's compiled step holds,
#: by name, gated like the attention kernels: a scanned run of Mamba-2 layers
#: holds ``ssd_fwd`` twice (remat ``full`` makes the layer again) and
#: ``ssd_bwd`` once — the hybrid's five layers are two runs, Nemotron's four
#: are three — and three convolutions (x, B, C) beside each: ``conv1d_fwd``
#: six times a scanned run and ``conv1d_bwd`` three. A run of ONE block holds
#: ``conv1d_fwd`` three times: the compiler merges the pass with the one made
#: again where the two calls are the same call (the scan's are not: the
#: differentiated one keeps the chunks' entry states) — two of Nemotron's
#: three runs. A cell with none of them here may hold none: a step that fell
#: back to ``jax.numpy`` fails the gate.
MAMBA_KERNELS = {
    "hybrid_4x2": {"conv1d_bwd": 6, "conv1d_fwd": 12, "ssd_bwd": 2,
                   "ssd_fwd": 4},
    "nemotron_1x2": {"conv1d_bwd": 9, "conv1d_fwd": 12, "ssd_bwd": 3,
                     "ssd_fwd": 6},
    # two Mamba-1 layers, each a run of one made again by remat: the
    # selective scan's kernels (ops/selective_scan.py) and x's convolution
    "phi4flash_1x1": {"conv1d_bwd": 2, "conv1d_fwd": 4, "sscan_bwd": 2,
                      "sscan_fwd": 4},
    # four delta-rule layers in three runs (one, two scanned, one): the
    # recurrence's kernels (ops/kda.py) and three convolutions (q, k, v) a
    # layer; the scanned run holds its forward twice (remat makes the layer
    # again), a run of one once (the compiler merges the pass with the one
    # made again)
    "kimi_1x1": {"conv1d_bwd": 9, "conv1d_fwd": 12, "kda_bwd": 3,
                 "kda_fwd": 4},
}


def lower_program(name: str, devices):
    """The lowered step of ``PROGRAMS[name]`` on the first devices of a
    described topology."""
    import jax
    import jax.numpy as jnp
    import optax

    from easydl_tpu.core.mesh import MeshSpec, build_mesh
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.registry import get_model
    from easydl_tpu.ops import platform

    (factory, kwargs), key, batch, accum, optimizer, _ = PROGRAMS[name]
    bundle = get_model(factory, **kwargs)
    spec = MeshSpec.parse(key)
    trainer = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=getattr(optax, optimizer)(1e-3),
        config=TrainConfig(global_batch=batch, grad_accum=accum),
        mesh=build_mesh(spec, devices=devices[:spec.size]))
    tokens = jax.ShapeDtypeStruct((batch, kwargs["seq_len"]), jnp.int32)
    # the ops ask jax.devices() (the CPU here) whether their kernels are
    # compiled or interpreted: compiled, as on the chip — while this program
    # is traced and no longer (a test calls this in a process whose later
    # tests run the layers on the CPU: tests/test_tpu_compile.py)
    # A described chip cannot say how much memory it has: a v5e's limit,
    # stated, is what the Trainer fits what remat keeps to (ops/remat.py) —
    # it compiles the step with nothing kept to read its size, once more
    # where something fits, and hands back the lowering it settled on
    on_tpu, stats = platform.on_tpu, platform.memory_stats
    platform.on_tpu = lambda: True
    platform.memory_stats = lambda device: {"bytes_limit": V5E_BYTES,
                                            "bytes_in_use": 0}
    try:
        return trainer.step_fn.lower(
            trainer.abstract_state(), {"inputs": tokens, "targets": tokens})
    finally:
        platform.on_tpu, platform.memory_stats = on_tpu, stats


def compile_program(name: str, devices, lowered=None):
    """``(compiled step, GiB a device it takes)`` of ``PROGRAMS[name]`` on
    the first devices of a described topology (``lowered``: its lowered
    step, where the caller has it). The caller has turned the persistent
    compile cache off: a described-TPU executable cannot be read back
    without a chip."""
    compiled = (lowered or lower_program(name, devices)).compile()
    mem = compiled.memory_analysis()
    return compiled, (mem.argument_size_in_bytes
                      + mem.temp_size_in_bytes) / 2**30


def kernel_counts(calls) -> dict:
    """``{kernel's name: how many calls}`` of ``mosaic_calls``' result."""
    counts: dict = {}
    for instruction, _ in calls:
        kernel = instruction.strip("%").split(".")[0]
        counts[kernel] = counts.get(kernel, 0) + 1
    return dict(sorted(counts.items()))


def mosaic_calls(text: str):
    """``[(instruction name, result type)]`` of the Mosaic kernels in a
    compiled program's text."""
    return [(line.split(" = ", 1)[0].strip(),
             line.split(" = ", 1)[1].split(" custom-call(")[0])
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def program_sha256(text: str) -> str:
    """SHA-256 of a lowered step's text without what moves with a checkout:
    each Mosaic payload (MLIR bytecode WITH its source's path and lines)
    stands as the hash of its operations printed without locations, and
    private functions lose jax's running numbers (``@tril_217``). Equal
    hashes: the same program, op for op."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def bare(found):
        with mlir.make_ir_context() as context:
            context.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(found.group(2)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return found.group(1) + hashlib.sha256(asm.encode()).hexdigest()

    text = re.sub(r'(body\\22: \\22)([A-Za-z0-9+/=]+)', bare, text)
    text = re.sub(r"(@[A-Za-z_][\w.]*?)_\d+\b", r"\1", text)
    return hashlib.sha256(text.encode()).hexdigest()


_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8}
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")


def _instructions(text: str):
    """``(computation, name, type, dims, bytes, opcode, line)`` of every
    array-valued instruction of a compiled program's text, fused
    computations' left out (what a fusion holds is one pass)."""
    computation = None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            computation = line.split()[1 if line.startswith("ENTRY") else 0]
            continue
        found = _INSTRUCTION.match(line)
        if not found or "fused_computation" in (computation or ""):
            continue
        name, kind, dims, opcode = found.groups()
        size = _ITEMSIZE.get(kind, 4)
        for d in filter(None, dims.split(",")):
            size *= int(d)
        yield computation, name, kind, dims, size, opcode, line


def movement(text: str, least: int = 1 << 20):
    """``({"<what> <type>[<dims>]": how many}, [copies in chunk loops])`` of
    a compiled step: its top-level ``copy``, ``transpose`` and
    ``*bitcast_fusion`` instructions of ``least`` bytes or more — data XLA
    moves for a layout's sake (PR 44) — and, of the expert layer's chunk
    loops (a ``while`` under the scope ``live_rows``: ``ops/moe.py
    _live_rows``), every ``copy`` in the body of an array as large as one
    the loop carries: a carried buffer that is not updated in place."""
    moves: collections.Counter = collections.Counter()
    bodies = {}
    for line in text.splitlines():
        if " while(" in line and "live_rows/while" in line:
            body = re.search(r"body=(%[\w.-]+)", line).group(1)
            bodies[body] = set(re.findall(r"\w+\[[\d,]+\]", line.split(
                " while(")[0]))
    in_loops = []
    for computation, name, kind, dims, size, opcode, line in \
            _instructions(text):
        what = name.split(".")[0] if opcode == "fusion" else opcode
        if size >= least and (opcode in ("copy", "transpose")
                              or what.endswith("bitcast_fusion")):
            moves[f"{what} {kind}[{dims}]"] += 1
        if opcode == "copy" and f"{kind}[{dims}]" in bodies.get(
                computation, ()) and size >= least:
            in_loops.append(f"{computation}: {name} {kind}[{dims}]")
    return dict(sorted(moves.items())), in_loops


def against(name: str, mine: dict, parents: dict) -> list:
    """What ``mine``, a program's census, holds against the parent
    commit's: nothing where a program without expert layers lowers to the
    parent's text, and one with them keeps its chunk loops free of copies
    and moves no large array the parent's did not."""
    if not mine["expert_layers"]:
        return [] if mine["lowered_sha256"] == parents["lowered_sha256"] \
            else [f"{name}: lowers to {mine['lowered_sha256'][:12]}, the "
                  f"parent to {parents['lowered_sha256'][:12]}"]
    more = collections.Counter(mine["moves"])
    more.subtract(parents["moves"])
    return [f"{name}: a chunk loop copies {copy}"
            for copy in mine["chunk_loop_copies"]] \
        + [f"{name}: {n} x {what} more than the parent"
           for what, n in sorted(more.items()) if n > 0]


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--tree")
    parser.add_argument("--census")
    parser.add_argument("--against")
    parser.add_argument("names", nargs="*", metavar="NAME")
    options = parser.parse_args()
    if options.tree:
        sys.path.remove(REPO)
        sys.path.insert(0, os.path.abspath(options.tree))
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    over, census = [], {}
    parents = json.load(open(options.against)) if options.against else {}
    compare = bool(options.against or options.census)
    for name in options.names or list(PROGRAMS):
        (factory, _), key, batch, accum, optimizer, limit = PROGRAMS[name]
        t0 = time.perf_counter()
        if compare:
            lowered = lower_program(name, topo.devices)
            compiled, gib = compile_program(name, topo.devices, lowered)
        else:
            compiled, gib = compile_program(name, topo.devices)
        dt = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        calls = mosaic_calls(text)
        shapes = sorted({re.sub(r"\{[^}]*\}", "", out) for _, out in calls})
        forwards = sum(any(kernel in instruction for kernel in (
            "flash_fwd", "mla_fwd", "swa_fwd")) for instruction, _ in calls)
        collectives = {op: len(re.findall(rf" {op}(?:-start)?\(", text))
                       for op in ("all-gather", "all-reduce", "all-to-all",
                                  "reduce-scatter", "collective-permute")}
        # q/k/v are [rows, 1024, heads, 64] activations: an all-gather of
        # that rank with rows > 1 in front of a kernel would undo the
        # per-shard call. (Rows == 1 is a layer's FSDP weight gather.)
        gathered = sorted({re.sub(r"\{[^}]*\}", "", m) for m in re.findall(
            r"= (\S+) all-gather(?:-start)?\(", text)})
        kernels = kernel_counts(calls)
        print(f"{name}: {factory} mesh {key} batch {batch} accum {accum} "
              f"{optimizer}: compiled in {dt:.1f}s; per device: arguments "
              f"{mem.argument_size_in_bytes / 2**30:.2f} GiB + temporaries "
              f"{mem.temp_size_in_bytes / 2**30:.2f} GiB = {gib:.3f} GiB"
              + (f" (limit {limit})" if limit else "") + f"; "
              f"{len(calls)} Mosaic calls, {forwards} of them a flash forward "
              f"(flash_fwd, mla_fwd, swa_fwd): {kernels}; "
              f"outputs {shapes}; "
              f"collectives {collectives}; all-gathered shapes {gathered}",
              flush=True)
        if limit and gib > limit:
            over.append(f"{name}: {gib:.3f} GiB a device, over its {limit}")
        attention = {kernel: n for kernel, n in kernels.items()
                     if kernel.startswith(("flash_", "mla_", "swa_", "bd_",
                                           "diff_", "dsa_"))}
        if attention != ATTENTION_KERNELS.get(name, attention):
            over.append(f"{name}: attention kernels {attention}, not "
                        f"{ATTENTION_KERNELS[name]}")
        mamba = {kernel: n for kernel, n in kernels.items()
                 if kernel.startswith(("ssd_", "conv1d_", "sscan_", "kda_"))}
        if limit and mamba != MAMBA_KERNELS.get(name, {}):
            over.append(f"{name}: Mamba-2 kernels {mamba}, not "
                        f"{MAMBA_KERNELS.get(name, {})}")
        if compare:
            moves, in_loops = movement(text)
            census[name] = {
                "lowered_sha256": program_sha256(lowered.as_text()),
                "expert_layers": "rows_to_tokens" in kernels,
                "moves": moves, "chunk_loop_copies": in_loops,
                "gib": round(gib, 3)}
            print(f"{name}: lowered {census[name]['lowered_sha256'][:12]}; "
                  f"{sum(moves.values())} copies, transposes and bitcast "
                  f"fusions of a megabyte or more; {len(in_loops)} copies "
                  f"inside a chunk loop", flush=True)
            if name in parents:
                over.extend(against(name, census[name], parents[name]))
    if options.census:
        with open(options.census, "w") as f:
            json.dump(census, f, indent=1, sort_keys=True)
    if over:
        sys.exit("; ".join(over))


if __name__ == "__main__":
    main()
