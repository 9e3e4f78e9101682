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
the exit code 1. A compile that passes is not a run.

Usage: JAX_PLATFORMS=cpu python scripts/rehearse_tpu_compile.py [NAME ...]
       (names: the keys of PROGRAMS; default: all)
"""

from __future__ import annotations

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
#: shared block, kernels or policy may not grow them unseen.
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
    "laguna_1x2": (LAGUNA, "dp=1", 2, 1, "adamw", 15.05),
    "zaya_1x2": (ZAYA, "dp=1", 2, 1, "adamw", 14.85),
    "joyai_1x2": (JOYAI, "dp=1", 2, 1, "adamw", 14.55),
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
    "nemotron_1x2": (NEMOTRON, "dp=1", 2, 1, "adamw", 14.05),
    "mellum_1x2": (MELLUM, "dp=1", 2, 1, "adamw", 12.1),
}

#: name -> the attention kernels (``flash_*``, ``mla_*``, ``swa_*``) a cell's
#: compiled step holds, by name: the second gate, exit code 1 like the first.
#: A forward kernel stands once where its ``out`` and ``lse`` are kept and
#: twice where remat makes them again: medium's two copies of the step's body
#: (the first microbatch, then the scan) hold it twice each under ``dots``;
#: under ``full`` ZAYA1's six scanned layers hold it ONCE and JoyAI-LLM-Flash's
#: four scanned layers once beside the dense layer's two (PR 38: 2 and 4 if
#: the scanned run made it again). ``flash_bwd`` / ``mla_bwd`` is the looped
#: backward as ONE call (PR 39); ``*_bwd_dq`` + ``*_bwd_dkv`` the unrolled one.
ATTENTION_KERNELS = {
    "medium_4x8": {"flash_bwd_dkv": 2, "flash_bwd_dq": 2, "flash_fwd": 4},
    "worker_4x8": {"flash_bwd_dkv": 2, "flash_bwd_dq": 2, "flash_fwd": 4},
    "xl_fsdp4": {"flash_bwd_dkv": 1, "flash_bwd_dq": 1, "flash_fwd": 2},
    "hybrid_4x2": {"flash_bwd": 2, "flash_fwd": 2},
    "ouro_4x1": {"flash_bwd": 2, "flash_fwd": 4},
    "laguna_1x2": {"flash_bwd": 2, "flash_fwd": 2, "swa_bwd_dkv": 1,
                   "swa_bwd_dq": 1, "swa_fwd": 2},
    "zaya_1x2": {"flash_bwd": 1, "flash_fwd": 1},
    "joyai_1x2": {"mla_bwd": 3, "mla_fwd": 3},
    "nemotron_1x2": {"flash_bwd": 1, "flash_fwd": 1},
    # the three window-1,024 layers are one scanned run on the band path
    # (``swa_fwd`` twice: remat makes it again), the full layer keeps its
    # forward's results
    "mellum_1x2": {"flash_bwd": 1, "flash_fwd": 1, "swa_bwd_dkv": 1,
                   "swa_bwd_dq": 1, "swa_fwd": 2},
}

#: name -> the Mamba-2 mixer's kernels (ops/ssd.py: the scan's ``ssd_*``,
#: PR 43; the convolutions' ``conv1d_*``, PR 44) a cell's compiled step holds,
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
}


def compile_program(name: str, devices):
    """``(compiled step, GiB a device it takes)`` of ``PROGRAMS[name]`` on
    the first devices of a described topology. The caller has turned the
    persistent compile cache off: a described-TPU executable cannot be read
    back without a chip."""
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
    on_tpu, platform.on_tpu = platform.on_tpu, lambda: True
    try:
        compiled = trainer.step_fn.lower(
            trainer.abstract_state(),
            {"inputs": tokens, "targets": tokens}).compile()
    finally:
        platform.on_tpu = on_tpu
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


def main() -> None:
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    over = []
    for name in sys.argv[1:] or list(PROGRAMS):
        (factory, _), key, batch, accum, optimizer, limit = PROGRAMS[name]
        t0 = time.perf_counter()
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
                     if kernel.startswith(("flash_", "mla_", "swa_"))}
        if attention != ATTENTION_KERNELS.get(name, attention):
            over.append(f"{name}: attention kernels {attention}, not "
                        f"{ATTENTION_KERNELS[name]}")
        mamba = {kernel: n for kernel, n in kernels.items()
                 if kernel.startswith(("ssd_", "conv1d_"))}
        if limit and mamba != MAMBA_KERNELS.get(name, {}):
            over.append(f"{name}: Mamba-2 kernels {mamba}, not "
                        f"{MAMBA_KERNELS.get(name, {})}")
    if over:
        sys.exit("; ".join(over))


if __name__ == "__main__":
    main()
