#!/usr/bin/env python3
"""Compile the real-size train step for a DESCRIBED v5e 2x2 — no chip needed.

The third rehearsal of /opt/skills/guides/on-chip-measurement (section 2):
the TPU compiler is installed here and compiles for a topology that is
described, not attached. What it refuses here (a Mosaic kernel GSPMD cannot
partition, a program that does not fit 16 GB) costs no chip time. Compiles
chip_smoke.py's programs — GPT-2 345M, seq 1024, bf16, remat "dots", flash
attention — and prints, per program, the bytes on each device, the Mosaic
calls and their per-device operand shapes, and the collectives in front of
them. A compile that passes is not a run.

Usage: JAX_PLATFORMS=cpu python scripts/rehearse_tpu_compile.py [NAME ...]
       (names: one dp4 fsdp2tp2 one_accum; default: all)
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

#: name -> (mesh shape key, global batch, grad_accum)
PROGRAMS = {
    "one": ("dp=1", 8, 1),            # chip_smoke train/resume/elastic
    "one_accum": ("dp=1", 32, 8),     # chip_smoke --mesh, the comparison
    "dp4": ("dp=4", 32, 1),
    "fsdp2tp2": ("fsdp=2,tp=2", 32, 1),
}


def main() -> None:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from easydl_tpu.core.mesh import MeshSpec, build_mesh
    from easydl_tpu.core.train_loop import TrainConfig, Trainer
    from easydl_tpu.models.registry import get_model

    # A described-TPU executable cannot be read back without a chip.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # attention_impl="flash", stated: "auto" asks jax.devices(), which is
    # the CPU here, and would compile the reference path instead.
    bundle = get_model("gpt", size="345m", seq_len=1024, dtype="bfloat16",
                       remat=True, remat_policy="dots",
                       attention_impl="flash")
    for name in sys.argv[1:] or list(PROGRAMS):
        key, batch, accum = PROGRAMS[name]
        spec = MeshSpec.parse(key)
        trainer = Trainer(
            init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
            optimizer=optax.adamw(1e-3),
            config=TrainConfig(global_batch=batch, grad_accum=accum),
            mesh=build_mesh(spec, devices=topo.devices[:spec.size]))
        tokens = jax.ShapeDtypeStruct((batch, 1024), jnp.int32)
        t0 = time.perf_counter()
        compiled = trainer.step_fn.lower(
            trainer.abstract_state(),
            {"inputs": tokens, "targets": tokens}).compile()
        dt = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        kernels = [line.split(" = ", 1)[1].split(" custom-call(")[0]
                   for line in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in line]
        shapes = sorted({re.sub(r"\{[^}]*\}", "", out) for out in kernels})
        collectives = {op: len(re.findall(rf" {op}(?:-start)?\(", text))
                       for op in ("all-gather", "all-reduce", "all-to-all",
                                  "reduce-scatter", "collective-permute")}
        # q/k/v are [rows, 1024, heads, 64] activations: an all-gather of
        # that rank with rows > 1 in front of a kernel would undo the
        # per-shard call. (Rows == 1 is a layer's FSDP weight gather.)
        gathered = sorted({re.sub(r"\{[^}]*\}", "", m) for m in re.findall(
            r"= (\S+) all-gather(?:-start)?\(", text)})
        print(f"{name}: mesh {key} batch {batch} accum {accum}: compiled in "
              f"{dt:.1f}s; per device: arguments "
              f"{mem.argument_size_in_bytes / 2**30:.2f} GiB + temporaries "
              f"{mem.temp_size_in_bytes / 2**30:.2f} GiB; "
              f"{len(kernels)} Mosaic calls, outputs {shapes}; "
              f"collectives {collectives}; all-gathered shapes {gathered}",
              flush=True)


if __name__ == "__main__":
    main()
