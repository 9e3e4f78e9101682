"""Closed-loop training through ``Trainer.train_step``, in this process.

One process drives the cell's chips. Set-up: devices, the model bundle, the
comparison with the plain reference (its buffers freed before the trainer's
state exists), state made on the device from the seed, the step program
compiled ahead (its memory analysis and HLO are artifacts), warm-up steps.
Window: steps until ``--seconds`` have passed, the loss fetched after every
step as ``models/run.py`` and the elastic worker do; the clock stops when the
last step's loss is on the host. A traced run then profiles a few more steps
with ``jax.profiler`` and the benchmark's own host annotations.

Two keys of the traffic file, both absent in a mix that does not need them,
keep a run's work and time the cell's own (PERF.md section 2, "What steadies
a run"):

- ``dispatch_ahead_steps`` (0): how many steps may be dispatched beyond the
  one whose loss is awaited. At 0 the loop is closed. Above 0 the chip stays
  fed while the host stands still for as long as that many steps take; losses
  are read that many steps late; when the time is up nothing more is sent,
  every step that was sent is waited for, and the clock is read after that
  wait: all of that work over all of that time.
- ``weights_seed`` (``--seed``): the seed of the TRAINED state, where the
  weights decide how much work a step is (a top-1 router's load). The
  batches, the check's weights and its sequences stay ``--seed``'s.

Reads from the traffic file besides: ``global_batch``, ``grad_accum``,
``optimizer`` (an optax factory and its arguments), ``tokens.support``,
``warmup_steps``, ``trace_steps``. Reads from the configuration file:
``platform``, ``chips``, ``mesh``, ``factory``, ``kwargs``, ``check``.
"""

from __future__ import annotations

import collections
import contextlib
import glob
import importlib
import math
import os
import time
from typing import Any, Dict


def drive(trainer: Any, state: Any, data: Any, ahead: int, more: Any,
          span: Any = None):
    """Steps while ``more()``, at most ``ahead`` of them dispatched beyond the
    one whose loss is awaited; then every step that was sent is waited for.
    Returns the state, each loss and the host clock as it arrived. ``span``
    names the host's three parts of a step in a traced run."""
    span = span or (lambda name: contextlib.nullcontext())
    sent = collections.deque()
    losses, arrived = [], []

    def fetch():
        with span("bench/fetch_loss"):
            losses.append(float(sent.popleft()["loss"]))  # blocks: it is done
        arrived.append(time.perf_counter())

    while more():
        with span("bench/next_data"):
            host_batch = next(data)
        with span("bench/dispatch"):
            state, metrics = trainer.train_step(state, host_batch)
        sent.append(metrics)
        while len(sent) > ahead:
            fetch()
    while sent:
        fetch()
    return state, losses, arrived


def run(run: Any) -> Dict[str, Any]:
    import jax
    import optax

    from easydl_tpu.utils.env import configure_compile_cache

    from lib import devices as dev, flops, hlo, program, trace_reduce, traffic
    from lib.compile_watch import CompileWatch

    config, mix = run.config, run.traffic
    marks = {}  # seconds since process start at the end of each set-up part

    def mark(name):
        marks[name] = time.time() - run.t_start

    mark("imports")
    configure_compile_cache()
    watch = CompileWatch()
    devices = dev.require(config["platform"], run.cell["chips"])
    mark("devices")
    kwargs = config["kwargs"]
    opt = mix["optimizer"]
    bundle, trainer = program.build_trainer(
        config, mix["global_batch"], mix["grad_accum"],
        getattr(optax, opt["name"])(**opt["args"]),
        mix.get("weights_seed", run.seed), devices)
    ahead = mix.get("dispatch_ahead_steps", 0)

    checker = importlib.import_module(f"lib.{config['check']['module']}")
    check = checker.check(config, bundle, trainer, run.seed)
    print(f"benchmark: reference check {check}", flush=True)
    mark("reference_check")

    state = trainer.init_state()
    jax.block_until_ready(state)
    mark("init_state")
    seq_len = kwargs["seq_len"]
    data = traffic.token_batches(run.seed, mix["global_batch"], seq_len,
                                 kwargs["vocab"], mix["tokens"]["support"])
    batch = next(data)
    compiled = trainer.step_fn.lower(state, trainer.shard_batch(batch)).compile()
    memory = hlo.step_memory(compiled)
    mark("step_compiled")
    for _ in range(mix["warmup_steps"]):
        state, metrics = trainer.train_step(state, batch)
        batch = next(data)
    float(metrics["loss"])

    # ------------------------------------------------------------ window
    compiles_before = watch.events
    t_open = time.perf_counter()
    setup_s = time.time() - run.t_start
    state, losses, arrived = drive(
        trainer, state, data, ahead,
        lambda: time.perf_counter() - t_open < run.seconds)
    elapsed_s = arrived[-1] - t_open  # read after the last wait
    # a step's time: from the loss before it (the window's opening) to its own
    step_s = [b - a for a, b in zip([t_open] + arrived, arrived)]
    compiles_in_window = watch.events - compiles_before

    traced = {}
    if run.trace:
        trace_dir = os.path.join(run.workdir, "trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans are the benchmark's own
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench/window"):
            left = iter(range(mix["trace_steps"]))
            state, more_losses, _ = drive(
                trainer, state, data, ahead,
                lambda: next(left, None) is not None,
                jax.profiler.TraceAnnotation)
            losses += more_losses
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        trace = trace_reduce.load_xplane(path)
        summary = trace_reduce.summarise(trace)
        if summary is None and config["platform"] == "tpu":
            raise SystemExit("benchmark: the trace holds no device "
                             "operation. No result printed.")
        traced = {"trace_summary": summary,
                  "flash_calls": hlo.flash_calls(compiled.as_text())}
        if summary is not None:
            traced["busy"] = {"busy_s": summary["busy_s"],
                              "window_s": summary["window_s"],
                              "busy_source": "device_trace"}
            traced["breakdown"] = {"device_ops": summary["top_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
        if run.keep_raw:  # for looking at a trace by hand
            traced.update(trace=trace,
                          trace_describe=trace_reduce.describe_xplane(path))

    shapes = jax.eval_shape(bundle.init_fn, jax.random.PRNGKey(0))
    n_params = flops.count_params(shapes)
    n_window = len(step_s)
    allocator_peak = dev.peak_bytes_in_use(devices)
    return {
        **traced,
        "device": dev.describe(devices),
        "chips": len(devices),
        "memory_peak_bytes": max(
            allocator_peak,
            memory["argument_bytes"] + memory["temp_bytes"]
            + memory["output_bytes"] - memory["alias_bytes"]),
        "allocator_peak_bytes": allocator_peak,
        "setup_s": setup_s,
        "setup_marks_s": marks,
        "window_s": elapsed_s,
        "steps": n_window,
        "tokens_per_step": mix["global_batch"] * seq_len,
        "step_s": step_s,
        "dispatch_ahead_steps": ahead,
        "weights_seed": mix.get("weights_seed", run.seed),
        "losses": losses[:n_window],
        "attempted": n_window,
        "failed": sum(1 for x in losses if not math.isfinite(x)),
        "correct": bool(check["ok"]),
        "check": check,
        "compile": {"compile_s": watch.seconds, "events": watch.events,
                    "cache_hits": watch.hits, "cache_misses": watch.misses,
                    "cache_saved_s": watch.saved,
                    "compiles_in_window": compiles_in_window},
        "step_memory": memory,
        "n_params": n_params,
    }
