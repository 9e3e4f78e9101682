"""scope_reduce and the new readers, on what was recorded on the chip in
PR 23: two stretches of a traced step of gpt2-medium.steady with every
operation's path, the distinct paths and kernel names of both steady cells
(``chip_step_scopes.json``: see its ``about``), and the timeline of a
kill-resume run with the phases PR 23 added (``chip_kill_resume_phases.json``).
"""

import glob
import importlib.util
import json
import os

import pytest

from conftest import BENCH, HERE
from lib import hlo, peaks, scope_reduce as sr, told, trace_reduce as tr

DEVICE_READERS = (
    "fwd_time_pct", "bwd_time_pct", "remat_time_pct", "attn_time_pct",
    "ffn_time_pct", "head_loss_time_pct", "optimizer_time_pct",
    "accum_time_pct", "unscoped_time_pct", "flash_fwd_roofline",
    "flash_dq_roofline", "flash_dkv_roofline")
ELASTIC_READERS = (
    "resume_reap_s", "resume_decide_s", "resume_runtime_s", "resume_build_s",
    "resume_trace_lower_s", "resume_program_load_s", "resume_cache_misses",
    "ckpt_snapshot_s", "ckpt_write_mb_s")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load(name):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def scopes():
    fixture = load("chip_step_scopes.json")
    table = fixture["path_table"]
    fixture["trace"]["paths"] = {
        name: table[i] for name, i in fixture["paths"].items()}
    return fixture


# ------------------------------------------------------------ classify
@pytest.mark.parametrize("path,expect", [
    ("jit(train_step)/jvp(Transformer)/while/body/closed_call/blocks/"
     "attention/multihead_attention/flash_fwd/pallas_call",
     ("fwd", "attention")),
    ("jit(train_step)/transpose(jvp(Transformer))/while/body/closed_call/"
     "checkpoint/rematted_computation/blocks/attention/multihead_attention/"
     "flash_fwd/pallas_call", ("remat", "attention")),
    ("jit(train_step)/transpose(jvp(Transformer))/while/body/closed_call/"
     "checkpoint/blocks/attention/multihead_attention/flash_bwd_dq/"
     "pallas_call", ("bwd", "attention")),
    ("jit(train_step)/transpose(jvp(Transformer))/while/body/closed_call/"
     "checkpoint/blocks/ffn/down/dot_general", ("bwd", "ffn")),
    ("jit(train_step)/jvp(Transformer)/lm_head/tok_emb.attend/dot_general",
     ("fwd", "head_loss")),
    ("jit(train_step)/transpose(jvp(loss))/jit(take_along_axis)/scatter-add",
     ("bwd", "head_loss")),
    ("jit(train_step)/jvp(lm_head_loss)/while/body/dot_general",
     ("fwd", "head_loss")),
    ("jit(train_step)/optimizer/mul", ("none", "optimizer")),
    ("jit(train_step)/grad_norm/sqrt", ("none", "optimizer")),
    ("jit(train_step)/while/body/closed_call/accumulate/add",
     ("none", "accumulate")),
    ("jit(train_step)/jvp(cast_params)/convert_element_type",
     ("fwd", "other")),
    ("jit(train_step)/transpose(jvp(Transformer))/tok_emb/jit(_take)/"
     "scatter-add", ("bwd", "other")),
    ("jit(train_step)/jvp(Transformer)/while/body/dynamic_slice",
     ("fwd", "other")),
    ("jit(train_step)/jvp()/mul", ("fwd", "unscoped")),
    ("jit(train_step)/while/body/add", ("none", "unscoped")),
    ("jit(train_step)/jit(_where)/select_n", ("none", "unscoped")),
    ("dot_general", ("none", "unscoped")),
    ("", ("none", "unscoped")),
])
def test_classify(path, expect):
    found = sr.classify(path)
    assert (found["pass"], found["part"]) == expect


@pytest.mark.parametrize("cell", ["medium", "xl"])
def test_every_recorded_path_has_one_pass_and_one_part(scopes, cell):
    paths = scopes["distinct_paths"] if cell == "medium" \
        else scopes["xl"]["distinct_paths"]
    assert len(paths) > 300
    seen = set()
    for path in paths:
        found = sr.classify(path)
        assert found["pass"] in sr.PASSES and found["part"] in sr.PARTS
        seen.add((found["pass"], found["part"]))
    # the whole table of the step, both partitions crossed
    for cellkey in (("fwd", "attention"), ("bwd", "attention"),
                    ("remat", "attention"), ("fwd", "ffn"), ("bwd", "ffn"),
                    ("fwd", "head_loss"), ("bwd", "head_loss"),
                    ("none", "optimizer"), ("fwd", "other"),
                    ("bwd", "other")):
        assert cellkey in seen, cellkey
    # only a cell that accumulates has the scope
    assert (("none", "accumulate") in seen) == (cell == "medium")
    # nothing outside the differentiated function claims a pass, and
    # nothing of the trainer's own sits inside it
    assert not {(p, "optimizer") for p in ("fwd", "bwd", "remat")} & seen
    assert not {(p, "accumulate") for p in ("fwd", "bwd", "remat")} & seen


# --------------------------------------------------------------- shares
def test_both_partitions_sum_to_100_and_cover_the_busy_time(scopes):
    found = sr.shares(scopes["trace"])
    assert sum(found["pass_pct"].values()) == pytest.approx(100.0)
    assert sum(found["part_pct"].values()) == pytest.approx(100.0)
    assert sum(found["table_pct"].values()) == pytest.approx(100.0)
    assert set(found["pass_pct"]) == set(sr.PASSES)
    assert set(found["part_pct"]) == set(sr.PARTS)
    # self time by containment counts every busy nanosecond once
    assert found["total_s"] == pytest.approx(
        sum(tr.time_by_op(scopes["trace"]).values()))
    # the stretches hold the step's end: every part but attention and FFN is
    # over-represented against the whole step (PERF.md section 5)
    assert found["part_pct"]["optimizer"] == pytest.approx(6.681, abs=1e-2)
    assert found["part_pct"]["accumulate"] == pytest.approx(2.344, abs=1e-2)
    assert found["part_pct"]["head_loss"] == pytest.approx(7.105, abs=1e-2)
    assert found["part_pct"]["unscoped"] == pytest.approx(0.526, abs=1e-2)
    assert found["pass_pct"]["remat"] == pytest.approx(1.383, abs=1e-2)
    assert sr.named_parts(scopes["trace"])


def test_a_program_without_names_has_passes_and_parts_to_say_nothing_of(
        scopes):
    bare = dict(scopes["trace"], paths={
        name: path.rsplit("/", 1)[-1]
        for name, path in scopes["trace"]["paths"].items()})
    assert not sr.named_parts(bare) and not sr.whole_paths(bare)
    assert sr.whole_paths(scopes["trace"])
    # the parent's executable on the chip (PR 23 call 7): bare primitives,
    # but for a few helper calls that keep a short stack of their own
    few = dict(bare["paths"])
    for name in list(few)[:20]:
        few[name] = "jit(train_step)/jvp(loss)/jit(take_along_axis)/add"
    assert not sr.whole_paths(dict(bare, paths=few))
    found = sr.shares(bare)
    assert found["part_pct"]["unscoped"] == pytest.approx(100.0)
    assert found["pass_pct"]["none"] == pytest.approx(100.0)
    assert sr.shares({"devices": {}, "host": [], "paths": {}}) is None


# -------------------------------------------------------------- kernels
KERNELS = {"flash_fwd_roofline": "flash_fwd",
           "flash_dq_roofline": "flash_bwd_dq",
           "flash_dkv_roofline": "flash_bwd_dkv"}


def test_kernels_told_by_name_on_one_chip_and_under_shard_map(scopes):
    """The kernel a GPT-2 cell's module names for each roofline is on the
    recorded calls' paths, on one chip and under ``shard_map``, and agrees
    with the kind ``lib/hlo.py`` reads from the call's own name."""
    from lib import cell_gpt2

    stated = cell_gpt2.kernels({})
    assert {q: k.name for q, k in stated.items()} == KERNELS
    kind_of = {"flash_fwd": "fwd", "flash_bwd_dq": "dq",
               "flash_bwd_dkv": "dkv"}
    for calls, paths in (
            (scopes["flash_calls"], scopes["trace"]["paths"]),
            (scopes["xl"]["flash_calls"], scopes["xl"]["kernel_paths"])):
        have = [c for c in calls if c["name"] in paths]
        assert have
        for call in have:
            names = sr.names_on(paths[call["name"]])[1]
            kernel, = (k for k in kind_of if k in names)
            assert kind_of[kernel] == call["kind"]
            assert hlo.kernel_name(call["name"], "") == kernel
    xl = scopes["xl"]
    assert len(xl["kernel_paths"]) >= len(xl["flash_calls"])
    assert all("shard_map" in xl["kernel_paths"][c["name"]]
               for c in xl["flash_calls"])


def test_kernel_rooflines_weighted_by_time_give_flash_roofline(
        scopes, monkeypatch):
    trace = scopes["trace"]
    ops = tr.ops_by_name(trace)
    artifacts = {"trace_summary": {"ops": ops},
                 "flash_calls": scopes["flash_calls"],
                 "config": {"readers": {"module": "cell_gpt2"}},
                 "device": {"kind": "TPU v5 lite"}}
    monkeypatch.setattr(sr, "of_run", lambda artifacts: {
        "paths": trace["paths"], "whole_paths": True})
    whole = reader("flash_roofline")(artifacts)
    weighted = took = 0.0
    each = {}
    for quantity, kernel in KERNELS.items():
        pct = told.kernel_roofline_pct(artifacts, quantity)
        seconds = sum(
            ops[c["name"]]["seconds"] for c in scopes["flash_calls"]
            if c["name"] in ops and kernel in sr.names_on(
                trace["paths"].get(c["name"], ""))[1])
        each[kernel] = pct
        weighted += pct * seconds
        took += seconds
    assert weighted / took == pytest.approx(whole)
    # per call at [128, 1024, 64]: forward 14.8%, dq 24.9%, dkv 20.2%
    assert each["flash_fwd"] == pytest.approx(14.8, abs=0.1)
    assert each["flash_bwd_dq"] == pytest.approx(24.9, abs=0.1)
    assert each["flash_bwd_dkv"] == pytest.approx(20.2, abs=0.15)
    # a cell whose backward is unrolled has no one-call backward to read
    assert told.kernel_roofline_pct(artifacts, "flash_bwd_roofline") is None


# ------------------------------------------------------------- the file
def test_wire_reader_on_a_message_made_by_hand():
    # field 1 varint 300; field 2 bytes "ab"; field 3 fixed32; field 4 fixed64
    raw = bytes([0x08, 0xAC, 0x02, 0x12, 0x02, 0x61, 0x62,
                 0x1D, 1, 0, 0, 0, 0x21, 2, 0, 0, 0, 0, 0, 0, 0])
    got = [(n, v if isinstance(v, int) else bytes(v))
           for n, v in sr.fields(memoryview(raw))]
    assert got == [(1, 300), (2, b"ab"), (3, bytes([1, 0, 0, 0])),
                   (4, bytes([2, 0, 0, 0, 0, 0, 0, 0]))]
    with pytest.raises(ValueError, match="wire type"):
        list(sr.fields(memoryview(bytes([0x0B]))))


def test_op_paths_from_a_trace_file_written_here(tmp_path):
    """The real file format, on the CPU: a traced jit with a named scope in
    a scan under grad. The device planes are a TPU's, so nothing is timed —
    only the embedded HLO's paths are read."""
    import jax
    import jax.numpy as jnp

    def f(x):
        def body(c, _):
            with jax.named_scope("inner_scope"):
                return jnp.sin(c) * 2.0, None
        with jax.named_scope("outer_scope"):
            y, _ = jax.lax.scan(body, x, None, length=3)
        return y.sum()

    g = jax.jit(jax.grad(f))
    x = jnp.ones((4,))
    g(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    g(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    paths = sr.op_paths(path)
    scoped = [p for p in paths.values() if "inner_scope" in p]
    assert scoped and all("outer_scope" in p for p in scoped)
    assert {sr.classify(p)["pass"] for p in scoped} >= {"fwd", "bwd"}
    assert sr.load(path)["devices"] == {}  # no TPU plane in a CPU trace


def test_no_device_trace_no_number():
    """A run that took no device trace (``--trace 0``, the CPU rehearsal,
    the elastic cell): every device reader returns None and none raises."""
    for artifacts in ({}, {"trace_summary": None, "flash_calls": []}):
        for name in DEVICE_READERS:
            assert reader(name)(artifacts) is None, name


def test_flash_calls_reads_named_kernels():
    """``lib/hlo.flash_calls`` keys on the custom call's target and tells
    the kernel by the name on the call's path; the text is the chip's
    (PR 23 chip call 3)."""
    line = (
        '  %flash_fwd.25 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}, '
        'f32[128,1024,1]{2,1,0:T(8,128)}) custom-call(%bitcast.1170, '
        '%bitcast.1174, %bitcast.1175), custom_call_target="tpu_custom_call"'
        ', metadata={op_name="jit(train_step)/jvp(Transformer)/while/body/'
        'closed_call/blocks/attention/multihead_attention/flash_fwd/'
        'pallas_call" stack_frame_id=40}')
    assert hlo.flash_calls(line) == [{
        "name": "flash_fwd.25", "kernel": "flash_fwd", "kind": "fwd",
        "batch_heads": 128, "seq": 1024, "head_dim": 64}]


# ------------------------------------------------------------- elastic
def test_elastic_readers_on_a_timeline_from_before_the_phases():
    old = load("chip_kill_resume.json")
    for name in ELASTIC_READERS:
        assert reader(name)(old) is None, name
    for name in ELASTIC_READERS:  # and on a run with no kill at all
        assert reader(name)({"t_kill": None, "timeline": [],
                             "records": []}) is None, name


def test_elastic_readers_on_the_recorded_phases():
    run = load("chip_kill_resume_phases.json")
    read = {name: reader(name)(run) for name in ELASTIC_READERS + (
        "resume_detect_s", "resume_boot_s", "resume_first_step_s",
        "save_stall_s", "ckpt_commit_s")}
    expect = run["expect"]
    for name in ELASTIC_READERS:
        assert read[name] == pytest.approx(expect[name], abs=2e-3), name
    # the two legs of detection, and the five of the boot, add up
    assert read["resume_reap_s"] + read["resume_decide_s"] == pytest.approx(
        read["resume_detect_s"], abs=1e-6)
    gen = 2
    from lib import timeline_reduce as tl
    legs = ["spawn", "worker_main_start", "jax_imported", "dist_init_done",
            "devices_ready", "trainer_built"]
    boot = sum(tl.phase_span_s(run["timeline"], gen, a, b)
               for a, b in zip(legs, legs[1:]))
    assert boot == pytest.approx(read["resume_boot_s"], abs=1e-6)
    # directive_t lies between the crash and the spawn it caused
    spawn = next(e for e in run["timeline"]
                 if e["phase"] == "spawn" and e["gen"] == gen)
    crash = next(e for e in run["timeline"] if e["phase"] == "worker_crash")
    assert crash["t"] <= spawn["directive_t"] <= spawn["t"]
    assert crash["gen"] == run["killed_generation"] and crash["code"] == -9
    # the snapshot is the save's stall; the commit is the snapshot, the
    # chunks and the marker
    assert read["ckpt_snapshot_s"] == pytest.approx(read["save_stall_s"],
                                                    abs=0.1)
    assert read["resume_cache_misses"] == 0
    assert read["resume_trace_lower_s"] + read["resume_program_load_s"] < \
        read["resume_first_step_s"]
