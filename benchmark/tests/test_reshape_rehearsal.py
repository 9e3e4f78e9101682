"""The elastic driver's second mesh (PR 57), rehearsed on the CPU with four
host devices at the ``test`` size: a job saved under ``dp=4``, killed, and
resumed under ``fsdp=2,tp=2`` through the master's own mesh-shape policy, the
kill and the resume in set-up and the window on the resumed job, whose rate
is all its steps over all its time; the comparison with the checkpoint's own
files that refuses a restore altered by one bit, under one mesh or under
both alike; and that a mix without the new keys builds the job and the
schedule the driver built before them."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT
from test_rehearsal import last_line, run_py

TEST_JSON = os.path.join(HERE, "BENCHMARK.reshape-test.json")
CELL = "gpt2-test.reshape-resume"
REAL = "gpt2-medium.reshape-resume"

spec = importlib.util.spec_from_file_location(
    "elastic_driver", os.path.join(BENCH, "drivers", "elastic.py"))
elastic = importlib.util.module_from_spec(spec)
spec.loader.exec_module(elastic)


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def artifacts():
    return load(BENCH, ".work", CELL, "artifacts.json")


@pytest.mark.parametrize("trace", [0, 1])
def test_saved_under_one_mesh_resumed_under_another(trace):
    line = last_line(run_py(
        ["--benchmark-json", TEST_JSON, "--workload", CELL, "--seed",
         "2147483701", "--seconds", "25", "--trace", str(trace)], devices=4))
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    # every number compared, beside its limit, as the line's last key
    assert list(line)[-1] == "compared"
    compared = line["compared"]
    assert set(compared) == {
        "replay_loss_rel_1", "replay_loss_rel_2", "replay_loss_rel_3",
        "records_on_another_mesh", "restored_leaves_that_differ"}
    assert all(number <= limit for number, limit in compared.values())
    run = artifacts()
    mix = run["traffic"]
    n = mix["save_every"]
    # the kill falls behind C0 and the resume restores it: both in set-up,
    # the window opens at the resumed generation's first record
    killed = [r for r in run["records"]
              if r["generation"] <= run["killed_generation"]]
    assert max(r["step"] for r in killed) >= n + mix["kill_after_save_steps"]
    assert run["restored_step"] == n
    assert run["commits"][str(n)] <= run["t_kill"] < run["t_open"]
    first = min((r for r in run["records"]
                 if r["generation"] > run["killed_generation"]),
                key=lambda r: r["t"])
    assert first["step"] == n + 1
    assert 0 <= run["t_open"] - first["t"] < 1.0
    assert 0 < run["t_open"] - run["t_kill"] < run["setup_s"]
    # the records' mesh changes at the kill, and nowhere else
    later = [r for r in run["records"]
             if r["generation"] > run["killed_generation"]]
    assert {r["mesh"] for r in killed} == {"dp=4"}
    assert later and {r["mesh"] for r in later} == {"fsdp=2,tp=2"}
    # it is the master's policy that said so: the pin moved on it
    job = os.path.join(BENCH, ".work", CELL, "job")
    assert load(job, "job.json")["mesh_policy"] == {"pin": "dp=4"}
    with open(os.path.join(job, "events.jsonl")) as f:
        decided = [e for e in map(json.loads, f)
                   if e["kind"] == "mesh_shape"]
    assert [e["mesh"] for e in decided] == ["dp=4", "fsdp=2,tp=2"]
    assert all(e["inputs"]["reason"] == "pinned" for e in decided)
    # both meshes' step programs were compiled, the larger one is reported
    assert len(run["step_memories"]) == 2
    assert run["memory_peak_bytes"] == max(
        elastic._program_bytes(m) for m in run["step_memories"])
    assert run["restored_leaves"] > 10
    if trace:
        listed = {m["name"] for m in load(TEST_JSON)["per_layer"]}
        assert set(line["metrics"]) == listed
        assert line["metrics"]["extra_generations"]["value"] == 0
        assert line["metrics"]["resume_cache_misses"]["value"] == 0
    else:
        assert set(line["metrics"]) == {"tokens_per_s", "setup_s",
                                        "recovery_s"}
        assert len(resumed_pairs(run)) >= 16
        # the resume has a number of its own: SIGKILL to the first resumed
        # record, which is where the window opens
        recovery = line["metrics"]["recovery_s"]["value"]
        assert recovery == pytest.approx(first["t"] - run["t_kill"])
        assert 0 <= run["t_open"] - run["t_kill"] - recovery < 1.0
        # the window is one generation: every step over all the time, from
        # the record that opened it to its last, a save's stall among them
        inside = [r for r in later
                  if run["t_open"] <= r["t"] <= run["t_close"]]
        assert 2 * n in {r["step"] for r in inside}
        assert line["metrics"]["tokens_per_s"]["value"] == pytest.approx(
            run["tokens_per_step"] * (inside[-1]["step"] - first["step"])
            / (inside[-1]["t"] - first["t"]))
        # which the loop's own pace, a median of gaps, cannot see
        from lib import timeline_reduce as tl
        pace = run["tokens_per_step"] / tl.step_interval_s(
            run["records"], run["t_open"], run["t_close"], run["save_steps"])
        assert line["metrics"]["tokens_per_s"]["value"] < pace


def resumed_pairs(run):
    from lib import timeline_reduce as tl
    return [pair for pair in tl.pace_pairs(
        run["records"], run["t_open"], run["t_close"], run["save_steps"])
        if pair[0]["generation"] > run["killed_generation"]]


ALTERED = '''
import runpy, sys
import jax.numpy as jnp
from easydl_tpu.core import train_loop

restore_from, calls = train_loop.Trainer.restore_from, []


def altered(self, checkpoint, step=None):
    """The restores in this process named on the command line (`second`,
    or `every`: a fault both meshes share) hand back one leaf with one bit
    of one number turned."""
    state = restore_from(self, checkpoint, step)
    calls.append(step)
    if WHICH == "second" and len(calls) != 2:
        return state
    import jax
    leaves, tree = jax.tree.flatten(state)
    at = max(range(len(leaves)), key=lambda i: leaves[i].size)
    leaf = leaves[at]
    bits = jax.lax.bitcast_convert_type(leaf, jnp.uint32)
    bits = bits.at[(0,) * bits.ndim].set(bits[(0,) * bits.ndim] ^ 1)
    leaves[at] = jax.device_put(
        jax.lax.bitcast_convert_type(bits, leaf.dtype), leaf.sharding)
    return jax.tree.unflatten(tree, leaves)


train_loop.Trainer.restore_from = altered
WHICH = sys.argv.pop(1)
sys.argv = ["run.py"] + sys.argv[1:]
runpy.run_path(sys.argv.pop(1), run_name="__main__")
'''


@pytest.mark.parametrize("which, leaves", [("second", 1), ("every", 2)])
def test_a_restore_altered_by_one_bit_is_refused(tmp_path, which, leaves):
    """The whole run with the program's restore broken underneath, in the
    benchmark's process alone (the workers' restores are sound): a restore
    differs from the checkpoint's files in one bit of one leaf — the second
    mesh's alone, or both meshes' alike, which a comparison of the two with
    each other would pass — and ``correct`` comes out false by that number
    and no other."""
    script = tmp_path / "altered.py"
    script.write_text(ALTERED)
    test_json = load(TEST_JSON)
    test_json["workloads"][0]["name"] = CELL + "-altered"
    for metric in test_json["per_layer"]:
        metric["workloads"] = [CELL + "-altered"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(test_json))
    env = dict(os.environ, JAX_PLATFORMS="cpu", EASYDL_COMPILE_CACHE="off",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=ROOT)
    proc = subprocess.run(
        [sys.executable, str(script), which, os.path.join(BENCH, "run.py"),
         "--benchmark-json", str(tmp_path / "BENCHMARK.json"), "--workload",
         CELL + "-altered", "--seed", "2147483702", "--seconds", "25",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=420)
    line = last_line(proc)
    assert line["correct"] is False and line["failed"] == 1
    assert line["compared"]["restored_leaves_that_differ"] == [leaves, 0]
    others = {k: v for k, v in line["compared"].items()
              if k != "restored_leaves_that_differ"}
    assert all(number <= limit for number, limit in others.values())
    # the last lines of standard error say the same
    assert f"compared restored_leaves_that_differ {leaves} limit 0" in \
        proc.stderr.strip().splitlines()[-1]


def test_a_mix_without_the_new_keys_builds_the_job_it_built():
    """``kill-resume-test.json`` through the driver's two functions: the
    job's configuration key for key as the parent wrote it (no
    ``mesh_policy`` in it), and the kill behind S1 at 2N + 3."""
    config = load(BENCH, "configs", "gpt2-test.json")
    mix = load(BENCH, "traffic", "kill-resume-test.json")
    assert elastic.job_config(config, mix, "/corpus", 7) == {
        "model": "gpt",
        "model_kwargs": {"size": "test", "seq_len": 64, "vocab": 1024,
                         "dtype": "bfloat16", "remat": True,
                         "remat_policy": "dots"},
        "global_batch": 8, "grad_accum": 2, "data_dir": "/corpus",
        "total_steps": 10_000_000, "ckpt_interval": 5, "seed": 7,
        "lr": 0.001, "mesh": {}}
    assert elastic.kill_step_of(mix) == 13
    real = load(BENCH, "traffic", "kill-resume.json")
    assert elastic.kill_step_of(real) == 53
    assert not {"mesh", "resume_mesh", "resume_in_setup",
                "replay_loss_steps"} & set(real)
    # the new mix: behind C0
    assert elastic.kill_step_of(load(BENCH, "traffic",
                                     "reshape-resume-test.json")) == 8
    reshape = load(BENCH, "traffic", "reshape-resume.json")
    assert elastic.kill_step_of(reshape) == 55 == reshape["save_every"] + 25
    assert elastic.job_config(load(BENCH, "configs", "gpt2-medium.json"),
                              reshape, "/c", 1)["mesh_policy"] == {
        "pin": "dp=4"}


def test_the_test_files_entries_are_the_real_files_entries():
    """``BENCHMARK.reshape-test.json`` rehearses what ``BENCHMARK.json``
    lists for the real cell: the same entries, by the cell's name alone."""
    real = [m for m in load(ROOT, "BENCHMARK.json")["per_layer"]
            if REAL in m.get("workloads", [])]
    test = load(TEST_JSON)["per_layer"]
    assert [dict(m, workloads=None) for m in real] == [
        dict(m, workloads=None) for m in test]
    assert all(m["workloads"] == [CELL] for m in test)
    # a traced run of the real mix's test copy differs from it by size alone
    a = load(BENCH, "traffic", "reshape-resume.json")
    b = load(BENCH, "traffic", "reshape-resume-test.json")
    for key in ("driver", "mesh", "resume_mesh", "resume_in_setup",
                "replay_loss_steps", "replay_loss_rtol", "lr", "env"):
        assert a[key] == b[key], key
