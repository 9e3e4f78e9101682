"""chip_smoke.py rehearsed without the chip, and the no-fallback contracts
it rests on.

- the phase functions at test size on the forced-CPU mesh — steered from
  here (a ``Size``, interpret mode, what the agent is told its host has),
  never by an option of the script;
- on a machine without a chip, ``chip_smoke.py`` and ``benchmark/run.py`` exit
  non-zero, name the missing chip and print no metric;
- the compile-cache resolver, the MFU denominator, and the agent's refusal
  to preflight onto a device its own worker holds.
"""

from __future__ import annotations

import functools
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

#: GPT "test" size (2 layers, d_model 128, 4 heads) with the real run's
#: dtype/remat choices; ``attention_impl`` stated so the CPU takes the
#: kernel's path (interpreted) instead of the reference.
TEST = chip_smoke.Size(
    platform="cpu",
    model=(("size", "test"), ("seq_len", 64), ("vocab", 1024),
           ("dtype", "bfloat16"), ("remat", True), ("remat_policy", "dots")),
    vocab=1024, seq_len=64, batch=8, attn_shape=(2, 128, 4, 32),
    interpret=True, mesh_batch=8, mesh_accum=2)


def test_kernel_phase_interpreted():
    r = chip_smoke.phase_kernel(TEST)
    assert r["interpret"] and r["fwd_max_abs_err"] <= r["fwd_atol"]
    assert max(r["grad_rel_err"].values()) <= r["grad_rtol"]


def test_device_phase_fails_without_the_chip():
    assert chip_smoke.phase_device(TEST)["platform"] == "cpu"
    with pytest.raises(chip_smoke.PhaseFailed, match="found no tpu"):
        chip_smoke.phase_device(chip_smoke.REAL)


def test_train_then_resume_phases(tmp_path, monkeypatch):
    """The zoo runner twice, as the smoke runs it: the second run resumes
    from step 6 and finds the first one's compiles in the cache that
    JAX_COMPILATION_CACHE_DIR names (no directory set in code)."""
    cache = tmp_path / "cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    monkeypatch.setenv("EASYDL_COMPILE_CACHE", "")
    work = str(tmp_path / "work")
    os.makedirs(work)
    train = chip_smoke.phase_train(TEST, work)
    assert train["losses"]["6"] < train["losses"]["1"]
    assert train["cache_misses"] > 0 and os.listdir(cache)
    resume = chip_smoke.phase_resume(
        json.loads(json.dumps(train)), TEST, work)
    assert resume["resumed_from"] == 6
    assert resume["cache_hits"] > 0
    assert resume["warm_compile_s"] < resume["cold_compile_s"]
    with open(os.path.join(work, "runner-steps9.log")) as f:
        log = f.read()
    assert f"compile cache: {cache}" in log
    assert "data cursor resumed" in log


def test_mesh_phase_on_four_cpu_devices(tmp_path, monkeypatch):
    """Rehearsal of the four-chip option: one device vs dp=4 vs
    fsdp=2 x tp=2 with the kernel called per shard (interpreted here),
    then save under dp=4 and restore under fsdp=2 x tp=2."""
    from easydl_tpu.ops import attention

    monkeypatch.setattr(
        attention, "flash_attention",
        functools.partial(attention.flash_attention, interpret=True))
    size = chip_smoke.dataclasses.replace(
        TEST, model=TEST.model + (("attention_impl", "flash"),))
    r = chip_smoke.phase_mesh(size, str(tmp_path))
    assert r["devices_pieces"]["dp=4"]["batch"] == (4, 4)
    assert r["devices_pieces"]["fsdp=2,tp=2"]["params"] == (4, 4)
    assert set(r["losses"]) == {"one_device", "dp=4", "fsdp=2,tp=2"}


def test_elastic_phase_declines_preflight_on_a_held_device(tmp_path):
    """Master -> agent -> worker, kill, recover — with the agent told its
    host has a tpu (the workers still run on the CPU the env forces): the
    standing preflight hint is declined while the worker lives, both
    spawns are cold, and the process that hosts master and agent never
    initialises a jax backend (the phase checks; hence its own process)."""
    code = (
        "import json, chip_smoke; from chip_smoke import Size; "
        f"print(json.dumps(chip_smoke.phase_elastic({TEST!r}, "
        f"{str(tmp_path)!r}, timeout=240.0, agent_platform='tpu')))")
    proc = _run(["-c", code], PYTHONPATH=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(r["preflight_skipped"]) == {"device_held"}
    assert [s["mode"] for s in r["spawns"]] == ["cold", "cold"]
    assert r["spawns"][-1]["reason"] == "device_held"
    assert r["recovered_to_step"] > r["killed_at_step"] > r["restored_step"]
    # the recovery on the agent's and the workers' timeline: the reaped
    # worker, then the spawn with the RUN's first sighting between the two;
    # the resumed worker's boot with its devices and compile counters
    from easydl_tpu.elastic.timeline import read

    events = read(str(tmp_path / "elastic" / "timeline-a0.jsonl"))
    crash, = [e for e in events if e["phase"] == "worker_crash"]
    spawn = next(e for e in events
                 if e["phase"] == "spawn" and e["gen"] == 2)
    assert crash["gen"] == 1 and crash["code"] == -9
    assert crash["t"] <= spawn["directive_t"] <= spawn["t"]
    gen2 = [e["phase"] for e in events if e["gen"] == 2]
    assert gen2.index("dist_init_done") < gen2.index("devices_ready") \
        < gen2.index("trainer_built")
    first = next(e for e in events
                 if e["phase"] == "first_step_done" and e["gen"] == 2)
    assert {"trace_s", "lower_s", "backend_s", "cache_retrieval_s",
            "cache_hits", "cache_misses"} <= set(first)


def _run(argv, cwd=REPO, **env):
    return subprocess.run(
        [sys.executable] + argv, cwd=cwd, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


@pytest.mark.parametrize("argv", [
    ["chip_smoke.py"],
    ["benchmark/run.py", "--workload", "gpt2-medium.steady", "--seed", "1",
     "--seconds", "50", "--trace", "0"],
], ids=["chip_smoke.py", "benchmark-run.py"])
def test_no_chip_no_metric(argv):
    """On a CPU-only machine the measurement paths fail; they do not fall
    back. Non-zero exit, the missing chip named, no JSON line on stdout."""
    proc = _run([os.path.join(REPO, argv[0])] + argv[1:])
    assert proc.returncode != 0
    assert "tpu" in (proc.stderr + proc.stdout).lower()
    assert not [line for line in proc.stdout.splitlines()
                if '"ok": true' in line or '"value"' in line]


def test_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run([str(tmp_path / "chip_smoke.py")], cwd=str(tmp_path))
    assert proc.returncode != 0 and not proc.stdout.strip()
    assert "easydl_tpu" in proc.stderr


@pytest.fixture
def cache_config(monkeypatch):
    """Record what configure_compile_cache sets, and undo it after."""
    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_traceback_in_locations_limit",
             "jax_compilation_cache_include_metadata_in_key")
    before = {n: getattr(jax.config, n) for n in names}
    calls = {}
    real_update = jax.config.update

    def update(name, value):
        calls[name] = value
        real_update(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    yield calls
    for n, v in before.items():
        real_update(n, v)


@pytest.mark.parametrize("case", ["env_set", "env_unset", "off"])
def test_compile_cache_resolver(case, cache_config, monkeypatch, tmp_path):
    from easydl_tpu.utils import env

    monkeypatch.setenv("EASYDL_COMPILE_CACHE", "off" if case == "off" else "")
    if case == "env_set":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = env.configure_compile_cache()
    if case != "off":
        # see test_tpu_compile: cache keys of Mosaic programs ...
        assert cache_config["jax_traceback_in_locations_limit"] == 1
        # ... and test_step_scopes: the names the device trace is read by
        assert cache_config["jax_compilation_cache_include_metadata_in_key"]
    if case == "env_set":  # jax reads it; the code sets no directory
        assert got == str(tmp_path)
        assert "jax_compilation_cache_dir" not in cache_config
    elif case == "env_unset":  # one fixed path of the checkout
        assert got == os.path.join(REPO, ".jax_cache") == env.COMPILE_CACHE_DIR
        assert cache_config["jax_compilation_cache_dir"] == got
    else:
        assert got is None
        assert cache_config == {"jax_enable_compilation_cache": False}


@pytest.mark.parametrize("kind,override,expect", [
    ("cpu", "", None),                 # no peak for a CPU: raises
    ("TPU v5 lite", "", 197e12),
    ("cpu", "100", 100e12),            # the operator's statement wins
])
def test_peak_flops_unknown_kind_raises(kind, override, expect, monkeypatch):
    from easydl_tpu.core.mfu import peak_flops_per_chip

    monkeypatch.setenv("EASYDL_CHIP_PEAK_TFLOPS", override)
    if expect is None:
        with pytest.raises(ValueError, match="no peak FLOP/s known"):
            peak_flops_per_chip(kind)
    else:
        assert peak_flops_per_chip(kind) == expect


def test_agent_platform_follows_jax_platforms(monkeypatch, tmp_path):
    from easydl_tpu.elastic.agent import Agent

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert Agent("a", "localhost:1", str(tmp_path)).platform == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    assert Agent("a", "localhost:1", str(tmp_path)).platform == "tpu"
    assert Agent("a", "localhost:1", str(tmp_path),
                 platform="cpu").platform == "cpu"
