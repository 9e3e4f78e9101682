"""The elastic worker seen from inside (PR 33): a step's host side on its
record, the commit's flag, the counters, the boot's own age, compiles by
program, and the profile window a running worker can be asked for.

The worker runs IN this process (``run_worker`` on a tiny MLP,
as tests/test_mesh_shapes.py runs it), so a test can raise the signals at a
phase of its choosing and read the files afterwards; the instruments
(``utils/profiling``) and the agent's side are tested alone.
"""

from __future__ import annotations

import contextlib
import json
import os
import signal
import statistics
import subprocess
import sys
import threading
import time
import types

import jax
import jax.numpy as jnp
import optax
import pytest

from easydl_tpu.elastic import timeline, worker
from easydl_tpu.utils import profiling

JOB = {"model": "mlp",
       "model_kwargs": {"input_shape": [8, 8, 1], "features": [16]},
       "global_batch": 8, "total_steps": 12, "ckpt_interval": 5,
       "lr": 0.01, "seed": 0}
FIELDS = ("data_s", "shard_s", "dispatch_s", "wait_s")


# ------------------------------------------------------------ instruments
def test_host_span_is_an_annotation_that_keeps_its_seconds():
    assert issubclass(profiling.host_span, jax.profiler.TraceAnnotation)
    with profiling.host_span("easydl/test") as span:
        time.sleep(0.02)
    assert 0.02 <= span.seconds < 0.5
    assert not hasattr(profiling, "trace")  # the unused helper is gone


def _tiny_trainer():
    from easydl_tpu.core import MeshSpec, TrainConfig, Trainer, build_mesh
    from easydl_tpu.models import get_model

    bundle = get_model("mlp", input_shape=[8, 8, 1], features=[16])
    trainer = Trainer(
        init_fn=bundle.init_fn, loss_fn=bundle.loss_fn,
        optimizer=optax.adam(1e-2), config=TrainConfig(global_batch=8),
        mesh=build_mesh(MeshSpec(), devices=jax.devices()[:1]))
    return bundle, trainer


def test_trainer_leaves_its_host_seconds():
    bundle, trainer = _tiny_trainer()
    assert trainer.host_seconds == {}
    data = iter(bundle.make_data(8, seed=0))
    state = trainer.init_state()
    t0 = time.perf_counter()
    state, metrics = trainer.train_step(state, next(data))
    wall = time.perf_counter() - t0
    first = dict(trainer.host_seconds)
    assert set(first) == {"shard_s", "dispatch_s"}
    assert first["shard_s"] > 0 and first["dispatch_s"] > 0
    assert first["shard_s"] + first["dispatch_s"] <= wall
    seconds = trainer.host_seconds
    trainer.train_step(state, next(data))
    # the same dict, overwritten: the second call had nothing to compile
    assert trainer.host_seconds is seconds
    assert seconds["dispatch_s"] < first["dispatch_s"]
    # every entry of the step's metrics that is one number, and no array
    scalars = worker.scalars_of(dict(metrics, hist=jnp.ones(3), aux={},
                                     n=3, x=0.5))
    assert set(scalars) == set(metrics) | {"n", "x"}
    assert {"loss", "grad_norm"} <= set(scalars)


@pytest.fixture
def compile_watch():
    return profiling.CompileWatch()


def _nested_program():
    inner = jax.jit(lambda x: jnp.where(x > 0, jnp.tanh(x), x) * 3.0)

    def inside_outer(x):
        return jnp.tanh(x) * 2

    def outer_program(x):
        for _ in range(5):
            x = inner(x) + jax.jit(inside_outer)(x)
        return x

    return inside_outer, jax.jit(outer_program)


def test_compile_watch_names_programs_and_columns_sum_to_totals(
        compile_watch):
    before = compile_watch.totals()
    _, outer = _nested_program()
    outer(jnp.ones((7, 3))).block_until_ready()
    table = compile_watch.table()
    row = table["outer_program"]  # traced as `outer_program`, lowered and
    assert row["trace_s"] > 0     # compiled as `jit(outer_program)`: one row
    assert row["lower_s"] > 0 and row["backend_s"] > 0
    assert not any(name.startswith("jit(") for name in table)
    assert row["start"] <= row["end"] <= time.time()
    assert time.time() - row["start"] < 60  # on time.time()'s clock
    since = compile_watch.since(before)
    for column in profiling.CompileWatch.COLUMNS:
        assert sum(r[column] for r in table.values()) == pytest.approx(
            compile_watch.totals()[column], abs=1e-9)
        assert since[column] >= 0


def test_compile_watch_folds_a_nested_jit_into_its_outer_program(
        compile_watch):
    inside_outer, outer = _nested_program()
    outer(jnp.ones((5, 3))).block_until_ready()
    table = compile_watch.table()
    # the jits traced inside `outer_program` are its own seconds, as
    # trace_seconds has always counted them
    assert "inside_outer" not in table and "<lambda>" not in table
    assert table["outer_program"]["trace_s"] > 0
    # alone, the same function is a program of its own
    jax.jit(inside_outer)(jnp.ones(4)).block_until_ready()
    assert compile_watch.table()["inside_outer"]["backend_s"] > 0


def test_compile_watch_table_since_and_largest_programs(compile_watch):
    _, outer = _nested_program()
    outer(jnp.ones((3, 3))).block_until_ready()
    earlier = compile_watch.table()
    assert compile_watch.table_since(earlier) == {}

    def late_program(x):
        return (x * x).sum()

    jax.jit(late_program)(jnp.ones(9)).block_until_ready()
    stretch = compile_watch.table_since(earlier)
    assert "late_program" in stretch and "outer_program" not in stretch
    assert stretch["late_program"]["start"] >= earlier["outer_program"]["end"]
    record = profiling.largest_programs(stretch, n=1)
    assert [r["name"] for r in record["programs"]] == [max(
        stretch, key=lambda n: sum(
            stretch[n][c] for c in ("trace_s", "lower_s", "backend_s")))]
    (late,) = [r for r in profiling.largest_programs(
        stretch, n=len(stretch))["programs"] if r["name"] == "late_program"]
    assert set(late) == {  # a program new in the stretch has its start too
        "name", "trace_s", "lower_s", "backend_s", "cache_retrieval_s",
        "start", "end"}
    rest = sum(sum(row[c] for c in ("trace_s", "lower_s", "backend_s"))
               for name, row in stretch.items()
               if name != record["programs"][0]["name"])
    assert record["other_programs_s"] == pytest.approx(rest, abs=1e-3)
    assert profiling.largest_programs({}) == {"programs": [],
                                              "other_programs_s": 0}


def test_since_exec_s_is_this_process_age():
    age = worker.since_exec_s()
    if not os.path.exists("/proc/self/stat"):
        assert age is None
        return
    assert age is not None and age > 0
    child = subprocess.run(
        [sys.executable, "-c",
         "from easydl_tpu.elastic.worker import since_exec_s; "
         "print(since_exec_s())"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
    assert child.returncode == 0, child.stderr[-2000:]
    assert 0 < float(child.stdout) < age + 1  # younger than this process


# -------------------------------------------------------------- checkpoint
def test_in_flight_exactly_between_save_and_committed(tmp_path):
    from easydl_tpu.core.checkpoint import CheckpointManager

    written, seen = threading.Event(), {}
    manager = None

    def on_event(name, **data):
        seen[name] = manager.in_flight
        if name == "ckpt_chunks_written":
            assert written.wait(30)  # hold the commit until the test looked

    manager = CheckpointManager(str(tmp_path), async_save=True,
                                on_event=on_event)
    assert manager.in_flight is False
    manager.save(1, {"w": jnp.arange(8.0)})
    assert manager.in_flight is True  # save() is back, nothing committed
    written.set()
    manager.wait()
    assert manager.in_flight is False
    # the snapshot is inside save(); the chunks are written under the flag;
    # at the commit's own event it is down
    assert seen == {"ckpt_snapshot_done": False, "ckpt_chunks_written": True,
                    "ckpt_committed": False}
    sync = CheckpointManager(str(tmp_path / "sync"), async_save=False)
    sync.save(1, {"w": jnp.arange(8.0)})
    assert sync.in_flight is False


# ------------------------------------------------- the worker, in process
@contextlib.contextmanager
def _worker_in_process():
    """``run(tmp_path, job=None, on_phase=None)``: ``run_worker`` in this
    process, to its end; ``on_phase(record)`` sees every timeline record as
    it is emitted. Signal dispositions and the worker's flags are put back
    on the way out."""
    old = {s: signal.getsignal(s) for s in (signal.SIGUSR1, signal.SIGUSR2)}
    real_emit = timeline.emit
    patch = pytest.MonkeyPatch()

    def run(tmp_path, job=None, on_phase=None):
        work = str(tmp_path)
        with open(os.path.join(work, "job.json"), "w") as f:
            json.dump(dict(JOB, **(job or {})), f)

        def emit(path, phase, generation, /, **data):
            real_emit(path, phase, generation, **data)
            if on_phase is not None:
                on_phase(dict(data, phase=phase))

        patch.setattr(timeline, "emit", emit)
        env = {"EASYDL_RANK": "0", "EASYDL_WORLD": "1", "EASYDL_COORD": "",
               "EASYDL_GEN": "1", "EASYDL_WORKDIR": work,
               "EASYDL_METRICS": os.path.join(work, "metrics-a0.jsonl"),
               "EASYDL_TIMELINE": os.path.join(work, "timeline-a0.jsonl"),
               "EASYDL_AGENT_ID": "a0"}
        assert worker.run_worker(env) == 0
        with open(env["EASYDL_METRICS"]) as f:
            records = [json.loads(line) for line in f]
        return records, timeline.read(env["EASYDL_TIMELINE"])

    try:
        yield run
    finally:
        patch.undo()
        for sig, handler in old.items():
            signal.signal(sig, handler)
        worker._QUIESCE["flag"] = worker._PROFILE["flag"] = False


@pytest.fixture
def in_process():
    with _worker_in_process() as run:
        yield run


@pytest.fixture(scope="module")
def plain_run(tmp_path_factory):
    """One run with nothing asked of it, read by several tests."""
    with _worker_in_process() as run:
        return run(tmp_path_factory.mktemp("plain"))


def _ask_at(phase, **equal):
    """An ``on_phase`` that raises SIGUSR2 at the first matching record."""
    def on_phase(record):
        if record["phase"] == phase and all(
                record.get(k) == v for k, v in equal.items()):
            signal.raise_signal(signal.SIGUSR2)
    return on_phase


def _phases(events, name):
    return [e for e in events if e["phase"] == name]


def _host_spans(xplane):
    from jax.profiler import ProfileData

    spans = {}
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("easydl/", "train_step")):
                        spans.setdefault(e.name, []).append(e)
    return spans


def test_record_fields_tile_the_step_and_the_interval(plain_run):
    records, _ = plain_run
    assert [r["step"] for r in records] == list(range(1, 13))
    for r in records:
        assert all(r[f] >= 0 for f in FIELDS) and "straggle_s" not in r
        # the spans lie inside the timed window, one after the other
        assert sum(r[f] for f in FIELDS) <= r["step_time_s"]
    assert "gap_s" not in records[0]  # no step before the first
    residual = [r["step_time_s"] - sum(r[f] for f in FIELDS)
                for r in records]
    assert statistics.median(residual) < 1e-3  # the statements between
    # gap + the four = the interval between the records (each stamped
    # `t` the same few statements after its fetch returned)
    off = [abs((b["t"] - a["t"]) - b["gap_s"] - sum(b[f] for f in FIELDS))
           for a, b in zip(records, records[1:])]
    assert statistics.median(off) < 2e-3


def test_record_counters_hold_every_scalar_of_the_metrics(plain_run):
    records, _ = plain_run
    for r in records:
        assert set(r["counters"]) == {"grad_norm", "accuracy"}  # the MLP's
        assert all(isinstance(v, float) for v in r["counters"].values())
        assert "loss" not in r["counters"] and isinstance(r["loss"], float)
        assert r["counters"]["grad_norm"] > 0


def test_records_carry_commit_in_flight_as_read_at_the_step_start(plain_run):
    records, events = plain_run
    assert all(isinstance(r["commit_in_flight"], bool) for r in records)
    # saves at 5 and 10: no step before the first save ran beside one
    assert not any(r["commit_in_flight"] for r in records[:5])
    commits = {e["step"]: e["t"] for e in _phases(events, "ckpt_committed")}
    for r in records:
        if r["commit_in_flight"]:
            save = max(s for s in (5, 10) if s < r["step"])
            # its t0 (its record's t less the step) is before that commit
            assert r["t"] - r["step_time_s"] <= commits[save] + 1e-3


def test_boot_phases_carry_since_exec_s_and_programs(plain_run):
    _, events = plain_run
    start = _phases(events, "worker_main_start")[0]
    if os.path.exists("/proc/self/stat"):
        assert start["since_exec_s"] > 0
    first = _phases(events, "first_step_done")[0]
    rows = {row["name"]: row for row in first["programs"]}
    assert "train_step" in rows and len(rows) <= 5
    # the table's columns sum to the totals the phase always carried
    for column in ("trace_s", "lower_s", "backend_s"):
        assert sum(r[column] for r in rows.values()) <= first[column] + 2e-3
    named = sum(r["trace_s"] + r["lower_s"] + r["backend_s"]
                for r in rows.values()) + first["other_programs_s"]
    assert named == pytest.approx(
        first["trace_s"] + first["lower_s"] + first["backend_s"], abs=0.01)
    restored = _phases(events, "restored")[0]
    assert restored["t"] - 1e-3 <= rows["train_step"]["start"]
    assert rows["train_step"]["end"] <= first["t"] + 1e-3


def test_step_time_and_t_keep_their_meaning_on_a_fake_clock(
        in_process, tmp_path, monkeypatch):
    """Golden: with a clock that moves only inside ``train_step`` (5 s a
    call), ``step_time_s`` is the time from the step's start to its loss on
    the host, ``t`` the moment the record is written, ``gap_s`` nothing."""
    from easydl_tpu.core import Trainer

    clock = types.SimpleNamespace(now=0.0)
    monkeypatch.setattr(worker, "time", types.SimpleNamespace(
        perf_counter=lambda: clock.now, time=lambda: 1e9 + clock.now,
        sleep=time.sleep))
    real_step = Trainer.train_step

    def slow_step(self, state, batch):
        clock.now += 5.0
        return real_step(self, state, batch)

    monkeypatch.setattr(Trainer, "train_step", slow_step)
    records, _ = in_process(tmp_path, {"total_steps": 4,
                                       "ckpt_interval": -1})
    assert [(r["step"], r["step_time_s"], r["t"], r["samples_per_sec"])
            for r in records] == [
        (n, 5.0, 1e9 + 5.0 * n, 8 / 5.0) for n in (1, 2, 3, 4)]
    assert [r.get("gap_s") for r in records] == [None, 0.0, 0.0, 0.0]


@pytest.mark.parametrize("request_file", [True, False])
def test_sigusr2_opens_a_window_with_the_loops_spans(
        in_process, tmp_path, request_file):
    request = {"steps": 2, "dir": str(tmp_path / "asked")}
    if request_file:
        with open(tmp_path / "profile-a0.json", "w") as f:
            json.dump(request, f)
    records, events = in_process(tmp_path, on_phase=_ask_at("restored"))
    (started,), (written,) = (_phases(events, "profile_started"),
                              _phases(events, "profile_written"))
    steps, logdir = (2, request["dir"]) if request_file else (
        4, str(tmp_path / "profile" / "gen1-step0"))
    assert (started["step"], started["steps"], started["dir"]) == (
        0, steps, logdir)
    assert (written["first_step"], written["last_step"]) == (1, steps)
    assert not os.path.exists(tmp_path / "profile-a0.json")  # read, removed
    assert written["path"].startswith(logdir)
    assert written["path"].endswith(".xplane.pb")
    assert written["bytes"] == os.path.getsize(written["path"]) > 0
    assert written["seconds"] >= 0
    spans = _host_spans(written["path"])
    for name in ("easydl/next_batch", "easydl/fetch_loss", "easydl/record",
                 "easydl/shard_batch", "easydl/dispatch", "train_step"):
        assert len(spans[name]) == steps, name
    # one clock: the mark's unix_s is profile_started's t, to the digit
    (mark,) = spans["easydl/clock"]
    assert float(dict(mark.stats)["unix_s"]) == started["t"]
    zero = started["t"] - mark.start_ns / 1e9  # the trace's 0 in unix time
    # ... and through it a record's `t` falls inside its own record span
    first = min(spans["easydl/record"], key=lambda e: e.start_ns)
    begin = zero + first.start_ns / 1e9
    assert begin - 1e-3 <= records[0]["t"] <= \
        begin + first.duration_ns / 1e9 + 1e-3
    assert len(records) == 12  # the job went on to its end


def test_a_second_request_inside_a_window_is_dropped(in_process, tmp_path,
                                                     caplog):
    with open(tmp_path / "profile-a0.json", "w") as f:
        json.dump({"steps": 3}, f)
    asked = []

    def on_phase(record):
        if record["phase"] in ("restored", "profile_started"):
            asked.append(record["phase"])
            signal.raise_signal(signal.SIGUSR2)

    profiling.log.addHandler(caplog.handler)  # the package's logs stay
    try:                                      # under their own root
        _, events = in_process(tmp_path, on_phase=on_phase)
    finally:
        profiling.log.removeHandler(caplog.handler)
    assert asked == ["restored", "profile_started"]
    assert "profile request at step 1 dropped" in caplog.text
    (started,), (written,) = (_phases(events, "profile_started"),
                              _phases(events, "profile_written"))
    assert (written["first_step"], written["last_step"]) == (1, 3)


def test_a_worker_that_leaves_inside_a_window_closes_it(
        in_process, tmp_path):
    with open(tmp_path / "profile-a0.json", "w") as f:
        json.dump({"steps": 50}, f)
    records, events = in_process(
        tmp_path, {"total_steps": 6}, on_phase=_ask_at("first_step_done"))
    (started,), (written,) = (_phases(events, "profile_started"),
                              _phases(events, "profile_written"))
    assert (started["step"], started["steps"]) == (1, 50)
    assert (written["first_step"], written["last_step"]) == (2, 6)
    assert os.path.getsize(written["path"]) > 0
    assert len(records) == 6
    # a request after the last boundary finds nobody: inert, not fatal
    assert signal.getsignal(signal.SIGUSR2) == signal.SIG_IGN


def test_an_unreadable_request_file_means_the_defaults(tmp_path):
    seen = []
    path = tmp_path / "profile-a0.json"
    path.write_text("{not json")
    asked = profiling.RequestedProfile(
        str(path), lambda s: str(tmp_path / f"step{s}"),
        lambda name, **data: seen.append((name, data)))
    asked.close()  # nothing open: nothing said
    assert seen == []
    assert asked._request(7) == (4, str(tmp_path / "step7"))
    path.write_text(json.dumps({"steps": "many"}))
    assert asked._request(8) == (4, str(tmp_path / "step8"))
    path.write_text(json.dumps({"steps": 0, "dir": ""}))
    assert asked._request(9) == (1, str(tmp_path / "step9"))


# ------------------------------------------------------------ the agent
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHILD = """
import signal, sys, time
signal.signal(signal.SIGUSR2, lambda *_: print("usr2", flush=True))
print("ready", flush=True)
time.sleep(60)
"""


@pytest.fixture
def agent(tmp_path):
    from easydl_tpu.elastic.agent import Agent

    a = Agent("a0", "localhost:1", str(tmp_path), platform="cpu")
    yield a
    if a._proc is not None and a._proc.poll() is None:
        a._proc.kill()
        a._proc.wait()


def test_profile_worker_writes_the_request_and_signals(agent, tmp_path):
    agent._proc = subprocess.Popen([sys.executable, "-c", CHILD],
                                   stdout=subprocess.PIPE, text=True)
    assert agent._proc.stdout.readline() == "ready\n"
    agent._applied_key = (3, "localhost:4003")
    request = tmp_path / "profile-a0.json"
    # no step of generation 3 on record yet: nothing to profile, no signal
    assert agent.profile_worker(8) is False and not request.exists()
    with open(agent.metrics_path, "w") as f:
        f.write(json.dumps({"step": 1, "generation": 3}) + "\n")
    assert agent.profile_worker(8, logdir="/somewhere") is True
    assert json.loads(request.read_text()) == {"steps": 8,
                                               "dir": "/somewhere"}
    assert agent._proc.stdout.readline() == "usr2\n"
    assert agent.profile_worker(2) is True
    assert json.loads(request.read_text()) == {"steps": 2}
    assert not os.path.exists(str(request) + ".tmp")


def test_profile_worker_on_a_dead_worker_is_a_no_op(agent, tmp_path):
    assert agent.profile_worker(4) is False  # never spawned
    agent._proc = subprocess.Popen([sys.executable, "-c", "pass"])
    agent._proc.wait()
    agent._applied_key = (1, "localhost:4001")
    with open(agent.metrics_path, "w") as f:
        f.write(json.dumps({"step": 9, "generation": 1}) + "\n")
    assert agent.profile_worker(4) is False
    assert not (tmp_path / "profile-a0.json").exists()
