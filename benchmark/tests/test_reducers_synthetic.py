"""trace_reduce and timeline_reduce on made-up inputs whose answers can be
worked out by hand."""

import pytest

from lib import hlo, timeline_reduce as tl, trace_reduce as tr

MS = 1e6  # ns


def trace(devices, host=()):
    return {"devices": devices, "host": [list(h) for h in host]}


def test_union_self_time_and_busy():
    # a while [0, 100) around two fusions [10, 40) [50, 90); then idle
    # until a copy [150, 160); window from the first start to the last end
    ops = [["while.1", 0, 100 * MS], ["fusion.2", 10 * MS, 30 * MS],
           ["fusion.3", 50 * MS, 40 * MS], ["copy.4", 150 * MS, 10 * MS]]
    t = trace({"/device:TPU:0": ops})
    assert tr.window_of(t) == (0, 160 * MS)
    busy = tr.busy(t)
    assert busy["busy_s"] == pytest.approx(0.110)   # nested counted once
    assert busy["window_s"] == pytest.approx(0.160)
    by_op = tr.time_by_op(t)
    assert by_op["while.1"] == pytest.approx(0.030)  # 100 - 30 - 40
    assert by_op["fusion.3"] == pytest.approx(0.040)
    assert sum(by_op.values()) == pytest.approx(busy["busy_s"])
    ops = tr.ops_by_name(t)
    assert ops["while.1"] == {"seconds": pytest.approx(0.100), "calls": 1}
    assert ops["fusion.2"]["seconds"] + ops["copy.4"]["seconds"] == \
        pytest.approx(0.040)
    summary = tr.summarise(t, top=1)
    assert summary["top_ops"] == [["fusion.3", pytest.approx(0.040)]]
    assert summary["busy_s"] == busy["busy_s"] and summary["exposed_s"] == 0


def test_host_window_clips_and_names_idle_gaps():
    ops = [["fusion.1", 0, 20 * MS], ["fusion.2", 30 * MS, 30 * MS],
           ["fusion.3", 100 * MS, 50 * MS]]
    host = [("bench/window", 10 * MS, 130 * MS),
            ("bench/fetch_loss", 55 * MS, 20 * MS),
            ("bench/next_data", 75 * MS, 20 * MS),
            ("bench/dispatch", 95 * MS, 6 * MS)]
    t = trace({"/device:TPU:0": ops}, host)
    assert tr.window_of(t) == (10 * MS, 140 * MS)
    busy = tr.busy(t)
    # [10,20) + [30,60) + [100,140)
    assert busy["busy_s"] == pytest.approx(0.080)
    assert busy["window_s"] == pytest.approx(0.130)
    gaps = dict(tr.idle_gaps(t))
    # [20,30) has no annotation; [60,100) is shared out: fetch_loss covers
    # 15 ms of it, next_data 20, dispatch 5
    assert gaps == {"bench/next_data": pytest.approx(0.020),
                    "bench/fetch_loss": pytest.approx(0.015),
                    "bench/dispatch": pytest.approx(0.005),
                    "host: unannotated": pytest.approx(0.010)}


def test_collectives_exposed_or_hidden_averaged_over_devices():
    # device 0: an asynchronous all-gather from its start at 0 to the end of
    # its done at 40, with a fusion over [10, 30): 20 exposed;
    # device 1: all-reduce [0, 20) alone (exposed) and a fusion after it.
    # A while around everything is a parent, not compute that hides.
    d0 = [["while.9", 0, 100 * MS], ["all-gather-start.1", 0, 1 * MS],
          ["fusion.2", 10 * MS, 20 * MS], ["all-gather-done.1", 38 * MS, 2 * MS],
          ["fusion.3", 40 * MS, 60 * MS]]
    d1 = [["all-reduce.5", 0, 20 * MS], ["fusion.6", 20 * MS, 80 * MS]]
    t = trace({"/device:TPU:0": d0, "/device:TPU:1": d1})
    c = tr.collectives(t)
    assert c["collective_s"] == pytest.approx((0.040 + 0.020) / 2)
    assert c["exposed_s"] == pytest.approx((0.020 + 0.020) / 2)
    assert tr.async_spans(d0) == [["all-gather-start.1", 0, 40 * MS]]
    assert tr.async_spans([["copy-start", 5, 1], ["copy-done", 9, 2],
                           ["copy-done.7", 20, 1]]) == [["copy-start", 5, 6]]
    assert tr.is_collective("%collective-permute-start.3")
    assert tr.is_collective("async-collective-done")
    assert not tr.is_collective("fusion.all-gather")


def test_empty_trace_reads_nothing():
    t = trace({})  # no device plane
    assert tr.busy(t) is None and tr.collectives(t) is None
    assert tr.idle_gaps(t) == [] and tr.time_by_op(t) == {}
    assert tr.ops_by_name(t) == {} and tr.summarise(t) is None


def test_flash_calls_from_hlo_text():
    """Told by the name the program gives a call — on its ``op_name`` path,
    or where a line has no metadata the instruction's own — and listed where
    the results are as many as that kind gives."""
    meta = ('metadata={op_name="jit(train_step)/jvp(Transformer)/blocks/'
            'attention/multihead_attention/%s/pallas_call"}')
    text = """
  %flash_fwd.63 = (bf16[128,1024,64]{2,1,0}, f32[128,1024,1]{2,1,0}) custom-call(%a, %b, %c), custom_call_target="tpu_custom_call", backend_config={...}
  %flash_bwd_dq.65 = bf16[128,1024,64]{2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call"
  ROOT %x.66 = (bf16[100,1024,64]{2,1,0}, bf16[100,1024,64]{2,1,0}) custom-call(%a), custom_call_target="tpu_custom_call", """ + meta % "swa_bwd_dkv" + """
  %y.67 = (bf16[2,8192,6144]{2,1,0}, bf16[2,8192,6144]{2,1,0}, bf16[2,8192,4096]{2,1,0}) custom-call(%a), custom_call_target="tpu_custom_call", """ + meta % "mla_bwd" + """
  %rope_fwd.70 = bf16[2,8192,6144]{2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call"
  %rope_bwd.71 = bf16[2,8192,6144]{2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call"
  %grouped_weights.72 = bf16[16,2048,768]{2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call"
  %ssd_fwd.73 = (bf16[2,64,64,8192]{3,2,1,0}, f32[2,64,4096,128]{3,2,1,0}) custom-call(%a), custom_call_target="tpu_custom_call"
  %flash_fwd.74 = bf16[128,1024,64]{2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call"
  %other = f32[8]{0} custom-call(%a), custom_call_target="Sharding"
  %transpose_jvp_flash_bwd_dq__.1 = bf16[8,1024,1024]{2,1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(loss)/transpose(jvp(flash_bwd_dq))/pallas_call"}
  %jvp_flash_fwd_.1 = (bf16[8,1024,1024]{2,1,0}, f32[8,16,1024,1]{3,2,1,0}) custom-call(%a), custom_call_target="tpu_custom_call"
"""
    calls = hlo.flash_calls(text)
    # a kernel differentiated on its own (no model around it: tier-1's
    # tests/test_tpu_compile.py) carries the transforms' names around its own
    assert [(c["name"], c["kernel"], c["kind"]) for c in calls[4:]] == [
        ("transpose_jvp_flash_bwd_dq__.1", "flash_bwd_dq", "dq"),
        ("jvp_flash_fwd_.1", "jvp_flash_fwd", "fwd")]
    calls = calls[:4]
    assert [(c["name"], c["kernel"], c["kind"], c["batch_heads"])
            for c in calls] == [
        ("flash_fwd.63", "flash_fwd", "fwd", 128),
        ("flash_bwd_dq.65", "flash_bwd_dq", "dq", 128),
        ("x.66", "swa_bwd_dkv", "dkv", 100),
        ("y.67", "mla_bwd", "bwd", 2)]
    # the one-call backward's sizes are its first result's: dq's
    assert (calls[3]["seq"], calls[3]["head_dim"]) == (8192, 6144)


# ----------------------------------------------------------------- timeline
def rec(step, gen, t, dt=0.8, loss=5.0):
    return {"step": step, "generation": gen, "t": t, "step_time_s": dt,
            "loss": loss}


def made_up_run(extra_generation=False):
    """N = 5: C0 at step 5, window opens at t=104, S1 at step 10 stalls 3 s,
    kill after step 13 at t=116.5; the resume's first record at t=140."""
    records = [rec(s, 1, 100 + s) for s in range(1, 11)]          # t=101..110
    records += [rec(11, 1, 114.0), rec(12, 1, 115.0), rec(13, 1, 116.0)]
    gen2 = 3 if extra_generation else 2
    records += [rec(6, gen2, 140.0, dt=2.0), rec(7, gen2, 141.0),
                rec(8, gen2, 142.0), rec(9, gen2, 143.0)]
    timeline = [{"t": 90, "phase": "spawn", "gen": 1}]
    if extra_generation:  # a generation that spawned and never trained
        timeline += [{"t": 118, "phase": "spawn", "gen": 2}]
    timeline += [{"t": 122, "phase": "spawn", "gen": gen2},
                 {"t": 132, "phase": "trainer_built", "gen": gen2},
                 {"t": 137, "phase": "restored", "gen": gen2, "step": 5},
                 {"t": 140, "phase": "first_step_done", "gen": gen2}]
    return records, timeline


@pytest.mark.parametrize("extra", [False, True])
def test_timeline_reduce_on_a_made_up_run(extra):
    records, timeline = made_up_run(extra)
    t_open, t_close, t_kill, saves = 104.0, 150.0, 116.5, [5, 10]
    pairs = tl.pace_pairs(records, t_open, t_close, saves)
    # steps 4..10 are in the window: 6 pairs less 5->6 (C0's save); 11-12,
    # 12-13; three of the resumed generation; not 10->11 (S1's save), not
    # 13->6 (the kill)
    assert len(pairs) == 5 + 2 + 3
    assert tl.step_interval_s(records, t_open, t_close, saves) == 1.0
    assert tl.loop_overhead_pct(records, t_open, t_close, saves) == \
        pytest.approx(20.0)
    assert tl.save_stall_s(records, 10) == pytest.approx(4.0 - 0.8)
    assert tl.resume_s(records, t_kill, 1) == pytest.approx(23.5)
    gen = tl.resuming_generation(records, 1)
    assert gen == (3 if extra else 2)
    assert tl.extra_generations(timeline) == (1 if extra else 0)
    assert tl.phase_t(timeline, "spawn", gen) - t_kill == pytest.approx(5.5)
    assert tl.phase_span_s(timeline, gen, "spawn", "trainer_built") == 10
    assert tl.phase_span_s(timeline, gen, "trainer_built", "restored") == 5
    assert tl.phase_span_s(timeline, gen, "restored", "first_step_done") == 3
    assert tl.phase_span_s(timeline, gen, "restored", "nothing") is None
    assert tl.commit_s(records, {"5": 104.0}, 5) == pytest.approx(-1.0 + 0.0)
    assert tl.commit_s(records, {}, 10) is None


def test_no_kill_no_numbers():
    records = [rec(s, 1, 100 + s) for s in range(1, 4)]
    assert tl.resume_s(records, 103.5, 1) is None
    assert tl.save_stall_s(records, 10) is None
    assert tl.step_interval_s(records, 200, 300, []) is None


@pytest.mark.parametrize("stall, steps, seconds", [
    (0.0, 9, 9.0),    # nine steps of a second each
    (3.0, 9, 12.0),   # a save's stall among them: the rate falls with it
    (45.0, 5, 50.0),  # a compile that eats the window: five steps of it
])
def test_a_one_generation_windows_rate_is_all_steps_over_all_time(
        stall, steps, seconds):
    """From the record that opened the window (the newest at or before
    ``t_open``) to the window's last; the median of gaps sees none of it."""
    records = [rec(s, 1, 50.0 + s) for s in range(1, 6)]    # before the kill
    records += [rec(6, 2, 100.0 - 0.02)]                    # opens the window
    records += [rec(s, 2, 100.0 - 0.02 + (s - 6) + (stall if s > 9 else 0.0))
                for s in range(7, 16)]
    assert tl.window_steps_per_s(records, 100.0, 150.0) == pytest.approx(
        steps / seconds)
    assert tl.step_interval_s(records, 100.0, 150.0, []) == pytest.approx(1.0)
    # a kill between the two records: no one generation, no such rate
    assert tl.window_steps_per_s(records, 52.5, 150.0) is None
    assert tl.window_steps_per_s(records, 200.0, 250.0) is None
