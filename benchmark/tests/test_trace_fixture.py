"""trace_reduce on a trace recorded on the chip (PR 22, chip call 1): 101
device events around a step boundary of gpt2-medium.steady on one v5e, kept
in the reducer's own structure (see the fixture's ``about``)."""

import importlib.util
import json
import os

import pytest

from conftest import BENCH, HERE
from lib import flops, peaks, trace_reduce as tr


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(HERE, "fixtures",
                           "chip_trace_step_boundary.json")) as f:
        return json.load(f)


def test_busy_and_window(fixture):
    busy = tr.busy(fixture["trace"])
    assert busy["window_s"] == pytest.approx(8.470522e-3)
    assert busy["busy_s"] == pytest.approx(4.339698e-3)
    by_op = tr.time_by_op(fixture["trace"])
    assert sum(by_op.values()) == pytest.approx(busy["busy_s"])
    summary = tr.summarise(fixture["trace"], top=1)
    assert summary["top_ops"][0][0] == "convert_element_type.300"


def test_idle_gap_between_two_steps_by_host_span(fixture):
    gaps = dict(tr.idle_gaps(fixture["trace"]))
    # the device stands idle while the host still waits for the loss, draws
    # the next batch, and until the dispatched program starts
    assert gaps["bench/fetch_loss"] == pytest.approx(3.216649e-3)
    assert gaps["bench/dispatch"] == pytest.approx(0.541605e-3)
    assert gaps["bench/next_data"] == pytest.approx(0.36254e-3)
    busy = tr.busy(fixture["trace"])
    assert sum(gaps.values()) == pytest.approx(
        busy["window_s"] - busy["busy_s"])


def test_flash_call_found_by_its_hlo_name_and_held_to_the_roofline(fixture):
    calls = fixture["flash_calls"]
    ops = tr.ops_by_name(fixture["trace"])
    ran = {c["name"]: ops[c["name"]] for c in calls if c["name"] in ops}
    assert ran == {"tpu_custom_call.63": {
        "seconds": pytest.approx(0.589098e-3), "calls": 1}}
    took = ran["tpu_custom_call.63"]["seconds"]
    fwd = next(c for c in calls if c["name"] == "tpu_custom_call.63")
    assert fwd["kind"] == "fwd"
    cost = flops.flash_causal_cost("fwd", fwd["batch_heads"], fwd["seq"],
                                   fwd["head_dim"])
    least = flops.roofline_seconds(
        cost["flops"], cost["bytes"],
        peaks.peak("TPU v5 lite", "bf16_flops_per_s"),
        peaks.peak("TPU v5 lite", "hbm_bytes_per_s"))
    assert least["bound"] == "compute"
    # 17.2 GFLOP at 197 TFLOP/s is 87 us; the call took 589 us
    assert 100 * least["seconds"] / took == pytest.approx(14.8, abs=0.1)


def test_the_readers_on_the_recorded_trace(fixture):
    """The per-layer readers, given the artifacts a traced run would hold."""
    artifacts = {"trace_summary": tr.summarise(fixture["trace"]),
                 "flash_calls": fixture["flash_calls"],
                 "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    assert reader("flash_roofline")(artifacts) == pytest.approx(14.8, abs=0.1)
    assert reader("flash_time_pct")(artifacts) == pytest.approx(
        100 * 0.589098 / 4.339698)
    assert reader("device_idle_pct")(artifacts) == pytest.approx(
        100 * (1 - 4.339698 / 8.470522))
    assert reader("collective_pct")(artifacts) == 0
    assert reader("collective_exposed_pct")(artifacts) == 0
    # no trace, nothing to read; a TPU the table does not know is an error
    for name in ("flash_roofline", "flash_time_pct", "device_idle_pct",
                 "collective_pct", "collective_exposed_pct"):
        assert reader(name)({"trace_summary": None}) is None
    artifacts["device"]["kind"] = "TPU v9"
    with pytest.raises(KeyError, match="no published peak"):
        reader("flash_roofline")(artifacts)


def test_no_collective_on_one_chip(fixture):
    assert tr.collectives(fixture["trace"]) == {"collective_s": 0.0,
                                                "exposed_s": 0.0}
