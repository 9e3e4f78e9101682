"""``lib/scope_names.py`` on paths and self times written by hand: a name's
seconds, nothing where the program has no such name, and the scan's roofline
share from counts."""

import pytest

from lib import flops_ssd, scope_names

STEP = "jit(train_step)/transpose(jvp(Transformer))/while/body/closed_call"
PATHS = {
    "fusion.1": "jit(train_step)/jvp(Transformer)/while/body/closed_call/"
                "blocks_0/ssm/in_x/dot_general",
    "fusion.2": f"{STEP}/checkpoint/rematted_computation/blocks_0/ssm/ssd/"
                "while/body/checkpoint/dot_general",
    "fusion.3": f"{STEP}/checkpoint/blocks_0/ssm/ssd/while/body/checkpoint/"
                "rematted_computation/exp",
    "fusion.4": f"{STEP}/checkpoint/blocks_0/ssm/conv1d/mul",
    "fusion.5": f"{STEP}/checkpoint/blocks_1/attention/q/dot_general",
    "fusion.6": "jit(train_step)/optimizer/add",
    "copy.7": "",
}
SELF = {"fusion.1": 1.0, "fusion.2": 2.0, "fusion.3": 0.5, "fusion.4": 0.25,
        "fusion.5": 4.0, "fusion.6": 1.0, "copy.7": 0.25}


@pytest.mark.parametrize("name,seconds", [
    ("ssm", 3.75), ("ssd", 2.5), ("conv1d", 0.25), ("attention", 4.0)])
def test_seconds_under_a_name(name, seconds):
    assert scope_names.seconds_under(name, PATHS, SELF) == seconds


def test_a_name_the_program_does_not_have_reads_nothing():
    """The parent's program has no ``ssm``: None, not 0."""
    parent = {k: v for k, v in PATHS.items() if "ssm" not in v}
    assert scope_names.seconds_under("ssm", parent, SELF) is None
    assert scope_names.seconds_under("ssd", parent, SELF) is None


def test_a_primitive_is_not_a_name():
    # "mul" ends fusion.4's path: the primitive, not a scope
    assert scope_names.seconds_under("mul", PATHS, SELF) is None


def test_no_traced_run_reads_nothing():
    for artifacts in ({}, {"trace_summary": None}):
        assert scope_names.name_pct(artifacts, "ssm") is None
        assert scope_names.ssd_roofline_of_run(artifacts) is None


def test_scan_roofline_share_from_counts():
    config = {"mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
              "mamba_n_groups": 1, "mamba_chunk_size": 256,
              "layer_types": ["mamba"] * 5 + ["attention"],
              "kwargs": {"seq_len": 4096}}
    traffic = {"trace_steps": 4, "global_batch": 8}
    tokens = 4 * 8 * 4096 * 5
    cost = flops_ssd.ssd_train_cost_per_token(64, 64, 128, 1, 256)
    least = tokens * cost["flops"] / 197e12  # compute bound
    assert least == pytest.approx(0.04251, rel=1e-3)
    got = scope_names.ssd_roofline_pct(config, traffic, 0.5, 197e12, 819e9)
    assert got == pytest.approx(100 * least / 0.5)
    assert got < 100
