"""``lib/scope_names.py`` and ``lib/told.py`` on paths and self times written
by hand: seconds under names, nothing where the program has no such name,
what a configuration's module tells, and the scan's roofline share from
counts."""

import pytest

from lib import flops_ssd, scope_names, scope_reduce, told

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
HYBRID = {"mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
          "mamba_n_groups": 1, "mamba_chunk_size": 256,
          "layer_types": ["mamba"] * 5 + ["attention"],
          "kwargs": {"seq_len": 4096},
          "readers": {"module": "cell_granite_hybrid"}}


@pytest.fixture
def traced(monkeypatch):
    """A traced run stood in: ``PATHS`` and ``SELF``."""
    def run(paths=PATHS, **more):
        monkeypatch.setattr(scope_reduce, "of_run", lambda artifacts: {
            "paths": paths, "whole_paths": True,
            "total_s": sum(SELF.values())})
        monkeypatch.setattr(scope_reduce, "trace_file", lambda: __file__)
        monkeypatch.setattr(scope_names, "_self_seconds",
                            lambda path, mtime: SELF)
        return dict({"trace_summary": {"ops": {}}}, **more)
    return run


@pytest.mark.parametrize("name,seconds", [
    ("ssm", 3.75), ("ssd", 2.5), ("conv1d", 0.25), ("attention", 4.0)])
def test_seconds_under_a_name(traced, name, seconds):
    assert scope_names.seconds_under(traced(), (), (name,)) == seconds
    assert scope_names.pct_under_any(traced(), (name,)) == pytest.approx(
        100.0 * seconds / 9.0)


def test_every_name_of_one_list_and_any_of_another(traced):
    # the ``ssd`` scope of run ``blocks_0`` alone; an operation once
    run = traced()
    assert scope_names.seconds_under(run, ("ssd",), ("blocks_0",)) == 2.5
    assert scope_names.seconds_under(run, ("ssd",), ("blocks_1",)) is None
    assert scope_names.seconds_under(run, (), ("ssm", "ssd")) == 3.75
    assert scope_names.seconds_under(run, (), ("ssm", "attention")) == 7.75


def test_a_name_the_program_does_not_have_reads_nothing(traced):
    """The parent's program has no ``ssm``: None, not 0."""
    parent = {k: v for k, v in PATHS.items() if "ssm" not in v}
    assert scope_names.seconds_under(traced(parent), (), ("ssm",)) is None
    assert scope_names.pct_under_any(traced(parent), ("ssd",)) is None


def test_a_primitive_is_not_a_name(traced):
    # "mul" ends fusion.4's path: the primitive, not a scope
    assert scope_names.seconds_under(traced(), (), ("mul",)) is None


def test_no_traced_run_reads_nothing():
    for artifacts in ({}, {"trace_summary": None}):
        assert scope_names.pct_under_any(artifacts, ("ssm",)) is None
        assert told.ssd_roofline_pct(dict(artifacts, config=HYBRID)) is None
        assert told.share_pct(dict(artifacts, config=HYBRID),
                              "attn_time_pct") is None


def test_what_a_module_does_not_state_reads_nothing(traced):
    """A quantity the cell's module has no word on, a configuration that
    names no module: None, and nothing raises."""
    run = traced(config=HYBRID, flash_calls=[{"name": "x"}])
    assert told.share_pct(run, "band_attn_time_pct") is None
    assert told.kernel_roofline_pct(run, "flash_dq_roofline") is None
    bare = traced(config={"kwargs": {"seq_len": 4096}},
                  flash_calls=[{"name": "x"}],
                  device={"platform": "tpu", "kind": "TPU v5 lite"},
                  step_s=[1.0])
    assert told.module_of(bare) is None
    assert told.share_pct(bare, "attn_time_pct") is None
    assert told.kernel_roofline_pct(bare, "flash_fwd_roofline") is None
    assert told.mfu_pct(bare) is None and told.ssd_roofline_pct(bare) is None


def test_run_names():
    runs = [("full_attention", "dense", 1), ("sliding_attention", "sparse", 3),
            ("full_attention", "sparse", 1)]
    assert told.run_names(runs, "full_attention") == ("blocks_0", "blocks_2")
    assert told.run_names(runs, "sliding_attention") == ("blocks_1",)
    assert told.run_names(runs[:1], "full_attention") == ("blocks",)


def test_scan_roofline_share_from_counts(traced):
    traffic = {"trace_steps": 4, "global_batch": 8}
    tokens = 4 * 8 * 4096 * 5
    cost = flops_ssd.ssd_train_cost_per_token(64, 64, 128, 1, 256)
    least = tokens * cost["flops"] / 197e12  # compute bound
    assert least == pytest.approx(0.04251, rel=1e-3)
    run = traced(config=HYBRID, traffic=traffic,
                 device={"platform": "tpu", "kind": "TPU v5 lite"})
    got = told.ssd_roofline_pct(run)  # 2.5 s under ``ssd``
    assert got == pytest.approx(100 * least / 2.5)
    assert got < 100
