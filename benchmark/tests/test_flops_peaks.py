"""lib/flops.py against hand counts, lib/peaks.py against its source."""

import json
import os

import pytest

from conftest import BENCH
from lib import flops, peaks

# Hand counts (vocab 50304, 1024 positions, tied head):
# medium: 50304*1024 + 1024*1024 + 24*(4*1024 + 4*(1024^2+1024)
#         + 1024*4096+4096 + 4096*1024+1024) + 2*1024
# xl:     50304*1600 + 1024*1600 + 48*(4*1600 + 4*(1600^2+1600)
#         + 1600*6400+6400 + 6400*1600+1600) + 2*1600
HAND = {"gpt2-medium": 354_871_296, "gpt2-xl": 1_557_686_400}


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(HAND))
def test_param_count_by_hand_and_from_the_real_tree(name):
    import jax

    from easydl_tpu.models.registry import get_model

    c = _config(name)
    by_formula = flops.gpt2_param_count(
        c["n_layer"], c["n_embd"], c["n_inner"], c["vocab_size"],
        c["n_positions"])
    assert by_formula == HAND[name]
    bundle = get_model(c["factory"], **c["kwargs"])
    tree = jax.eval_shape(bundle.init_fn, jax.random.PRNGKey(0))
    assert flops.count_params(tree) == HAND[name]


@pytest.mark.parametrize("name,expect", [
    # 6 N + 12 L d s
    ("gpt2-medium", 6 * 354_871_296 + 12 * 24 * 1024 * 1024),
    ("gpt2-xl", 6 * 1_557_686_400 + 12 * 48 * 1600 * 1024),
])
def test_train_flops_per_token(name, expect):
    c = _config(name)
    got = flops.train_flops_per_token(HAND[name], c["n_layer"], c["n_embd"],
                                      c["kwargs"]["seq_len"])
    assert got == expect
    assert got == pytest.approx({"gpt2-medium": 2.431e9,
                                 "gpt2-xl": 10.29e9}[name], rel=1e-3)


@pytest.mark.parametrize("kind,matmuls,mats,vecs", [
    ("fwd", 2, 4, 1), ("dq", 3, 5, 2), ("dkv", 4, 6, 2), ("bwd", 5, 8, 1)])
def test_flash_causal_cost_by_hand(kind, matmuls, mats, vecs):
    bh, s, d = 128, 1024, 64   # 8 sequences x 16 heads, the medium cell
    cost = flops.flash_causal_cost(kind, bh, s, d)
    # a full [s,d]x[d,s] product is 2 s^2 d; causal keeps s(s+1)/2 of s^2
    assert cost["flops"] == bh * matmuls * 2 * d * s * (s + 1) / 2
    assert cost["bytes"] == bh * (mats * s * d * 2 + vecs * s * 4)


@pytest.mark.parametrize("d,dv,per_score", [(128, 128, 640),
                                             (192, 128, 832)])
def test_the_one_call_backward_by_a_hand_count(d, dv, per_score):
    """The looped backward's ONE kernel (PR 39) makes five products a live
    pair — ``S^T = K Q^T``, ``dK = dS^T Q`` and ``dQ = dS K`` at the scores'
    depth ``d``, ``dP^T = V dO^T`` and ``dV = P^T dO`` at the values' ``dv``
    — so a head costs ``2 x pairs x (3 d + 2 dv)`` FLOP: 640 a score at 128 /
    128, 832 at 192 / 128; and it reads q, k, v, O, dO and writes dq, dk, dv
    once each, beside the float32 ``lse`` rows."""
    from lib import flops_joyai

    batch, seq, heads = 2, 8192, 32
    pairs = seq * (seq + 1) // 2
    assert 3 * d + 2 * dv == per_score
    want = batch * heads * 2.0 * pairs * per_score
    mla = flops_joyai.mla_flash_cost("bwd", batch, seq, heads, d, dv)
    assert mla["flops"] == want
    # q, k, dq, dk at the scores' width, v, O, dO, dv at the values'
    assert mla["bytes"] == batch * heads * seq * (
        (4 * d + 4 * dv) * 2 + 4)
    if d == dv:
        # on [batch, seq, heads x d], the width taken as one head's
        plain = flops.flash_causal_cost("bwd", batch, seq, heads * d)
        assert plain["flops"] == want
        assert plain["bytes"] == batch * (8 * seq * heads * d * 2 + seq * 4)
        # under grouped-query attention: the same products, k, v, dk, dv at
        # the key/value heads
        gqa = flops.flash_gqa_cost("bwd", batch, seq, heads, 4, d)
        assert gqa["flops"] == want
        assert gqa["bytes"] == batch * seq * (
            (4 * heads + 4 * 4) * d * 2 + heads * 4)
        # five products where the two-kernel backward makes seven
        two = sum(flops.flash_causal_cost(k, batch, seq, heads * d)["flops"]
                  for k in ("dq", "dkv"))
        assert plain["flops"] * 7 == two * 5
    # compute sets the roofline at every shape a cell runs
    assert mla["flops"] / 197e12 > mla["bytes"] / 819e9


def test_roofline_names_its_bound():
    v5e = peaks.PEAKS["TPU v5 lite"]
    cost = flops.flash_causal_cost("fwd", 128, 1024, 64)
    r = flops.roofline_seconds(cost["flops"], cost["bytes"],
                               v5e["bf16_flops_per_s"],
                               v5e["hbm_bytes_per_s"])
    assert r["bound"] == "compute"
    assert r["seconds"] == cost["flops"] / 197e12
    assert flops.roofline_seconds(1.0, 1e9, 197e12, 819e9)["bound"] == "memory"


def test_v5e_peaks_and_unknown_kind(monkeypatch):
    assert peaks.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert peaks.peak("TPU v5 lite", "hbm_bytes") == 16e9
    # exact match only, and the program's override does not reach here
    monkeypatch.setenv("EASYDL_CHIP_PEAK_TFLOPS", "1000")
    assert peaks.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    for kind in ("TPU v5", "tpu v5 lite", "TPU v5e", "cpu", ""):
        with pytest.raises(KeyError, match="no published peak"):
            peaks.peak(kind, "bf16_flops_per_s")
