"""``lib/reference_phi4flash.py`` held to the equations it is the plain
form of, on small made-up numbers: the kinds by published index, the
recurrence against its closed form, a differential head against two
softmaxes written out, the window's edge, what the layers hand on."""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH
from lib import reference_phi4flash as ref

HP = {"layer_ids": (0, 1, 16, 17, 18, 19), "n_layers": 32, "heads": 4,
      "kv_heads": 2, "head_dim": 4, "window": 3, "d_state": 2, "dt_rank": 2,
      "d_conv": 4, "mb_per_layer": 2, "eps": 1e-5}


def _normal(seed, *shapes):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(s, np.float32))
                 for s in shapes)


def test_it_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "lib", "reference_phi4flash.py")) as f:
        tree = ast.parse(f.read())
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)] + [
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names]
    assert not [n for n in names if "easydl" in n or n.startswith("lib")]
    assert 'default_matmul_precision("highest")' in open(
        os.path.join(BENCH, "lib", "reference_phi4flash.py")).read()


def test_kinds_and_lambda_by_published_index():
    kinds = [ref.kind_of(i, HP) for i in range(32)]
    assert kinds[:4] == ["mamba", "window", "mamba", "window"]
    assert kinds[14:20] == ["mamba", "window", "mamba", "full", "gmu",
                            "cross"]
    assert kinds[30:] == ["gmu", "cross"]
    assert ref.lambda_init(0) == pytest.approx(0.2)
    assert ref.lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))


@pytest.mark.parametrize("seq", [5, 256], ids=["unchunked", "chunked"])
def test_the_recurrence_against_its_closed_form(seq):
    x, dt, a, B, C, D = _normal(1, (seq, 3), (seq, 3), (3, 2), (seq, 2),
                                (seq, 2), (3,))
    dt, A = jax.nn.softplus(dt), -jnp.exp(a)
    y = ref.recurrence(x, dt, A, B, C, D)
    # h_t = sum_{s <= t} exp(A sum_{s < r <= t} dt_r) dt_s x_s B_s
    total = jnp.cumsum(dt, 0)
    want = []
    for t in (0, seq // 2, seq - 1):
        decay = jnp.exp((total[t] - total[:t + 1])[:, :, None] * A)
        h = jnp.sum(decay * (dt * x)[:t + 1, :, None] * B[:t + 1, None, :], 0)
        want.append(h @ C[t] + D * x[t])
    np.testing.assert_allclose(y[jnp.array([0, seq // 2, seq - 1])],
                               jnp.stack(want), rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("window", [None, 3])
def test_a_differential_head_against_two_softmaxes(window):
    seq, d = 7, 4
    q, k, v, gain = _normal(2, (seq, 4, d), (seq, 2, d), (seq, 2, d), (2 * d,))
    lam = 0.3
    before, after = ref.diff_heads(q, k, v, lam, gain, 17, HP, window)
    t = np.arange(seq)
    seen = t[None, :] <= t[:, None]
    if window:
        seen &= t[None, :] > t[:, None] - window
    for j in range(2):  # pair j of group j // 2 = 0: keys 0, 1; value [v0;v1]
        value = jnp.concatenate([v[:, 0], v[:, 1]], -1)
        parts = []
        for c in range(2):
            s = q[:, 2 * j + c] @ k[:, c].T / 2.0
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
            parts.append(p @ value)
        want = parts[0] - lam * parts[1]
        np.testing.assert_allclose(before[:, j], want, rtol=1e-5, atol=1e-6)
        normed = want / jnp.sqrt(jnp.mean(want ** 2, -1, keepdims=True)
                                 + 1e-5) * gain
        np.testing.assert_allclose(
            after[:, j], (1 - ref.lambda_init(17)) * normed, rtol=1e-5,
            atol=1e-6)


def test_the_windows_edge():
    q, k = _normal(3, (1, 6, 4), (1, 6, 4))
    probs = ref.attention_probs(q, k, 3, 0)[0]
    live = np.asarray(probs > 0)
    assert [int(r.sum()) for r in live] == [1, 2, 3, 3, 3, 3]
    assert live[5, 3] and not live[5, 2] and not live[2, 3]
    # a block of rows that starts at 3 sees the same keys
    later = ref.attention_probs(q[:, 3:], k, 3, 3)[0]
    np.testing.assert_allclose(later, probs[3:], rtol=1e-6)


def test_the_convolution_is_causal_with_its_bias():
    x, w, b = _normal(4, (6, 2), (4, 2), (2,))
    y = ref.conv_silu(x, w, b)
    pre = w[3] * x[2] + w[2] * x[1] + w[1] * x[0] + b
    np.testing.assert_allclose(y[2], jax.nn.silu(pre), rtol=1e-6)
    np.testing.assert_allclose(y[0], jax.nn.silu(w[3] * x[0] + b), rtol=1e-6)


def test_layers_hand_on_memory_and_keys_and_values():
    """Layer 16 gives the memory (y, its D x in it, before the gate), layer
    17 its K and V; the unit multiplies exactly that y, the cross layer
    scores against exactly those."""
    d, inner, n, r = 16, 32, 2, 2
    u, = _normal(5, (6, d))
    p = dict(zip(
        ("in_proj", "conv_w", "conv_b", "x_proj", "dt_proj", "dt_bias",
         "A_log", "D", "out_proj"),
        _normal(6, (d, 2 * inner), (4, inner), (inner,), (inner, r + 2 * n),
                (r, inner), (inner,), (inner, n), (inner,), (inner, d))))
    out, y = ref.mamba(u, p, HP)
    x, z, dt, B, C = ref.mamba_operands(u, p, HP)
    np.testing.assert_allclose(
        y, ref.recurrence(x, dt, -jnp.exp(p["A_log"]), B, C, p["D"]),
        rtol=1e-6)
    np.testing.assert_allclose(out, (y * jax.nn.silu(z)) @ p["out_proj"],
                               rtol=1e-5, atol=1e-5)
    g_in, g_out = _normal(7, (d, inner), (inner, d))
    np.testing.assert_allclose(
        ref.gmu(u, {"gmu_in": g_in, "gmu_out": g_out}, y),
        (y * jax.nn.silu(u @ g_in)) @ g_out, rtol=1e-5, atol=1e-5)
