"""The suite's own harness (``conftest.py``). Its limit: a test that runs
past ``--test-limit``, in its call or in the set-up of a fixture it asks,
fails alone, with its name, and the run goes on to the next test; a call
inside C is not interrupted but leaves its stack. ``described_tpu``: the ONE answer to "is this a TPU?"
(``ops/platform.on_tpu``) that attention's ``auto`` and the expert layer both
ask, yes for a described-chip compile and taken back after it."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from easydl_tpu.ops import attention, moe, platform

HERE = os.path.dirname(os.path.abspath(__file__))


def _attention_asks() -> bool:
    """Whether ``impl="auto"`` asks for the Pallas kernels."""
    return attention._on_kernels("auto")


def _expert_layer_asks() -> bool:
    """Whether the expert layer's kernels are compiled (not interpreted),
    read from the ``pallas_call``s it traces to."""
    x = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 16, 8), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda h, weights, up, down, chosen: moe.routed_experts(
            h, chosen, weights, up, up, down, 0, 8))(
        x, jax.ShapeDtypeStruct((32, 2), jnp.float32), w,
        jax.ShapeDtypeStruct((4, 8, 16), jnp.float32),
        jax.ShapeDtypeStruct((32, 2), jnp.int32)))
    assert "pallas_call" in text
    return "interpret=False" in text and "interpret=True" not in text


@pytest.mark.parametrize("asks", [_attention_asks, _expert_layer_asks],
                         ids=["attention-auto", "expert-layer"])
@pytest.mark.parametrize("described", [False, True],
                         ids=["as-it-is", "described-tpu"])
def test_one_answer_to_is_this_a_tpu(request, asks, described):
    """On the CPU both say no; under ``described_tpu`` both say yes, by the
    one function; and whatever case runs next finds the answer taken back."""
    assert not platform.on_tpu()  # nothing leaked from an earlier test
    if described:
        request.getfixturevalue("described_tpu")
    assert platform.on_tpu() is described
    assert asks() is described


def test_the_answer_was_taken_back():
    assert not platform.on_tpu() and not _attention_asks()


def test_a_test_past_its_limit_fails_alone_and_the_run_goes_on(tmp_path):
    (tmp_path / "test_sleeps.py").write_text(textwrap.dedent("""
        import hashlib
        import time

        import pytest


        def test_hangs():
            time.sleep(30)


        def test_after_it():
            pass


        @pytest.fixture(scope="module")
        def dear():
            time.sleep(30)


        def test_hangs_in_set_up(dear):
            pass


        def test_hangs_in_c():
            def seconds(rounds):
                start = time.monotonic()
                hashlib.pbkdf2_hmac("sha256", b"x", b"y", rounds)
                return time.monotonic() - start

            # ONE call inside C of 3 s or more, by the fastest of three
            # timings (a busy machine only makes it longer)
            seconds(int(3.0 / min(seconds(50_000) for _ in range(3))) * 50_000)


        @pytest.mark.slow
        def test_slow_is_exempt():
            time.sleep(1.5)
    """))
    # the suite's conftest as a plugin of a run elsewhere, a limit of a second
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "conftest", "--test-limit", "1",
         "-p", "no:cacheprovider", "-q", "-rfE", str(tmp_path)],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=os.pathsep.join(
            [HERE, os.path.dirname(HERE)])))
    out = proc.stdout
    assert "2 failed, 2 passed" in out and "1 error in" in out, \
        out + proc.stderr
    assert "test_sleeps.py::test_hangs ran past its limit of 1 s" in out, out
    # every thread's stack, in the failed test's captured stderr
    assert 'test_sleeps.py", line 9 in test_hangs' in out, out
    # a fixture's set-up is inside the limit of the first test that asks it
    assert "ERROR test_sleeps.py::test_hangs_in_set_up" in out, out
    assert 'test_sleeps.py", line 18 in dear' in out, out
    # a call inside C is not interrupted: the watchdog leaves its stack on
    # the run's own stderr while it hangs, and the test fails with its name
    # once it returns
    assert "in test_hangs_in_c" in proc.stderr, proc.stderr
    assert "test_sleeps.py::test_hangs_in_c ran past its limit of 1 s" \
        in out, out
