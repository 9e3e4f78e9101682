"""Test bootstrap: force an 8-device CPU platform so every sharding/collective
path runs without TPU hardware (SURVEY.md §4 item 3).

Must run before jax initialises its backends, hence the env vars are set at
import time of conftest (pytest imports conftest before test modules).
"""

import os

# Force, not setdefault: tests run on the forced-multi-device CPU platform
# whatever the environment says (on a TPU host jax would otherwise take the
# chip).
# Appended (not prepended): XLA parses duplicate flags last-wins, so ours must
# come after any copy inherited from the environment.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "0"

# Route the host-local chunk cache (core/chunk_cache.py) into a per-session
# tmp dir instead of /dev/shm: the cache stays exercised by every checkpoint
# test (including subprocess workers, which inherit the env), while repeated
# suite runs can't accumulate tmpfs debris. Tests that need it off/elsewhere
# monkeypatch over this.
import tempfile  # noqa: E402

_cache_root = tempfile.mkdtemp(prefix="easydl-test-chunk-cache-")
os.environ.setdefault("EASYDL_CHUNK_CACHE", _cache_root)

# Persistent compile cache: OFF for the suite, here and in every worker it
# spawns (they inherit the env; utils/env.py configure_compile_cache honours
# "off"). Tests must compile what they test — an entry left by an earlier
# run, or by another process of this one, must not stand in for it — and the
# described-TPU compiles (test_tpu_compile.py) cannot be read back without
# a chip anyway. An explicit EASYDL_COMPILE_CACHE in the environment wins.
os.environ.setdefault("EASYDL_COMPILE_CACHE", "off")

from easydl_tpu.utils.env import configure_compile_cache  # noqa: E402

configure_compile_cache()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 forced CPU devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture
def fused_head(monkeypatch):
    """``fused_head(chunk_rows=None)``: from the call on, every shape gets
    the fused chunked head (``models/lm.FUSED_HEAD_LOGITS_BYTES`` -> 0) and,
    where ``chunk_rows`` is named, chunks of that many rows a device
    (``ops/fused_xent.CHUNK_ROWS``), so that a test-size model runs the head
    the hybrid's cell runs, in several chunks. Both constants are read when
    a loss is TRACED: build the full-logits side of a comparison before the
    call. Which head a shape gets is no argument of the program; a test
    steers it here."""
    def steer(chunk_rows=None):
        from easydl_tpu.models import lm
        from easydl_tpu.ops import fused_xent

        monkeypatch.setattr(lm, "FUSED_HEAD_LOGITS_BYTES", 0)
        if chunk_rows is not None:
            monkeypatch.setattr(fused_xent, "CHUNK_ROWS", chunk_rows)

    return steer


@pytest.fixture
def flash_kept(monkeypatch):
    """``flash_kept()``: from the call on, every flash forward is dear
    enough to keep (``ops/remat.FLASH_KEEP_FLOP_PER_BYTE`` -> 0), so that a
    test-size stack under remat keeps ``out`` and ``lse`` as JoyAI-LLM-Flash's
    and ZAYA1's cells do. The constant is read when the kernel's
    differentiation rule is TRACED: build the other side of a comparison
    before the call. What a call keeps is no argument of the program; a test
    steers it here."""
    def steer():
        from easydl_tpu.ops import remat

        monkeypatch.setattr(remat, "FLASH_KEEP_FLOP_PER_BYTE", 0)

    return steer
