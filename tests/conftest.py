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

import faulthandler  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--test-limit", type=float, default=600.0,
        help="seconds a test may run before it is failed alone (tests "
             "marked slow are exempt): twice the dearest case of the "
             "driver's run at PR 39, 290 s")


_deadline = pytest.StashKey[float]()


def _own_limit(item, fresh=False):
    """One phase of ``item`` (set-up with every fixture it builds, the call,
    or the teardown) under the test's own limit: past ``--test-limit``
    seconds the test fails with its name and every thread's stack on stderr,
    and the run goes on — one hang costs one case, not the run's whole time
    limit. Set-up and call share one deadline; the teardown has a limit of
    its own (``fresh``), so that a test that ran out still cleans up. On
    ``SIGALRM`` (xdist's workers run tests in their main thread); a test
    marked ``slow`` is exempt. The handler is Python: it runs when the
    interpreter has control again, so a call that hangs inside C++ (a
    compile) is failed only once it returns — ``pytest_runtest_protocol``
    below leaves its stacks meanwhile."""
    seconds = item.config.getoption("--test-limit")
    if item.get_closest_marker("slow") or seconds <= 0:
        return (yield)
    if fresh or _deadline not in item.stash:
        item.stash[_deadline] = time.monotonic() + seconds

    def over(signum, frame):
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        pytest.fail(f"{item.nodeid} ran past its limit of {seconds:g} s",
                    pytrace=False)

    before = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL,
                     max(item.stash[_deadline] - time.monotonic(), 1e-3))
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


# Armed inside each phase's hook, never between them: what the handler raises
# is then always caught as that phase's outcome, not as a fault of pytest's.
@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_setup(item):
    return (yield from _own_limit(item))


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_call(item):
    return (yield from _own_limit(item))


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_teardown(item):
    return (yield from _own_limit(item, fresh=True))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item):
    """What ``SIGALRM`` cannot interrupt still leaves a trace: at one and a
    half times the limit (a test the handler did fail is over by then) a
    watchdog thread of ``faulthandler`` (C, needs no interpreter) writes
    every thread's stack where pytest's own fault handler writes, the run's
    stderr, and the test goes on hanging — the run's time limit ends it."""
    seconds = item.config.getoption("--test-limit")
    if item.get_closest_marker("slow") or seconds <= 0:
        return (yield)
    from _pytest.faulthandler import fault_handler_stderr_fd_key

    faulthandler.dump_traceback_later(
        1.5 * seconds, exit=False,
        file=item.config.stash.get(fault_handler_stderr_fd_key, 2))
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()


def normal(seed, *shapes, dtype="float32"):
    """Seeded standard-normal arrays, one a shape, drawn on the host: a
    kernel case's inputs cost no compile (``jax.random.normal`` is one a
    shape). ``dtype`` may be ``"bfloat16"``: rounded on the host too."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal(shape, np.float32).astype(
            jnp.dtype(dtype))) for shape in shapes)


def out_and_grads(fn, scalar):
    """ONE jitted program a case: ``run(*args) -> (fn(*args), gradients of
    scalar(fn(*args)) by every argument)`` — a kernel's forward and backward
    compiled together, once, not dispatched operation by operation."""
    import jax

    def run(*args):
        def loss(*a):
            out = fn(*a)
            return scalar(out), out

        grads, out = jax.grad(loss, argnums=tuple(range(len(args))),
                              has_aux=True)(*args)
        return out, grads

    return jax.jit(run)


def written_out(q, k, v, window):
    """Softmax attention with the mask written out entry by entry (numpy
    loops), bottom-right aligned: query i of the last s_q positions."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    mask = np.zeros((s_q, s_k), bool)
    for i in range(s_q):
        for j in range(s_k):
            at = i + s_k - s_q
            mask[i, j] = j <= at and (window is None or at - j < window)
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    scores = np.where(mask, scores, -np.inf)
    scores = scores - scores.max(-1, keepdims=True)
    p = np.exp(scores)
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.fixture
def described_tpu(monkeypatch):
    """The one answer to "is this a TPU?" (``ops/platform.on_tpu``) is yes
    for the rest of the test: a compile for a DESCRIBED chip holds the Mosaic
    kernels, not the interpreter's loops, though ``jax.devices()`` is the
    CPU. ``monkeypatch`` takes it back whatever the test does (left set by
    hand it failed nine later cases of a worker: PR 34's ledger lines)."""
    from easydl_tpu.ops import platform

    monkeypatch.setattr(platform, "on_tpu", lambda: True)


@pytest.fixture
def ssd_log(monkeypatch):
    """Messages ``ops/ssd.py`` logs during the test (the scan's ``ssd:`` and
    the convolutions' ``conv1d:`` lines), with ``log_once`` forgetting what
    earlier tests of this process said."""
    import logging

    from easydl_tpu.ops import ssd
    from easydl_tpu.utils import logging as easydl_logging

    monkeypatch.setattr(easydl_logging, "_logged_once", set())
    records = []
    handler = logging.Handler()
    handler.emit = lambda record: records.append(record.getMessage())
    ssd.log.addHandler(handler)
    yield records
    ssd.log.removeHandler(handler)


@pytest.fixture(scope="session")
def v5e_2x2():
    """The four devices of a DESCRIBED v5e 2x2 (``tests/test_tpu_compile*.py``
    compile for them; nothing runs there)."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # no libtpu here, or it cannot describe a v5e
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture
def no_persistent_cache():
    """A described-TPU executable is written to the persistent cache but
    cannot be read back without a chip (the next compile warns and
    recompiles) — turn the cache off around these compiles."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


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
    """``flash_kept()``: from the call on, every candidate of what remat
    keeps is dear enough and has room (``ops/remat.FLOOR_FLOP_PER_BYTE`` -> 0,
    and where no chooser is open one with room without end is), so that a
    test-size stack under remat keeps ``out`` and ``lse`` as JoyAI-LLM-Flash's
    and ZAYA1's cells do (and, under ``full``, its FFN's first products).
    Both are read when a block is TRACED: build the other side of a
    comparison before the call. What a call keeps is no argument of the
    program; a test steers it here."""
    def steer():
        import contextvars

        from easydl_tpu.ops import remat

        monkeypatch.setattr(remat, "FLOOR_FLOP_PER_BYTE", 0)
        monkeypatch.setattr(remat, "_chooser", contextvars.ContextVar(
            "easydl_remat_chooser_with_room", default=remat.Chooser(1 << 60)))

    return steer
