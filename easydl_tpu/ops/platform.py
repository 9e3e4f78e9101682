"""The one place the ops ask what they run on: the Pallas kernels are
compiled on a TPU and nowhere else (``ops/attention.py`` takes the XLA
reference path off it, ``ops/moe.py`` the Pallas interpreter). A compile for
a DESCRIBED chip in a process whose ``jax.devices()`` is the CPU patches this
function and nothing else (``tests/conftest.py described_tpu``,
``scripts/rehearse_tpu_compile.py``)."""

import jax


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"
