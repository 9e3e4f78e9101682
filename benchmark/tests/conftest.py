"""The benchmark's own tests: ``python -m pytest benchmark/tests``.

They run on the CPU and test counts, reducers and control flow; no speed.
Tier-1 (``pytest tests/``) does not collect this directory — a benchmark PR
may add no file outside the benchmark's own."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("EASYDL_COMPILE_CACHE", "off")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
