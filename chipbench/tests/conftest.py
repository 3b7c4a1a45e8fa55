"""Tests of the yardstick itself.  Run with ``JAX_PLATFORMS=cpu python3 -m
pytest chipbench/tests -q`` from the repo root; they are not part of the
repo's tier-1 suite."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# tests compile tiny programs; keep them out of the chip runs' cache
os.environ.setdefault("RLT_COMPILE_CACHE", "0")

TINY = {"n_layer": 2, "n_embd": 64, "n_head": 2, "n_positions": 64,
        "n_ctx": 64, "vocab_size": 512}
