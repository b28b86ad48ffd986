"""Tests of the benchmark itself (not tier-1: they sit with the yardstick).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
