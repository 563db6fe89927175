"""Puts the benchmark's package and the program on the import path.

Run from the checkout's root:  JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))
