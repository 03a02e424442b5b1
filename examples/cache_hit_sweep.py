"""Reproduce paper Fig 7 (cache hit rate vs GPU expert capacity) using the
shared benchmark pipeline — prints the sweep for every policy.

Run:  PYTHONPATH=src python examples/cache_hit_sweep.py
"""
import sys
import os

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.fig7_cache_hit import run  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

if __name__ == "__main__":
    enable_compile_cache()
    run()
