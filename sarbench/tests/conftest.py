"""Benchmark unit tests: ``pytest sarbench/tests`` from the repository
root, on the CPU (Pallas kernels in interpret mode)."""
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
