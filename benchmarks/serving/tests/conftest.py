"""Tests of the benchmark's own code, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/serving/tests
"""
import os
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))
# CPU programs stay out of the checkout's compile cache, which serves the chip
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench_test_jax_cache_"))
