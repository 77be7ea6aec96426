"""The benchmark's own tests run on the CPU, without the chip:

    python -m pytest benchmarks/chip/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parents[1] / "src"))

# a small configuration with the kinds of leaves the real ones have
TINY_CONFIG = {
    "name": "tiny", "dtype": "bfloat16", "init_std": 0.02,
    "leaves": [
        {"path": ["blocks", "w_in"], "shape": [2, 128, 192],
         "init": "normal"},
        {"path": ["blocks", "norm"], "shape": [2, 128], "init": "ones"},
        {"path": ["blocks", "a_log"], "shape": [2, 48], "init": "zeros"},
        {"path": ["embed"], "shape": [1000, 128], "init": "normal"},
    ],
}
