"""The benchmark's own tests: run them by path, on the host CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parents[1] / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
