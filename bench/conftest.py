"""Test settings for the benchmark's own tests: JAX on the CPU, and the
benchmark's modules and the program's sources on the path."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.abspath(__file__))
for _p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
