"""The chip benchmark's harness (``benchmarks/chip/chipbench``) on the path."""
import os
import sys

_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "chip")
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)
