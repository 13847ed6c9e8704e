#!/usr/bin/env python3
"""Chip benchmark of the served coded matmul.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose jax sees a TPU; any
other platform exits non-zero with no result.  The cells, configurations,
metrics and bounds are in ``BENCHMARK.json``; ``benchmarks/chip/chipbench``
says where each file lives.  The last stdout line is the result: one JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` when traced), and last ``checks``, each compared number
beside its limit, as the last stderr lines also give them.
"""
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
