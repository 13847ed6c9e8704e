#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest rate the system sustains.

    python3 benchmarks/chip/sweep.py --workload <cell> \
        --seed 7 --seconds 40 --fractions 0.5,0.55,0.6,0.65,0.7

One process on the chip.  It first serves the cell's configuration in a
closed loop with two full batches outstanding, which gives the capacity
(requests answered per second with every batch full); then it offers
open-loop Poisson traffic at each fraction of that capacity, with the
cell's accuracy target.  Each step prints one
JSON line: offered and answered rate, latency p50 and p95, and the ratio
of the mean latency of the last third of the requests to that of the first
third, which grows past 1 where the backlog grows through the window.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import numpy as np  # noqa: E402

from chipbench import spec  # noqa: E402
from chipbench.harness import run_cell  # noqa: E402


def step(workload, seed, seconds, traffic) -> dict:
    got: dict = {}
    run_cell(workload, seed, seconds, False, traffic=traffic, readings=got)
    run = got["run"]
    reqs = sorted(run.answered, key=lambda r: r.due)
    lat = np.array([r.answered - r.due for r in reqs])
    third = max(len(lat) // 3, 1)
    return {"answered": len(reqs), "window_s": run.window.seconds,
            "answered_per_s": len(reqs) / run.window.seconds,
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "late_over_early": float(lat[-third:].mean()
                                     / lat[:third].mean()),
            "gap": got["gap"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--fractions", default="0.5,0.7,0.8,0.9,1.0,1.1")
    args = ap.parse_args()
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    traffic = spec.load_traffic(cell["traffic"])
    bs = int(spec.load_config(bench, cell["config"])["assumed"]["batch_size"])
    closed = {"loop": "closed", "outstanding": 2 * bs,
              "pool_per_second": 24, "target_error": traffic["target_error"]}
    cap = step(args.workload, args.seed, args.seconds, closed)
    print(json.dumps({"closed_loop": cap}), flush=True)
    for i, f in enumerate(float(x) for x in args.fractions.split(",")):
        rate = f * cap["answered_per_s"]
        open_loop = {"loop": "open", "process": "poisson", "rate": rate,
                     "target_error": traffic["target_error"]}
        row = step(args.workload, args.seed + 1 + i, args.seconds,
                   open_loop)
        print(json.dumps({"fraction": f, "offered_per_s": rate, **row}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
