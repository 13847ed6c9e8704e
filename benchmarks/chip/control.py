#!/usr/bin/env python3
"""Readings that set a cell's limits: the program, its control and faults.

    python3 benchmarks/chip/control.py --workload <cell> \
        --seeds 11,12,13 --seconds 10 --paths program,high,bf16

One process on the chip.  For each seed and each product path it runs the
cell with that path in place of the program's worker products and prints
one JSON line with every number of the comparison (``chipbench.check``):
``program`` is the program itself, ``high`` the control (three bf16 passes,
the step below float32 at ``highest``), ``bf16`` one pass, and the planted
faults of ``chipbench.faults``, and ``host_f64`` the program's float64
host products as a witness.  ``--target`` replaces the traffic's accuracy
target.  The benchmark's own runs use none of these.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import spec  # noqa: E402
from chipbench.faults import CONTROLS, FAULTS, WITNESSES  # noqa: E402
from chipbench.harness import run_cell  # noqa: E402

PATHS = {"program": None, **CONTROLS, **FAULTS, **WITNESSES}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--paths", default="program,high")
    ap.add_argument("--target", type=float, default=None,
                    help="replace the traffic's accuracy target")
    args = ap.parse_args()
    bench = spec.load_benchmark()
    traffic = spec.load_traffic(
        spec.find_cell(bench, args.workload)["traffic"])
    if args.target is not None:
        traffic = dict(traffic, target_error=args.target)
    for seed in (int(s) for s in args.seeds.split(",")):
        for path in args.paths.split(","):
            got: dict = {}
            res = run_cell(args.workload, seed, args.seconds, False,
                           products=PATHS[path], traffic=traffic,
                           readings=got)
            run = got.pop("run")
            print(json.dumps({"seed": seed, "path": path,
                              "correct": res["correct"],
                              "attempted": res["attempted"],
                              "failed": res["failed"],
                              "window_s": run.window.seconds, **got}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
