"""The control of ``correct``, and the planted faults, at a cell's own
size on the chip (or tiny on the CPU with --rehearse-cpu):

    python benchmark/tests/control.py --workload k8m3-4m-write \
        --seconds 10 --seeds 1,2,3 [--fault parity_dropped]

One process; one run per seed with the fault planted, each printing
its checks. Every run has to come out not correct. The benchmark's own
runs never plant anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

import plant  # noqa: E402
from harness import runner  # noqa: E402
from harness.registry import Bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default="parity_dropped",
                    choices=sorted(plant.FAULTS))
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        runner.setup_cache()
    bench = Bench()
    k = int(bench.cell(args.workload).config["pool"]["ec_profile"]["k"])
    caught = 0
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        with plant.FAULTS[args.fault](k):
            r = runner.run(args.workload, seed, args.seconds, False,
                           rehearse=args.rehearse_cpu, bench=bench)
        caught += not r["correct"]
        print(json.dumps({"fault": args.fault, "seed": seed,
                          "correct": r["correct"], "checks": r["checks"],
                          "metrics": r["metrics"]}), flush=True)
    print(json.dumps({"fault": args.fault, "workload": args.workload,
                      "runs": len(seeds), "caught": caught}))
    return 0 if caught == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
