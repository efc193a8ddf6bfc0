#!/usr/bin/env python3
"""Runs of one cell on several seeds in one process, with a control or
a planted fault underneath, to read the numbers its limits are set
from.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s> --faults none,foreign_routes [--out results.jsonl]

For each fault (``none`` for the program as it is; the others are in
``canalbench.faults``) and each seed it makes one run as ``run.py``
would, prints a line ``<fault> seed=<n> correct=<bool>`` with every
number compared, and appends the run's result line, tagged with the
fault and seed, to ``--out``. It needs the chip the cell asks for; the
benchmark's own runs never plant anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH_DIR)),
                                "src"))

import run as bench  # noqa: E402
from canalbench import faults, registry  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", default="none")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    bench_json = registry.load_benchmark()
    cell = registry.cell(bench_json, args.workload)
    generator = registry.traffic(cell["traffic"])["generator"]
    for fault in args.faults.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            run_args = argparse.Namespace(workload=args.workload, seed=seed,
                                          seconds=args.seconds,
                                          trace=args.trace)
            with faults.planted(generator, fault):
                result = bench.measure(run_args)
            numbers = " ".join(f"{k}={v['value']}"
                               for k, v in result["checks"].items())
            print(f"{fault} seed={seed} correct={result['correct']} "
                  f"{numbers}", flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(dict(result, fault=fault,
                                            seed=seed)) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
