"""The control of a cell's `correct`: the program's broken path, judged.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...] [--seconds s]

Runs the cell on the chip at its own size with `faults.no_promotion`
switched on (remote acquires no longer promote, which breaks the
visibility guarantee the protocol gives), once per seed in one process,
and prints each run's result line.  Every run has to come out not
correct.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
        devices = run.preflight(cell.chips)
    except (run.Refused, KeyError, FileNotFoundError) as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    from repro.workloads import faults, harness
    proto = faults.no_promotion(
        harness.resolve_proto(cell.traffic["scenario"]))
    drv = run.load_driver(cell, proto)
    for seed in args.seeds:
        res = run.run_cell(cell, seed, args.seconds, False, devices=devices,
                           drv=drv)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
