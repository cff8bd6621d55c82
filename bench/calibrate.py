#!/usr/bin/env python3
"""Readings for the limits of ``limits/<configuration>.json``: one cell on
many seeds, and its control on a few, in one process (set-up is paid once
per seed, the compiles once).

    python3 bench/calibrate.py --workload <cell> --seconds 8 \\
        --seeds 101 102 ... --control index --control-seeds 201 202 203

Prints one JSON line per run: seed, control, correct, and the numbers
compared. The benchmark's own runs never run a control.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(ROOT, "bench", ".cache", "tpu_logs"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--control", default=None)
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--cpu", action="store_true",
                   help="rehearsal on the CPU: no chip, no compile cache")
    args = p.parse_args(argv)
    from bench import harness
    runs = [(s, None) for s in args.seeds]
    runs += [(s, args.control) for s in args.control_seeds]
    for seed, control in runs:
        r = harness.run(args.workload, seed, args.seconds, False, time.time(),
                        require_tpu=not args.cpu, cache=not args.cpu,
                        control=control)
        print("READING " + json.dumps(
            {"workload": args.workload, "seed": seed, "control": control,
             "correct": r["correct"], "metrics": r["metrics"],
             "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
