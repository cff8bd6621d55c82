#!/usr/bin/env python3
"""Runs one cell of the chip benchmark on the TPU it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. One process, one cell. It needs as many TPU
chips as the cell asks for and exits 2, printing no result, where JAX finds
fewer (there is no CPU fallback). Set-up lines, the window's counts, the
compiles inside the window (there should be none) and the peak device
bytes go to standard output; the numbers compared with the reference,
each beside its limit, are the last lines of standard error. The last line
of standard output is the result: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and the
compared numbers under ``checks``.
"""
import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime's logs stay inside the checkout, not in a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(ROOT, "bench", ".cache", "tpu_logs"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    from bench import harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_PROCESS)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
