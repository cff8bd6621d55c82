"""Drives whole runs of the tiny checkout on the CPU, past the harness's
look for a chip, with the program's timed path broken underneath."""
from __future__ import annotations

import time

from bench import harness

SEED = 2**31 + 4242
WINDOW_S = 1.5


def run(root, cell: str, control=None, trace=False) -> dict:
    return harness.run(cell, SEED, WINDOW_S, trace, time.time(), root=root,
                       require_tpu=False, cache=False, control=control)
