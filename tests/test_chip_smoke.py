"""``chip_smoke.py`` on the CPU: its phases at a tiny size, and its refusal
to report a result without a TPU. The script itself runs at LiveJournal's
size on the chip; these tests keep its paths from rotting in between."""
import json
import os
import subprocess
import sys

import pytest

from conftest import REPO, run_with_devices

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402
from repro.configs.frogwild_graphs import GraphConfig  # noqa: E402

# n not a multiple of 4: the mesh paths then carry padding rows, as at
# LiveJournal's n.
TINY = GraphConfig("tiny", n=3001, avg_out_deg=14.2)


def test_one_chip_phases_pass_on_a_tiny_graph():
    with chip_smoke.Timer() as timer:
        chip_smoke.run_one_chip(TINY, timer)
    assert {"index build (walk index on the device)", "HTTP GET /topk",
            "batch FrogWild (svc.pagerank)"} <= set(timer.times)


def test_four_chip_phases_pass_on_virtual_devices():
    out = run_with_devices(f"""
import sys
sys.path.insert(0, {REPO!r})
import jax
import chip_smoke
from repro.configs.frogwild_graphs import GraphConfig
with chip_smoke.Timer() as timer:
    chip_smoke.run_four_chips(GraphConfig("tiny", n={TINY.n},
                                          avg_out_deg={TINY.avg_out_deg}),
                              timer, jax.devices())
print("FOUR-CHIP-PHASES-OK")
""", n_devices=4)
    assert "mesh == fused byte-identical: False" not in out
    assert "FOUR-CHIP-PHASES-OK" in out


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_no_result_without_a_tpu(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py", *argv], cwd=REPO,
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2, out.stderr
    assert "needs" in out.stderr
    assert not any(line.startswith("{") and json.loads(line).get("ok")
                   for line in out.stdout.splitlines())
