"""Shared test config. NOTE: no XLA_FLAGS here — tests see 1 real device;
multi-device behaviour is exercised via subprocess (test_multidevice.py)."""
import os
import subprocess
import sys
import warnings

import pytest
from hypothesis import settings

# Donation of per-wave walk-state operands leaves some donated inputs with
# no output to alias (one int32[W] output, five donated operands) —
# expected, not a leak (see repro/query/engine.py). pytest's warning
# capture overrides the library's import-time filter, so repeat it here.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


def pytest_configure(config):
    config.addinivalue_line(
        "filterwarnings",
        "ignore:Some donated buffers were not usable")

# Keep hypothesis fast and deadline-free on the CPU backend.
settings.register_profile("ci", max_examples=20, deadline=None)
settings.load_profile("ci")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_with_devices(script: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Runs a python snippet in a subprocess with N placeholder devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, timeout=timeout,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout
