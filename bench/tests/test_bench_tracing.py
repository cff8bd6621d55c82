"""The trace reduction: device busy union, idle share, top device ops,
idle gaps named by the host span that covers them, program counts."""
from pathlib import Path

import pytest

from bench import tracing

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6


def ev(plane, line, name, t_ms, d_ms):
    return (plane, line, name, t_ms * MS, d_ms * MS)


EVENTS = [
    ev(HOST, "python", "bench.trace_window", 0, 100),
    ev(HOST, "python", "bench.step", 0, 40),
    ev(HOST, "python", "bench.poll", 40, 20),
    ev(HOST, "python", "bench.step", 60, 40),
    ev(DEV, "XLA Modules", "jit_wave(1)", 10, 10),
    ev(DEV, "XLA Ops", "gather", 10, 6),
    ev(DEV, "XLA Ops", "scatter", 14, 6),      # overlaps the gather by 2
    ev(DEV, "XLA Modules", "jit_wave(1)", 70, 5),
    ev(DEV, "XLA Ops", "gather", 70, 5),
    ev(DEV, "XLA Ops", "gather", 150, 5),      # after the window
]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    red = tracing.reduce(EVENTS)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.015)
    assert red["busy_s"] <= red["window_s"]


def test_top_ops_and_program_counts():
    red = tracing.reduce(EVENTS)
    assert red["device_ops"][0] == ["gather", pytest.approx(0.011)]
    assert red["device_ops"][1] == ["scatter", pytest.approx(0.006)]
    assert red["modules"] == {"jit_wave(1)": 2}
    assert tracing.program_of("jit_wave(1)") == "waves_run"
    assert tracing.program_of("jit__walks_program") == "estimates"
    assert tracing.program_of("jit_wave_prep(3)") is None


def test_idle_gaps_are_named_by_the_host_span_covering_them():
    red = tracing.reduce(EVENTS)
    gaps = red["idle_gaps"]
    # gaps: 0-10 (step), 20-70 (poll 40-60 covers 20 ms, steps 20+10),
    # 75-100 (step)
    assert [g[1] for g in gaps] == pytest.approx([0.05, 0.025, 0.01])
    assert [g[0] for g in gaps] == ["bench.step", "bench.step", "bench.step"]
    assert sum(g[1] for g in gaps) == pytest.approx(0.1 - red["busy_s"])


def test_no_window_or_no_device_gives_nothing():
    assert tracing.reduce([e for e in EVENTS
                           if e[2] != "bench.trace_window"]) is None
    assert tracing.reduce([e for e in EVENTS if e[0] == HOST]) is None


def test_events_round_trip_through_a_file(tmp_path):
    path = Path(tmp_path) / "events.json"
    tracing.save_events(EVENTS, path)
    assert tracing.read_events(path) == EVENTS


RECORDED = Path(__file__).resolve().parent / "data" / "trace_lj_refresh.json"


def test_recorded_tpu_trace():
    """1.2 s of a traced lj-ppr-refresh window on a TPU v5 lite (op texts
    cut to 80 characters): six waves, the device idle most of the time."""
    import numpy as np
    events = tracing.read_events(RECORDED)
    red = tracing.reduce(events)
    assert red["window_s"] == pytest.approx(1.2)
    # the busy union, recomputed on a microsecond grid
    w0 = next(e[3] for e in events if e[2] == tracing.WINDOW_SPAN)
    grid = np.zeros(1_200_000, bool)
    for p, line, name, t, d in events:
        if line == tracing.OPS_LINE:
            a = int(max(t - w0, 0) // 1e3)
            b = int(min(t + d - w0, 1.2e9) // 1e3)
            grid[a:b] = True
    assert red["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=0.02)
    assert 0.9 < 1 - red["busy_s"] / red["window_s"] < 1
    waves = sum(c for m, c in red["modules"].items()
                if tracing.program_of(m) == "waves_run")
    assert waves == 6
    assert red["device_ops"][0][0] == "while.10"
    assert all(name.startswith("bench.") for name, _ in red["idle_gaps"])
    assert sum(s for _, s in red["idle_gaps"]) <= red["window_s"]


def test_recorded_tpu_trace_reduces_as_it_always_has():
    """The fields of the reduction before the program's spans were read
    (``reduced.json``: the same trace reduced then) are unchanged, and
    the idle time splits whole among the host spans."""
    import json
    red = tracing.reduce(tracing.read_events(RECORDED))
    before = json.loads(RECORDED.with_suffix(".reduced.json").read_text())
    for key in ("busy_s", "window_s", "device_ops", "idle_gaps", "modules"):
        assert red[key] == before[key], key
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-12)
    assert set(red["idle_by_span"]) <= {"bench.step", "bench.poll",
                                        "bench.submit", "host"}
    assert red["span_counts"]["bench.step"] == 6


# the program's stage spans inside the harness's first step: step_wave
# 2-38 holds prepare 4-9 and d2h 20-30
NESTED = EVENTS + [
    ev(HOST, "python", "repro.scheduler.step_wave", 2, 36),
    ev(HOST, "python", "repro.wave.prepare", 4, 5),
    ev(HOST, "python", "repro.wave.d2h", 20, 10),
]


def test_idle_time_goes_to_the_innermost_host_span():
    red = tracing.reduce(NESTED)
    # idle 0-10 (step 0-2, step_wave 2-4 and 9-10, prepare 4-9),
    # 20-70 (d2h 20-30, step_wave 30-38, step 38-40, poll 40-60,
    # step 60-70), 75-100 (step)
    want = {"bench.step": 0.002 + 0.002 + 0.010 + 0.025,
            "repro.scheduler.step_wave": 0.002 + 0.001 + 0.008,
            "repro.wave.prepare": 0.005, "repro.wave.d2h": 0.010,
            "bench.poll": 0.020}
    assert red["idle_by_span"] == pytest.approx(want)
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert red["span_counts"] == {
        "bench.step": 2, "bench.poll": 1, "repro.scheduler.step_wave": 1,
        "repro.wave.prepare": 1, "repro.wave.d2h": 1}


def test_idle_gaps_are_named_by_the_harness_spans_alone():
    with_program = tracing.reduce(NESTED)
    without = tracing.reduce(EVENTS)
    for key in ("busy_s", "window_s", "device_ops", "idle_gaps", "modules"):
        assert with_program[key] == without[key], key


def test_idle_outside_every_span_is_the_hosts():
    events = [ev(HOST, "python", "bench.trace_window", 0, 10),
              ev(DEV, "XLA Ops", "gather", 2, 2)]
    red = tracing.reduce(events)
    assert red["idle_by_span"] == pytest.approx({"host": 0.008})
    assert red["span_counts"] == {}
