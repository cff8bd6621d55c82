"""Metric arithmetic and the per-layer readers, on made-up runs."""
import math

import pytest

from bench import stats
from bench.registry import Benchmark


def test_rate_over_the_window():
    assert stats.rate(45, 30.0) == 1.5
    with pytest.raises(ValueError):
        stats.rate(3, 0.0)


def test_p90_counts_refused_requests_as_later_than_any():
    served = [0.1 * i for i in range(1, 10)]          # 9 answers
    assert stats.percentile(served + [0.05], 90) == pytest.approx(0.8)
    # one refused request in ten: the 90th percentile is still an answer
    assert stats.percentile(served + [math.inf], 90) == pytest.approx(0.9)
    # two in ten: it is the refusal
    assert stats.percentile(served[:8] + [math.inf] * 2, 90) == math.inf


def test_percentile_nearest_rank():
    assert stats.percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert stats.percentile([5.0], 90) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_occupancy_from_counter_deltas():
    assert stats.occupancy(81920, 10, 8192) == 1.0
    assert stats.occupancy(8192 * 9 + 2792, 10, 8192) == pytest.approx(
        (8192 * 9 + 2792) / 81920)
    assert stats.occupancy(0, 0, 8192) is None


RUN = {
    "loop": "closed", "window_s": 30.0, "max_walks": 8192,
    "counters": {"waves_run": 10, "walks_executed": 70000, "requests": 40,
                 "cache_hits": 6, "joins": 2, "sheds": 0, "rejected": 0,
                 "finished": 16, "tally_bytes_out": 1_551_225_600,
                 "repro.scheduler.step_wave.s": 2.2,
                 "repro.scheduler.step_wave.n": 10,
                 "repro.scheduler.admit.s": 0.0045,
                 "repro.wave.prepare.s": 0.091,
                 "repro.wave.launch.s": 0.035,
                 "repro.wave.device_wait.s": 0.1,
                 "repro.wave.d2h.s": 0.6,
                 "repro.scheduler.accumulate.s": 1.2,
                 "repro.scheduler.finalize.s": 0.248,
                 "repro.scheduler.finalize.n": 16},
    "waves_host": 10, "wave_s": 5.0, "index_build_s": 18.5,
    "setup_spans": {"repro.index.walk": {"n": 8, "s": 11.0, "self_s": 11.0},
                    "repro.index.copy_out": {"n": 9, "s": 6.0,
                                             "self_s": 6.0}},
    "trace": {"busy_s": 0.25, "window_s": 5.0, "device_ops": [],
              "idle_gaps": [], "modules": {}},
    "trace_counts": {"waves_run": 10, "estimates": 4},
}


@pytest.mark.parametrize("name, want", [
    ("gateway.cache_hit_share", 8 / 40),
    ("scheduler.walk_occupancy", 70000 / 81920),
    ("scheduler.wave_ms", 500.0),
    ("scheduler.wave_ms.interactive", 500.0),
    ("wave.device_ms", 25.0),
    ("batch.device_ms", 62.5),
    ("device.idle_share.serve", 0.95),
    ("device.idle_share.interactive", 0.95),
    ("device.idle_share.batch", 0.95),
    ("index.build_s", 18.5),
    ("scheduler.step_wave_ms", 220.0),
    ("scheduler.admit_ms", 0.45),
    ("wave.prepare_ms", 9.1),
    ("wave.launch_ms", 3.5),
    ("wave.device_wait_ms", 10.0),
    ("wave.d2h_ms", 60.0),
    ("wave.tally_mb", 155.12256),
    ("scheduler.accumulate_ms", 120.0),
    ("scheduler.finalize_ms", 15.5),
    ("index.walk_s", 11.0),
    ("index.copy_out_s", 6.0),
])
def test_reader(name, want):
    assert Benchmark().reader(name)(RUN) == pytest.approx(want)


STAGES = ["scheduler.step_wave_ms", "scheduler.admit_ms", "wave.prepare_ms",
          "wave.launch_ms", "wave.device_wait_ms", "wave.d2h_ms",
          "wave.tally_mb", "scheduler.accumulate_ms", "scheduler.finalize_ms",
          "index.walk_s", "index.copy_out_s"]


@pytest.mark.parametrize("name", [
    "gateway.cache_hit_share", "scheduler.walk_occupancy", "wave.device_ms",
    "batch.device_ms", "device.idle_share.serve"] + STAGES)
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    empty = {"counters": {"waves_run": 0, "requests": 0}, "trace": None,
             "trace_counts": None}
    assert Benchmark().reader(name)(empty) is None


@pytest.mark.parametrize("name", STAGES)
def test_a_stage_reader_reads_nothing_in_an_untraced_run(name):
    """An untraced run keeps the stage spans off: its counters carry the
    always-on tally bytes, but no span, no trace and no set-up spans."""
    untraced = dict(RUN, trace=None, trace_counts=None, setup_spans=None,
                    counters={k: v for k, v in RUN["counters"].items()
                              if not k.startswith("repro.")})
    assert Benchmark().reader(name)(untraced) is None
