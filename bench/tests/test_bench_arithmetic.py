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
                 "cache_hits": 6, "joins": 2, "sheds": 0, "rejected": 0},
    "waves_host": 10, "wave_s": 5.0, "index_build_s": 18.5,
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
])
def test_reader(name, want):
    assert Benchmark().reader(name)(RUN) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "gateway.cache_hit_share", "scheduler.walk_occupancy", "wave.device_ms",
    "batch.device_ms", "device.idle_share.serve"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    empty = {"counters": {"waves_run": 0, "requests": 0}, "trace": None,
             "trace_counts": None}
    assert Benchmark().reader(name)(empty) is None
