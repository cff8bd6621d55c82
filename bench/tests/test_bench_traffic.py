"""The traffic generator: deterministic per seed, and the shapes hold."""
import itertools
import types

import numpy as np
import pytest

from bench import arrivals, serve, tracing
from bench.registry import Benchmark

N = 100_000
PPR = {"k": 10, "epsilon": 0.3, "delta": 0.1}
CLOSED = {"loop": "closed", "request": PPR, "sources": {"dist": "uniform"}}
OPEN = {"loop": "open", "request": PPR,
        "arrivals": {"process": "poisson", "rate_per_s": 5.0},
        "sources": {"dist": "zipf", "constant": 0.99}}


@pytest.mark.parametrize("params", [CLOSED, OPEN])
def test_the_same_seed_gives_the_same_requests(params):
    a = arrivals.Mix(params, N, 2**31 + 17).requests(500)
    b = arrivals.Mix(params, N, 2**31 + 17).requests(500)
    c = arrivals.Mix(params, N, 2**31 + 18).requests(500)
    assert a == b
    assert a != c


def test_warmup_requests_are_a_stream_of_their_own():
    m = arrivals.Mix(CLOSED, N, 5)
    assert ({r.source for r in m.requests(200)}
            != {r.source for r in m.requests(200, warmup=True)})


def test_closed_loop_sources_never_repeat():
    src = [r.source for r in arrivals.Mix(CLOSED, N, 3).requests(4096)]
    assert len(set(src)) == 4096
    assert min(src) >= 0 and max(src) < N


def test_poisson_arrivals_have_a_fixed_count_and_uniform_times():
    m = arrivals.Mix(OPEN, N, 11)
    t = m.arrival_times(40.0)
    assert len(t) == 200 and np.all(np.diff(t) >= 0)
    assert t.min() >= 0 and t.max() < 40.0
    # conditioned on their count, Poisson arrival times are uniform
    hist, _ = np.histogram(arrivals.Mix(OPEN, N, 12).arrival_times(4000.0),
                           bins=10, range=(0, 4000.0))
    assert hist.min() > 1500 and hist.max() < 2500
    assert not np.array_equal(t, arrivals.Mix(OPEN, N, 12).arrival_times(40))


@pytest.mark.parametrize("key, value", [
    ("arrivals", {"process": "onoff", "rate_per_s": 10.0}),
    ("sources", {"dist": "pareto"}),
])
def test_an_unknown_process_or_distribution_is_refused(key, value):
    m = arrivals.Mix(dict(OPEN, **{key: value}), N, 1)
    make = m.arrival_times if key == "arrivals" else m.requests
    with pytest.raises(ValueError):
        make(10)


def test_zipf_ranks_follow_ycsb_zipfian():
    r = np.random.default_rng(0)
    ranks = arrivals.zipf_ranks(r, 10_000, 200_000, 0.99)
    zeta = np.sum(np.arange(1, 10_001, dtype=float) ** -0.99)
    counts = np.bincount(ranks, minlength=10_000)
    assert counts[0] / 200_000 == pytest.approx(1 / zeta, rel=0.05)
    assert counts[1] / 200_000 == pytest.approx(2 ** -0.99 / zeta, rel=0.05)
    # the head is heavy and the tail long: the top 1% of items take a
    # third or more of the draws, and most items are drawn at least once
    assert counts[:100].sum() / 200_000 > 0.33
    assert np.count_nonzero(counts) > 5_000


def test_zipf_sources_repeat_over_a_seeded_permutation():
    src = [r.source for r in arrivals.Mix(OPEN, N, 4).requests(1000)]
    repeats = 1 - len(set(src)) / len(src)
    assert 0.05 < repeats < 0.5
    other = [r.source for r in arrivals.Mix(OPEN, N, 5).requests(1000)]
    top = max(set(src), key=src.count)
    assert top != max(set(other), key=other.count)



LJ_N = 4_847_571


@pytest.mark.parametrize("seed", [0, 2**31 + 4242, 2**32 + 977])
def test_a_closed_loops_first_block_is_what_it_always_sent(seed):
    """The refresh mix's supply starts with ``requests(max_requests)``,
    the whole of what a closed loop could send before it became endless,
    and goes on without repeating a source."""
    params = Benchmark().traffic("refresh")
    block = params["max_requests"]
    assert block == 4096
    m = arrivals.Mix(params, LJ_N, seed)
    supply = [r.source for r in itertools.islice(m.stream(), 2 * block)]
    assert supply[:block] == [r.source for r in m.requests(block)]
    assert len(set(supply)) == 2 * block


def test_a_uniform_supply_runs_out_with_an_error_that_names_it():
    m = arrivals.Mix(dict(CLOSED, max_requests=5, name="sixteen"), 16, 2**31)
    supply = m.stream()
    sent = [next(supply).source for _ in range(16)]
    assert sorted(sent) == list(range(16))
    with pytest.raises(RuntimeError,
                       match=r"'sixteen', seed 2147483648: all 16 sources "
                             r"sent after 16 requests"):
        next(supply)


def test_a_zipf_supply_keeps_its_popular_vertices_from_block_to_block():
    zipf = dict(CLOSED, sources=OPEN["sources"], max_requests=1000)
    m = arrivals.Mix(zipf, N, 9)
    supply = [r.source for r in itertools.islice(m.stream(), 2000)]
    first, second = supply[:1000], supply[1000:]
    assert first == [r.source for r in m.requests(1000)]
    assert first != second
    assert (max(set(first), key=first.count)
            == max(set(second), key=second.count))


class _Gateway:
    """Answers every request in flight at each step."""

    def __init__(self):
        self.sent, self.open = [], []

    def ppr(self, source, k, epsilon, delta):
        h = types.SimpleNamespace(
            finished=False, done=lambda: h.finished,
            result=lambda max_waves: types.SimpleNamespace(
                vertices=[source], scores=[1.0], num_walks=1, num_steps=1,
                epsilon_bound=0.3, degraded=False))
        self.sent.append(source)
        self.open.append(h)
        return h

    def step(self):
        for h in self.open:
            h.finished = True
        progressed, self.open = bool(self.open), []
        return progressed


def test_a_closed_loop_outlasts_its_first_block():
    """A loop that answers more than ``max_requests`` requests goes on
    into the next block: its window completes, every source in the run
    is new, and the first block is ``requests(max_requests)``."""
    mix = arrivals.Mix(dict(CLOSED, outstanding=3, max_requests=8,
                            warmup_s=0), N, 2**31 + 99)
    gw = _Gateway()
    counters = types.SimpleNamespace(start=lambda: None, stop=lambda: None)
    recs, (t0, t_end), _ = serve.closed_loop(
        gw, mix, 0.01, tracing.Spans(), counters, lambda now: None)
    assert len(gw.sent) > 8 * 4
    assert len(set(gw.sent)) == len(gw.sent)
    assert gw.sent[:8] == [r.source for r in mix.requests(8)]
    assert recs and all(r.answer is not None for r in recs)
    assert t_end >= t0 + 0.01
