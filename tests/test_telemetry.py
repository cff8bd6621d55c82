"""Stage spans (repro.telemetry): off they are one shared no-op; on they
count calls, seconds and self seconds per name, in the scheduler's waves
and the walk-index build, without changing a single answer bit."""
import pytest

from conftest import run_with_devices
from repro import Gateway, RuntimeConfig, ServingConfig, ShardConfig, telemetry
from repro.graph import chung_lu_powerlaw
from repro.query import (QueryRequest, QueryScheduler, WalkIndexConfig,
                         shard_walk_index)
from repro.service import build_index

WAVE_SPANS = ("repro.wave.prepare", "repro.wave.launch",
              "repro.wave.device_wait", "repro.wave.d2h",
              "repro.scheduler.accumulate")


@pytest.fixture
def spans_on():
    telemetry.reset()
    telemetry.enable(True)
    yield
    telemetry.enable(False)
    telemetry.reset()


@pytest.fixture
def clock(monkeypatch):
    """A fake ``perf_counter`` that the test advances by hand."""
    now = [0.0]
    monkeypatch.setattr(telemetry.time, "perf_counter", lambda: now[0])
    return now


def test_off_is_one_shared_noop_that_records_nothing():
    telemetry.reset()
    assert not telemetry.enabled()
    a, b = telemetry.span("repro.a"), telemetry.span("repro.b")
    assert a is b
    with a:
        with b:
            pass
    assert telemetry.totals() == {}


def test_nested_spans_count_calls_seconds_and_self_seconds(spans_on, clock):
    for _ in range(2):
        with telemetry.span("repro.outer"):
            clock[0] += 1.0
            with telemetry.span("repro.inner"):
                clock[0] += 3.0
            with telemetry.span("repro.inner"):
                clock[0] += 0.5
            clock[0] += 0.25
    t = telemetry.totals()
    assert t["repro.outer"] == {"n": 2, "s": pytest.approx(9.5),
                                "self_s": pytest.approx(2.5)}
    assert t["repro.inner"] == {"n": 4, "s": pytest.approx(7.0),
                                "self_s": pytest.approx(7.0)}
    telemetry.reset()
    assert telemetry.totals() == {}


def test_a_span_closes_when_its_body_raises(spans_on, clock):
    with pytest.raises(ValueError):
        with telemetry.span("repro.failing"):
            clock[0] += 2.0
            raise ValueError("boom")
    with telemetry.span("repro.after"):       # not nested in the failed one
        clock[0] += 1.0
    t = telemetry.totals()
    assert t["repro.failing"] == {"n": 1, "s": 2.0, "self_s": 2.0}
    assert t["repro.after"] == {"n": 1, "s": 1.0, "self_s": 1.0}


def _index(n=300, shards=1):
    g = chung_lu_powerlaw(n=n, avg_out_deg=6, seed=4)
    idx = build_index(g, WalkIndexConfig(
        segments_per_vertex=8, segment_len=3, num_shards=2, seed=5))
    return g, (shard_walk_index(idx, shards) if shards > 1 else idx)


def _serve(g, index, max_queries=4, **kw):
    """Four equal requests: every wave carries all four (Q_b = 4) and fills
    its 1,024 walk slots (W_b = 1024)."""
    sched = QueryScheduler(g, index, max_walks=1024, max_queries=max_queries,
                           max_steps=24, seed=9, **kw)
    for i in range(4):
        kind = "ppr" if i % 2 else "topk"
        assert sched._submit(QueryRequest(
            rid=i, kind=kind, source=11 * i, k=8, num_walks=1000)).admitted
    calls = 0
    while sched.step_wave():
        calls += 1
    return sched, calls + 1, sorted(sched.finished, key=lambda r: r.rid)


def test_every_wave_stage_is_timed_once_per_step_wave(spans_on):
    g, idx = _index()
    sched, calls, results = _serve(g, idx)
    waves = sched.stats().waves_run
    assert waves == 4 and len(results) == 4
    t = telemetry.totals()
    assert t["repro.scheduler.step_wave"]["n"] == calls
    assert t["repro.scheduler.admit"]["n"] == calls
    for name in WAVE_SPANS:
        assert t[name]["n"] == waves, name
    assert t["repro.scheduler.finalize"]["n"] == len(results)
    inner = sum(t[name]["s"] for name in WAVE_SPANS + (
        "repro.scheduler.admit", "repro.scheduler.finalize"))
    step = t["repro.scheduler.step_wave"]
    assert step["self_s"] == pytest.approx(step["s"] - inner)
    assert sched.stats().tally_bytes_out == waves * 1024 * 4


def test_the_tally_counter_runs_with_spans_off():
    g, idx = _index()
    telemetry.reset()
    sched, _, _ = _serve(g, idx)
    assert sched.stats().tally_bytes_out == 4 * 1024 * 4
    assert telemetry.totals() == {}


@pytest.mark.parametrize("shards,dispatch", [
    (1, "fused"), (4, "fused"), (4, "loop")],
    ids=["gathered", "sharded", "loop"])
def test_answers_are_byte_identical_with_spans_on_and_off(shards, dispatch):
    g, idx = _index(shards=shards)
    telemetry.reset()
    _, _, off = _serve(g, idx, sharded_dispatch=dispatch)
    telemetry.enable(True)
    try:
        sched, _, on = _serve(g, idx, sharded_dispatch=dispatch)
        assert telemetry.totals()["repro.wave.d2h"]["n"] == 4
        assert shards == 1 or sched.dispatch == dispatch
    finally:
        telemetry.enable(False)
        telemetry.reset()
    for a, b in zip(off, on):
        assert a.vertices.tobytes() == b.vertices.tobytes(), a.rid
        assert a.scores.tobytes() == b.scores.tobytes(), a.rid
        assert a.num_walks == b.num_walks and a.waves == b.waves
    # every dispatch path copies its int32[W_b] endpoints at the one site
    assert sched.stats().tally_bytes_out == 4 * 1024 * 4


def test_mesh_answers_are_byte_identical_with_spans_on_and_off():
    out = run_with_devices("""
import jax
from repro import telemetry
from repro.distributed import ShardRuntime
from repro.graph import chung_lu_powerlaw
from repro.query import QueryRequest, QueryScheduler, WalkIndexConfig
from repro.service import build_index
mesh = jax.make_mesh((4,), ("vertex",),
                     axis_types=(jax.sharding.AxisType.Auto,))
g = chung_lu_powerlaw(n=402, avg_out_deg=6, seed=4)
cfg = WalkIndexConfig(segments_per_vertex=8, segment_len=3, seed=5)
telemetry.enable(True)
idx = build_index(g, cfg, mesh=mesh, reassemble=False)
t = telemetry.totals()
assert t["repro.index.walk"]["n"] == 1 and t["repro.index.copy_out"]["n"] == 1
rt = ShardRuntime.for_mesh(mesh)

def serve():
    sched = QueryScheduler(g, idx, max_walks=1024, max_queries=4,
                           max_steps=24, seed=9, runtime=rt)
    for i in range(4):
        assert sched._submit(QueryRequest(
            rid=i, kind="ppr" if i % 2 else "topk", source=11 * i, k=8,
            num_walks=1000)).admitted
    assert sched.dispatch == "mesh"
    res = sorted(sched.run(), key=lambda r: r.rid)
    return sched, [(r.vertices.tobytes(), r.scores.tobytes(), r.num_walks)
                   for r in res]

telemetry.enable(False)
telemetry.reset()
_, off = serve()
telemetry.enable(True)
sched, on = serve()
assert telemetry.totals()["repro.wave.d2h"]["n"] == sched.stats().waves_run
assert off == on
st = sched.stats()
assert st.tally_bytes_out == st.waves_run * 1024 * 4
print("MESH-SPANS-OK")
""", n_devices=4)
    assert "MESH-SPANS-OK" in out


def test_index_build_spans(spans_on):
    _index()                                   # two build shards
    t = telemetry.totals()
    assert t["repro.index.walk"]["n"] == 2
    # one copy per shard, and the concatenation with the slab's upload
    assert t["repro.index.copy_out"]["n"] == 3


def _rc():
    return RuntimeConfig(p_T=0.15, runtime=ShardConfig(seed=3),
                         serving=ServingConfig(segments_per_vertex=8,
                                               segment_len=3, max_walks=512,
                                               max_queries=2, max_steps=24))


def test_gateway_stats_carry_spans_only_while_enabled():
    g = chung_lu_powerlaw(n=200, avg_out_deg=6, seed=2)
    telemetry.reset()
    with Gateway.open(g, _rc(), replicas=1) as gw:
        gw.ppr(5, k=5, epsilon=0.4).result()
        stats = gw.stats()
        assert stats["spans"] is None
        # the tally counter is always on, and /metrics serves it per replica
        rep = stats["replicas"][0]
        assert rep["waves_run"] >= 1
        # 4·W_b a wave, W_b on the ladder from 512 // 8 to 512 walk slots
        assert (rep["waves_run"] * 64 * 4 <= rep["tally_bytes_out"]
                <= rep["waves_run"] * 512 * 4)
    telemetry.enable(True)
    try:
        with Gateway.open(g, _rc(), replicas=1) as gw:
            gw.ppr(6, k=5, epsilon=0.4).result()
            spans = gw.stats()["spans"]
    finally:
        telemetry.enable(False)
        telemetry.reset()
    assert spans["repro.index.walk"]["n"] >= 1
    assert spans["repro.scheduler.step_wave"]["n"] >= 1
    assert set(spans["repro.wave.d2h"]) == {"n", "s", "self_s"}
