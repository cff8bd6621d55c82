"""A wave hands the host each walk's endpoint (``int32[W_b]``, ``-1`` for a
discarded or dead walk), and each query slot counts its walks over their
support. Held here to a dense reference kept in the test: ``np.bincount``
of the same endpoints with ``minlength=n``, ranked by
``np.argsort(-c, kind="stable")[:k]`` — the parent design's answer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices
from repro.distributed import ShardRuntime
from repro.distributed.faults import FaultInjector, FaultPlan
from repro.graph import chung_lu_powerlaw
from repro.query import (QueryRequest, QueryScheduler, WalkIndexConfig,
                         shard_walk_index)
from repro.query.scheduler import QueryPlan, _Active
from repro.service import build_index


def _dense_top(ends, n, k):
    c = np.bincount(ends, minlength=n).astype(np.int64)
    top = np.argsort(-c, kind="stable")[:k]
    return top, c[top]


def _slot():
    plan = QueryPlan(num_walks=1, num_steps=1, epsilon=1.0, delta=0.1, k=1)
    return _Active(req=QueryRequest(rid=0), plan=plan, remaining=1,
                   total_walks=1, waves=0, t_submit=0.0, deadline=0.0,
                   downgraded=False)


@pytest.mark.parametrize("n,k,waves,per_wave,spread", [
    (5000, 10, 40, 200, 30),       # PPR-like: a few hot vertices
    (5000, 10, 40, 200, 5000),     # top-k-like: uniform, mostly count 1
    (64, 20, 30, 100, 64),         # the support reaches n
    (1000, 10, 3, 2, 1000),        # support smaller than k: the pad
    (1000, 10, 1, 0, 1000),        # nothing landed
], ids=["ppr", "topk", "full-support", "pad", "empty"])
def test_sparse_counts_rank_like_the_dense_reference(n, k, waves, per_wave,
                                                     spread):
    rng = np.random.default_rng(n + waves)
    a, seen = _slot(), []
    for _ in range(waves):
        ends = rng.integers(0, spread, per_wave).astype(np.int32)
        a.count(ends)
        seen.append(ends)
        want_v, want_c = _dense_top(np.concatenate(seen), n, k)
        got_v, got_c = a.top(k)
        assert got_v.tobytes() == want_v.tobytes()
        assert got_c.tobytes() == want_c.tobytes()
    assert np.all(np.diff(a.ids) > 0) and np.all(a.hits > 0)
    assert a.hits.sum() == waves * per_wave


def _graph():
    g = chung_lu_powerlaw(n=250, avg_out_deg=8, seed=2)
    idx = build_index(g, WalkIndexConfig(segments_per_vertex=6,
                                         segment_len=2, num_shards=2))
    return g, idx


@pytest.mark.parametrize("req", [
    QueryRequest(rid=0, kind="ppr", source=7, k=10, num_walks=3000),
    QueryRequest(rid=0, kind="topk", k=10, num_walks=3000),
    QueryRequest(rid=0, kind="topk", k=40, num_walks=700),
    QueryRequest(rid=0, kind="ppr", source=7, k=10, num_walks=3),
], ids=["ppr", "topk", "topk-ties", "ppr-pad"])
def test_answers_and_partials_equal_the_dense_reference(monkeypatch, req):
    """One request served over many waves: every ``partial()`` snapshot and
    the answer equal the dense reference over the endpoints its waves
    returned, scores included, byte for byte."""
    g, idx = _graph()
    sched = QueryScheduler(g, idx, max_walks=256, max_queries=1,
                           max_steps=24, seed=5)
    seen = []
    run_wave = QueryScheduler._run_wave

    def recording(self, *args):
        ends, clean, dt = run_wave(self, *args)
        seen.append(np.asarray(ends))
        return ends, clean, dt

    monkeypatch.setattr(QueryScheduler, "_run_wave", recording)
    assert sched._submit(req).admitted
    while sched.step_wave():
        landed = np.concatenate(seen)
        landed = landed[landed >= 0]
        if sched.active:
            p = sched.partial(req.rid)
            top, c = _dense_top(landed, g.n, req.k)
            assert p.vertices.tobytes() == top.tobytes()
            assert p.scores.tobytes() == (c / float(landed.size)).tobytes()
    r = sched.result_for(req.rid)
    top, c = _dense_top(landed, g.n, req.k)
    assert r.vertices.tobytes() == top.tobytes()
    assert r.scores.tobytes() == (c / float(landed.size)).tobytes()
    assert r.num_walks == landed.size == req.num_walks
    assert r.waves == len(seen) == -(-req.num_walks // 256)


def _operands(W, Q, n, seed):
    rng = np.random.default_rng(seed)
    qid = rng.integers(0, Q + 1, W).astype(np.int32)      # Q: idle slot
    uniform = rng.random(W) < 0.5
    start = rng.integers(0, n, W).astype(np.int32)
    return start, uniform, qid, np.full(W, 24, np.int32)


@pytest.mark.parametrize("lost_shard", [None, 2], ids=["intact", "evicted"])
def test_fused_and_loop_return_byte_identical_endpoints(lost_shard):
    g, idx = _graph()
    sh = shard_walk_index(idx, 4)              # 4 × 63 rows: 2 padding rows
    W, Q = 640, 3
    start, uniform, qid, t_cap = _operands(W, Q, g.n, seed=1)
    key = jax.random.PRNGKey(3)

    def ends(dispatch, lost):
        sched = QueryScheduler(
            g, sh, max_walks=W, max_queries=Q, max_steps=24,
            runtime=ShardRuntime(num_shards=4, mesh=None),
            sharded_dispatch=dispatch, walk_buckets=(W,), query_buckets=(Q,))
        out = sched._wave_for(W, Q)(
            jnp.asarray(start), jnp.asarray(uniform), jnp.asarray(qid),
            jnp.asarray(t_cap), key, jnp.asarray(lost))
        return sched._tally_to_host(out)

    intact = ends("fused", np.zeros(4, bool))
    assert intact.dtype == np.int32 and intact.shape == (W,)
    assert np.array_equal(intact < 0, qid == Q)   # only idle slots are -1
    lost = np.zeros(4, bool)
    if lost_shard is not None:
        lost[lost_shard] = True
    fused, loop = ends("fused", lost), ends("loop", lost)
    assert fused.tobytes() == loop.tobytes()
    if lost_shard is None:
        assert fused.tobytes() == intact.tobytes()
        return
    lo, hi = lost_shard * sh.shard_size, (lost_shard + 1) * sh.shard_size
    # a survivor's walk is the intact wave's walk; every walk that ends in
    # the evicted range is dead, and nothing lands there
    alive = fused >= 0
    assert np.array_equal(fused[alive], intact[alive])
    assert not np.any((fused >= lo) & (fused < hi))
    assert np.all(fused[(intact >= lo) & (intact < hi)] == -1)
    assert np.any(~alive & (qid < Q))


def test_a_degraded_query_loses_the_walks_its_waves_returned_as_dead():
    """``walks_lost`` counts the slot's ``-1`` endpoints, on every path."""
    g, idx = _graph()
    sh = shard_walk_index(idx, 4)
    out = {}
    for dispatch in ("fused", "loop"):
        sched = QueryScheduler(
            g, sh, max_walks=512, max_queries=1, max_steps=24, seed=4,
            runtime=ShardRuntime(num_shards=4, mesh=None),
            sharded_dispatch=dispatch,
            fault_injector=FaultInjector(FaultPlan(shard_losses=((1, 3),))))
        assert sched._submit(QueryRequest(rid=0, kind="topk", k=10,
                                          num_walks=2000)).admitted
        r = sched._drain()[0]
        assert r.degraded and r.walks_lost > 0
        assert r.num_walks + r.walks_lost == 2000
        out[dispatch] = (r.vertices.tobytes(), r.scores.tobytes(),
                         r.num_walks, r.walks_lost)
    assert out["fused"] == out["loop"]


@pytest.mark.parametrize("shards,dispatch", [
    (1, "fused"), (4, "fused"), (4, "loop")],
    ids=["gathered", "sharded", "loop"])
def test_each_wave_copies_four_bytes_a_walk_slot(monkeypatch, shards,
                                                 dispatch):
    """``tally_bytes_out == 4·W_b`` summed over waves, on a ladder whose
    waves run at different walk buckets."""
    g, idx = _graph()
    index = shard_walk_index(idx, shards) if shards > 1 else idx
    kw = {} if shards == 1 else dict(
        runtime=ShardRuntime(num_shards=shards, mesh=None),
        sharded_dispatch=dispatch)
    sched = QueryScheduler(g, index, max_walks=512, max_queries=2,
                           max_steps=24, seed=1, **kw)
    buckets = []
    run_wave = QueryScheduler._run_wave

    def recording(self, start, uniform, qid, t_cap, k_wave, W_b, Q_b):
        buckets.append(W_b)
        return run_wave(self, start, uniform, qid, t_cap, k_wave, W_b, Q_b)

    monkeypatch.setattr(QueryScheduler, "_run_wave", recording)
    for rid, walks in enumerate((600, 90)):      # waves of 512, then 178
        assert sched._submit(QueryRequest(rid=rid, kind="ppr", source=rid,
                                          num_walks=walks)).admitted
    sched._drain()
    assert len(set(buckets)) > 1
    assert sched.stats().tally_bytes_out == 4 * sum(buckets)


def test_mesh_endpoints_equal_the_fused_programs():
    """The mesh wave's replicated endpoints are one ``int32[W]`` on the
    host, byte-identical to the fused program's, evicted shard or not."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed import ShardRuntime
from repro.distributed.faults import FaultInjector, FaultPlan
from repro.graph import chung_lu_powerlaw
from repro.query import QueryScheduler, WalkIndexConfig, shard_walk_index
from repro.service import build_index
mesh = jax.make_mesh((4,), ("vertex",),
                     axis_types=(jax.sharding.AxisType.Auto,))
g = chung_lu_powerlaw(n=250, avg_out_deg=8, seed=2)
sh = shard_walk_index(build_index(g, WalkIndexConfig(
    segments_per_vertex=6, segment_len=2, num_shards=2)), 4)
W, Q = 640, 3
rng = np.random.default_rng(1)
qid = rng.integers(0, Q + 1, W).astype(np.int32)
uniform = rng.random(W) < 0.5
start = rng.integers(0, g.n, W).astype(np.int32)
t_cap = np.full(W, 24, np.int32)
kw = dict(max_walks=W, max_queries=Q, max_steps=24, walk_buckets=(W,),
          query_buckets=(Q,))
m = QueryScheduler(g, sh, runtime=ShardRuntime.for_mesh(mesh), **kw)
f = QueryScheduler(g, sh, runtime=ShardRuntime(num_shards=4, mesh=None), **kw)
assert m.dispatch == "mesh" and f.dispatch == "fused"
for lost in (np.zeros(4, bool), np.array([False, True, False, False])):
    ends = []
    for s in (m, f):
        out = s._wave_for(W, Q)(
            jnp.asarray(start), jnp.asarray(uniform), jnp.asarray(qid),
            jnp.asarray(t_cap), jax.random.PRNGKey(3), jnp.asarray(lost))
        ends.append(s._tally_to_host(out))
    assert ends[0].shape == (W,) and ends[0].dtype == np.int32
    assert ends[0].tobytes() == ends[1].tobytes()
    assert (ends[0] == -1).sum() >= (qid == Q).sum()
assert m.stats().tally_bytes_out == 2 * 4 * W
print("MESH-ENDPOINTS-OK")
""", n_devices=4)
    assert "MESH-ENDPOINTS-OK" in out
