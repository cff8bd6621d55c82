"""Fused wave programs + the AOT bucket-shape ladder (PR 9).

Three claims under test:

1. **Byte-identity across dispatch paths.** The fused single-dispatch wave
   (``lax.scan`` over stitch rounds against the stacked slab) is the *same
   program* as the legacy per-shard host loop and as the gathered dense
   wave — same key stream ⇒ same bytes, including at non-divisible
   (walk-slot, query-slot, shard) shapes where the slab carries padding
   rows.

2. **Zero retraces after warmup.** ``warm_ladder()`` compiles one program
   per (walk-bucket, query-bucket) pair; afterwards an arbitrary mixed
   topk/PPR sweep re-buckets into warm executables — the trace counter
   (``repro.distributed.runtime.wave_trace_count``) must not move.

3. **Ladder mechanics.** Default ladders are the cap and its halvings;
   user ladders are validated and always topped by the cap; bucketing
   picks the smallest member ≥ demand.
"""
import numpy as np
import pytest

from repro.distributed.runtime import (ShardRuntime, reset_wave_trace_count,
                                       wave_trace_count)
from repro.graph import chung_lu_powerlaw
from repro.query import (QueryRequest, QueryScheduler, WalkIndexConfig,
                         build_walk_index, shard_walk_index)


def _graph_and_index(n=250, R=6, L=2, seed=2, shards=4):
    """n=250 with 4 shards ⇒ shard_size 63, 252 slab rows: 2 padding rows
    the fused gather must never touch."""
    g = chung_lu_powerlaw(n=n, avg_out_deg=8, seed=seed)
    idx = build_walk_index(g, WalkIndexConfig(
        segments_per_vertex=R, segment_len=L, num_shards=2))
    return g, idx, shard_walk_index(idx, shards)


def _reqs():
    return [QueryRequest(rid=0, kind="topk", k=10, epsilon=0.4),
            QueryRequest(rid=1, kind="ppr", source=7, k=10, epsilon=0.4),
            QueryRequest(rid=2, kind="topk", k=5, num_walks=300)]


def _run(g, index, reqs, seed=11, **kw):
    kw.setdefault("max_walks", 640)      # non-power-of-two walk cap
    kw.setdefault("max_queries", 3)
    sched = QueryScheduler(g, index, max_steps=24, seed=seed, **kw)
    for r in reqs:
        assert sched.submit(r).admitted
    return sched, sorted(sched.run(), key=lambda r: r.rid)


# --- byte-identity across dispatch paths -------------------------------------


def test_fused_matches_legacy_loop_exactly():
    g, _, sh = _graph_and_index()
    sched_f, res_f = _run(g, sh, _reqs(), sharded_dispatch="fused")
    sched_l, res_l = _run(g, sh, _reqs(), sharded_dispatch="loop")
    assert sched_f.dispatch == "fused" and sched_l.dispatch == "loop"
    assert [r.rid for r in res_f] == [0, 1, 2]
    for a, b in zip(res_f, res_l):
        assert (a.vertices == b.vertices).all(), a.rid
        assert np.array_equal(a.scores, b.scores), a.rid
        assert a.num_walks == b.num_walks and a.waves == b.waves


def test_fused_sharded_matches_gathered_exactly():
    g, idx, sh = _graph_and_index()
    _, res_g = _run(g, idx, _reqs())
    sched_s, res_s = _run(g, sh, _reqs())
    assert sched_s.dispatch == "fused"
    for a, b in zip(res_g, res_s):
        assert (a.vertices == b.vertices).all(), a.rid
        assert np.array_equal(a.scores, b.scores), a.rid


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_fused_kernel_paths_match_loop(impl):
    """The gather-only stitch kernels (tally=False) inside the fused scan
    must produce the same slots as the tallying kernels in the host loop."""
    g, _, sh = _graph_and_index(n=130, R=5, L=2, seed=3, shards=2)
    _, res_f = _run(g, sh, _reqs()[:2], impl=impl, max_walks=320,
                    sharded_dispatch="fused")
    _, res_l = _run(g, sh, _reqs()[:2], impl=impl, max_walks=320,
                    sharded_dispatch="loop")
    for a, b in zip(res_f, res_l):
        assert (a.vertices == b.vertices).all(), (impl, a.rid)
        assert np.array_equal(a.scores, b.scores), (impl, a.rid)


def test_donation_off_matches_donation_on():
    g, _, sh = _graph_and_index(n=130, R=5, L=2, seed=3, shards=2)
    _, res_d = _run(g, sh, _reqs(), donate_wave_buffers=True)
    _, res_n = _run(g, sh, _reqs(), donate_wave_buffers=False)
    for a, b in zip(res_d, res_n):
        assert (a.vertices == b.vertices).all(), a.rid
        assert np.array_equal(a.scores, b.scores), a.rid


def test_bucketing_does_not_change_answers():
    """A coarse single-bucket ladder and a fine ladder run different padded
    shapes — but the bucket choice is a pure host function of the same
    allocation, so the same ladder on both paths keeps bytes equal. Across
    *different* ladders only the distribution is shared (padding slots
    consume key draws), so here we assert the coarse ladder byte-matches
    the default — both bucket every wave to the full cap shape when demand
    exceeds the sub-cap rungs."""
    g, _, sh = _graph_and_index(n=130, R=5, L=2, seed=3, shards=2)
    _, res_a = _run(g, sh, _reqs(), max_walks=320,
                    walk_buckets=(320,), query_buckets=(3,))
    # default ladder: demand (3 queries, >160 walks) also buckets to cap
    _, res_b = _run(g, sh, _reqs(), max_walks=320)
    for a, b in zip(res_a, res_b):
        assert (a.vertices == b.vertices).all(), a.rid
        assert np.array_equal(a.scores, b.scores), a.rid


# --- AOT ladder: zero retraces after warmup ----------------------------------


def test_warm_ladder_then_mixed_sweep_zero_retraces():
    g, _, sh = _graph_and_index(n=130, R=5, L=2, seed=3, shards=2)
    sched = QueryScheduler(g, sh, max_walks=320, max_queries=3, max_steps=24,
                           seed=11, walk_buckets=(80, 160, 320),
                           query_buckets=(1, 2, 3))
    warmed = sched.warm_ladder()
    assert warmed == 9                       # 3 walk × 3 query buckets
    before = wave_trace_count()
    rid = 0
    for round_ in range(4):                  # shifting query mix per wave
        for spec in ([("topk", 60)], [("topk", 40), ("ppr", 70)],
                     [("topk", 300), ("ppr", 20), ("topk", 5)])[
                         round_ % 3:round_ % 3 + 1]:
            for kind, walks in spec:
                sched.submit(QueryRequest(
                    rid=rid, kind=kind, k=5, num_walks=walks,
                    source=7 if kind == "ppr" else None))
                rid += 1
            sched.run()
    assert wave_trace_count() == before, "query-mix change retraced a wave"


def test_aot_warmup_flag_compiles_at_build():
    g, _, sh = _graph_and_index(n=130, R=5, L=2, seed=3, shards=2)
    reset_wave_trace_count()
    sched = QueryScheduler(g, sh, max_walks=320, max_queries=2, max_steps=24,
                           walk_buckets=(320,), query_buckets=(2,),
                           aot_warmup=True)
    assert len(sched._wave_fns) == 1
    traced = wave_trace_count()
    assert traced >= 0                       # may be 0 on a cache hit
    sched.submit(QueryRequest(rid=0, kind="topk", k=5, num_walks=100))
    sched.run()
    assert wave_trace_count() == traced      # serving never traces


def test_wave_cache_shared_across_equal_geometry_schedulers():
    """Programs key on WaveSpec and take slab/graph arrays as operands, so
    a second scheduler over the same geometry reuses the executable."""
    g, _, sh = _graph_and_index(n=130, R=5, L=2, seed=3, shards=2)
    kw = dict(max_walks=320, max_queries=2, max_steps=24,
              walk_buckets=(320,), query_buckets=(2,))
    QueryScheduler(g, sh, **kw).warm_ladder()
    cache = ShardRuntime.wave_cache()
    h0, m0 = cache.hits, cache.misses
    QueryScheduler(g, sh, seed=99, **kw).warm_ladder()
    assert cache.misses == m0                # no new compile
    assert cache.hits > h0


# --- the per-poll top-k finalize ---------------------------------------------


def test_topk_stable_matches_full_stable_argsort():
    """The partition strategy (and the full sort when k ≥ n) must
    reproduce the head of the full stable argsort exactly, ties and zeros
    included."""
    from repro.query.scheduler import _topk_stable
    rng = np.random.default_rng(0)
    cases = [(1000, 10, 30), (1000, 10, 900), (1000, 25, 5),
             (50, 60, 20), (64, 64, 10), (128, 5, 0), (40, 40, 40)]
    for n, k, nnz in cases:
        counts = np.zeros(n, np.int64)
        if nnz:
            idx = rng.choice(n, nnz, replace=False)
            counts[idx] = rng.integers(1, 5, nnz)   # heavy ties
        want = np.argsort(-counts, kind="stable")[:k]
        got = _topk_stable(counts, k)
        assert np.array_equal(got, want), (n, k, nnz)
    # negative entries rank like any others
    scores = rng.normal(size=500)
    want = np.argsort(-scores, kind="stable")[:7]
    assert np.array_equal(_topk_stable(scores, 7), want)


# --- ladder mechanics --------------------------------------------------------


def test_default_ladder_is_cap_and_halvings():
    norm = QueryScheduler._normalize_buckets
    assert norm(None, 1024, "walk_buckets", floor=128) == (128, 256, 512,
                                                           1024)
    assert norm(None, 12, "walk_buckets", floor=1) == (1, 3, 6, 12)
    assert norm(None, 1, "query_buckets", floor=1) == (1,)


def test_user_ladder_validated_and_topped_by_cap():
    norm = QueryScheduler._normalize_buckets
    assert norm((64, 256), 1024, "walk_buckets", floor=1) == (64, 256, 1024)
    assert norm((1024, 64), 1024, "walk_buckets", floor=1) == (64, 1024)
    with pytest.raises(ValueError, match="walk_buckets"):
        norm((0, 64), 1024, "walk_buckets", floor=1)
    with pytest.raises(ValueError, match="walk_buckets"):
        norm((2048,), 1024, "walk_buckets", floor=1)
    with pytest.raises(ValueError, match="sharded_dispatch"):
        g, _, sh = _graph_and_index(n=64, R=3, L=2, seed=1, shards=2)
        QueryScheduler(g, sh, sharded_dispatch="turbo")


def test_bucket_picks_smallest_fit():
    bucket = QueryScheduler._bucket
    ladder = (64, 256, 1024)
    assert bucket(ladder, 1) == 64
    assert bucket(ladder, 64) == 64
    assert bucket(ladder, 65) == 256
    assert bucket(ladder, 1024) == 1024
    assert bucket(ladder, 9999) == 1024      # top bucket bounds demand
