"""Fault tolerance: injected shard loss, degraded waves with widened
ε-bounds, bounded retry/backoff + failover supervision, and checkpoint
integrity (crash-during-write, corrupt-payload quarantine + rebuild).

The organizing claim is FrogWild's own: missing contributions are priced,
not fatal. A lost shard turns into walks that die at its endpoint range —
the surviving tallies renormalize and the result's ``epsilon_bound`` widens
to exactly the ε Theorem 1 certifies at N = walks executed (the anytime
accounting applied to loss instead of budget). Zero faults must be
byte-identical to the unfaulted scheduler; retries replay the same wave
key, so a successful retry is byte-identical too.
"""
import math
import os

import numpy as np
import pytest

from conftest import run_with_devices
from repro.checkpoint import (CheckpointCorruptError, Checkpointer,
                              latest_step, restore_checkpoint,
                              save_checkpoint)
from repro.config import (RuntimeConfig, ServingConfig, ShardConfig)
from repro.core import theory
from repro.distributed.faults import (FaultInjector, FaultPlan,
                                      WaveFailedError)
from repro.graph import chung_lu_powerlaw
from repro.query import (QueryRequest, QueryScheduler, ShardedWalkIndex,
                         WalkIndexConfig, build_walk_index,
                         load_or_repair_walk_index, load_walk_index,
                         save_walk_index_shard, shard_walk_index)
from repro.service import FrogWildService


S = 4          # serving shards in these tests
R, L = 6, 2    # walk-index geometry


def _graph_and_shards(n=256, seed=2):
    """A graph plus a genuinely S-way-partitioned index (build partitioning
    == serving shards, so single-shard rebuilds are byte-identical)."""
    g = chung_lu_powerlaw(n=n, avg_out_deg=6, seed=seed)
    idx = build_walk_index(g, WalkIndexConfig(
        segments_per_vertex=R, segment_len=L, num_shards=S, seed=seed))
    return g, shard_walk_index(idx, S)


def _sched(g, sh, plan=None, **kw):
    inj = FaultInjector(plan) if plan is not None else None
    kw.setdefault("max_walks", 512)
    kw.setdefault("max_queries", 4)
    kw.setdefault("max_steps", 12)
    return QueryScheduler(g, sh, seed=7, fault_injector=inj, **kw)


def _reqs():
    return [QueryRequest(rid=0, kind="topk", k=8, num_walks=900),
            QueryRequest(rid=1, kind="ppr", source=5, k=8, num_walks=900)]


def _drain(sched, reqs):
    for r in reqs:
        assert sched._submit(r).admitted
    return sorted(sched._drain(), key=lambda r: r.rid)


# --- zero faults: byte identity and bounded overhead -------------------------


def test_zero_faults_byte_identical_with_supervision_armed():
    """Empty fault plan + armed timeout: the supervised scheduler answers
    bit-for-bit what the unsupervised one does (the masked wave program
    with an all-False eviction mask is the unmasked program)."""
    g, sh = _graph_and_shards()
    plain = _drain(_sched(g, sh), _reqs())
    armed = _drain(_sched(g, sh, plan=FaultPlan(), wave_timeout_s=60.0),
                   _reqs())
    for a, b in zip(plain, armed):
        assert (a.vertices == b.vertices).all()
        assert (a.scores == b.scores).all()
        assert not b.degraded and b.walks_lost == 0 and b.shards_lost == ()


# --- shard loss: degraded waves, renormalization, widened bound --------------


def test_shard_loss_degrades_with_theorem1_widened_bound():
    """A shard lost mid-query: results flag ``degraded``, tallies
    renormalize by the walks that completed, and ``epsilon_bound`` is
    exactly Theorem 1 at N = executed (p_s = 1, p_cap = 0)."""
    g, sh = _graph_and_shards()
    sched = _sched(g, sh, plan=FaultPlan(shard_losses=((1, 2),)))
    results = _drain(sched, _reqs())
    assert sched.lost_shards == {2}
    lo, hi = sh.shard_size * 2, sh.shard_size * 3
    for r in results:
        assert r.degraded and r.shards_lost == (2,)
        assert r.walks_lost > 0
        assert r.num_walks + r.walks_lost == 900   # every walk accounted
        want = theory.epsilon_bound(sched.p_T, r.num_steps, 8, 0.1,
                                    r.num_walks, 1.0, 0.0)
        assert math.isclose(r.epsilon_bound, want)
        # renormalized by executed: scores are integer tallies over the
        # walks that completed, and no mass lands in the evicted range
        counts = r.scores * r.num_walks
        assert np.allclose(counts, np.rint(counts))
        for v, sc in zip(r.vertices, r.scores):
            assert not (sc > 0 and lo <= int(v) < hi)

    # vs the unfaulted run: the degraded one executed strictly fewer walks
    # (the difference is exactly what it reported lost)
    base = _drain(_sched(g, sh), _reqs())
    for rb, rd in zip(base, results):
        assert rb.num_walks == 900 and rd.num_walks == 900 - rd.walks_lost


def test_partial_carries_degraded_provenance():
    g, sh = _graph_and_shards()
    sched = _sched(g, sh, plan=FaultPlan(shard_losses=((0, 1),)))
    req = QueryRequest(rid=0, kind="topk", k=8, num_walks=2000)
    assert sched._submit(req).admitted
    sched.step_wave()
    p = sched.partial(0)
    assert p.degraded and p.shards_lost == (1,) and p.walks_lost > 0
    assert p.walks_done + p.walks_lost == 512    # one full wave allocated
    sched._drain()
    done = sched.partial(0)
    assert done.done and done.degraded and done.shards_lost == (1,)


def test_evicting_everything_is_unservable():
    g, sh = _graph_and_shards()
    sched = _sched(g, sh)
    for s in range(S - 1):
        sched._evict_shard(s, wave_no=0)
    with pytest.raises(WaveFailedError, match="no shard left"):
        sched._evict_shard(S - 1, wave_no=0)
    # a dense slab has no shard granularity to degrade to
    g2 = chung_lu_powerlaw(n=64, avg_out_deg=4, seed=3)
    dense = build_walk_index(g2, WalkIndexConfig(
        segments_per_vertex=R, segment_len=L, num_shards=1, seed=3))
    with pytest.raises(WaveFailedError, match="dense"):
        QueryScheduler(g2, dense, max_walks=64, max_steps=8,
                       seed=1)._evict_shard(0, wave_no=0)


# --- retry / backoff / timeout supervision -----------------------------------


def test_transient_faults_retried_byte_identically_then_bounded():
    """Retries replay the same wave key → a run that needed retries
    answers bit-for-bit what a fault-free run answers; one more injected
    failure than max_retries allows raises WaveFailedError."""
    g, sh = _graph_and_shards()
    base = _drain(_sched(g, sh), _reqs())
    sched = _sched(g, sh, plan=FaultPlan(transient_faults=((0, 2),)),
                   max_retries=2, backoff_base_s=0.001, backoff_max_s=0.002)
    retried = _drain(sched, _reqs())
    for a, b in zip(base, retried):
        assert (a.vertices == b.vertices).all()
        assert (a.scores == b.scores).all()
    assert [e.kind for e in sched.fault_log] == ["retry", "retry"]
    assert max(e.attempt for e in sched.fault_log) == 2

    broke = _sched(g, sh, plan=FaultPlan(transient_faults=((0, 3),)),
                   max_retries=2, backoff_base_s=0.001, backoff_max_s=0.002)
    assert broke._submit(QueryRequest(rid=0, num_walks=100)).admitted
    with pytest.raises(WaveFailedError, match="after 3 attempts"):
        broke.step_wave()
    # the failed wave left nothing behind: no tallies, budget intact
    a = next(iter(broke.active.values()))
    assert a.executed == 0 and a.remaining == 100 and a.hits.sum() == 0
    assert a.ids.size == 0


def test_stall_detected_as_timeout_and_retried():
    """An injected slow wave overruns ``wave_timeout_s``: the result is
    discarded, the wave retried from the same key (byte-identical), and
    the faulted wall time never reaches the admission EMA."""
    g, sh = _graph_and_shards()
    base = _drain(_sched(g, sh), _reqs())
    sched = _sched(g, sh, plan=FaultPlan(stalls=((1, 0.3),)),
                   wave_timeout_s=0.25, wave_time_estimate_s=0.01,
                   backoff_base_s=0.001, backoff_max_s=0.002)
    out = _drain(sched, _reqs())
    for a, b in zip(base, out):
        assert (a.vertices == b.vertices).all()
        assert (a.scores == b.scores).all()
    assert any(e.kind == "retry" for e in sched.fault_log)
    # EMA robustness: the 0.3s stall (30× the estimate) was skipped, and
    # clean waves are clamped — the estimate cannot have been poisoned
    # anywhere near the stall.
    assert sched._wave_time < 0.1


def test_ema_skips_faulted_waves_and_clamps_outliers():
    g, sh = _graph_and_shards()
    sched = _sched(g, sh, plan=FaultPlan(stalls=((1, 0.5),)),
                   wave_time_estimate_s=0.02)   # no timeout: wave lands
    _drain(sched, _reqs())
    # the stalled wave completed and its tallies counted, but its 0.5s wall
    # time was excluded from the EMA (non-clean), so the estimate stays at
    # machine speed.
    assert sched._wave_time < 0.25
    assert any(e.kind == "stall" for e in
               (sched._injector.fired if sched._injector else []))


# --- capacity loss: admission + re-admission ---------------------------------


def test_eviction_shrinks_capacity_and_readmits_queued_slo_work():
    g, sh = _graph_and_shards()
    sched = _sched(g, sh, wave_time_estimate_s=1.0, max_queries=1)
    assert sched._effective_walks() == 512
    # slot 0 is busy; the SLO queries wait in the queue
    assert sched._submit(QueryRequest(rid=0, num_walks=512)).admitted
    sched._admit()
    # feasible at full capacity: 1024 walks / 512-per-wave in a 4-wave SLO
    ok = sched._submit(QueryRequest(rid=1, num_walks=1024, slo_s=4.0))
    dg = sched._submit(QueryRequest(rid=2, num_walks=1024, slo_s=4.0,
                                    allow_downgrade=True))
    assert ok.admitted and dg.admitted
    # lose 3 of 4 shards → effective throughput 128 walks/wave
    for s in (0, 1, 3):
        sched._evict_shard(s, wave_no=0)
    assert sched._effective_walks() == 128
    # rid=1 can no longer fit and was honestly rejected; rid=2 downgraded
    assert sched.query_state(1) == "rejected"
    reason = next(d.reason for d in sched.rejected if d.rid == 1)
    assert "shard" in reason
    q2 = next(e for e in sched.queue if e.req.rid == 2)
    assert q2.downgraded and q2.walks < 1024
    assert any(e.kind == "readmit" for e in sched.fault_log)


def test_cancel_mid_degraded_leaves_scheduler_serviceable():
    g, sh = _graph_and_shards()
    sched = _sched(g, sh, plan=FaultPlan(shard_losses=((0, 3),)))
    for r in _reqs():
        assert sched._submit(r).admitted
    sched.step_wave()
    assert sched.cancel(0)
    assert sched.query_state(0) == "cancelled"
    sched._drain()
    assert not sched.active and not sched.queue
    assert {r.rid for r in sched.finished} == {1}
    # still serviceable after cancellation + degradation
    assert sched._submit(QueryRequest(rid=9, num_walks=300)).admitted
    sched._drain()
    assert sched.query_state(9) == "finished"
    assert sched.result_for(9).degraded     # shard 3 stays evicted


# --- checkpoint integrity ----------------------------------------------------


def test_crash_during_write_never_exposes_torn_checkpoint(tmp_path):
    d = str(tmp_path / "ckpt")
    tree = {"a": np.arange(12, dtype=np.int32).reshape(3, 4)}
    save_checkpoint(d, 0, tree)
    # simulate a crash mid-write of step 1: the tmp dir exists, partially
    # populated, and was never renamed
    torn = os.path.join(d, "step_00000001.tmp")
    os.makedirs(torn)
    with open(os.path.join(torn, "arrays.npz"), "wb") as f:
        f.write(b"partial")
    assert latest_step(d) == 0                      # .tmp is invisible
    out = restore_checkpoint(d, 0, {"a": np.zeros((3, 4), np.int32)})
    assert (np.asarray(out["a"]).reshape(3, 4) == tree["a"]).all()


def test_corrupt_and_truncated_payloads_are_detected(tmp_path):
    d = str(tmp_path / "ckpt")
    tree = {"a": np.arange(4096, dtype=np.int32)}
    save_checkpoint(d, 0, tree)
    payload = os.path.join(d, "step_00000000", "arrays.npz")
    like = {"a": np.zeros(4096, np.int32)}

    data = bytearray(open(payload, "rb").read())
    data[len(data) // 2] ^= 0xFF                    # silent bit flip
    open(payload, "wb").write(bytes(data))
    with pytest.raises(CheckpointCorruptError, match="step_00000000"):
        restore_checkpoint(d, 0, like)

    save_checkpoint(d, 0, tree)
    size = os.path.getsize(payload)
    with open(payload, "r+b") as f:
        f.truncate(size // 2)                       # torn write
    with pytest.raises(CheckpointCorruptError):
        restore_checkpoint(d, 0, like)


def test_async_checkpoint_write_failure_surfaces_at_wait(tmp_path):
    victim = tmp_path / "not_a_dir"
    victim.write_text("a file where the checkpointer wants a directory")
    ck = Checkpointer(str(victim))
    ck.save_async(0, {"a": np.zeros(3)})
    with pytest.raises(RuntimeError, match="background checkpoint write"):
        ck.wait()
    ck.wait()                                       # error is consumed


def test_corrupt_shards_quarantined_and_rebuilt_byte_identically(tmp_path):
    """The repair loader: corrupt / truncated / missing shard checkpoints
    are quarantined and rebuilt with the original build's key stream —
    byte-identical blocks, healthy shards never re-walked."""
    g, sh = _graph_and_shards()
    d = str(tmp_path / "walk_index")
    for s in range(S):
        save_walk_index_shard(d, s, S, g.n, sh.blocks[s], sh.segment_len,
                              sh.seed)
    inj = FaultInjector(FaultPlan(corrupt_ckpt_shards=(1,),
                                  truncate_ckpt_shards=(3,)))
    assert len(inj.mangle_checkpoints(d)) == 2

    # the plain loader refuses, actionably
    with pytest.raises(CheckpointCorruptError) as ei:
        load_walk_index(d, reassemble=False)
    msg = str(ei.value)
    assert "shard_0001" in msg                    # names the broken dir
    assert f"R={R}" in msg and f"L={L}" in msg    # and the expected (R, L)

    cfg = WalkIndexConfig(segments_per_vertex=R, segment_len=L,
                          num_shards=S, seed=sh.seed)
    fixed = load_or_repair_walk_index(d, g, cfg, reassemble=False)
    assert isinstance(fixed, ShardedWalkIndex)
    assert (np.asarray(fixed.blocks) == np.asarray(sh.blocks)).all()
    quarantined = [x for x in os.listdir(d) if x.startswith("quarantine")]
    assert sorted(quarantined) == ["quarantine.shard_0001",
                                   "quarantine.shard_0003"]
    # and the repaired layout round-trips through the plain loader
    again = load_walk_index(d, reassemble=False)
    assert (np.asarray(again.blocks) == np.asarray(sh.blocks)).all()

    # a missing shard dir is likewise rebuilt in place
    import shutil
    shutil.rmtree(os.path.join(d, "shard_0002"))
    fixed2 = load_or_repair_walk_index(d, g, cfg, reassemble=False)
    assert (np.asarray(fixed2.blocks) == np.asarray(sh.blocks)).all()


# --- the service front door --------------------------------------------------


def _service_config(tmp=None, faults=None):
    return RuntimeConfig(
        runtime=ShardConfig(num_shards=S, seed=3),
        serving=ServingConfig(segments_per_vertex=R, segment_len=L,
                              build_shards=S, max_walks=512, max_queries=4,
                              max_steps=12, checkpoint_dir=tmp),
        faults=faults)


def test_service_serves_degraded_and_exposes_fault_provenance():
    g = chung_lu_powerlaw(n=256, avg_out_deg=6, seed=2)
    svc = FrogWildService.open(
        g, _service_config(faults=FaultPlan(shard_losses=((1, 0),))))
    r = svc.topk(k=8, num_walks=1200, early_stop=False).result()
    assert r.degraded and r.shards_lost == (0,)
    want = theory.epsilon_bound(svc.config.p_T, r.num_steps, 8, 0.1,
                                r.num_walks, 1.0, 0.0)
    assert math.isclose(r.epsilon_bound, want)
    assert svc.lost_shards == frozenset({0})
    assert any(e.kind == "shard_loss" for e in svc.fault_log)


def test_service_repairs_mangled_checkpoints_before_serving(tmp_path):
    g, sh = _graph_and_shards()
    d = str(tmp_path / "walk_index")
    for s in range(S):
        save_walk_index_shard(d, s, S, g.n, sh.blocks[s], sh.segment_len,
                              sh.seed)
    svc = FrogWildService.open(
        g, _service_config(tmp=d, faults=FaultPlan(corrupt_ckpt_shards=(2,))))
    idx = svc.ensure_index()
    assert isinstance(idx, ShardedWalkIndex)
    assert (np.asarray(idx.blocks) == np.asarray(sh.blocks)).all()
    assert [x for x in os.listdir(d) if x.startswith("quarantine")] \
        == ["quarantine.shard_0002"]


# --- mesh failover (subprocess: needs multiple devices) ----------------------


def test_mesh_timeout_fails_over_to_host_loop_byte_identically():
    """A mesh whose waves keep timing out fails over once to the host-loop
    dispatch of the identical per-shard program — answers byte-identical
    to a scheduler that ran the host loop from the start."""
    run_with_devices("""
import numpy as np
from repro.distributed.faults import FaultInjector, FaultPlan
from repro.distributed.runtime import ShardRuntime
from repro.graph import chung_lu_powerlaw
from repro.query import (QueryRequest, QueryScheduler, WalkIndexConfig,
                         build_walk_index, shard_walk_index)

S, R, L = 4, 6, 2
g = chung_lu_powerlaw(n=256, avg_out_deg=6, seed=2)
idx = build_walk_index(g, WalkIndexConfig(
    segments_per_vertex=R, segment_len=L, num_shards=S, seed=2))
sh = shard_walk_index(idx, S)

def drain(sched):
    for rid in (0, 1):
        kind = "topk" if rid == 0 else "ppr"
        assert sched._submit(QueryRequest(
            rid=rid, kind=kind, source=5, k=8, num_walks=900)).admitted
    return sorted(sched._drain(), key=lambda r: r.rid)

loop = QueryScheduler(g, sh, max_walks=512, max_queries=4, max_steps=12,
                      seed=7, runtime=ShardRuntime(num_shards=S, mesh=None))
assert not loop.runtime.is_mesh
base = drain(loop)

mesh_rt = ShardRuntime.acquire(S)
assert mesh_rt.is_mesh
# wave 0 hangs through the mesh's whole retry budget (1 + max_retries
# attempts) -> failover to the host loop, whose first attempt succeeds
inj = FaultInjector(FaultPlan(wave_timeouts=((0, 2),)))
sched = QueryScheduler(g, sh, max_walks=512, max_queries=4, max_steps=12,
                       seed=7, runtime=mesh_rt, fault_injector=inj,
                       max_retries=1, backoff_base_s=0.001,
                       backoff_max_s=0.002)
out = drain(sched)
assert sched._failed_over and not sched.runtime.is_mesh
assert any(e.kind == "failover" for e in sched.fault_log)
for a, b in zip(base, out):
    assert (a.vertices == b.vertices).all()
    assert (a.scores == b.scores).all()
    assert not b.degraded
print("failover-ok")
""", n_devices=4)
