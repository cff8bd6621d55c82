#!/usr/bin/env python3
"""Smoke run of the FrogWild serving path on TPU, at LiveJournal's scale.

One process; it starts no children. With no arguments it needs one TPU
chip and runs the main path once:

1. device check: ``jax.devices()`` must be TPU, or the script exits 2
   without printing the ok line (there is no CPU fallback);
2. graph: a Chung–Lu power-law graph at LiveJournal's shape
   (``configs/frogwild_graphs.py:LIVEJOURNAL_FULL``: n = 4,847,571, mean
   out-degree 14.2, seed 0), generated here from the seed;
3. reference: 50 power iterations (``core/pagerank.py``) on the chip;
4. serving: ``Gateway`` → ``FrogWildService`` → ``QueryScheduler`` with
   the default geometry, the walk index built on the chip. Four global
   top-k answers (one of them over HTTP) must each capture π-mass of at
   least ``μ_k(π) − epsilon_bound`` (Theorem 1), four PPR answers must list
   their source first, every answer must capture normalized mass of at
   least ``MASS_FLOOR``, the non-HTTP answers must be byte-identical to a
   replay through the host-loop oracle dispatch, and a dominated repeat
   must be a cache hit that runs no walk;
5. batch: ``FrogWildService.pagerank`` checked against π the same way.

``--chips 4`` runs only the multi-chip paths, on a 4-chip mesh: sharded
serving against single-device fused dispatch over the same slab
(byte-identical answers, no failover), and the mesh batch engine against
power iteration.

Every failed check raises, so the script exits non-zero without the ok
line. The last line of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The times printed are set-up times of this smoke run, not benchmark
metrics. Run from the repository root::

    python3 chip_smoke.py
    python3 chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import FrogWildService, Gateway, RuntimeConfig, ShardConfig  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.frogwild_graphs import LIVEJOURNAL_FULL, GraphConfig  # noqa: E402
from repro.core import theory  # noqa: E402
from repro.core.pagerank import power_iteration  # noqa: E402
from repro.distributed.runtime import ShardRuntime  # noqa: E402
from repro.gateway import serve_http  # noqa: E402
from repro.graph import chung_lu_powerlaw  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.query import (QueryRequest, QueryScheduler, ShardedWalkIndex,  # noqa: E402
                         plan_query, shard_walk_index)

K, EPSILON, DELTA = 10, 0.3, 0.1
NUM_QUERIES = 4               # top-k answers and PPR answers, each
POWER_ITERS = 50
# At ε = 0.3 Theorem 1's bound exceeds μ_k(π) (about 0.039 on this graph),
# so it admits any k vertices. The floor on normalized mass captured,
# μ_k(π̂)/μ_k(π), is what a wrong answer fails: correct answers read 0.88
# to 0.98 on a TPU v5e, while k vertices drawn at random capture about
# 1e-4 of μ_k(π).
MASS_FLOOR = 0.5


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class Timer:
    """Wall time per phase, plus the backend compile time inside it."""

    def __init__(self):
        self.times: dict = {}
        self._compile_s = 0.0

    def __enter__(self) -> "Timer":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._compile_s += duration

    @contextlib.contextmanager
    def phase(self, name: str):
        t0, c0 = time.perf_counter(), self._compile_s
        yield
        dt, dc = time.perf_counter() - t0, self._compile_s - c0
        self.times[name] = dt
        print(f"[{name}] {dt:.3f} s wall, {dc:.3f} s of it backend compile",
              flush=True)


def tpu_devices(count: int) -> list:
    """The first ``count`` devices, which must all be TPU; exits 2 if not."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < count:
        print(f"chip_smoke: needs {count} TPU device(s), JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        sys.exit(2)
    return devs


def make_graph(gcfg: GraphConfig, timer: Timer):
    """The graph from its seed, then a timed re-upload of its CSR."""
    with timer.phase("graph generation (host numpy, incl. CSR upload)"):
        g = chung_lu_powerlaw(n=gcfg.n, avg_out_deg=gcfg.avg_out_deg,
                              theta=gcfg.theta, seed=gcfg.seed)
        jax.block_until_ready(g.col_idx)
    host = g.to_numpy()
    with timer.phase("host-to-device transfer (CSR)"):
        g = dataclasses.replace(
            g, row_ptr=jax.device_put(host.row_ptr),
            col_idx=jax.device_put(host.col_idx),
            out_deg=jax.device_put(host.out_deg), _derived={})
        jax.block_until_ready((g.row_ptr, g.col_idx, g.out_deg))
    nbytes = sum(a.nbytes for a in (host.row_ptr, host.col_idx,
                                    host.out_deg))
    print(f"graph: Chung-Lu at LiveJournal's shape ({gcfg.name}): "
          f"n={g.n} nnz={g.nnz} mean out-degree {g.nnz / g.n:.2f} "
          f"seed={gcfg.seed}; CSR {nbytes} bytes", flush=True)
    return g


def reference(g, timer: Timer) -> np.ndarray:
    with timer.phase(f"reference: {POWER_ITERS} power iterations"):
        pi = np.asarray(power_iteration(g, num_iters=POWER_ITERS))
    check(bool(np.isfinite(pi).all()), "π has non-finite entries")
    check(abs(float(pi.sum()) - 1.0) < 1e-3, f"π sums to {pi.sum()}")
    print(f"reference: sum(pi)={pi.sum():.6f} mu_k(pi)="
          f"{mu_k(pi, K):.6f} (k={K})", flush=True)
    return pi


def mu_k(scores: np.ndarray, k: int) -> float:
    """``core/metrics.py:mass_captured`` of the vector against itself."""
    return float(np.sort(scores)[-k:].sum())


def check_topk(name: str, vertices, eps_bound: float, degraded: bool,
               pi: np.ndarray) -> None:
    """Theorem 1's contract, μ_k(π̂) ≥ μ_k(π) − ε, for a non-degraded
    answer, with μ_k of the answer's k vertices read off π; and the
    normalized mass captured at least :data:`MASS_FLOOR`."""
    v = np.asarray(vertices)
    mass, best = float(pi[v].sum()), mu_k(pi, len(v))
    print(f"{name}: k={len(v)} pi-mass {mass:.6f} >= mu_k(pi) {best:.6f} "
          f"- epsilon_bound {eps_bound:.4f}; normalized {mass / best:.4f} "
          f">= {MASS_FLOOR} (degraded={degraded})", flush=True)
    check(not degraded, f"{name}: degraded answer")
    check(len(set(v.tolist())) == len(v), f"{name}: repeated vertices")
    check(mass >= best - eps_bound,
          f"{name}: pi-mass {mass} < mu_k {best} - eps {eps_bound}")
    check(mass / best >= MASS_FLOOR,
          f"{name}: normalized mass {mass / best} < {MASS_FLOOR}")


def same_answer(a, b) -> bool:
    """Byte-identical vertices and scores, from as many walks."""
    return (np.asarray(a.vertices).tobytes()
            == np.asarray(b.vertices).tobytes()
            and np.asarray(a.scores).tobytes()
            == np.asarray(b.scores).tobytes()
            and a.num_walks == b.num_walks)


def check_ppr(name: str, result, source: int) -> None:
    v = np.asarray(result.vertices)
    print(f"{name}: source {source} -> first {v[0]} score "
          f"{float(np.asarray(result.scores)[0]):.4f} "
          f"walks {result.num_walks}", flush=True)
    check(not result.degraded, f"{name}: degraded answer")
    check(int(v[0]) == source, f"{name}: source {source} is not first")


def http_topk(url: str) -> dict:
    query = f"/topk?k={K}&epsilon={EPSILON}&delta={DELTA}&timeout_s=900"
    with urllib.request.urlopen(url + query, timeout=960) as resp:
        check(resp.status == 200, f"HTTP /topk answered {resp.status}")
        return json.loads(resp.read())


def ppr_sources(n: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return [int(s) for s in rng.choice(n, NUM_QUERIES, replace=False)]


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def check_kernels(timer: Timer) -> None:
    """Each Pallas kernel that compiles for the chip, against its oracle;
    the mode is the backend's (compiled on TPU)."""
    with timer.phase("kernels: frog_count (Pallas) vs its oracle"):
        for i, (N, n) in enumerate(((8192, 65_536), (5000, 70_001))):
            dest = jax.random.randint(jax.random.PRNGKey(i), (N,), 0, n)
            got = np.asarray(ops.frog_count(dest, n, impl="pallas"))
            want = np.asarray(ops.frog_count(dest, n, impl="ref"))
            check(np.array_equal(got, want),
                  f"frog_count N={N} n={n}: Pallas differs from the oracle")
    print(f"kernels: frog_count compiled = {not ops.resolve_interpret()}, "
          f"matches its oracle", flush=True)


def run_one_chip(gcfg: GraphConfig, timer: Timer) -> None:
    """Serving and batch FrogWild on one device."""
    check_kernels(timer)
    g = make_graph(gcfg, timer)
    pi = reference(g, timer)
    cfg = RuntimeConfig()
    with timer.phase("index build (walk index on the device)"):
        gw = Gateway.open(g, cfg, replicas=1)
        jax.block_until_ready(gw.pool.index.endpoints)
    with gw, serve_http(gw) as srv:
        svc = gw.pool.replicas[0]
        sources = ppr_sources(g.n, gcfg.seed)
        with timer.phase("first answer (top-k, incl. compile)"):
            first = svc.topk(k=K, epsilon=EPSILON, delta=DELTA).result()
        with timer.phase("waves run (top-k and PPR in flight together)"):
            topk = [svc.topk(k=K, epsilon=EPSILON, delta=DELTA)
                    for _ in range(NUM_QUERIES - 2)]
            ppr = [gw.ppr(s, k=K, epsilon=EPSILON, delta=DELTA)
                   for s in sources]
            topk = [first] + [h.result() for h in topk]
            ppr = [h.result() for h in ppr]
        with timer.phase("HTTP GET /topk"):
            over_http = http_topk(srv.url)
        stats = svc.serving_stats()
        print(f"serving: {stats.waves_run} waves, {stats.walks_executed} "
              f"walks, geometry R={cfg.serving.segments_per_vertex} "
              f"L={cfg.serving.segment_len} W={cfg.serving.max_walks} "
              f"Q={cfg.serving.max_queries}", flush=True)
        for i, r in enumerate(topk):
            check_topk(f"top-k #{i}", r.vertices, r.epsilon_bound,
                       r.degraded, pi)
        check(over_http["source"] == "live", "HTTP /topk was not live")
        check_topk("top-k over HTTP", over_http["vertices"],
                   over_http["epsilon_bound"], over_http["degraded"], pi)
        for i, (r, s) in enumerate(zip(ppr, sources)):
            check_ppr(f"ppr #{i}", r, s)
        with timer.phase("replay through the host-loop oracle dispatch"):
            replayed = serve_loop_oracle(g, gw.pool.index, cfg, [
                [request_of(first, 0)],
                [request_of(r, 0) for r in topk[1:]]
                + [request_of(r, s) for r, s in zip(ppr, sources)]])
        for a, b in zip(topk + ppr, replayed):
            same = same_answer(a, b)
            print(f"{a.kind} rid {a.rid}: served == loop oracle "
                  f"byte-identical: {same}", flush=True)
            check(same, f"request {a.rid}: served and oracle answers differ")

        before = svc.serving_stats()
        hit = gw.topk(k=K, epsilon=EPSILON + 0.1, delta=DELTA)
        after = svc.serving_stats()
        print(f"dominated repeat: served from {hit.source}, new walks "
              f"{after.walks_executed - before.walks_executed}", flush=True)
        check(hit.source == "cache", f"repeat served from {hit.source}")
        check(hit.done() and after.walks_executed == before.walks_executed
              and after.waves_run == before.waves_run,
              "the cache hit ran walks")

        with timer.phase("batch FrogWild (svc.pagerank)"):
            res = svc.pagerank(epsilon=EPSILON, delta=DELTA)
            pi_hat = np.asarray(res.pi_hat)
        check_batch("batch", pi_hat, res.counts, res.num_frogs, cfg, pi)
    print(f"peak device bytes in use: {peak_bytes(jax.devices()[0])}",
          flush=True)


def check_batch(name, pi_hat, counts, launched, cfg, pi) -> None:
    """Frog conservation, then Theorem 1 at the frogs actually launched
    (the mesh engine launches the plan's count rounded down to a multiple
    of the shard count)."""
    plan = plan_query(K, EPSILON, DELTA, p_T=cfg.p_T,
                      max_steps=cfg.serving.max_steps)
    counts = np.asarray(counts)
    bound = theory.epsilon_bound(cfg.p_T, plan.num_steps, K, DELTA,
                                 launched, 1.0, 0.0)
    print(f"{name}: {launched} frogs (plan {plan.num_walks}), "
          f"t={plan.num_steps}, counts sum {counts.sum()}", flush=True)
    check(bool(np.isfinite(pi_hat).all()), f"{name}: non-finite estimate")
    check(int(counts.sum()) == launched,
          f"{name}: {counts.sum()} frogs tallied, {launched} launched")
    check_topk(name, np.argsort(-pi_hat, kind="stable")[:K], bound, False,
               pi)


def request_of(result, source: int) -> QueryRequest:
    """The request the smoke sent for ``result`` (the service defaults)."""
    return QueryRequest(rid=result.rid, kind=result.kind, k=K,
                        source=source, epsilon=EPSILON, delta=DELTA)


def serve_loop_oracle(g, index, cfg: RuntimeConfig, batches) -> list:
    """Replays ``batches`` of requests, one batch run to its end before
    the next is sent, as the served run sent them. The replay goes
    through the structural reference: the legacy host-loop dispatch
    (one device call per shard and stitch round, cross-shard sums on the
    host) over a 4-way sharded copy of the slab, with the jnp oracle
    stitch. The key stream starts at wave 0 in both, so the answers must
    be byte-identical to the served ones."""
    scfg = cfg.serving
    sched = QueryScheduler(
        g, shard_walk_index(index, 4), max_walks=scfg.max_walks,
        max_queries=scfg.max_queries, max_steps=scfg.max_steps,
        p_T=cfg.p_T, impl="ref", seed=cfg.runtime.seed,
        runtime=ShardRuntime(num_shards=4, mesh=None),
        sharded_dispatch="loop")
    check(sched.dispatch == "loop", f"oracle ran {sched.dispatch}")
    out = []
    for batch in batches:
        for req in batch:
            check(sched._submit(req).admitted,
                  f"oracle refused request {req.rid}")
        while sched.step_wave():
            pass
        out += [sched.result_for(req.rid) for req in batch]
    return out


def serve_fused(g, index: ShardedWalkIndex, cfg: RuntimeConfig,
                requests) -> list:
    """The same requests through single-device fused dispatch over the
    same sharded slab: the path a mesh failover lands on."""
    scfg = cfg.serving
    sched = QueryScheduler(
        g, index, max_walks=scfg.max_walks, max_queries=scfg.max_queries,
        max_steps=scfg.max_steps, p_T=cfg.p_T, impl=cfg.kernel.stitch_impl,
        seed=cfg.runtime.seed,
        runtime=ShardRuntime(num_shards=index.num_shards, mesh=None))
    check(sched.dispatch == "fused", f"reference ran {sched.dispatch}")
    for req in requests:
        check(sched._submit(dataclasses.replace(req, t_submit=None)).admitted,
              f"fused reference refused request {req.rid}")
    while sched.step_wave():
        pass
    return [sched.result_for(req.rid) for req in requests]


def run_four_chips(gcfg: GraphConfig, timer: Timer, devices) -> None:
    """Mesh serving against fused dispatch, and the mesh batch engine."""
    g = make_graph(gcfg, timer)
    pi = reference(g, timer)
    mesh = jax.make_mesh((4,), ("vertex",), devices=devices[:4])
    cfg = RuntimeConfig(runtime=ShardConfig(num_shards=4))
    svc = FrogWildService.open(g, cfg, mesh=mesh)
    check(svc.runtime.is_mesh and svc.runtime.num_shards == 4,
          "service did not take the 4-device mesh")
    with timer.phase("index build (shard_map over the mesh)"):
        index = svc.ensure_index()
    check(isinstance(index, ShardedWalkIndex) and index.num_shards == 4,
          "index is not sharded 4 ways")
    sources = ppr_sources(g.n, gcfg.seed)
    with timer.phase("mesh serving (incl. compile)"):
        handles = ([svc.topk(k=K, epsilon=EPSILON, delta=DELTA)
                    for _ in range(NUM_QUERIES)]
                   + [svc.ppr(s, k=K, epsilon=EPSILON, delta=DELTA)
                      for s in sources])
        mesh_res = [h.result() for h in handles]
    sched = svc.scheduler
    check(sched.dispatch == "mesh" and sched.runtime.is_mesh,
          f"served through {sched.dispatch}, not the mesh")
    failovers = [e for e in svc.fault_log if e.kind == "failover"]
    print(f"mesh serving: is_mesh={sched.runtime.is_mesh} "
          f"{sched.stats().waves_run} waves, fault log "
          f"{[e.kind for e in svc.fault_log]}", flush=True)
    check(not failovers, f"mesh serving failed over: {failovers}")
    slab_block = index.shard_size * index.segments_per_vertex * 4
    in_use = [int((d.memory_stats() or {}).get("bytes_in_use", -1))
              for d in mesh.devices.flat]
    print(f"placement: slab block {slab_block} bytes per device, bytes in "
          f"use per device {in_use}", flush=True)
    placed = sched._placed_blocks
    check(len(placed.sharding.device_set) == 4
          and {s.data.shape for s in placed.addressable_shards}
          == {(1, index.shard_size, index.segments_per_vertex)},
          "slab blocks are not one per device")
    if min(in_use) >= 0:            # the CPU backend reports no stats
        check(min(in_use) >= slab_block,
              "a device holds less than its slab block")

    with timer.phase("single-device fused serving (incl. compile)"):
        fused_res = serve_fused(g, index, cfg, [h.request for h in handles])
    for a, b in zip(mesh_res, fused_res):
        same = same_answer(a, b)
        print(f"{a.kind} rid {a.rid}: mesh == fused byte-identical: {same}",
              flush=True)
        check(same, f"request {a.rid}: mesh and fused answers differ")
    for i, r in enumerate(mesh_res[:NUM_QUERIES]):
        check_topk(f"mesh top-k #{i}", r.vertices, r.epsilon_bound,
                   r.degraded, pi)
    for i, (r, s) in enumerate(zip(mesh_res[NUM_QUERIES:], sources)):
        check_ppr(f"mesh ppr #{i}", r, s)

    with timer.phase("batch engine on the mesh (incl. compile)"):
        res = svc.pagerank(epsilon=EPSILON, delta=DELTA)
        pi_hat = np.asarray(res.pi_hat)
    print(f"batch engine: overflow {res.overflow}, frogs sent per step "
          f"{np.asarray(res.sent_per_step).tolist()}", flush=True)
    check(res.overflow == 0, f"engine dropped {res.overflow} frogs")
    launched = res.config.num_frogs // 4 * 4
    check_batch("mesh batch engine", pi_hat, res.counts, launched, cfg, pi)
    for d in mesh.devices.flat:
        print(f"peak device bytes in use, device {d.id}: {peak_bytes(d)}",
              flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the mesh paths, on a 4-chip mesh")
    args = parser.parse_args(argv)
    devices = tpu_devices(args.chips)
    if args.chips == 4:
        check(len(devices) == 4, f"{len(devices)} TPU devices, wanted 4")
    cache = enable_compile_cache(ROOT)
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}, compile cache {cache}", flush=True)
    with Timer() as timer:
        if args.chips == 4:
            run_four_chips(LIVEJOURNAL_FULL, timer, devices)
        else:
            run_one_chip(LIVEJOURNAL_FULL, timer)
    print("smoke set-up times, s (not benchmark metrics): "
          + json.dumps({k: round(v, 3) for k, v in timer.times.items()}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
