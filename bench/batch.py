"""Batch loop: ``FrogWildService.pagerank`` back to back, each estimate
from a key of its own, each ending in ``block_until_ready``.

It bypasses the gateway, the scheduler and the walk index: the paper's own
algorithm, N frogs for t supersteps. The comparison holds a sample of the
window's estimates, drawn from the seed, against the exact distribution
of the truncated walk and against global PageRank.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from bench import check, store
from bench.reference import pagerank as ref_pagerank

KEEP = 4              # estimates of the window kept for the comparison
WARMUP = 2            # estimates before the window (the first compiles)


def run(cfg, mix, n, graph, perm, seed, seconds, spans, phases, bench,
        trace, trace_dir, t_process, cc, control=None) -> dict:
    from repro import FrogWildService, RuntimeConfig, ShardConfig
    from repro.graph.csr import CSRGraph

    from bench.harness import Counters, TraceWindow, _Arm, log

    g_row_ptr, g_col_idx, g_deg = graph
    g = CSRGraph(n=n, row_ptr=g_row_ptr, col_idx=g_col_idx, out_deg=g_deg)
    N, t = int(mix["num_frogs"]), int(mix["num_steps"])
    p_T, p_s = float(mix["p_T"]), float(mix["p_s"])
    launched = N // 4 if control == "frogs" else N
    rc = RuntimeConfig(num_frogs=launched, num_steps=t, p_T=p_T, p_s=p_s,
                       runtime=ShardConfig(seed=int(seed) % 2**32))
    svc = FrogWildService.open(g, rc)
    base = jax.random.fold_in(jax.random.PRNGKey(int(seed) % 2**32), 0xBA7C)
    with phases(f"warm-up: {WARMUP} estimates"):
        for i in range(WARMUP):
            jax.block_until_ready(
                svc.pagerank(key=jax.random.fold_in(base, 1_000_000 + i)
                             ).counts)
    done = [0]
    counters = Counters(lambda: {"estimates": done[0]})
    tw = TraceWindow(trace, trace_dir, seconds, counters.read)
    arm = _Arm(counters, tw, t_process, cc)
    keep_rng = np.random.default_rng([int(seed), 0xBA7])
    kept = []
    arm.start()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    t_last = t0
    while t_last < t_end:
        with spans.span("bench.estimate"):
            res = svc.pagerank(key=jax.random.fold_in(base, done[0]))
            jax.block_until_ready(res.counts)
        t_last = time.perf_counter()
        done[0] += 1
        if len(kept) < KEEP:                         # reservoir sample
            kept.append(res)
        else:
            j = int(keep_rng.integers(done[0]))
            if j < KEEP:
                kept[j] = res
        tw(t_last)
    arm.stop()
    tw.finish()
    window = arm.window()
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    estimates = [{"counts": np.asarray(r.counts), "pi_hat": np.asarray(r.pi_hat)}
                 for r in kept]
    del kept, res, svc
    gc.collect()
    log(f"window: {done[0]} estimates in {t_last - t0:.3f} s")

    t_ref = time.perf_counter()

    def base():           # the cached base graph, read again only if needed
        return store.base_graph(cfg, bench.cache_dir, None)[1:3]

    pi_t = store.cached_vector(
        cfg, bench.cache_dir, f"truncated_walk_t{t}_pT{p_T}",
        lambda: ref_pagerank.truncated_walk(*base(), np.full(n, 1.0 / n),
                                            t, p_T))
    pi = store.cached_vector(
        cfg, bench.cache_dir, f"pagerank_pT{p_T}",
        lambda: ref_pagerank.global_pagerank(*base(), p_T))
    perm_h = np.asarray(perm)
    pi_t_run = np.empty_like(pi_t)
    pi_t_run[perm_h] = pi_t
    pi_run = np.empty_like(pi)
    pi_run[perm_h] = pi
    verdict = check.batch(estimates, pi_t_run, pi_run, N, t, p_T,
                          int(mix["k"]), float(mix["delta"]),
                          bench.limits(cfg["name"]))
    log(f"reference and comparison: {time.perf_counter() - t_ref:.3f} s")
    run_info = {"loop": "batch", "window_s": seconds,
                "batch_s": (t_last - t0) / done[0],
                "estimates": done[0], "trace": tw.reduce(),
                "trace_counts": tw.counts}
    return {"attempted": done[0], "failed": 0, "verdict": verdict,
            "setup_s": window["setup_s"], "memory_peak_bytes": int(mem),
            "run": run_info, "compiles_in_window": window["compiles"]}
