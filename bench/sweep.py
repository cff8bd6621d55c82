#!/usr/bin/env python3
"""The knee of an open-loop cell: its mix offered at a list of fixed rates,
one after another over one set-up, each from a cold result cache.

    python3 bench/sweep.py --workload lj-ppr-interactive --seed 7 \\
        --seconds 30 --rates 2 3 4 5 6

Prints, per rate, the answers completed per second, the 50th and 90th
percentile latency from the due time, the requests still in flight at the
close and those refused. The knee is the highest rate whose answers keep
up with it and whose latency does not grow with the run; the cell's mix
then fixes its rate at four fifths of it (PERF.md keeps the sweep).
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(ROOT, "bench", ".cache", "tpu_logs"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)

    from repro import Gateway, RuntimeConfig, ServingConfig, ShardConfig
    from repro.graph.csr import CSRGraph

    from bench import arrivals, harness, serve, stats, store, tracing
    from bench.registry import Benchmark

    bench = Benchmark()
    cell = bench.cell(args.workload)
    harness.chips(int(cell["chips"]))
    harness.enable_compile_cache(bench)
    cfg, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    n, rp, ci, _ = store.base_graph(cfg, bench.cache_dir,
                                    bench.generator(cfg["graph"]["generator"]))
    g_rp, g_ci, g_deg, _ = store.relabel(n, rp, ci, args.seed)
    g = CSRGraph(n=n, row_ptr=g_rp, col_idx=g_ci, out_deg=g_deg)
    rc = RuntimeConfig(p_T=float(cfg["p_T"]),
                       runtime=ShardConfig(seed=args.seed % 2**32),
                       serving=ServingConfig(**cfg["serving"]))
    gw = Gateway.open(g, rc, replicas=1)
    gw.pool.replicas[0].scheduler.warm_ladder()
    spans = tracing.Spans()
    for i, rate in enumerate(args.rates):
        params = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
        m = arrivals.Mix(params, n, args.seed, stream_offset=100 * (i + 1))
        gw.cache.clear()
        client = serve.Client(gw, spans)
        recs, (t0, t_end) = serve._offered(client, m, args.seconds, False,
                                           None, lambda now: None)
        in_flight = sum(1 for r in recs if r.t_done is None or r.t_done > t_end)
        lat = [(r.t_done - r.t_due) if r.answer is not None else float("inf")
               for r in recs]
        done = sum(1 for r in recs if r.answer is not None
                   and r.t_done <= t_end)
        print("SWEEP " + json.dumps({
            "rate_per_s": rate, "requests": len(recs),
            "answers_per_s": stats.rate(done, args.seconds),
            "p50_s": stats.percentile(lat, 50),
            "p90_s": stats.percentile(lat, 90),
            "in_flight_at_close": in_flight,
            "refused": sum(r.refused for r in recs)}), flush=True)
    gw.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
