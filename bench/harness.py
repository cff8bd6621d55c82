"""One run of one cell: set-up, warm-up, the measured window, the
comparison with the reference, and the result line.

``run()`` returns the result object the last line of standard output
carries; ``run.py`` is the command. The traffic loops (``serve.py``,
``batch.py``) are chosen by the mix's ``loop``.
"""
from __future__ import annotations

import contextlib
import gc
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import arrivals, check, serve, stats, store, tracing
from bench.registry import ROOT, Benchmark

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
TRACE_MAX_S = 5.0          # longest profiler window
STEP_SPAN = "repro.scheduler.step_wave"   # the program's span of one wave
# the stages inside it, each per wave but finalize, which runs per answer
WAVE_STAGES = ("repro.scheduler.admit", "repro.wave.prepare",
               "repro.wave.launch", "repro.wave.device_wait", "repro.wave.d2h",
               "repro.scheduler.accumulate")
FINALIZE_SPAN = "repro.scheduler.finalize"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, flush=True)


def chips(count: int) -> List:
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < count:
        raise NoChip(f"the cell needs {count} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform!r} device(s)")
    return devs[:count]


def peaks(kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {PEAKS_FILE.name}")
    return table[kind]


def enable_compile_cache(bench: Benchmark) -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout;
    every program is kept, however fast it compiled."""
    path = str(bench.cache_dir / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@contextlib.contextmanager
def stage_spans(on: bool):
    """The program's own stage spans (``repro.telemetry``), counted from
    zero and on from before set-up in a traced run, where the program has
    them; off again when the run ends."""
    try:
        from repro import telemetry
    except ImportError:
        telemetry = None
    if not on or telemetry is None:
        yield
        return
    telemetry.reset()
    telemetry.enable(True)
    try:
        yield
    finally:
        telemetry.enable(False)


class Phases:
    """Wall time of each set-up phase, and the compiles inside it."""

    def __init__(self, cc: tracing.CompileCounter):
        self.cc = cc

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0, c0 = time.perf_counter(), self.cc.snapshot()
        yield
        c = [b - a for a, b in zip(c0, self.cc.snapshot())]
        log(f"[set-up] {name}: {time.perf_counter() - t0:.3f} s, {c[1]} "
            f"compiles, {c[2]} compile-cache hits, {c[0]} traces")


class Counters:
    """Program counters at the window's start and close."""

    def __init__(self, read):
        self.read = read
        self.at_start = self.at_stop = None

    def start(self):
        self.at_start = (time.perf_counter(), self.read())

    def stop(self):
        self.at_stop = (time.perf_counter(), self.read())

    def delta(self) -> Dict[str, float]:
        """Each counter's move over the window; one first seen at the close
        (a stage span that had not yet run) moved from 0."""
        a, b = self.at_start[1], self.at_stop[1]
        return {k: b[k] - a.get(k, 0) for k in b}


class TraceWindow:
    """Starts the profiler a third into the window and stops it after
    ``min(window / 3, TRACE_MAX_S)``; ``read`` gives the program counters
    at both ends, so the trace's waves can be checked against them."""

    def __init__(self, enabled: bool, directory: Path, window_s: float,
                 read):
        self.enabled = enabled
        self.directory = directory
        self.lead = window_s / 3.0
        self.length = min(window_s / 3.0, TRACE_MAX_S)
        self.read = read
        self.t_start = None
        self.state = "idle"
        self.counts = None

    def arm(self, t0: float):
        self.t_start = t0 + self.lead

    def __call__(self, now: float):
        if not self.enabled or self.t_start is None:
            return
        if self.state == "idle" and now >= self.t_start:
            self.profile = tracing.Profile(self.directory).__enter__()
            self.annotation = jax.profiler.TraceAnnotation(
                tracing.WINDOW_SPAN)
            self.annotation.__enter__()
            self.c0 = self.read()
            self.t_on = time.perf_counter()
            self.state = "on"
        elif self.state == "on" and now >= self.t_on + self.length:
            self.finish()

    def finish(self):
        if self.state != "on":
            return
        c1 = self.read()
        self.annotation.__exit__(None, None, None)
        self.profile.__exit__(None, None, None)
        self.counts = {k: c1[k] - self.c0.get(k, 0) for k in c1}
        self.state = "done"

    def reduce(self) -> Optional[dict]:
        if self.state != "done":
            return None
        events = tracing.load_events(self.profile.xplane())
        return tracing.reduce(events)


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_process: float, root: Path = ROOT, require_tpu: bool = True,
        cache: bool = True, control: Optional[str] = None) -> dict:
    """One run of ``cell_name``. ``control`` runs the program below what
    the configuration states, as a later change might be tempted to, and
    the comparison must call it not correct: ``"index"`` a quarter of the
    walk index (segments_per_vertex / 4), ``"frogs"`` a quarter of the
    frogs. The benchmark's own runs never use one
    (``calibrate.py`` does)."""
    bench = Benchmark(root)
    cell = bench.cell(cell_name)
    devices = (chips(int(cell["chips"])) if require_tpu
               else jax.devices()[:int(cell["chips"])])
    dev = devices[0]
    if require_tpu:
        peaks(dev.device_kind)
    if cache:
        log(f"compile cache: {enable_compile_cache(bench)}")
    cfg = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    log(f"cell {cell_name}: config {cfg['name']}, traffic {mix['name']} "
        f"({mix['loop']} loop), seed {seed}, window {seconds} s, "
        f"trace {int(trace)}, device {dev.platform} {dev.device_kind} "
        f"x{len(devices)}")
    with tracing.CompileCounter() as cc, stage_spans(trace):
        phases = Phases(cc)
        with phases("graph (load, or generate once per checkout)"):
            n, row_ptr, col_idx, made = store.base_graph(
                cfg, bench.cache_dir, bench.generator(cfg["graph"]["generator"]))
        log(f"graph {cfg['name']}: n={n} arcs={col_idx.shape[0]}"
            + (" (generated in this run)" if made else " (from the cache)"))
        with phases("relabel by seed (device)"):
            g_row_ptr, g_col_idx, g_deg, perm = store.relabel(
                n, row_ptr, col_idx, seed)
        del row_ptr, col_idx
        spans = tracing.Spans(annotate=trace)
        trace_dir = bench.cache_dir / "trace" / cell_name
        if mix["loop"] == "batch":
            from bench import batch
            out = batch.run(cfg, mix, n, (g_row_ptr, g_col_idx, g_deg), perm,
                            seed, seconds, spans, phases, bench, trace,
                            trace_dir, t_process, cc, control)
        else:
            out = _serve(cfg, mix, n, (g_row_ptr, g_col_idx, g_deg), seed,
                         seconds, spans, phases, trace, trace_dir, t_process,
                         cc, bench.limits(cfg["name"]), control)
    return _result(bench, cell_name, out, trace, devices)


def _serve(cfg, mix, n, graph, seed, seconds, spans, phases, trace,
           trace_dir, t_process, cc, lim, control=None) -> dict:
    from repro import Gateway, RuntimeConfig, ServingConfig, ShardConfig
    from repro.graph.csr import CSRGraph

    g_row_ptr, g_col_idx, g_deg = graph
    g = CSRGraph(n=n, row_ptr=g_row_ptr, col_idx=g_col_idx, out_deg=g_deg)
    serving = dict(cfg["serving"])
    if control == "index":
        serving["segments_per_vertex"] //= 4
    rc = RuntimeConfig(p_T=float(cfg["p_T"]),
                       runtime=ShardConfig(seed=int(seed) % 2**32),
                       serving=ServingConfig(**serving))
    t_build = time.perf_counter()
    with phases("index build (Gateway.open)"):
        gw = Gateway.open(g, rc, replicas=1)
        jax.block_until_ready(gw.pool.index.endpoints)
    index_build_s = time.perf_counter() - t_build
    setup_spans = gw.stats().get("spans")
    svc = gw.pool.replicas[0]
    sched = svc.scheduler
    if mix["loop"] == "open":
        with phases("wave ladder (every bucket the traffic can use)"):
            log(f"wave ladder: {sched.warm_ladder()} programs")

    def read():
        st = svc.serving_stats()
        gs = gw.stats()
        out = {"waves_run": st.waves_run, "finished": st.finished,
               "walks_executed": st.walks_executed,
               "requests": gs["requests"], "cache_hits": gs["cache_hits"],
               "joins": gs["joins"], "sheds": gs["sheds"],
               "rejected": gs["rejected"]}
        if hasattr(st, "tally_bytes_out"):
            out["tally_bytes_out"] = st.tally_bytes_out
        for name, totals in (gs.get("spans") or {}).items():
            for field in ("n", "s", "self_s"):
                out[f"{name}.{field}"] = totals[field]
        return out

    counters = Counters(read)
    tw = TraceWindow(trace, trace_dir, seconds, read)
    m = arrivals.Mix(mix, n, seed)
    loop = serve.closed_loop if mix["loop"] == "closed" else serve.open_loop
    arm = _Arm(counters, tw, t_process, cc)
    recs, (t0, t_end), client = loop(gw, m, seconds, spans, arm, tw)
    tw.finish()
    window = arm.window()
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    delta = counters.delta()
    max_walks = sched.max_walks
    gw.close()
    del gw, svc, sched
    gc.collect()

    answered = [r for r in recs if r.answer is not None]
    lat = []
    for r in recs:
        if not (t0 <= r.t_due < t_end):
            continue
        ok = r.answer is not None
        lat.append(r.t_done - r.t_due if ok else float("inf"))
    unanswered = sum(1 for r in recs if r.handle is not None
                     and not r.settled)
    refused = sum(1 for r in recs if r.refused)
    failed = sum(1 for r in recs if r.failed)
    in_window = [r for r in answered if t0 <= r.t_done <= t_end]
    late = [r.t_submit - r.t_due for r in recs if r.t_submit is not None]
    log(f"window: {t_end - t0:.3f} s, {len(recs)} requests, "
        f"{len(in_window)} answered inside it, {len(answered)} in all, {refused} refused, {failed} failed, "
        f"{unanswered} unanswered after the drain")
    if late:
        log(f"generator lateness: mean {np.mean(late) * 1e3:.3f} ms, "
            f"max {np.max(late) * 1e3:.3f} ms over {len(late)} sends")
    t_check = time.perf_counter()
    scfg = cfg["serving"]
    verdict = check.serving(
        [r.answer for r in answered], unanswered, g_row_ptr, g_col_idx, n,
        float(cfg["p_T"]), int(scfg["max_steps"]),
        int(scfg["segments_per_vertex"]), int(scfg["segment_len"]), seed,
        lim)
    log(f"reference and comparison: {time.perf_counter() - t_check:.3f} s")
    finite = [x for x in lat if x != float("inf")]
    if lat and float("inf") in lat:
        cap = (max(finite) if finite else 0.0) + serve.DRAIN_S
        lat = [x if x != float("inf") else cap for x in lat]
    wave_s = sum(b - a for a, b in client.waves if t0 <= a < t_end)
    waves_host = sum(1 for a, _ in client.waves if t0 <= a < t_end)
    run_info = {
        "loop": mix["loop"], "window_s": t_end - t0,
        "answers_per_s": stats.rate(len(in_window), t_end - t0),
        "answer_p90_s": stats.percentile(lat, 90) if lat else None,
        "counters": delta, "max_walks": max_walks,
        "waves_host": waves_host, "wave_s": wave_s,
        "index_build_s": index_build_s, "setup_spans": setup_spans,
        "trace": tw.reduce(), "trace_counts": tw.counts,
    }
    return {"attempted": len(recs), "failed": refused + failed + unanswered,
            "verdict": verdict, "setup_s": window["setup_s"],
            "memory_peak_bytes": int(mem), "run": run_info,
            "compiles_in_window": window["compiles"]}


class _Arm:
    """The window's start: records set-up time and compile counts, and
    arms the trace window. Handed to the traffic loops as their counters."""

    def __init__(self, counters: Counters, tw: TraceWindow, t_process: float,
                 cc: tracing.CompileCounter):
        self.counters, self.tw, self.t_process, self.cc = (
            counters, tw, t_process, cc)

    def start(self):
        self.setup_s = time.time() - self.t_process
        self.c0 = self.cc.snapshot()
        self.counters.start()
        self.tw.arm(time.perf_counter())

    def stop(self):
        self.counters.stop()
        self.c1 = self.cc.snapshot()

    def window(self) -> dict:
        c = [b - a for a, b in zip(self.c0, self.c1)]
        log(f"inside the window: {c[1]} compiles, {c[2]} compile-cache "
            f"hits, {c[0]} traces")
        return {"setup_s": self.setup_s, "compiles": c[1] + c[2]}


def _result(bench: Benchmark, cell_name: str, out: dict, trace: bool,
            devices) -> dict:
    run_info = out["run"]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    metrics = {}
    if not trace:
        for m in bench.end_to_end(cell_name):
            value = (out["setup_s"] if m["name"] == "setup_s"
                     else run_info.get(m["name"]))
            if value is None:
                raise RuntimeError(f"the run measured no {m['name']}")
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        red = run_info["trace"]
        if red is None:
            raise RuntimeError("the traced window recorded no device work")
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        for m in bench.per_layer(cell_name):
            value = bench.reader(m["name"])(run_info)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        _trace_checks(run_info)
    verdict = out["verdict"]
    for line in verdict.lines():
        print(line, file=sys.stderr, flush=True)
    result = {"correct": verdict.correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        red = run_info["trace"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = verdict.numbers
    log(f"peak device bytes in use: {out['memory_peak_bytes']}; "
        f"compiles inside the window: {out['compiles_in_window']}")
    return result


def _trace_checks(run_info: dict) -> None:
    """The traced run's own consistency: device busy time within the
    window, and as many wave (or estimate) programs, and as many
    ``repro.scheduler.step_wave`` spans, in the trace as the program's
    counters moved while it ran. Logs where the device's idle time went."""
    red, counts = run_info["trace"], run_info["trace_counts"] or {}
    log(f"trace: busy {red['busy_s']:.6f} s of a {red['window_s']:.6f} s "
        f"window ({'ok' if red['busy_s'] <= red['window_s'] else 'OVER'})")
    idle = red["window_s"] - red["busy_s"]
    for name, sec in list(red["idle_by_span"].items())[:10]:
        log(f"trace: idle {sec:.6f} s ({sec / idle:.2%} of the idle time) "
            f"under {name}")
    if f"{STEP_SPAN}.n" in counts:
        traced = red["span_counts"].get(STEP_SPAN, 0)
        log(f"trace: {traced} {STEP_SPAN} spans in the trace, counters "
            f"moved {counts['waves_run']} "
            f"({'ok' if traced == counts['waves_run'] else 'differ'})")
    c = run_info.get("counters") or {}
    if c.get(f"{STEP_SPAN}.s") and c.get(f"{FINALIZE_SPAN}.n"):
        waves = c["waves_run"]
        answers = c["finished"] / waves
        stages = (sum(c.get(f"{s}.s", 0.0) for s in WAVE_STAGES) / waves
                  + c[f"{FINALIZE_SPAN}.s"] / c[f"{FINALIZE_SPAN}.n"]
                  * answers)
        step = c[f"{STEP_SPAN}.s"] / waves
        log(f"stages: {stages * 1e3:.4f} ms of {step * 1e3:.4f} ms per wave "
            f"in {STEP_SPAN} ({stages / step:.2%}), finalize at "
            f"{answers:.4f} answers per wave")
    for name, c in sorted(red["modules"].items(), key=lambda x: -x[1])[:6]:
        log(f"trace: program {name} ran {c:g} times")
    key = "waves_run" if "waves_run" in counts else "estimates"
    if key in counts:
        traced = sum(c for name, c in red["modules"].items()
                     if tracing.program_of(name) == key)
        log(f"trace: {traced:g} {key} programs in the trace, counters "
            f"moved {counts[key]} ({'ok' if traced == counts[key] else 'differ'})")
