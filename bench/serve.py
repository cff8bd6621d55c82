"""Serving loops: a mix's requests through ``Gateway.ppr``,
waves driven by ``Gateway.step``, answers read from each ``GatewayHandle``.

One thread: the client submits what is due, drives one wave, then polls
the handles. Latency runs from when a request was due (open loop: its
arrival time; closed loop: the moment a slot of the loop freed), so a wave
that holds up submission is charged to the requests behind it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

from repro.gateway.gateway import GatewayOverloadError

from bench.arrivals import Mix, Request

DRAIN_S = 60.0          # how long past the close an admitted request may take
WARM_WAVES = 12         # fewest waves a closed loop runs before its window


@dataclasses.dataclass
class Rec:
    req: Request
    t_due: float
    t_submit: Optional[float] = None
    t_done: Optional[float] = None
    handle: object = None
    refused: bool = False
    failed: bool = False
    answer: Optional[dict] = None

    @property
    def settled(self) -> bool:
        return self.answer is not None or self.refused or self.failed


def answer_of(req: Request, res) -> dict:
    return {"source": req.source, "k": req.k,
            "epsilon": req.epsilon, "delta": req.delta,
            "vertices": res.vertices, "scores": res.scores,
            "num_walks": int(res.num_walks), "num_steps": int(res.num_steps),
            "epsilon_bound": float(res.epsilon_bound),
            "degraded": bool(res.degraded)}


class Client:
    def __init__(self, gw, spans):
        self.gw = gw
        self.spans = spans
        self.inflight: List[Rec] = []
        self.waves: List[tuple] = []  # host (t0, t1) of steps that ran a wave

    def submit(self, rec: Rec) -> None:
        r = rec.req
        with self.spans.span("bench.submit"):
            rec.t_submit = time.perf_counter()
            try:
                h = self.gw.ppr(r.source, k=r.k, epsilon=r.epsilon,
                                delta=r.delta)
            except GatewayOverloadError:
                rec.refused = True
                return
        rec.handle = h
        self.inflight.append(rec)

    def step(self) -> bool:
        t0 = time.perf_counter()
        with self.spans.span("bench.step"):
            progressed = self.gw.step()
        if progressed:
            self.waves.append((t0, time.perf_counter()))
        return progressed

    def poll(self) -> None:
        with self.spans.span("bench.poll"):
            now = time.perf_counter()
            still = []
            for rec in self.inflight:
                if not rec.handle.done():
                    still.append(rec)
                    continue
                rec.t_done = now
                try:
                    rec.answer = answer_of(rec.req,
                                           rec.handle.result(max_waves=0))
                except Exception as e:            # noqa: BLE001 - a failed
                    rec.failed = True             # request is counted,
                    print(f"request failed: {type(e).__name__}: {e}",
                          flush=True)             # not fatal to the run
            self.inflight = still

    def step_and_poll(self) -> int:
        """One wave, then the handles; returns how many were answered."""
        self.step()
        before = len(self.inflight)
        self.poll()
        return before - len(self.inflight)

    def drain(self, deadline: float) -> None:
        while self.inflight and time.perf_counter() < deadline:
            self.step()
            self.poll()


def closed_loop(gw, mix: Mix, window_s: float, spans, counters, tick):
    """``outstanding`` requests kept in flight, warm-up and window alike,
    drawn from the mix's endless supply (``Mix.stream``). Returns the
    records of the window (those sent in it, and those sent before it that
    it answered), its ``(t0, t_end)`` (``window_s`` or a little more) and
    the client."""
    outstanding = int(mix.params["outstanding"])
    stream = mix.stream()
    client = Client(gw, spans)

    def refill(keep: List[Rec]):
        for _ in range(outstanding - len(client.inflight)):
            rec = Rec(next(stream), t_due=time.perf_counter())
            client.submit(rec)
            keep.append(rec)

    # warm-up: the loop's own traffic for warmup_s and at least WARM_WAVES
    # waves, two cycles of its steady pattern (all slots start together,
    # run full waves, and end in one partial wave), so every wave bucket
    # the window uses is compiled before it
    warm: List[Rec] = []
    t_warm = time.perf_counter() + float(mix.params["warmup_s"])
    while time.perf_counter() < t_warm or len(client.waves) < WARM_WAVES:
        refill(warm)
        client.step()
        client.poll()
    # The window opens and closes on an answer: the loop's answers come
    # in batches (all slots start and finish together), so a window of
    # whole batches counts all the work of all its time, where a fixed
    # window would count one batch more or less by its phase.
    while True:
        refill(warm)
        if client.step_and_poll():
            break
    recs: List[Rec] = []
    counters.start()
    t0 = time.perf_counter()
    due = t0 + window_s
    while True:
        refill(recs)
        answered = client.step_and_poll()
        now = time.perf_counter()
        tick(now)
        if now >= due and answered:
            break
    t_end = now
    counters.stop()
    client.drain(t_end + DRAIN_S)
    carried = [r for r in warm if r.t_done is not None
               and t0 <= r.t_done <= t_end]
    return carried + recs, (t0, t_end), client


def open_loop(gw, mix: Mix, window_s: float, spans, counters, tick):
    """Arrivals at the mix's fixed rate; the warm-up runs the same process
    on a stream of its own, and the result cache is emptied before the
    window. Returns the records due in the window, its ``(t0, t_end)`` and
    the client."""
    client = Client(gw, spans)
    _offered(client, mix, float(mix.params["warmup_s"]), True, None,
             lambda now: None)
    if gw.cache is not None:
        gw.cache.clear()
    recs, window = _offered(client, mix, window_s, False, counters, tick)
    return recs, window, client


def _offered(client: Client, mix: Mix, duration_s: float, warmup: bool,
             counters, tick):
    offsets = mix.arrival_times(duration_s, warmup=warmup)
    reqs = mix.requests(len(offsets), warmup=warmup)
    if counters is not None:
        counters.start()
    t0 = time.perf_counter()
    t_end = t0 + duration_s
    recs = [Rec(r, t_due=t0 + o) for r, o in zip(reqs, offsets)]
    i = 0
    while time.perf_counter() < t_end:
        now = time.perf_counter()
        while i < len(recs) and recs[i].t_due <= now:
            client.submit(recs[i])
            i += 1
        if client.inflight:
            client.step()
            client.poll()
        else:
            nxt = recs[i].t_due if i < len(recs) else t_end
            with client.spans.span("bench.wait"):
                time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))
        tick(time.perf_counter())
    for rec in recs[i:]:                  # due before the close, sent late
        client.submit(rec)
    if counters is not None:
        counters.stop()
    client.drain(t_end + DRAIN_S)
    return recs, (t0, t_end)
