"""Host spans, compile counting, and the reduction of a profiler trace to
device busy time, idle share, top device operations and idle gaps.

The harness wraps each of its own calls into the program in a span
(``bench.submit``, ``bench.step``, ``bench.poll``, ``bench.wait``,
``bench.estimate``): a ``jax.profiler.TraceAnnotation``, so it lands in the
trace on the device's clock, and a host-clock interval kept in memory.
The traced part of a run sits inside a ``bench.trace_window`` span; the
reduction measures the device against that span. The program's own stage
spans (``repro.*``, on in a traced run) are kept too: they split the
device's idle time by what the host was doing inside the harness's calls.
"""
from __future__ import annotations

import collections
import contextlib
import glob
import shutil
from pathlib import Path
from typing import Iterable, List, Optional, Sequence, Tuple

import jax

# Device planes and the line of each that holds one event per XLA op, and
# the line that holds one event per executed program.
DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
PROGRAM_SPAN_PREFIX = "repro."
# The program whose runs a counter of the harness counts, by the name the
# trace gives its executable.
PROGRAMS = {"waves_run": "jit_wave", "estimates": "jit__walks_program"}
WINDOW_SPAN = "bench.trace_window"


class Spans:
    """The harness's spans: with ``annotate``, ``jax.profiler``
    annotations that land in the trace on the device's clock."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str):
        if self.annotate:
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield


class CompileCounter:
    """Counts traces, backend compiles and persistent-cache hits, through
    ``jax.monitoring``."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.traces = self.compiles = self.cache_hits = 0

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_):
        if event == self.TRACE:
            self.traces += 1
        elif event == self.COMPILE:
            self.compiles += 1

    def _event(self, event: str, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> Tuple[int, int, int]:
        return self.traces, self.compiles, self.cache_hits


# --- capture ---------------------------------------------------------------

class Profile:
    """A profiler window written under ``directory`` (emptied first)."""

    def __init__(self, directory: Path):
        self.directory = Path(directory)

    def __enter__(self):
        shutil.rmtree(self.directory, ignore_errors=True)
        self.directory.mkdir(parents=True)
        jax.profiler.start_trace(str(self.directory))
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()

    def xplane(self) -> Path:
        found = sorted(glob.glob(str(self.directory / "**" /
                                     "*.xplane.pb"), recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.directory}")
        return Path(found[-1])


Event = Tuple[str, str, str, float, float]   # plane, line, name, t0, dur (ns)


def load_events(xplane: Path) -> List[Event]:
    """The events the reduction reads: the device planes' op and module
    lines, and the host spans of the harness and of the program."""
    pd = jax.profiler.ProfileData.from_file(str(xplane))
    out: List[Event] = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                keep = lambda ev: True
            elif not device:
                keep = lambda ev: ev.name.startswith(
                    (SPAN_PREFIX, PROGRAM_SPAN_PREFIX))
            else:
                continue
            for ev in line.events:
                if keep(ev):
                    out.append((plane.name, line.name, ev.name,
                                float(ev.start_ns), float(ev.duration_ns)))
    return out


# --- reduction -------------------------------------------------------------

def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def reduce(events: Sequence[Event], top: int = 10) -> Optional[dict]:
    """Device busy and idle time over the ``bench.trace_window`` span, the
    device operations that took most time, the longest idle gaps named by
    the harness span (``bench.*``) that covers most of each, how often each
    program ran, the idle time split by the innermost host span at each
    instant (``idle_by_span``: a ``repro.*`` span, else a ``bench.*`` one,
    else ``host``), and how many host spans of each name started in the
    window (``span_counts``). Times in seconds, averaged over the device
    planes. None where the trace holds no window or no device."""
    windows = [(t, t + d) for p, l, name, t, d in events
               if name == WINDOW_SPAN]
    devices = sorted({p for p, l, *_ in events
                      if p.startswith(DEVICE_PLANE_PREFIX)})
    if not windows or not devices:
        return None
    w0, w1 = windows[0]
    host = [(name, t, t + d) for p, l, name, t, d in events
            if not p.startswith(DEVICE_PLANE_PREFIX) and name != WINDOW_SPAN]
    spans = [s for s in host if s[0].startswith(SPAN_PREFIX)]
    inside = [s for s in host if s[1] < w1 and s[2] > w0]
    busy_total, ops, gaps = 0.0, collections.Counter(), []
    modules, idle_by = collections.Counter(), collections.Counter()
    for dev in devices:
        ivs = [(max(t, w0), min(t + d, w1)) for p, l, name, t, d in events
               if p == dev and l == OPS_LINE and t < w1 and t + d > w0]
        for p, l, name, t, d in events:
            if p != dev or not (w0 <= t < w1):
                continue
            if l == OPS_LINE:
                ops[op_name(name)] += d
            elif l == MODULES_LINE:
                modules[name] += 1
        merged = _union(ivs)
        busy_total += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for a, b in idle:
            gaps.append((_cover(spans, a, b), (b - a) / 1e9))
        idle_by.update(_idle_by_span(inside, idle))
    nd = len(devices)
    gaps.sort(key=lambda g: -g[1])
    return {
        "busy_s": busy_total / nd / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": [[name, ns / nd / 1e9]
                       for name, ns in ops.most_common(top)],
        "idle_gaps": [[name, s] for name, s in gaps[:top]],
        "modules": {name: c / nd for name, c in modules.items()},
        "idle_by_span": {name: ns / nd / 1e9
                         for name, ns in idle_by.most_common()},
        "span_counts": dict(collections.Counter(
            name for name, t0, _ in host if w0 <= t0 < w1)),
    }


def op_name(hlo: str) -> str:
    """An op's HLO instruction name (``%fusion.84 = s32[...] ...`` gives
    ``fusion.84``), which the trace gives with its whole text."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def program_of(module: str) -> Optional[str]:
    """The counter whose unit of work the traced program ``module`` is."""
    for counter, stem in PROGRAMS.items():
        if module == stem or module.startswith(stem + "("):
            return counter
    return None


def _cover(spans, a: float, b: float) -> str:
    """The host span overlapping most of [a, b], or ``host``."""
    best, name = 0.0, "host"
    for sname, s0, s1 in spans:
        o = min(b, s1) - max(a, s0)
        if o > best:
            best, name = o, sname
    return name


def _idle_by_span(spans, idle) -> collections.Counter:
    """Nanoseconds of the sorted, disjoint ``idle`` intervals under each
    host span, each instant given to the innermost span open then."""
    out = collections.Counter()
    cuts = sorted({x for _, a, b in spans for x in (a, b)}
                  | {x for iv in idle for x in iv})
    opening = sorted(spans, key=lambda s: s[1])
    active: List[tuple] = []
    i = j = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(opening) and opening[i][1] <= a:
            active.append(opening[i])
            i += 1
        active = [s for s in active if s[2] > a]
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        if j < len(idle) and idle[j][0] <= a:
            out[_innermost(active)] += b - a
    return out


def _innermost(active) -> str:
    """The span opened last among the program's open spans, else among the
    harness's, else ``host``."""
    for prefix in (PROGRAM_SPAN_PREFIX, SPAN_PREFIX):
        mine = [s for s in active if s[0].startswith(prefix)]
        if mine:
            return max(mine, key=lambda s: (s[1], -s[2]))[0]
    return "host"


def save_events(events: Sequence[Event], path: Path) -> None:
    import json
    path.write_text(json.dumps([list(e) for e in events]))


def read_events(path: Path) -> List[Event]:
    import json
    return [tuple(e) for e in json.loads(Path(path).read_text())]
