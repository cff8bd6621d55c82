"""Named host spans over the serving and index-build stages.

A process-wide switch, off by default (:func:`enable`). Off, :func:`span`
returns one shared no-op context manager: one global check, no allocation,
no clock read. On, each span

* enters a ``jax.profiler.TraceAnnotation`` of its name, so a profiler
  trace shows the stage on the same clock as the device's operations, and
* adds to in-memory totals per name: calls ``n``, seconds ``s`` and
  ``self_s`` (the seconds not covered by spans nested inside it on the
  same thread).

:func:`totals` reads them (``Gateway.stats()["spans"]``, and so the
``/metrics`` endpoint, serves the same dict); :func:`reset` clears them.
Every span name starts with ``repro.``.
"""
from __future__ import annotations

import threading
import time
from typing import Dict

import jax

_on = False
_lock = threading.Lock()
_totals: Dict[str, list] = {}          # name -> [n, s, self_s]
_local = threading.local()             # .stack: the open spans


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


class _Span:
    __slots__ = ("name", "annotation", "t0", "nested")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.annotation = jax.profiler.TraceAnnotation(self.name)
        self.annotation.__enter__()
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self)
        self.nested = 0.0
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        stack = _local.stack
        stack.pop()
        if stack:
            stack[-1].nested += dt
        with _lock:
            t = _totals.setdefault(self.name, [0, 0.0, 0.0])
            t[0] += 1
            t[1] += dt
            t[2] += dt - self.nested
        self.annotation.__exit__(*exc)
        return False


def enable(on: bool) -> None:
    """Turns the spans on or off for the whole process."""
    global _on
    _on = bool(on)


def enabled() -> bool:
    return _on


def span(name: str):
    """A context manager timing one stage called ``name``."""
    if not _on:
        return _NOOP
    return _Span(name)


def totals() -> Dict[str, Dict[str, float]]:
    """``{name: {"n", "s", "self_s"}}`` summed since the last reset."""
    with _lock:
        return {name: {"n": n, "s": s, "self_s": self_s}
                for name, (n, s, self_s) in _totals.items()}


def reset() -> None:
    with _lock:
        _totals.clear()
