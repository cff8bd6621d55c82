"""Seconds of the walk-index build's walk programs, dispatched and made
ready, in set-up (span ``repro.index.walk`` up to the end of
``Gateway.open``)."""
from bench import stats


def read(run):
    return stats.setup_span_s(run, "repro.index.walk")
