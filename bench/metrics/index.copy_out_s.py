"""Seconds of the walk-index build's copies: endpoints and masks off the
device, concatenated and cast, endpoints back (span
``repro.index.copy_out`` up to the end of ``Gateway.open``)."""
from bench import stats


def read(run):
    return stats.setup_span_s(run, "repro.index.copy_out")
