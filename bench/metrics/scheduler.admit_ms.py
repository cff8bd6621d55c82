"""Host time per wave of moving queued requests into query slots, each
new slot's accumulator made (span ``repro.scheduler.admit``), in
milliseconds."""
from bench import stats


def read(run):
    return stats.span_ms(run, "repro.scheduler.admit")
