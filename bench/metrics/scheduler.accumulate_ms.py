"""Host time per wave of adding each query's tally row into its slot's
accumulator (span ``repro.scheduler.accumulate``), in milliseconds."""
from bench import stats


def read(run):
    return stats.span_ms(run, "repro.scheduler.accumulate")
