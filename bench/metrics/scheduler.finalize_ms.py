"""Host time per answer of finishing a query: top-k over its
accumulator, the result, and the release of the slot's accumulator (span
``repro.scheduler.finalize`` over its calls), in milliseconds."""
from bench import stats


def read(run):
    return stats.span_ms(run, "repro.scheduler.finalize",
                         per="repro.scheduler.finalize.n")
