"""Host time per wave of the tally's copy off the device and its
reordering into vertex order (span ``repro.wave.d2h``), in milliseconds."""
from bench import stats


def read(run):
    return stats.span_ms(run, "repro.wave.d2h")
