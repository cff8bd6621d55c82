"""Host time per wave of the walk allocation, the numpy operand build
and the wave's key split (span ``repro.wave.prepare``), in milliseconds."""
from bench import stats


def read(run):
    return stats.span_ms(run, "repro.wave.prepare")
