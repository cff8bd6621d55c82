"""Host time per wave of the operand copies to the device and the wave
program's call until it returns (span ``repro.wave.launch``, every
attempt), in milliseconds."""
from bench import stats


def read(run):
    return stats.span_ms(run, "repro.wave.launch")
