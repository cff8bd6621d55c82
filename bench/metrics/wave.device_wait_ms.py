"""Host time per wave spent in ``block_until_ready`` on the wave
program's output (span ``repro.wave.device_wait``), in milliseconds."""
from bench import stats


def read(run):
    return stats.span_ms(run, "repro.wave.device_wait")
