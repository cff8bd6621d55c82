"""Host time of the program's whole ``QueryScheduler.step_wave`` per
wave in the window (span ``repro.scheduler.step_wave``), in milliseconds.
``scheduler.wave_ms`` less it is what the gateway, pool and service add."""
from bench import stats


def read(run):
    return stats.span_ms(run, "repro.scheduler.step_wave")
