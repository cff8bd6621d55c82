"""Tally bytes a wave hands the host (``SchedulerStats.tally_bytes_out``
differenced over the window, over ``waves_run``), in 1e6 B. Read in the
traced run, with the stage spans."""


def read(run):
    c = run.get("counters") or {}
    if (run.get("trace") is None or "tally_bytes_out" not in c
            or not c.get("waves_run")):
        return None
    return c["tally_bytes_out"] / c["waves_run"] / 1e6
