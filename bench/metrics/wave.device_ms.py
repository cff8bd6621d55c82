"""Device busy time per wave in the traced window: the union of the
device's op intervals over the waves the scheduler ran while it was
traced, in milliseconds."""


def read(run):
    red, counts = run.get("trace"), run.get("trace_counts") or {}
    if red is None or not counts.get("waves_run"):
        return None
    return 1e3 * red["busy_s"] / counts["waves_run"]
