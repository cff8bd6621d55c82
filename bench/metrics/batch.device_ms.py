"""Device busy time per batch estimate in the traced window, in
milliseconds."""


def read(run):
    red, counts = run.get("trace"), run.get("trace_counts") or {}
    if red is None or not counts.get("estimates"):
        return None
    return 1e3 * red["busy_s"] / counts["estimates"]
