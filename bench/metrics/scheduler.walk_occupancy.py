"""Share of the window's wave walk slots that carried a tallied walk:
``walks_executed / (waves_run * max_walks)``, scheduler counters
differenced over the window."""
from bench import stats


def read(run):
    c = run.get("counters") or {}
    return stats.occupancy(c.get("walks_executed", 0), c.get("waves_run", 0),
                           run.get("max_walks", 1))
