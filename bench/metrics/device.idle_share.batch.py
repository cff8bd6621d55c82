"""Share of the traced window in which no operation ran on the device:
``1 - busy / window``."""


def read(run):
    red = run.get("trace")
    if red is None or red["window_s"] <= 0:
        return None
    return 1.0 - red["busy_s"] / red["window_s"]
