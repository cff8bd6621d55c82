"""Mean host time of a ``Gateway.step()`` that ran a wave in the window
(the harness's own span around each call), in milliseconds."""


def read(run):
    if not run.get("waves_host"):
        return None
    return 1e3 * run["wave_s"] / run["waves_host"]
