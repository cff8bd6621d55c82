"""Seconds of ``Gateway.open`` in set-up: the walk-index build on the
device (the harness's span around the call, ended by
``block_until_ready`` on the slab)."""


def read(run):
    return run.get("index_build_s")
