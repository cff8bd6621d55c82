"""Share of the window's requests the gateway answered without a new
query: result-cache hits plus joins onto an in-flight duplicate
(``Gateway.stats()`` counters, differenced over the window)."""


def read(run):
    c = run.get("counters") or {}
    if not c.get("requests"):
        return None
    return (c["cache_hits"] + c["joins"]) / c["requests"]
