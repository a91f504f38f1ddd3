"""Mean documents per fold-in batch the micro-batcher dispatched in the
window, from the query server's own counters."""


def read(run):
    if not run.get("batches"):
        return None
    return run["docs"] / run["batches"]
