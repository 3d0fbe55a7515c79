"""Share of the window's served requests that found their session's KV on
the replica they were routed to (the serve loop's ``prefix_hits`` over
``served``), in %."""


def read(run):
    served = run.stats.get("served", 0.0)
    return 100.0 * run.stats["prefix_hits"] / served if served else None
