"""Share of the time with at least one request outstanding, inside the
traced window, in which no operation ran on the device, in %."""

import trace_reduce


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    out = run.trace_outstanding()
    total = trace_reduce.length(out)
    if total <= 0:
        return None
    busy = trace_reduce.length(
        trace_reduce.intersect(trace_reduce.busy(run.trace), out))
    return 100.0 * (1.0 - busy / total)
