"""The program's own spans in a profiler trace: ``serve.*``, ``router.*``
and ``payload.*``, which the server opens with ``TraceAnnotation`` on the
thread that calls ``submit()`` and ``step()`` (the host line that
``trace_reduce.load`` keeps).  Spans on one thread nest, so a span's self
time is its duration less the durations of the program spans directly
inside it.  A span counts where it ends inside the traced window
(``trace_reduce.window``); per request means over the ``serve.request``
spans that end there."""

from __future__ import annotations

from typing import List, Optional, Tuple

import trace_reduce

PREFIXES = ("serve.", "router.", "payload.")


def program_spans(trace: trace_reduce.Trace
                  ) -> List[Tuple[str, float, float, float]]:
    """``(name, start, end, self seconds)`` of each program span that ends
    inside the traced window (none where the trace holds no harness
    span, and so no window)."""
    if not any(s[0].startswith(trace_reduce.SPAN_PREFIX) for s in trace.host):
        return []
    found = sorted((s for s in trace.host if s[0].startswith(PREFIXES)),
                   key=lambda s: (s[1], -s[2]))
    inner = [0.0] * len(found)
    open_: List[int] = []
    for i, (_, a, b) in enumerate(found):
        while open_ and found[open_[-1]][2] <= a:
            open_.pop()
        if open_:
            inner[open_[-1]] += b - a
        open_.append(i)
    lo, hi = trace_reduce.window(trace)
    return [(n, a, b, b - a - c) for (n, a, b), c in zip(found, inner)
            if lo < b <= hi]


def per_request_ms(trace: Optional[trace_reduce.Trace], prefix: str,
                   self_time: bool) -> Optional[float]:
    """Seconds of the spans whose name starts with ``prefix`` (their self
    time, or their whole duration), in ms per request; None where the trace
    holds no request."""
    if trace is None:
        return None
    spans = program_spans(trace)
    requests = sum(1 for s in spans if s[0] == "serve.request")
    if not requests:
        return None
    secs = sum(own if self_time else b - a
               for n, a, b, own in spans if n.startswith(prefix))
    return secs / requests * 1e3


def mean_ms(trace: Optional[trace_reduce.Trace], name: str
            ) -> Optional[float]:
    """Mean duration of the spans named ``name``, in ms; None where there
    are none."""
    if trace is None:
        return None
    got = [b - a for n, a, b, _ in program_spans(trace) if n == name]
    return sum(got) / len(got) * 1e3 if got else None
