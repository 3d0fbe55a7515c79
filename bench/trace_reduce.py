"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time over a set of intervals, device time and call
count per program, the device operations that took most time, and the idle
gaps on the device named by what the host was doing in them.

Device events are those on planes named ``/device:TPU:<n>``: the ``XLA
Modules`` line holds one event per program run, ``XLA Ops`` one per
operation.  Host events are the thread line that holds the harness's own
spans (``bench.*``, written with ``jax.profiler.TraceAnnotation``), where
JAX also records each dispatch as ``PjitFunction(<function>)``.  All times
are seconds on the trace's clock; ``offset`` maps the harness's
``perf_counter`` onto it.
"""

from __future__ import annotations

import bisect
import gzip
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
SPAN_PREFIX = "bench."


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(merged: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merged)


def intersect(x: Sequence[Interval], y: Sequence[Interval]) -> List[Interval]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if a < b:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


@dataclass
class Trace:
    # device events: (name, start, end, device index)
    ops: List[Tuple[str, float, float, int]] = field(default_factory=list)
    modules: List[Tuple[str, float, float, int]] = field(default_factory=list)
    # host events on the harness's thread: (name, start, end)
    host: List[Tuple[str, float, float]] = field(default_factory=list)
    devices: int = 0


def load(path: str) -> Trace:
    """Read a trace file (``.xplane.pb``, or the same gzipped)."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    data = ProfileData.from_serialized_xspace(raw)
    tr = Trace()
    host_lines = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = tr.devices
            tr.devices += 1
            for line in plane.lines:
                dst = {"XLA Ops": tr.ops, "XLA Modules": tr.modules}.get(line.name)
                if dst is not None:
                    dst.extend((e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9, dev)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9)
                       for e in line.events]
                if any(n.startswith(SPAN_PREFIX) for n, _, _ in evs):
                    host_lines.append(evs)
    for evs in host_lines:
        tr.host.extend(evs)
    return tr


def offset(trace: Trace, spans: Sequence[Tuple[str, float, float]],
           tol: float = 2e-3) -> float:
    """Seconds to add to a harness ``perf_counter`` time to put it on the
    trace's clock.  The trace holds the harness spans of the traced part of
    the window only, while the harness keeps them all, so the two are
    aligned by time: each shift that lays the trace's first span on a
    harness span of its name is scored by how many of the trace's spans
    then start within ``tol`` of a harness span of theirs; the best shift
    is refined to the median start difference of the spans it matches."""
    ours = sorted((s for s in trace.host if s[0].startswith(SPAN_PREFIX)),
                  key=lambda s: s[1])
    mine: Dict[str, List[float]] = defaultdict(list)
    for n, a, _ in sorted(spans, key=lambda s: s[1]):
        mine[n].append(a)
    if not ours or not mine.get(ours[0][0]):
        raise ValueError("trace holds none of the harness's spans")

    def matched(shift: float, probe: Sequence) -> List[float]:
        diffs = []
        for n, a, _ in probe:
            starts = mine.get(n, [])
            i = bisect.bisect_left(starts, a - shift - tol)
            if i < len(starts) and abs(a - shift - starts[i]) <= tol:
                diffs.append(a - starts[i])
        return diffs

    probe = ours[:64]
    best = max((ours[0][1] - r for r in mine[ours[0][0]]),
               key=lambda shift: len(matched(shift, probe)))
    diffs = sorted(matched(best, ours))
    return diffs[len(diffs) // 2]


def busy(trace: Trace) -> List[Interval]:
    """Intervals in which some operation ran on some device."""
    return merge((a, b) for _, a, b, _ in trace.ops)


def busy_s(trace: Trace, within: Interval) -> float:
    """Seconds of ``within`` in which an operation ran, averaged over the
    devices traced."""
    if not trace.devices:
        return 0.0
    per = [length(intersect(merge((a, b) for _, a, b, d in trace.ops
                                  if d == dev), [within]))
           for dev in range(trace.devices)]
    return sum(per) / len(per)


def window(trace: Trace) -> Interval:
    """First to last harness span in the trace: the traced window."""
    ours = [s for s in trace.host if s[0].startswith(SPAN_PREFIX)]
    return min(a for _, a, _ in ours), max(b for _, _, b in ours)


def programs(trace: Trace) -> Dict[str, Tuple[float, int]]:
    """Program name -> (device seconds, runs)."""
    out: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    for n, a, b, _ in trace.modules:
        out[n][0] += b - a
        out[n][1] += 1
    return {n: (s, int(c)) for n, (s, c) in out.items()}


def dispatches(trace: Trace, function: str) -> int:
    """Host dispatches of the jitted ``function`` (JAX writes a nested pair
    of ``PjitFunction(<function>)`` events for each; the outer ones count)."""
    name = f"PjitFunction({function})"
    ends = sorted((a, b) for n, a, b in trace.host if n == name)
    count, end = 0, float("-inf")
    for a, b in ends:
        if a >= end:
            count += 1
            end = b
        else:
            end = max(end, b)
    return count


def program_time(trace: Trace, function: str) -> Optional[Tuple[float, int]]:
    """Device seconds and runs of the program compiled from ``function``,
    or None when it did not run.

    A program is found by its name where it carries ``function``.  A
    function jitted through ``functools.partial`` runs on the device as
    ``jit__unknown(<id>)``: then the program is the unnamed one whose run
    count is nearest the host's dispatches of ``function``."""
    progs = programs(trace)
    named = [v for n, v in progs.items() if function in n]
    if named:
        return sum(s for s, _ in named), sum(c for _, c in named)
    want = dispatches(trace, function)
    unnamed = [v for n, v in progs.items() if n.startswith("jit__unknown")]
    if not want or not unnamed:
        return None
    return min(unnamed, key=lambda v: abs(v[1] - want))


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    tot: Dict[str, float] = defaultdict(float)
    for name, a, b, _ in trace.ops:
        tot[name] += b - a
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def _innermost(host: Sequence[Tuple[str, float, float]], t: float) -> str:
    best = None
    for name, a, b in host:
        if a <= t < b and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0] if best else "no host span"


def idle_gaps(trace: Trace, within: Interval, n: int = 10) -> List[List]:
    """Device idle time inside ``within``, summed by the innermost host
    event at each gap's midpoint; the ``n`` largest."""
    lo, hi = within
    b = intersect(busy(trace), [(lo, hi)])
    edges = [lo] + [x for iv in b for x in iv] + [hi]
    tot: Dict[str, float] = defaultdict(float)
    for a, z in zip(edges[::2], edges[1::2]):
        if z > a:
            tot[_innermost(trace.host, (a + z) / 2)] += z - a
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
