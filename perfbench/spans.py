"""The program's own spans in a reduced trace: the serving path's phases
(``serve.*``) and the sharded fan-out (``shard.*``) that
``repro.serving.stats.span`` writes into the profiler's trace, on the
clock of the device planes.  They are host events of ``tracing.Trace``,
``(start_s, end_s, name)``; a parent is found by the interval that holds
a child's start."""

from __future__ import annotations

import bisect
from typing import List, Optional, Tuple

from perfbench import tracing

Interval = Tuple[float, float]


def intervals(trace: tracing.Trace, name: str) -> List[Interval]:
    """[(start, end)] of the host spans named ``name``, by start."""
    return [(s, e) for s, e, n in trace.host if n == name]


def within(children: List[Interval],
           parents: List[Interval]) -> List[List[Interval]]:
    """For each parent, the children that start inside it."""
    starts = [s for s, _ in children]
    out = []
    for lo, hi in parents:
        a, b = bisect.bisect_left(starts, lo), bisect.bisect_right(starts, hi)
        out.append([c for c in children[a:b] if c[1] <= hi])
    return out


def overlap_s(a: List[Interval], b: List[Interval]) -> float:
    """Seconds that the unions of ``a`` and ``b`` share."""
    ua, ub = tracing.union(a), tracing.union(b)
    total, j = 0.0, 0
    for lo, hi in ua:
        while j < len(ub) and ub[j][1] <= lo:
            j += 1
        k = j
        while k < len(ub) and ub[k][0] < hi:
            total += min(hi, ub[k][1]) - max(lo, ub[k][0])
            k += 1
    return total


def idle_while_s(trace: tracing.Trace, name: str,
                 device: Optional[str] = None) -> Optional[float]:
    """Seconds in which ``device`` (the most idle one by default) ran no
    operation while a span named ``name`` was open; None without spans
    or devices."""
    open_ = intervals(trace, name)
    if not open_ or not trace.ops:
        return None
    if device is None:
        busy = tracing.busy_s(trace)
        device = min(busy, key=busy.get)
    ops = [(a, b) for a, b, _, _ in trace.ops[device]]
    return sum(e - s for s, e in tracing.union(open_)) - overlap_s(open_, ops)
