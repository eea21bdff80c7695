"""End-to-end arithmetic over one run's request log.

Every request has the time it was due (its place in the open-loop
schedule) and the time its future resolved, both in seconds from the
start of the window.  Latency runs from the due time, so a stall makes
every request due during it wait, and a late generator cannot hide one.
"""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (linear interpolation between order
    statistics, numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def summarize(due_s, done_s, sent_s, window_s: float) -> dict:
    """``due_s``/``done_s``/``sent_s``: per request due in the window; a
    ``done_s`` of NaN is a request that never resolved.

    * latency percentiles over every request that resolved, late ones
      included (their wait counts);
    * ``qps``: requests resolved inside the window over its length;
    * ``late_ms``: how late the generator sent (p95 and max)."""
    due = np.asarray(due_s, np.float64)
    done = np.asarray(done_s, np.float64)
    sent = np.asarray(sent_s, np.float64)
    ok = np.isfinite(done)
    lat_ms = 1e3 * (done[ok] - due[ok])
    late_ms = 1e3 * (sent - due)
    return {
        "attempted": int(due.size),
        "resolved": int(ok.sum()),
        "latency_p50_ms": percentile(lat_ms, 50) if lat_ms.size else None,
        "latency_p95_ms": percentile(lat_ms, 95) if lat_ms.size else None,
        "latency_p99_ms": percentile(lat_ms, 99) if lat_ms.size else None,
        "qps": float(np.sum(done[ok] < window_s)) / window_s,
        "late_p95_ms": percentile(late_ms, 95) if late_ms.size else 0.0,
        "late_max_ms": float(late_ms.max()) if late_ms.size else 0.0,
    }
