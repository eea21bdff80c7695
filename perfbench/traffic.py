"""The one traffic generator: a mix is a JSON file of parameters under
``traffic/``, and this module turns it, the cell's rate and a seed into
an open-loop schedule of queries.

Parameters of a mix (``traffic/<name>.json``):

* ``rate_of_knee``: offered rate as a share of the configuration's
  ``knee_qps`` (the highest rate it was measured to sustain);
* ``arrivals``: ``"poisson"`` — exponential gaps, as from independent
  users;
* ``burst`` (optional): ``{"factor": f, "period_s": p, "duty": d}`` —
  the rate is ``f`` times the mean for the first ``d`` of every period
  and lower for the rest, so the mean is unchanged;
* ``queries``: ``{"kind": "distinct"}`` — every request a new query, or
  ``{"kind": "zipf", "pool": P, "exponent": s}`` — requests repeat a
  pool of ``P`` queries with Zipf popularity;
* ``check_sample``: how many answered requests the reference checks;
* ``drain_s``: how long after the window an answer is still waited for.

Every seed gets the same work: the same number of requests, the same
multiset of gaps (the exponential's quantiles) and of query popularity
ranks, in an order and with query vectors drawn from the seed.  So two
seeds differ in what they send and in what order, not in how much.

The queries themselves are the deployment's: its builder's
``make_queries`` draws a pool (any pytree whose leaves lead with the
pool's axis), and ``take`` indexes it.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, List, Sequence

import jax
import numpy as np


@dataclasses.dataclass(frozen=True)
class Schedule:
    due_s: np.ndarray          # [M] seconds from the window's start
    query: np.ndarray          # [M] index into the query pool
    rate_qps: float


def _quantiles(m: int) -> np.ndarray:
    return (np.arange(m) + 0.5) / m


def _warp(unit_t: np.ndarray, burst: dict) -> np.ndarray:
    """Map arrival times of a constant rate onto a rate that is
    ``factor`` times the mean for the first ``duty`` of each period:
    the inverse of the cumulative intensity, which keeps the count."""
    f, p, d = burst["factor"], burst["period_s"], burst["duty"]
    if not (f >= 1 and 0 < d < 1 and f * d <= 1):
        raise ValueError(f"burst {burst}: need factor >= 1, 0 < duty < 1 "
                         f"and factor * duty <= 1")
    low = (1 - f * d) / (1 - d)                 # off-phase rate share
    whole, frac = np.divmod(unit_t, p)           # unit time = mean-rate time
    on = frac < f * d * p
    inside = np.where(on, frac / f, d * p + (frac - f * d * p)
                      / max(low, 1e-12))
    return whole * p + inside


def schedule(params: dict, rate_qps: float, seconds: float,
             rng: np.random.Generator) -> Schedule:
    if params.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrivals {params['arrivals']!r}")
    m = max(1, int(round(rate_qps * seconds)))
    gaps = -np.log1p(-_quantiles(m)) / rate_qps
    gaps = rng.permutation(gaps)
    t = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    t *= seconds / gaps.sum()                    # exactly m in the window
    if params.get("burst"):
        t = _warp(t, params["burst"])
    q = params.get("queries", {"kind": "distinct"})
    if q["kind"] == "distinct":
        query = np.arange(m)
    elif q["kind"] == "zipf":
        pool, s = int(q["pool"]), float(q["exponent"])
        cdf = np.cumsum(1.0 / np.arange(1, pool + 1) ** s)
        ranks = np.searchsorted(cdf / cdf[-1], _quantiles(m))
        query = rng.permutation(pool)[rng.permutation(ranks)]
    else:
        raise ValueError(f"unknown query kind {q['kind']!r}")
    return Schedule(due_s=t, query=query, rate_qps=m / seconds)


def pool_size(sched: Schedule) -> int:
    return int(sched.query.max()) + 1


def make_queries(count: int, dim: int, rng: np.random.Generator) -> np.ndarray:
    """``count`` dense query vectors, f32 on the host as a client sends
    them: entries N(0, 1/dim), so a query has about unit norm."""
    return (rng.standard_normal((count, dim), dtype=np.float32)
            / np.float32(np.sqrt(dim)))


def take(pool, idx):
    """``x[idx]`` of every leaf of a query pool: one query for an integer
    ``idx``, a smaller pool for an index array."""
    return jax.tree.map(lambda x: x[idx], pool)


class OpenLoopClient:
    """One thread that submits each request at its due time, whether or
    not earlier ones have come back, and records when each future
    resolves (the callback runs where the result is set).

    ``requests`` holds each scheduled request's query, split from the
    pool before the window opens, so that sending one costs no more than
    a list's lookup whatever the pool's structure."""

    def __init__(self, submit: Callable, requests: Sequence,
                 sched: Schedule):
        self.submit, self.requests, self.sched = submit, requests, sched
        m = sched.due_s.size
        self.sent = np.full(m, np.nan)
        self.done = np.full(m, np.nan)
        self.futures: List = [None] * m
        self.errors: List = []
        self.t0 = 0.0
        self._all = threading.Event()
        self._left = m
        self._lock = threading.Lock()

    def _resolved(self, i: int, fut):
        t = time.perf_counter() - self.t0
        if fut.exception() is None:
            self.done[i] = t
        else:
            self.errors.append(fut.exception())
        with self._lock:
            self._left -= 1
            if self._left == 0:
                self._all.set()

    def run(self, window_s: float) -> float:
        """Send the whole schedule; returns when the window has closed."""
        self.t0 = t0 = time.perf_counter()
        for i, (due, query) in enumerate(zip(self.sched.due_s,
                                             self.requests)):
            wait = t0 + due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.sent[i] = time.perf_counter() - t0
            fut = self.submit(query)
            self.futures[i] = fut
            fut.add_done_callback(lambda f, i=i: self._resolved(i, f))
        rest = t0 + window_s - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
        return time.perf_counter() - t0

    def wait(self, timeout_s: float) -> bool:
        """Wait for the stragglers; False if some never resolved."""
        return self._all.wait(timeout_s)
