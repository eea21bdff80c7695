"""Percentile, rate and lateness arithmetic over a request log."""

import numpy as np
import pytest

from perfbench import latency


def test_known_percentiles_and_rate():
    # 101 requests due every 0.1 s over a 10.2 s window, latencies
    # 0, 1, ..., 100 ms: p50 50 ms, p95 95 ms
    due = np.arange(101) * 0.1
    done = due + np.arange(101) * 1e-3
    s = latency.summarize(due, done, due, 10.2)
    assert s["attempted"] == 101 and s["resolved"] == 101
    assert s["latency_p50_ms"] == pytest.approx(50.0)
    assert s["latency_p95_ms"] == pytest.approx(95.0)
    assert s["qps"] == pytest.approx(101 / 10.2)
    assert s["late_max_ms"] == 0.0


def test_a_stall_counts_from_the_due_time():
    # 100 requests due every 0.1 s in a 10 s window, each answered 10 ms
    # after it was due, except that the server stalls from 4.0 s to
    # 5.0 s: the 10 requests due then all resolve at 5.0 s.  Two never
    # resolve; the generator sent one of them 30 ms late.
    due = np.arange(100) * 0.1
    done = due + 0.010
    stalled = (due >= 4.0) & (due < 5.0)
    done[stalled] = 5.0
    done[[98, 99]] = np.nan
    sent = due.copy()
    sent[99] += 0.030
    s = latency.summarize(due, done, sent, 10.0)
    lat = np.sort(np.concatenate([np.full(88, 10.0),
                                  1e3 * (5.0 - due[stalled])]))
    assert s["resolved"] == 98
    assert s["latency_p50_ms"] == pytest.approx(10.0)
    assert s["latency_p95_ms"] == pytest.approx(np.percentile(lat, 95))
    assert s["latency_p95_ms"] > 500.0       # the stall shows in the tail
    assert s["qps"] == pytest.approx(98 / 10.0)
    assert s["late_max_ms"] == pytest.approx(30.0)


def test_answers_after_the_close_count_in_latency_not_in_rate():
    due = np.array([0.0, 0.5, 0.9])
    done = np.array([0.2, 0.7, 1.4])          # the last one after 1.0 s
    s = latency.summarize(due, done, due, 1.0)
    assert s["qps"] == pytest.approx(2.0)
    assert s["latency_p50_ms"] == pytest.approx(200.0)
    assert s["resolved"] == 3
