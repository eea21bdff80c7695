"""The ``batch.assemble_ms`` reader: the ``serve.assemble`` phase's
exact total over the batches, and nothing on a program without
phases."""

import pytest

from perfbench import harness


def _layers(phases=None, n_batches=4):
    class Stats:
        pass
    Stats.n_batches = n_batches
    if phases is not None:
        Stats.phase_total_s = phases
    return harness.Layers(stats=Stats(), batch_size=16, trace=None,
                          scan_work=None, peaks=None)


def test_assemble_ms_from_phase_totals():
    phases = {"gather": 0.04, "assemble": 0.006, "dispatch": 0.012,
              "sync": 0.6, "copy_back": 0.004, "fanout": 0.002}
    assert harness.reader("batch.assemble_ms").read(_layers(phases)) == \
        pytest.approx(1.5)


@pytest.mark.parametrize("phases, n_batches", [(None, 4), ({}, 4),
                                               ({"assemble": 0.1}, 0)],
                         ids=["no_phases", "empty", "no_batches"])
def test_assemble_ms_reads_nothing_without_phases(phases, n_batches):
    assert harness.reader("batch.assemble_ms").read(
        _layers(phases, n_batches)) is None


def test_assemble_ms_on_a_served_endpoint():
    """On a real ``EndpointSnapshot``: the mean of the phase the batcher
    records, which host-stacked batches keep small."""
    import numpy as np
    from repro.serving import RetrievalService

    svc = RetrievalService(cache_size=0)
    svc.register_runner("tiny", lambda q, _t: q * 2.0,
                        np.zeros(8, np.float32), batch_size=4,
                        max_wait_s=0.005)
    with svc:
        svc.retrieve([np.full(8, i, np.float32) for i in range(10)],
                     endpoint="tiny")
        ep = svc.snapshot().endpoints["tiny"]
    value = harness.reader("batch.assemble_ms").read(_layers(
        ep.phase_total_s, ep.n_batches))
    assert value == pytest.approx(1e3 * ep.phase_total_s["assemble"]
                                  / ep.n_batches)
    assert value > 0 and ep.host_assembled_total == ep.n_batches
