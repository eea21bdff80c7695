"""The program's spans in a profiler trace and the per-layer metrics that
read them: a tiny endpoint served under ``jax.profiler.trace`` on the
CPU, on one device and as a ``ShardedPipeline`` over four virtual ones;
the readers' arithmetic on hand-made intervals; and a short trace of
``dense.steady`` recorded on a TPU v5e (``testdata/``)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from perfbench import harness, spans, tracing, work

RECORDED = Path(__file__).resolve().parent / "testdata" / \
    "dense_spans_small.xplane.pb.gz"
CHILDREN = ["serve.assemble", "serve.dispatch", "serve.sync",
            "serve.copy_back", "serve.fanout"]

SERVE = r"""
import json, sys, tempfile, time
from concurrent.futures import ThreadPoolExecutor
sys.path[:0] = [ROOT, ROOT + "/src"]
import jax, numpy as np
from perfbench import tracing
from repro.core.pipeline import BruteForceGenerator, RetrievalPipeline
from repro.core.spaces import DenseSpace
from repro.serving import RetrievalService, ShardedPipeline
from repro.serving.sharded import CorpusShard

space = DenseSpace("ip")
corpus = np.asarray(jax.random.normal(jax.random.key(1), (256, 16)))
queries = np.asarray(jax.random.normal(jax.random.key(0), (8, 16)))
if SHARDS == 1:
    pipe = RetrievalPipeline(BruteForceGenerator(space, corpus),
                             cand_qty=20, final_qty=10)
else:
    n = 256 // SHARDS
    shards = tuple(CorpusShard(jax.device_put(corpus[s * n:(s + 1) * n], d),
                               s * n, n)
                   for s, d in enumerate(jax.devices()[:SHARDS]))
    pipe = ShardedPipeline(
        shards=shards, generators=tuple(BruteForceGenerator(space, s.corpus)
                                        for s in shards),
        cand_qty=20, final_qty=10, executor=ThreadPoolExecutor(SHARDS))
svc = RetrievalService(cache_size=0)
svc.register_pipeline("tiny", pipe, queries[0], batch_size=4,
                      max_wait_s=0.005)
svc.retrieve(list(queries[:4]), endpoint="tiny")      # compiles, untraced
d = tempfile.mkdtemp()
with jax.profiler.trace(d, profiler_options=tracing.profile_options()):
    svc.retrieve(list(queries[4:7]), endpoint="tiny")
    time.sleep(0.1)            # the worker closes its spans after the results
svc.close()
t = tracing.read(tracing.find_xspace(d))
print(json.dumps([[s, e, n] for s, e, n in t.host
                  if n.startswith(("serve.", "shard."))]))
"""


def _served(shards: int):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={shards}"
    code = f"ROOT = {str(harness.ROOT)!r}\nSHARDS = {shards}\n" + SERVE
    proc = subprocess.run([sys.executable, "-c", code], cwd=harness.ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    host = [tuple(e) for e in json.loads(proc.stdout.splitlines()[-1])]
    return tracing.Trace(window_s=1.0, ops={}, host=sorted(host))


@pytest.fixture(scope="module", params=[1, 4], ids=["one", "sharded4"])
def served(request):
    return request.param, _served(request.param)


def test_gather_precedes_its_batch(served):
    _, trace = served
    gathers = spans.intervals(trace, "serve.gather")
    batches = spans.intervals(trace, "serve.batch")
    assert batches and len(gathers) == len(batches)
    for (g0, g1), (b0, _) in zip(gathers, batches):
        assert g0 <= g1 <= b0


def test_batch_children_nest_in_order(served):
    _, trace = served
    batches = spans.intervals(trace, "serve.batch")
    kids = sorted((s, e, n) for s, e, n in trace.host if n in CHILDREN)
    for b0, b1 in batches:
        inside = [k for k in kids if b0 <= k[0] and k[1] <= b1]
        assert [n for _, _, n in inside] == CHILDREN
        ends = [e for _, e, _ in inside]
        starts = [s for s, _, _ in inside]
        assert all(e <= s for e, s in zip(ends, starts[1:]))


def test_shard_spans_sit_inside_dispatch(served):
    shards, trace = served
    dispatch = spans.intervals(trace, "serve.dispatch")
    scans = spans.within(spans.intervals(trace, "shard.scan"), dispatch)
    merges = spans.within(spans.intervals(trace, "shard.merge"), dispatch)
    if shards == 1:
        assert not any(scans) and not any(merges)
        return
    for scan, merge in zip(scans, merges):
        assert len(scan) == shards and len(merge) == 1
        assert max(e for _, e in scan) <= merge[0][0]


# -- the readers, on hand-made intervals --------------------------------------

def _trace():
    # two devices; the most idle one (TPU:1) runs 0.0-0.4 and 0.5-0.9.  The
    # batcher gathers 0.38-0.47 (0.07 s of it idle) and 0.95-1.0 (idle);
    # two dispatches fan out to four shards each, and merge.
    ops = {"/device:TPU:0": [(0.0, 1.0, "jit_k %k", "jit_k(1) | %k")],
           "/device:TPU:1": [(0.0, 0.4, "jit_k %k", "jit_k(1) | %k"),
                             (0.5, 0.9, "jit_k %k", "jit_k(1) | %k")]}
    host = [(0.38, 0.47, "serve.gather"), (0.95, 1.0, "serve.gather"),
            (0.47, 0.6, "serve.dispatch"), (1.0, 1.1, "serve.dispatch"),
            (0.48, 0.50, "shard.scan"), (0.49, 0.53, "shard.scan"),
            (0.50, 0.51, "shard.scan"), (0.52, 0.56, "shard.scan"),
            (0.56, 0.58, "shard.merge"),
            (1.01, 1.02, "shard.scan"), (1.01, 1.03, "shard.scan"),
            (1.02, 1.04, "shard.scan"), (1.03, 1.05, "shard.scan"),
            (1.05, 1.09, "shard.merge")]
    return tracing.Trace(window_s=1.2, ops=ops, host=sorted(host))


def _layers(trace, phases=None, n_batches=4):
    class Stats:
        execute_total_s = 0.8
        queue_wait_total_s = 0.2
        mean_batch_fill = 0.5
    Stats.n_batches = n_batches
    if phases is not None:
        Stats.phase_total_s = phases
    return harness.Layers(
        stats=Stats(), batch_size=16, trace=trace,
        scan_work=work.scan_work(b=16, n=2 ** 23, d=768, dtype_bytes=2,
                                 k=100),
        peaks=work.device_peaks("TPU v5 lite"))


def test_overlap_of_unions():
    assert spans.overlap_s([(0.0, 0.4), (0.3, 0.6)], [(0.5, 0.7)]) == \
        pytest.approx(0.1)
    assert spans.overlap_s([(0.0, 0.1)], [(0.2, 0.3)]) == 0.0


def test_device_idle_gather_joins_spans_to_ops():
    layers = _layers(_trace())
    assert spans.idle_while_s(layers.trace, "serve.gather") == \
        pytest.approx(0.07 + 0.05)
    assert harness.reader("device.idle_gather").read(layers) == \
        pytest.approx(100 * 0.12 / 1.2)


def test_shard_readers():
    layers = _layers(_trace())
    assert harness.reader("shard.scan_ms").read(layers) == \
        pytest.approx(1e3 * (0.08 + 0.04) / 2)
    assert harness.reader("shard.merge_ms").read(layers) == \
        pytest.approx(1e3 * (0.02 + 0.04) / 2)


def test_phase_readers():
    phases = {"gather": 0.04, "assemble": 0.008, "dispatch": 0.012,
              "sync": 0.6, "copy_back": 0.004, "fanout": 0.002}
    layers = _layers(None, phases)
    assert harness.reader("batcher.gather_ms").read(layers) == \
        pytest.approx(10.0)
    assert harness.reader("batch.host_ms").read(layers) == \
        pytest.approx(1e3 * 0.026 / 4)


@pytest.mark.parametrize("metric", [
    "batcher.gather_ms", "batch.host_ms", "device.idle_gather",
    "shard.scan_ms", "shard.merge_ms"])
def test_readers_find_nothing_without_spans(metric):
    """A program without the spans and counters (a build older than
    them) reads as nothing, and raises nothing."""
    bare = tracing.Trace(window_s=1.2, ops=_trace().ops, host=[])
    assert harness.reader(metric).read(_layers(bare)) is None
    assert harness.reader(metric).read(_layers(None)) is None


# -- a short trace recorded on the chip -----------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return tracing.read(str(RECORDED))


def test_recorded_trace_holds_the_spans(recorded):
    names = {n for _, _, n in recorded.host}
    assert {"serve.gather", "serve.batch", *CHILDREN} <= names
    assert len(spans.intervals(recorded, "serve.batch")) == RECORDED_BATCHES


def test_recorded_idle_gaps_are_named_by_the_batcher(recorded):
    gaps = tracing.idle_gaps(recorded, 3)
    assert gaps and all(name.startswith("serve.") for name, _ in gaps)


def test_recorded_idle_while_gathering(recorded):
    idle = spans.idle_while_s(recorded, "serve.gather")
    assert idle == pytest.approx(RECORDED_IDLE_GATHER_S, rel=1e-9)


# read by hand from the recorded file with jax.profiler.ProfileData (a
# 1.3-s window of dense.steady at 64 queries/s, recorded on a TPU v5e):
# the host events named serve.batch, and the seconds of the serve.gather
# events' union not covered by the union of the "XLA Ops" intervals on
# /device:TPU:0
RECORDED_BATCHES = 7
RECORDED_IDLE_GATHER_S = 0.084632367
