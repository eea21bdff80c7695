"""The reduction from a profiler trace to busy time, kernel time per call,
idle share and named idle gaps: on hand-made intervals, and on a short
trace of ``dense.steady`` recorded on a TPU v5e (``testdata/``)."""

from pathlib import Path

import pytest

from perfbench import harness, tracing, work

RECORDED = Path(__file__).resolve().parent / "testdata" / \
    "dense_small.xplane.pb.gz"
KERNEL = harness.reader("mips_topk.ms").KERNEL


def _trace():
    # one device: a kernel 0.0-0.4 and 0.5-0.9, a small op 0.35-0.45
    # overlapping it, a copy 0.95-1.0; window 1.2 s.  The host converts
    # results 0.45-0.5 and stacks queries 0.9-0.95.
    ops = {"/device:TPU:0": [
        (0.0, 0.4, "jit_k %k", "jit_k(1) | %k = custom-call tpu_custom_call"),
        (0.35, 0.45, "jit_s %s", "jit_s(2) | %s = slice"),
        (0.5, 0.9, "jit_k %k", "jit_k(1) | %k = custom-call tpu_custom_call"),
        (0.95, 1.0, "jit_c %c", "jit_c(3) | %c = copy"),
    ]}
    host = [(0.0, 1.2, "batcher loop"), (0.44, 0.5, "np.asarray"),
            (0.9, 0.95, "stack")]
    return tracing.Trace(window_s=1.2, ops=ops, host=host)


def test_union_merges_overlaps():
    assert tracing.union([(0.5, 0.9), (0.0, 0.4), (0.35, 0.45)]) == \
        [(0.0, 0.45), (0.5, 0.9)]


def test_busy_is_the_union_of_operations():
    t = _trace()
    assert tracing.busy_s(t)["/device:TPU:0"] == pytest.approx(0.9)


def test_kernel_calls_by_program():
    per = tracing.calls(_trace(), r"^jit_k\(.*tpu_custom_call")
    assert per == {"/device:TPU:0": (2, pytest.approx(0.8))}
    assert tracing.calls(_trace(), r"^jit_none\(") == {}


def test_idle_gaps_are_named_by_the_host():
    gaps = tracing.idle_gaps(_trace())
    assert [g[0] for g in gaps] == ["np.asarray", "stack"]
    assert [g[1] for g in gaps] == [pytest.approx(0.05)] * 2


def test_top_ops_rank_device_time():
    top = tracing.top_ops(_trace(), 2)
    assert top[0] == ["jit_k %k", pytest.approx(0.8)]


def _layers(trace, n_batches=10, exec_total_s=6.0):
    class Stats:
        execute_total_s = exec_total_s
        queue_wait_total_s = 1.6
        mean_batch_fill = 0.5
    Stats.n_batches = n_batches
    return harness.Layers(
        stats=Stats(), batch_size=16, trace=trace,
        scan_work=work.scan_work(b=16, n=2 ** 23, d=768, dtype_bytes=2,
                                 k=100),
        peaks=work.device_peaks("TPU v5 lite"))


def test_readers_on_hand_made_intervals():
    layers = _layers(_trace())
    layers.trace.ops["/device:TPU:0"] = [
        (a, b, n, t.replace("jit_k(", "jit_mips_topk("))
        for a, b, n, t in layers.trace.ops["/device:TPU:0"]]
    read = {m: harness.reader(m).read(layers) for m in (
        "mips_topk.ms", "mips_topk_roofline", "batch.exec_ms",
        "batch.overhead_ms", "batcher.queue_wait_ms", "device.idle")}
    assert read["mips_topk.ms"] == pytest.approx(400.0)
    assert read["mips_topk_roofline"] == pytest.approx(
        100 * (12884963840 / 819e9) / 0.4)
    assert read["batch.exec_ms"] == pytest.approx(600.0)
    assert read["batch.overhead_ms"] == pytest.approx(200.0)
    assert read["batcher.queue_wait_ms"] == pytest.approx(1e3 * 1.6 / 80)
    assert read["device.idle"] == pytest.approx(100 * (1 - 0.9 / 1.2))


def test_readers_find_nothing_without_a_trace():
    layers = _layers(None)
    assert harness.reader("mips_topk.ms").read(layers) is None
    assert harness.reader("mips_topk_roofline").read(layers) is None
    assert harness.reader("device.idle").read(layers) is None


@pytest.fixture(scope="module")
def recorded():
    return tracing.read(str(RECORDED))


def test_recorded_trace_devices_and_window(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    assert recorded.window_s == pytest.approx(RECORDED_WINDOW_S)


def test_recorded_trace_kernel_calls(recorded):
    (n, total), = tracing.calls(recorded, KERNEL).values()
    assert n == RECORDED_KERNEL_CALLS
    assert total == pytest.approx(RECORDED_KERNEL_S, rel=1e-9)


def test_recorded_trace_busy_and_idle(recorded):
    busy = tracing.busy_s(recorded)["/device:TPU:0"]
    assert busy == pytest.approx(RECORDED_BUSY_S, rel=1e-9)
    assert 0 < busy < recorded.window_s
    gaps = tracing.idle_gaps(recorded)
    assert gaps and all(0 < g[1] < recorded.window_s for g in gaps)
    assert sum(g[1] for g in gaps) <= recorded.window_s - busy + 1e-9


# read by hand from the recorded file with jax.profiler.ProfileData (a
# 1-s window of dense.steady, seed 912): the "XLA Ops" events whose
# instruction is the tpu_custom_call (count, summed durations), the
# union of all "XLA Ops" intervals on /device:TPU:0, and the Task
# Environment plane's profile_stop_time - profile_start_time
RECORDED_WINDOW_S = 1.299968817
RECORDED_KERNEL_CALLS = 5
RECORDED_KERNEL_S = 0.779186245
RECORDED_BUSY_S = 0.779312939
