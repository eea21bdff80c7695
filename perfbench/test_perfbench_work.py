"""The scan's work counts and the peaks table."""

import json

import pytest

from perfbench import work

V5E = "TPU v5 lite"


def test_dense_one_chip_shape():
    # dense.steady: 16 queries x 2^23 rows x 768 bf16, k = 100
    w = work.scan_work(b=16, n=2 ** 23, d=768, dtype_bytes=2, k=100)
    assert w["bytes"] == 8388608 * 1536 + 16 * 768 * 4 + 16 * 100 * 8
    assert w["bytes"] == 12884963840
    assert w["ops"] == 2 * 16 * 8388608 * 768 + 16 * 8388608
    assert w["ops"] == 206292647936


def test_dense_four_chip_shape_is_one_chips_share():
    # dense-x4.steady: each chip scans its own 2^23-row shard
    one = work.scan_work(b=16, n=2 ** 23, d=768, dtype_bytes=2, k=100)
    shard = work.scan_work(b=16, n=2 ** 25 // 4, d=768, dtype_bytes=2,
                           k=100)
    assert one == shard


def test_sparse_part_counts():
    # a fused row: 768 bf16 + 64 (int32 id, bf16 value) slots; 16 query
    # terms; hand count
    w = work.scan_work(b=16, n=2 ** 22, d=768, dtype_bytes=2, k=100,
                       nnz=64, value_bytes=2, q_nnz=16)
    assert w["bytes"] == (4194304 * (1536 + 64 * 6) + 16 * (768 * 4 + 16 * 6)
                          + 16 * 100 * 8)
    assert w["ops"] == (2 * 16 * 4194304 * 768 + 2 * 16 * 4194304 * 64
                        + 16 * 4194304)


def test_least_time_is_the_larger_bound():
    peaks = work.device_peaks(V5E)
    w = work.scan_work(b=16, n=2 ** 23, d=768, dtype_bytes=2, k=100)
    secs, bound = work.least_seconds(w, peaks)
    assert bound == "memory"
    assert secs == pytest.approx(12884963840 / 819e9)
    heavy = {"bytes": 1.0, "ops": 197e12}
    assert work.least_seconds(heavy, peaks) == (1.0, "compute")


def test_peaks_table_has_a_source_and_refuses_unknown_kinds():
    table = json.loads(work.PEAKS_FILE.read_text())
    assert table[V5E]["hbm_bytes_per_s"] == 819e9
    assert table[V5E]["bf16_flops_per_s"] == 197e12
    assert all(row.get("source") for row in table.values())
    with pytest.raises(KeyError, match="no peaks"):
        work.device_peaks("TPU v9 imaginary")
