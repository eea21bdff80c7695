"""The work of one scan call, counted from its shapes, and the least time
a chip could take for it.

The count belongs to the scan, not to an implementation of it: a kernel
that reads fewer bytes or runs fewer passes is read against the same
numbers, so its roofline share rises.  A dense row costs ``D`` values and
a sparse row ``NNZ`` (id, value) pairs; every query scores every row
(``2*D`` operations for the dense dot, ``2*NNZ`` for the sparse match)
and each score is compared once against the running top-k.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def device_peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind``; an unknown kind is an
    error, never another chip's numbers."""
    table = json.loads(PEAKS_FILE.read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS_FILE} "
                       f"(known: {sorted(table)})")
    return table[kind]


def scan_work(*, b: int, n: int, d: int, dtype_bytes: int, k: int,
              nnz: int = 0, value_bytes: int = 0, q_nnz: int = 0,
              q_bytes: int = 4) -> dict:
    """Bytes and operations of one top-``k`` scan of ``b`` queries over
    ``n`` rows of ``d`` dense values (``dtype_bytes`` each) and ``nnz``
    sparse slots (int32 id + ``value_bytes``)."""
    corpus = n * (d * dtype_bytes + nnz * (4 + value_bytes))
    queries = b * (d * q_bytes + q_nnz * (4 + value_bytes))
    results = b * k * (4 + 4)                   # f32 score + int32 id
    ops = 2 * b * n * d + 2 * b * n * nnz + b * n
    return {"bytes": corpus + queries + results, "ops": ops}


def least_seconds(work: dict, peaks: dict) -> tuple:
    """(seconds, bound): the larger of bytes over HBM bandwidth and
    operations over the bf16 peak, and which of the two it is."""
    mem = work["bytes"] / peaks["hbm_bytes_per_s"]
    comp = work["ops"] / peaks["bf16_flops_per_s"]
    return (mem, "memory") if mem >= comp else (comp, "compute")
