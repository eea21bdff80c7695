"""A deployment supplies its own queries, and its own control.

The harness draws a cell's queries only through its builder's
``make_queries`` and indexes them only through ``traffic.take``, so a
deployment whose queries are a pytree runs through the unchanged
``harness.run_cell`` and ``RetrievalService``.  This file shows it with a
deployment that exists only here: a fused dense + sparse corpus (D 128
f32, vocabulary 512, 8 term slots a row and 4 a query, 4,096 rows,
weights 0.7 / 0.3) served by the Pallas fused kernel, interpreted on the
CPU, and held against a plain float64 reference written in numpy.  This
module is that builder: the child process registers it under
``perfbench.builders`` and runs the cell from a copy of
``BENCHMARK.json`` that holds its entries, as
``test_perfbench_faults.py`` drives its planned cell.

The dense cells' pools stay what the harness drew before builders owned
them, and their control reads the same numbers through ``dep.control``.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, traffic, work
from repro.core.pipeline import BruteForceGenerator, RetrievalPipeline
from repro.core.sparse import SparseVectors
from repro.core.spaces import FusedSpace, FusedVectors
from repro.serving import EndpointSpec

# -- the test-only fused deployment ------------------------------------------

TINY_FUSED = {
    "deployment": "Exists only in this test: a fused dense + sparse corpus, "
                  "to drive pytree queries through the harness.",
    "rows": 4096, "dim": 128, "vocab": 512, "doc_nnz": 8, "q_nnz": 4,
    "w_dense": 0.7, "w_sparse": 0.3,
    "corpus_dtype": "float32", "cand_qty": 100, "final_qty": 10,
    "backend": "pallas", "builder": "tiny_fused", "ref_candidates": 32,
    "knee_qps": 40.0,
    "limits": {"unanswered": 0, "bad_ids": 0, "score_err_ulp": 32.0,
               "rank_gap_ulp": 32.0},
    "rehearsal": {},
}

ENTRIES = {
    "configs": [{"name": "tiny-fused", "source": "perfbench test",
                 "file": "tiny-fused.json", "reduced": [], "why": "test"}],
    "workloads": [{"name": "tiny-fused.steady", "config": "tiny-fused",
                   "traffic": "steady", "chips": 1, "why": "test"}],
}


def _terms(rng, count: int, nnz: int, vocab: int) -> np.ndarray:
    """``nnz`` distinct term ids a row."""
    return np.argsort(rng.random((count, vocab)), axis=1)[:, :nnz].astype(
        np.int32)


def _bag(ids: np.ndarray, vals: np.ndarray, vocab: int) -> np.ndarray:
    """Padded COO rows as dense f64 rows over the vocabulary."""
    out = np.zeros((ids.shape[0], vocab), np.float64)
    np.add.at(out, (np.arange(ids.shape[0])[:, None], ids), vals)
    return out


class TinyFused:
    def __init__(self, cfg: dict, seed: int, devices):
        self.cfg, self.device = cfg, devices[0]
        rng = np.random.default_rng([seed, 77])
        n, self.vocab = cfg["rows"], cfg["vocab"]
        self.dense = traffic.make_queries(n, cfg["dim"], rng)
        self.ids = _terms(rng, n, cfg["doc_nnz"], self.vocab)
        self.vals = rng.uniform(0.0, 1.0, self.ids.shape).astype(np.float32)

    def register(self, svc, name: str):
        cfg = self.cfg
        corpus = jax.device_put(FusedVectors(
            self.dense, SparseVectors(self.ids, self.vals)), self.device)
        space = FusedSpace(self.vocab, w_dense=cfg["w_dense"],
                           w_sparse=cfg["w_sparse"])
        pipe = RetrievalPipeline(BruteForceGenerator(space, corpus),
                                 cand_qty=cfg["cand_qty"],
                                 final_qty=cfg["final_qty"])
        pad = FusedVectors(
            np.zeros(cfg["dim"], np.float32),
            SparseVectors(np.full(cfg["q_nnz"], self.vocab, np.int32),
                          np.zeros(cfg["q_nnz"], np.float32)))
        svc.register_pipeline(name, pipe, pad,
                              spec=EndpointSpec(backend=cfg["backend"]))

    def make_queries(self, count: int, rng: np.random.Generator):
        dense = traffic.make_queries(count, self.cfg["dim"], rng)
        ids = _terms(rng, count, self.cfg["q_nnz"], self.vocab)
        vals = rng.uniform(0.0, 1.0, ids.shape).astype(np.float32)
        return FusedVectors(dense, SparseVectors(ids, vals))

    def _scores(self, queries) -> np.ndarray:
        """[S, N] f64: the weighted dense dot plus sparse term match."""
        dense = (np.asarray(queries.dense, np.float64)
                 @ self.dense.astype(np.float64).T)
        sparse = (_bag(np.asarray(queries.sparse.indices),
                       np.asarray(queries.sparse.values), self.vocab)
                  @ _bag(self.ids, self.vals, self.vocab).T)
        return self.cfg["w_dense"] * dense + self.cfg["w_sparse"] * sparse

    def reference(self, queries, m: int):
        scores = self._scores(queries)
        ids = np.argsort(-scores, axis=1, kind="stable")[:, :m]
        return np.take_along_axis(scores, ids, 1), ids

    def exact(self, queries, ids: np.ndarray) -> np.ndarray:
        return np.take_along_axis(self._scores(queries), ids, 1)

    def control(self, queries, m: int, precision: str, emulate: bool):
        """The reference over queries rounded to bf16, the precision
        below the f32 the endpoint takes (one control, whatever is
        asked)."""
        def bf16(x):
            return np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32)

        return self.reference(queries._replace(
            dense=bf16(queries.dense),
            sparse=queries.sparse._replace(
                values=bf16(queries.sparse.values))), m)

    def scan_work(self, batch: int) -> dict:
        cfg = self.cfg
        return work.scan_work(b=batch, n=cfg["rows"], d=cfg["dim"],
                              dtype_bytes=4, k=cfg["cand_qty"],
                              nnz=cfg["doc_nnz"], value_bytes=4,
                              q_nnz=cfg["q_nnz"])

    def close_program(self):
        pass

    def delete(self):
        self.dense = self.ids = self.vals = None


def build(cfg: dict, seed: int, devices) -> TinyFused:
    return TinyFused(cfg, seed, devices)


# -- in this process ---------------------------------------------------------

BENCH = harness.benchmark()
DENSE_CONFIGS = [c["name"] for c in BENCH["configs"]
                 if harness.config(BENCH, c["name"])["builder"]
                 == "dense_scan"]


@pytest.mark.parametrize("name", DENSE_CONFIGS)
def test_dense_pool_is_bitwise_the_parents(name):
    """What the harness drew before builders owned the pool: N(0, 1/D)
    f32, from the same generator in the same order."""
    cfg = harness.config(BENCH, name)
    cfg = {**cfg, **cfg["rehearsal"]}
    dep = harness.builder(cfg["builder"]).build(
        cfg, 5, jax.devices()[:1] * cfg["shards"])
    try:
        for seed in (3, 2 ** 31 + 11):
            got = dep.make_queries(33, np.random.default_rng([seed, 12]))
            rng = np.random.default_rng([seed, 12])
            want = (rng.standard_normal((33, 768), dtype=np.float32)
                    / np.float32(np.sqrt(768)))
            assert cfg["dim"] == 768
            assert got.dtype == np.float32 and got.shape == (33, 768)
            assert np.array_equal(got, want)
            assert np.array_equal(got, traffic.make_queries(
                33, 768, np.random.default_rng([seed, 12])))
    finally:
        dep.delete()


def _fused_pool(count: int):
    return FusedVectors(
        np.arange(count * 3, dtype=np.float32).reshape(count, 3),
        SparseVectors(np.arange(count * 2, dtype=np.int32).reshape(count, 2),
                      -np.arange(count * 2, dtype=np.float32)
                      .reshape(count, 2)))


@pytest.mark.parametrize("kind", ["array", "fused"])
def test_take_indexes_every_leaf(kind):
    pool = (np.arange(15, dtype=np.float32).reshape(5, 3) if kind == "array"
            else _fused_pool(5))
    one = traffic.take(pool, np.int64(3))
    some = traffic.take(pool, np.array([4, 0, 4]))
    assert type(one) is type(pool) and type(some) is type(pool)
    for leaf, a, b in zip(*(jax.tree.leaves(t) for t in (pool, one, some))):
        assert a.dtype == leaf.dtype and np.array_equal(a, leaf[3])
        assert np.array_equal(b, leaf[[4, 0, 4]])


# -- in a child process: the harness end to end on the CPU -------------------

DRIVER = r"""
import json, os, sys, time
from pathlib import Path
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [ROOT, ROOT + "/src"]
from unittest import mock
import jax.numpy as jnp
from perfbench import checks, harness
from perfbench import test_perfbench_queries as tiny
from perfbench.references import dense_ip
from perfbench.tools import calibrate
import repro.kernels.ops as ops
from repro.core.sparse import SparseVectors

sys.modules["perfbench.builders.tiny_fused"] = tiny
harness.set_up_jax(harness.ROOT, cache=False)
BENCH = Path(BENCH_ROOT)
SEED = 20260303
fused_topk = ops.fused_topk


def zero_sparse(q_sparse, *a, **kw):
    return fused_topk(SparseVectors(q_sparse.indices,
                                    jnp.zeros_like(q_sparse.values)), *a, **kw)


def control_verdict(c, checked):
    numbers = calibrate.control_numbers(c, checked, "highest", True)
    numbers["unanswered"] = 0
    return checks.verdict(numbers, c.cfg["limits"])


def parents_control(c, checked, precision, emulate):
    # tools/calibrate.control_numbers as it was before dep.control
    qs = checked["queries"]
    k = c.cfg["final_qty"]
    scan_q = dense_ip.query_bits_16(qs) if emulate else qs
    scores, ids = c.dep.reference(scan_q, k, precision=precision)
    exact_served = c.dep.exact(qs, ids)
    return checks.compare(scores, ids, checked["exact_top"], exact_served,
                          c.cfg["rows"])


def run(cell, hook=None):
    return harness.run_cell(cell, SEED, 2.0, False,
                            t_start=time.perf_counter(), root=BENCH,
                            rehearsal=True, hook=hook)


def emit(case, res, **extra):
    print(json.dumps({"case": case, "correct": res["correct"],
                      "failed": res["failed"], "checks": res["checks"],
                      **extra}), flush=True)


out = {}
res = run("tiny-fused.steady", lambda c, checked: out.update(
    control=control_verdict(c, checked)))
emit("fused", res)
correct, compared = out["control"]
print(json.dumps({"case": "fused_control", "correct": correct,
                  "failed": 0, "checks": compared}), flush=True)
with mock.patch.object(ops, "fused_topk", zero_sparse):
    emit("fused_zero_sparse", run("tiny-fused.steady"))

pairs = []


def both(c, checked):
    for precision, emulate in (("highest", True), ("high", False),
                               ("default", False)):
        pairs.append([calibrate.control_numbers(c, checked, precision,
                                                emulate),
                      parents_control(c, checked, precision, emulate)])


emit("dense_control", run("dense.steady", both), pairs=pairs)
"""


@pytest.fixture(scope="module")
def bench_root(tmp_path_factory):
    """A root whose BENCHMARK.json also holds the test-only cell."""
    root = tmp_path_factory.mktemp("bench")
    bench = harness.benchmark()
    for key, entries in ENTRIES.items():
        bench[key] += entries
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "tiny-fused.json").write_text(json.dumps(TINY_FUSED))
    (root / "perfbench").symlink_to(harness.ROOT / "perfbench")
    return root


@pytest.fixture(scope="module")
def outcomes(bench_root):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    code = (f"ROOT = {str(harness.ROOT)!r}\nBENCH_ROOT = {str(bench_root)!r}"
            f"\n" + DRIVER)
    proc = subprocess.run([sys.executable, "-c", code], cwd=harness.ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return {d["case"]: d for d in map(json.loads, proc.stdout.splitlines())}


def test_fused_deployment_rehearses_correct(outcomes):
    got = outcomes["fused"]
    assert got["correct"] is True and got["failed"] == 0, got["checks"]


@pytest.mark.parametrize("case", ["fused_zero_sparse", "fused_control"])
def test_fused_fault_is_not_correct(outcomes, case):
    """Sparse query values zeroed before the scan: the check sees the
    sparse half.  The deployment's own control fails too."""
    got = outcomes[case]
    assert got["correct"] is False, got["checks"]


def test_dense_control_reads_the_parents_numbers(outcomes):
    got = outcomes["dense_control"]
    assert got["correct"] is True, got["checks"]
    assert len(got["pairs"]) == 3
    for new, old in got["pairs"]:
        assert new == old
