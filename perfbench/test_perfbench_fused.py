"""The fused deployment (``builders/fused_scan.py``,
``references/fused_ip.py``, cell ``fused.steady``) on the CPU.

* the reference against a naive f64 bag-of-terms scorer written here;
* the builder's data: term ids in range and distinct in a row, a heavy
  head, live query terms between the bounds with the rest padded, and
  both parts of the mixed score deciding ranks;
* the rehearsed cell comes out not correct with each fault planted in
  the program (in a child process, as ``test_perfbench_faults.py``
  drives the dense cells): the sparse query values zeroed before the
  scan, the dense query zeroed, the weights swapped, the kernel's best
  id moved to the next row; and so does the deployment's control.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness
from perfbench.builders import fused_scan
from perfbench.references import fused_ip

CONFIG = harness.config(harness.benchmark(), "msmarco-passage-fused")


def _naive(q_dense, q_ids, q_vals, rows, ids, vals, vocab, w_dense,
           w_sparse):
    """Every query against every row, term by term, in f64."""
    out = np.zeros((len(q_dense), len(rows)))
    for s in range(len(q_dense)):
        weight = {}
        for t, v in zip(q_ids[s].tolist(), q_vals[s].tolist()):
            if 0 <= t < vocab:
                weight[t] = weight.get(t, 0.0) + v
        for n in range(len(rows)):
            sparse = sum(weight.get(t, 0.0) * float(v)
                         for t, v in zip(ids[n].tolist(), vals[n].tolist()))
            dense = float(np.dot(q_dense[s].astype(np.float64),
                                 rows[n].astype(np.float64)))
            out[s, n] = w_dense * dense + w_sparse * sparse
    return out


def test_reference_matches_a_naive_scorer():
    rng = np.random.default_rng(11)
    vocab, n, d, nnz, slots = 40, 64, 8, 5, 4
    rows = jnp.asarray(rng.standard_normal((n, d)), jnp.bfloat16)
    ids = np.argsort(rng.random((n, vocab)), 1)[:, :nnz].astype(np.int32)
    vals = jnp.asarray(rng.random((n, nnz)) + 0.1, jnp.bfloat16)
    q_dense = rng.standard_normal((6, d)).astype(np.float32)
    q_ids = rng.integers(0, vocab, (6, slots)).astype(np.int32)
    q_ids[:, -1] = vocab                    # the padding id
    q_ids[0, 0] = -1                        # out of range
    q_vals = rng.random((6, slots)).astype(np.float32)
    q_vals[:, -1] = 0.0
    want = _naive(q_dense, q_ids, q_vals, np.asarray(rows, np.float32), ids,
                  np.asarray(vals, np.float32), vocab, 0.7, 0.3)

    table = fused_ip.query_table(q_ids, q_vals, vocab)
    scores, top = fused_ip.candidates(
        jnp.asarray(q_dense), jnp.asarray(table), rows, jnp.asarray(ids),
        vals, m=8, block=16, w_dense=0.7, w_sparse=0.3)
    order = np.argsort(-want, 1, kind="stable")[:, :8]
    assert np.array_equal(np.asarray(top), order)
    np.testing.assert_allclose(np.asarray(scores),
                               np.take_along_axis(want, order, 1),
                               rtol=1e-5, atol=1e-6)

    got = fused_ip.exact(q_dense, q_ids, q_vals, vocab, 0.7, 0.3,
                         *fused_ip.rows_of(rows, jnp.asarray(ids), vals,
                                           order))
    np.testing.assert_allclose(got, np.take_along_axis(want, order, 1),
                               rtol=1e-12, atol=1e-12)


@pytest.fixture(scope="module")
def deployment():
    """The configuration at every published width over 2^15 rows."""
    cfg = {**CONFIG, "rows": 1 << 15, "gen_block": 8192, "ref_block": 8192}
    dep = fused_scan.build(cfg, 2 ** 32 + 5, jax.devices()[:1])
    yield dep
    dep.delete()


def test_document_terms(deployment):
    cfg = deployment.cfg
    ids = np.asarray(deployment.ids)
    vals = np.asarray(deployment.vals, np.float32)
    assert ids.shape == (cfg["rows"], cfg["doc_nnz"]) == vals.shape
    assert deployment.vals.dtype == jnp.bfloat16
    assert ids.min() >= 0 and ids.max() < cfg["vocab"]
    assert all(len(set(row)) == cfg["doc_nnz"] for row in ids.tolist())
    assert (vals > 0).all()
    # Zipf: the 100 most frequent terms hold about a fifth of the slots
    # (0.44 of the draws, before repeats), the rarer half of the
    # vocabulary about a tenth
    head = np.mean(ids < 100)
    tail = np.mean(ids >= cfg["vocab"] // 2)
    assert 0.1 < head < 0.4 and 0.03 < tail < 0.2
    # frequent terms weigh little: the head's mean value is below half
    # the tail's
    rare = ids >= cfg["vocab"] // 2
    assert vals[ids < 100].mean() < 0.5 * vals[rare].mean()


def test_query_terms(deployment):
    cfg = deployment.cfg
    qs = deployment.make_queries(500, np.random.default_rng(4))
    assert qs.dense.dtype == np.float32 and qs.dense.shape == (500, 768)
    ids, vals = qs.sparse
    assert ids.shape == vals.shape == (500, cfg["q_nnz"])
    assert ids.dtype == np.int32 and vals.dtype == np.float32
    live = ids < cfg["vocab"]
    n_live = live.sum(1)
    assert n_live.min() == cfg["q_live_min"]
    assert n_live.max() == cfg["q_live_max"]
    # live slots first, distinct; the rest the padding id with value 0
    assert all(row[:k].all() and not row[k:].any()
               for row, k in zip(live, n_live))
    assert all(len(set(r[:k])) == k for r, k in zip(ids.tolist(), n_live))
    assert (ids[~live] == cfg["vocab"]).all() and (vals[~live] == 0).all()
    assert (ids[live] >= 0).all() and (vals[live] >= 0.5).all()


def test_both_parts_decide_ranks(deployment):
    """Zeroing either part of the score changes the reference's top-10
    on most of 64 seeded queries."""
    dep = deployment
    qs = dep.make_queries(64, np.random.default_rng(9))
    _, full = dep.reference(qs, 10)
    changed = {}
    for part, zeroed in (
            ("dense", qs._replace(dense=np.zeros_like(qs.dense))),
            ("sparse", qs._replace(sparse=qs.sparse._replace(
                values=np.zeros_like(qs.sparse.values))))):
        _, top = dep.reference(zeroed, 10)
        changed[part] = np.mean([set(a) != set(b)
                                 for a, b in zip(full, top)])
    assert changed["dense"] > 0.5 and changed["sparse"] > 0.5, changed


def test_reference_and_exact_agree_at_the_widths(deployment):
    dep = deployment
    qs = dep.make_queries(8, np.random.default_rng(10))
    scores, ids = dep.reference(qs, 32)
    exact = dep.exact(qs, ids)
    unit = 2.0 ** (np.floor(np.log2(np.abs(exact).max(1, keepdims=True)))
                   - 23)
    assert (np.abs(scores - exact) / unit).max() < 32


# -- in a child process: the rehearsed cell, sound and broken ----------------

CHILD = r"""
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [ROOT, ROOT + "/src"]
from unittest import mock
import jax.numpy as jnp
from perfbench import checks, harness
from perfbench.tools import calibrate
import repro.kernels.ops as ops
from repro.core.brute_force import TopK
from repro.core.sparse import SparseVectors

harness.set_up_jax(harness.ROOT, cache=False)
SEED = 20260404
fused_topk = ops.fused_topk


def zero_sparse(q_sparse, *a, **kw):
    return fused_topk(SparseVectors(q_sparse.indices,
                                    jnp.zeros_like(q_sparse.values)), *a, **kw)


def zero_dense(q_sparse, q_dense, *a, **kw):
    return fused_topk(q_sparse, jnp.zeros_like(q_dense), *a, **kw)


def swapped(*a, w_dense=None, w_sparse=None, **kw):
    return fused_topk(*a, w_dense=w_sparse, w_sparse=w_dense, **kw)


def altered(*a, **kw):
    out = fused_topk(*a, **kw)
    return TopK(out.scores, out.indices.at[:, 0].add(1))


FAULTS = {"zero_sparse": zero_sparse, "zero_dense": zero_dense,
          "swapped": swapped, "altered": altered}


def run(hook=None):
    return harness.run_cell("fused.steady", SEED, 2.0, False,
                            t_start=time.perf_counter(), rehearsal=True,
                            hook=hook)


out = {}


def control(c, checked):
    numbers = calibrate.control_numbers(c, checked, "highest", True)
    numbers["unanswered"] = 0
    out["correct"], out["checks"] = checks.verdict(numbers, c.cfg["limits"])


res = run(control)
print(json.dumps({"fault": "none", "correct": res["correct"],
                  "checks": res["checks"]}), flush=True)
print(json.dumps({"fault": "control", **out}), flush=True)
for name, fault in FAULTS.items():
    with mock.patch.object(ops, "fused_topk", fault):
        res = run()
    print(json.dumps({"fault": name, "correct": res["correct"],
                      "checks": res["checks"]}), flush=True)
"""

FAULTS = ["zero_sparse", "zero_dense", "swapped", "altered", "control"]


@pytest.fixture(scope="module")
def outcomes():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    code = f"ROOT = {str(harness.ROOT)!r}\n" + CHILD
    proc = subprocess.run([sys.executable, "-c", code], cwd=harness.ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return {d["fault"]: d for d in map(json.loads, proc.stdout.splitlines())}


def test_rehearsed_cell_is_correct_unbroken(outcomes):
    got = outcomes["none"]
    assert got["correct"] is True, got["checks"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(outcomes, fault):
    got = outcomes[fault]
    assert got["correct"] is False, got["checks"]
