"""§2 claim: approximate k-NN (graph ANN / NAPP) reaches high recall at a
fraction of the brute-force distance computations — the
efficiency/effectiveness trade-off the paper argues dense-retrieval papers
ignore.

Swept over ef (graph) and num_search (NAPP) *through the registered
execution backends* (``make_backend("graph_ann"/"napp")``), so every row
carries the backend's declared-budget ``identity`` string, and written to
``BENCH_ann.json`` — the recall/QPS frontier as a tracked artifact whose
schema ``benchmarks/validate_bench.py`` checks in CI.  Runs on the same
planted-cluster corpora as the measured-recall contract tests
(``tests/_recall.py`` delegates to the constructions here in
``benchmarks/common.py``), so the artifact's gate — max-budget rows must
meet ``ANN_RECALL_TARGET`` — is an invariant of the data, not a seed
lottery.  Covers all three contract spaces: dense, sparse, fused.

    PYTHONPATH=src:. python -m benchmarks.ann_tradeoff [--smoke] [--out F]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import numpy as np

# script-mode shim: `python benchmarks/ann_tradeoff.py` puts benchmarks/
# itself on sys.path, not the repo root that `benchmarks.common` needs
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import (planted_cluster_dense, planted_cluster_fused,
                               time_call)
from repro.core.backends import ANN_RECALL_TARGET, make_backend
from repro.core.brute_force import exact_topk
from repro.core.fusion import topk_recall
from repro.core.spaces import DenseSpace, FusedSpace, SparseSpace

BENCH_SCHEMA = 1          # bumped when BENCH_ann.json's shape changes
K = 10
N_QUERIES = 32
N_CLUSTERS = 8
VOCAB, NNZ, DENSE_DIM = 64, 8, 32
GRAPH_HOPS = 8            # declared (fixed) so budgets compare like-for-like
GRAPH_DEGREE, GRAPH_ROUNDS = 16, 6

# search-budget sweeps: the budget axis is ef for the graph backend and
# num_search for NAPP; the LAST (largest) budget is the contract point —
# validate_bench requires its recall to meet ANN_RECALL_TARGET.
BUDGETS = {"graph_ann": (16, 32, 64, 128), "napp": (4, 8, 16)}
SMOKE_BUDGETS = {"graph_ann": (16, 64), "napp": (4, 8)}


def _spaces(n_docs: int, seed: int):
    """(name, space, queries, corpus) for the three contract spaces, all
    from the planted-cluster family."""
    dq, dc = planted_cluster_dense(n_docs, DENSE_DIM, N_QUERIES, K,
                                   n_clusters=N_CLUSTERS, seed=seed)
    fc, fq = planted_cluster_fused(n_docs, VOCAB, NNZ, DENSE_DIM,
                                   N_QUERIES, K, n_clusters=N_CLUSTERS,
                                   seed=seed)
    return [
        ("dense-ip", DenseSpace("ip"), dq, dc),
        ("sparse", SparseSpace(VOCAB), fq.sparse, fc.sparse),
        ("fused", FusedSpace(VOCAB, w_dense=0.5, w_sparse=1.5), fq, fc),
    ]


def _backend(method: str, budget: int):
    if method == "graph_ann":
        return make_backend("graph_ann", ef=budget, hops=GRAPH_HOPS,
                            degree=GRAPH_DEGREE, rounds=GRAPH_ROUNDS)
    return make_backend("napp", num_search=budget, min_times=1)


def _dist_frac(method: str, backend, n: int) -> float:
    """Unique distance evaluations per query as a fraction of brute
    force (estimate: entry scan + deduped frontier expansion for the
    graph; pivot scan + re-rank for NAPP)."""
    if method == "graph_ann":
        dists = min(int(n ** 0.5) + GRAPH_HOPS * backend.ef * backend.degree,
                    n)
    else:
        dists = min(backend.num_pivots + backend.rerank_qty, n)
    return dists / n


def sweep(n_docs: int, budgets, seed: int = 0, csv_rows=None):
    rows = []
    print("\n=== ANN efficiency/recall trade-off (via execution backends) "
          "===")
    for space_name, space, queries, corpus in _spaces(n_docs, seed):
        exact = exact_topk(space, queries, corpus, K)
        for method, axis in budgets.items():
            for budget in axis:
                backend = _backend(method, budget)
                # warm the index cache eagerly so the jit trace folds a
                # concrete index in as constants (timing measures search,
                # not a rebuild staged into the jaxpr)
                q1 = jax.tree.map(lambda x: x[:1], queries)
                jax.block_until_ready(
                    backend.topk(space, q1, corpus, K).scores)
                fn = jax.jit(lambda q, b=backend: b.topk(
                    space, q, corpus, K))
                us, tk = time_call(fn, queries)
                rec = float(topk_recall(exact.indices, tk.indices))
                frac = _dist_frac(method, backend, n_docs)
                qps = N_QUERIES / (us / 1e6)
                rows.append({"space": space_name, "method": method,
                             "budget": int(budget),
                             "identity": backend.identity,
                             "recall": round(rec, 4),
                             "dist_frac": round(frac, 4),
                             "qps": round(qps, 1)})
                print(f"{space_name:9s} {method:9s} budget={budget:4d}: "
                      f"recall@{K} {rec:.3f} dist-frac {frac:.3f} "
                      f"qps {qps:.0f}")
                if csv_rows is not None:
                    csv_rows.append(
                        (f"ann/{space_name}/{method}_b{budget}/recall",
                         0.0, round(rec, 4)))
            top = rows[-1]             # largest budget = contract point
            assert top["recall"] >= ANN_RECALL_TARGET, (
                f"{space_name}/{method} recall {top['recall']} at max "
                f"budget {top['budget']} below declared target "
                f"{ANN_RECALL_TARGET}")
    return rows


def write_artifact(rows, budgets, n_docs: int, out_path: str):
    payload = {
        "bench": "ann_tradeoff", "schema": BENCH_SCHEMA,
        "n_docs": n_docs, "k": K,
        "platform": jax.default_backend(),
        "recall_target": ANN_RECALL_TARGET,
        "requested": {
            "spaces": ["dense-ip", "sparse", "fused"],
            "budgets": {m: list(a) for m, a in budgets.items()},
        },
        "rows": rows,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {out_path} ({len(rows)} rows)")
    return payload


def run(csv_rows, seed=0, k=10, out_path="BENCH_ann.json", smoke=False):
    """benchmarks.run entry point (and the CLI's worker)."""
    n_docs = 256 if smoke else 2048
    budgets = SMOKE_BUDGETS if smoke else BUDGETS
    rows = sweep(n_docs, budgets, seed=seed, csv_rows=csv_rows)
    write_artifact(rows, budgets, n_docs, out_path)
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny preset for CI (n=256, two budgets per "
                         "method)")
    ap.add_argument("--out", default="BENCH_ann.json",
                    help="artifact path (default BENCH_ann.json)")
    args = ap.parse_args(argv)
    run([], smoke=args.smoke, out_path=args.out)
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
