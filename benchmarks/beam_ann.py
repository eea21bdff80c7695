"""The kernel-ANN speed claim, measured: graph beam traversal through the
fused Pallas hop kernel (``kernels/beam_topk.py``) vs the exact scan, at
corpus sizes where sub-linear search actually matters.

Exact scan cost grows linearly in N; the beam traversal's cost is
``hops * ef * degree`` candidate scores per query regardless of N.  This
bench pins the crossover as a tracked artifact: at the largest corpus
(10M rows in ``--full``) the kernel path must be at least
``SPEEDUP_TARGET``x faster than the exact streaming scan while holding
recall@k >= ``ANN_RECALL_TARGET`` against that same exact run — the
measured-recall contract tier, now with a measured *speed* side.

The corpus is the planted-cluster family every ANN gate runs on
(``benchmarks/common.py``), and the graph is its exact k-NN graph in
closed form (``planted_cluster_graph``) — the same graph NN-descent
converges to, built analytically because an O(N * degree^2 * rounds)
construction at 10M rows would dwarf the thing being measured.  The jnp
traversal (``kernel=off``) rides along at sizes where its dense
``bool[B, N]`` visited table is reasonable, so the artifact also records
what the kernel buys over the library hop loop.

Rows land in ``BENCH_beam_ann.json`` (schema checked by
``benchmarks/validate_bench.py`` in CI; the smoke variant runs the same
cells at a small N without the speedup gate — interpret-mode overhead
dominates tiny corpora).

    PYTHONPATH=src:. python -m benchmarks.beam_ann [--smoke] [--out F]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import numpy as np

# script-mode shim: `python benchmarks/beam_ann.py` puts benchmarks/
# itself on sys.path, not the repo root that `benchmarks.common` needs
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import (planted_cluster_dense, planted_cluster_fused,
                               planted_cluster_graph, time_call)
from repro.core import graph_ann
from repro.core.backends import ANN_RECALL_TARGET, GraphANNBackend, make_backend
from repro.core.fusion import topk_recall
from repro.core.spaces import DenseSpace, FusedSpace, SparseSpace

BENCH_SCHEMA = 1          # bumped when BENCH_beam_ann.json's shape changes
K = 10
N_QUERIES = 32
N_CLUSTERS = 8
VOCAB, NNZ, DENSE_DIM = 64, 8, 32
DEGREE, EF, HOPS = 16, 64, 4
SPEEDUP_TARGET = 10.0     # kernel vs exact at the largest full-mode corpus

# Full mode: the headline dense cells at 1M and 10M rows (the 10M cell
# carries the speedup gate), sparse and fused at 1M so every contract
# space has a measured kernel-traversal row.  The jnp hop loop's dense
# [B, N] visited table caps the sizes it rides along at.
FULL_SIZES = {"dense-ip": (1_000_000, 10_000_000),
              "sparse": (1_000_000,), "fused": (1_000_000,)}
SMOKE_SIZES = {"dense-ip": (8192,), "sparse": (8192,), "fused": (8192,)}
JNP_PATH_MAX_N = 1_000_000


def _space_data(space_name: str, n_docs: int, seed: int):
    if space_name == "dense-ip":
        q, c = planted_cluster_dense(n_docs, DENSE_DIM, N_QUERIES, K,
                                     n_clusters=N_CLUSTERS, seed=seed)
        return DenseSpace("ip"), q, c
    fc, fq = planted_cluster_fused(n_docs, VOCAB, NNZ, DENSE_DIM,
                                   N_QUERIES, K, n_clusters=N_CLUSTERS,
                                   seed=seed)
    if space_name == "sparse":
        return SparseSpace(VOCAB), fq.sparse, fc.sparse
    return FusedSpace(VOCAB, w_dense=0.5, w_sparse=1.5), fq, fc


def _ann_identity(kernel: bool) -> str:
    # rounds=0 marks the analytically-built exact k-NN graph (no
    # NN-descent refinement ran); every searched budget is declared
    return GraphANNBackend(degree=DEGREE, rounds=0, ef=EF, hops=HOPS,
                           kernel=kernel).identity


def _paths(n_docs: int):
    paths = ["exact", "kernel_ann"]
    if n_docs <= JNP_PATH_MAX_N:
        paths.append("jnp_ann")
    return paths


def plan_cells(sizes):
    return [[space, int(n), path]
            for space, ns in sizes.items()
            for n in ns
            for path in _paths(int(n))]


def run_cell(space_name, space, queries, corpus, index, n_docs, path,
             exact_ids, exact_ms):
    """One measured row.  ``exact_ids``/``exact_ms`` are None for the
    exact row itself (it IS the oracle and the baseline)."""
    # corpus/index ride as jit ARGUMENTS, not closure captures: a
    # closed-over 10M-row array becomes an XLA constant and constant
    # folding over it stalls compilation for minutes
    if path == "exact":
        backend = make_backend("streaming")
        fn = jax.jit(lambda q, c, i: backend.topk(space, q, c, K))
        identity = backend.identity
    elif path == "kernel_ann":
        fn = jax.jit(lambda q, c, i: graph_ann.kernel_beam_search(
            space, q, c, i, n_docs, k=K, ef=EF, hops=HOPS))
        identity = _ann_identity(kernel=True)
    else:
        fn = jax.jit(lambda q, c, i: graph_ann.beam_search(
            space, q, c, i, n_docs, k=K, ef=EF, hops=HOPS))
        identity = _ann_identity(kernel=False)
    us, tk = time_call(fn, queries, corpus, index)
    ms = us / 1e3
    recall = (1.0 if exact_ids is None
              else float(topk_recall(exact_ids, tk.indices)))
    speedup = 1.0 if exact_ms is None else exact_ms / ms
    row = {"space": space_name, "n_docs": int(n_docs), "path": path,
           "identity": identity, "ms_per_batch": round(ms, 3),
           "qps": round(N_QUERIES / (ms / 1e3), 1),
           "recall": round(recall, 4),
           "speedup_vs_exact": round(speedup, 2)}
    print(f"{space_name:9s} n={n_docs:>9d} {path:10s}: "
          f"{ms:9.1f} ms/batch  recall@{K} {recall:.3f}  "
          f"speedup {speedup:6.2f}x")
    return row, tk


def sweep(sizes, seed: int = 0, csv_rows=None):
    rows = []
    print("\n=== kernel-ANN vs exact scan (beam traversal kernel) ===")
    for space_name, ns in sizes.items():
        for n_docs in ns:
            space, queries, corpus = _space_data(space_name, int(n_docs),
                                                 seed)
            index = planted_cluster_graph(int(n_docs), DEGREE,
                                          n_clusters=N_CLUSTERS)
            exact_row, exact_tk = run_cell(space_name, space, queries,
                                           corpus, index, n_docs, "exact",
                                           None, None)
            rows.append(exact_row)
            for path in _paths(int(n_docs))[1:]:
                row, _ = run_cell(space_name, space, queries, corpus,
                                  index, n_docs, path,
                                  np.asarray(exact_tk.indices),
                                  exact_row["ms_per_batch"])
                rows.append(row)
                assert row["recall"] >= ANN_RECALL_TARGET, (
                    f"{space_name}@{n_docs}/{path} recall {row['recall']} "
                    f"below target {ANN_RECALL_TARGET}")
                if csv_rows is not None:
                    csv_rows.append(
                        (f"beam_ann/{space_name}/n{n_docs}/{path}/speedup",
                         0.0, row["speedup_vs_exact"]))
    return rows


def write_artifact(rows, sizes, mode: str, out_path: str):
    payload = {
        "bench": "beam_ann", "schema": BENCH_SCHEMA, "mode": mode,
        "k": K, "n_queries": N_QUERIES,
        "platform": jax.default_backend(),
        "recall_target": ANN_RECALL_TARGET,
        "speedup_target": SPEEDUP_TARGET,
        "graph": {"degree": DEGREE, "ef": EF, "hops": HOPS,
                  "source": "analytic planted-cluster exact k-NN graph"},
        "requested": {"cells": plan_cells(sizes)},
        "rows": rows,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {out_path} ({len(rows)} rows)")
    return payload


def run(csv_rows, seed=0, k=10, out_path="BENCH_beam_ann.json",
        smoke=False):
    """benchmarks.run entry point (and the CLI's worker)."""
    sizes = SMOKE_SIZES if smoke else FULL_SIZES
    mode = "smoke" if smoke else "full"
    rows = sweep(sizes, seed=seed, csv_rows=csv_rows)
    if not smoke:
        # the headline gate, asserted here AND recorded in the artifact
        # (validate_bench re-derives it from the rows in CI)
        top_n = max(n for ns in sizes.values() for n in ns)
        gate = [r for r in rows
                if r["n_docs"] == top_n and r["path"] == "kernel_ann"]
        for r in gate:
            assert r["speedup_vs_exact"] >= SPEEDUP_TARGET, (
                f"kernel-ANN speedup {r['speedup_vs_exact']}x at "
                f"n={top_n} below the {SPEEDUP_TARGET}x gate")
    write_artifact(rows, sizes, mode, out_path)
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny preset for CI (n=8192 per space, no "
                         "speedup gate — interpret overhead dominates)")
    ap.add_argument("--out", default="BENCH_beam_ann.json",
                    help="artifact path (default BENCH_beam_ann.json)")
    args = ap.parse_args(argv)
    run([], smoke=args.smoke, out_path=args.out)
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
