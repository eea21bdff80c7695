"""Served-funnel sweep: rerank serve-width x per-stage rerank budget.

Stands up one :class:`~repro.serving.funnel.FunnelPipeline` endpoint per
cell — staged candgen -> learned fusion -> neural rerank served as ONE
endpoint via ``EndpointSpec`` — and replays a fixed query workload.  The
rerank stage carries a known injected cost (a host-side delay on top of
the deterministic re-scorer), so the budget axis actually bites: a
``None`` budget never degrades, a budget below the injected cost forces
the funnel's counted degradation on every batch after the first (the
first batch always runs, seeding the EWMA cost estimate and counting one
overrun), and a generous budget runs the full funnel everywhere.

Each (rerank_keep, budget_ms) cell reports served qps and e2e latency,
the per-stage p50s from ``EndpointSnapshot.stages``, the degradation
bookkeeping (``fallbacks`` / ``overruns`` / ``rerank_runs`` /
``occupancy``), and — the contract point, gated in every mode —
``identity_ok``: every served answer is bit-identical to one of exactly
two offline references, the full funnel (``apply_rerankers`` with both
stages) or the degraded funnel (fusion-only, truncated to the serve
width).  There is no third behavior; a budget can cost you the rerank
stage, never the correctness of what is served.

Emits ``BENCH_funnel.json`` (schema 1, ``bench: funnel_serve``); the
``funnel_serve`` dispatch in ``benchmarks/validate_bench.py`` re-checks
the cell matrix, the identity honesty, the fallback-rate coherence
(``0 <= fallbacks <= n_batches``; unbudgeted rows never fall back), and
that the stage latencies sum to no more than the e2e latency plus slack
(the stages really are inside the served path, not measured elsewhere).

    PYTHONPATH=src:. python benchmarks/funnel_bench.py [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# script-mode shim: `python benchmarks/funnel_bench.py` puts benchmarks/
# itself on sys.path, not the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import planted_cluster_dense
from repro.core.pipeline import (BruteForceGenerator, _reorder,
                                 apply_rerankers)
from repro.core.spaces import DenseSpace
from repro.serving import (EndpointSpec, FunnelPipeline, RetrievalService,
                           StageBudget)

N_DOCS = 4096
DIM = 64
UNIQUE_QUERIES = 128
REQUESTS = 192
BATCH_SIZE = 4
CAND_QTY = 100
FUSION_QTY = 50
RERANK_KEEPS = (5, 10)
# the budget axis: None (never degrade), tight (below the injected cost
# -> every post-seeding batch degrades), generous (never trips)
BUDGETS_MS = (None, 0.5, 50.0)
RERANK_COST_S = 0.002      # injected host-side delay per rerank call
SEED = 0
BENCH_SCHEMA = 1

# --smoke: the tiny CI preset — same code paths, artifact schema and
# validator, small enough for a benchmark smoke job on a shared runner
SMOKE_OVERRIDES = dict(N_DOCS=512, UNIQUE_QUERIES=32, REQUESTS=48,
                      RERANK_KEEPS=(5,))


class _BiasRerank:
    """Deterministic re-scorer (score + id-hash bias) with an optional
    injected host-side cost, so the budget axis measures something."""

    def __init__(self, scale: float, cost_s: float = 0.0):
        self.scale = scale
        self.cost_s = cost_s
        self.calls = 0

    def rerank(self, q_tokens, cands, keep):
        self.calls += 1
        if self.cost_s:
            time.sleep(self.cost_s)
        bias = (cands.indices % 7).astype(jnp.float32) * self.scale
        mask = jnp.isfinite(cands.scores)
        return _reorder(cands, jnp.where(mask, cands.scores + bias,
                                         -jnp.inf), keep)


def _references(corpus, queries, keep):
    """The two legal served behaviors for a cell, precomputed offline:
    full funnel (fusion + rerank) and degraded funnel (fusion only,
    truncated to the serve width)."""
    gen = BruteForceGenerator(DenseSpace("ip"), corpus)
    cands = gen.generate(queries, CAND_QTY)
    full = apply_rerankers(cands, None, intermediate=_BiasRerank(0.5),
                           final=_BiasRerank(2.0), interm_qty=FUSION_QTY,
                           final_qty=keep)
    degraded = apply_rerankers(cands, None, intermediate=_BiasRerank(0.5),
                               final=None, interm_qty=FUSION_QTY,
                               final_qty=keep)
    return (np.asarray(full.indices), np.asarray(full.scores),
            np.asarray(degraded.indices), np.asarray(degraded.scores))


def run_cell(corpus, queries, workload, *, keep: int,
             budget_ms) -> dict:
    """One (rerank_keep, budget_ms) cell: fresh funnel endpoint, serve
    the workload one request at a time (deterministic batch boundaries
    -> deterministic degradation counts), check every answer against the
    two-behavior contract."""
    rerank = _BiasRerank(2.0, cost_s=RERANK_COST_S)
    funnel = FunnelPipeline(
        BruteForceGenerator(DenseSpace("ip"), corpus),
        fusion=_BiasRerank(0.5), rerank=rerank,
        cand_qty=CAND_QTY, fusion_qty=FUSION_QTY, rerank_keep=keep)
    budget = None if budget_ms is None else StageBudget(
        rerank_s=budget_ms / 1e3)
    spec = EndpointSpec(batch_size=BATCH_SIZE, max_wait_s=0.001,
                        budget=budget, rerank_keep=keep)
    fi, fs, di, ds = _references(corpus, queries, keep)

    identity_ok = True
    with RetrievalService(cache_size=0) as svc:
        svc.register_pipeline("funnel", funnel, queries[0], spec=spec)
        # warm the trace/dispatch caches off the clock, then reset; the
        # warm-up also seeds the served funnel's rerank EWMA, so tight-
        # budget cells measure steady-state degradation (the seeding
        # batch and its overrun land outside the measured window)
        svc.retrieve([queries[i % UNIQUE_QUERIES] for i in range(8)],
                     endpoint="funnel")
        svc.reset_stats()
        t0 = time.perf_counter()
        futs = [svc.submit(queries[i], endpoint="funnel")
                for i in workload]
        outs = [f.result() for f in futs]
        wall = time.perf_counter() - t0
        ep = svc.snapshot().endpoints["funnel"]

    for q, out in zip(workload, outs):
        is_full = (np.array_equal(out.indices, fi[q])
                   and np.array_equal(out.scores, fs[q]))
        is_degraded = (np.array_equal(out.indices, di[q])
                       and np.array_equal(out.scores, ds[q]))
        if not (is_full or is_degraded):
            identity_ok = False
    fallbacks = ep.stage_fallbacks["rerank"]
    assert identity_ok, (
        f"cell (keep={keep}, budget={budget_ms}) served an answer that "
        "is neither the full-funnel nor the degraded reference")

    stage_p50 = {s: (ep.stages[s].p50_ms if s in ep.stages
                     and ep.stages[s].count else None)
                 for s in ("candgen", "fusion", "rerank")}
    return {
        "rerank_keep": keep,
        "budget_ms": budget_ms,
        "identity": ep.backend,
        "qps": len(futs) / wall,
        "p50_ms": ep.e2e.p50_ms,
        "p99_ms": ep.e2e.p99_ms,
        "stage_p50_ms": stage_p50,
        "n_batches": int(ep.n_batches),
        "rerank_runs": int(ep.stages["rerank"].count
                           if "rerank" in ep.stages else 0),
        "fallbacks": int(fallbacks),
        "overruns": int(ep.stage_overruns["rerank"]),
        "occupancy": float(ep.stage_occupancy["rerank"]),
        "identity_ok": bool(identity_ok),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI preset (same code paths and artifact)")
    ap.add_argument("--out", default="BENCH_funnel.json",
                    help="artifact path (default: %(default)s)")
    args = ap.parse_args(argv)
    if args.smoke:
        globals().update(SMOKE_OVERRIDES)
    mode = "smoke" if args.smoke else "full"

    space_queries, corpus = planted_cluster_dense(
        N_DOCS, DIM, UNIQUE_QUERIES, max(RERANK_KEEPS), seed=SEED)
    queries = space_queries[:UNIQUE_QUERIES]
    rng = np.random.default_rng(SEED)
    workload = rng.integers(0, UNIQUE_QUERIES, REQUESTS).astype(np.int64)

    hdr = (f"{'keep':>5} {'budget':>7} {'qps':>8} {'p99_ms':>8} "
           f"{'batches':>7} {'reranks':>7} {'fallbk':>6} {'overrun':>7} "
           f"{'occup':>6}")
    print(f"funnel_serve [{mode}]: {N_DOCS} docs, {REQUESTS} requests, "
          f"cand {CAND_QTY} -> fuse {FUSION_QTY} -> keep, injected "
          f"rerank cost {1e3 * RERANK_COST_S:.1f}ms\n\n{hdr}\n"
          + "-" * len(hdr))

    rows = []
    for keep in RERANK_KEEPS:
        for budget_ms in BUDGETS_MS:
            r = run_cell(corpus, queries, workload, keep=keep,
                         budget_ms=budget_ms)
            rows.append(r)
            b = "none" if budget_ms is None else f"{budget_ms:.1f}"
            print(f"{keep:>5} {b:>7} {r['qps']:>8.1f} "
                  f"{r['p99_ms']:>8.2f} {r['n_batches']:>7} "
                  f"{r['rerank_runs']:>7} {r['fallbacks']:>6} "
                  f"{r['overruns']:>7} {r['occupancy']:>6.2f}")

    payload = {
        "bench": "funnel_serve",
        "schema": BENCH_SCHEMA,
        "mode": mode,
        "n_docs": N_DOCS,
        "dim": DIM,
        "requests": REQUESTS,
        "platform": jax.devices()[0].platform,
        "rerank_cost_ms": 1e3 * RERANK_COST_S,
        "requested": {"rerank_keeps": list(RERANK_KEEPS),
                      "budgets_ms": list(BUDGETS_MS)},
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"\nwrote {args.out} (two-behavior identity held in every "
          "cell; unbudgeted rows never degraded)")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
