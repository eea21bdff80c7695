"""Serving latency/throughput frontier: batch-size x deadline x cache,
plus shard-count, overload (admission-control), and execution-backend x
corpus-dtype sweeps.

Stands up a fresh :class:`RetrievalService` per configuration around a
brute-force dense funnel, replays a repeated-query workload (hot-set
skew, the cache's reason to exist), and reports qps + e2e p50/p99 per
point — the latency/throughput frontier the continuous batcher's two
knobs trace out, and the cache's effect on top.

The shard sweep serves the same corpus as a :class:`ShardedPipeline`
behind one endpoint for K in {1, 2, 4} and verifies every shard count
returns bit-identical results.  The overload sweep floods a bounded
admission queue (a deliberately slowed runner) under each policy and
reports served/rejected/shed, the maximum observed queue depth, and p99
under overload — the depth stays bounded instead of growing without
limit.  The backend sweep serves the same corpora — one dense, one fused
(mixed dense+sparse, the paper's novel representation), each at BOTH
residency dtypes (f32 and bf16) — through each execution backend
(reference / streaming / pallas-interpret), asserts the two-tier
precision contract (bitwise within a dtype, recall@k == 1.0 across
tiers), and emits one row per (space, dtype, backend) to
``BENCH_backends.json`` as a trajectory point whose schema
``benchmarks/validate_bench.py`` checks in CI (interpret-mode kernel
wall-clock is a correctness trace, not TPU perf — see
``benchmarks/kernel_bench.py``).

    PYTHONPATH=src python benchmarks/serve_bench.py [--preset smoke]
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

# script-mode shim: `python benchmarks/serve_bench.py` puts benchmarks/
# itself on sys.path, not the repo root that `benchmarks.common` needs
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import planted_margin_dense, planted_margin_fused
from benchmarks.grids import (BACKENDS, BATCH_SIZES, CACHE_SIZES,
                              DEADLINES_S, DTYPES, OVERLOAD_POLICIES,
                              SHARD_COUNTS, SMOKE_BATCH_SIZES,
                              SMOKE_DEADLINES_S, SMOKE_SHARD_COUNTS, SPACES)
from repro.core.brute_force import exact_topk
from repro.core.fusion import require_bf16_margin, topk_recall
from repro.core.pipeline import BruteForceGenerator, RetrievalPipeline
from repro.core.sparse import SparseVectors
from repro.core.spaces import DenseSpace, FusedSpace, FusedVectors
from repro.serving import (RetrievalService, ServiceOverloaded,
                           ShardedPipeline)

N_DOCS = 4096
DIM = 64
UNIQUE_QUERIES = 256
HOT_QUERIES = 16          # hot set receiving HOT_TRAFFIC of the stream
HOT_TRAFFIC = 0.5
# sweep grids live in benchmarks/grids.py (imported above), shared with
# the autotuner's seed population so the "beats the best grid point"
# gate in BENCH_pareto.json can't drift from what this bench measures
OVERLOAD_DEPTH = 32       # admission-queue bound during the flood
BENCH_SCHEMA = 2          # bumped when BENCH_backends.json's shape changes
FUSED_VOCAB = 512
FUSED_NNZ = 16
FUSED_REQUESTS = 96       # the fused reference path is heavier per query

# --preset smoke: the tiny CI preset — same code paths and assertions,
# small enough for a benchmark smoke job on a shared runner
SMOKE_OVERRIDES = dict(N_DOCS=1024, UNIQUE_QUERIES=64,
                       BATCH_SIZES=SMOKE_BATCH_SIZES,
                       DEADLINES_S=SMOKE_DEADLINES_S,
                       SHARD_COUNTS=SMOKE_SHARD_COUNTS,
                       FUSED_REQUESTS=32)


def make_workload(n_requests: int, seed: int = 0) -> np.ndarray:
    """Query indices with a hot set: repeats -> cache hits when enabled."""
    rng = np.random.default_rng(seed)
    hot = rng.random(n_requests) < HOT_TRAFFIC
    idx = np.where(hot, rng.integers(0, HOT_QUERIES, n_requests),
                   rng.integers(0, UNIQUE_QUERIES, n_requests))
    return idx.astype(np.int64)


def run_config(pipe, queries, warmup_queries, workload, *, batch_size: int,
               deadline_s: float, cache_size: int):
    svc = RetrievalService(cache_size=cache_size)
    svc.register_pipeline("dense", pipe, queries[0],
                          batch_size=batch_size, max_wait_s=deadline_s,
                          jit=True)
    with svc:
        # warm-up: one full batch triggers the jit compile off the clock;
        # warm-up queries are OUTSIDE the workload pool (no free cache
        # hits), and stats reset after so snapshots cover only real load
        svc.retrieve([warmup_queries[i % warmup_queries.shape[0]]
                      for i in range(batch_size)], endpoint="dense")
        svc.reset_stats()
        # two replays of the same stream: queries repeat within AND across
        # passes, so a cache's win is structural, not scheduling noise
        t0 = time.perf_counter()
        n_served = 0
        for _ in range(2):
            futs = [svc.submit(queries[i], endpoint="dense")
                    for i in workload]
            for f in futs:
                f.result()
            n_served += len(futs)
        wall = time.perf_counter() - t0
        snap = svc.snapshot()
    ep = snap.endpoints["dense"]
    return {
        "qps": n_served / wall,
        "p50_ms": ep.e2e.p50_ms,
        "p99_ms": ep.e2e.p99_ms,
        "fill": ep.mean_batch_fill,
        "hit_rate": snap.cache_hit_rate,
        "batches": ep.n_batches,
    }


def run_shard_sweep(space, corpus, queries, warmup_queries, workload):
    """Same corpus, same workload, K shards behind one endpoint."""
    results, reference = {}, None
    check_n = 8                              # queries compared across K
    for n_shards in SHARD_COUNTS:
        pipe = ShardedPipeline.from_corpus(space, corpus, n_shards,
                                           cand_qty=100, final_qty=10)
        svc = RetrievalService(cache_size=0)
        svc.register_pipeline("dense", pipe, queries[0],
                              batch_size=16, max_wait_s=0.005)
        with svc:
            svc.retrieve([warmup_queries[i % warmup_queries.shape[0]]
                          for i in range(16)], endpoint="dense")
            svc.reset_stats()
            t0 = time.perf_counter()
            futs = [svc.submit(queries[i], endpoint="dense")
                    for i in workload]
            for f in futs:
                f.result()
            wall = time.perf_counter() - t0
            snap = svc.snapshot()      # before the identity check: latency
            check = svc.retrieve([queries[i] for i in range(check_n)],
                                 endpoint="dense")   # stays workload-only
        pipe.close()
        ep = snap.endpoints["dense"]
        results[n_shards] = {"qps": len(futs) / wall,
                             "p50_ms": ep.e2e.p50_ms, "p99_ms": ep.e2e.p99_ms}
        if reference is None:
            reference = check
        else:
            for a, b in zip(reference, check):
                assert np.array_equal(a.scores, b.scores)
                assert np.array_equal(a.indices, b.indices)
    return results


def _sweep_endpoint(pipe, pick_query, warmup, workload, *,
                    corpus_dtype="float32", f32_check=None):
    """One endpoint per execution backend over the same corpus+workload
    at one residency dtype: returns per-backend rows plus the spot-check
    result set.  Within the dtype, results must be bit-identical across
    backends (all paths are exact over the same stored values); when the
    f32 tier's check set is supplied, the bf16 tier must additionally
    reach recall == 1.0 against it (the two-tier precision contract)."""
    rows, reference, check = [], None, None
    check_n = 8
    for backend in BACKENDS:
        svc = RetrievalService(cache_size=0)
        svc.register_pipeline("ep", pipe, pick_query(0),
                              batch_size=16, max_wait_s=0.005,
                              backend=backend, corpus_dtype=corpus_dtype)
        with svc:
            svc.retrieve(warmup, endpoint="ep")
            svc.reset_stats()
            t0 = time.perf_counter()
            futs = [svc.submit(pick_query(i), endpoint="ep")
                    for i in workload]
            for f in futs:
                f.result()
            wall = time.perf_counter() - t0
            snap = svc.snapshot()
            check = svc.retrieve([pick_query(i) for i in range(check_n)],
                                 endpoint="ep")
        ep = snap.endpoints["ep"]
        # each endpoint must really have RUN its requested backend and
        # dtype — a silent capability fallback would publish rows that
        # all measured the reference path
        assert ep.backend and ep.backend.startswith(backend), \
            f"stats should surface the {backend} backend: {ep.backend!r}"
        assert ep.corpus_dtype == corpus_dtype, \
            f"stats should surface dtype {corpus_dtype}: {ep.corpus_dtype!r}"
        rows.append({"backend": backend, "dtype": corpus_dtype,
                     "identity": ep.backend, "corpus_dtype": ep.corpus_dtype,
                     "qps": len(futs) / wall,
                     "p50_ms": ep.e2e.p50_ms, "p99_ms": ep.e2e.p99_ms})
        if reference is None:
            reference = check
        else:
            for a, b in zip(reference, check):
                assert np.array_equal(a.scores, b.scores), backend
                assert np.array_equal(a.indices, b.indices), backend
    if f32_check is not None:
        rec = topk_recall(np.stack([np.asarray(r.indices) for r in f32_check]),
                          np.stack([np.asarray(r.indices) for r in reference]))
        assert rec == 1.0, \
            f"{corpus_dtype} tier recall vs f32 oracle {rec} != 1.0"
    return rows, reference


def run_backend_sweep(pipe, queries, warmup_queries, workload,
                      out_path: str):
    """Dense AND fused corpora through every (execution backend x
    residency dtype) cell.

    The dense endpoints exercise ``kernels/mips_topk.py``; the fused
    endpoints exercise the one-pass fused score+select kernel
    (``kernels/fused_topk.py``) against the reference and streaming
    paths.  Per (space, dtype, backend) qps/p50/p99 rows land in
    ``out_path`` as one trajectory point, with the request matrix
    recorded so ``benchmarks/validate_bench.py`` can verify every
    requested cell actually ran."""
    warmup = [warmup_queries[i % warmup_queries.shape[0]] for i in range(16)]
    rows = []
    # recall-gate validity: the spot-check queries' f32 top-10 must be
    # margin-separated from rank 11 beyond the bf16 perturbation bound
    # (2^-8 x the absolute-valued score — the data is margin-planted,
    # this verifies it stayed that way)
    corpus = pipe.generator.corpus
    pert = float(jnp.max(jnp.abs(queries[:8]) @ jnp.abs(corpus).T)) * 2.0**-8
    require_bf16_margin(
        np.asarray(exact_topk(pipe.generator.space, queries[:8],
                              corpus, 11).scores),
        pert_bound=pert)
    f32_check = None
    for dtype in DTYPES:
        dtype_rows, check = _sweep_endpoint(
            pipe, lambda i: queries[i % queries.shape[0]], warmup, workload,
            corpus_dtype=dtype, f32_check=f32_check)
        for r in dtype_rows:
            rows.append({"space": "dense", **r})
        if dtype == "float32":
            f32_check = check

    # fused corpus: the paper's mixed dense+sparse representation,
    # margin-planted (benchmarks/common.py; numpy generator so the data
    # is identical across jax pins)
    fused_corpus, fused_queries = planted_margin_fused(
        N_DOCS, FUSED_VOCAB, FUSED_NNZ, DIM, UNIQUE_QUERIES, 16, seed=7)
    fused_space = FusedSpace(FUSED_VOCAB, w_dense=0.6, w_sparse=0.4)
    fused_pipe = RetrievalPipeline(
        BruteForceGenerator(fused_space, fused_corpus),
        cand_qty=100, final_qty=10)
    pick = lambda i: jax.tree.map(lambda x: x[i % UNIQUE_QUERIES],
                                  fused_queries)
    check_q = jax.tree.map(lambda x: x[:8], fused_queries)
    abs_tree = lambda fv: FusedVectors(
        jnp.abs(fv.dense), SparseVectors(fv.sparse.indices,
                                         jnp.abs(fv.sparse.values)))
    pert = float(jnp.max(fused_space.score_batch(
        abs_tree(check_q), abs_tree(fused_corpus)))) * 2.0**-8
    require_bf16_margin(
        np.asarray(exact_topk(fused_space, check_q, fused_corpus,
                              11).scores),
        pert_bound=pert)
    f32_check = None
    for dtype in DTYPES:
        dtype_rows, check = _sweep_endpoint(
            fused_pipe, pick, [pick(i) for i in range(16)],
            workload[:FUSED_REQUESTS], corpus_dtype=dtype,
            f32_check=f32_check)
        for r in dtype_rows:
            rows.append({"space": "fused", **r})
        if dtype == "float32":
            f32_check = check

    with open(out_path, "w") as f:
        json.dump({"bench": "serve_backends", "schema": BENCH_SCHEMA,
                   "n_docs": N_DOCS, "dim": DIM,
                   "requests": len(workload),
                   "platform": jax.default_backend(),
                   "fused_meta": {"vocab": FUSED_VOCAB, "nnz": FUSED_NNZ,
                                  "requests": FUSED_REQUESTS},
                   "requested": {"spaces": list(SPACES),
                                 "dtypes": list(DTYPES),
                                 "backends": list(BACKENDS)},
                   "rows": rows}, f, indent=2)
    return rows


def run_overload_sweep(pipe, queries, n_requests: int):
    """Flood a bounded queue through a deliberately slowed runner."""
    jit_run = jax.jit(pipe.run)
    results = {}
    for policy in OVERLOAD_POLICIES:
        def slow_run(q, _tokens):
            time.sleep(0.005)               # force arrival rate > service rate
            return jit_run(q, None)

        svc = RetrievalService(cache_size=0)
        svc.register_runner("dense", slow_run, queries[0],
                            batch_size=16, max_wait_s=0.005,
                            max_queue=OVERLOAD_DEPTH, overload=policy)
        with svc:
            svc.retrieve([queries[i % queries.shape[0]] for i in range(16)],
                         endpoint="dense")
            svc.reset_stats()
            futs, n_rejected, max_depth = [], 0, 0
            for i in range(n_requests):
                try:
                    futs.append(svc.submit(
                        queries[i % queries.shape[0]], endpoint="dense"))
                except ServiceOverloaded:
                    n_rejected += 1
                if i % 8 == 0:
                    max_depth = max(
                        max_depth,
                        svc.snapshot().endpoints["dense"].queue_depth)
            n_shed = 0
            for f in futs:
                try:
                    f.result()
                except ServiceOverloaded:
                    n_shed += 1
            snap = svc.snapshot()
        ep = snap.endpoints["dense"]
        assert ep.rejected == n_rejected and ep.shed == n_shed
        assert max_depth <= OVERLOAD_DEPTH, \
            f"queue depth {max_depth} exceeded bound {OVERLOAD_DEPTH}"
        results[policy] = {
            "served": len(futs) - n_shed, "rejected": n_rejected,
            "shed": n_shed, "max_depth": max_depth, "p99_ms": ep.e2e.p99_ms,
        }
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--preset", choices=("full", "smoke"), default="full",
                    help="smoke = the tiny CI preset (same sweeps and "
                         "assertions, small corpus/grid)")
    ap.add_argument("--backends-out", default="BENCH_backends.json",
                    help="where the backend-sweep trajectory point lands")
    args = ap.parse_args()
    if args.requests <= 0:
        ap.error("--requests must be positive")
    if args.preset == "smoke":
        globals().update(SMOKE_OVERRIDES)
        args.requests = min(args.requests, 96)

    # margin-planted (benchmarks/common.py) so the backend sweep's bf16
    # recall gate is an invariant; numpy generator = identical data
    # across jax pins.  Warmup queries are arbitrary (never asserted on).
    queries, corpus, _planted = planted_margin_dense(N_DOCS, DIM,
                                                     UNIQUE_QUERIES, 16)
    warmup_queries = jnp.asarray(
        np.random.default_rng(2).standard_normal((64, DIM)), jnp.float32)
    pipe = RetrievalPipeline(BruteForceGenerator(DenseSpace("ip"), corpus),
                             cand_qty=100, final_qty=10)
    workload = make_workload(args.requests)

    hdr = (f"{'batch':>5} {'deadline_ms':>11} {'cache':>5} {'qps':>8} "
           f"{'p50_ms':>8} {'p99_ms':>8} {'fill':>5} {'hit%':>5}")
    print(f"serve_bench: {args.requests} requests, {N_DOCS} docs, "
          f"{UNIQUE_QUERIES} unique queries "
          f"({HOT_QUERIES} hot @ {HOT_TRAFFIC:.0%} traffic)\n\n{hdr}\n"
          + "-" * len(hdr))

    cache_cmp = {}
    for batch in BATCH_SIZES:
        for dl in DEADLINES_S:
            for cache in CACHE_SIZES:
                r = run_config(pipe, queries, warmup_queries, workload,
                               batch_size=batch, deadline_s=dl,
                               cache_size=cache)
                tag = "on" if cache else "off"
                print(f"{batch:>5} {1e3 * dl:>11.1f} {tag:>5} "
                      f"{r['qps']:>8.1f} {r['p50_ms']:>8.2f} "
                      f"{r['p99_ms']:>8.2f} {r['fill']:>5.0%} "
                      f"{r['hit_rate']:>5.0%}")
                cache_cmp.setdefault((batch, dl), {})[tag] = r

    qps_on = np.mean([v["on"]["qps"] for v in cache_cmp.values()])
    qps_off = np.mean([v["off"]["qps"] for v in cache_cmp.values()])
    p50_wins = sum(v["on"]["p50_ms"] < v["off"]["p50_ms"]
                   for v in cache_cmp.values())
    print(f"\ncache-on vs cache-off on the repeated-query workload: "
          f"mean qps {qps_on:.0f} vs {qps_off:.0f}, "
          f"p50 better on {p50_wins}/{len(cache_cmp)} configurations")
    if args.preset == "full":
        # statistical claims need the full workload — the smoke preset's
        # tiny request count is scheduling-noise dominated, and its job
        # is exercising the sweeps + artifact schema, not the frontier
        assert qps_on > qps_off, "cache should raise mean throughput"
        assert p50_wins > len(cache_cmp) / 2, "cache should cut median latency"

    # ---- shard-count sweep (bit-identical across K, asserted inside) -------
    shard_res = run_shard_sweep(DenseSpace("ip"), corpus, queries,
                                warmup_queries, workload)
    print(f"\nshard sweep ({args.requests} requests, results bit-identical "
          f"across shard counts):\n"
          f"{'shards':>6} {'qps':>8} {'p50_ms':>8} {'p99_ms':>8}")
    for k, r in shard_res.items():
        print(f"{k:>6} {r['qps']:>8.1f} {r['p50_ms']:>8.2f} "
              f"{r['p99_ms']:>8.2f}")

    # ---- backend x dtype sweep (precision contract asserted inside) --------
    rows = run_backend_sweep(pipe, queries, warmup_queries, workload,
                             args.backends_out)
    print(f"\nbackend x dtype sweep ({args.requests} requests dense / "
          f"{FUSED_REQUESTS} fused; bitwise within dtype, recall@k=1.0 "
          f"across tiers; point written to {args.backends_out}):\n"
          f"{'space':>6} {'dtype':>9} {'backend':>10} {'qps':>8} "
          f"{'p50_ms':>8} {'p99_ms':>8}  identity")
    for r in rows:
        print(f"{r['space']:>6} {r['dtype']:>9} {r['backend']:>10} "
              f"{r['qps']:>8.1f} {r['p50_ms']:>8.2f} {r['p99_ms']:>8.2f}  "
              f"{r['identity']}")

    # ---- overload sweep (bounded queue, counted drops) ---------------------
    over_res = run_overload_sweep(pipe, queries, args.requests)
    print(f"\noverload sweep (queue bound {OVERLOAD_DEPTH}, slowed runner, "
          f"{args.requests} requests):\n"
          f"{'policy':>11} {'served':>7} {'rejected':>8} {'shed':>5} "
          f"{'max_depth':>9} {'p99_ms':>8}")
    for policy, r in over_res.items():
        print(f"{policy:>11} {r['served']:>7} {r['rejected']:>8} "
              f"{r['shed']:>5} {r['max_depth']:>9} {r['p99_ms']:>8.2f}")
    assert over_res["reject"]["rejected"] > 0, \
        "flood should trip the depth limit under policy 'reject'"
    assert over_res["shed_oldest"]["shed"] > 0, \
        "flood should evict queued requests under policy 'shed_oldest'"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
