"""Roofline-pruned Pareto autotune over the serving config space.

Evolves :class:`~repro.serving.autotune.ServingConfig` genomes — backend
x tile x residency dtype x shards x batching x admission x ANN budgets —
under a real :class:`RetrievalService` load generator, with the
zero-cost roofline proxy (``repro.launch.roofline``) pruning each
generation down to a small measured budget.  The hand-picked serve_bench
grid (``benchmarks/grids.py`` — the SAME tuples serve_bench sweeps) is
measured first and seeds the archive, so the evolved front can only ever
improve on the grid, and the artifact's domination gate is against real
grid measurements, not a strawman.

Emits ``BENCH_pareto.json`` (schema 1): every grid and front row carries
its genome, the endpoint identity that proves which path served, and the
measured (qps, p99_ms, recall) objectives.  ``validate_bench.py``'s
``pareto`` dispatch re-derives non-domination and — in ``full`` mode —
the two headline gates this driver also asserts in-process:

* the front strictly dominates the best hand-picked grid point (higher
  qps at equal-or-better recall, or lower p99 at equal-or-better
  recall), and
* the roofline proxy pruned at least half of all generated candidates
  (the counts are in the artifact — the measurement bill, not a claim).

    PYTHONPATH=src:. python benchmarks/autotune_pareto.py [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

# script-mode shim: `python benchmarks/autotune_pareto.py` puts
# benchmarks/ itself on sys.path, not the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import planted_cluster_dense
from benchmarks.grids import serve_grid_configs
from repro.core.brute_force import exact_topk
from repro.core.spaces import DenseSpace
from repro.serving.autotune import (ServingConfig, TunedProfile, autotune,
                                    dominates, measure_config, pareto_front)

N_DOCS = 4096
DIM = 64
UNIQUE_QUERIES = 256
K = 10
REQUESTS = 512            # flood length; replayed PASSES times per run
PASSES = 2                # workload replays per cold run (cache honesty)
REPEATS = 3               # cold runs per config, medians published
GENERATIONS = 3
POPULATION = 32           # candidates generated per generation
MEASURE_BUDGET = 6        # survivors actually load-tested per generation
HOT_QUERIES = 16          # hot set receiving HOT_TRAFFIC of the stream
HOT_TRAFFIC = 0.5
CHECK_N = 16              # queries in the post-run recall spot-check
SEED = 0
BENCH_SCHEMA = 1
PRUNE_FRACTION_TARGET = 0.5

# --smoke: the tiny CI preset — same code paths, artifact schema and
# validator, small enough for a benchmark smoke job on a shared runner
# (the full-mode domination/prune gates are not asserted at this scale)
SMOKE_OVERRIDES = dict(N_DOCS=512, UNIQUE_QUERIES=64, REQUESTS=64,
                       REPEATS=2, GENERATIONS=2, POPULATION=12,
                       MEASURE_BUDGET=4)

# Hand-written corner genomes injected into generation 0 (legality-
# checked and proxy-ranked like any candidate): bounded-admission
# genomes — the axis the hand-picked grid never sweeps, and the one the
# proxy's backlog model puts at the low-latency boundary — plus one ANN
# genome per family.  Exploration hints, not measurements — the proxy
# still decides whether any of them is worth a load test.
EXPLORE_CONFIGS = (
    ServingConfig(backend="reference", batch_size=16, max_wait_s=0.0005,
                  cache_size=4096, max_queue=32, overload="reject"),
    ServingConfig(backend="reference", batch_size=16, max_wait_s=0.0005,
                  cache_size=4096, max_queue=32, overload="shed_oldest"),
    ServingConfig(backend="reference", batch_size=8, max_wait_s=0.0005,
                  cache_size=4096, max_queue=32, overload="reject"),
    ServingConfig(backend="graph_ann", batch_size=64, max_wait_s=0.0005,
                  cache_size=4096, ef=32),
    ServingConfig(backend="napp", batch_size=64, max_wait_s=0.0005,
                  cache_size=4096, num_search=8, rerank_qty=64),
)


def make_workload(n_requests: int, n_unique: int, seed: int) -> np.ndarray:
    """Query indices with a hot set: repeats -> cache hits when enabled."""
    rng = np.random.default_rng(seed)
    hot = rng.random(n_requests) < HOT_TRAFFIC
    idx = np.where(hot, rng.integers(0, HOT_QUERIES, n_requests),
                   rng.integers(0, n_unique, n_requests))
    return idx.astype(np.int64)


def best_grid_points(grid_points):
    """(best-qps, best-p99) grid rows — the targets the front must beat."""
    by_qps = max(grid_points, key=lambda p: p.qps)
    by_p99 = min(grid_points, key=lambda p: p.p99_ms)
    return by_qps, by_p99


def front_beats_grid(front, grid_points) -> bool:
    """True iff some front row strictly improves on the best hand-picked
    grid point: higher qps than the grid's best-qps row at >= its recall,
    or lower p99 than the grid's best-p99 row at >= its recall."""
    by_qps, by_p99 = best_grid_points(grid_points)
    for p in front:
        if p.qps > by_qps.qps and p.recall >= by_qps.recall:
            return True
        if p.p99_ms < by_p99.p99_ms and p.recall >= by_p99.recall:
            return True
    return False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI preset (same code paths and artifact)")
    ap.add_argument("--out", default="BENCH_pareto.json",
                    help="artifact path (default: %(default)s)")
    ap.add_argument("--profile-out", default=None,
                    help="also write the best-qps front row as a "
                         "TunedProfile JSON")
    args = ap.parse_args(argv)
    if args.smoke:
        globals().update(SMOKE_OVERRIDES)
    mode = "smoke" if args.smoke else "full"

    # corpus + oracle: planted clusters (graph-navigable, exact margins)
    # so ANN genomes compete at their honest measured recall
    space = DenseSpace("ip")
    n_pool = UNIQUE_QUERIES + 128       # + warm-up pool, outside workload
    queries, corpus = planted_cluster_dense(N_DOCS, DIM, n_pool, K,
                                            seed=SEED)
    warmup_queries = queries[UNIQUE_QUERIES:]
    queries = queries[:UNIQUE_QUERIES]
    oracle = np.asarray(exact_topk(space, queries, corpus, K).indices)
    workload = make_workload(REQUESTS, UNIQUE_QUERIES, SEED)
    # the replayed stream's actual repeat rate feeds the proxy's cache
    # model (pass 2+ repeats the whole stream, so the cache can win on
    # every re-seen query, not just the hot set)
    n_replayed = PASSES * len(workload)
    repeat_fraction = 1.0 - len(set(workload.tolist())) / n_replayed

    def measure(cfg: ServingConfig):
        return measure_config(cfg, space=space, corpus=corpus,
                              queries=queries,
                              warmup_queries=warmup_queries,
                              workload=workload, k=K,
                              oracle_indices=oracle, check_n=CHECK_N,
                              passes=PASSES, repeats=REPEATS)

    # 1) measure the hand-picked serve_bench grid — the baseline the
    #    evolved front must beat, and the archive's seed population
    grid_configs = serve_grid_configs(smoke=args.smoke)
    print(f"autotune_pareto [{mode}]: measuring {len(grid_configs)} "
          f"hand-picked grid points ({N_DOCS} docs, k={K}, "
          f"{REQUESTS} requests x {PASSES} passes, median of "
          f"{REPEATS} cold runs per point)")
    t0 = time.perf_counter()
    grid_points = []
    for cfg in grid_configs:
        point = measure(cfg)
        if point is None:
            raise RuntimeError(f"grid config served nothing: {cfg}")
        grid_points.append(point)

    # 2) evolve, with the grid as seed points
    result = autotune(measure, k=K, n_docs=N_DOCS, dim=DIM, seed=SEED,
                      generations=GENERATIONS, population=POPULATION,
                      measure_budget=MEASURE_BUDGET,
                      repeat_fraction=repeat_fraction,
                      seed_points=grid_points,
                      explore_configs=EXPLORE_CONFIGS,
                      space=space, corpus=corpus,
                      log=lambda m: print(f"  {m}"))
    wall = time.perf_counter() - t0
    counts = result.counts
    front = result.front
    prune_frac = counts["pruned"] / max(counts["generated"], 1)

    hdr = (f"{'backend':>10} {'qps':>8} {'p50_ms':>8} {'p99_ms':>8} "
           f"{'recall':>7}  config")
    print(f"\nPareto front ({len(front)} of {len(result.archive)} "
          f"measured points, {wall:.0f}s total):\n{hdr}\n" + "-" * len(hdr))
    for p in front:
        c = p.config
        knobs = [f"b={c.batch_size}", f"wait={1e3 * c.max_wait_s:g}ms",
                 f"cache={c.cache_size}"]
        if c.n_shards > 1:
            knobs.append(f"shards={c.n_shards}")
        if c.ef is not None:
            knobs.append(f"ef={c.ef}")
        if c.rerank_qty is not None:
            knobs.append(f"rerank={c.rerank_qty}")
        print(f"{c.backend:>10} {p.qps:>8.1f} {p.p50_ms:>8.2f} "
              f"{p.p99_ms:>8.2f} {p.recall:>7.3f}  {' '.join(knobs)}")
    by_qps, by_p99 = best_grid_points(grid_points)
    print(f"\nbest grid point: qps={by_qps.qps:.1f} "
          f"(recall {by_qps.recall:.3f}), p99={by_p99.p99_ms:.2f}ms "
          f"(recall {by_p99.recall:.3f})")
    print(f"counts: {counts['generated']} generated, "
          f"{counts['pruned']} proxy-pruned ({prune_frac:.0%}), "
          f"{counts['measured']} measured")

    # sanity invariant in every mode: the front really is non-dominated
    for i, p in enumerate(front):
        for q in result.archive:
            assert not dominates(q.objectives(), p.objectives()), \
                f"front[{i}] is dominated by an archive point"

    payload = {
        "bench": "pareto",
        "schema": BENCH_SCHEMA,
        "mode": mode,
        "n_docs": N_DOCS,
        "dim": DIM,
        "k": K,
        "requests": REQUESTS,
        "seed": SEED,
        "platform": jax.devices()[0].platform,
        "objectives": ["qps", "p99_ms", "recall"],
        "prune_fraction_target": PRUNE_FRACTION_TARGET,
        "counts": counts,
        "grid": [p.to_row() for p in grid_points],
        "front": [p.to_row() for p in front],
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"wrote {args.out}")

    if args.profile_out:
        profile = TunedProfile.from_point(max(front, key=lambda p: p.qps))
        with open(args.profile_out, "w") as f:
            f.write(profile.to_json() + "\n")
        print(f"wrote {args.profile_out} ({profile.tag})")

    if mode == "full":
        # the headline gates, also re-derived by validate_bench.py
        assert front_beats_grid(front, grid_points), (
            "evolved front does not dominate the best hand-picked grid "
            "point — autotuning bought nothing")
        assert prune_frac >= PRUNE_FRACTION_TARGET, (
            f"roofline proxy pruned only {prune_frac:.0%} of generated "
            f"candidates (target {PRUNE_FRACTION_TARGET:.0%})")
        print("gates: front beats the best grid point; proxy pruned "
              f"{prune_frac:.0%} >= {PRUNE_FRACTION_TARGET:.0%}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
