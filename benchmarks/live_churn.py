"""Live-corpus churn sweep: write-rate x compaction-interval under load.

Stands up a :class:`RetrievalService` endpoint over a
:class:`~repro.serving.live.LiveCorpus` (the generation-versioned
segment model: frozen main + exactly-scanned append + tombstones) and
replays the serve_bench query workload while a writer thread mutates the
corpus at a fixed rate — interleaved insert and delete batches, the
background compactor waking every ``compact_interval`` seconds.  Each
(write_rate, compact_interval) cell reports served qps, the p99 of the
*snapshot age* sampled throughout the run (how stale the served epoch
gets between swaps — the freshness metric ``EndpointSnapshot`` also
surfaces), and the generation / compaction / tombstone bookkeeping at
the end of the run.

The contract point, gated in every mode: after the run drains and a
final compaction folds append ⊖ tombstones into a fresh single-segment
main, searching through the live path must match the exact frozen oracle
(``segments.frozen_topk`` over the materialized state) at recall@k >=
``recall_target`` — churn and compaction must not have corrupted the
served state.  With the default exact backend the match is bitwise and
recall is exactly 1.0; the gate is stated as a recall bound so an ANN
main (``--backend graph_ann``) is measured under the same schema.

Emits ``BENCH_live.json`` (schema 1, ``bench: live_churn``); the
``live_churn`` dispatch in ``benchmarks/validate_bench.py`` re-checks
the cell matrix, the identity honesty, the recall gate, and the
``generation_final >= compactions >= 1`` bookkeeping in CI.

    PYTHONPATH=src:. python benchmarks/live_churn.py [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import jax
import numpy as np

# script-mode shim: `python benchmarks/live_churn.py` puts benchmarks/
# itself on sys.path, not the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import planted_cluster_dense
from repro.core import segments
from repro.core.fusion import topk_recall
from repro.core.spaces import DenseSpace
from repro.serving import RetrievalService
from repro.serving.live import LiveCorpus

N_DOCS = 4096
DIM = 64
UNIQUE_QUERIES = 256
K = 10
REQUESTS = 512
HOT_QUERIES = 16          # hot set receiving HOT_TRAFFIC of the stream
HOT_TRAFFIC = 0.5
WRITE_BATCH = 2           # rows inserted AND rows deleted per writer tick
WRITE_RATES = (50.0, 200.0, 800.0)       # mutated rows / second
COMPACT_INTERVALS = (0.05, 0.2)          # compactor wake period, seconds
MAX_APPEND = 256          # threshold trigger backing up the interval
BACKEND = "reference"
CHECK_N = 16              # queries in the post-compaction recall gate
RECALL_TARGET = 0.95
AGE_SAMPLE_S = 0.002      # snapshot-age sampling period during load
SEED = 0
BENCH_SCHEMA = 1

# --smoke: the tiny CI preset — same code paths, artifact schema and
# validator, small enough for a benchmark smoke job on a shared runner
SMOKE_OVERRIDES = dict(N_DOCS=512, UNIQUE_QUERIES=64, REQUESTS=96,
                       WRITE_RATES=(50.0, 200.0),
                       COMPACT_INTERVALS=(0.05,), MAX_APPEND=64)


def make_workload(n_requests: int, seed: int) -> np.ndarray:
    """Query indices with a hot set: repeats -> cache hits when enabled."""
    rng = np.random.default_rng(seed)
    hot = rng.random(n_requests) < HOT_TRAFFIC
    idx = np.where(hot, rng.integers(0, HOT_QUERIES, n_requests),
                   rng.integers(0, UNIQUE_QUERIES, n_requests))
    return idx.astype(np.int64)


class _Writer(threading.Thread):
    """Mutates a LiveCorpus at ``rate`` rows/s until stopped: each tick
    inserts WRITE_BATCH fresh rows and deletes WRITE_BATCH previously
    live ones, so the live count stays level while append rows and
    tombstones accumulate for the compactor.  Sole mutator per run, so
    its local live-id ledger is authoritative."""

    def __init__(self, live: LiveCorpus, rate: float, dim: int, seed: int):
        super().__init__(name="churn-writer", daemon=True)
        self.live = live
        self.period = 2 * WRITE_BATCH / rate       # rows per tick / rate
        self.rng = np.random.default_rng(seed)
        self.dim = dim
        self.ids = [int(i) for i in np.asarray(self.live.snapshot().main_ids)]
        self.mutations = 0
        self._halt = threading.Event()

    def stop(self):
        self._halt.set()
        self.join()

    def run(self):
        while not self._halt.is_set():
            rows = self.rng.standard_normal(
                (WRITE_BATCH, self.dim)).astype(np.float32)
            self.ids.extend(int(i) for i in self.live.insert(rows))
            victims = sorted(
                int(self.ids[j]) for j in self.rng.choice(
                    len(self.ids), size=WRITE_BATCH, replace=False))
            self.live.delete(np.asarray(victims, dtype=np.int64))
            gone = set(victims)
            self.ids = [i for i in self.ids if i not in gone]
            self.mutations += 2 * WRITE_BATCH
            self._halt.wait(self.period)


class _AgeSampler(threading.Thread):
    """Samples ``snapshot_age_s`` on a fixed period during the load —
    the distribution the artifact's p99 is computed from."""

    def __init__(self, live: LiveCorpus):
        super().__init__(name="age-sampler", daemon=True)
        self.live = live
        self.ages = []
        self._halt = threading.Event()

    def stop(self):
        self._halt.set()
        self.join()

    def run(self):
        while not self._halt.is_set():
            self.ages.append(self.live.live_stats()["snapshot_age_s"])
            self._halt.wait(AGE_SAMPLE_S)


def run_cell(space, corpus, queries, warmup_queries, workload, *,
             write_rate: float, compact_interval: float, seed: int) -> dict:
    """One (write_rate, compact_interval) cell: fresh LiveCorpus, fresh
    service, measured under concurrent writes, then drained, compacted,
    and recall-gated against the exact frozen oracle."""
    live = LiveCorpus(space, corpus, backend=BACKEND,
                      max_append=MAX_APPEND,
                      compact_interval_s=compact_interval).start()
    svc = RetrievalService(cache_size=1024)
    svc.register_pipeline("live", None, queries[0],
                          batch_size=16, max_wait_s=0.002, live=live)
    writer = _Writer(live, write_rate, corpus.shape[1], seed)
    sampler = _AgeSampler(live)
    try:
        with svc:
            svc.retrieve([warmup_queries[i % warmup_queries.shape[0]]
                          for i in range(16)], endpoint="live")
            svc.reset_stats()
            writer.start()
            sampler.start()
            t0 = time.perf_counter()
            futs = [svc.submit(queries[i], endpoint="live")
                    for i in workload]
            for f in futs:
                f.result()
            wall = time.perf_counter() - t0
            sampler.stop()
            writer.stop()
            snap = svc.snapshot()
        ep = snap.endpoints["live"]

        # drain: fold everything outstanding into a single-segment main
        # (close() first so the final compact is not raced by the
        # background thread; the corpus stays queryable throughout)
        live.close()
        if not live.compact() and live.live_stats()["compactions"] == 0:
            # degenerate corner: the interval compactor already folded
            # everything and nothing has landed since — mutate once so
            # the cell still proves a post-run compaction
            live.delete(live.insert(np.zeros((1, corpus.shape[1]),
                                             dtype=np.float32)))
            live.compact()
        stats = live.live_stats()
        final = live.snapshot()
        assert final.n_append == 0 and final.n_dead == 0, \
            "final compaction left residue"

        # the contract point: the live path over the drained state must
        # match the exact frozen oracle at the same logical state
        frozen, ids = segments.materialize(final)
        oracle = segments.frozen_topk(space, frozen, ids,
                                      queries[:CHECK_N], K, "reference")
        got = live.topk(queries[:CHECK_N], K)
        recall = topk_recall(np.asarray(oracle.indices),
                             np.asarray(got.indices))
        assert recall >= RECALL_TARGET, (
            f"post-compaction recall {recall:.3f} below target "
            f"{RECALL_TARGET} (rate={write_rate}, "
            f"interval={compact_interval})")
    finally:
        if writer.is_alive():
            writer.stop()
        if sampler.is_alive():
            sampler.stop()
        live.close()

    ages = sampler.ages or [0.0]
    return {
        "write_rate": write_rate,
        "compact_interval": compact_interval,
        "identity": ep.backend,
        "qps": len(futs) / wall,
        "p50_ms": ep.e2e.p50_ms,
        "p99_ms": ep.e2e.p99_ms,
        "snapshot_age_p99_ms": 1e3 * float(np.percentile(ages, 99)),
        "post_compaction_recall": float(recall),
        "mutations": writer.mutations,
        "generation_final": int(stats["generation"]),
        "compactions": int(stats["compactions"]),
        "tombstones_final": int(final.n_dead),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI preset (same code paths and artifact)")
    ap.add_argument("--out", default="BENCH_live.json",
                    help="artifact path (default: %(default)s)")
    args = ap.parse_args(argv)
    if args.smoke:
        globals().update(SMOKE_OVERRIDES)
    mode = "smoke" if args.smoke else "full"

    # planted clusters (same generator as the ANN gates) so an ANN main
    # competes at honest recall; exact backends are oblivious to it
    space = DenseSpace("ip")
    n_pool = UNIQUE_QUERIES + 64        # + warm-up pool, outside workload
    queries, corpus = planted_cluster_dense(N_DOCS, DIM, n_pool, K,
                                            seed=SEED)
    warmup_queries = queries[UNIQUE_QUERIES:]
    queries = queries[:UNIQUE_QUERIES]
    workload = make_workload(REQUESTS, SEED)

    hdr = (f"{'rate/s':>7} {'interval':>8} {'qps':>8} {'p99_ms':>8} "
           f"{'age_p99':>8} {'recall':>7} {'gen':>6} {'compact':>7} "
           f"{'muts':>6}")
    print(f"live_churn [{mode}]: {N_DOCS} docs, {REQUESTS} requests, "
          f"writer {WRITE_BATCH}+{WRITE_BATCH} rows/tick, "
          f"backend={BACKEND}\n\n{hdr}\n" + "-" * len(hdr))

    rows = []
    for i, rate in enumerate(WRITE_RATES):
        for j, interval in enumerate(COMPACT_INTERVALS):
            r = run_cell(space, corpus, queries, warmup_queries, workload,
                         write_rate=rate, compact_interval=interval,
                         seed=SEED + 31 * i + j)
            rows.append(r)
            print(f"{rate:>7.0f} {interval:>8.3f} {r['qps']:>8.1f} "
                  f"{r['p99_ms']:>8.2f} {r['snapshot_age_p99_ms']:>8.2f} "
                  f"{r['post_compaction_recall']:>7.3f} "
                  f"{r['generation_final']:>6} {r['compactions']:>7} "
                  f"{r['mutations']:>6}")

    payload = {
        "bench": "live_churn",
        "schema": BENCH_SCHEMA,
        "mode": mode,
        "n_docs": N_DOCS,
        "dim": DIM,
        "k": K,
        "requests": REQUESTS,
        "platform": jax.devices()[0].platform,
        "recall_target": RECALL_TARGET,
        "requested": {"write_rates": list(WRITE_RATES),
                      "compact_intervals": list(COMPACT_INTERVALS),
                      "backend": BACKEND},
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"\nwrote {args.out} (post-compaction recall gate "
          f">= {RECALL_TARGET} held in every cell)")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
