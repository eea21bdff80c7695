"""End-to-end async serving driver: the paper's three spaces (dense,
sparse, fused) as live endpoints of one :class:`RetrievalService` — the
fused space with mixing weights LEARNED from training data and served by
the one-pass fused Pallas kernel (``backend="pallas"``), plus the fused
space a second time behind a 2-way sharded corpus on the reference
backend, the dense space a second time through the Pallas MIPS kernel,
a third time from a bf16-resident corpus (``corpus_dtype="bfloat16"``,
half the HBM footprint, f32 score accumulation), a fourth time
through the approximate ``graph_ann`` backend (the measured-recall
tier), a fifth time as a LIVE corpus (``live=``) that a writer
thread mutates with inserts and deletes while the load generator is
hitting it, and a sixth time as the paper's FULL FUNNEL — graph-ANN
candgen -> the trained LETOR fusion model -> a cross-encoder neural
rerank, registered through the consolidated ``EndpointSpec`` with a
per-stage budget, per-stage p50/p99 + fallback counters in the
snapshot — hit by a multi-client load generator.

Flow: synthetic corpus -> offline indexing (inverted BM25, dense
projection, fused composite) -> train a LETOR fusion re-ranker AND the
FusedSpace component weights -> stand up a RetrievalService with eight
endpoints + result cache (each endpoint with a bounded admission queue)
-> N client threads stream requests (hot-query repeats exercise the
cache) while a writer churns the live endpoint -> report per-endpoint
latency percentiles, batch fill, overload counters, execution backend +
corpus dtype, cache hit-rate, and MRR@10 on the sparse funnel — and
verify that the sharded reference-backed fused endpoint answered as the
kernel-backed one does, the pallas dense endpoint as the reference one
does (same ids, scores within the f32 bound of
``repro.core.precision``), the bf16 dense endpoint
recall-identically (the bounded-error precision tier) to the f32 one,
the graph-ANN endpoint to recall@10 >= the declared target (the
measured-recall tier) vs the exact one, and — after the churn drains
and a final compaction folds the append segment and tombstones away —
the live endpoint to recall@10 == 1.0 vs the exact frozen oracle
(``segments.frozen_topk`` over the materialized final state).

    PYTHONPATH=src python examples/serve_retrieval.py
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_retrieval import smoke_config
from repro.core import build_inverted_index
from repro.core.backends import ANN_RECALL_TARGET, GraphANNBackend
from repro.core.fusion import (coordinate_ascent, learn_fused_weights, mrr,
                               topk_recall)
from repro.core.inverted_index import daat_topk
from repro.core.precision import topk_close_violations
from repro.core.pipeline import (BruteForceGenerator, LinearReranker,
                                 RetrievalPipeline)
from repro.core.scorers import (CompositeExtractor, bm25_doc_vectors,
                                build_forward_index, query_sparse_vectors)
from repro.core.sparse import SparseVectors, densify
from repro.core.spaces import (DenseSpace, FusedSpace, FusedVectors,
                               SparseSpace)
from repro.core import segments
from repro.data.pipeline import pad_tokens
from repro.data.synthetic import make_corpus, qrels_to_labels
from repro.distributed.sharding import ParallelCtx
from repro.kernels.platform import interpreted
from repro.models import transformer as T
from repro.models.encoder import CrossEncoderReranker
from repro.serving import (EndpointSpec, FunnelPipeline, RetrievalService,
                           ShardedPipeline, StageBudget)
from repro.serving.live import LiveCorpus
from repro import configs as reg

N_CLIENTS = 4
HOT_FRACTION = 0.3      # share of requests drawn from a small hot set
REQUESTS_PER_CLIENT = 80


def build_service(rc, corpus):
    v = rc.vocab_lemmas

    # ---- offline indexing --------------------------------------------------
    fwd = build_forward_index(corpus.doc_lemmas, v)
    doc_bm25 = bm25_doc_vectors(fwd, nnz=rc.doc_nnz)
    inv = build_inverted_index(doc_bm25, v)
    q_tokens_all = jnp.asarray(pad_tokens(corpus.q_lemmas, 8, v))
    q_sparse_all = query_sparse_vectors(q_tokens_all, v, rc.query_nnz)

    # dense view: random projection of the BM25 vectors (stands in for a
    # trained encoder; see examples/train_encoder.py for the real one)
    proj = jax.random.normal(jax.random.PRNGKey(42), (v, rc.embed_dim))
    proj = proj / jnp.sqrt(float(v))
    doc_dense = densify(doc_bm25, v) @ proj
    q_dense_all = densify(q_sparse_all, v) @ proj

    # ---- train the fusion re-ranker on held-out queries --------------------
    train_n = 64
    comp = CompositeExtractor.from_config(
        [{"type": "TFIDFSimilarity", "params": {}},
         {"type": "proximity", "params": {"window": 4}}], fwd=fwd)
    cands = daat_topk(inv, SparseVectors(q_sparse_all.indices[:train_n],
                                         q_sparse_all.values[:train_n]),
                      rc.cand_qty)
    feats = comp.extract(q_tokens_all[:train_n], cands.indices)
    labels = jnp.asarray(qrels_to_labels(corpus, np.asarray(cands.indices)))
    w, train_m = coordinate_ascent(feats, labels, jnp.isfinite(cands.scores),
                                   metric="mrr", n_rounds=3, n_restarts=2)
    print(f"fusion model trained: MRR {train_m:.3f}, "
          f"weights {np.round(np.asarray(w), 3)}")
    reranker = LinearReranker(comp, w)

    # ---- learn the FusedSpace mixing weights from the same training data
    # (the paper's "weights learned from training data" for the mixed
    # representation): per-candidate dense and sparse component scores are
    # the two LETOR features; the learned mix rides the backend seam into
    # the fused Pallas kernel unchanged ------------------------------------
    c_qty = cands.indices.shape[1]
    nnz_q = q_sparse_all.indices.shape[-1]
    dense_comp = jnp.einsum("qd,qcd->qc", q_dense_all[:train_n],
                            doc_dense[cands.indices])
    q_sp_tiled = SparseVectors(
        jnp.broadcast_to(q_sparse_all.indices[:train_n, None, :],
                         (train_n, c_qty, nnz_q)),
        jnp.broadcast_to(q_sparse_all.values[:train_n, None, :],
                         (train_n, c_qty, nnz_q)))
    d_sp_cands = SparseVectors(doc_bm25.indices[cands.indices],
                               doc_bm25.values[cands.indices])
    sparse_comp = SparseSpace(v).score_pairs(q_sp_tiled, d_sp_cands)
    w_dense, w_sparse, fused_m = learn_fused_weights(
        dense_comp, sparse_comp, labels, jnp.isfinite(cands.scores),
        n_rounds=3, n_restarts=2)
    print(f"fused-space weights learned: MRR {fused_m:.3f}, "
          f"w_dense {w_dense:.3f}, w_sparse {w_sparse:.3f}")

    # ---- the service: the paper's spaces as endpoints (dense served twice:
    # reference and pallas execution backends over one corpus) ---------------
    svc = RetrievalService(cache_size=2048)

    def sparse_funnel(q_sp, q_tok):
        cands = daat_topk(inv, q_sp, rc.cand_qty)
        return reranker.rerank(q_tok, cands, 10)

    pad_sp = SparseVectors(q_sparse_all.indices[0], q_sparse_all.values[0])
    svc.register_runner("sparse", sparse_funnel, pad_sp, q_tokens_all[0],
                        batch_size=16, max_wait_s=0.01, jit=True)

    dense_pipe = RetrievalPipeline(
        BruteForceGenerator(DenseSpace("ip"), doc_dense),
        cand_qty=rc.cand_qty, final_qty=10)
    svc.register_pipeline("dense", dense_pipe, q_dense_all[0],
                          batch_size=16, max_wait_s=0.01,
                          backend="reference")

    # the same corpus and funnel through the Pallas fused MIPS+top-k
    # kernel (interpret mode off-TPU): one registration kwarg is the whole
    # difference, and the answers match "dense" within the f32 bound
    svc.register_pipeline("dense_pallas", dense_pipe, q_dense_all[0],
                          batch_size=16, max_wait_s=0.01,
                          backend="pallas")

    # ... and a THIRD time from a bf16-resident corpus (half the HBM
    # footprint, scores still accumulated in f32 — the bounded-error
    # precision tier): corpus_dtype= is the whole difference; answers are
    # recall-identical to "dense" (bitwise identity is an f32-tier
    # property, by design)
    svc.register_pipeline("dense_bf16", dense_pipe, q_dense_all[0],
                          batch_size=16, max_wait_s=0.01,
                          backend="pallas", corpus_dtype="bfloat16")

    # ... and a FOURTH time through the approximate graph-ANN backend
    # (NN-descent proximity graph + beam search) — the measured-recall
    # tier: ef must cover the funnel's cand_qty (the backend refuses
    # k > ef rather than silently degrade), the budget-bearing identity
    # (including kernel=on) lands in snapshots and cache keys, and
    # main() measures recall vs the exact "dense" sibling live.
    # kernel=True traverses the graph through the fused Pallas beam
    # kernel (kernels/beam_topk.py) — same contract, sub-linear per-hop
    # work at corpus scale.  Mosaic does not compile it yet, so on a TPU
    # the jnp hop loop serves.
    beam_kernel = interpreted()
    ann_backend = GraphANNBackend(ef=max(64, rc.cand_qty), kernel=beam_kernel)
    svc.register_pipeline("dense_ann", dense_pipe, q_dense_all[0],
                          batch_size=16, max_wait_s=0.01,
                          backend=ann_backend)

    # ... and a FIFTH time as a LIVE corpus: the same dense rows behind
    # the generation-versioned segment model (frozen main + exactly
    # scanned append + tombstones; core/segments.py), mutated by a
    # writer thread WHILE the load generator is hitting it.  live= is
    # the whole registration difference: every cache key carries the
    # served snapshot's generation, so a mutation can never surface a
    # stale cached result, and main() verifies the endpoint against the
    # exact frozen oracle after the churn drains and compaction folds
    # the segments away.
    live = LiveCorpus(DenseSpace("ip"), doc_dense, backend="reference",
                      max_append=64, compact_interval_s=0.05).start()
    svc.register_pipeline("dense_live", None, q_dense_all[0],
                          batch_size=16, max_wait_s=0.01, live=live)

    # ... and a SIXTH dense view: the paper's FULL funnel as ONE endpoint
    # — approximate candgen (graph-ANN over the same dense corpus) -> the
    # LETOR fusion model trained above -> a cross-encoder neural rerank —
    # registered through the consolidated EndpointSpec with a per-stage
    # rerank budget.  Per-stage p50/p99, fallback counters, and batch
    # occupancy land in EndpointSnapshot.stages; if the rerank stage ever
    # stops fitting its soft deadline the funnel serves the fused
    # candidates instead (counted, never an error).
    tcfg = reg.get_smoke_config("smollm-360m")
    tparams, _ = T.init_transformer(jax.random.PRNGKey(7), tcfg)
    d_tokens = jnp.asarray(pad_tokens(corpus.doc_lemmas, 12, v))
    cross = CrossEncoderReranker(tparams, tcfg, ParallelCtx(None, {}),
                                 d_tokens)
    funnel = FunnelPipeline(
        BruteForceGenerator(DenseSpace("ip"), doc_dense),
        fusion=reranker, rerank=cross,
        cand_qty=rc.cand_qty, fusion_qty=16, rerank_keep=10)
    svc.register_pipeline(
        "dense_funnel", funnel, q_dense_all[0], q_tokens_all[0],
        spec=EndpointSpec(batch_size=16, max_wait_s=0.01,
                          backend=GraphANNBackend(ef=max(64, rc.cand_qty),
                                                  kernel=beam_kernel),
                          budget=StageBudget(rerank_s=2.0)))

    # the mixed representation with the LEARNED mixing weights, scored and
    # selected on-device by the fused Pallas kernel (interpret mode
    # off-TPU): backend="pallas" is the whole difference, and the answers
    # match the reference-backed sharded endpoint below (f32 rule)
    fused_space = FusedSpace(v, w_dense=w_dense, w_sparse=w_sparse)
    fused_corpus = FusedVectors(doc_dense, doc_bm25)
    fused_pipe = RetrievalPipeline(
        BruteForceGenerator(fused_space, fused_corpus),
        cand_qty=rc.cand_qty, final_qty=10)
    pad_fused = FusedVectors(q_dense_all[0], pad_sp)
    svc.register_pipeline("fused", fused_pipe, pad_fused,
                          batch_size=16, max_wait_s=0.01,
                          backend="pallas")

    # the same fused space served from a 2-way sharded corpus on the
    # reference backend: one endpoint, per-shard search + global merge,
    # the same answers as the kernel-backed "fused" (cross-backend AND
    # cross-layout, under the f32 rule); the bounded queue with "block" backpressures
    # clients instead of dropping work (benchmarks/serve_bench.py
    # exercises the reject/shed policies)
    fused_sharded = ShardedPipeline.from_corpus(
        fused_space, fused_corpus, n_shards=2,
        cand_qty=rc.cand_qty, final_qty=10)
    svc.register_pipeline("fused_sharded", fused_sharded, pad_fused,
                          batch_size=16, max_wait_s=0.01,
                          max_queue=1024, overload="block")

    fused_repr = lambda i: (FusedVectors(
        q_dense_all[i], SparseVectors(q_sparse_all.indices[i],
                                      q_sparse_all.values[i])), None)
    reprs = {
        "sparse": lambda i: (SparseVectors(q_sparse_all.indices[i],
                                           q_sparse_all.values[i]),
                             q_tokens_all[i]),
        "dense": lambda i: (q_dense_all[i], None),
        "dense_pallas": lambda i: (q_dense_all[i], None),
        "dense_bf16": lambda i: (q_dense_all[i], None),
        "dense_ann": lambda i: (q_dense_all[i], None),
        "dense_live": lambda i: (q_dense_all[i], None),
        "dense_funnel": lambda i: (q_dense_all[i], q_tokens_all[i]),
        "fused": fused_repr,
        "fused_sharded": fused_repr,
    }
    return svc, fused_sharded, reprs, train_n, doc_dense, live


def run_load(svc, reprs, query_pool):
    """N client threads; each mixes cold queries with a hot repeated set."""
    endpoints = list(reprs)
    hot = query_pool[:8]
    records, lock = [], threading.Lock()

    def client(cid):
        rng = np.random.default_rng(1000 + cid)
        for _ in range(REQUESTS_PER_CLIENT):
            qi = int(rng.choice(hot) if rng.random() < HOT_FRACTION
                     else rng.choice(query_pool))
            ep = endpoints[int(rng.integers(len(endpoints)))]
            query_repr, q_tok = reprs[ep](qi)
            fut = svc.submit(query_repr, q_tok, endpoint=ep)
            with lock:
                records.append((ep, qi, fut))
            time.sleep(float(rng.uniform(0, 0.002)))   # think time

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(N_CLIENTS)]
    t0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for _, _, fut in records:
        fut.result()
    return records, time.time() - t0


def main():
    rc = smoke_config()
    corpus = make_corpus(n_docs=rc.n_docs, n_queries=200,
                         vocab_lemmas=rc.vocab_lemmas, n_topics=10, seed=0)
    svc, sharded_pipe, reprs, train_n, doc_dense, live = \
        build_service(rc, corpus)

    # the live endpoint's writer: inserts fresh rows and deletes prior
    # ones while the clients are querying — append rows, tombstones, and
    # background compactions all happen under real load
    stop_writer = threading.Event()
    live_ids = list(range(doc_dense.shape[0]))
    n_churned = [0]

    def churn():
        rng = np.random.default_rng(7)
        while not stop_writer.is_set():
            rows = rng.standard_normal(
                (2, doc_dense.shape[1])).astype(np.float32)
            live_ids.extend(int(i) for i in live.insert(rows))
            victims = sorted(int(live_ids[j]) for j in rng.choice(
                len(live_ids), size=2, replace=False))
            live.delete(np.asarray(victims, dtype=np.int64))
            gone = set(victims)
            live_ids[:] = [i for i in live_ids if i not in gone]
            n_churned[0] += 4
            stop_writer.wait(0.01)

    writer = threading.Thread(target=churn, name="live-writer", daemon=True)

    with svc:
        # warm-up: one request per endpoint triggers each jit compile so
        # the reported percentiles reflect serving, not tracing; warm-up
        # uses a train query (outside the measured pool), stats reset after
        for ep in svc.endpoints():
            query_repr, q_tok = reprs[ep](0)
            svc.submit(query_repr, q_tok, endpoint=ep).result()
        svc.reset_stats()

        query_pool = np.arange(train_n, 200)
        writer.start()
        records, wall = run_load(svc, reprs, query_pool)
        stop_writer.set()
        writer.join()
        snap = svc.snapshot()

        # sharded-vs-unsharded and pallas-vs-reference spot checks: same
        # queries through both members of each pair must come back with
        # the same ids and scores within the f32 bound (a compiled kernel
        # and XLA's dot sum in different orders, so not bit for bit)
        check = [int(q) for q in query_pool[:8]]
        for ep_a, ep_b in (("fused", "fused_sharded"),
                           ("dense", "dense_pallas")):
            futs_a = [svc.submit(*reprs[ep_a](i), endpoint=ep_a)
                      for i in check]
            futs_b = [svc.submit(*reprs[ep_b](i), endpoint=ep_b)
                      for i in check]
            for a, b in zip(futs_a, futs_b):
                bad = topk_close_violations(a.result(), b.result())
                assert not bad, (ep_a, ep_b, bad)

        # bf16-vs-f32 spot check: the bounded-error precision tier can't
        # be bitwise, so the contract is recall parity — the bf16
        # endpoint must return exactly the same top-10 id SET as "dense"
        # for every checked query.  On real data some queries have
        # rank-10/11 near-ties SMALLER than the bf16 rounding bound;
        # recall parity is only a well-defined expectation where the f32
        # margin exceeds that bound, so check queries are selected by
        # measured margin (and the guard re-asserts it loudly)
        from repro.core.brute_force import exact_topk
        from repro.core.fusion import require_bf16_margin
        pool = [int(qi) for qi in query_pool]
        q_pool = jnp.stack([reprs["dense"](i)[0] for i in pool])
        oracle = np.asarray(
            exact_topk(DenseSpace("ip"), q_pool, doc_dense, 11).scores)
        pert = np.asarray(jnp.abs(q_pool) @ jnp.abs(doc_dense).T
                          ).max(axis=1) * 2.0 ** -8
        eligible = np.nonzero(oracle[:, 9] - oracle[:, 10] > 2 * pert)[0]
        assert len(eligible) >= 8, "too few margin-separated queries"
        sel = eligible[:8]
        require_bf16_margin(oracle[sel], pert_bound=pert[sel])
        check_bf16 = [pool[i] for i in sel]
        futs_a = [svc.submit(*reprs["dense"](i), endpoint="dense")
                  for i in check_bf16]
        futs_b = [svc.submit(*reprs["dense_bf16"](i), endpoint="dense_bf16")
                  for i in check_bf16]
        bf16_recall = topk_recall(
            np.stack([f.result().indices for f in futs_a]),
            np.stack([f.result().indices for f in futs_b]))
        assert bf16_recall == 1.0, \
            f"dense_bf16 recall@10 vs dense = {bf16_recall}"

        # approximate-tier spot check: the graph-ANN endpoint's contract
        # is MEASURED recall vs its exact sibling, not identity — serve
        # the same queries through "dense" and "dense_ann" and report
        # recall@10 against the declared target
        futs_a = [svc.submit(*reprs["dense"](i), endpoint="dense")
                  for i in check]
        futs_b = [svc.submit(*reprs["dense_ann"](i), endpoint="dense_ann")
                  for i in check]
        ann_recall = float(topk_recall(
            np.stack([f.result().indices for f in futs_a]),
            np.stack([f.result().indices for f in futs_b])))
        ann_identity = svc.snapshot().endpoints["dense_ann"].backend
        print(f"dense_ann [{ann_identity}] measured recall@10 vs dense: "
              f"{ann_recall:.3f} (declared target {ANN_RECALL_TARGET})")
        assert ann_recall >= ANN_RECALL_TARGET, \
            f"dense_ann recall@10 vs dense = {ann_recall}"

        # live-tier spot check: with the churn drained, force a final
        # compaction (append segment and tombstones fold into a fresh
        # single-segment main) and serve the check queries through the
        # endpoint — the answers must match the exact frozen oracle at
        # the same logical state (segments.frozen_topk over the
        # materialized final state).  The backend is exact, so this is
        # recall@10 == 1.0 by bitwise identity, not approximation.
        live.close()                   # stop the background compactor
        live.compact()
        final = live.snapshot()
        assert final.n_append == 0 and final.n_dead == 0
        frozen, ids = segments.materialize(final)
        q_check = jnp.stack([reprs["dense_live"](i)[0] for i in check])
        oracle_live = segments.frozen_topk(
            DenseSpace("ip"), frozen, ids, q_check, 10, "reference")
        futs = [svc.submit(*reprs["dense_live"](i), endpoint="dense_live")
                for i in check]
        got_ids = np.stack([f.result().indices for f in futs])
        got_scores = np.stack([f.result().scores for f in futs])
        live_recall = float(topk_recall(np.asarray(oracle_live.indices),
                                        got_ids))
        live_gen = svc.snapshot().endpoints["dense_live"].generation
        print(f"dense_live measured recall@10 vs exact frozen oracle at "
              f"generation {live_gen} ({n_churned[0]} churned rows): "
              f"{live_recall:.3f}")
        assert np.array_equal(got_scores, np.asarray(oracle_live.scores))
        assert np.array_equal(got_ids, np.asarray(oracle_live.indices))
        assert live_recall == 1.0, \
            f"dense_live recall@10 vs frozen oracle = {live_recall}"
    sharded_pipe.close()

    # ---- quality on the sparse funnel (one result per unique query) --------
    by_q = {}
    for ep, qi, fut in records:
        if ep == "sparse" and qi not in by_q:
            by_q[qi] = fut.result()
    qis = sorted(by_q)
    ids = np.stack([by_q[qi].indices for qi in qis])
    scores = np.stack([by_q[qi].scores for qi in qis])
    labels = qrels_to_labels(
        type("C", (), {"qrels": [corpus.qrels[qi] for qi in qis]})(), ids)
    m = float(mrr(jnp.asarray(scores), jnp.asarray(labels),
                  jnp.ones_like(jnp.asarray(labels), bool)))

    # ---- report -------------------------------------------------------------
    n = len(records)
    print(f"\nserved {n} requests from {N_CLIENTS} clients in {wall:.2f}s "
          f"({n / wall:.1f} qps)  cache hit-rate "
          f"{snap.cache_hit_rate:.0%} ({snap.cache_hits}/{snap.cache_hits + snap.cache_misses})")
    for name in sorted(snap.endpoints):
        ep = snap.endpoints[name]
        print(f"  {name:>13}: {ep.n_requests:4d} req in {ep.n_batches:3d} "
              f"batches (fill {ep.mean_batch_fill:.0%}, "
              f"close size/deadline {ep.closed_by_size}/{ep.closed_by_deadline}, "
              f"rejected/shed {ep.rejected}/{ep.shed}, "
              f"backend {ep.backend or '-'}, "
              f"dtype {ep.corpus_dtype or '-'})  "
              f"e2e p50 {ep.e2e.p50_ms:6.1f} ms  p99 {ep.e2e.p99_ms:6.1f} ms")
        if ep.stages:          # the funnel endpoint: per-stage breakdown
            for st in ("candgen", "fusion", "rerank"):
                s = ep.stages.get(st)
                if s is None or not s.count:
                    continue
                print(f"  {'':>13}  stage {st:>7}: p50 {s.p50_ms:6.1f} ms  "
                      f"p99 {s.p99_ms:6.1f} ms  "
                      f"occupancy {ep.stage_occupancy[st]:.0%}  "
                      f"fallbacks {ep.stage_fallbacks[st]}  "
                      f"overruns {ep.stage_overruns[st]}")
    print("fused_sharded matches fused, dense_pallas matches dense (same "
          "ids, scores within the f32 bound), dense_bf16 recall@10 == 1.0 "
          "vs dense, "
          "dense_ann recall@10 >= target vs dense, dense_live recall@10 "
          "== 1.0 vs the exact frozen oracle after churn + compaction, "
          "on spot-check queries")
    print(f"sparse funnel MRR@10 {m:.3f}")
    assert m > 0.3
    assert snap.cache_hits > 0

    # the staged funnel really ran all three stages under load: every
    # batch generated candidates and fused them, and with the generous
    # rerank budget the neural stage never fell back
    fep = snap.endpoints["dense_funnel"]
    assert set(fep.stages) == {"candgen", "fusion", "rerank"}
    assert fep.stage_occupancy["candgen"] == 1.0
    assert fep.stage_fallbacks["rerank"] == 0, \
        "generous rerank budget should never degrade"
    print(f"dense_funnel served {fep.n_requests} requests through all "
          f"three stages (rerank occupancy "
          f"{fep.stage_occupancy['rerank']:.0%}, 0 fallbacks)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
