"""Sharded-corpus serving: partitioning, bit-identical merges against the
unsharded pipeline (dense and fused spaces, with and without rerankers,
serial and host-parallel, offline and behind a live endpoint), device
placement via ParallelCtx, and per-shard graph-ANN."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph_ann
from repro.core.brute_force import TopK, concat_topk
from repro.core.pipeline import (BruteForceGenerator, GraphANNGenerator,
                                 RetrievalPipeline)
from repro.core.sparse import from_dense
from repro.core.spaces import DenseSpace, FusedSpace, FusedVectors
from repro.distributed import ParallelCtx
from repro.distributed.mesh_utils import local_mesh
from repro.serving import RetrievalService, ShardedPipeline, shard_corpus
from _precision import assert_topk_close

N_DOCS, DIM, VOCAB, NNZ = 257, 16, 64, 8   # odd N: uneven shard splits


@pytest.fixture(scope="module")
def dense_data():
    corpus = jax.random.normal(jax.random.PRNGKey(1), (N_DOCS, DIM))
    queries = jax.random.normal(jax.random.PRNGKey(2), (12, DIM))
    return corpus, queries


@pytest.fixture(scope="module")
def fused_data():
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(3), 4)
    corpus = FusedVectors(
        jax.random.normal(k1, (N_DOCS, DIM)),
        from_dense(jax.nn.relu(jax.random.normal(k2, (N_DOCS, VOCAB))), NNZ))
    queries = FusedVectors(
        jax.random.normal(k3, (6, DIM)),
        from_dense(jax.nn.relu(jax.random.normal(k4, (6, VOCAB))), NNZ))
    return corpus, queries


def assert_topk_equal(a: TopK, b: TopK):
    np.testing.assert_array_equal(np.asarray(a.scores), np.asarray(b.scores))
    np.testing.assert_array_equal(np.asarray(a.indices), np.asarray(b.indices))


class TestShardCorpus:
    def test_contiguous_cover_and_offsets(self, dense_data):
        corpus, _ = dense_data
        shards = shard_corpus(corpus, 3)
        assert sum(s.n_rows for s in shards) == N_DOCS
        row = 0
        for s in shards:
            assert s.offset == row
            np.testing.assert_array_equal(np.asarray(s.corpus),
                                          np.asarray(corpus[row:row + s.n_rows]))
            row += s.n_rows

    def test_pytree_corpus_shards_every_leaf(self, fused_data):
        corpus, _ = fused_data
        shards = shard_corpus(corpus, 4)
        for s in shards:
            assert s.corpus.dense.shape[0] == s.n_rows
            assert s.corpus.sparse.indices.shape[0] == s.n_rows
            assert s.corpus.sparse.values.shape[0] == s.n_rows

    def test_bad_shard_counts_rejected(self, dense_data):
        corpus, _ = dense_data
        with pytest.raises(ValueError):
            shard_corpus(corpus, 0)
        with pytest.raises(ValueError):
            shard_corpus(corpus, N_DOCS + 1)

    def test_mesh_placement_via_parallel_ctx(self, dense_data):
        corpus, queries = dense_data
        ctx = ParallelCtx(local_mesh(("data", "model")),
                          {"corpus": "model"})
        sharded = ShardedPipeline.from_corpus(
            DenseSpace("ip"), corpus, 2, ctx=ctx, axis="corpus",
            cand_qty=20, final_qty=10)
        devices = {jax.tree.leaves(s.corpus)[0].devices().pop()
                   for s in sharded.shards}
        assert devices <= set(ctx.mesh.devices.flat)
        base = RetrievalPipeline(
            BruteForceGenerator(DenseSpace("ip"), corpus),
            cand_qty=20, final_qty=10)
        assert_topk_equal(sharded.run(queries), base.run(queries))


class TestBitIdentical:
    @pytest.mark.parametrize("n_shards", [2, 3, 5])
    def test_dense_matches_unsharded(self, dense_data, n_shards):
        corpus, queries = dense_data
        base = RetrievalPipeline(
            BruteForceGenerator(DenseSpace("ip"), corpus),
            cand_qty=50, final_qty=10)
        sharded = ShardedPipeline.from_corpus(
            DenseSpace("ip"), corpus, n_shards, cand_qty=50, final_qty=10)
        assert_topk_equal(sharded.run(queries), base.run(queries))

    def test_fused_space_matches_unsharded(self, fused_data):
        corpus, queries = fused_data
        space = FusedSpace(VOCAB, w_dense=0.5, w_sparse=0.5)
        base = RetrievalPipeline(BruteForceGenerator(space, corpus),
                                 cand_qty=40, final_qty=10)
        sharded = ShardedPipeline.from_corpus(
            space, corpus, 3, cand_qty=40, final_qty=10)
        assert_topk_equal(sharded.run(queries), base.run(queries))

    def test_tie_break_matches_unsharded(self):
        """Duplicate rows straddling a shard boundary: the tied doc with the
        lower global id must win in both layouts."""
        row = jnp.ones((1, 4))
        corpus = jnp.concatenate([jnp.tile(row, (8, 1)),
                                  jnp.zeros((8, 4))])     # rows 0..7 all tie
        queries = jnp.ones((2, 4))
        base = RetrievalPipeline(
            BruteForceGenerator(DenseSpace("ip"), corpus),
            cand_qty=8, final_qty=6)
        sharded = ShardedPipeline.from_corpus(
            DenseSpace("ip"), corpus, 4, cand_qty=8, final_qty=6)
        out = sharded.run(queries)
        assert_topk_equal(out, base.run(queries))
        np.testing.assert_array_equal(np.asarray(out.indices),
                                      np.tile(np.arange(6), (2, 1)))

    def test_jit_run_matches_eager(self, dense_data):
        """jax.jit over a host-parallel pipeline must not leak tracers into
        worker threads: tracing falls back to the serial path."""
        corpus, queries = dense_data
        sharded = ShardedPipeline.from_corpus(
            DenseSpace("ip"), corpus, 4, cand_qty=30, final_qty=10)
        jitted = jax.jit(lambda q: sharded.run(q))
        assert_topk_equal(jitted(queries), sharded.run(queries))

    def test_close_shuts_down_executor_and_stays_usable(self, dense_data):
        corpus, queries = dense_data
        sharded = ShardedPipeline.from_corpus(
            DenseSpace("ip"), corpus, 3, cand_qty=20, final_qty=10)
        before = sharded.run(queries)
        with sharded:
            pass                      # context manager closes the pool
        assert sharded.executor is None
        assert_topk_equal(sharded.run(queries), before)   # serial fallback

    def test_serial_matches_host_parallel(self, dense_data):
        corpus, queries = dense_data
        kw = dict(cand_qty=30, final_qty=10)
        par = ShardedPipeline.from_corpus(DenseSpace("ip"), corpus, 4,
                                          host_parallel=True, **kw)
        ser = ShardedPipeline.from_corpus(DenseSpace("ip"), corpus, 4,
                                          host_parallel=False, **kw)
        assert par.executor is not None and ser.executor is None
        assert_topk_equal(par.run(queries), ser.run(queries))

    def test_reranker_runs_on_merged_global_candidates(self, dense_data):
        """Rerankers see identical merged candidate lists, so any
        deterministic rerank stays bit-identical."""
        corpus, queries = dense_data

        class FlipReranker:
            def rerank(self, q_tokens, cands, keep):
                vals, pos = jax.lax.top_k(-cands.scores, keep)
                return TopK(vals, jnp.take_along_axis(cands.indices, pos,
                                                      axis=1))

        base = RetrievalPipeline(
            BruteForceGenerator(DenseSpace("ip"), corpus),
            final=FlipReranker(), cand_qty=25, final_qty=5)
        sharded = ShardedPipeline.from_corpus(
            DenseSpace("ip"), corpus, 3, final=FlipReranker(),
            cand_qty=25, final_qty=5)
        assert_topk_equal(sharded.run(queries), base.run(queries))


class TestGeneratorFactory:
    def test_per_shard_graph_ann(self, dense_data):
        """Approximate path: a graph index per shard, merged globally.
        Recall is checked against exact search, not bit-identity."""
        corpus, queries = dense_data
        space = DenseSpace("ip")

        def factory(shard):
            index = graph_ann.nn_descent(space, shard.corpus, shard.n_rows,
                                         degree=12, rounds=4,
                                         node_block=shard.n_rows,
                                         key=jax.random.PRNGKey(shard.offset))
            return GraphANNGenerator(space, shard.corpus, index,
                                     shard.n_rows, ef=48)

        sharded = ShardedPipeline.from_corpus(
            space, corpus, 2, generator_factory=factory,
            cand_qty=20, final_qty=10)
        out = sharded.run(queries)
        exact = RetrievalPipeline(BruteForceGenerator(space, corpus),
                                  cand_qty=20, final_qty=10).run(queries)
        assert np.asarray(out.indices).min() >= 0
        assert np.asarray(out.indices).max() < N_DOCS
        recall = np.mean([
            len(set(np.asarray(out.indices)[i]) &
                set(np.asarray(exact.indices)[i])) / 10
            for i in range(out.indices.shape[0])])
        assert recall > 0.5

    def test_sharded_pipeline_as_candidate_generator(self, dense_data):
        """ShardedPipeline satisfies the CandidateGenerator protocol."""
        corpus, queries = dense_data
        inner = ShardedPipeline.from_corpus(DenseSpace("ip"), corpus, 3,
                                            cand_qty=30)
        outer = RetrievalPipeline(inner, cand_qty=30, final_qty=10)
        base = RetrievalPipeline(
            BruteForceGenerator(DenseSpace("ip"), corpus),
            cand_qty=30, final_qty=10)
        assert_topk_equal(outer.run(queries), base.run(queries))


class TestServedSharded:
    def test_endpoint_bit_identical_under_concurrent_load(self, dense_data):
        """Acceptance: a K=2 sharded endpoint and the unsharded endpoint,
        hammered concurrently from several client threads, return exactly
        the same top-k for every query."""
        corpus, queries = dense_data
        space = DenseSpace("ip")
        base = RetrievalPipeline(BruteForceGenerator(space, corpus),
                                 cand_qty=30, final_qty=10)
        sharded = ShardedPipeline.from_corpus(space, corpus, 2,
                                              cand_qty=30, final_qty=10)
        svc = RetrievalService(cache_size=0)
        svc.register_pipeline("flat", base, queries[0],
                              batch_size=4, max_wait_s=0.005)
        svc.register_pipeline("sharded", sharded, queries[0],
                              batch_size=4, max_wait_s=0.005)
        results = {"flat": {}, "sharded": {}}
        lock = threading.Lock()

        def client(endpoint, order):
            for i in order:
                r = svc.submit(queries[i], endpoint=endpoint).result(timeout=30)
                with lock:
                    results[endpoint][i] = r

        n = queries.shape[0]
        with svc:
            threads = [threading.Thread(target=client, args=(ep, order))
                       for ep in ("flat", "sharded")
                       for order in (range(n), reversed(range(n)))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        off = base.run(queries)
        for i in range(n):
            for ep in ("flat", "sharded"):
                np.testing.assert_array_equal(
                    results[ep][i].scores, np.asarray(off.scores)[i])
                np.testing.assert_array_equal(
                    results[ep][i].indices, np.asarray(off.indices)[i])

    def test_fused_sharded_endpoint(self, fused_data):
        corpus, queries = fused_data
        space = FusedSpace(VOCAB, w_dense=0.5, w_sparse=0.5)
        sharded = ShardedPipeline.from_corpus(space, corpus, 2,
                                              cand_qty=20, final_qty=5)
        base = RetrievalPipeline(BruteForceGenerator(space, corpus),
                                 cand_qty=20, final_qty=5)
        pad = jax.tree.map(lambda x: x[0], queries)
        with RetrievalService(cache_size=0) as svc:
            svc.register_pipeline("fused_sharded", sharded, pad,
                                  batch_size=3, max_wait_s=0.005)
            res = svc.retrieve([jax.tree.map(lambda x: x[i], queries)
                                for i in range(queries.dense.shape[0])],
                               endpoint="fused_sharded")
        off = base.run(queries)
        np.testing.assert_array_equal(np.stack([r.scores for r in res]),
                                      np.asarray(off.scores))
        np.testing.assert_array_equal(np.stack([r.indices for r in res]),
                                      np.asarray(off.indices))

    @pytest.mark.fused
    @pytest.mark.parametrize("backend", ["streaming", "pallas"])
    def test_fused_sharded_backend_offline(self, fused_data, backend):
        """Per-shard fused generators on the kernel/tiled paths — the
        K-shard merge matches the unsharded reference scan within the f32
        bound (shard slices are just smaller fused corpora)."""
        corpus, queries = fused_data
        space = FusedSpace(VOCAB, w_dense=0.6, w_sparse=0.4)
        base = RetrievalPipeline(BruteForceGenerator(space, corpus),
                                 cand_qty=40, final_qty=10)
        with ShardedPipeline.from_corpus(space, corpus, 3, cand_qty=40,
                                         final_qty=10,
                                         backend=backend) as sharded:
            from repro.core.backends import ReferenceBackend
            assert not any(isinstance(g.backend, ReferenceBackend)
                           for g in sharded.generators), \
                "fused shards must resolve to the requested backend"
            assert_topk_close(base.run(queries), sharded.run(queries))

    @pytest.mark.fused
    def test_fused_sharded_pallas_endpoint_under_concurrent_load(
            self, fused_data):
        """Satellite acceptance: the fused endpoint on the pallas backend,
        served K=2-sharded, answers as the unsharded reference endpoint
        does (within the f32 bound) while several client threads hammer
        both."""
        corpus, queries = fused_data
        space = FusedSpace(VOCAB, w_dense=0.5, w_sparse=0.5)
        base = RetrievalPipeline(BruteForceGenerator(space, corpus),
                                 cand_qty=30, final_qty=10)
        sharded = ShardedPipeline.from_corpus(space, corpus, 2,
                                              cand_qty=30, final_qty=10,
                                              backend="pallas")
        pad = jax.tree.map(lambda x: x[0], queries)
        svc = RetrievalService(cache_size=0)
        svc.register_pipeline("flat", base, pad,
                              batch_size=3, max_wait_s=0.005,
                              backend="reference")
        svc.register_pipeline("sharded_pallas", sharded, pad,
                              batch_size=3, max_wait_s=0.005)
        n = queries.dense.shape[0]
        results = {"flat": {}, "sharded_pallas": {}}
        lock = threading.Lock()

        def client(endpoint, order):
            for i in order:
                q = jax.tree.map(lambda x: x[i], queries)
                r = svc.submit(q, endpoint=endpoint).result(timeout=30)
                with lock:
                    results[endpoint][i] = r

        with svc:
            threads = [threading.Thread(target=client, args=(ep, order))
                       for ep in ("flat", "sharded_pallas")
                       for order in (range(n), reversed(range(n)))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            snap = svc.snapshot()
        sharded.close()
        assert snap.endpoints["sharded_pallas"].backend.startswith("pallas(")
        off = base.run(queries)
        for i in range(n):
            np.testing.assert_array_equal(
                results["flat"][i].scores, np.asarray(off.scores)[i])
            np.testing.assert_array_equal(
                results["flat"][i].indices, np.asarray(off.indices)[i])
            assert_topk_close(TopK(off.scores[i], off.indices[i]),
                              results["sharded_pallas"][i], ctx=i)


class TestConcatTopk:
    def test_single_part_passthrough(self):
        part = TopK(jnp.ones((2, 3)), jnp.zeros((2, 3), jnp.int32))
        out = concat_topk([part])
        assert out is part

    def test_concat_preserves_order(self):
        a = TopK(jnp.asarray([[3.0, 1.0]]), jnp.asarray([[0, 1]], jnp.int32))
        b = TopK(jnp.asarray([[2.0]]), jnp.asarray([[7]], jnp.int32))
        cat = concat_topk([a, b])
        np.testing.assert_array_equal(np.asarray(cat.scores), [[3.0, 1.0, 2.0]])
        np.testing.assert_array_equal(np.asarray(cat.indices), [[0, 1, 7]])
