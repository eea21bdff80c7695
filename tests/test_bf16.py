"""bf16 mixed-precision retrieval: the bounded-error precision tier.

The contract under test (the `bf16` CI marker mirrors the `fused` one):

  * corpora resident in bf16 serve on ALL THREE execution backends
    (reference / streaming / pallas-interpret) for dense, sparse, and
    fused spaces;
  * **within** the bf16 tier the backends agree under the f32 rule of
    ``tests/_precision.py`` — every path upcasts the stored values to
    f32 before the first multiply, and the cast commutes with tiling;
  * **across** tiers, bf16 results hold recall@k == 1.0 against the f32
    oracle with score error inside the documented ULP bound
    (``tests/_precision.py``);
  * the existing f32 tier is untouched — casting an f32 corpus "to f32"
    changes nothing, bit for bit;
  * the ``corpus_dtype=`` seam threads through generators, pipelines,
    sharded serving, and endpoint registration, showing up in stats
    snapshots and cache keys exactly like ``backend=`` does;
  * ``auto_tile_n``'s warm cache hits on repeat calls, re-tunes per
    dtype (bf16 halves bytes_per_row), and survives concurrent served
    load.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _precision import (BF16_MAX_ULP, assert_bf16_oracle_contract,
                        assert_topk_bitwise, assert_topk_close,
                        planted_margin_corpus,
                        recall_at_k, require_margin)
from repro.core.backends import (PallasBackend, ReferenceBackend,
                                 StreamingBackend, clear_tile_cache,
                                 make_backend, resolve_backend,
                                 tile_cache_info)
from repro.core.pipeline import (BruteForceGenerator, RetrievalPipeline,
                                 StreamingGenerator)
from repro.core.spaces import (DenseSpace, FusedSpace, FusedVectors,
                               SparseSpace, canonical_dtype, cast_corpus,
                               corpus_dtype)
from repro.serving import QueryCache, RetrievalService, ShardedPipeline

pytestmark = pytest.mark.bf16

BACKENDS = ("reference", "streaming", "pallas")
# (n, d, b, k, tile): multiples, non-multiples (padding), tile > n
SHAPES = [
    (64, 16, 2, 4, 32),
    (300, 32, 4, 5, 64),
    (257, 48, 3, 7, 512),
]


def _bf16(corpus):
    return cast_corpus(corpus, "bfloat16")


def _fused_setup(n=300, v=50, nnz=8, dd=16, b=3, k=6, seed=0):
    """Fused corpus with a *planted sparse margin* so the bf16 recall
    assertion is an invariant — delegates to the ONE canonical
    construction (``benchmarks/common.py: planted_margin_fused``, on
    sys.path via ``_precision``) that the benches' margin-guarded gates
    use too; ``require_margin`` re-verifies the margin on the oracle in
    each test."""
    from benchmarks.common import planted_margin_fused

    return planted_margin_fused(n, v, nnz, dd, b, k, seed=seed)


class TestDtypeHelpers:
    def test_canonical_dtype_accepts_aliases(self):
        assert canonical_dtype("bf16") == "bfloat16"
        assert canonical_dtype(jnp.bfloat16) == "bfloat16"
        assert canonical_dtype("f32") == "float32"
        assert canonical_dtype(np.float32) == "float32"

    def test_canonical_dtype_rejects_outside_contract(self):
        for bad in ("float64", "int8", np.float16):
            with pytest.raises(ValueError, match="precision"):
                canonical_dtype(bad)

    def test_cast_corpus_keeps_integer_leaves(self):
        corpus, _ = _fused_setup(n=32)
        cast = _bf16(corpus)
        assert str(cast.dense.dtype) == "bfloat16"
        assert str(cast.sparse.values.dtype) == "bfloat16"
        assert str(cast.sparse.indices.dtype) == "int32"
        assert corpus_dtype(cast) == "bfloat16"
        assert corpus_dtype(corpus) == "float32"

    def test_cast_is_idempotent_and_f32_noop(self):
        c = jnp.asarray(np.random.default_rng(0).standard_normal((8, 4)),
                        jnp.float32)
        np.testing.assert_array_equal(np.asarray(cast_corpus(c, "float32")),
                                      np.asarray(c))
        once = cast_corpus(c, "bfloat16")
        twice = cast_corpus(once, "bfloat16")
        np.testing.assert_array_equal(
            np.asarray(once, np.float32), np.asarray(twice, np.float32))

    def test_corpus_dtype_mixed_is_none(self):
        corpus, _ = _fused_setup(n=32)
        mixed = FusedVectors(_bf16(corpus.dense), corpus.sparse)
        assert corpus_dtype(mixed) is None

    def test_widening_cast_is_refused(self):
        """bf16 -> f32 would relabel already-rounded values as the f32
        tier, silently breaking the same-dtype bitwise guarantee — the
        seam refuses the round-trip at every layer."""
        _q, c, _ = planted_margin_corpus(32, 8, 2, 4)
        cb = _bf16(c)
        with pytest.raises(ValueError, match="widening"):
            cast_corpus(cb, "float32")
        gen = BruteForceGenerator(DenseSpace("ip"), c,
                                  corpus_dtype="bfloat16")
        with pytest.raises(ValueError, match="widening"):
            gen.with_corpus_dtype("float32")
        with pytest.raises(ValueError, match="widening"):
            BruteForceGenerator(DenseSpace("ip"), cb,
                                corpus_dtype="float32")
        # an out-of-contract SOURCE is refused too, even at equal width:
        # f16 -> bf16 would double-round and relabel
        with pytest.raises(ValueError, match="outside"):
            cast_corpus(c.astype(jnp.float16), "bfloat16")


class TestDenseBf16:
    """Dense ip/l2: within-tier parity (the f32 rule) + cross-tier
    oracle contract, the acceptance sweep."""

    @pytest.mark.parametrize("kind", ["ip", "l2"])
    @pytest.mark.parametrize("n,d,b,k,tile", SHAPES)
    @pytest.mark.parametrize("name", BACKENDS[1:])
    def test_backends_bitwise_within_bf16_tier(self, name, n, d, b, k, tile,
                                               kind):
        q, c, _ = planted_margin_corpus(n, d, b, k)
        cb = _bf16(c)
        space = DenseSpace(kind)
        want = ReferenceBackend().topk(space, q, cb, k)
        assert str(want.scores.dtype) == "float32"   # f32 accumulation
        got = make_backend(name, tile_n=tile).topk(space, q, cb, k)
        assert_topk_close(want, got, ctx=(name, kind, n))

    @pytest.mark.parametrize("kind", ["ip", "l2"])
    @pytest.mark.parametrize("n,d,b,k,tile", SHAPES)
    @pytest.mark.parametrize("name", BACKENDS)
    def test_recall_and_ulp_vs_f32_oracle(self, name, n, d, b, k, tile, kind):
        q, c, planted = planted_margin_corpus(n, d, b, k)
        space = DenseSpace(kind)
        oracle = ReferenceBackend().topk(space, q, c, k)
        # the construction's guarantee really holds in f32
        assert set(np.asarray(oracle.indices).ravel()) == \
            set(np.asarray(planted).tolist())
        got = make_backend(name, **({} if name == "reference"
                                    else {"tile_n": tile})).topk(
            space, q, _bf16(c), k)
        assert_bf16_oracle_contract(oracle, got, ctx=(name, kind, n))

    @pytest.mark.parametrize("name", BACKENDS)
    def test_degenerate_k_exceeding_n_valid(self, name):
        """The -inf reference tail must align across tiers too."""
        q, c, _ = planted_margin_corpus(12, 8, 2, 4)
        space = DenseSpace("ip")
        oracle = ReferenceBackend().topk(space, q, c, 8, n_valid=4)
        got = make_backend(name, **({} if name == "reference"
                                    else {"tile_n": 4})).topk(
            space, q, _bf16(c), 8, n_valid=4)
        assert_bf16_oracle_contract(oracle, got, ctx=name)

    def test_parity_survives_jit(self):
        q, c, _ = planted_margin_corpus(300, 32, 4, 10)
        cb = _bf16(c)
        space = DenseSpace("l2")
        outs = []
        for name in BACKENDS:
            backend = make_backend(name)
            outs.append(jax.jit(lambda qq, be=backend: be.topk(
                space, qq, cb, 10))(q))
        for got in outs[1:]:
            assert_topk_close(outs[0], got)
        assert_bf16_oracle_contract(
            ReferenceBackend().topk(space, q, c, 10), outs[0])


class TestSparseFusedBf16:
    @pytest.mark.parametrize("name", BACKENDS)
    def test_sparse_bf16_contract(self, name):
        corpus, queries = _fused_setup()
        space = SparseSpace(50)
        qs, cs = queries.sparse, corpus.sparse
        k = 6
        oracle = ReferenceBackend().topk(space, qs, cs, k)
        require_margin(ReferenceBackend().topk(space, qs, cs, k + 1).scores,
                       min_gap=1.0)
        cb = _bf16(cs)
        want = ReferenceBackend().topk(space, qs, cb, k)
        got = make_backend(name, **({} if name == "reference"
                                    else {"tile_n": 64})).topk(
            space, qs, cb, k)
        assert_topk_close(want, got, ctx=name)         # within-tier
        assert_bf16_oracle_contract(oracle, got, ctx=name)

    @pytest.mark.parametrize("wd,ws", [(0.6, 0.4), (1.0, 1.0), (-0.5, 1.5)])
    @pytest.mark.parametrize("name", BACKENDS)
    def test_fused_bf16_contract(self, name, wd, ws):
        corpus, queries = _fused_setup()
        space = FusedSpace(50, w_dense=wd, w_sparse=ws)
        k = 6
        oracle = ReferenceBackend().topk(space, queries, corpus, k)
        require_margin(
            ReferenceBackend().topk(space, queries, corpus, k + 1).scores,
            min_gap=1.0)
        cb = _bf16(corpus)
        want = ReferenceBackend().topk(space, queries, cb, k)
        got = make_backend(name, **({} if name == "reference"
                                    else {"tile_n": 64})).topk(
            space, queries, cb, k)
        assert_topk_close(want, got, ctx=(name, wd, ws))
        assert_bf16_oracle_contract(oracle, got, ctx=(name, wd, ws))

    def test_pallas_serves_bf16_sparse_and_fused(self):
        """The capability matrix change: bf16 components no longer force
        the reference fallback."""
        corpus, _ = _fused_setup(n=64)
        cb = _bf16(corpus)
        assert isinstance(
            resolve_backend("pallas", FusedSpace(50), cb), PallasBackend)
        assert isinstance(
            resolve_backend("pallas", SparseSpace(50), cb.sparse),
            PallasBackend)
        assert isinstance(
            resolve_backend("streaming", FusedSpace(50), cb),
            StreamingBackend)
        # outside the contract still falls back
        int_corpus = jnp.zeros((64, 8), jnp.int8)
        assert isinstance(
            resolve_backend("pallas", DenseSpace("ip"), int_corpus),
            ReferenceBackend)


class TestCorpusDtypeSeam:
    def test_generator_constructor_and_with_corpus_dtype(self):
        q, c, _ = planted_margin_corpus(128, 16, 2, 4)
        explicit = BruteForceGenerator(DenseSpace("ip"), _bf16(c))
        via_kwarg = BruteForceGenerator(DenseSpace("ip"), c,
                                        corpus_dtype="bf16")
        via_rebind = BruteForceGenerator(DenseSpace("ip"),
                                         c).with_corpus_dtype("bfloat16")
        assert explicit.corpus_dtype == "bfloat16"      # observed
        assert via_kwarg.corpus_dtype == "bfloat16"     # canonicalised
        assert via_rebind.corpus_dtype == "bfloat16"
        want = explicit.generate(q, 4)
        for gen in (via_kwarg, via_rebind):
            assert_topk_bitwise(want, gen.generate(q, 4))

    def test_f32_generator_reports_observed_dtype(self):
        _q, c, _ = planted_margin_corpus(64, 16, 2, 4)
        assert BruteForceGenerator(DenseSpace("ip"), c).corpus_dtype \
            == "float32"

    def test_with_corpus_dtype_rebinds_bound_backend(self):
        q, c, _ = planted_margin_corpus(128, 16, 2, 4)
        gen = BruteForceGenerator(DenseSpace("ip"), c).with_backend("pallas")
        rebound = gen.with_corpus_dtype("bfloat16")
        assert isinstance(rebound.backend, PallasBackend)
        assert str(rebound.corpus.dtype) == "bfloat16"
        assert_topk_close(
            BruteForceGenerator(DenseSpace("ip"), _bf16(c)).generate(q, 4),
            rebound.generate(q, 4))

    def test_streaming_generator_seam(self):
        q, c, _ = planted_margin_corpus(128, 16, 2, 4)
        gen = StreamingGenerator(DenseSpace("ip"), c,
                                 tile_n=32).with_corpus_dtype("bf16")
        assert gen.corpus_dtype == "bfloat16" and gen.tile_n == 32
        assert_topk_close(
            ReferenceBackend().topk(DenseSpace("ip"), q, _bf16(c), 4),
            gen.generate(q, 4))

    def test_pipeline_seam_and_descriptor_key(self):
        q, c, _ = planted_margin_corpus(128, 16, 2, 4)
        gen = BruteForceGenerator(DenseSpace("ip"), c)
        pipe = RetrievalPipeline(gen, cand_qty=8, final_qty=4)
        rebound = pipe.with_corpus_dtype("bfloat16")
        assert pipe.corpus_dtype == "float32"
        assert rebound.corpus_dtype == "bfloat16"
        from_desc = RetrievalPipeline.from_descriptor(
            {"candProv": "gen", "corpusDtype": "bf16", "backend": "pallas",
             "candQty": 8, "finalQty": 4}, {"gen": gen})
        assert from_desc.corpus_dtype == "bfloat16"
        assert isinstance(from_desc.backend, PallasBackend)
        assert_topk_close(rebound.run(q), from_desc.run(q))

    def test_pipeline_without_seam_raises(self):
        from repro.core.pipeline import InvertedIndexGenerator
        pipe = RetrievalPipeline(InvertedIndexGenerator(index=None))
        with pytest.raises(TypeError, match="corpus residency dtype"):
            pipe.with_corpus_dtype("bfloat16")


class TestShardedBf16:
    @pytest.mark.parametrize("name", BACKENDS)
    def test_sharded_bf16_bit_identical_to_unsharded(self, name):
        q, c, _ = planted_margin_corpus(300, 32, 4, 10, seed=7)
        space = DenseSpace("ip")
        base = RetrievalPipeline(BruteForceGenerator(space, _bf16(c)),
                                 cand_qty=20, final_qty=10)
        with ShardedPipeline.from_corpus(space, c, 3, cand_qty=20,
                                         final_qty=10, backend=name,
                                         corpus_dtype="bfloat16") as sharded:
            assert sharded.corpus_dtype == "bfloat16"
            assert_topk_close(base.run(q), sharded.run(q), ctx=name)

    def test_with_corpus_dtype_recasts_every_shard(self):
        q, c, _ = planted_margin_corpus(256, 16, 3, 8)
        space = DenseSpace("l2")
        with ShardedPipeline.from_corpus(space, c, 2, cand_qty=16,
                                         final_qty=8) as sharded:
            rebound = sharded.with_corpus_dtype("bf16")
            try:
                assert rebound.corpus_dtype == "bfloat16"
                assert all(str(s.corpus.dtype) == "bfloat16"
                           for s in rebound.shards)
                base = RetrievalPipeline(
                    BruteForceGenerator(space, _bf16(c)),
                    cand_qty=16, final_qty=8)
                assert_topk_bitwise(base.run(q), rebound.run(q))
            finally:
                rebound.close()


class TestServedBf16:
    def test_endpoint_pair_recall_parity_under_load(self):
        """The acceptance contract at the serving layer: one corpus live
        as f32 and bf16 endpoints, recall parity through the batcher,
        dtype visible in snapshots."""
        q, c, _ = planted_margin_corpus(300, 16, 40, 10, seed=3)
        pipe = RetrievalPipeline(BruteForceGenerator(DenseSpace("ip"), c),
                                 cand_qty=20, final_qty=10)
        svc = RetrievalService(cache_size=0)
        svc.register_pipeline("dense", pipe, q[0], batch_size=8,
                              max_wait_s=0.005, backend="reference")
        svc.register_pipeline("dense_bf16", pipe, q[0], batch_size=8,
                              max_wait_s=0.005, backend="pallas",
                              corpus_dtype="bfloat16")
        with svc:
            futs_a = [svc.submit(q[i], endpoint="dense") for i in range(40)]
            futs_b = [svc.submit(q[i], endpoint="dense_bf16")
                      for i in range(40)]
            for a, b in zip(futs_a, futs_b):
                ra, rb = a.result(), b.result()
                assert recall_at_k(ra.indices[None], rb.indices[None]) == 1.0
            snap = svc.snapshot()
        assert snap.endpoints["dense"].corpus_dtype == "float32"
        assert snap.endpoints["dense_bf16"].corpus_dtype == "bfloat16"
        assert snap.endpoints["dense_bf16"].backend.startswith("pallas")

    def test_served_bf16_matches_offline_bf16_bitwise(self):
        q, c, _ = planted_margin_corpus(128, 16, 8, 6)
        pipe = RetrievalPipeline(BruteForceGenerator(DenseSpace("ip"), c),
                                 cand_qty=12, final_qty=6)
        svc = RetrievalService(cache_size=0)
        svc.register_pipeline("bf16", pipe, q[0], batch_size=4,
                              max_wait_s=0.002, corpus_dtype="bf16")
        with svc:
            served = [f.result() for f in
                      [svc.submit(q[i], endpoint="bf16") for i in range(8)]]
        off = pipe.with_corpus_dtype("bfloat16").run(q)
        np.testing.assert_array_equal(
            np.stack([r.indices for r in served]), np.asarray(off.indices))
        np.testing.assert_array_equal(
            np.stack([r.scores for r in served]), np.asarray(off.scores))

    def test_register_pipeline_rejects_seamless_pipeline(self):
        class OpaquePipeline:
            def run(self, q, t):
                return q

        q = jnp.zeros((4, 8), jnp.float32)
        svc = RetrievalService(cache_size=0)
        with svc:
            with pytest.raises(TypeError, match="with_corpus_dtype"):
                svc.register_pipeline("x", OpaquePipeline(), q[0],
                                      corpus_dtype="bfloat16")

    def test_mixed_shard_dtypes_never_claim_a_uniform_tier(self):
        """A duck-typed sharded pipeline mixing a dtype-less generator
        with a bf16 one must label as unknown (None), not 'bfloat16' —
        stats/cache keys may only claim a tier the whole endpoint has."""
        from repro.serving.service import _pipeline_corpus_dtype

        _q, c, _ = planted_margin_corpus(64, 8, 2, 4)

        class SeamlessGen:                # no corpus_dtype attribute
            pass

        class DuckSharded:                # no corpus_dtype property
            def __init__(self, gens):
                self.generators = gens

        bf16_gen = BruteForceGenerator(DenseSpace("ip"), c,
                                       corpus_dtype="bfloat16")
        f32_gen = BruteForceGenerator(DenseSpace("ip"), c)
        assert _pipeline_corpus_dtype(
            DuckSharded([SeamlessGen(), bf16_gen])) is None
        assert _pipeline_corpus_dtype(
            DuckSharded([bf16_gen, bf16_gen])) == "bfloat16"
        assert _pipeline_corpus_dtype(
            DuckSharded([f32_gen, bf16_gen])) \
            == "mixed(bfloat16,float32)"

    def test_runner_corpus_dtype_is_label_only(self):
        q = jnp.zeros((2, 4), jnp.float32)
        svc = RetrievalService(cache_size=0)
        svc.register_runner("raw", lambda qq, t: qq, q[0],
                            corpus_dtype="bfloat16")
        with svc:
            svc.submit(q[0], endpoint="raw").result()
            snap = svc.snapshot()
        assert snap.endpoints["raw"].corpus_dtype == "bfloat16"

    def test_sharded_dtype_rebind_closes_intermediate_pool(self):
        """register_pipeline(corpus_dtype=, backend=) rebinds twice; the
        intermediate rebound pipeline's worker pool must not leak."""
        q, c, _ = planted_margin_corpus(128, 8, 4, 4)
        pipe = ShardedPipeline.from_corpus(DenseSpace("ip"), c, 2,
                                           cand_qty=8, final_qty=4)
        before = {t for t in threading.enumerate()
                  if t.name.startswith("shard")}
        svc = RetrievalService(cache_size=0)
        svc.register_pipeline("s", pipe, q[0], batch_size=4,
                              max_wait_s=0.002, backend="streaming",
                              corpus_dtype="bfloat16")
        with svc:
            svc.submit(q[0], endpoint="s").result()
            snap = svc.snapshot()
        pipe.close()
        assert snap.endpoints["s"].corpus_dtype == "bfloat16"
        assert snap.endpoints["s"].backend.startswith("streaming")
        leaked = [t for t in threading.enumerate()
                  if t.name.startswith("shard") and t not in before
                  and t.is_alive()]
        assert not leaked, f"dtype/backend rebind leaked threads: {leaked}"


class TestCacheDtypeIdentity:
    def test_key_differs_by_corpus_dtype(self):
        cache = QueryCache(16)
        q = np.ones(8, np.float32)
        keys = {cache.key("dense", q, backend="reference"),
                cache.key("dense", q, backend="reference",
                          corpus_dtype="float32"),
                cache.key("dense", q, backend="reference",
                          corpus_dtype="bfloat16")}
        assert len(keys) == 3

    def test_key_fields_are_framed(self):
        cache = QueryCache(16)
        q = np.ones(8, np.float32)
        assert (cache.key("dense", q, backend="ab", corpus_dtype="c")
                != cache.key("dense", q, backend="a", corpus_dtype="bc"))

    def test_service_cache_isolates_dtypes(self):
        q, c, _ = planted_margin_corpus(64, 8, 4, 4)
        pipe = RetrievalPipeline(BruteForceGenerator(DenseSpace("ip"), c),
                                 cand_qty=8, final_qty=4)
        svc = RetrievalService(cache_size=64)
        svc.register_pipeline("f32", pipe, q[0], batch_size=4,
                              max_wait_s=0.002)
        svc.register_pipeline("bf16", pipe, q[0], batch_size=4,
                              max_wait_s=0.002, corpus_dtype="bfloat16")
        with svc:
            svc.submit(q[0], endpoint="f32").result()
            svc.submit(q[0], endpoint="bf16").result()
            snap1 = svc.snapshot()
            svc.submit(q[0], endpoint="f32").result()
            svc.submit(q[0], endpoint="bf16").result()
            snap2 = svc.snapshot()
        assert snap1.cache_hits == 0 and snap1.cache_misses == 2
        assert snap2.cache_hits == 2
        assert len(svc.cache) == 2


class TestTileCacheWarm:
    """The warm per-(space-kind, corpus-shape, dtype) auto_tile_n cache."""

    def setup_method(self):
        clear_tile_cache()

    def teardown_method(self):
        clear_tile_cache()

    def test_hit_miss_and_dtype_keyed_retuning(self):
        q, c, _ = planted_margin_corpus(4096, 128, 8, 16)
        pal = PallasBackend()          # tile_n=None -> auto-tuned
        space = DenseSpace("ip")
        pal.topk(space, q, c, 16)
        info = tile_cache_info()
        assert info == {"size": 1, "hits": 0, "misses": 1}
        pal.topk(space, q, c, 16)                       # warm
        assert tile_cache_info()["hits"] == 1
        # bf16 halves bytes_per_row -> a distinct key, tuned once
        cb = _bf16(c)
        pal.topk(space, q, cb, 16)
        pal.topk(space, q, cb, 16)
        info = tile_cache_info()
        assert info["size"] == 2 and info["misses"] == 2
        assert info["hits"] == 2

    def test_bf16_tunes_at_least_f32_tile(self):
        """Half the stream bytes can only move the roofline knee toward
        larger tiles (never smaller): assert directly on auto_tile_n."""
        from repro.core.backends import auto_tile_n
        kwargs = dict(b=8, k=16, flops_per_row=2 * 8 * 128,
                      resident_bytes=8 * (128 + 32) * 4)
        f32_tile = auto_tile_n(1 << 20, bytes_per_row=128 * 4, **kwargs)
        bf16_tile = auto_tile_n(1 << 20, bytes_per_row=128 * 2, **kwargs)
        assert bf16_tile >= f32_tile
        assert tile_cache_info()["size"] == 2

    def test_explicit_tile_bypasses_cache(self):
        q, c, _ = planted_margin_corpus(256, 16, 2, 4)
        PallasBackend(tile_n=64).topk(DenseSpace("ip"), q, c, 4)
        assert tile_cache_info() == {"size": 0, "hits": 0, "misses": 0}

    def test_thread_safety_under_concurrent_tuning(self):
        """Many threads auto-tuning distinct and shared configurations
        concurrently: every call is counted exactly once and the cache
        converges to one entry per configuration."""
        q, c, _ = planted_margin_corpus(512, 32, 4, 8)
        corpora = {"float32": c, "bfloat16": _bf16(c)}
        pal = PallasBackend()
        space = DenseSpace("ip")
        n_threads, reps = 8, 5
        errors = []

        def hammer(i):
            try:
                corpus = corpora["float32" if i % 2 else "bfloat16"]
                for _ in range(reps):
                    pal.topk(space, q, corpus, 8)
            except Exception as exc:      # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=hammer, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        info = tile_cache_info()
        assert info["size"] == 2
        assert info["hits"] + info["misses"] == n_threads * reps
        # get-or-compute is atomic under the cache lock, so racing first
        # calls can never double-miss: exactly one miss per configuration
        assert info["misses"] == 2

    def test_served_concurrent_load_keeps_cache_consistent(self):
        """The serving-layer version: a pallas-auto endpoint hammered by
        client threads; the warm cache serves every request after the
        first without a wrong-size tile or a torn counter."""
        q, c, _ = planted_margin_corpus(256, 16, 32, 6, seed=5)
        pipe = RetrievalPipeline(BruteForceGenerator(DenseSpace("ip"), c),
                                 cand_qty=12, final_qty=6)
        svc = RetrievalService(cache_size=0)
        svc.register_pipeline("auto_pallas", pipe, q[0], batch_size=8,
                              max_wait_s=0.002, backend="pallas",
                              corpus_dtype="bfloat16")
        with svc:
            futs, lock = [], threading.Lock()

            def client(lo, hi):
                for i in range(lo, hi):
                    f = svc.submit(q[i], endpoint="auto_pallas")
                    with lock:
                        futs.append((i, f))

            threads = [threading.Thread(target=client,
                                        args=(i * 8, (i + 1) * 8))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            results = [(i, f.result()) for i, f in futs]
        assert len(results) == 32
        info = tile_cache_info()
        assert info["hits"] + info["misses"] >= 1
        assert info["misses"] == info["size"]      # one miss per entry
        off = pipe.with_corpus_dtype("bf16").with_backend("pallas").run(q)
        # batching pads to batch_size with q[0]; every row must still be
        # the offline bf16 answer for its query
        for i, r in results:
            np.testing.assert_array_equal(r.indices,
                                          np.asarray(off.indices)[i])


class TestUlpHarnessSelfCheck:
    """The harness must be able to FAIL — a contract that can't reject
    anything guards nothing."""

    def test_recall_violation_detected(self):
        from repro.core.brute_force import TopK
        a = TopK(jnp.zeros((1, 3)), jnp.asarray([[0, 1, 2]], jnp.int32))
        b = TopK(jnp.zeros((1, 3)), jnp.asarray([[0, 1, 9]], jnp.int32))
        with pytest.raises(AssertionError, match="recall"):
            assert_bf16_oracle_contract(a, b)

    def test_ulp_violation_detected(self):
        from repro.core.brute_force import TopK
        idx = jnp.asarray([[0, 1, 2]], jnp.int32)
        a = TopK(jnp.asarray([[4.0, 2.0, 1.0]], jnp.float32), idx)
        bad = TopK(jnp.asarray([[4.5, 2.0, 1.0]], jnp.float32), idx)
        with pytest.raises(AssertionError, match="ULP"):
            assert_bf16_oracle_contract(a, bad)
        # and the bound itself admits exactly BF16_MAX_ULP at scale 4
        ok = TopK(jnp.asarray(
            [[4.0 + BF16_MAX_ULP * 2.0 ** -5, 2.0, 1.0]], jnp.float32), idx)
        assert_bf16_oracle_contract(a, ok)
