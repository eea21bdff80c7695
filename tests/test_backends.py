"""Execution backends: one generator API, three exact paths.

The contract under test: ``reference`` (one-shot exact_topk),
``streaming`` (tiled scan), and ``pallas`` (fused kernel, interpret mode
on CPU) return the same ids with f32 scores within the stated bound of
``tests/_precision.py`` for dense ip/l2,
``resolve_backend`` falls back to reference for spaces the kernel can't
serve, and the serving stack exposes the backend per endpoint — in stats
snapshots and in cache keys (the regression half of this file).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backends import (AUTO_STREAMING_MIN_ROWS, PallasBackend,
                                 ReferenceBackend, StreamingBackend,
                                 available_backends, backend_identity,
                                 legal_tile, make_backend, resolve_backend)
from repro.core.brute_force import TopK
from repro.core.pipeline import (BruteForceGenerator, InvertedIndexGenerator,
                                 RetrievalPipeline, StreamingGenerator)
from repro.core.sparse import from_dense
from repro.core.spaces import DenseSpace, FusedSpace, FusedVectors, SparseSpace
from repro.serving import QueryCache, RetrievalService, ShardedPipeline
from _precision import assert_topk_close

BACKENDS = ("reference", "streaming", "pallas")
SHAPES = [
    # (n, d, b, k, tile): multiples, non-multiples (padding), tile > n
    (64, 16, 2, 4, 32),
    (300, 32, 4, 5, 64),
    (512, 64, 8, 10, 128),
    (257, 48, 3, 7, 512),
]


def _mk(n, d, b, seed=0, dtype=jnp.float32):
    kq, kc = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kq, (b, d), dtype),
            jax.random.normal(kc, (n, d), dtype))


def _sparse_setup(n=64, v=50, nnz=8, b=3):
    rng = np.random.default_rng(0)
    cd = rng.uniform(size=(n, v)) * (rng.uniform(size=(n, v)) > 0.7)
    qd = rng.uniform(size=(b, v)) * (rng.uniform(size=(b, v)) > 0.6)
    return (SparseSpace(v),
            from_dense(jnp.asarray(qd, jnp.float32), nnz),
            from_dense(jnp.asarray(cd, jnp.float32), nnz))


class TestParity:
    @pytest.mark.parametrize("kind", ["ip", "l2"])
    @pytest.mark.parametrize("n,d,b,k,tile", SHAPES)
    @pytest.mark.parametrize("name", BACKENDS[1:])
    def test_bit_identical_to_reference(self, name, n, d, b, k, tile, kind):
        """streaming and pallas (interpret) == reference within the f32
        bound."""
        q, c = _mk(n, d, b)
        space = DenseSpace(kind)
        want = ReferenceBackend().topk(space, q, c, k)
        got = make_backend(name, tile_n=tile).topk(space, q, c, k)
        assert_topk_close(want, got, ctx=(name, n, kind))

    @pytest.mark.parametrize("name", BACKENDS[1:])
    def test_tie_break_matches_reference(self, name):
        """Duplicate corpus rows force exact score ties straddling tile
        boundaries; every backend must break them toward the lower row id
        like lax.top_k does."""
        base = jax.random.normal(jax.random.PRNGKey(3), (8, 16))
        c = jnp.tile(base, (16, 1))                   # 128 rows, 16x each
        q = jax.random.normal(jax.random.PRNGKey(4), (2, 16))
        space = DenseSpace("ip")
        want = ReferenceBackend().topk(space, q, c, 24)
        got = make_backend(name, tile_n=32).topk(space, q, c, 24)
        assert_topk_close(want, got, ctx=name)
        # duplicates score identically within one path: ties go to the
        # lower row id at every rank
        assert np.array_equal(np.asarray(want.indices), np.asarray(got.indices))

    @pytest.mark.parametrize("name", BACKENDS)
    def test_n_valid_masks_padding_rows(self, name):
        """A pre-padded corpus with n_valid never surfaces padding rows."""
        q, c = _mk(96, 16, 2)
        c = jnp.pad(c, ((0, 32), (0, 0)))            # 32 zero padding rows
        space = DenseSpace("ip")
        got = make_backend(name, **({} if name == "reference"
                                    else {"tile_n": 32})).topk(
            space, q, c, 8, n_valid=96)
        assert np.asarray(got.indices).max() < 96
        want = ReferenceBackend().topk(space, q, c[:96], 8)
        assert_topk_close(want, got, ctx=name)

    @pytest.mark.parametrize("n_valid", [0, 4])
    @pytest.mark.parametrize("name", BACKENDS)
    def test_k_exceeding_n_valid_matches_reference(self, name, n_valid):
        """Degenerate k > n_valid: the tiled paths must reproduce the
        reference tail exactly (-inf scores, indices continuing from the
        first masked row) instead of surfacing their own fill values."""
        q, c = _mk(12, 8, 2)
        space = DenseSpace("ip")
        want = ReferenceBackend().topk(space, q, c, 8, n_valid=n_valid)
        got = make_backend(name, **({} if name == "reference"
                                    else {"tile_n": 4})).topk(
            space, q, c, 8, n_valid=n_valid)
        assert np.array_equal(np.asarray(want.scores), np.asarray(got.scores))
        assert np.array_equal(np.asarray(want.indices), np.asarray(got.indices))

    def test_parity_inside_jit(self):
        """The batcher may jit whole funnels: parity (within the f32
        bound) must survive tracing."""
        q, c = _mk(300, 32, 4)
        space = DenseSpace("l2")
        outs = []
        for name in BACKENDS:
            backend = make_backend(name)
            fn = jax.jit(lambda qq: backend.topk(space, qq, c, 10))
            outs.append(fn(q))
        for got in outs[1:]:
            assert_topk_close(outs[0], got)


class TestResolution:
    def test_registry_lists_all(self):
        assert set(BACKENDS) <= set(available_backends())

    def test_unknown_name_raises(self):
        with pytest.raises(ValueError, match="unknown backend"):
            make_backend("simd")

    def test_named_resolution_types(self):
        q, c = _mk(64, 16, 2)
        space = DenseSpace("ip")
        assert isinstance(resolve_backend("reference", space, c),
                          ReferenceBackend)
        assert isinstance(resolve_backend("streaming", space, c),
                          StreamingBackend)
        assert isinstance(resolve_backend("pallas", space, c), PallasBackend)

    def test_pallas_serves_sparse_ip_refuses_cosine(self):
        """PR 4: the fused kernel took over sparse-ip corpora; cosine
        (which normalises inside score_batch) still falls back."""
        space, _q, c = _sparse_setup()
        assert isinstance(resolve_backend("pallas", space, c),
                          PallasBackend)
        cosine = SparseSpace(space.vocab_size, "cosine")
        assert isinstance(resolve_backend("pallas", cosine, c),
                          ReferenceBackend)

    def test_fused_corpus_serves_on_every_backend(self):
        """PR 4: fused corpora stopped forcing the reference fallback —
        streaming scans the pytree tiles, pallas runs the fused kernel."""
        sp_space, qs, cs = _sparse_setup()
        dq, dc = _mk(64, 16, 3)
        fused_c = FusedVectors(dc, cs)
        space = FusedSpace(sp_space.vocab_size)
        assert isinstance(resolve_backend("streaming", space, fused_c),
                          StreamingBackend)
        assert isinstance(resolve_backend("pallas", space, fused_c),
                          PallasBackend)
        # reference itself always serves
        assert isinstance(resolve_backend("reference", space, fused_c),
                          ReferenceBackend)
        # the kernel's fused capability is ip-only: l2 fused falls back
        assert isinstance(
            resolve_backend("pallas", FusedSpace(sp_space.vocab_size,
                                                 dense_kind="l2"), fused_c),
            ReferenceBackend)

    def test_pallas_refuses_non_ip_l2_kinds(self):
        _q, c = _mk(64, 16, 2)
        assert PallasBackend().supports(DenseSpace("cosine"), c) is not None
        assert PallasBackend().supports(DenseSpace("ip"), c) is None
        assert PallasBackend().supports(DenseSpace("l2"), c) is None

    def test_pallas_refuses_unsupported_dtype(self):
        """The capability matrix follows the precision contract: f32 and
        bf16 corpora are served (dense AND sparse/fused components —
        tests/test_bf16.py sweeps the bf16 tier); anything else falls
        back to the library path."""
        _q, c = _mk(64, 16, 2)
        assert PallasBackend().supports(
            DenseSpace("ip"), c.astype(jnp.int8)) is not None
        assert PallasBackend().supports(
            DenseSpace("ip"), c.astype(jnp.bfloat16)) is None
        space, _qs, cs = _sparse_setup()
        bf16_sparse = type(cs)(cs.indices, cs.values.astype(jnp.bfloat16))
        assert PallasBackend().supports(space, bf16_sparse) is None
        fused = FusedSpace(space.vocab_size)
        assert PallasBackend().supports(
            fused, FusedVectors(c.astype(jnp.bfloat16), bf16_sparse)) is None
        assert PallasBackend().supports(
            fused, FusedVectors(c.astype(jnp.float16), None)) is not None

    def test_instance_passthrough_and_fallback(self):
        q, c = _mk(64, 16, 2)
        be = StreamingBackend(tile_n=16)
        assert resolve_backend(be, DenseSpace("ip"), c) is be
        space, _qs, cs = _sparse_setup()
        assert resolve_backend(be, space, cs) is be   # PR 4: pytree tiles
        # a corpus with no row-major array leaves still falls back
        class OpaqueIndex:
            pass
        assert isinstance(resolve_backend(be, space, OpaqueIndex()),
                          ReferenceBackend)

    def test_auto_small_dense_is_reference(self):
        q, c = _mk(64, 16, 2)
        assert isinstance(resolve_backend("auto", DenseSpace("ip"), c),
                          ReferenceBackend)

    def test_auto_large_dense_is_streaming_off_tpu(self):
        c = jnp.zeros((AUTO_STREAMING_MIN_ROWS, 4), jnp.float32)
        resolved = resolve_backend("auto", DenseSpace("ip"), c)
        if jax.default_backend() == "tpu":
            assert isinstance(resolved, PallasBackend)
        else:
            assert isinstance(resolved, StreamingBackend)

    def test_auto_sparse_is_reference(self):
        space, _qs, cs = _sparse_setup()
        assert isinstance(resolve_backend("auto", space, cs),
                          ReferenceBackend)

    def test_legal_tile_clamps(self):
        assert legal_tile(300, 8192) == 300
        assert legal_tile(8192, 2048) == 2048
        assert legal_tile(5, 0) == 1

    def test_identity_strings(self):
        assert ReferenceBackend().identity == "reference"
        assert "tile_n=64" in StreamingBackend(tile_n=64).identity
        assert PallasBackend().identity.startswith("pallas(")
        assert backend_identity(None) is None
        assert backend_identity("pallas") == "pallas"
        assert backend_identity(ReferenceBackend()) == "reference"


class TestGenerators:
    def test_generator_with_backend_parity(self):
        q, c = _mk(300, 32, 4)
        gen = BruteForceGenerator(DenseSpace("l2"), c)
        want = gen.generate(q, 10)
        for name in BACKENDS:
            got = gen.with_backend(name).generate(q, 10)
            assert_topk_close(want, got, ctx=name)

    def test_string_backend_in_constructor(self):
        """The documented contract: backend= accepts a name directly at
        construction, not only via with_backend."""
        q, c = _mk(256, 16, 3)
        want = BruteForceGenerator(DenseSpace("ip"), c).generate(q, 8)
        for name in ("pallas", "streaming", "auto"):
            got = BruteForceGenerator(DenseSpace("ip"), c,
                                      backend=name).generate(q, 8)
            assert_topk_close(want, got, ctx=name)

    def test_streaming_generator_alias(self):
        q, c = _mk(256, 16, 3)
        a = StreamingGenerator(DenseSpace("ip"), c, tile_n=64).generate(q, 8)
        b = BruteForceGenerator(DenseSpace("ip"), c).generate(q, 8)
        assert_topk_close(b, a)

    def test_streaming_generator_with_backend_keeps_tile(self):
        """tile_n bounds the working set; rebinding must not silently
        revert it to the default."""
        _q, c = _mk(256, 16, 3)
        gen = StreamingGenerator(DenseSpace("ip"), c, tile_n=64)
        assert gen.with_backend("streaming").backend.tile_n == 64
        assert gen.with_backend("pallas").backend.tile_n == 64
        assert isinstance(gen.with_backend("reference").backend,
                          ReferenceBackend)

    def test_pipeline_with_backend(self):
        q, c = _mk(300, 32, 4)
        pipe = RetrievalPipeline(BruteForceGenerator(DenseSpace("ip"), c),
                                 cand_qty=20, final_qty=10)
        rebound = pipe.with_backend("pallas")
        assert isinstance(rebound.backend, PallasBackend)
        a, b = pipe.run(q), rebound.run(q)
        assert_topk_close(a, b)

    def test_pipeline_with_backend_rejects_backendless_generator(self):
        pipe = RetrievalPipeline(InvertedIndexGenerator(index=None))
        with pytest.raises(TypeError, match="does not take"):
            pipe.with_backend("pallas")

    def test_from_descriptor_backend_key(self):
        q, c = _mk(128, 16, 2)
        gen = BruteForceGenerator(DenseSpace("ip"), c)
        pipe = RetrievalPipeline.from_descriptor(
            {"candProv": "gen", "backend": "streaming", "candQty": 16,
             "finalQty": 8},
            {"gen": gen})
        assert isinstance(pipe.backend, StreamingBackend)
        want = RetrievalPipeline(gen, cand_qty=16, final_qty=8).run(q)
        got = pipe.run(q)
        assert_topk_close(want, got)


class TestShardedBackend:
    @pytest.mark.parametrize("name", BACKENDS)
    def test_sharded_backend_bit_identical(self, name):
        q, c = _mk(300, 32, 4, seed=7)
        space = DenseSpace("ip")
        base = RetrievalPipeline(BruteForceGenerator(space, c),
                                 cand_qty=20, final_qty=10)
        with ShardedPipeline.from_corpus(space, c, 3, cand_qty=20,
                                         final_qty=10,
                                         backend=name) as sharded:
            want, got = base.run(q), sharded.run(q)
            assert_topk_close(want, got, ctx=name)

    def test_backend_and_factory_mutually_exclusive(self):
        _q, c = _mk(64, 16, 2)
        with pytest.raises(ValueError, match="not both"):
            ShardedPipeline.from_corpus(
                DenseSpace("ip"), c, 2, backend="pallas",
                generator_factory=lambda s: BruteForceGenerator(
                    DenseSpace("ip"), s.corpus))

    def test_with_backend_rebinds_every_shard(self):
        q, c = _mk(256, 16, 3)
        space = DenseSpace("l2")
        with ShardedPipeline.from_corpus(space, c, 2, cand_qty=16,
                                         final_qty=8) as sharded:
            rebound = sharded.with_backend("pallas")
            try:
                assert all(isinstance(g.backend, PallasBackend)
                           for g in rebound.generators)
                want, got = sharded.run(q), rebound.run(q)
                assert_topk_close(want, got)
            finally:
                rebound.close()


class TestServedParity:
    """The acceptance contract: one corpus, live endpoints differing only
    in ``backend=``, the same answers (within the f32 bound) through the
    batcher under load,
    backend identity visible in snapshots."""

    @pytest.mark.parametrize("name", BACKENDS)
    def test_endpoint_pair_parity_under_load(self, name):
        corpus = jax.random.normal(jax.random.PRNGKey(1), (300, 16))
        queries = jax.random.normal(jax.random.PRNGKey(2), (40, 16))
        pipe = RetrievalPipeline(BruteForceGenerator(DenseSpace("ip"), corpus),
                                 cand_qty=20, final_qty=10)
        svc = RetrievalService(cache_size=0)
        svc.register_pipeline("ref", pipe, queries[0], batch_size=8,
                              max_wait_s=0.005, backend="reference")
        svc.register_pipeline("alt", pipe, queries[0], batch_size=8,
                              max_wait_s=0.005, backend=name)
        with svc:
            futs_ref = [svc.submit(queries[i], endpoint="ref")
                        for i in range(40)]
            futs_alt = [svc.submit(queries[i], endpoint="alt")
                        for i in range(40)]
            for a, b in zip(futs_ref, futs_alt):
                assert_topk_close(a.result(), b.result(), ctx=name)
            snap = svc.snapshot()
        assert snap.endpoints["ref"].backend == "reference"
        assert snap.endpoints["alt"].backend.startswith(name)
        # served results equal the offline run too
        off = pipe.run(queries)
        assert np.array_equal(
            np.stack([f.result().indices for f in futs_ref]),
            np.asarray(off.indices))
        served = [f.result() for f in futs_alt]
        assert_topk_close(off, TopK(np.stack([r.scores for r in served]),
                                    np.stack([r.indices for r in served])))

    def test_service_closes_rebound_sharded_pool(self):
        """register_pipeline(backend=) on a ShardedPipeline creates a
        rebound pipeline with its own thread pool; the service must shut
        that pool down on close (the caller never sees the rebound
        object)."""
        import threading

        corpus = jax.random.normal(jax.random.PRNGKey(1), (128, 8))
        queries = jax.random.normal(jax.random.PRNGKey(2), (4, 8))
        pipe = ShardedPipeline.from_corpus(DenseSpace("ip"), corpus, 2,
                                           cand_qty=8, final_qty=4)
        before = {t for t in threading.enumerate()
                  if t.name.startswith("shard")}
        svc = RetrievalService(cache_size=0)
        svc.register_pipeline("s", pipe, queries[0], batch_size=4,
                              max_wait_s=0.002, backend="streaming")
        with svc:
            svc.submit(queries[0], endpoint="s").result()
        pipe.close()
        leaked = [t for t in threading.enumerate()
                  if t.name.startswith("shard") and t not in before
                  and t.is_alive()]
        assert not leaked, f"rebound pipeline leaked threads: {leaked}"

    def test_runner_backend_is_label_only(self):
        corpus = jax.random.normal(jax.random.PRNGKey(1), (64, 8))
        queries = jax.random.normal(jax.random.PRNGKey(2), (4, 8))
        pipe = RetrievalPipeline(BruteForceGenerator(DenseSpace("ip"), corpus),
                                 cand_qty=8, final_qty=4)
        svc = RetrievalService(cache_size=0)
        svc.register_runner("raw", lambda q, t: pipe.run(q, t), queries[0],
                            backend="custom-simd")
        with svc:
            svc.submit(queries[0], endpoint="raw").result()
            snap = svc.snapshot()
        assert snap.endpoints["raw"].backend == "custom-simd"

    def test_register_pipeline_rejects_backendless_pipeline(self):
        """backend= on register_pipeline promises rebinding; a duck-typed
        pipeline without the seam must be rejected, not silently labelled
        with a backend that is not executing."""
        class OpaquePipeline:
            def run(self, q, t):
                return q

        queries = jax.random.normal(jax.random.PRNGKey(2), (4, 8))
        svc = RetrievalService(cache_size=0)
        with svc:
            with pytest.raises(TypeError, match="register_runner"):
                svc.register_pipeline("x", OpaquePipeline(), queries[0],
                                      backend="pallas")


class TestCacheBackendIdentity:
    """Regression: the result cache must never alias entries across
    endpoints that differ only in execution backend."""

    def test_key_differs_by_backend(self):
        cache = QueryCache(16)
        q = np.ones(8, np.float32)
        k_ref = cache.key("dense", q, backend="reference")
        k_pal = cache.key("dense", q, backend="pallas(tile_n=2048)")
        k_none = cache.key("dense", q)
        assert len({k_ref, k_pal, k_none}) == 3

    def test_key_fields_are_framed(self):
        """Sliding bytes across the endpoint/backend boundary must not
        collide (framing regression)."""
        cache = QueryCache(16)
        q = np.ones(8, np.float32)
        assert (cache.key("denseab", q, backend="c")
                != cache.key("densea", q, backend="bc"))
        assert (cache.key("dense", q, backend="ab")
                != cache.key("densea", q, backend="b"))

    def test_service_cache_isolates_backends(self):
        """Same corpus + same query through two endpoints differing only
        in backend: each endpoint takes its own cache miss (no aliasing),
        repeats hit their own entry."""
        corpus = jax.random.normal(jax.random.PRNGKey(1), (128, 8))
        queries = jax.random.normal(jax.random.PRNGKey(2), (4, 8))
        pipe = RetrievalPipeline(BruteForceGenerator(DenseSpace("ip"), corpus),
                                 cand_qty=8, final_qty=4)
        svc = RetrievalService(cache_size=64)
        svc.register_pipeline("ref", pipe, queries[0], batch_size=4,
                              max_wait_s=0.002, backend="reference")
        svc.register_pipeline("pal", pipe, queries[0], batch_size=4,
                              max_wait_s=0.002, backend="pallas")
        with svc:
            a = svc.submit(queries[0], endpoint="ref").result()
            b = svc.submit(queries[0], endpoint="pal").result()
            snap1 = svc.snapshot()
            # repeats: must be hits now
            a2 = svc.submit(queries[0], endpoint="ref").result()
            b2 = svc.submit(queries[0], endpoint="pal").result()
            snap2 = svc.snapshot()
        assert snap1.cache_hits == 0 and snap1.cache_misses == 2
        assert snap2.cache_hits == 2
        assert len(svc.cache) == 2          # one entry per backend endpoint
        assert_topk_close(a, b)
        assert np.array_equal(a2.scores, a.scores)
        assert np.array_equal(b2.scores, b.scores)


class TestBackendImmutability:
    def test_backends_are_frozen_and_hashable(self):
        """Backends ride inside frozen generator dataclasses and jit
        closures: they must be immutable value objects."""
        for be in (ReferenceBackend(), StreamingBackend(), PallasBackend()):
            assert dataclasses.is_dataclass(be)
            hash(be)
            with pytest.raises(dataclasses.FrozenInstanceError):
                be.tile_n = 1
