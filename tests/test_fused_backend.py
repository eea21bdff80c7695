"""Fused-space execution backends: mixed dense+sparse retrieval selects
on-device, within the stated f32 bound.

The contract under test: for ``FusedSpace``/``SparseSpace`` corpora,
``reference`` (one-shot exact_topk), ``streaming`` (pytree tile scan),
and ``pallas`` (the one-pass fused score+select kernel
``kernels/fused_topk.py``, interpret mode on CPU) return the same ids
and f32 scores within the bound of ``tests/_precision.py`` across
eager/jit/scan contexts; ``resolve_
backend`` stops falling back to reference for fused corpora (``"auto"``
picks the kernel for large ones); learned ``w_dense``/``w_sparse``
weights thread from ``core.fusion`` through the backend seam; and
``tile_n`` auto-tunes from the roofline model instead of a fixed size.
Mirrors the structure of ``tests/test_backends.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # bare install: seeded parametrized fallback
    from _proptest import given, settings, st

from repro.core.backends import (AUTO_PALLAS_MIN_ROWS, PallasBackend,
                                 ReferenceBackend, StreamingBackend,
                                 auto_tile_n, legal_tile, make_backend,
                                 resolve_backend)
from repro.core.brute_force import TopK
from repro.core.fusion import learn_fused_weights
from repro.core.pipeline import BruteForceGenerator, RetrievalPipeline
from repro.core.sparse import SparseVectors, from_dense
from repro.core.spaces import DenseSpace, FusedSpace, FusedVectors, SparseSpace
from repro.kernels import ops, ref
from repro.serving import RetrievalService
from _precision import assert_scores_close, assert_topk_close

pytestmark = pytest.mark.fused

BACKENDS = ("reference", "streaming", "pallas")
# (n, d_dense, nnz, b, k, tile): multiples, non-multiples (padding),
# tile > n, single-tile
SHAPES = [
    (64, 16, 4, 2, 4, 32),
    (300, 32, 8, 4, 5, 64),
    (257, 8, 16, 3, 7, 512),
    (128, 24, 6, 2, 10, 128),
]
WEIGHTS = [(0.6, 0.4), (1.0, 1.0), (0.0, 2.0), (0.3, 0.0), (-0.5, 1.5)]


def _fused_setup(n, v, nnz, dd, b, seed=0):
    rng = np.random.default_rng(seed)
    cd = rng.uniform(size=(n, v)) * (rng.uniform(size=(n, v)) > 0.8)
    qd = rng.uniform(size=(b, v)) * (rng.uniform(size=(b, v)) > 0.6)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    corpus = FusedVectors(jax.random.normal(k1, (n, dd)),
                          from_dense(jnp.asarray(cd, jnp.float32), nnz))
    queries = FusedVectors(jax.random.normal(k2, (b, dd)),
                           from_dense(jnp.asarray(qd, jnp.float32), nnz))
    return corpus, queries


def assert_topk_equal(want, got, ctx=""):
    """One program against itself: bit for bit."""
    np.testing.assert_array_equal(np.asarray(want.scores),
                                  np.asarray(got.scores), err_msg=str(ctx))
    np.testing.assert_array_equal(np.asarray(want.indices),
                                  np.asarray(got.indices), err_msg=str(ctx))


class TestKernelVsOracle:
    """ops.fused_topk against the pure library-path oracle ref.fused_topk_ref
    (which delegates to spaces.dense_scores + sparse_inner_qbatch_docs)."""

    @pytest.mark.parametrize("wd,ws", WEIGHTS)
    @pytest.mark.parametrize("n,dd,nnz,b,k,tile", SHAPES)
    def test_bit_identical_to_oracle(self, n, dd, nnz, b, k, tile, wd, ws):
        v = 50
        corpus, queries = _fused_setup(n, v, nnz, dd, b)
        got = ops.fused_topk(queries.sparse, queries.dense, corpus.sparse,
                             corpus.dense, v, k, w_dense=wd, w_sparse=ws,
                             tile_n=tile)
        want = ref.fused_topk_ref(
            queries.sparse, queries.dense, corpus.sparse, corpus.dense, v, k,
            w_dense=wd, w_sparse=ws)
        assert_topk_close(TopK(*want), got, ctx=(n, wd, ws))

    def test_l2_dense_component(self):
        """The kernel's l2 branch matches the oracle (kernel-level only:
        the backend capability gates fused corpora to ip — see
        core/backends.py)."""
        v = 40
        corpus, queries = _fused_setup(200, v, 6, 16, 3)
        got = ops.fused_topk(queries.sparse, queries.dense, corpus.sparse,
                             corpus.dense, v, 6, w_dense=0.7, w_sparse=0.3,
                             dense_kind="l2", tile_n=64)
        want = ref.fused_topk_ref(
            queries.sparse, queries.dense, corpus.sparse, corpus.dense, v, 6,
            w_dense=0.7, w_sparse=0.3, dense_kind="l2")
        assert_topk_close(TopK(*want), got)

    def test_single_component_calls(self):
        v = 50
        corpus, queries = _fused_setup(300, v, 8, 16, 3)
        # sparse-only, unscaled (SparseSpace semantics)
        got = ops.fused_topk(queries.sparse, None, corpus.sparse, None, v, 5,
                             tile_n=128)
        want = ref.fused_topk_ref(queries.sparse, None, corpus.sparse, None,
                                  v, 5)
        assert_topk_close(TopK(*want), got)
        # dense-only with a baked weight
        got = ops.fused_topk(None, queries.dense, None, corpus.dense, 0, 5,
                             w_dense=0.7, tile_n=64)
        want = ref.fused_topk_ref(None, queries.dense, None, corpus.dense,
                                  0, 5, w_dense=0.7)
        assert_topk_close(TopK(*want), got)

    def test_no_components_raises(self):
        with pytest.raises(ValueError, match="no overlapping components"):
            ops.fused_topk(None, None, None, None, 10, 5)

    def test_unweighted_two_components_raise(self):
        """Regression: both components with default (None) weights must
        raise, not silently drop the sparse part — there is no unscaled
        two-component path in the library either (FusedSpace always
        mixes with weights)."""
        v = 50
        corpus, queries = _fused_setup(128, v, 4, 8, 2)
        with pytest.raises(ValueError, match="requires w_dense"):
            ops.fused_topk(queries.sparse, queries.dense, corpus.sparse,
                           corpus.dense, v, 5, tile_n=64)
        with pytest.raises(ValueError, match="requires w_dense"):
            ref.fused_topk_ref(queries.sparse, queries.dense, corpus.sparse,
                               corpus.dense, v, 5)

    def test_fused_scores_bit_identical_to_space(self):
        """Regression: the score-only kernel (ops.fused_scores) stays a
        drop-in for FusedSpace.score_batch, within the f32 bound."""
        v = 50
        corpus, queries = _fused_setup(300, v, 8, 16, 3)
        space = FusedSpace(v, w_dense=0.6, w_sparse=0.4)
        want = space.score_batch(queries, corpus)
        got = ops.fused_scores(queries.sparse, queries.dense, corpus.sparse,
                               corpus.dense, v, 0.6, 0.4, tile_n=64)
        assert_scores_close(want, got)


class TestParity:
    """reference == streaming == pallas-interpret, within the f32 bound,
    for fused and pure-sparse corpora."""

    @pytest.mark.parametrize("wd,ws", WEIGHTS)
    @pytest.mark.parametrize("n,dd,nnz,b,k,tile", SHAPES)
    @pytest.mark.parametrize("name", BACKENDS[1:])
    def test_fused_bit_identical_to_reference(self, name, n, dd, nnz, b, k,
                                              tile, wd, ws):
        v = 50
        corpus, queries = _fused_setup(n, v, nnz, dd, b)
        space = FusedSpace(v, w_dense=wd, w_sparse=ws)
        want = ReferenceBackend().topk(space, queries, corpus, k)
        got = make_backend(name, tile_n=tile).topk(space, queries, corpus, k)
        assert_topk_close(want, got, ctx=(name, n, wd, ws))

    @pytest.mark.parametrize("name", BACKENDS[1:])
    def test_sparse_space_bit_identical(self, name):
        """Pure-sparse corpora ride the same kernel (dense part absent,
        sparse part unscaled)."""
        v = 50
        corpus, queries = _fused_setup(300, v, 8, 4, 3)
        space = SparseSpace(v)
        want = ReferenceBackend().topk(space, queries.sparse, corpus.sparse, 9)
        got = make_backend(name, tile_n=64).topk(space, queries.sparse,
                                                 corpus.sparse, 9)
        assert_topk_close(want, got, ctx=name)

    @pytest.mark.parametrize("name", BACKENDS[1:])
    def test_partial_components_match_reference(self, name):
        """FusedVectors with one side missing a component score only the
        overlap — identically on every backend."""
        v = 50
        corpus, queries = _fused_setup(200, v, 8, 16, 3)
        space = FusedSpace(v, w_dense=0.5, w_sparse=2.0)
        for q, c in [(FusedVectors(None, queries.sparse), corpus),
                     (queries, FusedVectors(corpus.dense, None)),
                     (FusedVectors(queries.dense, None),
                      FusedVectors(corpus.dense, None))]:
            want = ReferenceBackend().topk(space, q, c, 6)
            got = make_backend(name, tile_n=64).topk(space, q, c, 6)
            assert_topk_close(want, got, ctx=name)

    @pytest.mark.parametrize("name", BACKENDS[1:])
    def test_tie_break_matches_reference(self, name):
        """Duplicate fused rows force exact ties straddling tile
        boundaries; every backend breaks them toward the lower row id."""
        v = 30
        base, queries = _fused_setup(16, v, 4, 8, 2, seed=3)
        corpus = FusedVectors(
            jnp.tile(base.dense, (8, 1)),
            SparseVectors(jnp.tile(base.sparse.indices, (8, 1)),
                          jnp.tile(base.sparse.values, (8, 1))))
        space = FusedSpace(v, w_dense=0.5, w_sparse=0.5)
        want = ReferenceBackend().topk(space, queries, corpus, 24)
        got = make_backend(name, tile_n=32).topk(space, queries, corpus, 24)
        assert_topk_close(want, got, ctx=name)
        # duplicates score identically within one path: ties go to the
        # lower row id at every rank
        np.testing.assert_array_equal(np.asarray(want.indices),
                                      np.asarray(got.indices))

    @pytest.mark.parametrize("name", BACKENDS)
    def test_n_valid_masks_padding_rows(self, name):
        v = 40
        corpus, queries = _fused_setup(128, v, 6, 16, 2)
        padded = FusedVectors(
            jnp.pad(corpus.dense, ((0, 32), (0, 0))),
            SparseVectors(
                jnp.pad(corpus.sparse.indices, ((0, 32), (0, 0)),
                        constant_values=v),
                jnp.pad(corpus.sparse.values, ((0, 32), (0, 0)))))
        space = FusedSpace(v, w_dense=0.5, w_sparse=0.5)
        got = make_backend(name, **({} if name == "reference"
                                    else {"tile_n": 32})).topk(
            space, queries, padded, 8, n_valid=128)
        assert np.asarray(got.indices).max() < 128
        want = ReferenceBackend().topk(space, queries, corpus, 8)
        assert_topk_close(want, got, ctx=name)

    @pytest.mark.parametrize("n_valid", [0, 4])
    @pytest.mark.parametrize("name", BACKENDS)
    def test_k_exceeding_n_valid_matches_reference(self, name, n_valid):
        """Degenerate k > n_valid: the tiled paths reproduce reference's
        tail exactly (-inf scores, indices continuing from the first
        masked row)."""
        v = 30
        corpus, queries = _fused_setup(12, v, 4, 8, 2)
        space = FusedSpace(v, w_dense=0.5, w_sparse=0.5)
        want = ReferenceBackend().topk(space, queries, corpus, 8,
                                       n_valid=n_valid)
        got = make_backend(name, **({} if name == "reference"
                                    else {"tile_n": 4})).topk(
            space, queries, corpus, 8, n_valid=n_valid)
        assert_topk_equal(want, got, (name, n_valid))

    def test_parity_inside_jit(self):
        """The batcher may jit whole funnels: parity must survive tracing
        (the scan context comes free — streaming's tile loop is a
        lax.scan inside the jitted graph)."""
        v = 50
        corpus, queries = _fused_setup(300, v, 8, 16, 4)
        space = FusedSpace(v, w_dense=0.6, w_sparse=0.4)
        outs = []
        for name in BACKENDS:
            backend = make_backend(name)
            fn = jax.jit(lambda qq: backend.topk(space, qq, corpus, 10))
            outs.append(fn(queries))
        for got in outs[1:]:
            assert_topk_close(outs[0], got)

    def test_parity_jit_vs_eager(self):
        """With the corpus as a jit ARGUMENT (no constant folding), jitted
        results equal eager results within the f32 bound on every
        backend (XLA may fuse the jitted graph differently)."""
        v = 50
        corpus, queries = _fused_setup(300, v, 8, 16, 4)
        space = FusedSpace(v, w_dense=0.6, w_sparse=0.4)
        for name in BACKENDS:
            backend = make_backend(name)
            eager = backend.topk(space, queries, corpus, 10)
            jitted = jax.jit(lambda q, c: backend.topk(space, q, c, 10))(
                queries, corpus)
            assert_topk_close(eager, jitted, ctx=name)

    def test_auto_tiled_kernel_matches_fixed_tile(self):
        """tile_n=None auto-tunes; answers agree (within the f32 bound)
        at any tile."""
        v = 50
        corpus, queries = _fused_setup(300, v, 8, 16, 4)
        space = FusedSpace(v, w_dense=0.6, w_sparse=0.4)
        fixed = PallasBackend(tile_n=64).topk(space, queries, corpus, 10)
        auto = PallasBackend().topk(space, queries, corpus, 10)
        assert_topk_close(fixed, auto)


class TestPaddedCOOInvariants:
    """Property tests for the padded-COO layout through the fused kernel."""

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_extra_pad_slots_are_inert(self, seed):
        """Appending pad slots (id == V, value 0) to every corpus row must
        not change scores or selected ids: the pad id matches no query
        term."""
        v = 40
        corpus, queries = _fused_setup(128, v, 6, 8, 3, seed=seed % 997)
        space = FusedSpace(v, w_dense=0.5, w_sparse=0.5)
        extra = 3
        fat = FusedVectors(
            corpus.dense,
            SparseVectors(
                jnp.pad(corpus.sparse.indices, ((0, 0), (0, extra)),
                        constant_values=v),
                jnp.pad(corpus.sparse.values, ((0, 0), (0, extra)))))
        want = PallasBackend(tile_n=32).topk(space, queries, corpus, 7)
        got = PallasBackend(tile_n=32).topk(space, queries, fat, 7)
        assert_topk_equal(want, got)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_nnz_permutation_invariance(self, seed):
        """Permuting the nnz slots within every corpus row never changes
        scores or selected ids — checked through pallas AND reference, so
        the property holds across the whole seam.  Weights are
        integer-valued floats so every product and partial sum is exactly
        representable: the invariance is then bitwise (with arbitrary
        floats the slot-order reduction would round differently, on every
        backend alike — an IEEE property, not a kernel bug)."""
        rng = np.random.default_rng(seed % 2**31)
        v, nnz, b, n = 40, 6, 3, 96
        cd = (rng.integers(0, 8, size=(n, v))
              * (rng.random(size=(n, v)) > 0.8)).astype(np.float32)
        qd = (rng.integers(0, 8, size=(b, v))
              * (rng.random(size=(b, v)) > 0.6)).astype(np.float32)
        k1, _ = jax.random.split(jax.random.PRNGKey(seed % 997))
        corpus = FusedVectors(jax.random.normal(k1, (n, 16)),
                              from_dense(jnp.asarray(cd), nnz))
        queries = FusedVectors(jax.random.normal(k1, (b, 16)),
                               from_dense(jnp.asarray(qd), nnz))
        perm = rng.permutation(nnz)
        shuffled = FusedVectors(
            corpus.dense,
            SparseVectors(corpus.sparse.indices[:, perm],
                          corpus.sparse.values[:, perm]))
        space = FusedSpace(v, w_dense=0.5, w_sparse=0.5)
        for backend in (PallasBackend(tile_n=32), ReferenceBackend()):
            want = backend.topk(space, queries, corpus, 7)
            got = backend.topk(space, queries, shuffled, 7)
            np.testing.assert_array_equal(np.asarray(want.scores),
                                          np.asarray(got.scores))
            np.testing.assert_array_equal(np.asarray(want.indices),
                                          np.asarray(got.indices))


class TestResolution:
    def test_auto_selects_pallas_for_large_fused_f32(self):
        """The acceptance criterion: 'auto' stops degrading fused corpora
        to reference once they are large."""
        n = AUTO_PALLAS_MIN_ROWS
        corpus, _ = _fused_setup(64, 16, 2, 8, 1)
        big = FusedVectors(
            jnp.zeros((n, 8), jnp.float32),
            SparseVectors(jnp.zeros((n, 2), jnp.int32),
                          jnp.zeros((n, 2), jnp.float32)))
        assert isinstance(resolve_backend("auto", FusedSpace(16), big),
                          PallasBackend)
        assert isinstance(resolve_backend("auto", FusedSpace(16), corpus),
                          ReferenceBackend)
        # pure-sparse too
        assert isinstance(resolve_backend("auto", SparseSpace(16),
                                          big.sparse), PallasBackend)

    def test_capability_refusals_fall_back(self):
        v = 30
        corpus, _ = _fused_setup(64, v, 4, 8, 2)
        # bf16 components are INSIDE the precision contract now (PR 5,
        # tests/test_bf16.py) — the refusal cases are dtypes outside it
        f16_dense = FusedVectors(corpus.dense.astype(jnp.float16),
                                 corpus.sparse)
        f16_vals = FusedVectors(corpus.dense,
                                SparseVectors(corpus.sparse.indices,
                                              corpus.sparse.values.astype(
                                                  jnp.float16)))
        for space, c in [
            (FusedSpace(v, dense_kind="l2"), corpus),        # l2 fused
            (FusedSpace(v, dense_kind="cosine"), corpus),    # cosine fused
            (SparseSpace(v, "cosine"), corpus.sparse),       # cosine sparse
            (FusedSpace(v), f16_dense),                      # non-contract
            (FusedSpace(v), f16_vals),                       # dtypes
            (FusedSpace(v), FusedVectors(None, None)),       # empty corpus
        ]:
            assert PallasBackend().supports(space, c) is not None, space
            assert isinstance(resolve_backend("pallas", space, c),
                              ReferenceBackend), space

    def test_learned_weights_thread_through_seam(self):
        """fusion.learn_fused_weights -> FusedSpace.with_weights ->
        pallas backend: the learned mix is what the kernel executes."""
        v = 50
        corpus, queries = _fused_setup(300, v, 8, 16, 8, seed=11)
        space = FusedSpace(v)
        # candidate pool + labels that prefer the dense component
        dense_s = np.asarray(DenseSpace("ip").score_batch(queries.dense,
                                                          corpus.dense))
        sparse_s = np.asarray(SparseSpace(v).score_batch(queries.sparse,
                                                         corpus.sparse))
        labels = (dense_s >= np.quantile(dense_s, 0.9, axis=1,
                                         keepdims=True)).astype(np.float32)
        wd, ws, metric = learn_fused_weights(
            jnp.asarray(dense_s), jnp.asarray(sparse_s),
            jnp.asarray(labels), jnp.ones_like(jnp.asarray(labels), bool),
            n_rounds=2, n_restarts=1)
        assert metric > 0
        learned = space.with_weights(wd, ws)
        want = ReferenceBackend().topk(learned, queries, corpus, 10)
        got = resolve_backend("pallas", learned, corpus).topk(
            learned, queries, corpus, 10)
        assert_topk_close(want, got)
        # and the learned weights actually reach the scores: a different
        # mix must produce different top-1 scores somewhere
        other = ReferenceBackend().topk(space.with_weights(ws, wd),
                                        queries, corpus, 10)
        if not np.allclose(wd, ws):
            assert not np.array_equal(np.asarray(want.scores),
                                      np.asarray(other.scores))

    def test_pipeline_seam_fused_pallas(self):
        """generator backend=, with_backend, descriptor key — the existing
        seams now carry fused corpora to the kernel."""
        v = 50
        corpus, queries = _fused_setup(300, v, 8, 16, 4)
        space = FusedSpace(v, w_dense=0.6, w_sparse=0.4)
        gen = BruteForceGenerator(space, corpus)
        want = gen.generate(queries, 10)
        for name in BACKENDS:
            got = gen.with_backend(name).generate(queries, 10)
            assert_topk_close(want, got, ctx=name)
        rebound = RetrievalPipeline(gen, cand_qty=10,
                                    final_qty=10).with_backend("pallas")
        assert isinstance(rebound.backend, PallasBackend)
        assert_topk_close(want, rebound.run(queries))


class TestAutoTile:
    def test_tiles_are_legal_and_lane_aligned(self):
        for n, bpr, fpr in [(100000, 256, 1024), (10**6, 65536, 2**17),
                            (50000, 8, 64)]:
            tile = auto_tile_n(n, b=8, k=10, bytes_per_row=bpr,
                               flops_per_row=fpr)
            assert 1 <= tile <= n
            assert tile % 128 == 0 or tile == n
            assert tile == legal_tile(n, tile)

    def test_small_corpus_clamps(self):
        assert auto_tile_n(300, b=4, k=5, bytes_per_row=64,
                           flops_per_row=128) == 300

    def test_fat_rows_get_smaller_tiles(self):
        thin = auto_tile_n(10**6, b=8, k=10, bytes_per_row=256,
                           flops_per_row=1024)
        fat = auto_tile_n(10**6, b=8, k=10, bytes_per_row=65536,
                          flops_per_row=1024)
        assert fat < thin        # VMEM budget binds sooner on fat rows

    def test_resident_bytes_shrink_budget(self):
        free = auto_tile_n(10**6, b=8, k=10, bytes_per_row=4096,
                           flops_per_row=1024)
        crowded = auto_tile_n(10**6, b=8, k=10, bytes_per_row=4096,
                              flops_per_row=1024,
                              resident_bytes=7 * 2**20)
        assert crowded <= free

    def test_explicit_tile_still_wins(self):
        v = 50
        corpus, queries = _fused_setup(300, v, 8, 16, 2)
        space = FusedSpace(v, w_dense=0.5, w_sparse=0.5)
        be = PallasBackend(tile_n=64)
        assert be._fused_tile(300, 2, 5, v, 8, 16) == 64
        assert "tile_n=64" in be.identity
        assert "tile_n=auto" in PallasBackend().identity


class TestServedFused:
    def test_fused_endpoint_pair_parity_under_load(self):
        """One fused corpus behind two endpoints differing only in
        backend= — the same answers (within the f32 bound) through the
        batcher under concurrent load, kernel identity in the stats
        snapshot."""
        v = 50
        corpus, queries = _fused_setup(300, v, 8, 16, 40, seed=7)
        space = FusedSpace(v, w_dense=0.6, w_sparse=0.4)
        pipe = RetrievalPipeline(BruteForceGenerator(space, corpus),
                                 cand_qty=20, final_qty=10)
        one = lambda i: jax.tree.map(lambda x: x[i], queries)
        svc = RetrievalService(cache_size=0)
        svc.register_pipeline("ref", pipe, one(0), batch_size=8,
                              max_wait_s=0.005, backend="reference")
        svc.register_pipeline("pal", pipe, one(0), batch_size=8,
                              max_wait_s=0.005, backend="pallas")
        with svc:
            futs_ref = [svc.submit(one(i), endpoint="ref") for i in range(40)]
            futs_pal = [svc.submit(one(i), endpoint="pal") for i in range(40)]
            for a, b in zip(futs_ref, futs_pal):
                assert_topk_close(a.result(), b.result())
            snap = svc.snapshot()
        assert snap.endpoints["ref"].backend == "reference"
        assert snap.endpoints["pal"].backend.startswith("pallas(")
        # served results equal the offline run too
        off = pipe.run(queries)
        assert np.array_equal(
            np.stack([f.result().indices for f in futs_ref]),
            np.asarray(off.indices))
        served = [f.result() for f in futs_pal]
        assert_topk_close(off, TopK(np.stack([r.scores for r in served]),
                                    np.stack([r.indices for r in served])))
