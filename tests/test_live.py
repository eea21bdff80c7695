"""The live tier: mutable corpora under the frozen-equivalence contract.

One invariant anchors everything here (docs/ARCHITECTURE.md "Live
corpora"): a query against a corpus that got there by *any* randomized
sequence of insert / delete / upsert batches — before or after any
number of compactions — answers exactly like a **fresh-built frozen
corpus at the same logical state**.  Bit-identical for the exact
backends (reference / streaming / pallas), measured-recall-equivalent
(tests/_recall.py gates) when the main segment is served by an ANN
backend.  On top of that: segment-algebra properties (compaction
commutes with querying, tombstoned ids never surface even when
``k > n_live``, logical ids are stable across epochs), snapshot
consistency (a reader can never observe a half-applied mutation batch),
generation-keyed cache isolation (a mutation makes a stale hit
structurally impossible), and writer/reader/compactor races under a
real ``RetrievalService``.  CI runs this file via the ``live`` marker
step; schedules come from ``tests/_mutation.py``.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # bare install: seeded parametrized cases
    from _proptest import given, settings, st

from repro.core import segments
from repro.core.backends import (GraphANNBackend, ann_index_cache_info,
                                 clear_ann_index_cache)
from repro.core.brute_force import TopK, concat_topk, exact_topk, merge_topk
from repro.core.pipeline import BruteForceGenerator, RetrievalPipeline
from repro.core.spaces import DenseSpace
from repro.serving import (LiveCorpus, LiveGenerator, RetrievalService,
                           SnapshotGenerator, quantized_key)
from repro.serving.sharded import CorpusShard, ShardedPipeline
from tests._mutation import (apply_schedule, assert_live_equals_frozen,
                             assert_topk_equal, frozen_oracle,
                             random_schedule, simulate_live_ids)
from tests._recall import (ANN_RECALL_TARGET, assert_recall_contract,
                           oracle_margin, planted_cluster_corpus)

pytestmark = pytest.mark.live

N0, D, B, K = 48, 16, 4, 10
SEED_MAX = 2**31 - 1


def _space():
    return DenseSpace("ip")


def _base(seed=0, n=N0):
    rng = np.random.default_rng(seed)
    corpus = jnp.asarray(rng.standard_normal((n, D)).astype(np.float32))
    queries = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))
    return corpus, queries


def _fresh(corpus=None, **kw):
    kw.setdefault("max_append", 10**9)     # no implicit compaction unless
    return LiveCorpus(_space(), corpus, **kw)   # a test asks for it


def _track_vectors(corpus_np, ops):
    """id -> latest row vector, walked independently of the corpus."""
    vec = {i: corpus_np[i] for i in range(len(corpus_np))}
    next_id = len(corpus_np)
    for op in ops:
        if op[0] == "insert":
            for j, row in enumerate(np.asarray(op[1])):
                vec[next_id + j] = row
            next_id += len(op[1])
        elif op[0] == "delete":
            for i in op[1]:
                del vec[int(i)]
        else:
            for i, row in zip(op[1], np.asarray(op[2])):
                vec[int(i)] = row
    return vec


# ---------------------------------------------------------------------------
# Property tests: the frozen-equivalence contract and segment algebra.
# ---------------------------------------------------------------------------
class TestFrozenEquivalence:

    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, SEED_MAX))
    def test_random_schedule_matches_fresh_frozen_corpus(self, seed):
        """THE co-headline invariant: after any generated mutation
        sequence, live results == fresh-built frozen corpus, bitwise —
        and forcing a compaction changes nothing."""
        corpus, queries = _base()
        live = _fresh(corpus)
        apply_schedule(live, random_schedule(seed, 12, D, N0))
        pre = assert_live_equals_frozen(live, queries, K, ctx="pre-compact")
        assert live.compact() or live.snapshot().n_dead == 0
        post = assert_live_equals_frozen(live, queries, K, ctx="post-compact")
        # compaction commutes with querying: same answer either side
        assert_topk_equal(post, pre, ctx="compaction commutation")

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, SEED_MAX))
    def test_mid_schedule_compaction_is_invisible(self, seed):
        """Compacting halfway through a history must not change where
        the history ends up: same ops with and without the mid-point
        compaction answer bit-identically."""
        corpus, queries = _base()
        ops = random_schedule(seed, 14, D, N0)
        with_c, without_c = _fresh(corpus), _fresh(corpus)
        apply_schedule(with_c, ops[:7])
        with_c.compact()
        apply_schedule(with_c, ops[7:])
        apply_schedule(without_c, ops)
        assert_topk_equal(with_c.topk(queries, K),
                          without_c.topk(queries, K),
                          ctx="mid-schedule compaction")
        assert_live_equals_frozen(with_c, queries, K)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, SEED_MAX))
    def test_tombstoned_ids_never_surface(self, seed):
        """Even with k > n_live, dead ids must not appear: the head holds
        only live ids, the tail is -inf scores with synthetic ids
        n_live, n_live+1, ... (``_reference_tail`` semantics)."""
        corpus, queries = _base()
        live = _fresh(corpus)
        ops = random_schedule(seed, 10, D, N0,
                              kinds=("delete", "delete", "upsert", "insert"))
        apply_schedule(live, ops)
        expected_live = simulate_live_ids(N0, ops)
        assert set(int(i) for i in live.snapshot().live_ids()) \
            == expected_live
        n_live = len(expected_live)
        k = n_live + 5
        for label in ("pre", "post"):
            got = live.topk(queries, k)
            scores = np.asarray(got.scores)
            ids = np.asarray(got.indices)
            finite = np.isfinite(scores)
            assert set(ids[finite].ravel().tolist()) <= expected_live, \
                f"tombstoned id surfaced ({label}-compaction)"
            # every query sees every live row once k clears n_live
            for row in range(B):
                assert set(ids[row][finite[row]].tolist()) == expected_live
            tail = ids[~finite]
            assert np.all(tail >= n_live), \
                f"tail ids must be synthetic (>= n_live) ({label})"
            assert_topk_equal(got, frozen_oracle(
                live.space, live.snapshot(), queries, k), ctx=label)
            live.compact()

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, SEED_MAX))
    def test_ids_stable_across_epochs(self, seed):
        """A logical id keeps answering for its (latest) vector across
        any number of compactions: under an l2 space, querying a live
        row's exact vector returns that id at rank 1, before and after
        every epoch swap.  A schedule may insert a row equal to a live
        one; such ids tie exactly, so the probe's answer is the set of
        ids holding its vector, at the head of the list."""
        rng = np.random.default_rng(seed)
        corpus_np = rng.standard_normal((N0, D)).astype(np.float32)
        live = LiveCorpus(DenseSpace("l2"), jnp.asarray(corpus_np),
                          max_append=10**9)
        ops = random_schedule(seed, 10, D, N0, min_live=4)
        apply_schedule(live, ops)
        vec = _track_vectors(corpus_np, ops)
        probe_ids = sorted(vec)[:3] + sorted(vec)[-3:]
        probes = jnp.asarray(np.stack([vec[i] for i in probe_ids]))
        holders = [{j for j in vec if np.array_equal(vec[j], vec[i])}
                   for i in probe_ids]
        depth = max(len(h) for h in holders)
        for epoch in range(3):
            got = np.asarray(live.topk(probes, depth).indices)
            heads = [set(row[:len(h)].tolist())
                     for row, h in zip(got, holders)]
            assert heads == holders, f"id instability at epoch {epoch}"
            live.upsert(np.array([probe_ids[0]]),
                        vec[probe_ids[0]][None])     # dirty -> compactable
            live.compact()

    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, SEED_MAX))
    def test_batch_order_irrelevant(self, seed):
        """Query batch order commutes with everything: permuting the
        query batch permutes the result rows and nothing else."""
        corpus, queries = _base(seed=1)
        live = _fresh(corpus)
        apply_schedule(live, random_schedule(seed, 8, D, N0))
        perm = np.random.default_rng(seed).permutation(B)
        got = live.topk(queries, K)
        got_perm = live.topk(queries[jnp.asarray(perm)], K)
        assert_topk_equal(
            TopK(np.asarray(got.scores)[perm], np.asarray(got.indices)[perm]),
            got_perm, ctx="batch permutation")

    @pytest.mark.parametrize("main_bk,app_bk", [
        ("reference", "reference"),
        ("streaming", "reference"),
        ("pallas", "reference"),
        ("reference", "streaming"),
        ("streaming", "pallas"),
    ])
    def test_exact_backend_combinations_bitwise(self, main_bk, app_bk):
        """Every exact main x append backend pairing stays bitwise on
        the frozen-equivalence contract (the reference oracle)."""
        corpus, queries = _base(seed=2)
        live = _fresh(corpus, backend=main_bk, append_backend=app_bk)
        apply_schedule(live, random_schedule(7, 10, D, N0))
        got = live.topk(queries, K)
        want = frozen_oracle(live.space, live.snapshot(), queries, K)
        assert_topk_equal(got, want, ctx=f"{main_bk}+{app_bk}")
        live.compact()
        assert_topk_equal(live.topk(queries, K), want,
                          ctx=f"{main_bk}+{app_bk} post-compact")


# ---------------------------------------------------------------------------
# LiveCorpus unit semantics.
# ---------------------------------------------------------------------------
class TestLiveCorpusUnits:

    def test_empty_corpus_serves_reference_tail(self):
        _, queries = _base()
        live = _fresh()
        got = live.topk(queries, 3)
        assert np.all(np.asarray(got.scores) == -np.inf)
        np.testing.assert_array_equal(np.asarray(got.indices),
                                      np.tile([0, 1, 2], (B, 1)))

    def test_insert_into_empty_assigns_sequential_ids(self):
        _, queries = _base()
        live = _fresh()
        ids = live.insert(jnp.ones((3, D)))
        assert ids.tolist() == [0, 1, 2]
        assert live.corpus_dtype == "float32"
        assert_live_equals_frozen(live, queries, 5)

    def test_deleted_ids_are_never_reused(self):
        corpus, _ = _base()
        live = _fresh(corpus)
        live.delete([N0 - 1])
        assert live.insert(jnp.ones((1, D))).tolist() == [N0]

    def test_delete_unknown_id_raises_and_leaves_state_unchanged(self):
        corpus, _ = _base()
        live = _fresh(corpus)
        g0 = live.generation
        with pytest.raises(KeyError):
            live.delete([5, 999])
        assert live.generation == g0
        assert live.snapshot().n_dead == 0

    def test_upsert_inserts_unknown_ids_under_stable_ids(self):
        corpus, queries = _base()
        live = _fresh(corpus)
        live.upsert(np.array([N0 + 7]), jnp.ones((1, D)))
        assert N0 + 7 in set(int(i) for i in live.snapshot().live_ids())
        # next fresh insert id skips past the upserted id
        assert live.insert(jnp.zeros((1, D))).tolist() == [N0 + 8]
        assert_live_equals_frozen(live, queries, K)

    def test_upsert_same_id_twice_in_one_batch_last_wins(self):
        live = LiveCorpus(DenseSpace("l2"), jnp.zeros((2, D)),
                          max_append=10**9)
        a, b = np.ones(D, np.float32), np.full(D, 2.0, np.float32)
        live.upsert(np.array([0, 0]), jnp.asarray(np.stack([a, b])))
        assert live.snapshot().n_live == 2
        got = live.topk(jnp.asarray(b)[None], 1)
        assert int(np.asarray(got.indices)[0, 0]) == 0
        assert float(np.asarray(got.scores)[0, 0]) == 0.0   # exact match

    def test_generation_increments_once_per_batch(self):
        corpus, _ = _base()
        live = _fresh(corpus)
        assert live.generation == 0
        live.insert(jnp.ones((3, D)))            # one batch, one bump
        assert live.generation == 1
        live.delete([0, 1])
        assert live.generation == 2
        live.upsert(np.array([2]), jnp.ones((1, D)))
        assert live.generation == 3
        assert live.compact() and live.generation == 4
        assert not live.compact() and live.generation == 4   # no-op: no bump

    def test_snapshot_arrays_are_frozen(self):
        corpus, _ = _base()
        snap = _fresh(corpus).snapshot()
        with pytest.raises(ValueError):
            snap.main_dead[0] = True
        with pytest.raises(ValueError):
            snap.main_ids[0] = 99

    def test_snapshot_validates_row_counts(self):
        corpus, _ = _base()
        with pytest.raises(ValueError):
            segments.SegmentSnapshot(main=corpus,
                                     main_ids=np.arange(3, dtype=np.int64),
                                     main_dead=np.zeros(3, bool))

    def test_init_rejects_duplicate_or_mismatched_ids(self):
        corpus, _ = _base()
        with pytest.raises(ValueError):
            _fresh(corpus, ids=np.zeros(N0, dtype=np.int64))
        with pytest.raises(ValueError):
            _fresh(corpus, ids=np.arange(N0 - 1))

    def test_append_backend_must_be_exact(self):
        corpus, _ = _base()
        with pytest.raises(ValueError):
            _fresh(corpus, append_backend="graph_ann")

    def test_threshold_triggers_inline_compaction(self):
        corpus, queries = _base()
        live = LiveCorpus(_space(), corpus, max_append=4)
        for _ in range(4):
            live.insert(jnp.ones((1, D)))
        snap = live.snapshot()
        assert snap.n_append == 0 and snap.n_main == N0 + 4
        assert live.live_stats()["compactions"] == 1
        assert_live_equals_frozen(live, queries, K)

    def test_max_dead_threshold_triggers_compaction(self):
        corpus, _ = _base()
        live = LiveCorpus(_space(), corpus, max_dead=3)
        live.delete([0, 1, 2])
        assert live.snapshot().n_dead == 0      # compacted away
        assert live.snapshot().n_main == N0 - 3

    def test_live_stats_shape(self):
        corpus, _ = _base()
        live = _fresh(corpus)
        live.insert(jnp.ones((2, D)))
        live.delete([0])
        s = live.live_stats()
        assert s["generation"] == 2
        assert s["segment_rows"] == {"main": N0, "append": 2}
        assert s["tombstones"] == 1
        assert s["snapshot_age_s"] >= 0.0
        assert s["compactions"] == 0 and s["compaction_s"] == []


# ---------------------------------------------------------------------------
# Snapshot consistency: no reader can observe a half-applied batch.
# ---------------------------------------------------------------------------
class _RecordingLive(LiveCorpus):
    """Records every swapped-in snapshot, keyed by generation (the swap
    happens under the writer lock, so the record is complete)."""

    def __init__(self, *a, **kw):
        self.history = {}
        super().__init__(*a, **kw)
        self.history[self._snapshot.generation] = self._snapshot

    def _swap(self, snap):
        self.history[snap.generation] = snap
        super()._swap(snap)


class TestSnapshotConsistency:

    def test_reader_only_ever_sees_recorded_post_batch_states(self):
        """Any snapshot a racing reader grabs IS (by identity) a state
        some complete mutation batch produced — the epoch swap is one
        atomic reference assignment, so a torn/intermediate state is
        unobservable."""
        corpus, queries = _base()
        live = _RecordingLive(_space(), corpus, max_append=10**9)
        stop = threading.Event()
        failures = []

        def reader():
            while not stop.is_set():
                snap = live.snapshot()
                if snap is not live.history.get(snap.generation):
                    failures.append(snap.generation)
                # and the snapshot is always internally servable
                live_res = segments.live_topk(live.space, snap, queries, K)
                if np.asarray(live_res.indices).shape != (B, K):
                    failures.append(("shape", snap.generation))

        threads = [threading.Thread(target=reader) for _ in range(2)]
        for t in threads:
            t.start()
        apply_schedule(live, random_schedule(3, 40, D, N0))
        live.compact()
        stop.set()
        for t in threads:
            t.join()
        assert not failures
        # generations are dense and monotone: one per batch + compaction
        assert sorted(live.history) == list(range(live.generation + 1))

    def test_bound_snapshot_pins_through_mutations(self):
        """An in-flight batch finishes on the snapshot it started with:
        binding, then mutating, still answers at the bound state."""
        corpus, queries = _base()
        live = _fresh(corpus)
        gen = LiveGenerator(live)
        bound = gen.bind_snapshot()
        assert gen.last_served_generation == 0
        want_old = frozen_oracle(live.space, live.snapshot(), queries, K)
        live.delete(list(range(8)))
        live.insert(jnp.ones((4, D)))
        assert_topk_equal(bound.generate(queries, K), want_old,
                          ctx="pinned snapshot")
        # a fresh bind serves the new state
        rebound = gen.bind_snapshot()
        assert gen.last_served_generation == 2
        assert_topk_equal(
            rebound.generate(queries, K),
            frozen_oracle(live.space, live.snapshot(), queries, K),
            ctx="rebound snapshot")

    def test_sharded_pipeline_pins_live_shards(self):
        """ShardedPipeline binds every live shard's snapshot before the
        fan-out; the merged result equals the per-shard frozen oracles
        merged, before and after mutating one shard."""
        corpus, queries = _base()
        half = N0 // 2
        live_a = _fresh(corpus[:half])
        live_b = _fresh(corpus[half:], ids=np.arange(half, N0))
        pipe = ShardedPipeline(
            shards=(CorpusShard(corpus[:half], 0, half),
                    CorpusShard(corpus[half:], 0, half)),
            generators=(LiveGenerator(live_a), LiveGenerator(live_b)),
            cand_qty=K, final_qty=K)

        def want():
            parts = [frozen_oracle(_space(), lv.snapshot(), queries, K)
                     for lv in (live_a, live_b)]
            return merge_topk(concat_topk(parts), K)

        assert_topk_equal(pipe.generate(queries, K), want(), ctx="sharded")
        live_a.delete(list(range(4)))
        live_b.upsert(np.array([N0 - 1]), jnp.ones((1, D)))
        assert_topk_equal(pipe.generate(queries, K), want(),
                          ctx="sharded post-mutation")


# ---------------------------------------------------------------------------
# ANN main segment: recall-equivalence instead of bitwise identity.
# ---------------------------------------------------------------------------
class TestLiveANN:
    NA, DA, BA, KA = 512, 32, 16, 10

    def test_churned_ann_meets_recall_contract(self):
        """graph_ann serving the main segment under churn: recall@10 vs
        the exact frozen oracle at the same logical state holds before
        compaction (warm index + tombstone over-fetch + exact append
        scan) and after (rebuilt index) — and the retired main's index
        entries are invalidated without clearing anything else."""
        queries, corpus = planted_cluster_corpus(
            self.NA, self.DA, self.BA, self.KA, n_clusters=8)
        corpus_np = np.asarray(corpus)
        oracle0 = exact_topk(DenseSpace("ip"), queries, corpus, self.KA + 1)
        oracle_margin(oracle0.scores)
        clear_ann_index_cache()
        live = LiveCorpus(DenseSpace("ip"), corpus,
                          backend=GraphANNBackend(rounds=2, degree=8),
                          max_append=10**9)
        live.topk(queries, self.KA)             # lazy first build
        assert ann_index_cache_info()["size"] == 1
        # churn that keeps the planted geometry: jittered cluster rows
        ops = random_schedule(
            11, 16, self.DA, self.NA, max_batch=2, min_live=self.NA - 40,
            row_fn=lambda rng, m: (
                corpus_np[rng.integers(0, self.NA, m)]
                + 0.01 * rng.standard_normal((m, self.DA))))
        apply_schedule(live, ops)
        snap = live.snapshot()
        # the ANN over-fetch budget stays legal: k + main dead <= ef
        assert self.KA + int(snap.main_dead.sum()) <= live.main_backend.ef
        want = frozen_oracle(live.space, snap, queries, self.KA)
        got = live.topk(queries, self.KA)
        assert_recall_contract(want, got, ctx="live ANN pre-compaction")
        assert live.compact()
        # compaction warmed the new main's index and invalidated only
        # the retired main's entries
        assert ann_index_cache_info()["size"] == 1
        got2 = live.topk(queries, self.KA)
        want2 = frozen_oracle(live.space, live.snapshot(), queries, self.KA)
        assert_recall_contract(want2, got2, ctx="live ANN post-compaction")


# ---------------------------------------------------------------------------
# Generation-keyed caching.
# ---------------------------------------------------------------------------
class TestGenerationKeys:

    def test_generation_is_part_of_the_key(self):
        q = np.ones(D, np.float32)
        k_none = quantized_key("ep", q, generation=None)
        k0 = quantized_key("ep", q, generation=0)
        k1 = quantized_key("ep", q, generation=1)
        assert len({k_none, k0, k1}) == 3   # None != 0 != 1
        assert quantized_key("ep", q, generation=1) == k1

    def test_generation_cannot_slide_into_other_fields(self):
        """Length-framing: a generation digit can't alias a profile (or
        any neighbour field) byte pattern."""
        q = np.ones(D, np.float32)
        assert quantized_key("ep", q, profile="1", generation=None) \
            != quantized_key("ep", q, profile="", generation=1)
        assert quantized_key("ep", q, profile="p1", generation=2) \
            != quantized_key("ep", q, profile="p", generation=12)


def _live_service(live, pad, **kw):
    svc = RetrievalService(**{k: kw.pop(k) for k in ("cache_size",)
                              if k in kw})
    pipe = RetrievalPipeline(generator=LiveGenerator(live),
                             cand_qty=16, final_qty=8)
    svc.register_pipeline("dense_live", pipe, pad, live=live, **kw)
    return svc, pipe


def _row(res):
    return (np.asarray(res.scores), np.asarray(res.indices))


class TestServedLive:

    def test_register_validations(self):
        corpus, queries = _base()
        live = _fresh(corpus)
        svc = RetrievalService()
        with pytest.raises(ValueError):
            svc.register_pipeline("a", None, queries[0], live=live,
                                  backend="streaming")
        with pytest.raises(ValueError):
            svc.register_pipeline("b", None, queries[0], live=live,
                                  corpus_dtype="bfloat16")
        with pytest.raises(ValueError):
            svc.register_pipeline("c", None, queries[0], live=live,
                                  jit=True)
        with pytest.raises(ValueError):
            svc.register_pipeline("d", None, queries[0], live=live,
                                  profile=object())
        frozen_pipe = RetrievalPipeline(
            BruteForceGenerator(_space(), corpus))
        with pytest.raises(ValueError):
            svc.register_pipeline("e", frozen_pipe, queries[0], live=live)
        other = _fresh(corpus)
        wrong = RetrievalPipeline(generator=LiveGenerator(other))
        with pytest.raises(ValueError):
            svc.register_pipeline("f", wrong, queries[0], live=live)
        svc.close()

    def test_served_equals_frozen_pipeline_at_each_state(self):
        """Served results match an offline pipeline run pinned at the
        same snapshot — across mutations."""
        corpus, queries = _base()
        live = _fresh(corpus)
        svc, pipe = _live_service(live, queries[0], cache_size=0,
                                  batch_size=B, max_wait_s=0.005)
        with svc:
            def offline():
                return RetrievalPipeline(
                    generator=SnapshotGenerator(live, live.snapshot()),
                    cand_qty=16, final_qty=8).run(queries)

            for step in range(3):
                want = offline()
                res = svc.retrieve(list(queries), endpoint="dense_live")
                np.testing.assert_array_equal(
                    np.stack([r.indices for r in res]),
                    np.asarray(want.indices), err_msg=f"step {step}")
                np.testing.assert_array_equal(
                    np.stack([r.scores for r in res]),
                    np.asarray(want.scores), err_msg=f"step {step}")
                live.delete([int(live.snapshot().live_ids()[0])])
                live.insert(jnp.ones((2, D)))

    def test_mutation_invalidates_stale_cache_hits(self):
        """A hit is only possible at the generation that produced the
        entry: after deleting the top-ranked doc, the same query misses
        and re-serves fresh results."""
        corpus, queries = _base()
        live = _fresh(corpus)
        svc, _ = _live_service(live, queries[0], batch_size=1,
                               max_wait_s=0.001)
        with svc:
            q = queries[0]
            first = svc.submit(q, endpoint="dense_live").result(timeout=30)
            again = svc.submit(q, endpoint="dense_live").result(timeout=30)
            assert svc.snapshot().cache_hits == 1
            np.testing.assert_array_equal(first.indices, again.indices)
            top = int(first.indices[0])
            live.delete([top])
            fresh = svc.submit(q, endpoint="dense_live").result(timeout=30)
            snap = svc.snapshot()
            assert snap.cache_hits == 1          # no stale hit
            assert top not in set(fresh.indices.tolist())

    def test_result_is_cached_under_the_generation_that_served_it(self):
        """A mutation landing between submit and batch close: the result
        is computed at (and stored under) the NEWER generation, so the
        next current-generation submit hits."""
        corpus, queries = _base()
        live = _fresh(corpus)
        svc, _ = _live_service(live, queries[0], batch_size=2,
                               max_wait_s=0.4)
        with svc:
            q = queries[1]
            fut = svc.submit(q, endpoint="dense_live")   # opens the batch
            time.sleep(0.05)
            live.insert(jnp.ones((1, D)))                # lands pre-close
            first = fut.result(timeout=30)
            hit = svc.submit(q, endpoint="dense_live").result(timeout=30)
            snap = svc.snapshot()
            assert snap.cache_hits == 1, \
                "result was not re-keyed to the served generation"
            np.testing.assert_array_equal(first.indices, hit.indices)

    def test_endpoint_snapshot_reports_live_freshness(self):
        corpus, queries = _base()
        live = LiveCorpus(_space(), corpus, max_append=10**9)
        svc, _ = _live_service(live, queries[0], batch_size=1,
                               max_wait_s=0.001)
        with svc:
            svc.retrieve(list(queries[:2]), endpoint="dense_live")
            live.insert(jnp.ones((3, D)))
            live.delete([0])
            live.compact()
            ep = svc.snapshot().endpoints["dense_live"]
            assert ep.generation == live.generation == 3
            assert ep.segment_rows == {"main": N0 + 2, "append": 0}
            assert ep.tombstones == 0
            assert ep.compactions == 1
            assert ep.compaction is not None and ep.compaction.count == 1
            assert ep.snapshot_age_s is not None and ep.snapshot_age_s >= 0
            assert ep.backend == "reference"
            assert ep.corpus_dtype == "float32"

    def test_frozen_endpoints_report_no_live_fields(self):
        corpus, queries = _base()
        pipe = RetrievalPipeline(BruteForceGenerator(_space(), corpus),
                                 cand_qty=16, final_qty=8)
        with RetrievalService() as svc:
            svc.register_pipeline("frozen", pipe, queries[0])
            svc.retrieve([queries[0]], endpoint="frozen")
            ep = svc.snapshot().endpoints["frozen"]
            assert ep.generation is None and ep.segment_rows is None
            assert ep.tombstones is None and ep.compaction is None


# ---------------------------------------------------------------------------
# Writer/reader/compactor races under a real service.
# ---------------------------------------------------------------------------
class TestConcurrentStress:

    def test_writers_readers_compactor_race(self):
        """N writers + M query clients + the background compactor racing
        one endpoint: every served result equals a recorded generation's
        answer with generation >= the generation current at submit (so a
        cache hit can never be stale), and observed generations are
        monotone."""
        corpus, queries = _base(n=64)
        live = _RecordingLive(_space(), corpus, max_append=24,
                              compact_interval_s=0.005)
        live.start()
        svc = RetrievalService(cache_size=256)
        pipe = RetrievalPipeline(generator=LiveGenerator(live),
                                 cand_qty=16, final_qty=8)
        svc.register_pipeline("dense_live", pipe, queries[0],
                              batch_size=4, max_wait_s=0.002, live=live)
        probes = [np.asarray(queries[i]) for i in range(3)]
        stop = threading.Event()
        observed = []          # (probe_idx, generation at submit, result)
        obs_lock = threading.Lock()
        gens_seen = []

        def writer(seed):
            rng = np.random.default_rng(seed)
            for _ in range(25):
                kind = rng.integers(3)
                try:
                    if kind == 0:
                        live.insert(jnp.asarray(
                            rng.standard_normal((2, D)).astype(np.float32)))
                    else:
                        ids = live.snapshot().live_ids()
                        pick = np.array([int(rng.choice(ids))])
                        if kind == 1 and len(ids) > 16:
                            live.delete(pick)
                        else:
                            live.upsert(pick, jnp.asarray(
                                rng.standard_normal((1, D))
                                .astype(np.float32)))
                except KeyError:
                    pass       # lost a pick race with the other writer
                time.sleep(0.001)

        def reader(seed):
            rng = np.random.default_rng(seed)
            for _ in range(20):
                i = int(rng.integers(len(probes)))
                g = live.generation
                fut = svc.submit(jnp.asarray(probes[i]),
                                 endpoint="dense_live")
                r = fut.result(timeout=60)
                with obs_lock:
                    observed.append((i, g, _row(r)))

        def sampler():
            while not stop.is_set():
                gens_seen.append(live.generation)

        threads = ([threading.Thread(target=writer, args=(s,))
                    for s in (1, 2)]
                   + [threading.Thread(target=reader, args=(s,))
                      for s in (3, 4)]
                   + [threading.Thread(target=sampler)])
        for t in threads:
            t.start()
        for t in threads[:-1]:
            t.join()
        stop.set()
        threads[-1].join()
        svc.close()
        live.close()

        assert gens_seen == sorted(gens_seen), "generation went backwards"
        # every generation ever swapped in is on record, densely
        assert sorted(live.history) == list(range(live.generation + 1))

        expected = {}

        def answer(g, i):
            if (g, i) not in expected:
                res = RetrievalPipeline(
                    generator=SnapshotGenerator(live, live.history[g]),
                    cand_qty=16, final_qty=8).run(
                        jnp.asarray(probes[i])[None])
                expected[(g, i)] = (np.asarray(res.scores)[0],
                                    np.asarray(res.indices)[0])
            return expected[(g, i)]

        for i, g_submit, (scores, ids) in observed:
            ok = any(
                np.array_equal(scores, answer(g, i)[0])
                and np.array_equal(ids, answer(g, i)[1])
                for g in range(g_submit, live.generation + 1))
            assert ok, (
                f"result for probe {i} submitted at gen {g_submit} matches "
                "no generation >= submit gen: stale or torn result")

    def test_service_close_drains_cleanly_mid_compaction(self):
        """service.close() while the background compactor is busy: all
        admitted futures resolve, close returns promptly, and the
        compactor thread itself shuts down cleanly afterwards."""
        corpus, queries = _base()

        class _SlowCompact(LiveCorpus):
            def compact(self):
                time.sleep(0.3)
                return super().compact()

        live = _SlowCompact(_space(), corpus, max_append=4)
        live.start()
        svc, _ = _live_service(live, queries[0], batch_size=2,
                               max_wait_s=0.005)
        live.insert(jnp.ones((5, D)))       # over threshold -> compactor busy
        futs = [svc.submit(queries[i % B], endpoint="dense_live")
                for i in range(6)]
        t0 = time.monotonic()
        svc.close()
        assert time.monotonic() - t0 < 5.0
        for f in futs:
            r = f.result(timeout=1)         # already resolved by the drain
            assert np.asarray(r.indices).shape == (8,)
        live.close()
        assert live._thread is None
        # the triggered compaction did land (close waits the thread out)
        assert live.snapshot().n_append == 0
        assert live.live_stats()["compactions"] >= 1
