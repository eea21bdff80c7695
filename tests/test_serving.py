"""Serving subsystem: batching, admission control, cache, router, stats —
and the contract that served results are bit-identical to the offline
pipeline (the sharded-endpoint contract lives in test_sharded.py)."""

import threading
import time
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pipeline import BruteForceGenerator, RetrievalPipeline
from repro.core.sparse import SparseVectors
from repro.core.spaces import (DenseSpace, FusedSpace, FusedVectors,
                               SparseSpace)
from repro.launch.serve import BatchingServer
from repro.serving import (QueryCache, RetrievalService, ServiceOverloaded,
                           quantized_key)
from repro.serving.batcher import ContinuousBatcher, Request
from repro.serving.stats import PHASES


@pytest.fixture(scope="module")
def dense_setup():
    corpus = jax.random.normal(jax.random.PRNGKey(1), (256, 16))
    queries = jax.random.normal(jax.random.PRNGKey(0), (40, 16))
    pipe = RetrievalPipeline(BruteForceGenerator(DenseSpace("ip"), corpus),
                             cand_qty=20, final_qty=10)
    return pipe, queries


def _service(pipe, queries, **kw):
    defaults = dict(batch_size=16, max_wait_s=0.01)
    defaults.update({k: kw.pop(k) for k in ("batch_size", "max_wait_s")
                     if k in kw})
    svc = RetrievalService(**kw)
    svc.register_pipeline("dense", pipe, queries[0], **defaults)
    return svc


class TestBatching:
    def test_served_bit_identical_to_offline(self, dense_setup):
        """The acceptance contract: streaming through padded 16-batches
        returns exactly what one offline run over all 40 queries returns."""
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=0) as svc:
            res = svc.retrieve([q[i] for i in range(40)], endpoint="dense")
        off = pipe.run(q)
        assert np.array_equal(np.stack([r.scores for r in res]),
                              np.asarray(off.scores))
        assert np.array_equal(np.stack([r.indices for r in res]),
                              np.asarray(off.indices))

    def test_partial_batch_padding_correct(self, dense_setup):
        """3 requests into a 16-slot batch: pad rows are scored and
        discarded without perturbing the real rows."""
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=0, batch_size=16,
                      max_wait_s=0.005) as svc:
            res = svc.retrieve([q[i] for i in range(3)], endpoint="dense")
            snap = svc.snapshot()
        off = pipe.run(q[:3])
        assert np.array_equal(np.stack([r.indices for r in res]),
                              np.asarray(off.indices))
        assert np.array_equal(np.stack([r.scores for r in res]),
                              np.asarray(off.scores))
        ep = snap.endpoints["dense"]
        assert ep.n_batches == 1 and ep.mean_batch_fill == pytest.approx(3 / 16)

    def test_batch_closes_on_size(self, dense_setup):
        """A full batch must not wait out a long deadline."""
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=0, batch_size=4,
                      max_wait_s=5.0) as svc:
            t0 = time.monotonic()
            svc.retrieve([q[i] for i in range(8)], endpoint="dense")
            elapsed = time.monotonic() - t0
            snap = svc.snapshot()
        ep = snap.endpoints["dense"]
        assert elapsed < 4.0          # did not sleep through the 5 s window
        assert ep.closed_by_size == 2 and ep.closed_by_deadline == 0
        assert ep.mean_batch_fill == pytest.approx(1.0)

    def test_batch_closes_on_deadline(self, dense_setup):
        """An underfull batch closes when the deadline trips."""
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=0, batch_size=64,
                      max_wait_s=0.05) as svc:
            svc.retrieve([q[i] for i in range(3)], endpoint="dense")
            snap = svc.snapshot()
        ep = snap.endpoints["dense"]
        assert ep.closed_by_deadline >= 1
        assert ep.closed_by_size == 0
        assert ep.mean_batch_fill < 1.0

    def test_drain_on_close(self, dense_setup):
        """close() flushes queued work instead of abandoning futures."""
        pipe, q = dense_setup
        svc = _service(pipe, q, cache_size=0, batch_size=64, max_wait_s=30.0)
        futs = svc.submit_many([q[i] for i in range(3)], endpoint="dense")
        t0 = time.monotonic()
        svc.close()
        assert time.monotonic() - t0 < 5.0    # not the 30 s window
        off = pipe.run(q[:3])
        for i, f in enumerate(futs):
            r = f.result(timeout=1)
            assert np.array_equal(r.indices, np.asarray(off.indices)[i])
        assert svc.snapshot().endpoints["dense"].closed_by_drain >= 1
        with pytest.raises(RuntimeError):
            svc.submit(q[0], endpoint="dense")

    def test_cancelled_future_does_not_kill_worker(self, dense_setup):
        """A client cancelling a queued request must not crash the batch
        fan-out (set_result on a cancelled future raises)."""
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=0, batch_size=4,
                      max_wait_s=0.2) as svc:
            futs = svc.submit_many([q[i] for i in range(3)],
                                   endpoint="dense")
            cancelled = futs[1].cancel()
            alive = [f.result(timeout=5) for f in (futs[0], futs[2])]
            # worker must still serve subsequent traffic
            again = svc.submit(q[5], endpoint="dense").result(timeout=5)
        assert all(r is not None for r in alive) and again is not None
        if cancelled:       # cancel only wins if it beat the batcher
            assert futs[1].cancelled()

    def test_runner_exception_fails_batch_not_worker(self):
        calls = {"n": 0}

        def flaky(batch, _tokens):
            calls["n"] += 1
            if calls["n"] == 1:
                raise ValueError("boom")
            return batch * 2

        svc = RetrievalService(cache_size=0)
        svc.register_runner("flaky", flaky, jnp.zeros((4,)),
                            batch_size=2, max_wait_s=0.01)
        with svc:
            bad = svc.submit(jnp.ones((4,)), endpoint="flaky")
            with pytest.raises(ValueError, match="boom"):
                bad.result(timeout=5)
            ok = svc.submit(jnp.ones((4,)), endpoint="flaky")
            np.testing.assert_allclose(ok.result(timeout=5), 2 * np.ones(4))


class TestCache:
    def test_hit_miss_semantics(self, dense_setup):
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=64, max_wait_s=0.005) as svc:
            a = svc.submit(q[0], endpoint="dense").result()
            b = svc.submit(q[0], endpoint="dense").result()   # hit
            c = svc.submit(q[1], endpoint="dense").result()   # miss
            snap = svc.snapshot()
        assert snap.cache_hits == 1 and snap.cache_misses == 2
        assert np.array_equal(a.scores, b.scores)
        assert np.array_equal(a.indices, b.indices)
        assert not np.array_equal(a.indices, c.indices) or \
            not np.array_equal(a.scores, c.scores)

    def test_hit_skips_the_funnel(self, dense_setup):
        """A hit never reaches the batcher: batch count stays flat."""
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=64, max_wait_s=0.005) as svc:
            svc.submit(q[0], endpoint="dense").result()
            before = svc.snapshot().endpoints["dense"].n_batches
            svc.submit(q[0], endpoint="dense").result()
            after = svc.snapshot().endpoints["dense"].n_batches
        assert after == before

    def test_quantized_key_absorbs_jitter(self, dense_setup):
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=64, cache_decimals=4,
                      max_wait_s=0.005) as svc:
            svc.submit(q[0], endpoint="dense").result()
            jittered = q[0] + 1e-7          # below the 1e-4 quantum
            svc.submit(jittered, endpoint="dense").result()
            snap = svc.snapshot()
        assert snap.cache_hits == 1

    def test_cached_result_immutable_against_client_mutation(self, dense_setup):
        """Hits alias the stored arrays, so they are frozen: in-place
        mutation raises instead of corrupting every later hit."""
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=64, max_wait_s=0.005) as svc:
            first = svc.submit(q[0], endpoint="dense").result()
            with pytest.raises(ValueError):
                first.scores[0] = -1.0
            hit = svc.submit(q[0], endpoint="dense").result()
        off = pipe.run(q[:1])
        assert np.array_equal(hit.scores, np.asarray(off.scores)[0])
        assert np.array_equal(hit.indices, np.asarray(off.indices)[0])

    def test_cache_disabled(self, dense_setup):
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=0) as svc:
            svc.submit(q[0], endpoint="dense").result()
            svc.submit(q[0], endpoint="dense").result()
            snap = svc.snapshot()
        assert snap.cache_hits == 0 and snap.cache_misses == 0
        ep = snap.endpoints["dense"]
        assert ep.n_requests == 2

    def test_lru_eviction(self):
        cache = QueryCache(capacity=2)
        k = [cache.key("e", jnp.asarray([float(i)])) for i in range(3)]
        cache.put(k[0], "a")
        cache.put(k[1], "b")
        assert cache.get(k[0]) == "a"       # refresh 0 -> 1 becomes LRU
        cache.put(k[2], "c")
        assert cache.get(k[1]) is None and cache.get(k[0]) == "a"
        assert len(cache) == 2

    def test_key_separates_endpoints_and_shapes(self):
        x = jnp.asarray([1.0, 2.0])
        assert quantized_key("a", x) != quantized_key("b", x)
        assert quantized_key("a", x) != quantized_key("a", x.reshape(2, 1))
        assert quantized_key("a", x) == quantized_key("a", x + 1e-9)

    def test_key_normalises_negative_zero(self):
        """Jitter crossing zero (-1e-9 vs +1e-9) must still hit."""
        a = quantized_key("e", jnp.asarray([-1e-9, 1.0]))
        b = quantized_key("e", jnp.asarray([1e-9, 1.0]))
        assert a == b


class TestRouter:
    def test_dispatch_reaches_the_right_pipeline(self):
        svc = RetrievalService(cache_size=0)
        svc.register_runner("double", lambda b, _t: b * 2, jnp.zeros((3,)),
                            batch_size=4, max_wait_s=0.005)
        svc.register_runner("negate", lambda b, _t: -b, jnp.zeros((3,)),
                            batch_size=4, max_wait_s=0.005)
        with svc:
            x = jnp.asarray([1.0, 2.0, 3.0])
            d = svc.submit(x, endpoint="double").result(timeout=5)
            n = svc.submit(x, endpoint="negate").result(timeout=5)
        np.testing.assert_allclose(d, [2, 4, 6])
        np.testing.assert_allclose(n, [-1, -2, -3])
        assert sorted(svc.endpoints()) == ["double", "negate"]

    def test_unknown_endpoint_raises(self, dense_setup):
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=0) as svc:
            with pytest.raises(KeyError, match="unknown endpoint"):
                svc.submit(q[0], endpoint="nope")

    def test_default_endpoint_only_when_unambiguous(self, dense_setup):
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=0) as svc:
            assert svc.submit(q[0]).result() is not None   # sole endpoint
        svc2 = RetrievalService(cache_size=0)
        svc2.register_runner("a", lambda b, _t: b, jnp.zeros(()),
                             batch_size=1, max_wait_s=0.001)
        svc2.register_runner("b", lambda b, _t: b, jnp.zeros(()),
                             batch_size=1, max_wait_s=0.001)
        with svc2:
            with pytest.raises(ValueError, match="endpoint required"):
                svc2.submit(jnp.zeros(()))

    def test_duplicate_registration_rejected(self, dense_setup):
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=0) as svc:
            with pytest.raises(ValueError, match="already registered"):
                svc.register_pipeline("dense", pipe, q[0])


class _GatedService:
    """A service whose single worker blocks inside the runner until released:
    the queue can be filled to an exact depth deterministically."""

    def __init__(self, max_queue, overload):
        self.gate = threading.Event()
        self.entered = threading.Event()

        def gated(batch, _tokens):
            self.entered.set()
            assert self.gate.wait(timeout=30)
            return batch
        self.svc = RetrievalService(cache_size=0)
        self.svc.register_runner("gated", gated, jnp.zeros((2,)),
                                 batch_size=1, max_wait_s=0.001,
                                 max_queue=max_queue, overload=overload)

    def occupy_worker(self):
        """Park the worker inside a batch so later submits stay queued."""
        fut = self.svc.submit(jnp.ones((2,)), endpoint="gated")
        assert self.entered.wait(timeout=10)
        return fut

    def release(self):
        self.gate.set()


class TestAdmissionControl:
    def test_reject_at_depth_limit(self):
        g = _GatedService(max_queue=2, overload="reject")
        with g.svc:
            inflight = g.occupy_worker()
            queued = [g.svc.submit(jnp.ones((2,)), endpoint="gated")
                      for _ in range(2)]          # fills the queue exactly
            assert g.svc.stats.snapshot().endpoints["gated"].queue_depth == 2
            with pytest.raises(ServiceOverloaded, match="depth limit 2"):
                g.svc.submit(jnp.ones((2,)), endpoint="gated")
            with pytest.raises(ServiceOverloaded):
                g.svc.submit(jnp.ones((2,)), endpoint="gated")
            snap = g.svc.snapshot()
            g.release()
            for f in [inflight] + queued:          # admitted work still lands
                assert f.result(timeout=10) is not None
        ep = snap.endpoints["gated"]
        assert ep.rejected == 2 and ep.shed == 0
        assert ep.depth_limit == 2
        assert ep.queue_depth <= 2                 # bounded, not unbounded

    def test_shed_oldest_fails_stalest_future(self):
        g = _GatedService(max_queue=2, overload="shed_oldest")
        with g.svc:
            inflight = g.occupy_worker()
            f_old = g.svc.submit(jnp.full((2,), 1.0), endpoint="gated")
            f_mid = g.svc.submit(jnp.full((2,), 2.0), endpoint="gated")
            f_new = g.svc.submit(jnp.full((2,), 3.0), endpoint="gated")
            # f_old was evicted to make room for f_new
            with pytest.raises(ServiceOverloaded, match="shed"):
                f_old.result(timeout=10)
            snap = g.svc.snapshot()
            g.release()
            assert inflight.result(timeout=10) is not None
            np.testing.assert_allclose(f_mid.result(timeout=10), [2.0, 2.0])
            np.testing.assert_allclose(f_new.result(timeout=10), [3.0, 3.0])
        ep = snap.endpoints["gated"]
        assert ep.shed == 1 and ep.rejected == 0

    def test_block_backpressures_submitter(self):
        g = _GatedService(max_queue=1, overload="block")
        with g.svc:
            g.occupy_worker()
            g.svc.submit(jnp.ones((2,)), endpoint="gated")   # queue now full
            done = threading.Event()
            held = {}

            def submitter():
                held["fut"] = g.svc.submit(jnp.ones((2,)), endpoint="gated")
                done.set()

            t = threading.Thread(target=submitter)
            t.start()
            assert not done.wait(timeout=0.15)     # blocked at the limit
            g.release()
            assert done.wait(timeout=10)           # space freed -> admitted
            t.join()
            assert held["fut"].result(timeout=10) is not None
            snap = g.svc.snapshot()
        ep = snap.endpoints["gated"]
        assert ep.rejected == 0 and ep.shed == 0

    def test_close_wakes_blocked_submitter(self):
        g = _GatedService(max_queue=1, overload="block")
        g.occupy_worker()
        g.svc.submit(jnp.ones((2,)), endpoint="gated")
        errs = []

        def submitter():
            try:
                g.svc.submit(jnp.ones((2,)), endpoint="gated")
            except RuntimeError as e:
                errs.append(e)

        t = threading.Thread(target=submitter)
        t.start()
        time.sleep(0.05)
        g.release()            # let the drain finish promptly
        g.svc.close()
        t.join(timeout=10)
        assert not t.is_alive()

    def test_unbounded_queue_never_overloads(self, dense_setup):
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=0) as svc:   # max_queue=None
            svc.retrieve([q[i] for i in range(30)], endpoint="dense")
            snap = svc.snapshot()
        ep = snap.endpoints["dense"]
        assert ep.depth_limit is None
        assert ep.rejected == 0 and ep.shed == 0

    def test_cache_hit_served_while_endpoint_saturated(self):
        """Hits bypass the admission queue: a saturated endpoint still
        answers hot queries from the cache."""
        gate = threading.Event()
        entered = threading.Event()

        def gated(batch, _tokens):
            entered.set()
            assert gate.wait(timeout=30)
            return batch

        svc = RetrievalService(cache_size=64)
        svc.register_runner("gated", gated, jnp.zeros((2,)),
                            batch_size=1, max_wait_s=0.001,
                            max_queue=1, overload="reject")
        with svc:
            hot = jnp.asarray([5.0, 6.0])
            first = svc.submit(hot, endpoint="gated")
            assert entered.wait(timeout=10)
            gate.set()
            first.result(timeout=10)               # now cached
            gate.clear()
            blocker = svc.submit(jnp.ones((2,)), endpoint="gated")
            assert svc.submit(hot, endpoint="gated").result(timeout=1) \
                is not None                        # hit, no queue involved
            gate.set()
            blocker.result(timeout=10)
            snap = svc.snapshot()
        assert snap.cache_hits == 1

    def test_rejected_submit_is_not_a_cache_miss(self):
        """Hit-rate must keep meaning 'share of admitted requests answered
        from cache': a ServiceOverloaded submit never counts as a miss."""
        gate = threading.Event()
        entered = threading.Event()

        def gated(batch, _tokens):
            entered.set()
            assert gate.wait(timeout=30)
            return batch

        svc = RetrievalService(cache_size=64)
        svc.register_runner("gated", gated, jnp.zeros((2,)),
                            batch_size=1, max_wait_s=0.001,
                            max_queue=1, overload="reject")
        with svc:
            first = svc.submit(jnp.ones((2,)), endpoint="gated")   # 1 miss
            assert entered.wait(timeout=10)
            svc.submit(jnp.full((2,), 2.0), endpoint="gated")      # 1 miss
            with pytest.raises(ServiceOverloaded):
                svc.submit(jnp.full((2,), 3.0), endpoint="gated")
            snap_mid = svc.snapshot()
            gate.set()
            first.result(timeout=10)
        assert snap_mid.cache_misses == 2          # the rejection: not a miss
        assert snap_mid.endpoints["gated"].rejected == 1

    def test_invalid_policy_and_depth_rejected(self):
        # both now rejected by EndpointSpec validation (check_config),
        # before any endpoint state exists
        svc = RetrievalService(cache_size=0)
        with pytest.raises(ValueError, match="overload"):
            svc.register_runner("bad", lambda b, _t: b, jnp.zeros((2,)),
                                overload="drop_newest")
        with pytest.raises(ValueError, match="max_queue"):
            svc.register_runner("bad2", lambda b, _t: b, jnp.zeros((2,)),
                                max_queue=0)
        svc.close()


class TestCompatShim:
    def test_batching_server_matches_batched_fn(self):
        """The legacy BatchingServer surface: deprecated (it now routes
        through EndpointSpec registration) but still serving full +
        partial batches bitwise-equal to the wrapped fn, stats populated,
        GC-safe close."""
        c = jax.random.normal(jax.random.PRNGKey(1), (128, 16))
        fn = jax.jit(lambda q: jax.lax.top_k(q @ c.T, 5))
        with pytest.warns(DeprecationWarning, match="EndpointSpec"):
            srv = BatchingServer(fn, batch_size=8,
                                 pad_query=jnp.zeros((16,)),
                                 window_s=0.005)
        qs = [jax.random.normal(jax.random.PRNGKey(i), (16,))
              for i in range(13)]            # one full + one partial batch
        out = srv.serve(qs)
        want_s, want_i = fn(jnp.stack(qs[:8]))
        for i in range(8):
            assert np.array_equal(out[i][0], np.asarray(want_s)[i])
            assert np.array_equal(out[i][1], np.asarray(want_i)[i])
        assert srv.stats.n_requests == 13 and srv.stats.n_batches == 2
        assert srv.stats.mean_latency_ms > 0
        srv.close()


class TestTokensAndStats:
    def test_tokens_without_pad_rejected_loudly(self):
        """q_tokens on an endpoint registered without pad_q_tokens would be
        silently dropped; submit must refuse instead."""
        svc = RetrievalService(cache_size=0)
        svc.register_runner("plain", lambda b, _t: b, jnp.zeros((2,)),
                            batch_size=2, max_wait_s=0.005)
        with svc:
            with pytest.raises(ValueError, match="pad_q_tokens"):
                svc.submit(jnp.zeros((2,)),
                           q_tokens=jnp.zeros((3,), jnp.int32),
                           endpoint="plain")

    def test_q_tokens_row_alignment(self):
        """Per-request tokens ride along and land on the right row."""
        def runner(batch, tokens):
            return batch + tokens.sum(axis=-1, keepdims=True)

        svc = RetrievalService(cache_size=0)
        svc.register_runner("tok", runner, jnp.zeros((2,)),
                            pad_q_tokens=jnp.zeros((3,), jnp.int32),
                            batch_size=4, max_wait_s=0.01)
        with svc:
            futs = [svc.submit(jnp.zeros((2,)),
                               q_tokens=jnp.full((3,), i, jnp.int32),
                               endpoint="tok") for i in range(4)]
            outs = [f.result(timeout=5) for f in futs]
        for i, o in enumerate(outs):
            np.testing.assert_allclose(o, np.full(2, 3 * i))

    def test_snapshot_accounting(self, dense_setup):
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=0, batch_size=8,
                      max_wait_s=0.005) as svc:
            svc.retrieve([q[i] for i in range(24)], endpoint="dense")
            snap = svc.snapshot()
        ep = snap.endpoints["dense"]
        assert snap.n_requests == 24 and ep.n_requests == 24
        assert ep.n_batches >= 3                      # 24 served in 8-batches
        assert ep.queue_wait.count == 24              # one wait per request
        assert ep.execute.count == ep.n_batches
        assert ep.e2e.count == 24
        for s in (ep.queue_wait, ep.execute, ep.e2e):
            assert 0.0 <= s.p50_ms <= s.p99_ms
        assert ep.execute_total_s >= 1e-3 * ep.execute.p50_ms  # exact sums
        assert ep.queue_depth == 0
        assert snap.qps > 0

    def test_phase_totals_are_exact(self):
        """The four timed phases tile a batch's execution: their exact
        lifetime totals sum to ``execute_total_s`` but for the few
        bytecodes between spans; the gather and the fan-out are counted
        beside them, for every batch."""
        def slow(q, _tokens):
            time.sleep(0.02)           # inside serve.dispatch
            return q * 2.0

        svc = RetrievalService(cache_size=0)
        svc.register_runner("slow", slow, np.zeros(4, np.float32),
                            batch_size=4, max_wait_s=0.005)
        with svc:
            svc.retrieve([np.full(4, i, np.float32) for i in range(10)],
                         endpoint="slow")
        ep = svc.snapshot().endpoints["slow"]
        ph = ep.phase_total_s
        assert list(ph) == list(PHASES)
        timed = ph["assemble"] + ph["dispatch"] + ph["sync"] + ph["copy_back"]
        assert ep.n_batches >= 3
        assert ph["dispatch"] >= 0.02 * ep.n_batches
        assert timed <= ep.execute_total_s
        assert timed == pytest.approx(ep.execute_total_s, rel=1e-2)
        assert ph["gather"] > 0 and ph["fanout"] > 0

    @pytest.mark.parametrize("kind", ["fused", "sparse"])
    def test_query_term_counters_count_live_slots(self, kind):
        """The sparse query term counters: every live id in [0, vocab) of
        the served requests, against batch size x slots per executed
        batch, pad rows included.  Padding ids (vocab) and ids out of
        range count in the slots and not among the live terms."""
        vocab, slots, nnz = 64, 5, 4
        rng = np.random.default_rng(3)
        c_sparse = SparseVectors(
            np.argsort(rng.random((256, vocab)), 1)[:, :nnz].astype(np.int32),
            rng.random((256, nnz)).astype(np.float32))
        c_dense = rng.standard_normal((256, 16)).astype(np.float32)
        q_ids = rng.integers(0, vocab, (20, slots)).astype(np.int32)
        live = rng.integers(0, slots + 1, 20)
        q_ids[np.arange(slots) >= live[:, None]] = vocab
        q_ids[0, -1] = -1                 # out of range: matches nothing
        q_ids[1, -1] = vocab + 7
        q_vals = rng.random((20, slots)).astype(np.float32)
        if kind == "fused":
            space = FusedSpace(vocab, w_dense=0.7, w_sparse=0.3)
            corpus = FusedVectors(c_dense, c_sparse)
            queries = [FusedVectors(rng.standard_normal(16).astype(
                np.float32), SparseVectors(q_ids[i], q_vals[i]))
                for i in range(20)]
            pad = FusedVectors(np.zeros(16, np.float32), SparseVectors(
                np.full(slots, vocab, np.int32), np.zeros(slots, np.float32)))
        else:
            space, corpus = SparseSpace(vocab), c_sparse
            queries = [SparseVectors(q_ids[i], q_vals[i]) for i in range(20)]
            pad = SparseVectors(np.full(slots, vocab, np.int32),
                                np.zeros(slots, np.float32))
        pipe = RetrievalPipeline(BruteForceGenerator(space, corpus),
                                 cand_qty=10, final_qty=5)
        svc = RetrievalService(cache_size=0)
        svc.register_pipeline("terms", pipe, pad, batch_size=16,
                              max_wait_s=0.005)
        with svc:
            svc.retrieve(queries, endpoint="terms")
            ep = svc.snapshot().endpoints["terms"]
        assert ep.query_terms_total == int(
            ((q_ids >= 0) & (q_ids < vocab)).sum())
        assert ep.query_term_slots_total == ep.n_batches * 16 * slots
        assert 0 < ep.query_terms_total < ep.query_term_slots_total

    def test_query_term_counters_zero_on_a_dense_endpoint(self, dense_setup):
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=0, max_wait_s=0.005) as svc:
            svc.retrieve([q[i] for i in range(5)], endpoint="dense")
            ep = svc.snapshot().endpoints["dense"]
        assert ep.n_batches >= 1
        assert ep.query_terms_total == 0 and ep.query_term_slots_total == 0

    def test_reset_stats_zeroes_but_keeps_endpoints(self, dense_setup):
        """Warm-up isolation: reset zeroes counters, then real load counts
        from a clean slate on the still-registered endpoint."""
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=64, max_wait_s=0.005) as svc:
            svc.submit(q[0], endpoint="dense").result()
            svc.submit(q[0], endpoint="dense").result()   # a hit
            svc.reset_stats()
            snap0 = svc.snapshot()
            assert snap0.n_requests == 0 and snap0.cache_hits == 0
            assert snap0.endpoints["dense"].n_batches == 0
            svc.submit(q[1], endpoint="dense").result()
            snap1 = svc.snapshot()
        assert snap1.n_requests == 1
        assert snap1.endpoints["dense"].n_batches == 1


def _fused_query(rng, ids_dtype=np.int32, vals_dtype=np.float32):
    return FusedVectors(rng.standard_normal(8).astype(np.float32),
                        SparseVectors(rng.integers(0, 50, 4).astype(ids_dtype),
                                      rng.random(4).astype(vals_dtype)))


class TestHostAssembly:
    """A batch whose query leaves are all host arrays is stacked on the
    host and moved in one transfer per leaf; the result is what
    ``jnp.stack`` gives, bit for bit and dtype for dtype."""

    @staticmethod
    def _batcher(pad, pad_tokens=None, batch_size=4):
        return ContinuousBatcher("asm", lambda q, _t: q, pad, pad_tokens,
                                 batch_size=batch_size, max_wait_s=0.005)

    @staticmethod
    def _requests(queries, tokens=None):
        tokens = tokens if tokens is not None else [None] * len(queries)
        return [Request(q, t, "asm", Future(), 0.0)
                for q, t in zip(queries, tokens)]

    @staticmethod
    def _device_stacked(trees):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    @staticmethod
    def _assert_same(got, want):
        got_leaves, want_leaves = jax.tree.leaves(got), jax.tree.leaves(want)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for g, w in zip(got_leaves, want_leaves):
            assert isinstance(g, jax.Array)
            assert g.dtype == w.dtype and g.shape == w.shape
            assert g.committed == w.committed
            assert np.array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("fill", [1, 3, 4])
    @pytest.mark.parametrize("kind", ["dense", "fused", "tokens"])
    def test_host_stacked_bitwise_equal_to_jnp_stack(self, kind, fill):
        rng = np.random.default_rng(fill)
        tokens = pad_tokens = None
        if kind == "fused":
            pad = FusedVectors(np.zeros(8, np.float32), SparseVectors(
                np.full(4, 50, np.int32), np.zeros(4, np.float32)))
            queries = [_fused_query(rng) for _ in range(fill)]
        else:
            pad = np.zeros(8, np.float32)
            queries = [rng.standard_normal(8).astype(np.float32)
                       for _ in range(fill)]
        if kind == "tokens":
            pad_tokens = np.zeros(6, np.int32)
            tokens = [rng.integers(0, 99, 6).astype(np.int32)
                      for _ in range(fill)]
        b = self._batcher(pad, pad_tokens)
        try:
            stacked, toks = b._assemble(self._requests(queries, tokens))
        finally:
            b.close()
        assert b._stacked_on == "host"
        self._assert_same(stacked,
                          self._device_stacked(queries + [pad] * (4 - fill)))
        if kind == "tokens":
            self._assert_same(toks, self._device_stacked(
                tokens + [pad_tokens] * (4 - fill)))
        else:
            assert toks is None

    def test_dtypes_canonicalised_as_jnp_stack(self):
        """int64 ids and float64 values (x64 off) come out int32 and
        float32, as the device path gives them."""
        rng = np.random.default_rng(5)
        pad = FusedVectors(np.zeros(8), SparseVectors(
            np.full(4, 50, np.int64), np.zeros(4, np.float64)))
        queries = [_fused_query(rng, np.int64, np.float64) for _ in range(2)]
        b = self._batcher(pad)
        try:
            stacked, _ = b._assemble(self._requests(queries))
        finally:
            b.close()
        want = self._device_stacked(queries + [pad] * 2)
        self._assert_same(stacked, want)
        assert stacked.dense.dtype == jnp.float32
        assert stacked.sparse.indices.dtype == jnp.int32
        assert stacked.sparse.values.dtype == jnp.float32

    def test_device_request_keeps_the_device_path(self):
        """One query leaf held on a device sends that leaf through
        ``jnp.stack``; the batch is not counted as host-assembled."""
        rng = np.random.default_rng(7)
        pad = FusedVectors(np.zeros(8, np.float32), SparseVectors(
            np.full(4, 50, np.int32), np.zeros(4, np.float32)))
        queries = [_fused_query(rng) for _ in range(4)]
        queries[2] = FusedVectors(jnp.asarray(queries[2].dense),
                                  queries[2].sparse)
        b = self._batcher(pad)
        try:
            stacked, _ = b._assemble(self._requests(queries))
        finally:
            b.close()
        assert b._stacked_on == "mixed"
        self._assert_same(stacked, self._device_stacked(queries))

        svc = RetrievalService(cache_size=0)
        svc.register_runner("mixed", lambda q, _t: q.dense, pad,
                            batch_size=4, max_wait_s=5.0)
        with svc:
            out = svc.retrieve(queries, endpoint="mixed")
            ep = svc.snapshot().endpoints["mixed"]
        assert ep.n_batches == 1 and ep.host_assembled_total == 0
        for q, o in zip(queries, out):
            assert np.array_equal(o, np.asarray(q.dense))

    @pytest.mark.parametrize("kind", ["dense", "fused"])
    def test_counter_counts_every_host_batch(self, kind):
        rng = np.random.default_rng(11)
        if kind == "fused":
            pad = FusedVectors(np.zeros(8, np.float32), SparseVectors(
                np.full(4, 50, np.int32), np.zeros(4, np.float32)))
            queries = [_fused_query(rng) for _ in range(10)]
            run = lambda q, _t: q.dense + q.sparse.values.sum(-1)[:, None]
        else:
            pad = np.zeros(8, np.float32)
            queries = [rng.standard_normal(8).astype(np.float32)
                       for _ in range(10)]
            run = lambda q, _t: q * 2.0
        svc = RetrievalService(cache_size=0)
        svc.register_runner("host", run, pad, batch_size=4,
                            max_wait_s=0.005)
        with svc:
            svc.retrieve(queries, endpoint="host")
            ep = svc.snapshot().endpoints["host"]
        assert ep.n_batches >= 3
        assert ep.host_assembled_total == ep.n_batches

    def test_counter_zero_on_a_device_endpoint(self, dense_setup):
        pipe, q = dense_setup
        with _service(pipe, q, cache_size=0, max_wait_s=0.005) as svc:
            svc.retrieve([q[i] for i in range(5)], endpoint="dense")
            ep = svc.snapshot().endpoints["dense"]
        assert ep.n_batches >= 1 and ep.host_assembled_total == 0
