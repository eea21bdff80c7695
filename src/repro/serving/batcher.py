"""Admission queue + continuous batcher, with overload admission control.

One :class:`ContinuousBatcher` per endpoint owns an admission queue and a
worker thread.  The worker closes a batch on whichever knob trips first:

  * **size** — ``batch_size`` requests are waiting (throughput knob);
  * **deadline** — ``max_wait_s`` elapsed since the batch opened
    (latency knob);
  * **drain** — the service is shutting down and flushes what's queued.

Partial batches are padded to the fixed ``batch_size`` with the
endpoint's pad query (jit shape stability — the padded rows are scored
and discarded), run through the endpoint's batched runner, and the rows
fan back out to per-request futures.  Each leaf of the batch's query
pytree that every query holds on the host is stacked there and moved to
the device in one transfer; a leaf any query holds on a device is
stacked on the device (``jnp.stack``).  A runner failure fails every
future in the batch; the worker survives and keeps serving.

Admission control: ``max_queue`` bounds the per-endpoint queue depth.
At the limit the configured ``overload`` policy decides what gives:

  * ``"block"`` (default) — the submitting thread waits for space:
    backpressure propagates to the caller, nothing is lost;
  * ``"reject"`` — ``submit`` raises :class:`ServiceOverloaded`
    immediately: the caller sees the overload synchronously and can back
    off or hedge to another replica;
  * ``"shed_oldest"`` — the oldest *queued* request is evicted (its
    future fails with :class:`ServiceOverloaded`) and the new one is
    admitted: freshest-first under overload, bounding queue wait.

Rejected/shed totals are surfaced per endpoint through
``ServingStats.snapshot()`` alongside the live queue depth and its limit.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparse import SparseVectors
from repro.core.spaces import FusedVectors
from repro.serving.stats import ServingStats, span

__all__ = ["Request", "ContinuousBatcher", "ServiceOverloaded",
           "OVERLOAD_POLICIES"]

_POLL_S = 0.02   # stop-flag poll while the queue is idle

OVERLOAD_POLICIES = ("block", "reject", "shed_oldest")


class ServiceOverloaded(RuntimeError):
    """An admission queue is at its depth limit: raised by ``submit`` under
    policy ``"reject"``, set on the evicted request's future under
    ``"shed_oldest"``."""


@dataclasses.dataclass
class Request:
    """One in-flight query: representation + (optional) raw tokens for the
    re-ranking stages, the future the result lands in, and timestamps."""

    query_repr: Any
    q_tokens: Optional[Any]
    endpoint: str
    future: Future
    t_admit: float
    cache_key: Optional[bytes] = None
    # live-corpus generation the cache_key was stamped with at submit
    # time (None on frozen endpoints): if the batch ends up served from
    # a newer snapshot, the service re-keys the stored result to the
    # generation that actually produced it
    generation: Optional[int] = None
    # live sparse query terms (ids in [0, vocab)), counted at admission
    # on endpoints whose queries have a sparse part
    query_terms: int = 0


def _on_host(x) -> bool:
    return isinstance(x, (np.ndarray, np.generic))


def _stack(trees: list, where: set):
    """Stack ``trees`` leaf by leaf, as ``jnp.stack`` would, dtypes
    included.  A leaf that every tree holds on the host is stacked there
    and moved in one transfer; a leaf any tree holds on a device keeps
    ``jnp.stack``, which pulls nothing back to the host.  Adds
    ``"host"`` or ``"device"`` to ``where`` for each leaf."""
    def leaf(*xs):
        if all(map(_on_host, xs)):
            where.add("host")
            # one array of each dtype: the promotion of all of them
            dtype = jnp.result_type(*{x.dtype: x for x in xs}.values())
            return jax.device_put(np.stack(xs, dtype=dtype))
        where.add("device")
        return jnp.stack(xs)

    return jax.tree.map(leaf, *trees)


def _host_copy(tree):
    """``tree`` with its host leaves as NumPy arrays (NumPy scalars
    become 0-d arrays) and its device leaves as they are."""
    return jax.tree.map(lambda x: np.asarray(x) if _on_host(x) else x, tree)


def _sparse_part(query_repr) -> Optional[SparseVectors]:
    if isinstance(query_repr, FusedVectors):
        return query_repr.sparse
    if isinstance(query_repr, SparseVectors):
        return query_repr
    return None


class _AdmissionQueue:
    """Bounded FIFO where admission, overload policy, and close are one
    atomic decision under one lock (a plain ``queue.Queue`` can't shed its
    oldest entry or refuse puts after close without racing the worker)."""

    def __init__(self, name: str, max_depth: Optional[int] = None,
                 policy: str = "block"):
        if policy not in OVERLOAD_POLICIES:
            raise ValueError(
                f"overload policy {policy!r} not in {OVERLOAD_POLICIES}")
        if max_depth is not None and max_depth < 1:
            raise ValueError("max_queue must be >= 1 (or None for unbounded)")
        self._name = name
        self._max = max_depth
        self._policy = policy
        self._items: "collections.deque[Request]" = collections.deque()
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self._closed = False

    def qsize(self) -> int:
        return len(self._items)       # len() is atomic on deque

    def put(self, item: Request) -> Optional[Request]:
        """Admit ``item``; returns the evicted request under shed_oldest
        (else None).  Raises :class:`ServiceOverloaded` (reject at depth)
        or RuntimeError (closed — also wakes blocked putters)."""
        with self._lock:
            while True:
                if self._closed:
                    raise RuntimeError(f"batcher {self._name!r} is closed")
                if self._max is None or len(self._items) < self._max:
                    self._items.append(item)
                    self._not_empty.notify()
                    return None
                if self._policy == "reject":
                    raise ServiceOverloaded(
                        f"endpoint {self._name!r}: admission queue at depth "
                        f"limit {self._max}")
                if self._policy == "shed_oldest":
                    shed = self._items.popleft()
                    self._items.append(item)
                    self._not_empty.notify()
                    return shed
                # block: wait for the worker to make space (bounded wait so
                # a missed notify can never wedge the submitter)
                self._not_full.wait(timeout=_POLL_S)

    def get(self, timeout: float) -> Optional[Request]:
        deadline = time.monotonic() + timeout
        with self._lock:
            while not self._items:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self._not_empty.wait(timeout=remaining)
            item = self._items.popleft()
            self._not_full.notify()
            return item

    def drain(self) -> List[Request]:
        with self._lock:
            items = list(self._items)
            self._items.clear()
            self._not_full.notify_all()
            return items

    def close(self):
        with self._lock:
            self._closed = True
            self._not_full.notify_all()
            self._not_empty.notify_all()


class ContinuousBatcher:
    def __init__(
        self,
        name: str,
        run_fn: Callable[[Any, Optional[Any]], Any],
        pad_query_repr: Any,
        pad_q_tokens: Optional[Any] = None,
        *,
        batch_size: int = 16,
        max_wait_s: float = 0.01,
        max_queue: Optional[int] = None,
        overload: str = "block",
        backend: Optional[str] = None,
        corpus_dtype: Optional[str] = None,
        profile: Optional[str] = None,
        query_vocab: Optional[int] = None,
        stats: Optional[ServingStats] = None,
        on_result: Optional[Callable[[Request, Any], None]] = None,
        time_fn: Callable[[], float] = time.monotonic,
    ):
        if batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        self.name = name
        self.run_fn = run_fn
        self.pad_query_repr = _host_copy(pad_query_repr)
        self.pad_q_tokens = _host_copy(pad_q_tokens)
        self.batch_size = batch_size
        self.max_wait_s = max_wait_s
        self.max_queue = max_queue
        self.overload = overload
        # execution-backend identity and corpus residency dtype of the
        # endpoint's runner: surfaced in stats snapshots and folded into
        # this endpoint's cache keys (two endpoints over one corpus that
        # differ only in dtype are different precision tiers and must
        # never alias)
        self.backend = backend
        self.corpus_dtype = corpus_dtype
        # tuned-profile tag (TunedProfile.tag) when this endpoint's knobs
        # came from an autotuned profile: provenance in snapshots + keys
        self.profile = profile
        # sparse query terms: with the vocabulary of the endpoint's
        # space, the slots of the pad query's sparse part are the slots
        # the scan matches per row of a batch; 0 turns the term counters
        # off (dense endpoints walk nothing per request)
        pad_sparse = _sparse_part(pad_query_repr)
        self._query_vocab = query_vocab
        self._term_slots = (int(np.shape(pad_sparse.indices)[-1])
                            if query_vocab is not None
                            and pad_sparse is not None else 0)
        self.stats = stats if stats is not None else ServingStats()
        self.on_result = on_result
        self._time_fn = time_fn
        # where the last batch's leaves were stacked ("host", "device" or
        # "mixed"): set by ``_assemble``, read by ``_execute``, both on
        # the worker thread
        self._stacked_on = "host"
        self._queue = _AdmissionQueue(name, max_queue, overload)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name=f"batcher-{name}", daemon=True)
        self.stats.register_endpoint(name, self._queue.qsize,
                                     depth_limit=max_queue, backend=backend,
                                     corpus_dtype=corpus_dtype,
                                     profile=profile)
        self._thread.start()

    # -- client side --------------------------------------------------------
    def submit(self, request: Request):
        if self.pad_q_tokens is None and request.q_tokens is not None:
            raise ValueError(
                f"endpoint {self.name!r} was registered without "
                "pad_q_tokens, so per-request q_tokens would be silently "
                "dropped; register the endpoint with a pad_q_tokens value")
        if self._term_slots:
            ids = np.asarray(_sparse_part(request.query_repr).indices)
            request.query_terms = int(np.count_nonzero(
                (ids >= 0) & (ids < self._query_vocab)))
        try:
            shed = self._queue.put(request)
        except ServiceOverloaded:
            self.stats.record_overload(self.name, "rejected")
            raise
        if shed is not None:
            self.stats.record_overload(self.name, "shed")
            if shed.future.set_running_or_notify_cancel():
                shed.future.set_exception(ServiceOverloaded(
                    f"endpoint {self.name!r}: request shed from a full "
                    f"admission queue (depth limit {self.max_queue})"))

    def queue_depth(self) -> int:
        return self._queue.qsize()

    # -- worker side --------------------------------------------------------
    def _loop(self):
        seq = 0          # this endpoint's batches: the spans' ``batch``
        while not self._stop.is_set():
            phases: dict = {}
            batch, closed_by = self._gather(seq, phases)
            if batch:
                self._safe_execute(batch, closed_by, seq, phases)
                seq += 1
        # drain: everything still queued is flushed in fixed-size batches
        leftover = self._queue.drain()
        for i in range(0, len(leftover), self.batch_size):
            self._safe_execute(leftover[i:i + self.batch_size], "drain",
                               seq, {})
            seq += 1

    def _safe_execute(self, batch: List[Request], closed_by: str, seq: int,
                      phases: dict):
        """The worker must survive anything a batch throws at it."""
        try:
            self._execute(batch, closed_by, seq, phases)
        except Exception as exc:            # noqa: BLE001
            for r in batch:
                if not r.future.done():
                    r.future.set_exception(exc)

    def _gather(self, seq: int, phases: dict):
        """Block for the first request, then fill until size or deadline.
        The ``serve.gather`` span (and phase) starts at the first request:
        the idle polling before it is not the batch's."""
        first = self._queue.get(timeout=_POLL_S)
        if first is None:
            return [], None
        with span("serve.gather", phases, endpoint=self.name,
                  batch=seq) as sp:
            batch, closed_by = self._fill([first])
            sp.set(closed_by=closed_by, n=len(batch))
        return batch, closed_by

    def _fill(self, batch: List[Request]):
        deadline = self._time_fn() + self.max_wait_s
        while len(batch) < self.batch_size:
            if self._stop.is_set():
                return batch, "drain"
            remaining = deadline - self._time_fn()
            if remaining <= 0:
                return batch, "deadline"
            nxt = self._queue.get(timeout=min(remaining, _POLL_S))
            if nxt is None:
                continue   # re-check stop flag and deadline
            batch.append(nxt)
        return batch, "size"

    def _assemble(self, batch: List[Request]):
        n_pad = self.batch_size - len(batch)
        where: set = set()
        reprs = [r.query_repr for r in batch] + [self.pad_query_repr] * n_pad
        stacked = _stack(reprs, where)
        tokens = None
        if self.pad_q_tokens is not None:
            toks = [r.q_tokens for r in batch] + [self.pad_q_tokens] * n_pad
            tokens = _stack(toks, where)
        self._stacked_on = where.pop() if len(where) == 1 else "mixed"
        return stacked, tokens

    def _execute(self, batch: List[Request], closed_by: str, seq: int,
                 phases: dict):
        """One batch, under the ``serve.batch`` span: the ``serve.assemble``,
        ``serve.dispatch``, ``serve.sync`` and ``serve.copy_back`` phases
        between ``t0`` and ``t1`` (``exec_s``), then ``serve.fanout``."""
        with span("serve.batch", endpoint=self.name, batch=seq,
                  served=len(batch)):
            t0 = self._time_fn()
            try:
                with span("serve.assemble", phases) as sp:
                    stacked, tokens = self._assemble(batch)
                    sp.set(stack=self._stacked_on)
                with span("serve.dispatch", phases):
                    if getattr(self.run_fn, "budget_aware", False):
                        # budget-aware runners (the served funnel) get the
                        # time this batch already spent queued —
                        # enforcement starts at batch close, so an
                        # end-to-end budget covers the request's whole
                        # life, not just compute
                        elapsed = max(t0 - min(r.t_admit for r in batch),
                                      0.0)
                        out = self.run_fn(stacked, tokens, elapsed_s=elapsed)
                    else:
                        out = self.run_fn(stacked, tokens)
                with span("serve.sync", phases):
                    jax.block_until_ready(out)
                with span("serve.copy_back", phases):
                    out = jax.tree.map(np.asarray, out)
            except Exception as exc:        # noqa: BLE001 — fan out to futures
                for r in batch:
                    if not r.future.done():
                        r.future.set_exception(exc)
                return
            t1 = self._time_fn()
            self.stats.record_batch(
                self.name, served=len(batch), capacity=self.batch_size,
                closed_by=closed_by,
                queue_waits_s=[t0 - r.t_admit for r in batch],
                exec_s=t1 - t0, phases=phases,
                query_terms=sum(r.query_terms for r in batch),
                term_slots=self.batch_size * self._term_slots,
                host_assembled=self._stacked_on == "host")
            fanout: dict = {}
            with span("serve.fanout", fanout):
                for i, r in enumerate(batch):
                    result = jax.tree.map(lambda x: x[i], out)
                    if self.on_result is not None:
                        self.on_result(r, result)
                    self.stats.record_e2e(self.name,
                                          self._time_fn() - r.t_admit)
                    # a client may have cancelled the future while it was
                    # queued; claiming it as running makes set_result
                    # race-free
                    if r.future.set_running_or_notify_cancel():
                        r.future.set_result(result)
            self.stats.record_fanout(self.name, fanout["fanout"])

    def close(self):
        """Stop accepting (wakes blocked submitters), flush the queue, join
        the worker.  Requests admitted before close are still served."""
        self._queue.close()
        self._stop.set()
        self._thread.join()
