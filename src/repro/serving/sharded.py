"""Sharded-corpus serving: K corpus shards behind one batcher endpoint.

NMSLIB scales its query server by splitting the collection across servers
and merging per-server result lists; this module is that idea inside one
process (and, with a mesh, across devices):

  * :func:`shard_corpus` partitions any row-major corpus pytree (dense
    ``[N, D]`` arrays, ``SparseVectors``, ``FusedVectors``) into K
    *contiguous row ranges*.  With a :class:`~repro.distributed.sharding.
    ParallelCtx` carrying a mesh, each shard is ``device_put`` onto a mesh
    device along the mapped axis; otherwise shards stay host-resident and
    are searched host-parallel (one thread per shard — JAX ops release the
    GIL while executing).
  * :class:`ShardedPipeline` runs one candidate generator per shard (exact
    brute force by default; graph-ANN or NAPP via ``generator_factory``),
    rebases local row ids by the shard offset, merges the K candidate
    lists with :func:`~repro.core.brute_force.merge_topk`, and applies the
    usual reranker tail once over the merged global candidates.  The
    per-shard execution path is pluggable: ``from_corpus(...,
    backend=...)`` / :meth:`ShardedPipeline.with_backend` resolve a
    :mod:`repro.core.backends` backend against each shard's slice.

Identity: contiguous shards concatenated in row order preserve
``lax.top_k``'s tie-break (lower slot == lower global row id), and every
per-row score is computed from the same values as the unsharded scan —
so for exact generators the sharded result equals the unsharded
``RetrievalPipeline.run`` (bit for bit on the reference backend; under
the f32 rule of ``core.precision`` on the kernel and tiled backends,
whose sum order depends on the shard's shape — ``tests/test_sharded.py``).
With a mesh, the per-shard ``B x k`` lists move to the first shard's
device for the merge.  Each shard's call runs under a ``shard.scan``
profiler span (attribute ``shard``) on its own thread, and the move and
merge under ``shard.merge`` on the calling one.

A ``ShardedPipeline`` exposes ``run(query_repr, q_tokens)`` and
``generate(query_repr, k)``, so it registers behind a single
:class:`~repro.serving.batcher.ContinuousBatcher` endpoint via
``RetrievalService.register_pipeline`` — the router, cache, and stats
layers never learn the corpus is sharded — and also slots into a larger
:class:`~repro.core.pipeline.RetrievalPipeline` as a candidate generator.
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Optional, Tuple

import jax

from repro.core.backends import resolve_backend
from repro.core.brute_force import TopK, concat_topk, merge_topk
from repro.core.pipeline import (BruteForceGenerator, apply_rerankers,
                                 pin_snapshot)
from repro.core.spaces import canonical_dtype, cast_corpus
from repro.serving.stats import span

__all__ = ["CorpusShard", "shard_corpus", "ShardedPipeline"]


@dataclasses.dataclass(frozen=True)
class CorpusShard:
    """One contiguous row range of the corpus: local rows ``[0, n_rows)``
    correspond to global rows ``[offset, offset + n_rows)``."""

    corpus: Any
    offset: int
    n_rows: int


def _corpus_rows(corpus) -> int:
    return jax.tree.leaves(corpus)[0].shape[0]


def _placement_devices(ctx, axis: str):
    """One device per shard slot along the mapped mesh axis (flat mesh
    order when the logical axis resolves to nothing)."""
    if ctx is None or getattr(ctx, "mesh", None) is None:
        return None
    mesh = ctx.mesh
    phys = ctx.mesh_axes(axis)
    if phys is None:
        return list(mesh.devices.flat)
    names = (phys,) if isinstance(phys, str) else list(phys)
    order = [mesh.axis_names.index(a) for a in names]
    rest = [i for i in range(mesh.devices.ndim) if i not in order]
    moved = mesh.devices.transpose(order + rest)
    # first device of each slice along the corpus axis/axes
    n_slots = 1
    for a in names:
        n_slots *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    return list(moved.reshape(n_slots, -1)[:, 0])


def shard_corpus(corpus, n_shards: int, *, ctx=None,
                 axis: str = "corpus") -> Tuple[CorpusShard, ...]:
    """Partition a corpus pytree into ``n_shards`` contiguous row ranges.

    Row order across shards equals global row order — load-bearing for the
    bit-identical merge (see module docstring).  ``ctx`` (a ParallelCtx)
    device-places shard ``i`` on the ``i % n_devices``-th device along the
    mesh axis that logical ``axis`` maps to; without a mesh the slices stay
    wherever the corpus lives.
    """
    n = _corpus_rows(corpus)
    if not 1 <= n_shards <= n:
        raise ValueError(f"n_shards={n_shards} must be in [1, {n}]")
    devices = _placement_devices(ctx, axis)
    bounds = [n * i // n_shards for i in range(n_shards + 1)]
    shards = []
    for i, (lo, hi) in enumerate(zip(bounds, bounds[1:])):
        piece = jax.tree.map(lambda x: x[lo:hi], corpus)
        if devices is not None:
            piece = jax.device_put(piece, devices[i % len(devices)])
        shards.append(CorpusShard(piece, lo, hi - lo))
    return tuple(shards)


def _on_one_device(parts):
    """Per-shard candidate lists committed to different devices (shards
    placed by a mesh) move to the first shard's device for the merge:
    ``B * k`` values per shard, not the corpus."""
    devices = [p.scores.devices() for p in parts]
    if all(d == devices[0] for d in devices):
        return parts
    target = next(iter(devices[0]))
    return [jax.device_put(p, target) for p in parts]


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedPipeline:
    """Drop-in for ``RetrievalPipeline.run`` over a K-way sharded corpus.

    Each shard's generator sees only its slice (local row ids); offsets
    rebase to global ids, ``merge_topk`` folds the K lists into the global
    top-``cand_qty``, and the rerankers run once on the merged candidates.
    Build with :meth:`from_corpus`.
    """

    shards: Tuple[CorpusShard, ...]
    generators: Tuple[Any, ...]
    intermediate: Optional[Any] = None
    final: Optional[Any] = None
    cand_qty: int = 100
    interm_qty: int = 50
    final_qty: int = 10
    executor: Optional[ThreadPoolExecutor] = None

    @classmethod
    def from_corpus(
        cls, space, corpus, n_shards: int, *, ctx=None, axis: str = "corpus",
        generator_factory: Optional[Callable[[CorpusShard], Any]] = None,
        backend=None, corpus_dtype: Optional[str] = None,
        intermediate=None, final=None,
        cand_qty: int = 100, interm_qty: int = 50, final_qty: int = 10,
        host_parallel: bool = True,
    ) -> "ShardedPipeline":
        """Shard ``corpus`` K ways and build one generator per shard.

        ``generator_factory(shard) -> CandidateGenerator`` defaults to exact
        ``BruteForceGenerator(space, shard.corpus)``; pass a factory building
        per-shard ``GraphANNGenerator`` / ``NappGenerator`` for approximate
        search (merged results are then the union-of-shards approximation,
        not bit-identical to a global index).

        ``backend`` selects the execution path of the default per-shard
        generators (a :mod:`repro.core.backends` name, ``"auto"``, or
        instance), resolved per shard against that shard's slice — a
        backend that cannot serve the space falls back to reference shard
        by shard.  Mutually exclusive with ``generator_factory`` (a custom
        factory owns its generators' execution entirely).

        ``corpus_dtype`` casts the corpus to a residency dtype *before*
        sharding (``"bfloat16"`` halves every shard's footprint; scores
        stay f32 — the precision contract in ``core.spaces``).  Casting
        commutes with row-slicing, so a bf16 sharded pipeline stays
        bit-identical to the unsharded bf16 scan.
        """
        if backend is not None and generator_factory is not None:
            raise ValueError(
                "pass either backend= or generator_factory=, not both: a "
                "custom factory owns its generators' execution path")
        if corpus_dtype is not None:
            corpus = cast_corpus(corpus, canonical_dtype(corpus_dtype))
        shards = shard_corpus(corpus, n_shards, ctx=ctx, axis=axis)
        if generator_factory is None:
            def generator_factory(shard: CorpusShard):
                resolved = (None if backend is None else
                            resolve_backend(backend, space, shard.corpus))
                return BruteForceGenerator(space, shard.corpus,
                                           backend=resolved)
        executor = (ThreadPoolExecutor(max_workers=n_shards,
                                       thread_name_prefix="shard")
                    if host_parallel and n_shards > 1 else None)
        return cls(shards=shards,
                   generators=tuple(generator_factory(s) for s in shards),
                   intermediate=intermediate, final=final, cand_qty=cand_qty,
                   interm_qty=interm_qty, final_qty=final_qty,
                   executor=executor)

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def corpus_dtype(self) -> Optional[str]:
        """The shards' common corpus residency dtype (None when the
        per-shard generators disagree or carry no dtype seam)."""
        dts = {getattr(g, "corpus_dtype", None) for g in self.generators}
        if len(dts) == 1 and (d := dts.pop()) is not None:
            return d
        return None

    def with_corpus_dtype(self, dtype) -> "ShardedPipeline":
        """Same shards, different corpus residency dtype: every per-shard
        generator is recast (casting commutes with the row-slicing that
        built the shards, so merged results equal an unsharded cast
        corpus bit for bit).  The rebound pipeline owns a fresh
        host-parallel pool — close it separately.  Raises TypeError when
        a shard generator has no dtype seam (e.g. per-shard graph-ANN)."""
        for g in self.generators:
            if not hasattr(g, "with_corpus_dtype"):
                raise TypeError(
                    f"shard generator {type(g).__name__} does not take a "
                    "corpus residency dtype")
        generators = tuple(g.with_corpus_dtype(dtype)
                           for g in self.generators)
        shards = tuple(
            dataclasses.replace(s, corpus=getattr(g, "corpus", s.corpus))
            for s, g in zip(self.shards, generators))
        executor = (ThreadPoolExecutor(max_workers=self.n_shards,
                                       thread_name_prefix="shard")
                    if self.executor is not None else None)
        return dataclasses.replace(self, shards=shards,
                                   generators=generators, executor=executor)

    def with_backend(self, backend) -> "ShardedPipeline":
        """Same shards, different execution path: every per-shard generator
        is rebound onto ``backend`` (resolved against its own slice, so an
        incapable backend falls back to reference shard by shard).  The
        rebound pipeline owns a fresh host-parallel pool — close it
        separately.  Raises TypeError when a shard generator has no
        backend seam (e.g. per-shard graph-ANN)."""
        for g in self.generators:
            if not hasattr(g, "with_backend"):
                raise TypeError(
                    f"shard generator {type(g).__name__} does not take an "
                    "execution backend")
        executor = (ThreadPoolExecutor(max_workers=self.n_shards,
                                       thread_name_prefix="shard")
                    if self.executor is not None else None)
        return dataclasses.replace(
            self,
            generators=tuple(g.with_backend(backend)
                             for g in self.generators),
            executor=executor)

    # CandidateGenerator protocol: a ShardedPipeline can itself feed a
    # larger RetrievalPipeline as its (sharded) candidate stage.
    def generate(self, query_repr, k: Optional[int] = None) -> TopK:
        """Global top-k candidates from the sharded generator stage."""
        k = self.cand_qty if k is None else k
        # Live-corpus shard generators are pinned up front, before the
        # fan-out, so one batch sees a mutually consistent set of
        # per-shard states even while writers and compactors race the
        # query threads (the pin_snapshot seam shared with
        # RetrievalPipeline and the serving funnel).
        generators = [pin_snapshot(g) for g in self.generators]

        def one(i: int, gen, shard: CorpusShard) -> TopK:
            with span("shard.scan", shard=i):
                local = gen.generate(query_repr, min(k, shard.n_rows))
            return TopK(local.scores, local.indices + shard.offset)

        # under a jit trace the queries are tracers, which must not cross
        # thread boundaries (UnexpectedTracerError) — the traced program is
        # "parallel" shard-by-shard in the compiled graph anyway
        tracing = any(isinstance(leaf, jax.core.Tracer)
                      for leaf in jax.tree.leaves(query_repr))
        ids = range(self.n_shards)
        if self.executor is not None and not tracing:
            parts = list(self.executor.map(one, ids, generators, self.shards))
        else:
            parts = list(map(one, ids, generators, self.shards))
        with span("shard.merge"):
            if not tracing:
                parts = _on_one_device(parts)
            cat = concat_topk(parts)
            return merge_topk(cat, min(k, cat.scores.shape[1]))

    def run(self, query_repr, q_tokens=None) -> TopK:
        cands = self.generate(query_repr, self.cand_qty)
        return apply_rerankers(
            cands, q_tokens, intermediate=self.intermediate, final=self.final,
            interm_qty=self.interm_qty, final_qty=self.final_qty)

    # -- lifecycle ----------------------------------------------------------
    def close(self):
        """Shut down the host-parallel worker pool (no-op when serial).
        Long-lived processes that rebuild pipelines (index refresh, shard
        sweeps) should close retired ones; ``run`` after close falls back
        to serial execution."""
        if self.executor is not None:
            self.executor.shutdown(wait=True)
            object.__setattr__(self, "executor", None)

    def __enter__(self) -> "ShardedPipeline":
        return self

    def __exit__(self, *exc):
        self.close()
