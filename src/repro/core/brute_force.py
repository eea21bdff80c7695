"""Exact k-NN / maximum inner-product search (brute force), tiled + sharded.

NMSLIB's brute-force scan is a SIMD loop over the corpus keeping a bounded
priority queue.  The TPU adaptation:

  * the distance loop becomes an MXU tiled matmul (``spaces.dense_scores``);
  * the priority queue becomes a *streaming top-k merge*: scan corpus tiles,
    concat the running [B, k] heap with the new [B, tile] scores and
    ``lax.top_k`` — O(B·(k+tile)·log) per tile, never materialising [B, N];
  * sharding: the corpus is row-sharded over a mesh axis; each shard
    produces a local top-k, and a distributed merge (all-gather of k·shards
    candidates, k ≪ N) yields the global result — this is the multi-chip
    version of NMSLIB's per-server sharding.

The Pallas kernel in ``repro.kernels.mips_topk`` implements the fused
score-tile + top-k-merge loop with explicit VMEM residency; this module is
the pure-jnp system path (and the kernel's oracle delegates here).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "TopK",
    "exact_topk",
    "streaming_topk",
    "concat_topk",
    "merge_topk",
    "sharded_exact_topk",
    "pad_corpus",
]


class TopK(NamedTuple):
    scores: jax.Array   # f32[B, K] descending
    indices: jax.Array  # i32[B, K] corpus row ids


def pad_corpus(x, multiple: int, fill: float = 0.0) -> Tuple[jax.Array, int]:
    """Pad the corpus row axis up to a multiple (padding rows score -inf via
    the valid-count mask threaded through scoring).  ``x`` may be any
    row-major corpus pytree (dense array, ``SparseVectors``,
    ``FusedVectors``): every leaf is padded along axis 0 with ``fill``
    cast to its dtype — safe because scores of padded rows are always
    masked by the valid count before selection."""
    n = jax.tree.leaves(x)[0].shape[0]
    padded = (n + multiple - 1) // multiple * multiple
    if padded == n:
        return x, n

    def pad_leaf(leaf):
        pad = [(0, padded - n)] + [(0, 0)] * (leaf.ndim - 1)
        return jnp.pad(leaf, pad, constant_values=leaf.dtype.type(fill))

    return jax.tree.map(pad_leaf, x), n


def _mask_invalid(scores: jax.Array, base: int, n_valid: int) -> jax.Array:
    """-inf out rows past the true corpus size inside a padded tile."""
    n_tile = scores.shape[-1]
    rows = base + jnp.arange(n_tile)
    return jnp.where(rows[None, :] < n_valid, scores, -jnp.inf)


def exact_topk(space, queries, corpus, k: int, n_valid: int | None = None) -> TopK:
    """One-shot exact top-k: full [B, N] score matrix then ``lax.top_k``.
    Best when B·N fits comfortably in HBM; otherwise use streaming_topk."""
    scores = space.score_batch(queries, corpus)
    if n_valid is not None:
        scores = _mask_invalid(scores, 0, n_valid)
    vals, idx = jax.lax.top_k(scores, k)
    return TopK(vals, idx.astype(jnp.int32))


def streaming_topk(
    space,
    queries,
    corpus,
    k: int,
    tile_n: int = 8192,
    n_valid: int | None = None,
) -> TopK:
    """Scan corpus tiles keeping a running [B, k] heap.  ``corpus`` may be
    any row-major pytree — a dense [N, D] array, ``SparseVectors``, or
    ``FusedVectors`` — with N % tile_n == 0 (see :func:`pad_corpus`);
    each tile is scored through ``space.score_batch``, so per-element
    arithmetic matches the one-shot reference scan exactly."""
    n = jax.tree.leaves(corpus)[0].shape[0]
    assert n % tile_n == 0, f"N={n} not a multiple of tile_n={tile_n}"
    n_tiles = n // tile_n
    b = jax.tree.leaves(queries)[0].shape[0]
    n_valid = n if n_valid is None else n_valid

    init = TopK(
        jnp.full((b, k), -jnp.inf, dtype=jnp.float32),
        jnp.zeros((b, k), dtype=jnp.int32),
    )
    tiles = jax.tree.map(
        lambda x: x.reshape(n_tiles, tile_n, *x.shape[1:]), corpus)

    def body(heap: TopK, inp):
        t, tile = inp
        base = t * tile_n
        s = space.score_batch(queries, tile).astype(jnp.float32)
        s = _mask_invalid(s, base, n_valid)
        ids = base + jnp.arange(tile_n, dtype=jnp.int32)
        cat_s = jnp.concatenate([heap.scores, s], axis=1)
        cat_i = jnp.concatenate([heap.indices, jnp.broadcast_to(ids, (b, tile_n))], axis=1)
        vals, pos = jax.lax.top_k(cat_s, k)
        return TopK(vals, jnp.take_along_axis(cat_i, pos, axis=1)), None

    heap, _ = jax.lax.scan(body, init, (jnp.arange(n_tiles), tiles))
    return heap


def concat_topk(parts) -> TopK:
    """Column-concatenate per-shard candidate lists, preserving their order.

    Order is load-bearing for bit-identical sharded merges: ``lax.top_k``
    breaks score ties toward the lower slot, so contiguous row-range shards
    concatenated in row order reproduce the unsharded tie-break (the lower
    global row id wins in both layouts)."""
    parts = list(parts)
    if len(parts) == 1:
        return parts[0]
    return TopK(jnp.concatenate([p.scores for p in parts], axis=1),
                jnp.concatenate([p.indices for p in parts], axis=1))


def merge_topk(parts: TopK, k: int) -> TopK:
    """Merge candidate lists: parts.scores [B, M>=k] (any order) -> top-k."""
    vals, pos = jax.lax.top_k(parts.scores, k)
    return TopK(vals, jnp.take_along_axis(parts.indices, pos, axis=1))


def sharded_exact_topk(
    space,
    queries: jax.Array,
    corpus: jax.Array,
    k: int,
    mesh,
    corpus_axis: str = "model",
    tile_n: int = 0,
) -> TopK:
    """Distributed exact MIPS via shard_map.

    corpus row-sharded over ``corpus_axis``; queries replicated along it.
    Each shard computes a local top-k with *global* row ids, then the k-sized
    candidate lists are all-gathered and merged — total wire traffic is
    O(B·k·shards) versus O(B·N) for gathering scores, which is the whole
    point of pushing top-k below the collective.
    """
    n_shards = mesh.shape[corpus_axis]
    n = corpus.shape[0]
    assert n % n_shards == 0, f"corpus rows {n} % shards {n_shards} != 0"
    per = n // n_shards

    def local(q, c_shard):
        shard_idx = jax.lax.axis_index(corpus_axis)
        base = shard_idx * per
        if tile_n:
            local_heap = streaming_topk(space, q, c_shard, k, tile_n)
        else:
            local_heap = exact_topk(space, q, c_shard, k)
        local_heap = TopK(local_heap.scores, local_heap.indices + base)
        all_s = jax.lax.all_gather(local_heap.scores, corpus_axis, axis=1, tiled=True)
        all_i = jax.lax.all_gather(local_heap.indices, corpus_axis, axis=1, tiled=True)
        return merge_topk(TopK(all_s, all_i), k)

    other_axes = tuple(a for a in mesh.axis_names if a != corpus_axis)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(*([None] * queries.ndim)), P(corpus_axis, *([None] * (corpus.ndim - 1)))),
        out_specs=TopK(P(), P()),
        check_vma=False,
    )
    return fn(queries, corpus)
