"""TPU-native proximity-graph k-NN search (NSW/HNSW adaptation) + NN-descent.

The paper's flagship retrieval algorithms are NSW (Malkov et al. 2014) and
HNSW (Malkov & Yashunin 2018): greedy/beam search over a navigable
neighborhood graph.  Their inner loop — pop best unvisited node, chase
pointers, update a scalar priority queue — is hostile to TPUs (data-
dependent control flow, irregular memory).  Following DESIGN.md §4 we
re-cast it:

  * fixed-degree flat graph ``neighbors: i32[N, R]`` built by NN-descent
    (Dong et al. 2011 — the KGraph algorithm the paper cites);
  * HNSW's hierarchy (whose role is supplying good entry points) becomes a
    brute-force scored *coarse entry set* — one MXU matmul over ~sqrt(N)
    sampled points;
  * the priority queue becomes a beam ``[B, ef]`` merged with candidate
    scores through ``lax.top_k``; visited-set is a boolean table;
  * convergence tests become a fixed hop count (scan) with an optional
    ``lax.while_loop`` early-exit variant for serving.

Everything is distance-agnostic through the ``Space`` interface — NMSLIB's
key design property (we never touch vector internals here, only
``score_many``/``score_batch``), so the fused sparse+dense space runs
*inside* graph search, which is the paper's novel capability.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.brute_force import TopK, merge_topk
from repro.core import spaces as spaces_lib
from repro.core.sparse import SparseVectors

__all__ = [
    "GraphIndex",
    "gather_items",
    "score_many",
    "nn_descent",
    "flat_adjacency",
    "default_hops",
    "beam_search",
    "beam_search_early_exit",
    "kernel_beam_search",
]


class GraphIndex(NamedTuple):
    neighbors: jax.Array   # i32[N, R]
    entry_ids: jax.Array   # i32[E] coarse entry-point sample


# ---------------------------------------------------------------------------
# Generic item gather / one-vs-many scoring for dense, sparse and fused data.
# ---------------------------------------------------------------------------

def gather_items(corpus, ids: jax.Array):
    """corpus rows at ``ids`` (any leading shape), for dense [N, D] arrays,
    SparseVectors, or FusedVectors."""
    if isinstance(corpus, spaces_lib.FusedVectors):
        return spaces_lib.FusedVectors(
            None if corpus.dense is None else corpus.dense[ids],
            None if corpus.sparse is None else gather_items(corpus.sparse, ids),
        )
    if isinstance(corpus, SparseVectors):
        return SparseVectors(corpus.indices[ids], corpus.values[ids])
    return corpus[ids]


def score_many(space, queries, items) -> jax.Array:
    """Scores [B, C] of query b against items[b, c]."""
    if isinstance(space, spaces_lib.DenseSpace):
        if space.kind == "ip":
            return jnp.einsum("bd,bcd->bc", queries, items)
        if space.kind == "cosine":
            qn = queries / jnp.maximum(jnp.linalg.norm(queries, axis=-1, keepdims=True), 1e-12)
            xn = items / jnp.maximum(jnp.linalg.norm(items, axis=-1, keepdims=True), 1e-12)
            return jnp.einsum("bd,bcd->bc", qn, xn)
        if space.kind == "l2":
            d = queries[:, None, :] - items
            return -jnp.sum(d * d, axis=-1)
        return jax.vmap(lambda q, x: space.score_pairs(jnp.broadcast_to(q, x.shape), x))(
            queries, items
        )
    if isinstance(space, spaces_lib.SparseSpace):
        from repro.core.sparse import densify

        qd = densify(queries, space.vocab_size)
        qd = jnp.pad(qd, ((0, 0), (0, 1)))

        def one(qrow, it_idx, it_val):
            return jnp.sum(qrow[it_idx] * it_val, axis=-1)

        return jax.vmap(one)(qd, items.indices, items.values)
    if isinstance(space, spaces_lib.FusedSpace):
        total = None
        if queries.dense is not None and items.dense is not None:
            total = space.w_dense * score_many(
                spaces_lib.DenseSpace(space.dense_kind), queries.dense, items.dense
            )
        if queries.sparse is not None and items.sparse is not None:
            s = score_many(
                spaces_lib.SparseSpace(space.vocab_size), queries.sparse, items.sparse
            )
            total = space.w_sparse * s if total is None else total + space.w_sparse * s
        return total
    raise TypeError(f"unsupported space {type(space)}")


# ---------------------------------------------------------------------------
# Graph construction: NN-descent (KGraph), batched.
# ---------------------------------------------------------------------------

def nn_descent(
    space,
    corpus,
    n_items: int,
    degree: int = 16,
    rounds: int = 6,
    key: jax.Array | None = None,
    node_block: int = 512,
    entry_count: int | None = None,
) -> GraphIndex:
    """Build a fixed-degree k-NN graph by neighbor-of-neighbor refinement.

    Per round, each node's candidate pool is {its neighbors} ∪ {neighbors of
    neighbors} ∪ {a few random ids}; the pool is scored against the node
    (batched, in node blocks of ``node_block``) and the best ``degree`` kept.
    Fixed ``rounds`` replaces NN-descent's convergence test (recall is
    asserted in tests).
    """
    key = jax.random.PRNGKey(0) if key is None else key
    n = n_items
    r = degree
    if n % node_block != 0:
        raise ValueError(
            f"node_block {node_block} must divide n_items {n} "
            f"(blocks are scanned with static shapes)")

    k0, k1 = jax.random.split(key)
    neighbors = jax.random.randint(k0, (n, r), 0, n, dtype=jnp.int32)

    n_rand = max(4, r // 4)
    node_ids = jnp.arange(n, dtype=jnp.int32)

    def one_round(neighbors, rkey):
        rand_cand = jax.random.randint(rkey, (n, n_rand), 0, n, dtype=jnp.int32)

        def block_body(_, blk):
            ids, nbrs, rnd = blk                           # [B], [B,R], [B,n_rand]
            two_hop = neighbors[nbrs].reshape(ids.shape[0], r * r)
            cand = jnp.concatenate([nbrs, two_hop, rnd], axis=1)   # [B, C]
            # dedupe + drop self: sort ids, mask repeats.
            cand = jnp.sort(cand, axis=1)
            dup = jnp.concatenate(
                [jnp.zeros_like(cand[:, :1], dtype=bool), cand[:, 1:] == cand[:, :-1]],
                axis=1,
            )
            self_mask = cand == ids[:, None]
            items = gather_items(corpus, cand)
            me = gather_items(corpus, ids)
            s = score_many(space, me, items)
            s = jnp.where(dup | self_mask, -jnp.inf, s)
            _, pos = jax.lax.top_k(s, r)
            return None, jnp.take_along_axis(cand, pos, axis=1)

        blocks = (
            node_ids.reshape(-1, node_block),
            neighbors.reshape(-1, node_block, r),
            rand_cand.reshape(-1, node_block, n_rand),
        )
        _, new_nbrs = jax.lax.scan(block_body, None, blocks)
        return new_nbrs.reshape(n, r)

    for i in range(rounds):
        key, rk = jax.random.split(key)
        neighbors = one_round(neighbors, rk)

    # clamp to n: more entries than items would duplicate ids in the
    # linspace sample, seeding the beam with repeated rows (e <= n keeps
    # the stride >= 1, so the int cast stays strictly increasing)
    e = min(n, entry_count or max(16, int(n**0.5)))
    entry_ids = jnp.linspace(0, n - 1, e).astype(jnp.int32)
    return GraphIndex(neighbors, entry_ids)


def flat_adjacency(neighbor_lists, n_items: int, degree: int,
                   sentinel: int | None = None) -> jax.Array:
    """Ragged adjacency -> the fixed-degree flat layout ``i32[N, R]``
    both beam searches traverse: row ``i`` holds ``neighbor_lists[i]``
    truncated to ``degree`` and padded with ``sentinel`` (default
    ``n_items`` — the out-of-range id every traversal already masks, so
    imported graphs with short rows cost masked lanes, never wrong
    candidates).  This is the import seam for externally built graphs
    (HNSW exports, exact k-NN graphs): NN-descent emits this layout
    natively."""
    if len(neighbor_lists) != n_items:
        raise ValueError(
            f"flat_adjacency: {len(neighbor_lists)} rows for "
            f"{n_items} items")
    pad = n_items if sentinel is None else sentinel
    out = np.full((n_items, degree), pad, dtype=np.int32)
    for i, row in enumerate(neighbor_lists):
        row = list(row)[:degree]
        out[i, :len(row)] = row
    return jnp.asarray(out)


# ---------------------------------------------------------------------------
# Batched beam search (the NSW/HNSW query algorithm, vectorised).
# ---------------------------------------------------------------------------

def default_hops(n_items: int) -> int:
    """Default fixed hop count ``max(4, int(2·ln N))`` — HNSW's expected
    search path length — computed host-side (no device round-trip)."""
    return max(4, int(2 * math.log(max(n_items, 1))))

class _BeamState(NamedTuple):
    beam: TopK            # [B, ef] current best (ids deduped)
    visited: jax.Array    # bool[B, N]
    frontier: jax.Array   # i32[B, F] ids expanded this hop


def _init_beam(space, queries, corpus, index: GraphIndex, ef: int, batch: int, n: int):
    entries = gather_items(corpus, index.entry_ids)
    s = space.score_batch(queries, entries)              # [B, E]
    k0 = min(ef, index.entry_ids.shape[0])
    vals, pos = jax.lax.top_k(s, k0)
    ids = index.entry_ids[pos]
    if k0 < ef:
        # Pad empty beam slots with the out-of-range sentinel ``n`` (never a
        # real corpus row) so the visited scatter drops them; padding with 0
        # would mark item 0 visited and make it unreachable for every query.
        vals = jnp.pad(vals, ((0, 0), (0, ef - k0)), constant_values=-jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, ef - k0)), constant_values=n)
    visited = jnp.zeros((batch, n), dtype=bool)
    visited = jax.vmap(lambda v, c: v.at[c].set(True, mode="drop"))(visited, ids)
    return _BeamState(TopK(vals, ids), visited, ids)


def _hop(space, queries, corpus, neighbors, state: _BeamState, ef: int):
    b = state.frontier.shape[0]
    r = neighbors.shape[1]
    # Frontier slots may hold the sentinel ``n`` (empty beam pad); clamp so
    # the neighbor gather stays in range — the extra candidates it surfaces
    # are real rows and only widen the beam.
    frontier = jnp.minimum(state.frontier, neighbors.shape[0] - 1)
    cand = neighbors[frontier].reshape(b, -1)            # [B, F*R]
    seen = jax.vmap(lambda v, c: v[c])(state.visited, cand)
    # in-candidate dedupe via sort
    order = jnp.argsort(cand, axis=1)
    cand_sorted = jnp.take_along_axis(cand, order, axis=1)
    seen_sorted = jnp.take_along_axis(seen, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros_like(cand_sorted[:, :1], dtype=bool),
         cand_sorted[:, 1:] == cand_sorted[:, :-1]],
        axis=1,
    )
    dead = seen_sorted | dup
    items = gather_items(corpus, cand_sorted)
    s = jnp.where(dead, -jnp.inf, score_many(space, queries, items))
    visited = jax.vmap(lambda v, c: v.at[c].set(True))(state.visited, cand_sorted)

    cat = TopK(
        jnp.concatenate([state.beam.scores, s], axis=1),
        jnp.concatenate([state.beam.indices, cand_sorted], axis=1),
    )
    new_beam = merge_topk(cat, ef)
    # next frontier = the fresh candidates that made it into the beam; to
    # keep shapes static we expand the *whole* new beam (already-expanded
    # nodes contribute only visited neighbors, masked next hop).
    return _BeamState(new_beam, visited, new_beam.indices)


def beam_search(
    space,
    queries,
    corpus,
    index: GraphIndex,
    n_items: int,
    k: int = 10,
    ef: int = 64,
    hops: int | None = None,
) -> TopK:
    """Fixed-hop batched beam search.  Returns global top-k (ids, scores)."""
    if isinstance(queries, spaces_lib.FusedVectors):
        batch = (queries.dense if queries.dense is not None else queries.sparse.indices).shape[0]
    elif isinstance(queries, SparseVectors):
        batch = queries.indices.shape[0]
    else:
        batch = queries.shape[0]
    hops = hops if hops is not None else default_hops(n_items)
    state = _init_beam(space, queries, corpus, index, ef, batch, n_items)

    def body(state, _):
        return _hop(space, queries, corpus, index.neighbors, state, ef), None

    state, _ = jax.lax.scan(body, state, None, length=int(hops))
    return merge_topk(state.beam, k)


def beam_search_early_exit(
    space, queries, corpus, index: GraphIndex, n_items: int,
    k: int = 10, ef: int = 64, max_hops: int = 32,
) -> TopK:
    """Serving variant: ``lax.while_loop`` exits when the beam stops changing
    (the NSW termination rule), bounded by ``max_hops``."""
    if isinstance(queries, spaces_lib.FusedVectors):
        batch = (queries.dense if queries.dense is not None else queries.sparse.indices).shape[0]
    elif isinstance(queries, SparseVectors):
        batch = queries.indices.shape[0]
    else:
        batch = queries.shape[0]
    state = _init_beam(space, queries, corpus, index, ef, batch, n_items)

    def cond(carry):
        state, prev_ids, it = carry
        changed = jnp.any(state.beam.indices != prev_ids)
        return jnp.logical_and(changed, it < max_hops)

    def body(carry):
        state, _, it = carry
        prev = state.beam.indices
        return _hop(space, queries, corpus, index.neighbors, state, ef), prev, it + 1

    state, _, _ = jax.lax.while_loop(cond, body, (state, -jnp.ones_like(state.beam.indices), 0))
    return merge_topk(state.beam, k)


# ---------------------------------------------------------------------------
# Kernelised beam search: the fused Pallas traversal (kernels/beam_topk.py)
# behind the same (space, queries, corpus, index) interface.
# ---------------------------------------------------------------------------

def _components(space, queries, corpus):
    """(qdensified, q_dense, c_idx, c_val, c_dense, w_dense, w_sparse,
    dense_kind, vocab) for the kernel call — the same component/weight
    conventions as ``backends.PallasBackend``'s fused dispatch: only
    components present on BOTH sides score, absent components carry no
    weight, a lone SparseSpace part stays unscaled."""
    from repro.core.sparse import densify

    if isinstance(space, spaces_lib.DenseSpace):
        return (None, queries, None, None, corpus, None, None, space.kind,
                None)
    if isinstance(space, spaces_lib.SparseSpace):
        qd = densify(queries, space.vocab_size)
        qd = jnp.pad(qd, ((0, 0), (0, 1)))
        return (qd, None, corpus.indices, corpus.values, None, None, None,
                "ip", space.vocab_size)
    if isinstance(space, spaces_lib.FusedSpace):
        has_dense = queries.dense is not None and corpus.dense is not None
        has_sparse = queries.sparse is not None and corpus.sparse is not None
        qd = c_idx = c_val = None
        if has_sparse:
            qd = densify(queries.sparse, space.vocab_size)
            qd = jnp.pad(qd, ((0, 0), (0, 1)))
            c_idx, c_val = corpus.sparse.indices, corpus.sparse.values
        return (qd,
                queries.dense if has_dense else None,
                c_idx, c_val,
                corpus.dense if has_dense else None,
                space.w_dense if has_dense else None,
                space.w_sparse if has_sparse else None,
                space.dense_kind, space.vocab_size)
    raise TypeError(f"unsupported space {type(space)}")


def kernel_beam_search(
    space,
    queries,
    corpus,
    index: GraphIndex,
    n_items: int,
    k: int = 10,
    ef: int = 64,
    hops: int | None = None,
    qb: int | None = None,
) -> TopK:
    """``beam_search`` through the fused Pallas traversal kernel.

    Entry-set scoring runs through the exact-scan kernels
    (``ops.mips_topk`` / ``ops.fused_topk`` over the gathered entry
    sub-corpus) so the whole search path is on-device; the hop loop is
    ``kernels.beam_topk.beam_search_pallas`` (per-hop neighbor gather +
    score + top-``ef`` merge fused, packed visited bitmask).  Same
    contract as ``beam_search`` — global top-k under the ANN
    measured-recall tier — with ``_reference_tail`` semantics when the
    beam cannot fill ``k`` reachable candidates.  Requires a dense /
    sparse-ip / fused-ip space with array components (the
    ``GraphANNBackend(kernel=True)`` capability gate routes everything
    else to the jnp path or the reference backend)."""
    from repro.kernels import ops

    (qd, q_dense, c_idx, c_val, c_dense, w_dense, w_sparse, dense_kind,
     vocab) = _components(space, queries, corpus)
    hops = hops if hops is not None else default_hops(n_items)

    # Coarse entry set, scored with the exact-scan kernels: local top-k0
    # over the gathered entry sub-corpus, mapped back to global ids.
    e = int(index.entry_ids.shape[0])
    entries = gather_items(corpus, index.entry_ids)
    k0 = min(ef, e)
    if isinstance(space, spaces_lib.DenseSpace):
        tk = ops.mips_topk(queries, entries, k0, tile_n=min(2048, e),
                           space=space.kind, n_valid=e)
    else:
        q_sparse = (queries if isinstance(space, spaces_lib.SparseSpace)
                    else queries.sparse if qd is not None else None)
        e_sparse = (entries if isinstance(space, spaces_lib.SparseSpace)
                    else entries.sparse if c_idx is not None else None)
        e_dense = (None if isinstance(space, spaces_lib.SparseSpace)
                   else entries.dense if c_dense is not None else None)
        tk = ops.fused_topk(q_sparse, q_dense, e_sparse, e_dense, vocab,
                            k0, w_dense=w_dense, w_sparse=w_sparse,
                            dense_kind=dense_kind, tile_n=min(1024, e),
                            n_valid=e)
    init_s = tk.scores
    init_ids = index.entry_ids[tk.indices]
    if k0 < ef:
        neg = float(jnp.finfo(jnp.float32).min)
        init_s = jnp.pad(init_s, ((0, 0), (0, ef - k0)),
                         constant_values=neg)
        init_ids = jnp.pad(init_ids, ((0, 0), (0, ef - k0)),
                           constant_values=n_items)

    return ops.beam_topk(qd, q_dense, init_s, init_ids, index.neighbors,
                         c_idx, c_val, c_dense, k, int(hops), int(n_items),
                         w_dense=w_dense, w_sparse=w_sparse,
                         dense_kind=dense_kind, qb=qb)
