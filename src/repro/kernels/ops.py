"""Jitted public wrappers for the Pallas kernels, with padding and
integration glue (so the retrieval core can call them as drop-ins).

No wrapper takes an ``interpret`` flag: ``kernels.platform`` compiles
the kernels with Mosaic when the program is lowered for a TPU and
interprets them on every other platform.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.brute_force import TopK
from repro.core.sparse import SparseVectors
from repro.kernels.beam_topk import (beam_search_pallas, mark_visited,
                                     visited_words)
from repro.kernels.fused_topk import fused_topk_pallas, query_terms
from repro.kernels.mips_topk import mips_topk_pallas
from repro.kernels.sparse_dense import fused_score_pallas


@functools.partial(jax.jit, static_argnames=("k", "tile_n", "space",
                                             "n_valid"))
def mips_topk(queries: jax.Array, corpus: jax.Array, k: int,
              tile_n: int = 2048, space: str = "ip",
              n_valid: int | None = None) -> TopK:
    """Kernelised exact k-NN over a dense corpus (pads N up to tile_n).
    ``n_valid`` masks trailing rows of an already-padded corpus; rows this
    wrapper pads on are always masked."""
    n = corpus.shape[0]
    n_valid = n if n_valid is None else min(n_valid, n)
    tile_n = min(tile_n, n)
    padded = (n + tile_n - 1) // tile_n * tile_n
    if padded != n:
        corpus = jnp.pad(corpus, ((0, padded - n), (0, 0)))
    s, i, _ = mips_topk_pallas(queries, corpus, k, tile_n=tile_n,
                               n_valid=n_valid, space=space)
    return TopK(s, i)


@functools.partial(jax.jit,
                   static_argnames=("vocab_size", "w_dense", "w_sparse",
                                    "tile_n"))
def fused_scores(q_sparse: SparseVectors, q_dense: jax.Array,
                 c_sparse: SparseVectors, c_dense: jax.Array,
                 vocab_size: int, w_dense: float = 1.0, w_sparse: float = 1.0,
                 tile_n: int = 1024) -> jax.Array:
    """Kernelised fused sparse+dense scoring [B, N] (FusedSpace drop-in)."""
    q_ids, q_w = query_terms(q_sparse.indices, q_sparse.values, vocab_size)
    n = c_dense.shape[0]
    tile = min(tile_n, n)
    padded = (n + tile - 1) // tile * tile
    ci, cv, cd = c_sparse.indices, c_sparse.values, c_dense
    if padded != n:
        ci = jnp.pad(ci, ((0, padded - n), (0, 0)), constant_values=vocab_size)
        cv = jnp.pad(cv, ((0, padded - n), (0, 0)))
        cd = jnp.pad(cd, ((0, padded - n), (0, 0)))
    out = fused_score_pallas(q_ids, q_w, q_dense, ci, cv, cd, w_dense,
                             w_sparse, tile_n=tile)
    return out[:, :n]


@functools.partial(jax.jit,
                   static_argnames=("vocab_size", "k", "w_dense", "w_sparse",
                                    "dense_kind", "tile_n", "n_valid"))
def fused_topk(q_sparse: SparseVectors | None, q_dense: jax.Array | None,
               c_sparse: SparseVectors | None, c_dense: jax.Array | None,
               vocab_size: int, k: int, w_dense: float | None = None,
               w_sparse: float | None = None, dense_kind: str = "ip",
               tile_n: int = 1024, n_valid: int | None = None) -> TopK:
    """One-pass fused score + select (``fused_topk_pallas`` drop-in for
    ``exact_topk`` over a ``FusedSpace``/``SparseSpace`` corpus), with the
    padding glue: pads N up to ``tile_n`` (padded COO rows get the trash
    id ``vocab_size``), lays the sparse queries out as the kernel's term
    operands (``fused_topk.query_terms``), and masks rows past
    ``n_valid``.  ``None``
    components are skipped; ``None`` weights leave a *single* component
    unscaled (SparseSpace semantics) — mixing two components requires
    both weights, pass 1.0 explicitly for an unweighted sum.  Requires
    ``k <= n_valid`` (the backend layer clamps and re-pads the
    degenerate tail — see ``core.backends``)."""
    has_sparse = c_sparse is not None and q_sparse is not None
    has_dense = c_dense is not None and q_dense is not None
    if not (has_sparse or has_dense):
        raise ValueError("fused_topk: no overlapping components to score")
    n = (c_dense if has_dense else c_sparse.indices).shape[0]
    n_valid = n if n_valid is None else min(n_valid, n)
    tile = min(tile_n, n)
    padded = (n + tile - 1) // tile * tile

    q_ids = q_w = None
    ci = cv = None
    cd = c_dense if has_dense else None
    qv = q_dense if has_dense else None
    if has_sparse:
        q_ids, q_w = query_terms(q_sparse.indices, q_sparse.values,
                                 vocab_size)
        ci, cv = c_sparse.indices, c_sparse.values
    if padded != n:
        if has_sparse:
            ci = jnp.pad(ci, ((0, padded - n), (0, 0)),
                         constant_values=vocab_size)
            cv = jnp.pad(cv, ((0, padded - n), (0, 0)))
        if has_dense:
            cd = jnp.pad(cd, ((0, padded - n), (0, 0)))
    s, i = fused_topk_pallas(q_ids, q_w, qv, ci, cv, cd, k, w_dense=w_dense,
                             w_sparse=w_sparse, tile_n=tile, n_valid=n_valid,
                             dense_kind=dense_kind)
    return TopK(s, i)


@functools.partial(jax.jit,
                   static_argnames=("k", "hops", "n_valid", "w_dense",
                                    "w_sparse", "dense_kind", "qb"))
def beam_topk(qdensified, q_dense, init_scores, init_ids, neighbors,
              c_idx, c_val, c_dense, k: int, hops: int, n_valid: int,
              w_dense=None, w_sparse=None, dense_kind: str = "ip",
              qb: int | None = None) -> TopK:
    """Kernelised graph-ANN traversal (``beam_topk.beam_search_pallas``
    drop-in for ``graph_ann.beam_search`` given a pre-scored entry
    beam): seeds the packed visited bitmask from the init beam, runs
    ``hops`` fused hops, and returns the beam's top ``k`` with
    ``_reference_tail`` semantics for sentinel slots (ids ``n_valid``,
    ``n_valid+1``, ... with ``-inf`` scores) so a starved beam degrades
    exactly like the exact backends' degenerate tails.

    ``init_scores``/``init_ids`` [B, ef] must be score-descending with
    sentinel slots (id >= ``n_valid``) carrying ``NEG`` — the layout
    ``graph_ann.kernel_beam_search`` builds from the entry set.
    Components and weights follow ``fused_topk``'s conventions."""
    b, ef = init_scores.shape
    if k > ef:
        raise ValueError(f"beam_topk: k={k} exceeds the beam width "
                         f"ef={ef}")
    visited = jnp.zeros((b, visited_words(n_valid)), jnp.uint32)
    visited = mark_visited(visited, init_ids, n_valid)
    beam_s, beam_i, _ = beam_search_pallas(
        qdensified, q_dense, init_scores, init_ids, visited, neighbors,
        c_idx, c_val, c_dense, n_valid=n_valid, hops=hops,
        w_dense=w_dense, w_sparse=w_sparse, dense_kind=dense_kind,
        qb=qb)
    # the beam is fold-sorted descending: its head IS the top-k
    s, i = beam_s[:, :k], beam_i[:, :k]
    sent = i >= n_valid
    i = jnp.where(sent, n_valid + jnp.cumsum(sent, axis=1) - 1, i)
    s = jnp.where(sent, -jnp.inf, s)
    return TopK(s, i.astype(jnp.int32))
