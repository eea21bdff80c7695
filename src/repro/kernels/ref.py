"""Pure-jnp oracles for the Pallas kernels (delegating to the system's own
library paths so kernel tests also pin the library semantics)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def mips_topk_ref(queries: jax.Array, corpus: jax.Array, k: int,
                  n_valid: int | None = None, space: str = "ip"):
    """Exact top-k via full score matrix + lax.top_k (the library's
    ``spaces.dense_scores`` arithmetic)."""
    from repro.core.spaces import dense_scores

    s = dense_scores(space, queries.astype(jnp.float32),
                     corpus.astype(jnp.float32))
    if n_valid is not None:
        mask = jnp.arange(corpus.shape[0])[None, :] < n_valid
        s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
    vals, idx = jax.lax.top_k(s, k)
    return vals, idx.astype(jnp.int32)


def fused_score_ref(qdensified: jax.Array, q_dense: jax.Array,
                    c_idx: jax.Array, c_val: jax.Array, c_dense: jax.Array,
                    w_dense: float, w_sparse: float):
    from repro.core.spaces import HIGHEST, dense_scores, weighted_mix

    dense = dense_scores("ip", q_dense, c_dense)
    picked = qdensified.astype(jnp.float32)[:, c_idx]           # [B, N, NNZ]
    sparse = jnp.einsum("bnk,nk->bn", picked, c_val.astype(jnp.float32),
                        precision=HIGHEST)
    return weighted_mix([dense, sparse], [w_dense, w_sparse])


def fused_topk_ref(q_sparse, q_dense, c_sparse, c_dense, vocab_size: int,
                   k: int, w_dense=None, w_sparse=None,
                   dense_kind: str = "ip", n_valid: int | None = None):
    """Oracle for ``fused_topk_pallas``: scores through the system's own
    library paths (``spaces.dense_scores`` + ``sparse.
    sparse_inner_qbatch_docs``, the exact arithmetic ``FusedSpace.
    score_batch`` runs), selection via ``lax.top_k`` — so kernel tests pin
    the library semantics (within the f32 score bound).  ``None`` weights leave a
    component unscaled (SparseSpace semantics); ``None`` components are
    skipped."""
    from repro.core import sparse as sp
    from repro.core.spaces import dense_scores, weighted_mix

    parts, weights = [], []
    if q_dense is not None and c_dense is not None:
        parts.append(dense_scores(dense_kind, q_dense.astype(jnp.float32),
                                  c_dense.astype(jnp.float32)))
        weights.append(w_dense)
    if q_sparse is not None and c_sparse is not None:
        parts.append(sp.sparse_inner_qbatch_docs(q_sparse, c_sparse,
                                                 vocab_size))
        weights.append(w_sparse)
    if not parts:
        raise ValueError("fused_topk_ref: no components to score")
    if all(w is None for w in weights) and len(parts) > 1:
        raise ValueError("mixing two components requires w_dense and "
                         "w_sparse (pass 1.0 explicitly for an unweighted "
                         "sum)")
    total = (weighted_mix(parts, weights)
             if any(w is not None for w in weights) else parts[0])
    if n_valid is not None:
        mask = jnp.arange(total.shape[1])[None, :] < n_valid
        total = jnp.where(mask, total, -jnp.inf)
    vals, idx = jax.lax.top_k(total, k)
    return vals, idx.astype(jnp.int32)


def beam_hop_ref(qdensified, q_dense, beam_s, beam_i, visited, neighbors,
                 c_idx, c_val, c_dense, *, n_valid: int,
                 w_dense=None, w_sparse=None, dense_kind: str = "ip"):
    """Oracle for one ``beam_topk`` hop, restating the traversal spec
    with independent machinery: the visited set is an *unpacked*
    ``bool[B, N]`` table (not a bitmask), in-hop dedup is a C x C
    strictly-lower-triangular equality (any earlier occurrence of the
    same raw id, valid or not, kills a candidate — matching the kernel's
    stable-sort formulation), and the beam merge is ``lax.top_k`` over
    the same ``[beam, candidates]`` concatenation the kernel folds
    (``_fold_topk`` == ``lax.top_k`` including ties, which both break
    toward the lower slot).  Scoring reuses the library's einsum
    groupings so parity with the kernel is bitwise.

    Returns ``(beam_s, beam_i, visited)`` with the new ``bool[B, N]``
    table (only *scored* candidates marked)."""
    from repro.kernels.mips_topk import NEG

    b, ef = beam_s.shape
    n = n_valid
    c = ef * neighbors.shape[1]

    src_ok = (beam_i >= 0) & (beam_i < n)
    safe_f = jnp.clip(beam_i, 0, n - 1)
    cand = neighbors[safe_f].reshape(b, c)
    cand_ok = (jnp.repeat(src_ok, neighbors.shape[1], axis=1)
               & (cand >= 0) & (cand < n))
    safe_c = jnp.clip(cand, 0, n - 1)
    seen = jax.vmap(lambda v, ids: v[ids])(visited, safe_c) & cand_ok

    eq = cand[:, :, None] == cand[:, None, :]               # [B, C, C]
    earlier = jnp.tril(jnp.ones((c, c), jnp.bool_), k=-1)   # j < i
    dup = jnp.any(eq & earlier[None, :, :], axis=2)

    valid = cand_ok & ~seen & ~dup

    parts, weights = [], []
    if c_dense is not None:
        q = q_dense.astype(jnp.float32)
        items = c_dense[safe_c].astype(jnp.float32)         # [B, C, Dd]
        dense = jnp.einsum("qd,qcd->qc", q, items,
                           preferred_element_type=jnp.float32)
        if dense_kind == "l2":
            q2 = jnp.einsum("qd,qd->q", q, q)[:, None]
            c2 = jnp.einsum("qcd,qcd->qc", items, items)
            dense = -(q2 + c2 - 2.0 * dense)
        parts.append(dense)
        weights.append(w_dense)
    if c_idx is not None:
        qd = qdensified.astype(jnp.float32)
        idx = c_idx[safe_c]                                 # [B, C, NNZ]
        val = c_val[safe_c].astype(jnp.float32)
        picked = jax.vmap(lambda qrow, irow: qrow[irow])(qd, idx)
        parts.append(jnp.einsum("qck,qck->qc", picked, val))
        weights.append(w_sparse)
    if not parts:
        raise ValueError("beam_hop_ref: no components to score")
    if any(w is not None for w in weights):
        total = jnp.einsum("...c,c->...", jnp.stack(parts, axis=-1),
                           jnp.asarray(weights, jnp.float32))
    else:
        total = parts[0]

    s = jnp.where(valid, total, NEG)
    cand_ids = jnp.where(valid, cand, n)

    cat_s = jnp.concatenate([beam_s, s], axis=1)
    cat_i = jnp.concatenate([beam_i, cand_ids], axis=1)
    new_s, pos = jax.lax.top_k(cat_s, ef)
    new_i = jnp.take_along_axis(cat_i, pos, axis=1)

    new_visited = jax.vmap(lambda v, ids, ok: v.at[ids].max(ok))(
        visited, safe_c, valid)
    return new_s, new_i.astype(jnp.int32), new_visited
