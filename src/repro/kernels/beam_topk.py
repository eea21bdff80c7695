"""Pallas beam-traversal kernel: one graph-ANN hop — neighbor gather,
visited-bitmask test, in-hop dedup, scoring and the running top-``ef``
beam merge — fused in a single on-device pass.

This is the kernel that makes ``graph_ann`` sub-linear *in practice*:
``core/graph_ann.py``'s jnp beam search keeps a ``bool[B, N]`` visited
table and an HBM-resident frontier, so every hop touches O(N) state and
the exact Pallas scan wins at every corpus size.  Here a hop touches
only O(ef·R) corpus rows:

  * the frontier is the beam itself — ``beam_ids: i32[B, ef]`` carried
    in VMEM alongside ``beam_scores: f32[B, ef]``;
  * the fixed-degree adjacency ``neighbors: i32[N, R]`` and the corpus
    components stay unblocked (``memory_space=ANY``) and are touched
    only through data-dependent row gathers — the first kernel in this
    tree whose memory access pattern is decided at run time;
  * the visited set is a packed ``uint32[B, ceil(N/32)]`` bitmask,
    *read* inside the kernel (gather + shift) but *written* outside it:
    the kernel emits per-candidate ``(word, addend)`` mark-deltas and
    the ``lax.scan`` hop loop (``beam_search_pallas``) commits them with
    one scatter-add — valid candidates are unique and unseen, so add
    and bitwise-or coincide.  Writing the mask from inside the kernel
    would thread the full ``[B, W]`` buffer through every grid step
    (a full copy per step in interpret mode; a VMEM round-trip on TPU);
  * scoring mirrors ``fused_topk.py`` component for component (dense
    ip/l2 einsum groupings, per-nnz-column sparse gather, the one-einsum
    weighted mix), and the beam merge reuses ``fused_topk``'s running
    top-k fold (``mips_topk._fold_topk``) so dense, sparse and fused
    spaces all traverse on-device with the same selection semantics
    (ties toward the lower concatenation slot, like ``lax.top_k``).

Candidate semantics (the oracle in ``ref.beam_hop_ref`` re-states these
independently):

  * a candidate is *valid* iff its source beam slot holds a real id
    (< n), its own id is in ``[0, n)``, its visited bit is clear, and it
    is the first occurrence of that id in the hop's candidate list
    (first-occurrence-wins dedup over the raw ``[B, ef·R]`` gather);
  * invalid candidates score ``NEG`` and their ids are replaced by the
    sentinel ``n`` before the merge, so the beam only ever holds ids
    that were actually scored (or the sentinel) — sentinels can then be
    rewritten to ``_reference_tail`` semantics after the last hop;
  * only valid candidates are marked visited, so the mask invariant is
    exactly "bit set iff the node was scored or seeded the beam" — the
    never-re-scored property the tests assert.

VMEM budget per grid step (``QB`` = queries per step, ``C = ef·R``):
the beam carry ``2·QB·ef``, the candidate block ``QB·C`` ids + scores +
mark-deltas, and the gathered rows ``QB·C·D`` (dense) / ``QB·C·NNZ``
(COO) — the gathered corpus block dominates, which is why
``check_beam_budget`` caps ``ef·R`` (``MAX_BEAM_CANDIDATES``) instead of
letting a large ``ef`` silently exceed VMEM.  The ``[B, W]`` bitmask
itself never enters VMEM as a block.  On CPU (interpret mode) ``QB = B``
— one grid step per hop; on TPU ``QB`` tiles the batch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mips_topk import NEG
from repro.kernels.platform import interpreted, pallas_call

# Cap on the per-hop candidate block C = ef * R (see the VMEM budget
# note above): at f32 x D=128 this bounds the gathered block near
# 16 MiB per query tile — beyond it the kernel refuses instead of
# compiling something that cannot fit VMEM on any TPU generation.
MAX_BEAM_CANDIDATES = 32768


# What the TPU v5e compiler (jax 0.9.0, libtpu 0.0.34) says of the hop
# kernel: it reads the visited bitmask, the adjacency and the corpus —
# refs left in HBM (memory_space=ANY) — with plain indexed loads, where
# Mosaic needs explicit async copies into VMEM.
TPU_REFUSAL = ("Loads are only allowed on VMEM and SMEM references. ANY "
               "memory space can only be accessed using async_copy.")


def check_beam_platform():
    """Refuse the kernel traversal where it would be compiled for a TPU,
    with the compiler's reason, instead of failing deep in lowering."""
    if not interpreted():
        raise NotImplementedError(
            "the graph-ANN beam kernel does not compile for TPU (Mosaic: "
            f"{TPU_REFUSAL!r}); serve graph_ann with kernel=False")


def visited_words(n: int) -> int:
    """uint32 words per query row in the packed visited bitmask."""
    return (n + 31) // 32


def check_beam_budget(ef: int, r: int):
    """Refuse candidate blocks that cannot fit the VMEM budget."""
    if ef * r > MAX_BEAM_CANDIDATES:
        raise ValueError(
            f"beam candidate block ef*R = {ef}*{r} = {ef * r} exceeds the "
            f"kernel budget {MAX_BEAM_CANDIDATES} (the gathered corpus "
            "block must stay VMEM-resident); lower ef or the graph degree")


def mark_visited(visited: jax.Array, ids: jax.Array, n_valid: int) -> jax.Array:
    """Set the bits of ``ids`` (i32[B, K], sentinel entries >= n_valid
    ignored) in the packed bitmask ``visited`` (u32[B, W]).  Duplicate
    ids within a row are tolerated (or-semantics), so this serves the
    init-beam marking where top-k entry ids are distinct by construction
    but callers need not prove it."""
    b, k = ids.shape
    rows = jnp.arange(b)

    def body(j, v):
        col = ids[:, j]
        ok = (col >= 0) & (col < n_valid)
        safe = jnp.clip(col, 0, n_valid - 1)
        w = safe >> 5
        bit = jnp.where(ok, jnp.uint32(1) << (safe & 31).astype(jnp.uint32),
                        jnp.uint32(0))
        return v.at[rows, w].set(v[rows, w] | bit)

    return jax.lax.fori_loop(0, k, body, visited)


def unpack_visited(visited: jax.Array, n: int) -> jax.Array:
    """bool[B, N] view of the packed bitmask (test/oracle helper)."""
    b, w = visited.shape
    bits = (visited[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(b, w * 32)[:, :n].astype(bool)


def _fold_topk(scores_row: jax.Array, ids_row: jax.Array, k: int):
    """``mips_topk._fold_topk`` with ``-inf`` masking instead of ``NEG``.

    The exact kernels never fold past their valid count (the backend
    clamps ``k <= n_valid``), so masking picked slots back to ``NEG``
    is safe there.  A *starved* beam does: when fewer than ``ef``
    reachable candidates exist, every remaining slot ties at ``NEG``
    and NEG-masking makes ``argmax`` re-pick slot 0's id each round,
    while the oracle's ``lax.top_k`` advances through distinct
    positions (emitting the sentinel ids those slots hold).  Masking
    strictly below every representable score keeps the fold bitwise
    equal to ``lax.top_k`` — ties, exhaustion and all."""
    out_s, out_i = [], []
    cur = scores_row
    for _ in range(k):
        mx = jnp.max(cur, axis=1)
        am = jnp.argmax(cur, axis=1)
        out_s.append(mx)
        out_i.append(jnp.take_along_axis(ids_row, am[:, None], axis=1)[:, 0])
        cur = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, cur.shape, 1) == am[:, None],
            -jnp.inf, cur)
    return jnp.stack(out_s, axis=1), jnp.stack(out_i, axis=1)


def _hop_kernel(*refs, n: int, ef: int, r: int, qb: int, nnz: int,
                weighted: bool, dense_kind: str, has_dense: bool,
                has_sparse: bool):
    it = iter(refs)
    w_ref = next(it) if weighted else None          # [1, C_parts] mix weights
    qd_ref = next(it) if has_sparse else None       # [QB, V+1] densified
    qdense_ref = next(it) if has_dense else None    # [QB, Dd]
    bs_ref = next(it)                               # [QB, ef] beam scores
    bi_ref = next(it)                               # [QB, ef] beam ids
    vis_ref = next(it)                              # ANY u32[B, W]
    nbr_ref = next(it)                              # ANY i32[N, R]
    cidx_ref = next(it) if has_sparse else None     # ANY i32[N, NNZ]
    cval_ref = next(it) if has_sparse else None     # ANY [N, NNZ]
    cdense_ref = next(it) if has_dense else None    # ANY [N, Dd]
    obs_ref, obi_ref, ow_ref, oa_ref = it

    g = pl.program_id(0)
    beam_s = bs_ref[...]
    beam_i = bi_ref[...]
    v = vis_ref[pl.dslice(g * qb, qb)]              # [QB, W] read-only
    c = ef * r

    # Frontier = the whole beam; sentinel slots gather a real row's
    # neighbors but src_ok masks every candidate they produce.
    src_ok = (beam_i >= 0) & (beam_i < n)
    safe_f = jnp.clip(beam_i, 0, n - 1)
    cand = nbr_ref[safe_f].reshape(qb, c)           # [QB, ef, R] -> [QB, C]
    cand_ok = (jnp.broadcast_to(src_ok[:, :, None], (qb, ef, r))
               .reshape(qb, c) & (cand >= 0) & (cand < n))
    safe_c = jnp.clip(cand, 0, n - 1)

    # Visited test against the packed mask.
    words = safe_c >> 5
    bits = (safe_c & 31).astype(jnp.uint32)
    seen = (jnp.take_along_axis(v, words, axis=1) >> bits) & jnp.uint32(1)

    # First-occurrence-wins dedup over the raw candidate list: stable
    # argsort groups equal ids, adjacent equality marks all but the
    # sorted-first (== lowest original position), scattered back.
    order = jnp.argsort(cand, axis=1, stable=True)
    sorted_cand = jnp.take_along_axis(cand, order, axis=1)
    dup_sorted = jnp.concatenate(
        [jnp.zeros((qb, 1), jnp.bool_),
         sorted_cand[:, 1:] == sorted_cand[:, :-1]], axis=1)
    dup = (jnp.zeros((qb, c), jnp.bool_)
           .at[jnp.arange(qb)[:, None], order].set(dup_sorted))

    valid = cand_ok & (seen == 0) & ~dup
    addend = jnp.where(valid, jnp.uint32(1) << bits, jnp.uint32(0))

    # Score valid candidates — fused_topk's arithmetic per component.
    parts = []
    if has_dense:
        q = qdense_ref[...].astype(jnp.float32)               # [QB, Dd]
        gathered = cdense_ref[safe_c].astype(jnp.float32)     # [QB, C, Dd]
        dense = jnp.einsum("qd,qcd->qc", q, gathered,
                           preferred_element_type=jnp.float32)
        if dense_kind == "l2":
            # exact grouping of spaces.dense_scores — see mips_topk.py
            q2 = jnp.einsum("qd,qd->q", q, q)[:, None]
            c2 = jnp.einsum("qcd,qcd->qc", gathered, gathered)
            dense = -(q2 + c2 - 2.0 * dense)
        parts.append(dense)
    if has_sparse:
        qd = qd_ref[...].astype(jnp.float32)                  # [QB, V+1]
        idx = cidx_ref[safe_c]                                # [QB, C, NNZ]
        val = cval_ref[safe_c].astype(jnp.float32)
        if nnz:
            # one gather per static nnz column, reduced with the same
            # einsum contraction as sparse_inner_qbatch_docs
            picked = jnp.stack(
                [jnp.take_along_axis(qd, idx[:, :, j], axis=1)
                 for j in range(nnz)], axis=-1)               # [QB, C, NNZ]
            sparse = jnp.einsum("qck,qck->qc", picked, val)
        else:
            sparse = jnp.zeros((qb, c), jnp.float32)
        parts.append(sparse)
    if weighted:
        # the library's exact mixing arithmetic (spaces.weighted_mix)
        total = jnp.einsum("...c,c->...", jnp.stack(parts, axis=-1),
                           w_ref[...][0])
    else:
        total = parts[0]

    s = jnp.where(valid, total, NEG)
    cand_ids = jnp.where(valid, cand, n)      # beam holds scored ids only

    cat_s = jnp.concatenate([beam_s, s], axis=1)
    cat_i = jnp.concatenate([beam_i, cand_ids], axis=1)
    new_s, new_i = _fold_topk(cat_s, cat_i, ef)

    obs_ref[...] = new_s
    obi_ref[...] = new_i
    ow_ref[...] = words
    oa_ref[...] = addend


def beam_hop_pallas(qdensified, q_dense, beam_s, beam_i, visited, neighbors,
                    c_idx, c_val, c_dense, *, n_valid: int,
                    w_dense=None, w_sparse=None, dense_kind: str = "ip",
                    qb: int | None = None):
    """One fused hop: ``(beam_s, beam_i, words, addend)``.

    ``beam_s/beam_i`` [B, ef] are the running beam (descending, sentinel
    slots carry id ``n_valid`` and score ``NEG``); ``visited`` is the
    packed u32[B, ceil(n/32)] bitmask (read-only here — commit the
    returned ``(words, addend)`` deltas with
    ``visited.at[rows, words].add(addend)``); ``neighbors`` i32[N, R].
    Corpus components follow ``fused_topk_pallas``'s conventions:
    ``qdensified`` [B, V+1] (zero trash column) + ``c_idx``/``c_val``
    [N, NNZ] for the sparse part, ``q_dense`` [B, Dd] + ``c_dense``
    [N, Dd] for the dense part; ``None`` weights leave a *single*
    component unscaled, mixing two components requires both weights."""
    has_dense = c_dense is not None
    has_sparse = c_idx is not None
    if not (has_dense or has_sparse):
        raise ValueError("beam_hop_pallas: no components to score")
    if has_sparse and dense_kind != "ip":
        raise ValueError("beam_hop_pallas: sparse/fused traversal supports "
                         "dense_kind='ip' only (like fused_topk_pallas)")
    weights = ([w_dense] if has_dense else []) + \
              ([w_sparse] if has_sparse else [])
    weighted = any(w is not None for w in weights)
    if weighted and any(w is None for w in weights):
        raise ValueError("give weights for all present components or none")
    if not weighted and len(weights) > 1:
        raise ValueError("mixing two components requires w_dense and "
                         "w_sparse (pass 1.0 explicitly for an unweighted "
                         "sum)")
    b, ef = beam_s.shape
    r = neighbors.shape[1]
    check_beam_budget(ef, r)
    qb = b if qb is None else qb
    if b % qb != 0:
        raise ValueError(f"query block {qb} must divide batch {b}")
    c = ef * r
    nnz = c_idx.shape[1] if has_sparse else 0

    in_specs, operands = [], []
    if weighted:
        c_parts = len(weights)
        in_specs.append(pl.BlockSpec((1, c_parts), lambda g: (0, 0)))
        operands.append(jnp.asarray([weights], jnp.float32))
    if has_sparse:
        vp1 = qdensified.shape[1]
        in_specs.append(pl.BlockSpec((qb, vp1), lambda g: (g, 0)))
        operands.append(qdensified)
    if has_dense:
        dd = q_dense.shape[1]
        in_specs.append(pl.BlockSpec((qb, dd), lambda g: (g, 0)))
        operands.append(q_dense)
    in_specs += [pl.BlockSpec((qb, ef), lambda g: (g, 0)),
                 pl.BlockSpec((qb, ef), lambda g: (g, 0)),
                 pl.BlockSpec(memory_space=pl.ANY),    # visited
                 pl.BlockSpec(memory_space=pl.ANY)]    # neighbors
    operands += [beam_s, beam_i, visited, neighbors]
    if has_sparse:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        operands += [c_idx, c_val]
    if has_dense:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(c_dense)

    kernel = functools.partial(
        _hop_kernel, n=n_valid, ef=ef, r=r, qb=qb, nnz=nnz,
        weighted=weighted, dense_kind=dense_kind,
        has_dense=has_dense, has_sparse=has_sparse)
    return pallas_call(
        kernel,
        grid=(b // qb,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((qb, ef), lambda g: (g, 0)),
            pl.BlockSpec((qb, ef), lambda g: (g, 0)),
            pl.BlockSpec((qb, c), lambda g: (g, 0)),
            pl.BlockSpec((qb, c), lambda g: (g, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, ef), jnp.float32),
            jax.ShapeDtypeStruct((b, ef), jnp.int32),
            jax.ShapeDtypeStruct((b, c), jnp.int32),
            jax.ShapeDtypeStruct((b, c), jnp.uint32),
        ],
    )(*operands)


def beam_search_pallas(qdensified, q_dense, beam_s, beam_i, visited,
                       neighbors, c_idx, c_val, c_dense, *, n_valid: int,
                       hops: int, w_dense=None, w_sparse=None,
                       dense_kind: str = "ip", qb: int | None = None):
    """``hops`` fused hops under a ``lax.scan``: the beam and the packed
    visited bitmask are the scan carry; each step runs the hop kernel
    and commits its mark-deltas (valid candidates are unique and unseen,
    so the scatter-add is an or).  Returns the final
    ``(beam_s, beam_i, visited)``."""
    b = beam_s.shape[0]
    rows = jnp.arange(b)[:, None]

    def hop(carry, _):
        bs, bi, v = carry
        bs, bi, words, addend = beam_hop_pallas(
            qdensified, q_dense, bs, bi, v, neighbors, c_idx, c_val,
            c_dense, n_valid=n_valid, w_dense=w_dense, w_sparse=w_sparse,
            dense_kind=dense_kind, qb=qb)
        v = v.at[rows, words].add(addend, mode="drop")
        return (bs, bi, v), None

    (beam_s, beam_i, visited), _ = jax.lax.scan(
        hop, (beam_s, beam_i, visited), None, length=int(hops))
    return beam_s, beam_i, visited
