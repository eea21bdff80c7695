"""Fused-space Pallas kernel: mixed dense+sparse scoring AND top-k
selection in one on-device pass — the paper's headline claim ("efficiently
retrieve mixed dense and sparse representations with weights learned from
training data") executed as a single corpus scan.

Per grid step over corpus tiles:

    score[b, n] = w_dense  * dense_kind(q_dense[b], c_dense[n])     (MXU)
                + w_sparse * sum_k q[b](c_idx[n, k]) * c_val[n, k]  (VPU+MXU)
    fold the [B, TILE_N] tile scores into the running top-k carried in
    VMEM scratch (K rounds of max/first-position/mask, from
    kernels/mips_topk.py)

so the [B, N] score matrix never exists anywhere — not in HBM (as in
``kernels/sparse_dense.py`` + host ``lax.top_k``) and not on the host.
This beats the two baselines the paper positions against: FAISS's fused
scan+select is dense-only, Lucene's inverted scan is sparse-only; here
the mixing happens *inside* the kernel, with the component weights as
compile-time constants.

The sparse part is a term match, not a gather.  Queries are few and
short, so they stay in padded COO: their ``B*T`` term ids form one
column, and their weights a block-diagonal ``[B, B*T]`` matrix.  Per
tile, each of the NNZ document slots is compared with every query term
and its value accumulated where the ids match (``hits[(b, t), n]``,
VPU); one MXU contraction with the query weights then gives
``sum_t q_w[b, t] * hits[(b, t), n]`` — the sparse inner product.  A
gather of a densified ``[B, V+1]`` query table at per-lane indices would
be the direct form, but Mosaic refuses it, and the table would have to
sit in VMEM; the match costs ``B*T*NNZ`` compares per row and holds no
vocabulary-sized state.  Query slots whose id is out of ``[0, V)`` (the
``V`` padding id) carry the id ``-1``, which no document slot holds.

Either component may be absent (static ``has_dense`` / ``has_sparse``):
the same kernel serves pure-dense fused vectors, pure-sparse fused
vectors, and plain ``SparseSpace`` corpora (a ``None`` weight leaves a
single component unscaled, as the library path does; mixing two
components always takes explicit weights, as ``FusedSpace`` does).
Selection breaks ties toward the lower corpus row id, like
``lax.top_k``.

Precision: operands upcast to f32 at the top of every tile and both
contractions run at ``Precision.HIGHEST``, so scores are f32 whatever the
residency dtype.  Against the library path (``FusedSpace.score_batch``)
they agree within the f32 score bound of ``tests/_precision.py``, not
bit for bit: the summation order differs (``tests/test_fused_backend.py``).

Compiled by Mosaic on a TPU and interpreted elsewhere
(``kernels.platform``); ``tests/test_tpu_compile.py`` compiles it for a
described v5e at vocabulary 30,522 and nnz 64.  TILE_N and the dense D
should be multiples of 128; ``core.backends.auto_tile_n`` budgets the
tile's VMEM working set, ``hits`` included.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mips_topk import NEG, _fold_topk, score_tile
from repro.kernels.platform import pallas_call


def query_terms(q_idx: jax.Array, q_val: jax.Array, vocab_size: int):
    """Padded-COO queries [B, T] -> the kernel's query operands: term ids
    as one column ``i32[B*T, 1]`` (out-of-vocabulary and padding slots
    become ``-1``, which matches nothing) and block-diagonal weights
    ``f32[B, B*T]``."""
    b, t = q_idx.shape
    ok = (q_idx >= 0) & (q_idx < vocab_size)
    ids = jnp.where(ok, q_idx, -1).astype(jnp.int32).reshape(b * t, 1)
    val = jnp.where(ok, q_val.astype(jnp.float32), 0.0)
    w = (jnp.eye(b, dtype=jnp.float32)[:, :, None] * val[None, :, :])
    return ids, w.reshape(b, b * t)


def sparse_scratch(bt: int, nnz: int, tile_n: int):
    """VMEM scratch :func:`sparse_tile` needs: the tile's COO ids and
    values transposed to ``[NNZ, TILE_N]``, and the ``[B*T, TILE_N]``
    hit accumulator."""
    return [pltpu.VMEM((nnz, tile_n), jnp.int32),
            pltpu.VMEM((nnz, tile_n), jnp.float32),
            pltpu.VMEM((bt, tile_n), jnp.float32)]


def sparse_tile(q_ids: jax.Array, q_w: jax.Array, c_idx: jax.Array,
                c_val: jax.Array, idx_scr, val_scr, hits_scr) -> jax.Array:
    """Sparse inner products of a corpus tile inside a kernel: ``q_ids``
    i32[B*T, 1], ``q_w`` f32[B, B*T] (from :func:`query_terms`),
    ``c_idx``/``c_val`` [TILE_N, NNZ] -> f32[B, TILE_N].

    The tile is transposed once so that each COO slot is a lane row, and
    the slots are visited by a loop rather than unrolled, which keeps
    the working set to one ``hits`` block."""
    idx_scr[...] = c_idx.T
    val_scr[...] = c_val.astype(jnp.float32).T
    hits_scr[...] = jnp.zeros_like(hits_scr)

    def slot(j, carry):
        ids = idx_scr[pl.ds(j, 1), :]                        # [1, TILE_N]
        vals = val_scr[pl.ds(j, 1), :]
        hits_scr[...] += jnp.where(ids == q_ids, vals, 0.0)  # [B*T, TILE_N]
        return carry

    jax.lax.fori_loop(0, c_idx.shape[1], slot, 0)
    return jax.lax.dot_general(q_w, hits_scr[...], (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(*refs, k: int, tile_n: int, n_tiles: int, n_valid: int,
            w_dense, w_sparse, dense_kind: str, has_dense: bool,
            has_sparse: bool):
    it = iter(refs)
    qids_ref = next(it) if has_sparse else None      # [B*T, 1] i32
    qw_ref = next(it) if has_sparse else None        # [B, B*T]
    qdense_ref = next(it) if has_dense else None     # [B, Dd]
    cidx_ref = next(it) if has_sparse else None      # [TILE_N, NNZ] i32
    cval_ref = next(it) if has_sparse else None      # [TILE_N, NNZ]
    cdense_ref = next(it) if has_dense else None     # [TILE_N, Dd]
    out_s_ref, out_i_ref, s_scr, i_scr, *sparse_scr = it

    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = jnp.full_like(s_scr, NEG)
        i_scr[...] = jnp.zeros_like(i_scr)

    total = None
    if has_dense:
        total = score_tile(qdense_ref[...], cdense_ref[...], dense_kind)
        if w_dense is not None:
            total = w_dense * total
    if has_sparse:
        sparse = sparse_tile(qids_ref[...], qw_ref[...], cidx_ref[...],
                             cval_ref[...], *sparse_scr)
        if w_sparse is not None:
            sparse = w_sparse * sparse
        total = sparse if total is None else total + sparse

    base = t * tile_n
    ids = base + jax.lax.broadcasted_iota(jnp.int32, total.shape, 1)
    s = jnp.where(ids < n_valid, total, NEG)

    cat_s = jnp.concatenate([s_scr[...], s], axis=1)
    cat_i = jnp.concatenate([i_scr[...], ids], axis=1)
    new_s, new_i = _fold_topk(cat_s, cat_i, k)
    s_scr[...] = new_s
    i_scr[...] = new_i

    @pl.when(t == n_tiles - 1)
    def _emit():
        out_s_ref[...] = s_scr[...]
        out_i_ref[...] = i_scr[...]


def fused_topk_pallas(q_ids, q_w, q_dense, c_idx, c_val, c_dense, k: int,
                      w_dense=None, w_sparse=None, tile_n: int = 1024,
                      n_valid: int | None = None, dense_kind: str = "ip"):
    """One-pass fused score + top-k: (scores [B, K], ids [B, K]) descending.

    ``q_ids``/``q_w`` (from :func:`query_terms`) + ``c_idx``/``c_val``
    [N, NNZ] form the sparse component; ``q_dense`` [B, Dd] + ``c_dense``
    [N, Dd] the dense one.  Pass ``None`` for an absent component (at
    least one required).  ``w_dense``/``w_sparse``: static mixing weights;
    ``None`` leaves a *single* component unscaled (SparseSpace
    semantics); mixing two components requires both weights.
    N must be a multiple of ``tile_n`` and ``k <= n_valid <= N`` — the
    padding/clamping glue lives in ``ops.fused_topk``.
    """
    has_dense = c_dense is not None
    has_sparse = c_idx is not None
    if not (has_dense or has_sparse):
        raise ValueError("fused_topk_pallas: no components to score")
    weights = ([w_dense] if has_dense else []) + \
              ([w_sparse] if has_sparse else [])
    weighted = any(w is not None for w in weights)
    if weighted and any(w is None for w in weights):
        raise ValueError("give weights for all present components or none")
    if not weighted and len(weights) > 1:
        # no unscaled multi-component path exists in the library either:
        # FusedSpace always mixes with weights, SparseSpace is one part
        raise ValueError("mixing two components requires w_dense and "
                         "w_sparse (pass 1.0 explicitly for an unweighted "
                         "sum)")
    n = (c_dense if has_dense else c_idx).shape[0]
    b = (q_dense if has_dense else q_w).shape[0]
    assert n % tile_n == 0, (n, tile_n)
    n_tiles = n // tile_n
    n_valid = n if n_valid is None else n_valid

    in_specs, operands = [], []
    if has_sparse:
        bt = q_ids.shape[0]
        in_specs.append(pl.BlockSpec((bt, 1), lambda t: (0, 0)))
        in_specs.append(pl.BlockSpec((b, bt), lambda t: (0, 0)))
        operands.extend([q_ids, q_w])                # query terms resident
    if has_dense:
        dd = q_dense.shape[1]
        in_specs.append(pl.BlockSpec((b, dd), lambda t: (0, 0)))
        operands.append(q_dense)                     # queries resident
    if has_sparse:
        nnz = c_idx.shape[1]
        in_specs.append(pl.BlockSpec((tile_n, nnz), lambda t: (t, 0)))
        in_specs.append(pl.BlockSpec((tile_n, nnz), lambda t: (t, 0)))
        operands.extend([c_idx, c_val])              # COO tiles streamed
    if has_dense:
        in_specs.append(pl.BlockSpec((tile_n, dd), lambda t: (t, 0)))
        operands.append(c_dense)                     # dense tiles streamed

    kernel = functools.partial(
        _kernel, k=k, tile_n=tile_n, n_tiles=n_tiles, n_valid=n_valid,
        w_dense=w_dense if has_dense else None,
        w_sparse=w_sparse if has_sparse else None,
        dense_kind=dense_kind, has_dense=has_dense, has_sparse=has_sparse)
    out_s, out_i = pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((b, k), lambda t: (0, 0)),
            pl.BlockSpec((b, k), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, k), jnp.float32),
            pltpu.VMEM((b, k), jnp.int32),
        ] + (sparse_scratch(bt, nnz, tile_n) if has_sparse else []),
    )(*operands)
    return out_s, out_i
