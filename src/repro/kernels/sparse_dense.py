"""Fused sparse+dense scoring Pallas kernel — the paper's NOVEL mixed
representation, scored in one pass.

score[b, n] = w_dense * <q_dense[b], c_dense[n]>
            + w_sparse * sum_k q[b](c_idx[n, k]) * c_val[n, k]

The dense component is an MXU matmul over the streamed corpus tile; the
sparse component matches the tile's padded-COO term ids against the
queries' terms and contracts the hits with the query weights
(``fused_topk.sparse_tile`` — the same tile arithmetic as the one-pass
score+select kernel in ``fused_topk.py``, which explains why it is a
match and not a gather).  One kernel pass replaces NMSLIB's two
per-component scans + host-side mixing.

Compiled by Mosaic on a TPU and interpreted elsewhere
(``kernels.platform``).  Validated against ``ref.fused_score_ref``
(tests/test_kernels.py) under the f32 score bound of
``tests/_precision.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_topk import sparse_scratch, sparse_tile
from repro.kernels.mips_topk import score_tile
from repro.kernels.platform import pallas_call


def _kernel(qids_ref, qw_ref, qdense_ref, cidx_ref, cval_ref, cdense_ref,
            out_ref, *sparse_scr, w_dense: float, w_sparse: float):
    dense = score_tile(qdense_ref[...], cdense_ref[...], "ip")
    sparse = sparse_tile(qids_ref[...], qw_ref[...], cidx_ref[...],
                         cval_ref[...], *sparse_scr)
    out_ref[...] = w_dense * dense + w_sparse * sparse


def fused_score_pallas(q_ids: jax.Array, q_w: jax.Array, q_dense: jax.Array,
                       c_idx: jax.Array, c_val: jax.Array,
                       c_dense: jax.Array, w_dense: float, w_sparse: float,
                       tile_n: int = 1024):
    """q_ids i32[B*T, 1] + q_w [B, B*T] (``fused_topk.query_terms``),
    q_dense [B, Dd], c_idx/c_val [N, NNZ], c_dense [N, Dd] -> scores
    [B, N]."""
    b, bt = q_w.shape
    n, nnz = c_idx.shape
    dd = q_dense.shape[1]
    assert n % tile_n == 0, (n, tile_n)
    kernel = functools.partial(_kernel, w_dense=w_dense, w_sparse=w_sparse)
    return pallas_call(
        kernel,
        name="sparse_dense",
        grid=(n // tile_n,),
        in_specs=[
            pl.BlockSpec((bt, 1), lambda t: (0, 0)),
            pl.BlockSpec((b, bt), lambda t: (0, 0)),
            pl.BlockSpec((b, dd), lambda t: (0, 0)),
            pl.BlockSpec((tile_n, nnz), lambda t: (t, 0)),
            pl.BlockSpec((tile_n, nnz), lambda t: (t, 0)),
            pl.BlockSpec((tile_n, dd), lambda t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((b, tile_n), lambda t: (0, t)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        scratch_shapes=sparse_scratch(bt, nnz, tile_n),
    )(q_ids, q_w, q_dense, c_idx, c_val, c_dense)
