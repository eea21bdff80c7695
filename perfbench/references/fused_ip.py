"""The plain reference for a fused dense + sparse scan: every query
against every row, ``w_dense * <q_d, x_d> + w_sparse * <q_s, x_s>`` in
f32 at full precision, a block of rows at a time; then the best
candidates rescored exactly, in f64 on the host.

It imports nothing of the program.  The data it reads (the rows' dense
part, their term ids and values, the queries) is made by the
benchmark's builder from the seed, not by the program.

The sparse part is the direct form: the queries' terms densified into a
``[V + 1, S]`` table (row ``V``, the padding id, is zero) and each
block's term ids gathered from it, times the rows' values, summed over
the slots.  ``exact`` scores a row's terms against a query's as bags of
terms, in f64.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.references.dense_ip import PRECISIONS, take_rows


def query_table(ids: np.ndarray, values: np.ndarray, vocab: int):
    """[S, T] padded query terms -> the f32 ``[V + 1, S]`` table of each
    query's value per term (ids out of ``[0, V)`` land in the zeroed
    last row)."""
    ids = np.asarray(ids)
    s, t = ids.shape
    table = np.zeros((vocab + 1, s), np.float32)
    ok = (ids >= 0) & (ids < vocab)
    np.add.at(table, (np.where(ok, ids, vocab),
                      np.broadcast_to(np.arange(s)[:, None], (s, t))),
              np.where(ok, np.asarray(values, np.float32), 0.0))
    table[vocab] = 0.0
    return table


@functools.partial(jax.jit, static_argnames=("m", "block", "precision",
                                             "w_dense", "w_sparse"))
def candidates(q_dense, table, dense, ids, values, *, m: int, block: int,
               w_dense: float, w_sparse: float, precision: str = "highest"):
    """[S, D] f32 queries and their ``[V + 1, S]`` term table against
    [N, D] dense rows and [N, NNZ] term ids and values -> the ``m`` best
    (f32 scores, row ids) per query, scanning ``block`` rows at a
    time."""
    n, d = dense.shape
    if n % block:
        raise ValueError(f"{n} rows are not a multiple of block {block}")
    s, nnz = q_dense.shape[0], ids.shape[1]
    prec = PRECISIONS[precision]

    def step(carry, xs):
        top_s, top_i = carry
        i, rows, row_ids, row_vals = xs
        dense_part = jax.lax.dot_general(
            q_dense, rows.astype(jnp.float32), (((1,), (1,)), ((), ())),
            precision=prec, preferred_element_type=jnp.float32)
        gathered = jnp.take(table, row_ids, axis=0)      # [block, NNZ, S]
        sparse_part = jnp.sum(
            gathered * row_vals.astype(jnp.float32)[:, :, None], axis=1).T
        scores = (jnp.float32(w_dense) * dense_part
                  + jnp.float32(w_sparse) * sparse_part)
        bs, bi = jax.lax.top_k(scores, m)
        cat_s = jnp.concatenate([top_s, bs], axis=1)
        cat_i = jnp.concatenate([top_i, bi + i * block], axis=1)
        keep_s, pos = jax.lax.top_k(cat_s, m)
        return (keep_s, jnp.take_along_axis(cat_i, pos, axis=1)), None

    init = (jnp.full((s, m), -jnp.inf, jnp.float32),
            jnp.zeros((s, m), jnp.int32))
    blocks = (jnp.arange(n // block), dense.reshape(n // block, block, d),
              ids.reshape(n // block, block, nnz),
              values.reshape(n // block, block, nnz))
    (top_s, top_i), _ = jax.lax.scan(step, init, blocks)
    return top_s, top_i


@jax.jit
def take_terms(ids, values, rows):
    return (jnp.take(ids, rows, axis=0),
            jnp.take(values, rows, axis=0).astype(jnp.float32))


def rows_of(dense, ids, values, rows: np.ndarray):
    """[S, R] row ids (each in range) -> their dense parts [S, R, D] and
    term ids and values [S, R, NNZ], on the host."""
    flat = jnp.asarray(rows.reshape(-1).astype(np.int32))
    d = np.asarray(take_rows(dense, flat))
    i, v = (np.asarray(x) for x in take_terms(ids, values, flat))
    shape = rows.shape
    return (d.reshape(shape + d.shape[1:]), i.reshape(shape + i.shape[1:]),
            v.reshape(shape + v.shape[1:]))


def exact(q_dense, q_ids, q_values, vocab: int, w_dense: float,
          w_sparse: float, row_dense, row_ids, row_values) -> np.ndarray:
    """[S, D] / [S, T] queries and [S, R, ...] rows -> [S, R] f64 mixed
    scores.  A query term out of ``[0, V)`` matches nothing."""
    dense = np.einsum("sd,srd->sr", np.asarray(q_dense, np.float64),
                      np.asarray(row_dense, np.float64))
    q_ids = np.asarray(q_ids)
    live = (q_ids >= 0) & (q_ids < vocab)
    q_w = np.where(live, np.asarray(q_values, np.float64), 0.0)
    match = (np.asarray(row_ids)[:, :, :, None]            # [S, R, NNZ, T]
             == np.where(live, q_ids, -1)[:, None, None, :]).astype(
                 np.float64)
    sparse = np.einsum("srkt,srk,st->sr", match,
                       np.asarray(row_values, np.float64), q_w)
    return w_dense * dense + w_sparse * sparse
