"""The plain reference for an inner-product scan: every query against
every row, in f32 at full precision, a block of rows at a time; then the
best candidates rescored exactly, in f64 on the host.

It imports nothing of the program.  The data it reads (the corpus rows,
the queries) is made by the benchmark's builder from the seed, not by
the program.

``candidates`` keeps the ``m`` best rows per query by the f32 scan;
``exact`` rescores rows in f64.  With ``m`` well above the ``k`` that is
served, the f64 top-``k`` of the candidates is the exact top-``k``: a
true top-``k`` row would have to lose ``m - k`` places to f32 rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = {"highest": jax.lax.Precision.HIGHEST,
              "high": jax.lax.Precision.HIGH,
              "default": jax.lax.Precision.DEFAULT}


@functools.partial(jax.jit, static_argnames=("m", "block", "precision"))
def candidates(queries, corpus, *, m: int, block: int,
               precision: str = "highest"):
    """[S, D] f32 queries x [N, D] rows -> the ``m`` best (f32 scores,
    row ids) per query, scanning ``block`` rows at a time."""
    n, d = corpus.shape
    if n % block:
        raise ValueError(f"{n} rows are not a multiple of block {block}")
    s = queries.shape[0]
    blocks = corpus.reshape(n // block, block, d)
    prec = PRECISIONS[precision]

    def step(carry, xs):
        top_s, top_i = carry
        i, rows = xs
        scores = jax.lax.dot_general(
            queries, rows.astype(jnp.float32), (((1,), (1,)), ((), ())),
            precision=prec, preferred_element_type=jnp.float32)
        bs, bi = jax.lax.top_k(scores, m)
        cat_s = jnp.concatenate([top_s, bs], axis=1)
        cat_i = jnp.concatenate([top_i, bi + i * block], axis=1)
        keep_s, pos = jax.lax.top_k(cat_s, m)
        return (keep_s, jnp.take_along_axis(cat_i, pos, axis=1)), None

    init = (jnp.full((s, m), -jnp.inf, jnp.float32),
            jnp.zeros((s, m), jnp.int32))
    (top_s, top_i), _ = jax.lax.scan(
        step, init, (jnp.arange(n // block), blocks))
    return top_s, top_i


@jax.jit
def take_rows(corpus, ids):
    return jnp.take(corpus, ids, axis=0).astype(jnp.float32)


def exact(queries: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """[S, D] queries and [S, R, D] rows -> [S, R] f64 inner products."""
    return np.einsum("sd,srd->sr", queries.astype(np.float64),
                     rows.astype(np.float64))


def query_bits_16(queries: np.ndarray) -> np.ndarray:
    """What a three-pass (``Precision.HIGH``) product reads of an f32
    query against a row that is exact in bf16: the query's top two bf16
    pieces, its last 8 mantissa bits dropped.  The same on every
    platform, so the control can be shown on a CPU too."""
    q = jnp.asarray(queries, jnp.float32)
    hi = q.astype(jnp.bfloat16).astype(jnp.float32)
    mid = (q - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return np.asarray(hi + mid)
