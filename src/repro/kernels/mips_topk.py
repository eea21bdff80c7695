"""Fused MIPS + streaming top-k Pallas kernel — the paper's hot loop.

NMSLIB's brute-force scan is `for each doc: dist(q, doc); push bounded
heap`.  On TPU the scan becomes a grid over corpus tiles where each grid
step does one MXU matmul [B, D] x [D, TILE_N] *and* folds the tile's scores
into a running top-k held in VMEM scratch — the score matrix [B, N] never
touches HBM.  Per-device HBM traffic is exactly one read of the corpus
tile stream plus one [B, K] result write: the kernel is corpus-bandwidth
bound, which is the roofline for exact k-NN search.

Top-k selection is a threshold-gated insertion fold (:func:`fold_tile`):
only tile rows that beat the running K-th score can enter the list, so a
tile runs as many insertion rounds as the most such rows any query has
(at most K).  Each round takes the tile's best remaining candidate per
query (max, first position: masked VPU lane reductions, no gather) and
inserts it into the sorted running list with a one-lane shift.  With rows
in an order unrelated to the query the running K-th score rises fast and
most tiles need no round at all; a corpus sorted to defeat it costs K
rounds a tile, as a full K-round fold would.  The kernel also returns the
number of rounds it ran.

Layout notes (TPU target):
  * TILE_N and D should be multiples of 128 (lane dim / MXU face);
    B is the sublane dim — multiples of 8 for f32.
  * scratch: scores f32[B, K], ids i32[B, K] in VMEM and the round count
    in SMEM; outputs are written on the final grid step (pl.when).
  * scores accumulate in f32 regardless of input dtype (bf16 corpus OK),
    and the MXU contraction runs at ``Precision.HIGHEST``.

Compiled by Mosaic on a TPU and interpreted elsewhere
(``kernels.platform``).  Validated against ``ref.mips_topk_ref`` over
shape/dtype sweeps under the f32 score bound of ``tests/_precision.py``
(tests/test_kernels.py), and compiled for a described v5e at D 768 in
``tests/test_tpu_compile.py``; also supports L2 via the -(q2+d2-2qd)
identity (the NMSLIB space flexibility, one kernel serving both).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

NEG = float(jnp.finfo(jnp.float32).min)
_POS_SENTINEL = int(jnp.iinfo(jnp.int32).max)


def fold_tile(s_scr, i_scr, s: jax.Array, base) -> jax.Array:
    """Fold one tile's scores ``s`` f32[B, TILE_N] (row ids ``base`` +
    lane) into the running top-k ``s_scr``/``i_scr`` [B, K], sorted
    descending; returns the number of insertion rounds run (i32).

    A row enters only if it beats the running K-th score: one equal to it
    loses to the running entry, whose id is lower.  Each round inserts
    every query's best remaining candidate, at its first position, after
    the running entries that are not below it, so ties keep the lower id
    first, as ``lax.top_k`` does.  A query out of candidates picks
    ``NEG`` and inserts it past the end, which changes nothing.  Every
    step is a lane reduction, compare or select, or a one-lane
    ``pltpu.roll``: Mosaic lowers all of them."""
    k = s_scr.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, s_scr.shape, 1)
    above = s > s_scr[:, k - 1:k]
    rounds = jnp.minimum(jnp.max(jnp.sum(above, axis=1)), k)

    def insert(_, cand):
        best = jnp.max(cand, axis=1, keepdims=True)
        first = jnp.min(jnp.where(cand == best, pos, _POS_SENTINEL), axis=1,
                        keepdims=True)
        run_s, run_i = s_scr[...], i_scr[...]
        at = jnp.sum(run_s >= best, axis=1, keepdims=True)
        s_scr[...] = jnp.where(slot < at, run_s, jnp.where(
            slot == at, best, pltpu.roll(run_s, 1, 1)))
        i_scr[...] = jnp.where(slot < at, run_i, jnp.where(
            slot == at, base + first, pltpu.roll(run_i, 1, 1)))
        return jnp.where(pos == first, NEG, cand)

    jax.lax.fori_loop(0, rounds, insert, jnp.where(above, s, NEG))
    return rounds


def score_tile(q: jax.Array, c: jax.Array, kind: str) -> jax.Array:
    """[B, D] x [TILE_N, D] -> f32 [B, TILE_N] scores inside a kernel:
    one MXU contraction at full f32 precision, plus the ``-||q - c||^2``
    identity for l2 (norms as lane sums, which Mosaic lowers)."""
    q = q.astype(jnp.float32)
    c = c.astype(jnp.float32)
    s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    if kind == "l2":
        q2 = jnp.sum(q * q, axis=1, keepdims=True)           # [B, 1]
        c2 = jnp.sum(c * c, axis=1)[None, :]                 # [1, TILE_N]
        s = -(q2 + c2 - 2.0 * s)
    return s


def _kernel(q_ref, c_ref, out_s_ref, out_i_ref, out_r_ref, s_scr, i_scr,
            r_scr, *, tile_n: int, n_tiles: int, n_valid: int, space: str):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = jnp.full_like(s_scr, NEG)
        i_scr[...] = jnp.zeros_like(i_scr)
        r_scr[0] = 0

    s = score_tile(q_ref[...], c_ref[...], space)          # [B, TILE_N]
    base = t * tile_n
    ids = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(ids < n_valid, s, NEG)
    r_scr[0] += fold_tile(s_scr, i_scr, s, base)

    @pl.when(t == n_tiles - 1)
    def _emit():
        out_s_ref[...] = s_scr[...]
        out_i_ref[...] = i_scr[...]
        out_r_ref[0] = r_scr[0]


def mips_topk_pallas(queries: jax.Array, corpus: jax.Array, k: int,
                     tile_n: int = 2048, n_valid: int | None = None,
                     space: str = "ip"):
    """queries [B, D], corpus [N, D] -> (scores [B, K], ids [B, K],
    rounds i32[1]): the top k descending, and the fold's insertion rounds
    summed over the tiles.  N must be a multiple of tile_n (pad via
    ``brute_force.pad_corpus``).  ``space``: "ip" | "l2" (negated)."""
    b, d = queries.shape
    n = corpus.shape[0]
    assert n % tile_n == 0, (n, tile_n)
    n_tiles = n // tile_n
    n_valid = n if n_valid is None else n_valid

    kernel = functools.partial(_kernel, tile_n=tile_n, n_tiles=n_tiles,
                               n_valid=n_valid, space=space)
    return pallas_call(
        kernel,
        name="mips_topk",
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((b, d), lambda t: (0, 0)),          # queries resident
            pl.BlockSpec((tile_n, d), lambda t: (t, 0)),     # corpus streamed
        ],
        out_specs=[
            pl.BlockSpec((b, k), lambda t: (0, 0)),
            pl.BlockSpec((b, k), lambda t: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, k), jnp.float32),
            pltpu.VMEM((b, k), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )(queries, corpus)
