"""Fused MIPS + streaming top-k Pallas kernel — the paper's hot loop.

NMSLIB's brute-force scan is `for each doc: dist(q, doc); push bounded
heap`.  On TPU the scan becomes a grid over corpus tiles where each grid
step does one MXU matmul [B, D] x [D, TILE_N] *and* folds the tile's scores
into a running top-k held in VMEM scratch — the score matrix [B, N] never
touches HBM.  Per-device HBM traffic is exactly one read of the corpus
tile stream plus one [B, K] result write: the kernel is corpus-bandwidth
bound, which is the roofline for exact k-NN search.

Top-k selection uses K rounds of (max, first position, mask) over the
concatenated [running-K | tile] score row — branch-free, fully vectorised
(VPU lane reductions), no data-dependent control flow and no gather.

Layout notes (TPU target):
  * TILE_N and D should be multiples of 128 (lane dim / MXU face);
    B is the sublane dim — multiples of 8 for f32.
  * scratch: scores f32[B, K], ids i32[B, K] in VMEM; outputs are written
    on the final grid step (pl.when).
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
_ID_FLOOR = int(jnp.iinfo(jnp.int32).min)


def _fold_topk(scores_row: jax.Array, ids_row: jax.Array, k: int):
    """K rounds of max / first-position / mask over [B, M] ->
    sorted-descending [B, K].  Branch-free, VPU-only; cost ~K * B * M
    compares.  Ties go to the lowest position, i.e. the lower corpus row
    id (the running top-k precedes the tile), like ``lax.top_k``.

    Every step is a masked lane reduction: Mosaic lowers these, while an
    ``argmax`` + ``take_along_axis`` id pick lowers to a gather it
    refuses."""
    pos = jax.lax.broadcasted_iota(jnp.int32, scores_row.shape, 1)
    out_s, out_i = [], []
    cur = scores_row
    for _ in range(k):
        mx = jnp.max(cur, axis=1, keepdims=True)
        first = jnp.min(jnp.where(cur == mx, pos, _POS_SENTINEL), axis=1,
                        keepdims=True)
        hit = pos == first
        out_s.append(mx)
        out_i.append(jnp.max(jnp.where(hit, ids_row, _ID_FLOOR), axis=1,
                             keepdims=True))
        cur = jnp.where(hit, NEG, cur)
    return jnp.concatenate(out_s, axis=1), jnp.concatenate(out_i, axis=1)


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


def _kernel(q_ref, c_ref, out_s_ref, out_i_ref, s_scr, i_scr, *,
            k: int, tile_n: int, n_tiles: int, n_valid: int, space: str):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = jnp.full_like(s_scr, NEG)
        i_scr[...] = jnp.zeros_like(i_scr)

    s = score_tile(q_ref[...], c_ref[...], space)          # [B, TILE_N]
    base = t * tile_n
    ids = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(ids < n_valid, s, NEG)

    cat_s = jnp.concatenate([s_scr[...], s], axis=1)     # [B, K+TILE_N]
    cat_i = jnp.concatenate([i_scr[...], ids], axis=1)
    new_s, new_i = _fold_topk(cat_s, cat_i, k)
    s_scr[...] = new_s
    i_scr[...] = new_i

    @pl.when(t == n_tiles - 1)
    def _emit():
        out_s_ref[...] = s_scr[...]
        out_i_ref[...] = i_scr[...]


def mips_topk_pallas(queries: jax.Array, corpus: jax.Array, k: int,
                     tile_n: int = 2048, n_valid: int | None = None,
                     space: str = "ip"):
    """queries [B, D], corpus [N, D] -> (scores [B, K], ids [B, K]),
    descending.  N must be a multiple of tile_n (pad via
    ``brute_force.pad_corpus``).  ``space``: "ip" | "l2" (negated)."""
    b, d = queries.shape
    n = corpus.shape[0]
    assert n % tile_n == 0, (n, tile_n)
    n_tiles = n // tile_n
    n_valid = n if n_valid is None else n_valid

    kernel = functools.partial(_kernel, k=k, tile_n=tile_n, n_tiles=n_tiles,
                               n_valid=n_valid, space=space)
    out_s, out_i = pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((b, d), lambda t: (0, 0)),          # queries resident
            pl.BlockSpec((tile_n, d), lambda t: (t, 0)),     # corpus streamed
        ],
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
        ],
    )(queries, corpus)
    return out_s, out_i
