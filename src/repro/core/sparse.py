"""Padded-COO sparse vectors and sparse inner-product primitives.

NMSLIB stores sparse vectors as (id, value) pairs with unlimited nnz and
computes inner products with SIMD-accelerated merge loops.  JAX requires
static shapes, so we use a *padded COO* layout:

    indices : i32[..., NNZ]   term ids, padding slots hold ``pad_id``
    values  : f32/bf16[..., NNZ]   weights, padding slots hold 0.0
                              (scores always accumulate in f32 — see the
                              precision contract in ``core.spaces``)

``pad_id`` is by convention ``vocab_size`` (one past the last real id), so a
scatter into a dense buffer of size ``vocab_size + 1`` sends padding into a
trash slot.  All routines below are pure jnp and jit/vmap/pjit friendly; the
Pallas kernel in ``repro.kernels.sparse_dense`` accelerates the hot
batch-vs-corpus scoring path with the same semantics (``ref.py`` delegates
here).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "SparseVectors",
    "from_dense",
    "densify",
    "sparse_inner_one_to_one",
    "sparse_inner_qbatch_docs",
    "sparse_inner_tiled",
    "l2_normalize_sparse",
    "topk_truncate",
]


# f32 contractions at full precision (a TPU's default is one bf16 pass);
# the same setting as ``spaces.HIGHEST``, which imports this module
_HIGHEST = jax.lax.Precision.HIGHEST


def _accum_f32(x: jax.Array) -> jax.Array:
    """Upcast sub-f32 values (bf16/f16 residency) to f32 for
    accumulation; f32 passes through and wider dtypes (outside the
    contract) are left alone rather than silently rounded down."""
    return (x.astype(jnp.float32)
            if jnp.dtype(x.dtype).itemsize < 4 else x)


class SparseVectors(NamedTuple):
    """A batch of padded-COO sparse vectors.

    ``indices[..., j] == pad_id`` marks an unused slot; its value must be 0.
    """

    indices: jax.Array  # i32[..., NNZ]
    values: jax.Array   # f32[..., NNZ]

    @property
    def nnz_capacity(self) -> int:
        return self.indices.shape[-1]

    @property
    def batch_shape(self):
        return self.indices.shape[:-1]


def from_dense(dense: jax.Array, nnz: int, pad_id: int | None = None) -> SparseVectors:
    """Convert dense rows [..., V] to padded COO keeping the top-``nnz``
    entries by |value| (NMSLIB export is lossless; ours truncates when a row
    has more than ``nnz`` non-zeros — the loss is measured in tests)."""
    vocab = dense.shape[-1]
    pad_id = vocab if pad_id is None else pad_id
    mag = jnp.abs(dense)
    vals, idx = jax.lax.top_k(mag, nnz)
    gathered = jnp.take_along_axis(dense, idx, axis=-1)
    keep = vals > 0.0
    idx = jnp.where(keep, idx, pad_id)
    gathered = jnp.where(keep, gathered, 0.0)
    return SparseVectors(idx.astype(jnp.int32), gathered)


def densify(sp: SparseVectors, vocab_size: int) -> jax.Array:
    """Scatter padded-COO rows back to dense [..., vocab_size]."""
    flat_idx = sp.indices.reshape(-1, sp.nnz_capacity)
    flat_val = sp.values.reshape(-1, sp.nnz_capacity)

    def one(idx, val):
        buf = jnp.zeros((vocab_size + 1,), dtype=val.dtype)
        buf = buf.at[idx].add(val)
        return buf[:vocab_size]

    out = jax.vmap(one)(flat_idx, flat_val)
    return out.reshape(*sp.batch_shape, vocab_size)


def l2_normalize_sparse(sp: SparseVectors, eps: float = 1e-12) -> SparseVectors:
    norm = jnp.sqrt(jnp.sum(sp.values * sp.values, axis=-1, keepdims=True))
    return SparseVectors(sp.indices, sp.values / jnp.maximum(norm, eps))


def topk_truncate(sp: SparseVectors, nnz: int, pad_id: int) -> SparseVectors:
    """Reduce nnz capacity, keeping largest-|value| entries."""
    vals, pos = jax.lax.top_k(jnp.abs(sp.values), nnz)
    idx = jnp.take_along_axis(sp.indices, pos, axis=-1)
    val = jnp.take_along_axis(sp.values, pos, axis=-1)
    keep = vals > 0.0
    return SparseVectors(
        jnp.where(keep, idx, pad_id).astype(jnp.int32), jnp.where(keep, val, 0.0)
    )


def sparse_inner_one_to_one(q: SparseVectors, d: SparseVectors, vocab_size: int) -> jax.Array:
    """<q_b, d_b> for aligned batches.  Scatter q into a dense scratch row of
    size V+1 (padding lands in the trash slot), then gather at d's indices.

    This is the TPU-friendly replacement for NMSLIB's sorted-merge loop: the
    scatter/gather are contiguous VMEM ops instead of a data-dependent merge.
    """

    def one(qi, qv, di, dv):
        # f32 accumulation regardless of storage dtype (precision
        # contract — see spaces.py): bf16 values upcast before the mul
        qv, dv = _accum_f32(qv), _accum_f32(dv)
        buf = jnp.zeros((vocab_size + 1,), dtype=qv.dtype).at[qi].add(qv)
        return jnp.sum(buf[di] * dv)

    flat = jax.vmap(one)
    bshape = q.batch_shape
    out = flat(
        q.indices.reshape(-1, q.nnz_capacity),
        q.values.reshape(-1, q.nnz_capacity),
        d.indices.reshape(-1, d.nnz_capacity),
        d.values.reshape(-1, d.nnz_capacity),
    )
    return out.reshape(bshape)


def sparse_inner_qbatch_docs(
    q: SparseVectors, docs: SparseVectors, vocab_size: int
) -> jax.Array:
    """All-pairs scores [B, N] between query batch (B) and doc set (N).

    Strategy: densify the *queries* (B is small: tens-to-thousands; V is the
    term vocabulary) then gather doc indices out of the dense query rows.
    Cost: B·V scatter + B·N·NNZ gather-multiply — the latter maps to a
    vectorised gather on TPU and is exactly what the Pallas kernel tiles.
    """
    # densify in the storage dtype, THEN upcast the table: the Pallas
    # fused kernel receives the same storage-dtype table and upcasts it
    # whole, so this exact order keeps bf16 corpora bit-identical
    # between the library and kernel paths (precision contract)
    qd = _accum_f32(densify(q, vocab_size))          # [B, V]
    qd = jnp.pad(qd, ((0, 0), (0, 1)))             # trash slot for pad_id
    # [B, N, NNZ] gather — tiled variant below bounds the intermediate.
    picked = qd[:, docs.indices]                   # [B, N, NNZ]
    return jnp.einsum("bnk,nk->bn", picked, _accum_f32(docs.values),
                      precision=_HIGHEST)


def sparse_inner_tiled(
    q: SparseVectors,
    docs: SparseVectors,
    vocab_size: int,
    tile_n: int = 4096,
) -> jax.Array:
    """Memory-bounded version of :func:`sparse_inner_qbatch_docs`.

    Scans the doc axis in tiles of ``tile_n`` so the [B, tile, NNZ]
    intermediate stays VMEM-sized; doc count must be a multiple of tile_n
    (callers pad — see ``brute_force.pad_corpus``)."""
    n = docs.indices.shape[0]
    assert n % tile_n == 0, f"doc count {n} not a multiple of tile {tile_n}"
    qd = _accum_f32(densify(q, vocab_size))           # f32 accumulation,
    qd = jnp.pad(qd, ((0, 0), (0, 1)))                # any storage dtype

    di = docs.indices.reshape(n // tile_n, tile_n, -1)
    dv = docs.values.reshape(n // tile_n, tile_n, -1)

    def body(carry, tile):
        ti, tv = tile
        picked = qd[:, ti]                          # [B, tile, NNZ]
        return carry, jnp.einsum("bnk,nk->bn", picked, _accum_f32(tv),
                                 precision=_HIGHEST)

    _, out = jax.lax.scan(body, None, (di, dv))
    return jnp.moveaxis(out, 0, 1).reshape(q.indices.shape[0], n)
