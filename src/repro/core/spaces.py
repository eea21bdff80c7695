"""Distance/similarity *spaces* — NMSLIB's central abstraction, in JAX.

NMSLIB calls a (data format, distance) combination a *space*; search methods
are distance-agnostic and work through this interface, which is what lets
the library add new distances without touching the retrieval algorithms
(paper §2).  We preserve that property: every index in ``repro.core``
(brute force, graph ANN, NAPP) takes a ``Space`` and only ever calls
``score_batch``/``score_pairs``.

Convention: scores are "higher is better".  Metric distances are negated
(``-L2``) so a single top-k path serves both similarities and distances —
mirroring NMSLIB's internal sign flip for similarity spaces.

Supported spaces (paper §2 lists the same inventory):
  * dense:  inner product, cosine, L2, Lp (p configurable)
  * sparse: inner product, cosine (padded COO — see ``core.sparse``)
  * fused sparse+dense inner product with adjustable component weights —
    the paper's NOVEL mixed representation (§3.2 export scenario 1); the
    composite-vector export (scenario 2) lives in ``core.fusion``.

Precision contract: corpora may be resident in any of
:data:`CORPUS_DTYPES` (f32, or bf16 for half the HBM footprint and
roughly double the effective scan bandwidth), but **scores always
accumulate and emit in f32**: every scoring path upcasts its operands
before the first multiply.  Since an elementwise cast commutes with
tiling, all execution backends (reference / streaming / pallas — whose
kernels upcast per tile) agree with each other *within* a corpus dtype
under the f32 rule; across dtypes the bf16 tier is held to a
recall@k == 1.0 vs-f32-oracle + bounded-ULP score-error contract
instead (``core/precision.py``; docs/ARCHITECTURE.md "Precision
contract").
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import sparse as sp

__all__ = [
    "DenseSpace",
    "SparseSpace",
    "FusedSpace",
    "FusedVectors",
    "dense_scores",
    "weighted_mix",
    "CORPUS_DTYPES",
    "canonical_dtype",
    "corpus_dtype",
    "cast_corpus",
]

# dtypes a corpus may be *stored* in; scores are always f32 (see module
# docstring).  Order matters nowhere — membership is the contract.
CORPUS_DTYPES = ("float32", "bfloat16")

_DTYPE_ALIASES = {"f32": "float32", "fp32": "float32",
                  "bf16": "bfloat16"}


def canonical_dtype(dtype) -> str:
    """Normalise a corpus-residency dtype spec (``"bf16"``,
    ``jnp.bfloat16``, ``np.float32``, ...) to its canonical string, or
    raise for dtypes outside the precision contract."""
    if isinstance(dtype, str):
        dtype = _DTYPE_ALIASES.get(dtype, dtype)
    s = str(jnp.dtype(dtype))
    if s not in CORPUS_DTYPES:
        raise ValueError(
            f"corpus dtype {dtype!r} not supported; the precision "
            f"contract covers {CORPUS_DTYPES}")
    return s


def corpus_dtype(corpus) -> Optional[str]:
    """Residency dtype of a corpus pytree: the dtype of its floating
    leaves when they agree and fall under the contract, else None
    (opaque index structures, mixed-precision pytrees)."""
    dts = {str(leaf.dtype) for leaf in jax.tree.leaves(corpus)
           if hasattr(leaf, "dtype")
           and jnp.issubdtype(leaf.dtype, jnp.floating)}
    if len(dts) == 1 and (d := dts.pop()) in CORPUS_DTYPES:
        return d
    return None


def cast_corpus(corpus, dtype):
    """Cast a corpus pytree's floating leaves to a residency ``dtype``
    (integer leaves — COO term ids — are layout, not values, and stay
    i32).  Casting is idempotent and safe to apply to slices: a cast
    then a row-slice equals a row-slice then a cast, which is what keeps
    sharded bf16 corpora bit-identical to unsharded ones.

    Source dtypes must themselves be inside :data:`CORPUS_DTYPES`, and
    only *narrowing* is allowed: widening (bf16 -> f32) is refused
    because the rounding already happened — the result would carry
    bf16-tier values under an f32 label — and an out-of-contract source
    (f16, f64) is refused for the same reason: re-rounding or silently
    relabeling it would claim tier guarantees the data does not
    satisfy.  Rebuild from the original f32 corpus instead."""
    target = jnp.dtype(canonical_dtype(dtype))

    def cast_leaf(leaf):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                     jnp.floating):
            if str(leaf.dtype) not in CORPUS_DTYPES:
                raise ValueError(
                    f"cast_corpus: source dtype {leaf.dtype} is outside "
                    f"the precision contract {CORPUS_DTYPES}; casting it "
                    f"to {target} would relabel out-of-contract data as "
                    "a tier whose guarantees it does not satisfy")
            if jnp.dtype(leaf.dtype).itemsize < target.itemsize:
                raise ValueError(
                    f"cast_corpus: widening {leaf.dtype} -> {target} is "
                    "irreversible (the values were already rounded) and "
                    "would mislabel bounded-error data as the "
                    f"{target} tier; rebuild from the original corpus")
            return jnp.asarray(leaf, target)
        return leaf

    return jax.tree.map(cast_leaf, corpus)


def _accum_f32(x: jax.Array) -> jax.Array:
    """Upcast sub-f32 operands (bf16/f16 residency) to f32 for
    accumulation; leave f32 untouched and *wider* dtypes (f64 under
    jax_enable_x64 — outside the contract) alone rather than silently
    rounding them down."""
    return (x.astype(jnp.float32)
            if jnp.dtype(x.dtype).itemsize < 4 else x)


# Every f32 contraction of a scoring path runs at full f32 precision.  On a
# TPU the default precision of an f32 matmul is one bf16 pass, which
# would make the f32 oracle a bf16-rounded answer; on a CPU the setting
# changes no bit.
HIGHEST = jax.lax.Precision.HIGHEST


def dense_scores(kind: str, q: jax.Array, d: jax.Array, p: float = 2.0) -> jax.Array:
    """All-pairs dense scores [B, N] for query [B, D] vs docs [N, D].

    Sub-f32 operands upcast to f32 before the first multiply (a no-op
    for f32 inputs), so bf16-resident corpora accumulate in f32 — the
    same arithmetic the Pallas kernels run after their per-tile
    upcasts — and every contraction runs at :data:`HIGHEST`."""
    q = _accum_f32(q)
    d = _accum_f32(d)
    if kind == "ip":
        return jnp.matmul(q, d.T, precision=HIGHEST)
    if kind == "cosine":
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        dn = d / jnp.maximum(jnp.linalg.norm(d, axis=-1, keepdims=True), 1e-12)
        return jnp.matmul(qn, dn.T, precision=HIGHEST)
    if kind == "l2":
        # -||q - d||^2 via the matmul identity: MXU-friendly, no B*N*D blowup.
        # Norms via einsum (a dot_general), at full f32 precision.
        q2 = jnp.einsum("bd,bd->b", q, q, precision=HIGHEST)[:, None]  # [B,1]
        d2 = jnp.einsum("nd,nd->n", d, d, precision=HIGHEST)[None, :]  # [1,N]
        return -(q2 + d2 - 2.0 * jnp.matmul(q, d.T, precision=HIGHEST))
    if kind == "lp":
        diff = jnp.abs(q[:, None, :] - d[None, :, :])      # [B,N,D] (small D only)
        return -jnp.sum(diff**p, axis=-1) ** (1.0 / p)
    raise ValueError(f"unknown dense space kind: {kind}")


@dataclasses.dataclass(frozen=True)
class DenseSpace:
    """Fixed-size dense vectors with ip / cosine / l2 / lp scoring."""

    kind: str = "ip"
    p: float = 2.0

    def score_batch(self, queries: jax.Array, corpus: jax.Array) -> jax.Array:
        return dense_scores(self.kind, queries, corpus, self.p)

    def score_pairs(self, queries: jax.Array, docs: jax.Array) -> jax.Array:
        """Aligned scores: queries [B, D] vs docs [B, D] -> [B]."""
        queries = _accum_f32(queries)
        docs = _accum_f32(docs)
        if self.kind == "ip":
            return jnp.sum(queries * docs, axis=-1)
        if self.kind == "cosine":
            qn = queries / jnp.maximum(jnp.linalg.norm(queries, axis=-1, keepdims=True), 1e-12)
            dn = docs / jnp.maximum(jnp.linalg.norm(docs, axis=-1, keepdims=True), 1e-12)
            return jnp.sum(qn * dn, axis=-1)
        if self.kind == "l2":
            d = queries - docs
            return -jnp.sum(d * d, axis=-1)
        if self.kind == "lp":
            return -jnp.sum(jnp.abs(queries - docs) ** self.p, axis=-1) ** (1.0 / self.p)
        raise ValueError(self.kind)


@dataclasses.dataclass(frozen=True)
class SparseSpace:
    """Variable-size sparse vectors (padded COO) under inner product/cosine."""

    vocab_size: int
    kind: str = "ip"
    tile_n: int = 0  # 0 = untiled

    def score_batch(self, queries: sp.SparseVectors, corpus: sp.SparseVectors) -> jax.Array:
        q = sp.l2_normalize_sparse(queries) if self.kind == "cosine" else queries
        d = sp.l2_normalize_sparse(corpus) if self.kind == "cosine" else corpus
        if self.tile_n:
            return sp.sparse_inner_tiled(q, d, self.vocab_size, self.tile_n)
        return sp.sparse_inner_qbatch_docs(q, d, self.vocab_size)

    def score_pairs(self, queries: sp.SparseVectors, docs: sp.SparseVectors) -> jax.Array:
        q = sp.l2_normalize_sparse(queries) if self.kind == "cosine" else queries
        d = sp.l2_normalize_sparse(docs) if self.kind == "cosine" else docs
        return sp.sparse_inner_one_to_one(q, d, self.vocab_size)


def weighted_mix(parts, weights) -> jax.Array:
    """Mix component score arrays through ONE einsum (a dot over the
    stacked component axis).  The obvious ``w_d * dense + w_s * sparse``
    is an elementwise mul+add chain that XLA fuses into an FMA under jit
    (the product loses its rounding step), so eager and jit contexts
    disagree in the last bit; a dot's accumulation order is fixed inside
    the op, making the mix bit-stable across eager/jit/scan — the same
    trick as the einsum L2 norms in :func:`dense_scores`.  Every fused
    scoring path (library, streaming tiles, the Pallas fused kernel) goes
    through this exact arithmetic."""
    return jnp.einsum("...c,c->...", jnp.stack(parts, axis=-1),
                      jnp.asarray(weights, jnp.float32), precision=HIGHEST)


class FusedVectors(NamedTuple):
    """The paper's mixed representation: one dense + one sparse component per
    item.  ``dense`` may be None for sparse-only items and vice versa."""

    dense: Optional[jax.Array]          # f32[..., D] or None
    sparse: Optional[sp.SparseVectors]  # padded COO or None


@dataclasses.dataclass(frozen=True)
class FusedSpace:
    """w_dense * <q_d, x_d>  +  w_sparse * <q_s, x_s>.

    This is FlexNeuART export scenario 1 (paper §3.2): NMSLIB combines the
    per-extractor representations *at query time* with adjustable weights,
    so the mixing weights can be re-tuned after the index is built.  The
    weights come from LETOR training (``core.fusion``).
    """

    vocab_size: int
    w_dense: float = 1.0
    w_sparse: float = 1.0
    dense_kind: str = "ip"
    tile_n: int = 0

    def with_weights(self, w_dense: float, w_sparse: float) -> "FusedSpace":
        return dataclasses.replace(self, w_dense=w_dense, w_sparse=w_sparse)

    def score_batch(self, queries: FusedVectors, corpus: FusedVectors) -> jax.Array:
        parts, weights = [], []
        if queries.dense is not None and corpus.dense is not None:
            parts.append(dense_scores(self.dense_kind, queries.dense, corpus.dense))
            weights.append(self.w_dense)
        if queries.sparse is not None and corpus.sparse is not None:
            parts.append(SparseSpace(self.vocab_size, "ip", self.tile_n).score_batch(
                queries.sparse, corpus.sparse
            ))
            weights.append(self.w_sparse)
        if not parts:
            raise ValueError("FusedSpace: no overlapping components to score")
        return weighted_mix(parts, weights)

    def score_pairs(self, queries: FusedVectors, docs: FusedVectors) -> jax.Array:
        parts, weights = [], []
        if queries.dense is not None and docs.dense is not None:
            parts.append(DenseSpace(self.dense_kind).score_pairs(queries.dense, docs.dense))
            weights.append(self.w_dense)
        if queries.sparse is not None and docs.sparse is not None:
            parts.append(SparseSpace(self.vocab_size).score_pairs(queries.sparse, docs.sparse))
            weights.append(self.w_sparse)
        if not parts:
            raise ValueError("FusedSpace: no overlapping components to score")
        return weighted_mix(parts, weights)
