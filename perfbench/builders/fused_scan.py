"""A fused dense + sparse deployment: each row a dense vector and a bag of
weighted terms, made on the chip from the seed, and its endpoint
registered the way a user of the program would.

The endpoint serves a ``RetrievalPipeline`` over a
``BruteForceGenerator`` of a ``FusedSpace`` (scores
``w_dense * <q_d, x_d> + w_sparse * <q_s, x_s>``); every knob the
configuration does not fix stays at the program's default.

The data:

* dense parts: N(0, 1/D) entries in the corpus dtype, made as
  ``dense_scan`` makes its rows;
* terms: ``doc_nnz`` distinct ids a row, Zipf-distributed with exponent
  1 over the vocabulary (``zipf_ranks``: rank ``r`` is
  ``floor((V + 1) ** u)`` for a uniform ``u``, so ``P(r) = log(1 + 1/r) /
  log(V + 1)``; id ``r - 1``).  A row takes the first ``doc_nnz``
  distinct ranks of ``term_draws`` draws, which is sampling without
  replacement in proportion to the law;
* term values: ``sparse_scale * idf(r) * (0.5 + u)``, positive, in the
  corpus dtype, with ``idf(r) = log(1 + 1 / (doc_nnz * P(r)))``: the
  rarer the term, the more it weighs;
* queries: a dense part N(0, 1/D) in f32 and ``q_nnz`` term slots, of
  which ``q_live_min``-``q_live_max`` (uniform) hold distinct ids drawn
  from the same law with f32 values ``0.5 + u``; the rest hold the
  padding id ``V`` and 0.

The reference (``references/fused_ip.py``) reads the same arrays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from perfbench import traffic, work
from perfbench.builders.dense_scan import _maker as dense_maker, seed_key
from perfbench.references import dense_ip, fused_ip
from repro.core.pipeline import BruteForceGenerator, RetrievalPipeline
from repro.core.sparse import SparseVectors
from repro.core.spaces import FusedSpace, FusedVectors
from repro.serving import EndpointSpec


def zipf_ranks(u, vocab: int):
    """Uniforms in [0, 1) -> ranks in [1, V] under ``P(r) = log(1 + 1/r)
    / log(V + 1)`` (numpy or jax arrays alike)."""
    xp = jnp if isinstance(u, jax.Array) else np
    r = xp.floor(xp.exp(u * np.log(vocab + 1.0)))
    return xp.clip(r, 1, vocab).astype(xp.int32)


def idf(ranks, vocab: int, nnz: int):
    """``log(1 + 1 / (nnz * P(r)))``: low for terms most rows hold."""
    p = jnp.log1p(1.0 / ranks.astype(jnp.float32)) / np.log(vocab + 1.0)
    return jnp.log1p(1.0 / (nnz * p))


def first_distinct(draws, m: int):
    """[R, M] draws -> [R, m]: each row's first ``m`` distinct values, in
    draw order (a row with fewer repeats its leftovers)."""
    rows, width = draws.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, draws.shape, 1)
    vals, pos = jax.lax.sort((draws, pos), dimension=1, num_keys=2)
    first = jnp.concatenate([jnp.ones((rows, 1), bool),
                             vals[:, 1:] != vals[:, :-1]], axis=1)
    _, pick = jax.lax.top_k(-jnp.where(first, pos, width + pos), m)
    return jnp.take_along_axis(vals, pick, axis=1)


@functools.lru_cache(maxsize=None)
def _terms_maker(rows: int, vocab: int, nnz: int, draws: int, scale: float,
                 block: int, dtype: str, device):
    def make(key):
        def one(i):
            k_rank, k_val = jax.random.split(jax.random.fold_in(key, i))
            ranks = first_distinct(zipf_ranks(
                jax.random.uniform(k_rank, (block, draws)), vocab), nnz)
            u = jax.random.uniform(k_val, (block, nnz))
            vals = scale * idf(ranks, vocab, nnz) * (0.5 + u)
            return ranks - 1, vals.astype(dtype)

        ids, vals = jax.lax.map(one, jnp.arange(rows // block))
        return ids.reshape(rows, nnz), vals.reshape(rows, nnz)

    sharding = SingleDeviceSharding(device)
    return jax.jit(make, out_shardings=(sharding, sharding))


def query_terms(count: int, cfg: dict, rng: np.random.Generator):
    """``count`` queries' padded term ids and f32 values, drawn from
    ``rng``."""
    vocab, slots = cfg["vocab"], cfg["q_nnz"]
    live = rng.integers(cfg["q_live_min"], cfg["q_live_max"] + 1, count)
    ids = np.full((count, slots), vocab, np.int32)
    vals = np.zeros((count, slots), np.float32)
    for q, n in enumerate(live):
        seen: list = []
        while len(seen) < n:            # the first n distinct draws
            for r in zipf_ranks(rng.random(4 * slots), vocab).tolist():
                if r not in seen and len(seen) < n:
                    seen.append(r)
        ids[q, :n] = np.array(seen) - 1
        vals[q, :n] = 0.5 + rng.random(n, dtype=np.float32)
    return ids, vals


class Deployment:
    def __init__(self, cfg: dict, seed: int, devices):
        if cfg["zipf_exponent"] != 1.0:
            raise ValueError("fused_scan draws Zipf terms of exponent 1 only")
        self.cfg = cfg
        self.device = devices[0]
        self.vocab = cfg["vocab"]
        self.space = FusedSpace(self.vocab, w_dense=cfg["w_dense"],
                                w_sparse=cfg["w_sparse"])
        self.dense = dense_maker(cfg["rows"], cfg["dim"], cfg["gen_block"],
                                 cfg["corpus_dtype"],
                                 self.device)(seed_key(seed, 0))
        self.ids, self.vals = _terms_maker(
            cfg["rows"], self.vocab, cfg["doc_nnz"], cfg["term_draws"],
            cfg["sparse_scale"], cfg["gen_block"], cfg["corpus_dtype"],
            self.device)(seed_key(seed, 1))
        jax.block_until_ready((self.dense, self.ids, self.vals))

    # -- the program, as a user registers it -------------------------------
    def register(self, svc, name: str):
        cfg = self.cfg
        corpus = FusedVectors(self.dense, SparseVectors(self.ids, self.vals))
        pipe = RetrievalPipeline(BruteForceGenerator(self.space, corpus),
                                 cand_qty=cfg["cand_qty"],
                                 final_qty=cfg["final_qty"])
        slots = cfg["q_nnz"]
        pad = FusedVectors(np.zeros(cfg["dim"], np.float32),
                           SparseVectors(np.full(slots, self.vocab, np.int32),
                                         np.zeros(slots, np.float32)))
        svc.register_pipeline(name, pipe, pad, spec=EndpointSpec(
            backend=cfg["backend"], corpus_dtype=cfg["corpus_dtype"]))

    def close_program(self):
        pass

    def make_queries(self, count: int, rng: np.random.Generator):
        dense = traffic.make_queries(count, self.cfg["dim"], rng)
        return FusedVectors(dense, SparseVectors(
            *query_terms(count, self.cfg, rng)))

    # -- what the reference reads ------------------------------------------
    def _scan(self, q_dense, q_ids, q_vals, m: int, precision: str):
        cfg = self.cfg
        q = jax.device_put(jnp.asarray(q_dense, jnp.float32), self.device)
        table = jax.device_put(
            fused_ip.query_table(q_ids, q_vals, self.vocab), self.device)
        scores, ids = fused_ip.candidates(
            q, table, self.dense, self.ids, self.vals, m=m,
            block=cfg["ref_block"], w_dense=cfg["w_dense"],
            w_sparse=cfg["w_sparse"], precision=precision)
        return np.asarray(scores), np.asarray(ids).astype(np.int64)

    def reference(self, queries, m: int, precision: str = "highest"):
        """The ``m`` best (f32 scores, ids) per query by the plain scan."""
        return self._scan(queries.dense, queries.sparse.indices,
                          queries.sparse.values, m, precision)

    def control(self, queries, m: int, precision: str, emulate: bool):
        """The reference scan at ``precision`` over the query values with
        their last 8 mantissa bits dropped, and, where ``emulate``, the
        dense part likewise (what a three-pass product reads of it)."""
        dense = (dense_ip.query_bits_16(queries.dense) if emulate
                 else queries.dense)
        return self._scan(dense, queries.sparse.indices,
                          dense_ip.query_bits_16(queries.sparse.values), m,
                          precision)

    def exact(self, queries, ids: np.ndarray) -> np.ndarray:
        cfg = self.cfg
        rows = fused_ip.rows_of(self.dense, self.ids, self.vals, ids)
        return fused_ip.exact(queries.dense, queries.sparse.indices,
                              queries.sparse.values, self.vocab,
                              cfg["w_dense"], cfg["w_sparse"], *rows)

    def scan_work(self, batch: int) -> dict:
        cfg = self.cfg
        size = jnp.dtype(cfg["corpus_dtype"]).itemsize
        return work.scan_work(b=batch, n=cfg["rows"], d=cfg["dim"],
                              dtype_bytes=size, k=cfg["cand_qty"],
                              nnz=cfg["doc_nnz"], value_bytes=size,
                              q_nnz=cfg["q_nnz"])

    def delete(self):
        for a in (self.dense, self.ids, self.vals):
            a.delete()
        self.dense = self.ids = self.vals = None


def build(cfg: dict, seed: int, devices) -> Deployment:
    return Deployment(cfg, seed, devices)
