"""A dense inner-product deployment: its corpus made on the chips from the
seed, and its endpoint registered the way a user of the program would.

One shard per chip.  On one chip the endpoint serves a
``RetrievalPipeline`` over a ``BruteForceGenerator``; on several, a
``ShardedPipeline`` whose ``CorpusShard``s are built here, each on its
own chip (``ShardedPipeline.from_corpus`` would need the whole corpus on
one device first).  Every knob the configuration does not fix stays at
the program's default.

Rows are N(0, 1/D) entries, made in blocks on each chip in one jitted
call and stored in the configuration's corpus dtype.  The reference
(``references/dense_ip.py``) reads the same arrays.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from perfbench import traffic, work
from perfbench.references import dense_ip
from repro.core.backends import resolve_backend
from repro.core.pipeline import BruteForceGenerator, RetrievalPipeline
from repro.core.spaces import DenseSpace
from repro.serving import EndpointSpec, ShardedPipeline
from repro.serving.sharded import CorpusShard


def seed_key(seed: int, *salt: int):
    """A PRNG key from a seed of any size (``jax.random.key`` keeps only
    the low 32 bits)."""
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    for s in salt:
        key = jax.random.fold_in(key, s)
    return key


@functools.lru_cache(maxsize=None)
def _maker(rows: int, dim: int, block: int, dtype: str, device):
    def make(key):
        def one(i):
            x = jax.random.normal(jax.random.fold_in(key, i), (block, dim),
                                  jnp.float32)
            return (x * dim ** -0.5).astype(dtype)

        return jax.lax.map(one, jnp.arange(rows // block)).reshape(rows, dim)

    return jax.jit(make, out_shardings=SingleDeviceSharding(device))


class Deployment:
    def __init__(self, cfg: dict, seed: int, devices):
        self.cfg = cfg
        self.devices = list(devices)[:cfg["shards"]]
        if len(self.devices) < cfg["shards"]:
            raise RuntimeError(f"{cfg['name']} needs {cfg['shards']} "
                               f"devices, found {len(self.devices)}")
        self.dim = cfg["dim"]
        self.rows_per_shard = cfg["rows"] // cfg["shards"]
        self.space = DenseSpace(cfg["space"])
        self.shards = [
            _maker(self.rows_per_shard, self.dim, cfg["gen_block"],
                   cfg["corpus_dtype"], dev)(seed_key(seed, s))
            for s, dev in enumerate(self.devices)]
        jax.block_until_ready(self.shards)
        self._owned = []

    # -- the program, as a user registers it -------------------------------
    def register(self, svc, name: str):
        cfg = self.cfg
        spec = EndpointSpec(backend=cfg["backend"],
                            corpus_dtype=cfg["corpus_dtype"])
        if len(self.shards) == 1:
            pipe = RetrievalPipeline(
                BruteForceGenerator(self.space, self.shards[0]),
                cand_qty=cfg["cand_qty"], final_qty=cfg["final_qty"])
        else:
            n = self.rows_per_shard
            shards = tuple(CorpusShard(c, s * n, n)
                           for s, c in enumerate(self.shards))
            gens = tuple(BruteForceGenerator(
                self.space, sh.corpus,
                backend=resolve_backend(cfg["backend"], self.space,
                                        sh.corpus)) for sh in shards)
            pipe = ShardedPipeline(
                shards=shards, generators=gens, cand_qty=cfg["cand_qty"],
                final_qty=cfg["final_qty"],
                executor=ThreadPoolExecutor(len(shards),
                                            thread_name_prefix="shard"))
            self._owned.append(pipe)
        svc.register_pipeline(name, pipe, np.zeros(self.dim, np.float32),
                              spec=spec)

    def close_program(self):
        for pipe in self._owned:
            pipe.close()
        self._owned = []

    def make_queries(self, count: int, rng: np.random.Generator):
        return traffic.make_queries(count, self.dim, rng)

    # -- what the reference reads ------------------------------------------
    def reference(self, queries: np.ndarray, m: int,
                  precision: str = "highest"):
        """The ``m`` best (f32 scores, global ids) per query over every
        shard, by the plain scan on each shard's own chip."""
        parts = []
        for s, (dev, corpus) in enumerate(zip(self.devices, self.shards)):
            q = jax.device_put(jnp.asarray(queries, jnp.float32), dev)
            sc, ids = dense_ip.candidates(q, corpus, m=m,
                                          block=self.cfg["ref_block"],
                                          precision=precision)
            parts.append((sc, ids, s * self.rows_per_shard))
        scores = np.concatenate([np.asarray(p[0]) for p in parts], axis=1)
        ids = np.concatenate([np.asarray(p[1]).astype(np.int64) + p[2]
                              for p in parts], axis=1)
        order = np.argsort(-scores, axis=1, kind="stable")[:, :m]
        return (np.take_along_axis(scores, order, 1),
                np.take_along_axis(ids, order, 1))

    def control(self, queries: np.ndarray, m: int, precision: str,
                emulate: bool):
        """The reference scan at ``precision``; where ``emulate``, over
        the queries with their last 8 mantissa bits dropped (what a
        three-pass product reads of them)."""
        scan_q = dense_ip.query_bits_16(queries) if emulate else queries
        return self.reference(scan_q, m, precision=precision)

    def rows(self, ids: np.ndarray) -> np.ndarray:
        """[S, R] global ids (each in range) -> [S, R, D] rows as f32."""
        shard = ids // self.rows_per_shard
        out = np.empty(ids.shape + (self.dim,), np.float32)
        for s, (dev, corpus) in enumerate(zip(self.devices, self.shards)):
            sel = shard == s
            if sel.any():
                local = jax.device_put(
                    (ids[sel] - s * self.rows_per_shard).astype(np.int32),
                    dev)
                out[sel] = np.asarray(dense_ip.take_rows(corpus, local))
        return out

    def exact(self, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
        return dense_ip.exact(queries, self.rows(ids))

    def scan_work(self, batch: int) -> dict:
        """The work of one served scan on one chip: the padded batch
        against that chip's shard, at the candidate depth."""
        return work.scan_work(b=batch, n=self.rows_per_shard, d=self.dim,
                              dtype_bytes=jnp.dtype(self.cfg["corpus_dtype"]).itemsize,
                              k=self.cfg["cand_qty"])

    def delete(self):
        for c in self.shards:
            c.delete()
        self.shards = []


def build(cfg: dict, seed: int, devices) -> Deployment:
    return Deployment(cfg, seed, devices)
