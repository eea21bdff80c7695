"""Serve the retrieval path once on a TPU and check what comes back.

    python chip_smoke.py               # one chip: five served endpoints
    python chip_smoke.py --chips 4     # four chips: the sharded path only
    python chip_smoke.py --rehearse    # tiny CPU run, Pallas interpreted

One chip.  A 1,048,576 x 768 dense corpus (the width of the BERT-base dual
encoders used on MS MARCO passage; MS MARCO's 8.8M passages cut to 2^20
rows) in f32 plus a bf16 copy, and a fused corpus with the same dense rows
plus a sparse part (BERT's 30,522-term vocabulary, 64 terms per document,
16 per query, as in ``configs/paper_retrieval.py``), served through
``RetrievalService`` as five endpoints:

  * ``dense``        the reference backend (the f32 oracle);
  * ``dense_pallas`` the compiled scan kernel, f32;
  * ``dense_bf16``   the compiled scan kernel over the bf16 copy;
  * ``fused``        the compiled fused dense+sparse kernel;
  * ``funnel``       pallas candidates (100) -> a ``LinearReranker``
                     fusion stage (16) -> a ``CrossEncoderReranker`` at the
                     full smollm-360m width (32 layers, d_model 960,
                     random weights) under a rerank budget (10 served).

Four chips.  4,194,304 x 768 bf16 rows in four shards, each committed to
its own chip (``ShardedPipeline.from_corpus(ctx=...)``), served through
``RetrievalService`` and compared with an exact scan on one chip.

The data is made on the device from ``--seed`` with margins planted as in
``benchmarks/common.py`` (``planted_margin_dense``/``planted_margin_fused``):
query ``i`` belongs to group ``g = i % 8``; 100 rows per group hold weight
``1 + r/64`` on axis ``g`` (exact in bf16) and every other row is a unit
vector orthogonal to the group axes, so each query's top 100 ids and their
order are known.  Answers are checked by the precision contract of
``repro.core.precision``: the same ids, scores within its stated f32 (or
bf16) bound.  Any failed check raises.  The timings printed are those of
this smoke run, not benchmark numbers.  The last line of a chip run is
``{"ok": true, "device": {...}}``; without a TPU the script exits non-zero
and prints no result (``--rehearse`` runs everything at a tiny size on
the CPU and says so instead).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time
from pathlib import Path

GROUPS = 8           # planted groups (query i belongs to group i % GROUPS)
DEPTH = 100          # planted rows per group = the funnel's candidate depth
SPARSE_DEPTH = 16    # top planted ranks that also carry the group's term
W_DENSE, W_SPARSE = 0.7, 0.3


@dataclasses.dataclass(frozen=True)
class Sizes:
    n: int                  # corpus rows (one chip)
    n4: int                 # corpus rows across four chips
    d: int                  # embedding width
    vocab: int              # sparse vocabulary
    doc_nnz: int
    q_nnz: int
    batch: int
    n_queries: int          # distinct queries (each client cycles them)
    requests: int           # requests per endpoint
    clients: int
    q_len: int              # cross-encoder query tokens
    doc_len: int            # cross-encoder document tokens
    chunk: int              # oracle row chunk
    block: int              # data-generation row block


CHIP = Sizes(n=1 << 20, n4=1 << 22, d=768, vocab=30522, doc_nnz=64,
             q_nnz=16, batch=16, n_queries=64, requests=64, clients=4,
             q_len=16, doc_len=112, chunk=8192, block=1 << 16)
REHEARSAL = dataclasses.replace(CHIP, n=8192, n4=16384, d=128, vocab=2000,
                                doc_len=16, block=4096)


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny run on the CPU (Pallas interpreted); never "
                         "prints a result")
    return ap.parse_args()


args = parse_args()
if args.rehearse:
    os.environ["JAX_PLATFORMS"] = "cpu"
    if args.chips == 4:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                                   "--xla_force_host_platform_device_count=4")
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402
import numpy as np                                       # noqa: E402

from repro.core.brute_force import TopK                  # noqa: E402
from repro.core.pipeline import (BruteForceGenerator,    # noqa: E402
                                 LinearReranker, RetrievalPipeline)
from repro.core.precision import (bf16_contract_violations,  # noqa: E402
                                  topk_close_violations)
from repro.core.scorers import (BM25Extractor,           # noqa: E402
                                CompositeExtractor, ForwardIndex,
                                ProximityExtractor)
from repro.core.sparse import SparseVectors              # noqa: E402
from repro.core.spaces import (DenseSpace, FusedSpace,   # noqa: E402
                               FusedVectors)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving import (EndpointSpec, FunnelPipeline,  # noqa: E402
                           RetrievalService, ShardedPipeline, StageBudget)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def check_clean(violations, what: str):
    check(not violations, f"{what}: {'; '.join(violations[:3])}")


# --------------------------------------------------------------------------
# compile time, as JAX reports it
# --------------------------------------------------------------------------

_COMPILE_S = [0.0]


def _on_duration(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_seconds() -> float:
    return _COMPILE_S[0]


def peak_bytes() -> str:
    out = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        out.append("not reported" if peak is None else f"{peak / 2**30:.2f} GiB")
    return ", ".join(out)


def set_up(what: str, make):
    """Run one set-up step to completion; print its wall and compile
    seconds."""
    t0, c0 = time.perf_counter(), compile_seconds()
    out = jax.block_until_ready(make())
    print(f"set-up {what}: {time.perf_counter() - t0:.1f} s "
          f"({compile_seconds() - c0:.1f} s compile)", flush=True)
    return out


# --------------------------------------------------------------------------
# data, made on the device from the seed
# --------------------------------------------------------------------------

def planted(n: int):
    """Planted row ids, their groups and ranks: ``DEPTH`` rows per group,
    the groups interleaved so every group spreads over the whole corpus
    (and over every shard)."""
    idx = np.arange(GROUPS * DEPTH)
    stride = n // idx.size
    return idx * stride + stride // 2, idx % GROUPS, idx // GROUPS


def expected_ids(n: int, queries: np.ndarray, depth: int) -> np.ndarray:
    """Each query's true top-``depth`` row ids, best first."""
    rows, group, rank = planted(n)
    table = np.zeros((GROUPS, DEPTH), np.int64)
    table[group, rank] = rows
    return table[queries % GROUPS][:, ::-1][:, :depth]


def dense_rows(key, n: int, d: int, block: int, dtype):
    """[n, d] unit rows orthogonal to the group axes, then the planted
    rows ``(1 + rank/64) * e_group`` (exact in bf16)."""
    rows, group, rank = planted(n)

    def one(i):
        x = jax.random.normal(jax.random.fold_in(key, i), (block, d))
        x = x.at[:, :GROUPS].set(0.0)
        return (x / jnp.linalg.norm(x, axis=1, keepdims=True)).astype(dtype)

    def make():
        c = jax.lax.map(one, jnp.arange(n // block)).reshape(n, d)
        plant = jnp.zeros((rows.size, d), dtype).at[
            np.arange(rows.size), group].set(
                jnp.asarray(1.0 + rank / 64.0, dtype))
        return c.at[rows].set(plant)

    return jax.jit(make)()


def dense_queries(key, count: int, d: int):
    x = jax.random.normal(key, (count, d))
    x = x.at[:, :GROUPS].set(0.0)
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    return x.at[np.arange(count), np.arange(count) % GROUPS].set(2.0)


def sparse_terms(key, rows: int, nnz: int, vocab: int, top: float):
    """Padded COO with one term per vocabulary stratum (distinct, never a
    group term) and values in (0, top]."""
    stride = (vocab - GROUPS) // nnz
    k1, k2 = jax.random.split(key)
    ids = (GROUPS + np.arange(nnz) * stride
           + jax.random.randint(k1, (rows, nnz), 0, stride)).astype(jnp.int32)
    vals = jax.random.uniform(k2, (rows, nnz), minval=top / 64, maxval=top)
    return ids, vals


def sparse_corpus(key, n: int, s: Sizes) -> SparseVectors:
    """Documents (values in (0, 1]); each group's top ``SPARSE_DEPTH``
    planted rows also hold the group's term (slot 0) with weight
    ``1 + r/2``."""
    rows, group, rank = planted(n)
    keep = rank >= DEPTH - SPARSE_DEPTH

    def make():
        ids, vals = sparse_terms(key, n, s.doc_nnz, s.vocab, 1.0)
        ids = ids.at[rows[keep], 0].set(group[keep])
        vals = vals.at[rows[keep], 0].set(
            1.0 + (rank[keep] - (DEPTH - SPARSE_DEPTH)) / 2.0)
        return SparseVectors(ids, vals)

    return jax.jit(make)()


def sparse_queries(key, count: int, s: Sizes) -> SparseVectors:
    """The group's term with weight 8, then terms of weight at most 1/16:
    all of them together move a score by less than a planted step."""
    ids, vals = sparse_terms(key, count, s.q_nnz - 1, s.vocab, 1 / 16)
    g = np.arange(count) % GROUPS
    return SparseVectors(jnp.concatenate([jnp.asarray(g[:, None], jnp.int32),
                                          ids], axis=1),
                         jnp.concatenate([jnp.full((count, 1), 8.0), vals],
                                         axis=1))


def forward_index(tokens: jax.Array, vocab: int) -> ForwardIndex:
    """The fusion stage's forward index over the documents' tokens, with
    exact document frequencies (each row's distinct tokens, counted)."""
    n, length = tokens.shape

    @jax.jit
    def df(t):
        # sorts and a binary search, no scatter: a scatter-add of N x L
        # updates into V bins serialises on the chip
        s = jnp.sort(t, axis=1)
        first = jnp.concatenate(
            [jnp.ones((n, 1), bool), s[:, 1:] != s[:, :-1]], axis=1)
        terms = jnp.sort(jnp.where(first, s, vocab).ravel())
        edges = jnp.searchsorted(terms, jnp.arange(vocab + 1))
        return jnp.diff(edges).astype(jnp.float32)

    return ForwardIndex(tokens, jnp.full((n,), length, jnp.int32), df(tokens),
                        vocab, float(length))


# --------------------------------------------------------------------------
# chunked exact oracle: the library's scoring, a row chunk at a time
# --------------------------------------------------------------------------

def chunked_topk(space, queries, corpus, k: int, chunk: int) -> TopK:
    """Exact top-k of ``space.score_batch`` over row chunks (f32 scores
    at ``Precision.HIGHEST``), merged: the plain oracle without the full
    ``[B, N, nnz]`` gather."""
    n = jax.tree.leaves(corpus)[0].shape[0]
    parts = jax.tree.map(lambda x: x.reshape(n // chunk, chunk, *x.shape[1:]),
                         corpus)

    @jax.jit
    def run(q, parts):
        def one(args):
            i, c = args
            s, ids = jax.lax.top_k(space.score_batch(q, c), k)
            return s, ids + i * chunk

        s, ids = jax.lax.map(one, (jnp.arange(n // chunk), parts))
        s = jnp.moveaxis(s, 0, 1).reshape(s.shape[1], -1)
        ids = jnp.moveaxis(ids, 0, 1).reshape(ids.shape[1], -1)
        top, pos = jax.lax.top_k(s, k)
        return TopK(top, jnp.take_along_axis(ids, pos, axis=1))

    return run(queries, parts)


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------

def drive(svc, reprs, s: Sizes):
    """``s.clients`` threads submit ``s.requests`` requests to every
    endpoint, cycling the queries; returns {endpoint: {query: result}}."""
    results = {ep: {} for ep in reprs}
    lock = threading.Lock()
    errors = []

    def client(c):
        try:
            futs = []
            for r in range(c, s.requests, s.clients):
                qi = r % s.n_queries
                for ep, make in reprs.items():
                    futs.append((ep, qi, svc.submit(*make(qi), endpoint=ep)))
            for ep, qi, fut in futs:
                res = fut.result(timeout=600)
                with lock:
                    results[ep][qi] = res
        except Exception as e:                 # noqa: BLE001 — re-raised
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(s.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return results


def stacked(rows: dict) -> TopK:
    keys = sorted(rows)
    return TopK(np.stack([rows[i].scores for i in keys]),
                np.stack([rows[i].indices for i in keys]))


def report(svc, names, warm_compile):
    snap = svc.snapshot()
    for name in names:
        ep = snap.endpoints[name]
        print(f"endpoint {name:>12}: backend {ep.backend}  dtype "
              f"{ep.corpus_dtype}  requests {ep.n_requests}  compile "
              f"{warm_compile[name]:.1f} s  smoke e2e p50 "
              f"{ep.e2e.p50_ms:.1f} ms  p99 {ep.e2e.p99_ms:.1f} ms",
              flush=True)
    return snap


def warm_up(svc, reprs):
    """One request per endpoint (compiles its path); compile seconds per
    endpoint."""
    out = {}
    for ep, make in reprs.items():
        before = compile_seconds()
        svc.submit(*make(0), endpoint=ep).result(timeout=1200)
        out[ep] = compile_seconds() - before
    svc.reset_stats()
    return out


# --------------------------------------------------------------------------
# one chip: five endpoints
# --------------------------------------------------------------------------

def one_chip(s: Sizes, seed: int, lm_config, chip: bool):
    from repro.models import transformer as T
    from repro.models.encoder import CrossEncoderReranker
    from repro.distributed.sharding import ParallelCtx

    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    lm_vocab = lm_config.vocab_size
    dense = set_up("dense rows", lambda: dense_rows(
        keys[0], s.n, s.d, s.block, jnp.float32))
    sparse = set_up("sparse rows", lambda: sparse_corpus(keys[1], s.n, s))
    q_dense = dense_queries(keys[2], s.n_queries, s.d)
    q_sparse = sparse_queries(keys[3], s.n_queries, s)
    doc_tokens = set_up("document tokens", lambda: jax.random.randint(
        keys[4], (s.n, s.doc_len), 0, lm_vocab))
    q_tokens = jax.random.randint(keys[5], (s.n_queries, s.q_len), 0,
                                  lm_vocab)
    params = set_up("cross-encoder weights", lambda: jax.jit(
        lambda k: T.init_transformer(k, lm_config)[0])(keys[6]))
    fwd = set_up("forward index", lambda: forward_index(doc_tokens, lm_vocab))
    print(f"data: dense {s.n} x {s.d} f32 (+ bf16 copy at registration), "
          f"fused sparse vocab {s.vocab} doc nnz {s.doc_nnz} query nnz "
          f"{s.q_nnz}, cross-encoder {lm_config.name} {lm_config.n_layers} "
          f"layers d_model {lm_config.d_model}, {s.q_len} + {s.doc_len} "
          f"tokens", flush=True)

    space = DenseSpace("ip")
    fused_space = FusedSpace(s.vocab, w_dense=W_DENSE, w_sparse=W_SPARSE)
    dense_pipe = RetrievalPipeline(BruteForceGenerator(space, dense),
                                   cand_qty=DEPTH, final_qty=10)
    fused_pipe = RetrievalPipeline(
        BruteForceGenerator(fused_space, FusedVectors(dense, sparse)),
        cand_qty=10, final_qty=10)
    fusion = LinearReranker(
        CompositeExtractor((BM25Extractor(fwd),
                            ProximityExtractor(fwd, window=4))),
        jnp.asarray([1.0, 0.5, 0.5]))
    cross = CrossEncoderReranker(params, lm_config, ParallelCtx(None, {}),
                                 doc_tokens)
    funnel = FunnelPipeline(BruteForceGenerator(space, dense), fusion=fusion,
                            rerank=cross, cand_qty=DEPTH, fusion_qty=16,
                            rerank_keep=10)

    def spec(**kw):
        return EndpointSpec(batch_size=s.batch, max_wait_s=0.01, **kw)

    fused_q = lambda i: FusedVectors(q_dense[i], SparseVectors(
        q_sparse.indices[i], q_sparse.values[i]))
    reprs = {
        "dense": lambda i: (q_dense[i], None),
        "dense_pallas": lambda i: (q_dense[i], None),
        "dense_bf16": lambda i: (q_dense[i], None),
        "fused": lambda i: (fused_q(i), None),
        "funnel": lambda i: (q_dense[i], q_tokens[i]),
    }
    registered = {"dense": "reference", "dense_pallas": "pallas(",
                  "dense_bf16": "pallas(", "fused": "pallas(",
                  "funnel": "pallas("}
    svc = RetrievalService(cache_size=0)
    svc.register_pipeline("dense", dense_pipe, q_dense[0],
                          spec=spec(backend="reference"))
    svc.register_pipeline("dense_pallas", dense_pipe, q_dense[0],
                          spec=spec(backend="pallas"))
    svc.register_pipeline("dense_bf16", dense_pipe, q_dense[0],
                          spec=spec(backend="pallas",
                                    corpus_dtype="bfloat16"))
    svc.register_pipeline("fused", fused_pipe, fused_q(0),
                          spec=spec(backend="pallas"))
    svc.register_pipeline("funnel", funnel, q_dense[0], q_tokens[0],
                          spec=spec(backend="pallas",
                                    budget=StageBudget(rerank_s=2.0)))

    with svc:
        warm = warm_up(svc, reprs)
        t0 = time.perf_counter()
        results = drive(svc, reprs, s)
        wall = time.perf_counter() - t0
        snap = report(svc, list(reprs), warm)
    print(f"load: {s.requests} requests per endpoint from {s.clients} "
          f"clients in {wall:.1f} s (smoke timing)", flush=True)

    # -- checks -------------------------------------------------------------
    for name, prefix in registered.items():
        ident = snap.endpoints[name].backend or ""
        check(ident.startswith(prefix),
              f"{name} served by {ident!r}, registered {prefix!r}")
        if chip:
            check("interpret=True" not in ident,
                  f"{name} runs the Pallas interpreter on the chip: {ident}")
    check(snap.endpoints["dense_bf16"].corpus_dtype == "bfloat16",
          "dense_bf16 does not serve the bf16 copy")
    qids = np.array(sorted(results["dense"]))
    check(len(qids) == s.n_queries, "not every query was answered")
    dense_res = stacked(results["dense"])
    check(np.array_equal(dense_res.indices, expected_ids(s.n, qids, 10)),
          "dense (reference) misses the planted top-10")
    pallas_res = stacked(results["dense_pallas"])
    check_clean(topk_close_violations(dense_res, pallas_res),
                "dense_pallas vs dense (f32 rule)")
    check(np.array_equal(pallas_res.indices, dense_res.indices),
          "dense_pallas ids differ from dense")
    check_clean(bf16_contract_violations(dense_res,
                                         stacked(results["dense_bf16"])),
                "dense_bf16 vs dense (bf16 rule)")

    q_fused = FusedVectors(q_dense[qids], SparseVectors(
        q_sparse.indices[qids], q_sparse.values[qids]))
    oracle = chunked_topk(fused_space, q_fused, FusedVectors(dense, sparse),
                          10, s.chunk)
    fused_res = stacked(results["fused"])
    check_clean(topk_close_violations(oracle, fused_res),
                "fused vs the chunked f32 oracle")
    check(np.array_equal(fused_res.indices, np.asarray(oracle.indices))
          and np.array_equal(fused_res.indices, expected_ids(s.n, qids, 10)),
          "fused ids differ from the planted top-10")

    fep = snap.endpoints["funnel"]
    check(fep.stage_occupancy.get("rerank") == 1.0
          and fep.stage_fallbacks.get("rerank", 0) == 0,
          f"funnel rerank occupancy {fep.stage_occupancy} fallbacks "
          f"{fep.stage_fallbacks}")
    funnel_res = stacked(results["funnel"])
    cands = expected_ids(s.n, qids, DEPTH)
    check(all(set(row) <= set(c) for row, c in
              zip(funnel_res.indices.tolist(), cands.tolist())),
          "funnel served ids outside the query's candidates")
    check(np.isfinite(funnel_res.scores).all(), "funnel scores not finite")
    offline = funnel.with_backend("pallas").run(q_dense[qids[:s.batch]],
                                                q_tokens[qids[:s.batch]])
    # the cross-encoder computes in bf16 (its config dtype): rows served
    # in other batches agree within 4 bf16 ULPs (2^16 f32 ULPs each)
    check_clean(topk_close_violations(
        offline, TopK(funnel_res.scores[:s.batch],
                      funnel_res.indices[:s.batch]), max_ulp=4 * 2.0 ** 16),
        "funnel served vs offline")
    for st in ("candgen", "fusion", "rerank"):
        lat = fep.stages[st]
        print(f"funnel stage {st:>7}: occupancy "
              f"{fep.stage_occupancy[st]:.0%}  fallbacks "
              f"{fep.stage_fallbacks.get(st, 0)}  smoke p50 {lat.p50_ms:.1f}"
              f" ms  p99 {lat.p99_ms:.1f} ms", flush=True)
    print("checks: dense == planted top-10; dense_pallas ids == dense, "
          "scores within the f32 bound; dense_bf16 recall@10 1.0, within 4 "
          "bf16 ULP; fused == chunked f32 oracle; funnel rerank occupancy "
          "100%, 0 fallbacks", flush=True)


# --------------------------------------------------------------------------
# four chips: the sharded path
# --------------------------------------------------------------------------

def four_chips(s: Sizes, seed: int):
    from repro.distributed import ParallelCtx
    from repro.distributed.mesh_utils import make_mesh

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, found "
          f"{len(jax.devices())}")
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    corpus = set_up("dense rows", lambda: dense_rows(
        keys[0], s.n4, s.d, s.block, jnp.bfloat16))
    queries = dense_queries(keys[1], s.n_queries, s.d)
    mesh = make_mesh((4,), ("corpus",))
    ctx = ParallelCtx(mesh, {"corpus": "corpus"})
    sharded = set_up("shards", lambda: ShardedPipeline.from_corpus(
        DenseSpace("ip"), corpus, 4, ctx=ctx, backend="pallas",
        cand_qty=10, final_qty=10))
    placed = [next(iter(sh.corpus.devices())) for sh in sharded.shards]
    check(len(set(placed)) == 4, f"shards share chips: {placed}")
    print(f"data: {s.n4} x {s.d} bf16 in 4 shards of "
          f"{sharded.shards[0].n_rows} rows on {[str(d) for d in placed]}",
          flush=True)

    reprs = {"sharded": lambda i: (queries[i], None)}
    svc = RetrievalService(cache_size=0)
    svc.register_pipeline("sharded", sharded, queries[0],
                          spec=EndpointSpec(batch_size=s.batch,
                                            max_wait_s=0.01))
    with svc:
        warm = warm_up(svc, reprs)
        results = drive(svc, reprs, s)
        snap = report(svc, list(reprs), warm)
    sharded.close()

    ident = snap.endpoints["sharded"].backend or ""
    check(ident.startswith("pallas("), f"sharded served by {ident!r}")
    qids = np.array(sorted(results["sharded"]))
    got = stacked(results["sharded"])
    oracle = chunked_topk(DenseSpace("ip"), queries[qids], corpus, 10,
                          s.chunk)
    check_clean(topk_close_violations(oracle, got),
                "sharded vs the one-chip exact scan")
    check(np.array_equal(got.indices, expected_ids(s.n4, qids, 10)),
          "sharded ids differ from the planted top-10")
    print("checks: 4-shard pallas endpoint == one-chip exact scan (ids "
          "exact, scores within the f32 bound)", flush=True)


def main():
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    kind = devices[0].device_kind
    print(f"device: {platform} {kind} x {len(devices)}; compile cache "
          f"{cache}", flush=True)
    s = REHEARSAL if args.rehearse else CHIP
    if args.chips == 4:
        four_chips(s, args.seed)
    else:
        from repro import configs
        lm = (configs.get_smoke_config("smollm-360m") if args.rehearse
              else configs.get_config("smollm-360m"))
        one_chip(s, args.seed, lm, chip=not args.rehearse)
    print(f"compile: {compile_seconds():.1f} s in all; peak device memory "
          f"{peak_bytes()}", flush=True)
    if args.rehearse:
        print("rehearsal passed on the CPU; no chip, so no result")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
