"""Kernel microbenchmarks: the NMSLIB SIMD-scan analogue.

Wall-clock here is CPU interpret-mode (NOT representative of TPU); what
matters and is recorded: (a) every execution backend (reference /
streaming / pallas) produces bit-identical output through the one
``ExecutionBackend.topk`` seam — per corpus dtype: f32 rows are the
historical bitwise tier, bf16 rows are bitwise *within* the tier and
recall-checked against the f32 oracle (the precision contract) — and
(b) the analytic bytes/FLOPs per call from which the TPU-side roofline
expectation is derived (corpus-stream bandwidth bound; bf16 residency
halves the stream, so its expectation is half the f32 one).

Standalone (the CI benchmark smoke job runs the tiny preset)::

    PYTHONPATH=src:. python -m benchmarks.kernel_bench [--smoke]
"""

import argparse

import jax.numpy as jnp
import numpy as np

from benchmarks.common import (planted_margin_dense, planted_margin_fused,
                               time_call)
from repro.core.backends import make_backend
from repro.core.fusion import require_bf16_margin, topk_recall
from repro.core.spaces import DenseSpace, cast_corpus

BACKENDS = ("reference", "streaming", "pallas")
DTYPES = ("float32", "bfloat16")
HBM_BYTES_S = 819e9            # v5e HBM-bound expectation

DENSE_SHAPES = [(8, 4096, 128, 16), (16, 8192, 64, 10)]
SMOKE_DENSE_SHAPES = [(4, 1024, 64, 8)]
FUSED_SHAPE = (8, 4096, 2048, 32, 64)       # b, n, vocab, nnz, dd
SMOKE_FUSED_SHAPE = (4, 1024, 512, 16, 32)


def _assert_tier(outs, f32_reference, dtype, ctx):
    """Within-dtype bitwise parity; bf16 additionally holds recall == 1.0
    against the f32 oracle (the two-tier precision contract)."""
    for name in BACKENDS[1:]:
        assert np.array_equal(np.asarray(outs[name].scores),
                              np.asarray(outs["reference"].scores)), \
            (ctx, dtype, name)
        assert np.array_equal(np.asarray(outs[name].indices),
                              np.asarray(outs["reference"].indices)), \
            (ctx, dtype, name)
    if dtype != "float32":
        rec = topk_recall(f32_reference.indices, outs["reference"].indices)
        assert rec == 1.0, f"{ctx}: {dtype} recall vs f32 oracle {rec}"


def run(csv_rows, *, smoke: bool = False):
    print("\n=== kernel microbench (CPU interpret mode) ===")
    space = DenseSpace("ip")
    # margin-planted data (benchmarks/common.py): the bf16 recall gate
    # must be an invariant of the data, not a seed lottery — and the
    # guard below verifies that at runtime against the rigorous
    # perturbation bound (2^-8 x the absolute-valued score)
    for b, n, d, k in (SMOKE_DENSE_SHAPES if smoke else DENSE_SHAPES):
        q, c32, _planted = planted_margin_dense(n, d, b, k, seed=b * n)
        pert = float(jnp.max(jnp.abs(q) @ jnp.abs(c32).T)) * 2.0 ** -8
        require_bf16_margin(
            make_backend("reference").topk(space, q, c32, k + 1).scores,
            pert_bound=pert)
        f32_reference = None
        for dtype in DTYPES:
            c = cast_corpus(c32, dtype)
            itemsize = jnp.dtype(dtype).itemsize
            stream_bytes = n * d * itemsize + b * k * 8
            tpu_us = stream_bytes / HBM_BYTES_S * 1e6
            tag = "" if dtype == "float32" else "_bf16"
            outs, line = {}, []
            for name in BACKENDS:
                backend = make_backend(name, **({"tile_n": 1024}
                                                if name != "reference"
                                                else {}))
                us, out = time_call(
                    lambda q, c, be=backend: be.topk(space, q, c, k), q, c)
                outs[name] = out
                line.append(f"{name} {us:.0f}us")
                csv_rows.append((f"kernel/mips_topk_{name}_B{b}N{n}{tag}",
                                 round(us, 1),
                                 round(tpu_us, 2) if name == "pallas"
                                 else None))
            if dtype == "float32":
                f32_reference = outs["reference"]
            _assert_tier(outs, f32_reference, dtype, f"mips_topk B{b} N{n}")
            parity = ("bit-identical" if dtype == "float32" else
                      "bit-identical within tier, recall@k=1.0 vs f32")
            print(f"mips_topk B{b} N{n} D{d} K{k} {dtype}: "
                  f"{' | '.join(line)} ({parity}) | "
                  f"TPU roofline expectation {tpu_us:.1f}us")

    from repro.core.sparse import SparseVectors
    from repro.core.spaces import FusedSpace, FusedVectors
    from repro.kernels import ops
    b, n, v, nnz, dd = SMOKE_FUSED_SHAPE if smoke else FUSED_SHAPE
    k = 16 if not smoke else 8
    fc32, fq = planted_margin_fused(n, v, nnz, dd, b, k)
    qs, qv = fq.sparse, fq.dense
    cs, cv = fc32.sparse, fc32.dense
    us, _ = time_call(
        lambda: ops.fused_scores(qs, qv, cs, cv, v, 0.5, 0.5, tile_n=1024))
    stream = n * (nnz * 8 + dd * 4)
    tpu_us = stream / HBM_BYTES_S * 1e6
    print(f"fused_score B{b} N{n} nnz{nnz}: kernel {us:.0f}us | "
          f"TPU expectation {tpu_us:.1f}us")
    csv_rows.append((f"kernel/fused_score_B{b}N{n}", round(us, 1),
                     round(tpu_us, 2)))

    # fused score+select in one pass, through the one topk seam: every
    # backend must stay bit-identical on the mixed representation too —
    # per corpus dtype, with bf16 recall-checked against the f32 oracle
    space = FusedSpace(v, w_dense=0.6, w_sparse=0.4)
    # perturbation bound: 2^-8 x the absolute-valued fused score (abs
    # components, abs weights) — see fusion.require_bf16_margin
    abs_space = FusedSpace(v, w_dense=0.6, w_sparse=0.4)
    abs_q = FusedVectors(jnp.abs(qv), SparseVectors(qs.indices,
                                                    jnp.abs(qs.values)))
    abs_c = FusedVectors(jnp.abs(cv), SparseVectors(cs.indices,
                                                    jnp.abs(cs.values)))
    pert = float(jnp.max(abs_space.score_batch(abs_q, abs_c))) * 2.0 ** -8
    require_bf16_margin(
        make_backend("reference").topk(space, fq, fc32, k + 1).scores,
        pert_bound=pert)
    f32_reference = None
    for dtype in DTYPES:
        fc = cast_corpus(fc32, dtype)
        itemsize = jnp.dtype(dtype).itemsize
        stream = n * (nnz * (4 + itemsize) + dd * itemsize)
        tpu_us = stream / HBM_BYTES_S * 1e6
        tag = "" if dtype == "float32" else "_bf16"
        outs, line = {}, []
        for name in BACKENDS:
            backend = make_backend(name, **({"tile_n": 1024}
                                            if name != "reference" else {}))
            us, out = time_call(
                lambda q, c, be=backend: be.topk(space, q, c, k), fq, fc)
            outs[name] = out
            line.append(f"{name} {us:.0f}us")
            csv_rows.append((f"kernel/fused_topk_{name}_B{b}N{n}{tag}",
                             round(us, 1),
                             round(tpu_us, 2) if name == "pallas" else None))
        if dtype == "float32":
            f32_reference = outs["reference"]
        _assert_tier(outs, f32_reference, dtype, f"fused_topk B{b} N{n}")
        parity = ("bit-identical" if dtype == "float32" else
                  "bit-identical within tier, recall@k=1.0 vs f32")
        print(f"fused_topk B{b} N{n} nnz{nnz} K{k} {dtype}: "
              f"{' | '.join(line)} ({parity}) | "
              f"TPU roofline expectation {tpu_us:.1f}us")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny preset for the CI benchmark smoke job")
    args = ap.parse_args()
    csv_rows: list = []
    run(csv_rows, smoke=args.smoke)
    print("\nname,us_per_call,derived")
    for row in csv_rows:
        print(",".join("" if v is None else str(v) for v in row))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
