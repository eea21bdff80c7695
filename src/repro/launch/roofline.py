"""Roofline-term derivation from compiled dry-run artifacts.

Per DESIGN.md §7, for each (arch x shape x mesh) cell:

    compute    = HLO_FLOPs / (chips * peak bf16 FLOP/s)
    memory     = HLO_bytes / (chips * HBM bytes/s)
    collective = collective_bytes / (chips * links * ICI bytes/s per link)

with the peaks of the device kind (:data:`PEAKS`, :func:`device_peaks`).

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` (whole-program,
all devices).  collective_bytes are parsed from the *optimized* HLO text:
we sum the output-tensor bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute op (per-device view; for
ring algorithms wire traffic is within 2x of this — the convention is
applied uniformly so deltas between §Perf iterations are meaningful).
Collectives inside loop bodies (scan over layers) appear once in the HLO
but execute per iteration — we multiply by the enclosing while-loop trip
count when it is statically recoverable from the HLO.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

import jax


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """One chip's published peaks, plus the scoped VMEM a Pallas kernel
    compiles under on it."""

    bf16_flops: float          # dense bf16 FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    ici_bytes_per_s: float     # per link, per direction
    ici_links: int             # links per chip in a 2D torus
    scoped_vmem_bytes: int     # Mosaic's default scoped VMEM limit


# Keyed by ``jax.Device.device_kind``.  TPU v5e: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s ICI over 4 links); the scoped VMEM limit is the one the
# v5e compiler enforces on a kernel ("limit 16.00M" in its
# out-of-VMEM error).
PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        ici_bytes_per_s=50e9, ici_links=4, scoped_vmem_bytes=16 * 2**20),
}

# The chip the kernels are written for: its peaks price kernels that run
# elsewhere (interpret mode on a CPU host runs the same tiles).
TARGET_KIND = "TPU v5 lite"


def device_peaks(kind: Optional[str] = None) -> DevicePeaks:
    """Peaks of ``kind``, or of the default device when it is a TPU (of
    :data:`TARGET_KIND` otherwise).  A TPU kind missing from
    :data:`PEAKS` raises: pricing it with another chip's numbers would
    tune tiles for the wrong machine."""
    if kind is None:
        dev = jax.devices()[0]
        kind = dev.device_kind if dev.platform == "tpu" else TARGET_KIND
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {kind!r}; add its published "
            f"numbers, with their source, to launch.roofline.PEAKS "
            f"(known: {sorted(PEAKS)})") from None


def topk_tile_seconds(tile_n: int, *, b: int, k: int, bytes_per_row: float,
                      flops_per_row: float) -> float:
    """Roofline seconds for ONE corpus tile of the fused scan+select
    kernels (``kernels/mips_topk.py``, ``kernels/fused_topk.py``).

    Per tile the kernel streams ``tile_n`` corpus rows from HBM
    (``bytes_per_row`` each), scores them (``flops_per_row`` each — MXU
    matmul and/or sparse gather-FMA), and folds the tile into the running
    top-k.  The tile time is the max of the compute and HBM-stream terms
    — the quantity ``tile_n`` auto-tuning (``core.backends.auto_tile_n``)
    minimises per corpus row: small tiles pay the ``B*K^2`` fold term
    once per few rows, large tiles stop fitting the VMEM working set.

    The fold term is stale.  It charges K full rounds over the
    ``[B, K + tile_n]`` concatenation to the MXU peak, the fold the
    kernels ran before ``mips_topk.fold_tile``; that fold now inserts
    only the rows above the running K-th score, a few rounds a tile on
    rows in an order unrelated to the query.  The model is kept as it
    is because it picks the tile the served kernels run."""
    peaks = device_peaks()
    compute = (flops_per_row * tile_n + b * k * (k + tile_n)) / peaks.bf16_flops
    memory = (bytes_per_row * tile_n) / peaks.hbm_bytes_per_s
    return max(compute, memory)

def serving_scan_seconds(n_rows: int, *, b: int, k: int, bytes_per_row: float,
                         flops_per_row: float, tile_n: Optional[int] = None,
                         n_shards: int = 1) -> float:
    """Roofline seconds for one batched exact top-k scan over a corpus of
    ``n_rows``, extended to the whole serving config: the corpus is split
    across ``n_shards`` (scanned in parallel, so the scan term is the
    slowest shard), each shard is streamed in ``tile_n``-row tiles
    (``topk_tile_seconds`` per tile), and the per-shard top-k lists are
    merged on one device afterwards (a ``[B, K * n_shards]`` sort-select,
    charged to the VPU).  ``bytes_per_row`` already reflects the corpus
    residency dtype, so the dtype knob flows through here for free."""
    if n_rows <= 0:
        return 0.0
    n_shards = max(1, int(n_shards))
    shard_rows = -(-n_rows // n_shards)          # ceil
    if tile_n is None or tile_n <= 0:
        tile_n = min(shard_rows, 8192)
    tile_n = min(tile_n, shard_rows)
    n_tiles = -(-shard_rows // tile_n)
    scan = n_tiles * topk_tile_seconds(tile_n, b=b, k=k,
                                       bytes_per_row=bytes_per_row,
                                       flops_per_row=flops_per_row)
    merge = ((b * k * n_shards * (k + 1.0)) / device_peaks().bf16_flops
             if n_shards > 1 else 0.0)
    return scan + merge


def serving_visit_seconds(n_visits: float, *, b: int, bytes_per_row: float,
                          flops_per_visit: float) -> float:
    """Roofline seconds for a batched graph-ANN traversal that scores
    ``n_visits`` candidates per query.  Unlike the dense scan, candidate
    rows are gathered (not streamed), so every visit pays the full
    ``bytes_per_row`` from HBM with no tile amortization; compute is the
    per-candidate distance (``flops_per_visit``) plus the beam fold."""
    if n_visits <= 0:
        return 0.0
    peaks = device_peaks()
    compute = (b * n_visits * flops_per_visit) / peaks.bf16_flops
    memory = (b * n_visits * bytes_per_row) / peaks.hbm_bytes_per_s
    return max(compute, memory)


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO shape string like 'bf16[8,128]{1,0}' or a tuple
    '(f32[4], f32[4])'."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Sum per-op-kind output bytes of collective ops in optimized HLO.

    Loop-body weighting: XLA prints each computation once; a collective
    inside a while body runs trip-count times.  Scan trip counts are not
    reliably recoverable from HLO text across versions, so we report the
    static (single-appearance) sum — uniform across baselines and
    iterations, which is what the §Perf deltas need.
    """
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # e.g.:  %all-reduce.5 = f32[8,128]{1,0} all-reduce(...)
        m = re.match(r"%?[\w.\-]+ = (.+?) ([a-z\-]+)\(", s)
        if not m:
            continue
        op = m.group(2)
        if op in _COLLECTIVES or op.rstrip("-start") in _COLLECTIVES:
            key = op[:-6] if op.endswith("-start") else op
            if key in out:
                out[key] += _shape_bytes(m.group(1))
    return out


@dataclasses.dataclass
class Roofline:
    flops: float
    bytes_accessed: float
    collective_bytes: float
    n_chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    per_collective: Dict[str, int]
    model_flops: Optional[float] = None
    useful_ratio: Optional[float] = None
    # resident-traffic lower bound: every live byte touched once per step.
    # ``bytes accessed`` from the CPU-backend HLO is an UPPER bound (CPU
    # fusion is much weaker than TPU fusion, so pre-fusion intermediate
    # traffic is over-counted ~10-100x); true TPU HBM traffic lies between.
    memory_lower_bytes: Optional[float] = None
    memory_lower_s: Optional[float] = None
    bottleneck_lower: Optional[str] = None

    def to_dict(self):
        return dataclasses.asdict(self)


def analyze_terms(flops: float, bytes_accessed: float,
                  per_collective: Dict[str, int], n_chips: int,
                  model_flops: Optional[float] = None,
                  resident_bytes: Optional[float] = None) -> Roofline:
    """Roofline terms from (possibly loop-corrected) aggregate counts.

    The compiled artifact is the SPMD *per-device* program, so
    ``flops``/``bytes_accessed``/collective bytes are all per-device
    quantities; the terms divide by single-chip peaks.  ``model_flops``
    is the GLOBAL analytic count, so the useful-compute ratio compares it
    against flops * n_chips."""
    peaks = device_peaks()
    coll = float(sum(per_collective.values()))
    compute_s = flops / peaks.bf16_flops
    memory_s = bytes_accessed / peaks.hbm_bytes_per_s
    collective_s = coll / (peaks.ici_links * peaks.ici_bytes_per_s)
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    useful = (model_flops / (flops * n_chips)) if (model_flops and flops) else None
    mem_lo_s = ((resident_bytes / peaks.hbm_bytes_per_s) if resident_bytes
                else None)
    bottleneck_lo = None
    if mem_lo_s is not None:
        terms_lo = {"compute": compute_s, "memory": mem_lo_s,
                    "collective": collective_s}
        bottleneck_lo = max(terms_lo, key=terms_lo.get)
    return Roofline(flops, bytes_accessed, coll, n_chips, compute_s, memory_s,
                    collective_s, bottleneck, dict(per_collective),
                    model_flops, useful, resident_bytes, mem_lo_s,
                    bottleneck_lo)


def analyze(compiled, n_chips: int, model_flops: Optional[float] = None,
            hlo_text: Optional[str] = None) -> Roofline:
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    txt = hlo_text if hlo_text is not None else compiled.as_text()
    per = collective_bytes_from_hlo(txt)
    return analyze_terms(flops, bytes_accessed, per, n_chips, model_flops)


def model_flops_for(cfg, shape) -> Optional[float]:
    """Analytic MODEL_FLOPS: 6*N*D train, 2*N*D forward (dense); active
    params for MoE; per-family analytic counts otherwise."""
    fam = getattr(cfg, "family", "lm")
    if fam == "lm":
        n_active = cfg.active_param_count()
        if shape.kind == "train":
            tokens = shape.seq_len * shape.global_batch
            return 6.0 * n_active * tokens
        if shape.kind == "prefill":
            tokens = shape.seq_len * shape.global_batch
            return 2.0 * n_active * tokens
        # decode: one token per sequence + attention over the cache
        tokens = shape.global_batch
        attn = (2.0 * cfg.n_layers * shape.global_batch * shape.seq_len *
                cfg.padded_heads * cfg.resolved_head_dim * 2)
        return 2.0 * n_active * tokens + attn
    if fam == "gnn":
        d = cfg.d_hidden
        if shape.kind == "batched":
            e = shape.n_edges * shape.batch_graphs
            n = shape.n_nodes * shape.batch_graphs
        elif shape.kind == "sampled":
            f1, f2 = shape.fanout
            e = shape.batch_nodes * (f1 + f1 * f2)
            n = shape.batch_nodes * (1 + f1 + f1 * f2)
        else:
            e, n = shape.n_edges, shape.n_nodes
        per_inter = 2.0 * (e * d + n * 3 * d * d + e * cfg.n_rbf * d)
        fwd = cfg.n_interactions * per_inter
        return 3.0 * fwd if shape.kind != "full" else 3.0 * fwd
    # recsys: embedding bytes dominate; FLOPs = MLP + interaction
    b = shape.batch if shape.kind != "retrieval" else 1
    mlp_in = None
    flops = 0.0
    dims = list(cfg.mlp)
    prev = None
    for a, bdim in zip(dims[:-1], dims[1:]):
        flops += 2.0 * b * a * bdim
    if cfg.seq_len:
        flops += 2.0 * b * cfg.seq_len * cfg.embed_dim * cfg.embed_dim * 4
    if shape.kind == "retrieval":
        flops += 2.0 * shape.n_candidates * cfg.embed_dim
    mult = 3.0 if shape.kind == "train" else 1.0
    return mult * flops
