"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
:func:`read` keeps what the reduction needs of it: per device the
intervals in which an operation ran, each named by its HLO instruction
and described by the program it ran in (``jit_mips_topk(<fingerprint>)
| %branch_0_fun.1 = ... custom-call(...), custom_call_target=
"tpu_custom_call"`` is the dense scan kernel on a v5e), the host
threads' events, and the length of the traced window.
The reduction works on that alone, so it is tested on a small trace
recorded on the chip (``testdata/``).

Busy time is the union of a device's operation intervals inside the
window; the idle share is one minus busy over the window.  An idle gap is
named by the host event that overlaps it most: what the host was doing
while the device waited.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re
from typing import Dict, List, Optional, Tuple

# the device plane's line of individual operations, and the line of the
# programs (jitted functions) they ran in
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    window_s: float
    # device name -> [(start_s, end_s, name, text)], sorted by start
    ops: Dict[str, List[Tuple[float, float, str, str]]]
    # [(start_s, end_s, name)] of every host thread
    host: List[Tuple[float, float, str]]


def profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # Python calls: costly, not needed
    opts.host_tracer_level = 2         # JAX's own host spans, for the gaps
    return opts


def find_xspace(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _described(ops, modules):
    """[(start, end, label, "<program> | <instruction>")]: each operation
    with the program whose run encloses its start; the label is the
    program's name and the instruction's."""
    mods = sorted((e.start_ns, e.end_ns, e.name) for e in modules)
    out, j = [], 0
    for e in sorted(ops, key=lambda e: e.start_ns):
        while j < len(mods) and mods[j][1] < e.start_ns:
            j += 1
        prog = (mods[j][2] if j < len(mods) and mods[j][0] <= e.start_ns
                else "")
        label = f"{prog.split('(')[0]} {e.name.split(' = ')[0]}".strip()
        out.append((e.start_ns / 1e9, e.end_ns / 1e9, label,
                    f"{prog} | {e.name}"))
    return out


def read(path: str) -> Trace:
    """Reduce an ``.xplane.pb`` (or its gzip, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    window = None
    ops: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats and "profile_stop_time" in stats:
            window = (stats["profile_stop_time"]
                      - stats["profile_start_time"]) / 1e9
        if plane.name.startswith("/device:"):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            evs = _described(lines.get(OPS_LINE, []),
                             lines.get(MODULES_LINE, []))
            if evs:
                ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            host += [(e.start_ns / 1e9, e.end_ns / 1e9, e.name)
                     for ln in plane.lines for e in ln.events
                     if e.duration_ns > 0]
    if window is None:
        ends = [e[1] for evs in ops.values() for e in evs]
        starts = [e[0] for evs in ops.values() for e in evs]
        window = (max(ends) - min(starts)) if ends else 0.0
    return Trace(window_s=window, ops=ops, host=sorted(host))


def union(intervals) -> List[Tuple[float, float]]:
    """Merge [(start, end)] into disjoint sorted intervals."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace) -> Dict[str, float]:
    """Seconds each device ran at least one operation."""
    return {dev: sum(e - s for s, e in union((a, b) for a, b, _, _ in evs))
            for dev, evs in trace.ops.items()}


def calls(trace: Trace, pattern: str) -> Dict[str, Tuple[int, float]]:
    """Per device: (count, total seconds) of the operations whose
    ``"<program> | <instruction>"`` text matches ``pattern`` (a regular
    expression)."""
    rx = re.compile(pattern)
    out = {}
    for dev, evs in trace.ops.items():
        hit = [b - a for a, b, _, text in evs if rx.search(text)]
        if hit:
            out[dev] = (len(hit), sum(hit))
    return out


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """The ``n`` operations (program and instruction) that took most
    device time, summed over devices and divided by their number."""
    total: Dict[str, float] = {}
    for evs in trace.ops.values():
        for a, b, name, _ in evs:
            total[name] = total.get(name, 0.0) + (b - a)
    k = max(1, len(trace.ops))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs / k] for name, secs in ranked]


def _label(trace: Trace, lo: float, hi: float) -> str:
    best, best_key = "no host event", None
    for s, e, name in trace.host:
        if s >= hi:
            break
        ov = min(e, hi) - max(s, lo)
        if ov <= 0:
            continue
        key = (ov, -(e - s))            # most overlap, then the innermost
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def idle_gaps(trace: Trace, n: int = 10,
              device: Optional[str] = None) -> List[list]:
    """The ``n`` longest gaps between busy intervals on ``device`` (the
    most idle one by default), each named by the host event that
    overlaps it most."""
    if not trace.ops:
        return []
    if device is None:
        busy = busy_s(trace)
        device = min(busy, key=busy.get)
    spans = union((a, b) for a, b, _, _ in trace.ops[device])
    gaps = [(b0, a1) for (_, b0), (a1, _) in zip(spans, spans[1:])]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_label(trace, lo, hi), hi - lo] for lo, hi in gaps[:n]]
