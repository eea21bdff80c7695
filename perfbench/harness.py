"""Runs one cell once: set up, warm up, drive the served path for the
window, check what it served, and return the result line.

Everything a cell is made of is found by name:

* ``BENCHMARK.json`` (the repository root) names the cell's
  configuration, traffic mix and metrics;
* the configuration is the JSON file its entry names; its ``builder``
  is a module under ``builders/``, which holds the data, registers the
  endpoint, draws the queries and answers through its plain reference
  (``references/``);
* a traffic mix is ``traffic/<name>.json``, read by ``traffic.py``;
* a per-layer metric is ``metrics/<name>.py``, whose ``read(layers)``
  returns the number or None when there is nothing to read.

A builder module has ``build(cfg, seed, devices)``, which makes the
deployment's data from the seed on ``devices`` and returns an object
with:

* ``register(svc, name)``: serve the deployment as endpoint ``name`` of
  the ``RetrievalService`` ``svc``, as a user of the program would;
* ``make_queries(count, rng)``: a pool of ``count`` queries drawn from
  ``rng``, in the form the endpoint takes, as any pytree whose every
  leaf leads with ``count`` (one f32 array for a dense space);
  ``traffic.take`` is the only way the harness indexes it;
* ``reference(queries, m)``: the ``m`` best (scores, global ids) per
  query of a pool, by the plain reference, which imports nothing of the
  program;
* ``exact(queries, ids)``: the exact (f64) score of each ``[S, R]`` id;
* ``control(queries, m, precision, emulate)``: (scores, ids) as the
  reference computes them in a precision below the configuration's, or
  with the queries' own precision lowered where ``emulate``: what
  ``tools/calibrate.py`` holds the limits against;
* ``scan_work(batch)``: the bytes and operations of one served scan on
  one chip (``work.scan_work``);
* ``close_program()``: stop what ``register`` started;
* ``delete()``: free the data on the devices.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from perfbench import checks, latency, tracing, traffic, work

ROOT = Path(__file__).resolve().parents[1]


def err(*args):
    print(*args, file=sys.stderr, flush=True)


# -- finding a cell's parts by name ----------------------------------------

def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                   f"(known: {[w['name'] for w in bench['workloads']]})")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return {**json.loads((root / c["file"]).read_text()),
                    "name": name}
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_mix(name: str, root: Path = ROOT) -> dict:
    return json.loads((root / "perfbench" / "traffic" / f"{name}.json")
                      .read_text())


def reader(metric: str, root: Path = ROOT):
    path = root / "perfbench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def builder(name: str):
    return importlib.import_module(f"perfbench.builders.{name}")


def metrics_of(bench: dict, kind: str, workload: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that this cell
    reports."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


# -- compile events ------------------------------------------------------------

class _Events:
    """JAX's timed events (tracing, lowering, compiling, cache reads):
    count and longest per name, and the backend compiles in all."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count, self.seconds = 0, 0.0
        self.by_name: dict = {}

    def __call__(self, event, duration, **_):
        n, longest = self.by_name.get(event, (0, 0.0))
        self.by_name[event] = (n + 1, max(longest, duration))
        if event == self.COMPILE:
            self.count += 1
            self.seconds += duration


COMPILES = _Events()


def set_up_jax(root: Path, cache: bool):
    import jax

    jax.monitoring.register_event_duration_secs_listener(COMPILES)
    if cache:
        # a fixed path inside the checkout, whatever the environment says:
        # the path is part of the cache's key, and two checkouts share
        # nothing
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
        # every program, however small, so that a warm run compiles none
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


# -- the per-layer metrics' inputs ---------------------------------------------

@dataclasses.dataclass
class Layers:
    """What a per-layer metric reads: the endpoint's serving stats over
    the traced window, the reduced trace, and the scan's work."""

    stats: object                 # serving.stats.EndpointSnapshot
    batch_size: int
    trace: Optional[tracing.Trace]
    scan_work: dict               # bytes, ops of one scan call
    peaks: Optional[dict]         # None off the chip

    def served(self) -> int:
        s = self.stats
        return int(round(s.mean_batch_fill * s.n_batches * self.batch_size))

    def kernel_ms(self, pattern: str) -> Optional[float]:
        """Device time per call of the kernel matching ``pattern``, on
        the slowest chip."""
        if self.trace is None:
            return None
        per = tracing.calls(self.trace, pattern)
        if not per:
            return None
        return max(1e3 * total / n for n, total in per.values())

    def least_ms(self) -> Optional[float]:
        if self.peaks is None:
            return None
        return 1e3 * work.least_seconds(self.scan_work, self.peaks)[0]


# -- one run -------------------------------------------------------------------

def _stack(results, k: int):
    scores = np.full((len(results), k), np.nan)
    ids = np.full((len(results), k), -1, np.int64)
    for r, res in enumerate(results):
        s, i = np.asarray(res.scores), np.asarray(res.indices)
        if s.shape == (k,) and i.shape == (k,):
            scores[r], ids[r] = s, i
    return scores, ids


class _Pauses:
    """Garbage-collector pauses while it is installed: count and
    longest, of full (generation 2) collections and of all."""

    def __init__(self):
        self.full, self.count, self.longest, self._t = 0, 0, 0.0, 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
            return
        self.count += 1
        self.full += info["generation"] == 2
        self.longest = max(self.longest, time.perf_counter() - self._t)


@dataclasses.dataclass
class Window:
    """One measured window: the schedule, what the client saw, and the
    endpoint's stats at its close."""

    sched: traffic.Schedule
    queries: object               # the pool, as make_queries drew it
    client: traffic.OpenLoopClient
    stats: object
    window_s: float
    compiles: int
    gc: _Pauses
    jax_events: dict


class Cell:
    """One cell's deployment behind a ``RetrievalService``, warmed up.

    ``rehearsal`` applies the configuration's ``rehearsal`` sizes (a tiny
    run off the chip)."""

    def __init__(self, workload: str, seed: int, *, root: Path = ROOT,
                 rehearsal: bool = False):
        import jax
        from repro.serving import RetrievalService

        self.name, self.seed = workload, seed
        self.bench = benchmark(root)
        w = cell(self.bench, workload)
        cfg = config(self.bench, w["config"], root)
        self.cfg = {**cfg, **cfg["rehearsal"]} if rehearsal else cfg
        self.mix = traffic_mix(w["traffic"], root)
        self.devices = jax.devices()[:w["chips"]]
        self.dep = builder(self.cfg["builder"]).build(self.cfg, seed,
                                                      self.devices)
        self.svc = RetrievalService()
        self.dep.register(self.svc, workload)
        self.batch = self.svc.router.resolve(workload).batch_size

    @property
    def rate(self) -> float:
        return self.mix["rate_of_knee"] * self.cfg["knee_qps"]

    def warm_up(self, rng: np.random.Generator):
        """Every shape the window uses: full batches and a part batch,
        of queries the window never sends."""
        n = 2 * self.batch
        qs = self.dep.make_queries(n + 1, rng)
        for f in [self.svc.submit(traffic.take(qs, i), endpoint=self.name)
                  for i in range(n)]:
            f.result()
        self.svc.submit(traffic.take(qs, n), endpoint=self.name).result()
        self.svc.reset_stats()

    def drive(self, rate: float, seconds: float, rng: np.random.Generator,
              trace_dir: Optional[Path] = None) -> Window:
        """Offer ``rate`` for ``seconds``; returns once the window has
        closed and every answer is in or ``drain_s`` has passed."""
        import jax

        sched = traffic.schedule(self.mix, rate, seconds, rng)
        queries = self.dep.make_queries(traffic.pool_size(sched), rng)
        requests = [traffic.take(queries, qi) for qi in sched.query]
        self.svc.reset_stats()
        compiles0, events0 = COMPILES.count, dict(COMPILES.by_name)
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(
                str(trace_dir), profiler_options=tracing.profile_options())
        client = traffic.OpenLoopClient(
            lambda q: self.svc.submit(q, endpoint=self.name), requests, sched)
        pauses = _Pauses()
        gc.callbacks.append(pauses)
        try:
            window_s = client.run(seconds)
        finally:
            gc.callbacks.remove(pauses)
        if trace_dir is not None:
            jax.profiler.stop_trace()
        stats = self.svc.snapshot().endpoints[self.name]
        compiles = COMPILES.count - compiles0
        events = {k: (n - events0.get(k, (0, 0.0))[0], longest)
                  for k, (n, longest) in COMPILES.by_name.items()
                  if n > events0.get(k, (0, 0.0))[0]}
        client.wait(self.mix["drain_s"])
        return Window(sched, queries, client, stats, window_s, compiles,
                      pauses, events)

    def memory_peak(self) -> int:
        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices)

    def close_program(self):
        self.svc.close()
        self.dep.close_program()

    def check(self, win: Window) -> dict:
        """The comparison with the reference, on a sample of the answered
        requests drawn from the seed."""
        cfg, dep = self.cfg, self.dep
        answered = np.flatnonzero(np.isfinite(win.client.done))
        size = min(self.mix["check_sample"], answered.size)
        sample = np.sort(np.random.default_rng([self.seed, 34]).choice(
            answered, size, replace=False))
        k = cfg["final_qty"]
        served_s, served_i = _stack(
            [win.client.futures[i].result() for i in sample], k)
        qs = traffic.take(win.queries, win.sched.query[sample])
        _, cand = dep.reference(qs, cfg["ref_candidates"])
        cand_exact = dep.exact(qs, cand)
        exact_top = -np.sort(-cand_exact, axis=1)[:, :k]
        in_range = (served_i >= 0) & (served_i < cfg["rows"])
        exact_served = dep.exact(qs, np.where(in_range, served_i, 0))
        numbers = checks.compare(served_s, served_i, exact_top,
                                 exact_served, cfg["rows"])
        numbers["unanswered"] = win.sched.due_s.size - int(answered.size)
        return dict(numbers=numbers, queries=qs, exact_top=exact_top)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = ROOT, rehearsal: bool = False,
             keep_trace: Optional[str] = None,
             hook: Optional[Callable[[Cell, dict], None]] = None) -> dict:
    """One run of ``workload``; returns the result line as a dict.

    ``hook(cell, checked)`` sees the run after the check and before the
    data is freed (the calibration tool reads the control there)."""
    import jax

    c = Cell(workload, seed, root=root, rehearsal=rehearsal)
    rng = np.random.default_rng([seed, 12])
    c.warm_up(rng)
    setup_s = time.perf_counter() - t_start
    trace_dir = root / ".perfbench" / "trace" if trace else None
    win = c.drive(c.rate, seconds, rng, trace_dir)
    e2e = latency.summarize(win.sched.due_s, win.client.done,
                            win.client.sent, seconds)
    peak = c.memory_peak()
    c.close_program()

    t_check = time.perf_counter()
    checked = c.check(win)
    numbers = checked["numbers"]
    correct, compared = checks.verdict(numbers, c.cfg["limits"])
    check_s = time.perf_counter() - t_check
    if hook is not None:
        hook(c, checked)
    scan_work = c.dep.scan_work(c.batch)
    c.dep.delete()

    # -- the result line ------------------------------------------------------
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    e2e_values = {"setup_s": setup_s, **{n: e2e[n] for n in (
        "latency_p50_ms", "latency_p95_ms", "latency_p99_ms", "qps")}}
    out_metrics, breakdown = {}, None
    if not trace:
        for m in metrics_of(c.bench, "end_to_end", workload):
            out_metrics[m["name"]] = {"value": e2e_values[m["name"]],
                                      "unit": m["unit"]}
    else:
        xspace = tracing.find_xspace(str(trace_dir))
        red = tracing.read(xspace)
        if keep_trace:
            shutil.copy(xspace, keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        busy = tracing.busy_s(red)
        device["busy_s"] = (sum(busy.values()) / len(busy)) if busy else 0.0
        device["window_s"] = red.window_s
        layers = Layers(
            stats=win.stats, batch_size=c.batch, trace=red,
            scan_work=scan_work,
            peaks=(None if rehearsal else work.device_peaks(dev0.device_kind)))
        for m in metrics_of(c.bench, "per_layer", workload):
            value = reader(m["name"], root).read(layers)
            if value is not None:
                out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": tracing.top_ops(red),
                     "idle_gaps": tracing.idle_gaps(red)}

    st = win.stats
    err(f"cell {workload}: seed {seed}, {c.cfg['rows']} rows x "
        f"{c.cfg['dim']} {c.cfg['corpus_dtype']} on {len(c.devices)} "
        f"device(s), backend {st.backend}, offered "
        f"{win.sched.rate_qps:.2f} queries/s")
    err(f"set-up {setup_s:.2f} s (compile {COMPILES.seconds:.2f} s); window "
        f"{win.window_s:.3f} s; compiles inside the window {win.compiles}; "
        f"check {check_s:.2f} s")
    err(f"stats: {st.n_batches} batches, fill {st.mean_batch_fill:.3f}, "
        f"exec mean {st.execute.mean_ms:.2f} ms, queue wait mean "
        f"{st.queue_wait.mean_ms:.2f} ms")
    late = win.client.sent - win.sched.due_s
    err(f"collector in the window: {win.gc.count} collections, "
        f"{win.gc.full} full, longest {1e3 * win.gc.longest:.3f} ms; JAX "
        f"events in the window: {win.jax_events or 'none'}")
    err(f"generator lateness p95 {e2e['late_p95_ms']:.3f} ms, max "
        f"{e2e['late_max_ms']:.3f} ms at {win.sched.due_s[np.argmax(late)]:.3f}"
        f" s; p99 latency "
        f"{e2e['latency_p99_ms']} ms; resolved {e2e['resolved']} of "
        f"{e2e['attempted']}")
    for name, value, limit in compared:
        err(f"check {name}: {value} (limit {limit})")
    result = {"correct": bool(correct), "attempted": e2e["attempted"],
              "failed": numbers["unanswered"], "metrics": out_metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in compared}
    return result
