"""Find a cell's knee: the highest offered rate it sustains, without a
growing backlog and at the latency it serves under light load.  One
process sets the cell up once and offers each rate for a window of its
own.

    python3 perfbench/tools/sweep.py --workload dense.steady --seed 5 \
        --rates 40,60,80,100 --seconds 10

A rate is sustained when the requests resolved inside the window keep
up with those offered, the latency of the window's second half is not
far above its first half's (a growing backlog makes every later request
wait longer), and its p95 is within 5 % of the first (lowest, light)
rate's: past that, requests that find a full batch ahead of them wait
for a third batch, and the tail climbs long before the backlog grows.
One JSON line per rate goes to standard output.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np
    from perfbench import harness, latency

    harness.set_up_jax(ROOT, cache=True)
    t0 = time.perf_counter()
    cell = harness.Cell(args.workload, args.seed)
    rng = np.random.default_rng([args.seed, 56])
    cell.warm_up(rng)
    print(f"set-up {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    light_p95 = None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        win = cell.drive(rate, args.seconds, rng)
        s = latency.summarize(win.sched.due_s, win.client.done,
                              win.client.sent, args.seconds)
        done, due = win.client.done, win.sched.due_s
        backlog = int(np.sum(~(done < args.seconds)))
        lat = done - due
        half = due < args.seconds / 2
        first = float(np.nanmedian(lat[half]))
        second = float(np.nanmedian(lat[~half]))
        st = win.stats
        light_p95 = light_p95 or s["latency_p95_ms"]
        print(json.dumps({
            "rate": rate, "qps": s["qps"], "p50_ms": s["latency_p50_ms"],
            "p95_ms": s["latency_p95_ms"], "p99_ms": s["latency_p99_ms"],
            "backlog_at_close": backlog, "batches": st.n_batches,
            "fill": st.mean_batch_fill, "exec_ms": st.execute.mean_ms,
            "late_p95_ms": s["late_p95_ms"],
            "p50_first_half_ms": 1e3 * first,
            "p50_second_half_ms": 1e3 * second,
            "sustained": bool(s["qps"] >= 0.95 * rate
                              and second <= 1.3 * first
                              and s["latency_p95_ms"] <= 1.05 * light_p95)}),
            flush=True)
    cell.close_program()
    cell.dep.delete()
    return 0


if __name__ == "__main__":
    sys.exit(main())
