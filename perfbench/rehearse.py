"""Rehearse benchmark cells on the CPU at a tiny size.

    python3 perfbench/rehearse.py [--workload dense.steady ...] [--seconds 2]

Each cell runs through the same harness, configuration and traffic files
as on the chip, with the configuration's ``rehearsal`` sizes, the Pallas
kernels interpreted and four virtual devices for cells that need four
chips.  It prints what the chip run would check and never a result
line; it exits 1 if a cell's answers fail the benchmark's comparison.
"""

import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--seed", type=int, default=20260101)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from perfbench import harness

    harness.set_up_jax(ROOT, cache=False)
    names = args.workload or [w["name"] for w in
                              harness.benchmark(ROOT)["workloads"]]
    ok = True
    for name in names:
        result = harness.run_cell(name, args.seed, args.seconds,
                                  bool(args.trace),
                                  t_start=time.perf_counter(),
                                  rehearsal=True)
        ok &= result["correct"]
        print(f"rehearsal {name}: correct {result['correct']}, attempted "
              f"{result['attempted']}, failed {result['failed']}, metrics "
              f"{sorted(result['metrics'])} (CPU: no device numbers)",
              flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
