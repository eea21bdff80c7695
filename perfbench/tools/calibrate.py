"""Read the numbers that ``correct`` compares, for sound runs of the
program and for the control, on many seeds in one process.

    python3 perfbench/tools/calibrate.py --workload dense.steady \
        --seeds 11,12,13 --seconds 6

For each seed the cell runs as the benchmark runs it (data made anew
from the seed, a short window at the cell's rate) and its answers are
compared with the reference.  Then the deployment's controls take the
program's place on the same sampled queries (its builder's ``control``):
for a dense scan, the reference computed in a lower precision than the
configuration states (``Precision.HIGH``, three bf16 passes; its
portable equivalent, the queries' last 8 mantissa bits dropped; and
``Precision.DEFAULT``, one bf16 pass).  One JSON line per seed; the
limits are set between the largest program reading and the smallest
control reading (``PERF.md``).
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def control_numbers(cell, checked, precision: str, emulate: bool):
    """The numbers ``correct`` compares, read off the deployment's
    control put in the program's place on the checked queries."""
    from perfbench import checks

    qs = checked["queries"]
    k = cell.cfg["final_qty"]
    scores, ids = cell.dep.control(qs, k, precision, emulate)
    exact_served = cell.dep.exact(qs, ids)
    return checks.compare(scores, ids, checked["exact_top"], exact_served,
                          cell.cfg["rows"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from perfbench import harness

    harness.set_up_jax(ROOT, cache=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        line = {"seed": seed}

        def hook(cell, checked):
            line["program"] = checked["numbers"]
            line["control_high"] = control_numbers(cell, checked, "high",
                                                   False)
            line["control_high_emulated"] = control_numbers(
                cell, checked, "highest", True)
            line["control_default"] = control_numbers(cell, checked,
                                                      "default", False)

        result = harness.run_cell(args.workload, seed, args.seconds, False,
                                  t_start=time.perf_counter(),
                                  hook=hook)
        line["correct"] = result["correct"]
        line["metrics"] = {k: v["value"] for k, v in
                           result["metrics"].items()}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
