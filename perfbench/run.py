"""Run one benchmark cell once and print its result line.

    python3 perfbench/run.py --workload dense.steady --seed 7 --seconds 30 --trace 0

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  The last line of standard output is the result, one JSON
object; the numbers compared with the reference are the last lines of
standard error.  Without a TPU, or with fewer chips than the cell needs,
it exits 2 and prints no result.  ``--trace 1`` traces the window and
reports the per-layer metrics in place of the end-to-end ones.
"""

import time

T_START = time.perf_counter()

import argparse                                          # noqa: E402
import json                                              # noqa: E402
import os                                                # noqa: E402
import sys                                               # noqa: E402
from pathlib import Path                                 # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the traced run's .xplane.pb here")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program under {ROOT / 'src'}: nothing to run",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the TPU runtime logs under /tmp unless told otherwise
    if "TPU_LOG_DIR" not in os.environ:
        logs = ROOT / ".perfbench" / "tpu_logs"
        logs.mkdir(parents=True, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(logs)
    from perfbench import harness

    chips = harness.cell(harness.benchmark(ROOT), args.workload)["chips"]
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"{args.workload} needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s): nothing "
              f"was run", file=sys.stderr)
        return 2
    harness.set_up_jax(ROOT, cache=True)
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
