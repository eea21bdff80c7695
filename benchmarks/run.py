# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure.

  table1_stats   — Table 1: dataset statistics
  table2_candgen — Table 2: candidate-generator effect on re-ranking
  table3_fusion  — Table 3: fusion models vs BM25(lemmas)
  ann_tradeoff   — §2: ANN recall vs distance-evaluation fraction
  kernel_bench   — NMSLIB SIMD-scan analogue (Pallas kernels)
  roofline_table — aggregates experiments/dryrun JSONs (if present)

``python -m benchmarks.run [module ...]`` runs a subset.
"""

import sys
import traceback


def main() -> None:
    from benchmarks import (ann_tradeoff, kernel_bench, roofline_table,
                            table1_stats, table2_candgen, table3_fusion)

    modules = {
        "table1_stats": table1_stats,
        "table2_candgen": table2_candgen,
        "table3_fusion": table3_fusion,
        "ann_tradeoff": ann_tradeoff,
        "kernel_bench": kernel_bench,
        "roofline_table": roofline_table,
    }
    selected = sys.argv[1:] or list(modules)
    csv_rows: list = []
    failures = []
    for name in selected:
        print(f"\n########## {name} ##########", flush=True)
        try:
            modules[name].run(csv_rows)
        except Exception as e:  # noqa: BLE001
            failures.append((name, e))
            traceback.print_exc()

    print("\nname,us_per_call,derived")
    for row in csv_rows:
        print(",".join("" if v is None else str(v) for v in row))
    if failures:
        print(f"\n{len(failures)} benchmark(s) failed: "
              f"{[n for n, _ in failures]}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
