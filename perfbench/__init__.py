"""The chip benchmark: one command runs one cell (a deployment under a
traffic mix) once, checks what it served, and prints one result line.

    python3 perfbench/run.py --workload dense.steady --seed 7 --seconds 30 --trace 0

``BENCHMARK.json`` at the repository root names the cells; each
configuration, traffic mix, per-layer metric, data builder and reference
sits in a file of its own under this directory and is found by name.
"""
