"""A run whose timed path is broken underneath comes out not correct, and
so does the control.

One child process (the CPU, four virtual devices, the cells' rehearsal
sizes) drives the harness past its look for a chip with one fault at a
time planted in the program:

* ``half_batch``: the batcher hands the scan only half of each batch's
  queries; the other rows get answers to other queries;
* ``no_exchange``: the sharded merge keeps only the first chip's list;
* ``altered``: the scan kernel's best id is moved to the next row;

and the control: the reference, computed in the precision below the
configuration's (the queries' last 8 mantissa bits dropped, what a
three-pass product reads), put in the program's place and held to the
configuration's own limits (the rehearsal's looser ones allow for the
CPU's f32 sums).

The four-chip cell ``dense-x4.steady`` is planned (``PERF.md``) and not
yet in ``BENCHMARK.json``: the child runs against a copy of the file that
holds its entries, so the sharded path of the builder is driven, sound
and broken, before the cell is measured.
"""

import json
import os
import subprocess
import sys

import pytest

from perfbench import harness

# the planned four-chip cell's entries, as BENCHMARK.json will hold them
PLANNED = {
    "configs": [{
        "name": "msmarco-v2-passage-dense-x4",
        "source": "https://microsoft.github.io/msmarco/TREC-Deep-Learning-2021",
        "file": "perfbench/configs/msmarco-v2-passage-dense-x4.json",
        "reduced": ["rows"], "why": "planned"}],
    "workloads": [{
        "name": "dense-x4.steady", "config": "msmarco-v2-passage-dense-x4",
        "traffic": "steady", "chips": 4, "why": "planned"}],
}

DRIVER = r"""
import json, os, sys, time
from pathlib import Path
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [ROOT, ROOT + "/src"]
from unittest import mock
import jax.numpy as jnp
from perfbench import checks, harness
from perfbench.tools import calibrate
import repro.kernels.ops as ops
import repro.serving.sharded as sharded
from repro.core.brute_force import TopK
from repro.serving.batcher import ContinuousBatcher

harness.set_up_jax(harness.ROOT, cache=False)
BENCH = Path(BENCH_ROOT)
SEED = 20260202
assemble = ContinuousBatcher._assemble
mips_topk = ops.mips_topk


def half_batch(self, batch):
    stacked, toks = assemble(self, batch)
    n = len(batch)
    h = n // 2
    return stacked.at[h:n].set(stacked[:n - h]), toks


def altered(*a, **kw):
    out = mips_topk(*a, **kw)
    return TopK(out.scores, out.indices.at[:, 0].add(1))


FAULTS = {
    "half_batch": lambda: mock.patch.object(ContinuousBatcher, "_assemble",
                                            half_batch),
    "no_exchange": lambda: mock.patch.object(sharded, "_on_one_device",
                                             lambda parts: parts[:1]),
    "altered": lambda: mock.patch.object(ops, "mips_topk", altered),
}


def run(cell, hook=None):
    return harness.run_cell(cell, SEED, 2.0, False,
                            t_start=time.perf_counter(), root=BENCH,
                            rehearsal=True, hook=hook)


for cell, fault in CASES:
    if fault == "control":
        out = {}

        def hook(c, checked):
            numbers = calibrate.control_numbers(c, checked, "highest", True)
            numbers["unanswered"] = 0
            limits = harness.config(c.bench, c.cfg["name"], BENCH)["limits"]
            out["correct"], out["compared"] = checks.verdict(numbers, limits)

        run(cell, hook)
        line = {"correct": out["correct"], "checks": out["compared"]}
    elif fault == "none":
        res = run(cell)
        line = {"correct": res["correct"], "checks": res["checks"]}
    else:
        with FAULTS[fault]():
            res = run(cell)
        line = {"correct": res["correct"], "checks": res["checks"]}
    print(json.dumps({"cell": cell, "fault": fault, **line}), flush=True)
"""

CASES = [("dense.steady", "half_batch"), ("dense.steady", "altered"),
         ("dense.steady", "control"), ("dense-x4.steady", "half_batch"),
         ("dense-x4.steady", "no_exchange"), ("dense-x4.steady", "altered"),
         ("dense-x4.steady", "control"), ("dense-x4.steady", "none")]
FAULTY = [case for case in CASES if case[1] != "none"]


@pytest.fixture(scope="module")
def bench_root(tmp_path_factory):
    """A root whose BENCHMARK.json also holds the planned cell."""
    root = tmp_path_factory.mktemp("bench")
    bench = harness.benchmark()
    for key, entries in PLANNED.items():
        have = {e["name"] for e in bench[key]}
        bench[key] += [e for e in entries if e["name"] not in have]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "perfbench").symlink_to(harness.ROOT / "perfbench")
    return root


@pytest.fixture(scope="module")
def outcomes(bench_root):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    code = (f"ROOT = {str(harness.ROOT)!r}\nBENCH_ROOT = {str(bench_root)!r}"
            f"\nCASES = {CASES!r}\n" + DRIVER)
    proc = subprocess.run([sys.executable, "-c", code], cwd=harness.ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return {(d["cell"], d["fault"]): d for d in
            map(json.loads, proc.stdout.splitlines())}


@pytest.mark.parametrize("cell,fault", FAULTY)
def test_fault_is_not_correct(outcomes, cell, fault):
    got = outcomes[(cell, fault)]
    assert got["correct"] is False, got["checks"]


def test_planned_sharded_cell_is_correct_unbroken(outcomes):
    got = outcomes[("dense-x4.steady", "none")]
    assert got["correct"] is True, got["checks"]
