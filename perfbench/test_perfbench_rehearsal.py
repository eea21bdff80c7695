"""Both cells rehearsed on the CPU through the benchmark's own harness,
configuration and traffic files (tiny sizes, Pallas interpreted, four
virtual devices): the served answers pass the benchmark's comparison and
no result line is printed."""

import os
import subprocess
import sys

import pytest

from perfbench import harness

CELLS = [w["name"] for w in harness.benchmark()["workloads"]]


@pytest.fixture(scope="module")
def rehearsal():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "perfbench/rehearse.py"],
                          cwd=harness.ROOT, env=env, capture_output=True,
                          text=True, timeout=300)
    return proc


def test_rehearsal_exits_zero(rehearsal):
    assert rehearsal.returncode == 0, rehearsal.stderr[-3000:]


@pytest.mark.parametrize("name", CELLS)
def test_cell_served_correct_answers(rehearsal, name):
    lines = [ln for ln in rehearsal.stdout.splitlines()
             if ln.startswith(f"rehearsal {name}:")]
    assert len(lines) == 1, rehearsal.stdout
    assert "correct True" in lines[0] and "failed 0" in lines[0]


def test_rehearsal_prints_no_result_line(rehearsal):
    assert '"correct"' not in rehearsal.stdout
    assert "check score_err_ulp" in rehearsal.stderr
