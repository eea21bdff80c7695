"""Without a TPU, or without the program, a run prints no result."""

import json
import os
import shutil
import subprocess
import sys

from perfbench import harness

ARGS = ["--workload", "dense.steady", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "perfbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            pass
    return False


def test_no_tpu_exits_nonzero_without_a_result_line():
    proc = _run(harness.ROOT)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "nothing was run" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.ROOT / "perfbench", tmp_path / "perfbench")
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
