"""Where the program runs: the kernel-mode resolver, the per-device peaks
table, the compile cache's location, and ``chip_smoke.py``'s refusal to
report a result without a TPU."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core.backends import GraphANNBackend, PallasBackend
from repro.kernels import platform
from repro.kernels.beam_topk import check_beam_platform
from repro.launch import compile_cache, roofline

REPO = Path(__file__).resolve().parent.parent


def test_kernels_are_interpreted_off_tpu():
    assert jax.default_backend() != "tpu"
    assert platform.interpreted()
    assert "interpret=True" in PallasBackend().identity


def test_beam_kernel_allowed_where_interpreted():
    check_beam_platform()                       # no TPU here: no refusal
    assert GraphANNBackend(kernel=True).kernel


def test_peaks_default_to_the_target_chip_off_tpu():
    assert roofline.device_peaks() is roofline.PEAKS[roofline.TARGET_KIND]
    assert roofline.device_peaks().scoped_vmem_bytes == 16 * 2**20


def test_unknown_tpu_kind_raises():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        roofline.device_peaks("TPU v99")


def test_compile_cache_leaves_a_set_directory_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr

