"""Where the Pallas kernels run: compiled by Mosaic when the program is
lowered for a TPU, in Pallas interpret mode on every other platform.

This module is the one place that decides.  :func:`pallas_call` stages
both forms of a kernel behind ``jax.lax.platform_dependent``, so the
choice is made when the surrounding program is lowered — for the
platform it is lowered for — and no caller carries an ``interpret``
flag.  A served path therefore cannot run the interpreter on a TPU, and
a program lowered for a described TPU from a CPU host (the compile
tests in ``tests/test_tpu_compile.py``) gets the Mosaic kernel.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl

__all__ = ["pallas_call", "interpreted"]


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)`` compiled on TPU and
    interpreted elsewhere; takes every ``pl.pallas_call`` keyword except
    ``interpret``."""
    compiled = pl.pallas_call(kernel, interpret=False, **kwargs)
    interpreted_call = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*operands):
        return jax.lax.platform_dependent(
            *operands, tpu=compiled, default=interpreted_call)

    return call


def interpreted() -> bool:
    """Whether kernels lowered for the default device run in interpret
    mode (for identity strings and reports; :func:`pallas_call` makes
    the same choice at lowering time)."""
    return jax.default_backend() != "tpu"
