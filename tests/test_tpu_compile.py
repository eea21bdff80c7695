"""The served scan kernels compile for a TPU v5e, at full width.

Each test lowers a kernel wrapper for one chip of a *described* v5e
topology (nothing is attached: the TPU compiler installed with jaxlib
compiles for it from the CPU host) and checks that Mosaic accepted the
kernel (``tpu_custom_call`` in the compiled program).  Interpret-mode
tests cannot see what these do: a gather Mosaic refuses, an unaligned
slice, a tile whose working set overflows scoped VMEM.

Shapes are the served deployment's: D 768 (BERT-base dual encoders), a
2^20-row corpus, batch 16, the tile ``PallasBackend`` picks through
``auto_tile_n``; the fused corpus adds BERT's 30,522-term vocabulary with
64 terms per document and 16 per query.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.  Keep every compile test in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.backends import PallasBackend
from repro.core.sparse import SparseVectors
from repro.kernels import ops

N, D, B = 1 << 20, 768, 16
VOCAB, DOC_NNZ, QUERY_NNZ = 30522, 64, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` -> a ShapeDtypeStruct on one described chip."""
    one_chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _assert_mosaic(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text, "the kernel was not compiled by Mosaic"


@pytest.mark.parametrize("dtype,space,k", [
    (jnp.float32, "ip", 10),
    (jnp.float32, "l2", 10),
    (jnp.bfloat16, "ip", 10),
    (jnp.bfloat16, "l2", 10),
    (jnp.float32, "ip", 100),      # the funnel's candidate depth
    (jnp.bfloat16, "ip", 100),
])
def test_mips_topk_compiles(shape, dtype, space, k):
    corpus = shape((N, D), dtype)
    tile = PallasBackend()._dense_tile(N, B, k, corpus)
    assert N % tile == 0 and tile % 128 == 0
    _assert_mosaic(ops.mips_topk.lower(
        shape((B, D), jnp.float32), corpus, k, tile_n=tile, space=space,
        n_valid=N))


@pytest.mark.parametrize("dtype,k", [
    (jnp.float32, 10),
    (jnp.bfloat16, 10),
    (jnp.float32, 100),
])
def test_fused_topk_compiles(shape, dtype, k):
    itemsize = jnp.dtype(dtype).itemsize
    tile = PallasBackend()._fused_tile(N, B, k, QUERY_NNZ, DOC_NNZ, D,
                                       val_itemsize=itemsize,
                                       dense_itemsize=itemsize)
    assert N % tile == 0 and tile % 128 == 0
    q_sparse = SparseVectors(shape((B, QUERY_NNZ), jnp.int32),
                             shape((B, QUERY_NNZ), jnp.float32))
    c_sparse = SparseVectors(shape((N, DOC_NNZ), jnp.int32),
                             shape((N, DOC_NNZ), dtype))
    _assert_mosaic(ops.fused_topk.lower(
        q_sparse, shape((B, D), jnp.float32), c_sparse, shape((N, D), dtype),
        VOCAB, k, w_dense=0.7, w_sparse=0.3, tile_n=tile, n_valid=N))
