# Pallas TPU kernels for the paper's compute hot-spots (NMSLIB's
# SIMD-accelerated distance scans):
#   mips_topk.py     fused tiled MIPS + streaming top-k (VMEM-resident heap)
#   sparse_dense.py  fused sparse+dense scoring (the paper's novel mixed
#                    representation, one pass)
#   fused_topk.py    sparse+dense scoring AND top-k selection in one pass —
#                    the `pallas` execution backend for fused/sparse spaces
# ops.py = jitted wrappers (library drop-ins); ref.py = pure-jnp oracles.
# Compiled by Mosaic on a TPU, interpreted elsewhere (platform.py);
# validated in interpret mode (tests/test_kernels.py) and compiled for a
# described v5e (tests/test_tpu_compile.py).

from repro.kernels import ops, ref  # noqa: F401
