"""Device time of one call of the fused dense + sparse scan kernel
(``kernels/fused_topk.py``), on the slowest chip: the kernel's
operations in the profiler trace, summed and divided by their count."""

# the Mosaic custom call inside the program ``ops.fused_topk`` jits
KERNEL = r"^jit_fused_topk\(.*tpu_custom_call"


def read(layers):
    return layers.kernel_ms(KERNEL)
