"""Device time of one call of the dense scan kernel
(``kernels/mips_topk.py``), on the slowest chip: the kernel's operations
in the profiler trace, summed and divided by their count."""

# the Mosaic custom call inside the program ``ops.mips_topk`` jits
KERNEL = r"^jit_mips_topk\(.*tpu_custom_call"


def read(layers):
    return layers.kernel_ms(KERNEL)
