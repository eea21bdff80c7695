"""What a batch costs beyond the scan kernel on its slowest chip: the
mean batch execution time (``ServingStats``, host clock) minus the
kernel's device time per call (trace).  On several chips it holds the
fan-out to the shards, the copies of their lists and the merge."""

KERNEL = r"^jit_mips_topk\(.*tpu_custom_call"


def read(layers):
    scan = layers.kernel_ms(KERNEL)
    if scan is None or not layers.stats.n_batches:
        return None
    return 1e3 * layers.stats.execute_total_s / layers.stats.n_batches - scan
