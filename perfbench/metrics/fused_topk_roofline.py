"""The fused scan kernel's share of its roofline: the least time the
chip could take for one call's work (``work.scan_work`` with the sparse
slots: bytes over HBM bandwidth or operations over the bf16 peak,
whichever is larger) over the call's device time."""

KERNEL = r"^jit_fused_topk\(.*tpu_custom_call"


def read(layers):
    took, least = layers.kernel_ms(KERNEL), layers.least_ms()
    if took is None or least is None:
        return None
    return 100.0 * least / took
