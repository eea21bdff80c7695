"""Mean time the batcher's worker spent assembling a batch: stacking the
requests' and the pads' query leaves and moving them to the device (the
``serve.assemble`` phase's exact lifetime total over the batches,
``ServingStats.phase_total_s``; host clock)."""


def read(layers):
    phases = getattr(layers.stats, "phase_total_s", None)
    if not phases or not layers.stats.n_batches:
        return None
    return 1e3 * phases["assemble"] / layers.stats.n_batches
