"""Mean time the batcher's worker spent gathering a batch, from the
first request it took off the queue to the batch's close (size or
deadline): the ``serve.gather`` phase's exact lifetime total over the
batches (``ServingStats.phase_total_s``; host clock)."""


def read(layers):
    phases = getattr(layers.stats, "phase_total_s", None)
    if not phases or not layers.stats.n_batches:
        return None
    return 1e3 * phases["gather"] / layers.stats.n_batches
