"""Mean host work a batch puts in series with the device: assembly,
dispatch, the copy back and the fan-out to the futures
(``serve.assemble``, ``serve.dispatch``, ``serve.copy_back``,
``serve.fanout``; ``ServingStats.phase_total_s`` exact totals over the
batches; host clock).  The wait for the device (``serve.sync``) and the
gather are left out."""

HOST = ("assemble", "dispatch", "copy_back", "fanout")


def read(layers):
    phases = getattr(layers.stats, "phase_total_s", None)
    if not phases or not layers.stats.n_batches:
        return None
    return 1e3 * sum(phases[p] for p in HOST) / layers.stats.n_batches
