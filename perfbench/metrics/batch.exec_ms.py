"""Mean time from a batch's close to its rows being back on the host:
assembly, the pipeline run, the copy back (``ServingStats`` exact total
over batches; host clock)."""


def read(layers):
    if not layers.stats.n_batches:
        return None
    return 1e3 * layers.stats.execute_total_s / layers.stats.n_batches
