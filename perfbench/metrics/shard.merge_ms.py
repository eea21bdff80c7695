"""Mean length of the ``shard.merge`` span: the shards' lists moved to
the first chip, concatenated and merged to the global top candidates
(host clock; the merge's device work is asynchronous and waits in
``serve.sync``)."""

from perfbench import spans


def read(layers):
    if layers.trace is None:
        return None
    merges = spans.intervals(layers.trace, "shard.merge")
    if not merges:
        return None
    return 1e3 * sum(e - s for s, e in merges) / len(merges)
