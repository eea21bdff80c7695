"""Mean time the sharded pipeline took to hand every shard its scan:
per ``serve.dispatch`` span, from the first ``shard.scan`` span's start
to the last one's end (the shard threads' dispatches under the GIL),
averaged over the batches that fanned out."""

from perfbench import spans


def read(layers):
    if layers.trace is None:
        return None
    per = [kids for kids in spans.within(
        spans.intervals(layers.trace, "shard.scan"),
        spans.intervals(layers.trace, "serve.dispatch")) if kids]
    if not per:
        return None
    return 1e3 * sum(max(e for _, e in kids) - min(s for s, _ in kids)
                     for kids in per) / len(per)
