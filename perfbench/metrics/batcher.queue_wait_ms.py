"""Mean time a request waited in the endpoint's admission queue before
its batch closed, over the traced window: the batcher's exact lifetime
total over the requests it served (``ServingStats``; host clock)."""


def read(layers):
    served = layers.served()
    if not served:
        return None
    return 1e3 * layers.stats.queue_wait_total_s / served
