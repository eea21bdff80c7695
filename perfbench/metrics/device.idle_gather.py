"""Share of the traced window in which the most idle chip ran no
operation while the batcher was gathering a batch: the ``serve.gather``
spans joined to the device's operations on the trace's one clock."""

from perfbench import spans


def read(layers):
    if layers.trace is None or not layers.trace.window_s:
        return None
    idle = spans.idle_while_s(layers.trace, "serve.gather")
    if idle is None:
        return None
    return 100.0 * idle / layers.trace.window_s
