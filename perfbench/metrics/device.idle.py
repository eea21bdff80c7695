"""Share of the traced window in which the most idle chip ran no
operation: one minus the union of its operation intervals over the
window."""

from perfbench import tracing


def read(layers):
    if layers.trace is None or not layers.trace.ops:
        return None
    busy = min(tracing.busy_s(layers.trace).values())
    return 100.0 * (1.0 - busy / layers.trace.window_s)
