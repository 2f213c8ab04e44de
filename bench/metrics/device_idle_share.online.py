"""Idle share of the device in an open-loop cell (%): one minus the
union of the device's operation intervals over the traced window
(arrivals and drain), averaged over the chips.  Moves latency_p95_ms."""


def read(ctx):
    return 100.0 * ctx.reduce.idle_share(ctx.trace)
