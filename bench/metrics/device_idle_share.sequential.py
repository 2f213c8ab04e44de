"""Idle share of the device in the sequential baseline (%): one minus
the union of the device's operation intervals over the traced window,
averaged over the chips.  Moves images_per_s.sequential."""


def read(ctx):
    return 100.0 * ctx.reduce.idle_share(ctx.trace)
