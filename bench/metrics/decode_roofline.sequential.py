"""decode_roofline of the sequential baseline (%): the least time for
the full-image XLA extractor's FLOP and bytes over the device time of
its decode program's runs.  Moves images_per_s.sequential."""


def read(ctx):
    return ctx.roofline("decode")
