"""ingest_roofline of the sequential baseline (%): the least time for
the unfused XLA preprocess's FLOP and bytes over the device time of its
ingest program's runs.  Moves images_per_s.sequential."""


def read(ctx):
    return ctx.roofline("ingest")
