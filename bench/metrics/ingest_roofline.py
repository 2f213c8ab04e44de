"""Ingest stage's share of its roofline (%): the least time for the
stage's FLOP and bytes (raw uint8 in, float32 decode input out) over
the device time of the ingest program's runs.  Moves images_per_s."""


def read(ctx):
    return ctx.roofline("ingest")
