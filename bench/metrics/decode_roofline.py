"""Decode stage's share of its roofline (%): the least time the chip
needs for the stage's FLOP and bytes (bench/flops.py) over the device
time of the decode program's runs in the trace.  The whole program is
timed (the Pallas extractor and the correlation dot), so moving work
between the kernel and XLA keeps the yardstick.  Moves images_per_s."""


def read(ctx):
    return ctx.roofline("decode")
