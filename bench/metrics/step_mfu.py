"""Whole detection step's share of the chip's bf16 peak (%): the
matmul FLOP one image needs (bench/flops.py, from the configuration's
shapes) times the images per second of the run's untraced window, over
chips times the peak (bench/peaks.json).  Moves images_per_s.

The fp32 rung runs its dots at Precision.HIGHEST, several bf16 passes
each, so its share sits well under 100% by construction; the bf16 peak
stays the one yardstick so that a change of rung is comparable."""


def read(ctx):
    return ctx.step_mfu()
