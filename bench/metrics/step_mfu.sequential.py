"""step_mfu of the sequential baseline: the whole step's share of the
chip's bf16 peak (%) at the images per second of the untraced window.
Moves images_per_s.sequential."""


def read(ctx):
    return ctx.step_mfu()
