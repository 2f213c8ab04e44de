"""Device time of the on-device Reed-Solomon program per image answered
in the window (us/image).  GF arithmetic has no FLOP count, so this is
a time, not a share.  Moves images_per_s."""


def read(ctx):
    t = ctx.module_s("rs")
    if t is None or not ctx.window.images:
        return None
    return 1e6 * t / ctx.window.images
