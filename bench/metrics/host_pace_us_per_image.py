"""The busiest program thread's work per image answered in the traced
window (us/image), from the program's own spans: the floor that one
Python thread puts on the time per image.  Near ``1e6 / images_per_s``
one thread sets the pace.  Moves images_per_s."""
from bench import program_spans


def read(ctx):
    work = program_spans.work_s_by_thread(ctx)
    if not work or not ctx.window.images:
        return None
    return 1e6 * max(work.values()) / ctx.window.images
