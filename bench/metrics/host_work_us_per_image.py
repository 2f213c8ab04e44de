"""The work of every program thread together per image answered in
the traced window (us/image), from the program's own spans.  Near
``1e6 / images_per_s`` with ``host_pace_us_per_image`` well below it,
the process as a whole (its one interpreter lock), not one stage, is
saturated.  Moves images_per_s."""
from bench import program_spans


def read(ctx):
    work = program_spans.work_s_by_thread(ctx)
    if not work or not ctx.window.images:
        return None
    return 1e6 * sum(work.values()) / ctx.window.images
